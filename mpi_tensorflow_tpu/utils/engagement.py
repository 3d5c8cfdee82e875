"""Trace-time path-engagement registry.

The transformer stack selects between implementations at trace time (Pallas
flash kernel vs XLA dense attention, chunked vs dense CE, packed vs
all-position MLM head; Mosaic-compiled vs interpreted vs XLA paged
attention).  A benchmark number is meaningless if the artifact can't say
which path it measured.

Model code calls ``record(key, value)`` at each selection point; an entry
point calls ``reset()`` before tracing and ``snapshot()`` after, embedding
the result in what it reports.  Records fire during ``jax.jit`` tracing
(Python executes once per compilation), so a snapshot taken after the first
call reflects exactly the paths baked into the compiled step.
"""

from __future__ import annotations

_RECORDS: dict = {}


def record(key: str, value) -> None:
    """Record a path selection (last write wins; layers all pick the same
    path, so one key per decision point suffices)."""
    _RECORDS[key] = value


def snapshot() -> dict:
    return dict(_RECORDS)


def reset() -> None:
    _RECORDS.clear()
