"""Process-wide log of a traced serving run's model dispatches.

Beside ``utils/engagement`` (which path a program took) this says what
each dispatch of it worked on, for per-layer metrics that need the
obliged work of a kernel: a benchmark's metric reader sees the run's
window and trace, not the engine (which is freed before readers run),
so the engine leaves its record here.  Written only with
``ServeConfig.trace == "on"`` (``PagedDecodeEngine._log_dispatch`` /
``_read_counters``); an untraced run never touches it.

A record is the list ``[time.perf_counter(), "decode" | "prefill", rows
(decode) or chunk tokens (prefill), cached tokens attended (summed over
the dispatch's queries, from host scheduler state), expert assignments,
experts touched]``; the last two are per dispatch, summed over the
layers that route, and ``None`` until the device counter was next read
(or for a model that routes nothing).  A model with a
``dispatch_extra`` method adds a seventh entry, a dict of what else the
dispatch obliged of its caches (models/phi4_flash: tokens through the
scans, keys its window layers and its full layer's cache were read for,
prefill lanes the cross-decoder was skipped on; models/cohere2_moe: the
(query, key) pairs of a full and of a window layer, the keys each reads
at least once, and the pairs the window spares).  ``totals`` is the
running assignment count per held expert.

Beside the dispatches, the traced engine's step records
(``serving/tracing.EngineTracer.end_step``: the self seconds of each
engine phase, programs built, the engine's signals), each as ``(its
time.perf_counter() start, record)``, the newest ``STEP_CAPACITY`` kept.
"""

from __future__ import annotations

from collections import deque

from mpi_tensorflow_tpu.serving.tracing import STEP_CAPACITY

_DISPATCHES: list = []
_TOTALS: list = []
_STEPS: deque = deque(maxlen=STEP_CAPACITY)


def record(at: float, kind: str, rows: int, attended: int,
           extra: dict = None) -> list:
    """Append one dispatch and return its (mutable) record."""
    rec = [at, kind, int(rows), int(attended), None, None]
    if extra is not None:
        rec.append(extra)
    _DISPATCHES.append(rec)
    return rec


def set_totals(per_expert) -> None:
    _TOTALS[:] = [int(n) for n in per_expert]


def record_step(at: float, rec: dict) -> None:
    _STEPS.append((at, rec))


def steps() -> list:
    """``[(start, step record)]``, oldest first."""
    return list(_STEPS)


def snapshot() -> dict:
    return {"dispatches": [list(r) for r in _DISPATCHES],
            "totals": list(_TOTALS)}


def reset() -> None:
    _DISPATCHES.clear()
    _TOTALS.clear()
    _STEPS.clear()
