"""The traced engine's step records inside a run's window.

A traced ``PagedDecodeEngine`` writes one record an ``EngineLoop.iterate``
to the program's ``utils/dispatch_log`` (``steps()``: ``(time.perf_counter()
at the step's start, record)``).  A record's ``phases`` hold the self
seconds of each engine phase span (``serving/tracing.STEP_SPANS``), which
sum to the step's length: ``serve.launch`` the calls into compiled
programs, ``serve.wait`` the blocking token read, ``serve.trace`` the
tracer's own bookkeeping, and so on.
"""

from __future__ import annotations


def in_window(run):
    """The step records whose step started inside the run's window; None
    where the program keeps no step log or none fell there."""
    try:
        from mpi_tensorflow_tpu.utils import dispatch_log
    except ImportError:
        return None
    steps = getattr(dispatch_log, "steps", None)
    if steps is None:
        return None
    lo, hi = run["window"]
    return [rec for at, rec in steps() if lo <= at < hi] or None


def mean_ms(run, seconds_of):
    """Mean over the window's steps of ``seconds_of(record)``, in ms."""
    recs = in_window(run)
    if recs is None:
        return None
    return 1e3 * sum(seconds_of(r) for r in recs) / len(recs)
