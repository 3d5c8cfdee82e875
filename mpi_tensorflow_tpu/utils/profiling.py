"""Profiling + host-side step timing — the repo's ONE timing idiom.

The reference has none — its only instrumentation is a commented-out
wall-clock timer (mpipy.py:78) and the 50-step print trace (SURVEY.md §5
tracing row).  Here measurement is a first-class utility:

- ``trace(dir)``: context manager around ``jax.profiler`` — produces an
  XPlane/TensorBoard trace of device + host activity;
- ``annotate(name)``: names a region so it shows up in the trace timeline
  (host side) and, via ``jax.named_scope``, in the compiled HLO;
- ``device_memory_stats()``: per-device HBM usage snapshot, for finding the
  working-set the rematerialization knobs should target;
- ``device_identity()``: platform / device_kind / device count as JAX
  reports them — printed by every entry point;
- ``StepTimer``: the warmup-skipping wall-clock step timer of the TRAIN
  loops — JAX dispatch is asynchronous, so it blocks on the final
  output (``block_until_ready``) and amortizes over many steps.
  Measurement rule: evaluation stays OFF
  the timed path (the reference's accidental every-step full-test eval
  at mpipy.py:86 is not replicated in what we time).

The SERVING side has its own timing layer — ``serving/tracing``
stamps request-lifecycle spans and per-step phase durations on the
serve loop's existing host clocks (it must never block on device
output the way ``StepTimer`` deliberately does).  Training times
here; serving traces there; nothing else reads a clock.

Wired into the CLI as ``--profile-dir`` (cli.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator, Optional


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace into ``log_dir`` (no-op when None)."""
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Label a region in both the profiler timeline and the jaxpr/HLO."""
    import jax

    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


@dataclasses.dataclass
class StepTimer:
    """Accumulates steady-state step wall time, skipping warmup steps
    (compile + first dispatches)."""
    warmup_steps: int = 2
    _steps: int = 0
    _total: float = 0.0
    _t0: float = 0.0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, count: int = 1) -> None:
        dt = time.perf_counter() - self._t0
        if self.warmup_steps > 0:
            self.warmup_steps -= count
            return
        self._steps += count
        self._total += dt

    @property
    def steps_timed(self) -> int:
        return self._steps

    @property
    def mean_step_seconds(self) -> float:
        return self._total / self._steps if self._steps else float("nan")

    def images_per_sec(self, batch_size: int) -> float:
        s = self.mean_step_seconds
        return batch_size / s if s == s and s > 0 else float("nan")


def device_identity() -> dict:
    """The device this process runs on, as JAX reports it.  Initializes
    the backend on first use."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def device_memory_stats() -> list:
    """Per-device memory snapshot: ``[{device, bytes_in_use, peak_bytes,
    limit_bytes}, ...]``.  Platforms without stats report ``None`` fields."""
    import jax

    out = []
    for d in jax.devices():
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:  # not all platforms implement memory_stats
            pass
        out.append({
            "device": str(d),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes": stats.get("peak_bytes_in_use"),
            "limit_bytes": stats.get("bytes_limit"),
        })
    return out
