"""Console trace in the reference's format, kept diffable MPI-vs-TPU.

The reference prints (mpipy.py:77, 88):
    ``Process ID: <rank>  training session starts!``
    ``<rank>  process at  <step> with test error: <e>%``
every 50 steps, flushing stdout.  We reproduce the exact format so traces can
be compared side by side (SURVEY.md §5 metrics row), and add the timing lines
the reference lacks (its timer is commented out at mpipy.py:78).
"""

from __future__ import annotations

import sys


def session_start(rank: int) -> None:
    print("Process ID:", rank, " training session starts!")
    sys.stdout.flush()


def step_trace(rank: int, step: int, test_error: float) -> None:
    # exact reference format (mpipy.py:88)
    print(rank, " process at ", step, "with test error: %.1f%%" % test_error)
    sys.stdout.flush()


def val_trace(rank: int, val_error: float) -> None:
    """Validation-error line (early-stopping mode; no reference analogue —
    the reference never reads its validation shards, mpipy.py:236-241)."""
    print(f"{rank}  validation error: {val_error:.1f}%")
    sys.stdout.flush()


def timing_summary(images_per_sec: float, step_time_ms: float,
                   num_devices: int) -> None:
    print(f"[timing] {images_per_sec:,.0f} images/sec "
          f"({images_per_sec / max(num_devices, 1):,.0f} /chip) | "
          f"step {step_time_ms:.3f} ms | {num_devices} device(s)")
    sys.stdout.flush()


def device_banner(identity: dict, file=None) -> None:
    """One start-up line naming the device (utils/profiling.
    device_identity): no run's output can leave its platform in doubt."""
    print(f"[device] platform={identity['platform']} "
          f"device_kind={identity['device_kind']!r} "
          f"count={identity['device_count']}", file=file or sys.stdout,
          flush=True)
