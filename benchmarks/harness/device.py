"""The device a run is on: identity, published peaks, memory, compiles.

Peaks are the chip's published numbers, keyed by the ``device_kind`` JAX
reports.  A device that is not in the table is an error, never a default.
(Copy of ``mpi_tensorflow_tpu/utils/flops.DEVICE_PEAKS``; the yardstick
lives with the benchmark.)
"""

from __future__ import annotations

import sys

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM
# bandwidth, 16 GB of HBM per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197.0e12, "hbm_bytes_per_s": 819.0e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class NoAcceleratorError(RuntimeError):
    """Fewer TPU chips than the cell asks for."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise NoAcceleratorError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to benchmarks/harness/device.py "
            f"with its source") from None


def claim(chips: int, rehearse_cpu: bool):
    """The ``chips`` devices this run uses.  Without ``rehearse_cpu`` they
    must be TPU chips whose kind has published peaks."""
    import jax

    devs = jax.devices()
    if rehearse_cpu:
        if len(devs) < chips:
            raise NoAcceleratorError(
                f"rehearsal needs {chips} devices, JAX has {len(devs)} "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count={chips})")
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise NoAcceleratorError(
            f"JAX found no accelerator (platform {devs[0].platform!r}); a "
            f"CPU rehearsal is reachable only through --rehearse-cpu")
    if len(devs) < chips:
        raise NoAcceleratorError(
            f"cell needs {chips} chips, JAX has {len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


def describe(devices) -> dict:
    """The ``device`` object of the result line; ``memory_peak_bytes`` is
    the peak on the fullest chip: the peak of live buffers plus the peak
    the runtime reserved for compiled programs' scratch.  On this TPU
    runtime ``peak_bytes_in_use`` leaves the scratch out (a BERT-base step
    whose compiler report says 10.3 GB of temporaries reads 2.0 GB there
    and 10.2 GB under ``peak_bytes_reserved``); the two pools are disjoint
    parts of the chip's memory."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    print(f"[device] memory_stats of {devices[0]}: "
          f"{devices[0].memory_stats()}", file=sys.stderr)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts programs built since ``start()``: each backend compile, or
    the persistent-cache load that replaced it, fires one
    ``backend_compile_duration`` event in ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._event)

    def _on(self, name, secs, **kw):
        if name == self.EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return self.count, self.seconds
