"""The benchmark's one command: runs one cell once and prints one JSON
line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (read from a profiler trace of a short window, the
harness's spans and the program's counters) with a ``breakdown``.  Exits
with 3 and prints no result when JAX finds no TPU or fewer chips than the
cell asks for.  ``--rehearse-cpu`` drives the same code at the tiny sizes
in the configuration's and the mix's ``rehearsal`` blocks and prints no
metric at all.  A serving run's line also carries ``counts``: what its
window held (see ``harness/serve_driver.py``).  See ``benchmarks/README.md``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Clock:
    """What a driver needs from the harness: process start, spans, the
    compile counter, the profiler around the window."""

    def __init__(self, spans, compiles, trace_dir, describe):
        self.t_start = T_START
        self.spans = spans
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.describe = describe

    def phase(self, name: str) -> None:
        """Log where set-up stands: seconds since process start, programs
        built so far, persistent-cache hits and misses."""
        c = self.compiles
        print(f"[setup] +{time.perf_counter() - self.t_start:7.2f}s {name} "
              f"(programs {c.count}, backend {c.seconds:.1f}s, cache hits "
              f"{c.hits} misses {c.misses})", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def tracer(self):
        if self.trace_dir is None:
            yield
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


def apply_rehearsal(cell: dict) -> None:
    for key in ("config_data", "traffic_data"):
        over = cell[key].get("rehearsal")
        if over is None:
            raise SystemExit(f"{key} of {cell['name']} has no 'rehearsal' "
                             f"block")
        for k, v in over.items():
            if isinstance(v, dict) and isinstance(cell[key].get(k), dict):
                cell[key][k] = {**cell[key][k], **v}
            else:
                cell[key][k] = v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave .bench_trace/<cell> for describe_trace.py")
    ap.add_argument("--sub-windows", default=(),
                    type=lambda s: [float(x) for x in s.split(",") if x],
                    help="serving: also read, into 'counts', the windows "
                         "of these lengths that open with the run's own")
    args = ap.parse_args(argv)

    from benchmarks.harness import (check, device, manifest, spans as
                                    spans_lib, trace_reduce)

    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    if args.rehearse_cpu:
        apply_rehearsal(cell)
    try:
        devices = device.claim(int(cell["chips"]), args.rehearse_cpu)
    except device.NoAcceleratorError as e:
        print(f"[benchmark] {e}", file=sys.stderr)
        return 3

    from mpi_tensorflow_tpu.utils import cache

    cache.enable_compile_cache()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    spans = spans_lib.Spans(annotate=bool(args.trace))
    clock = Clock(spans, device.CompileCounter(), trace_dir, device.describe)
    clock.phase("imports, device, manifest")
    driver = importlib.import_module(
        "benchmarks.harness.%s_driver" % cell["config_data"]["driver"])
    res = driver.run(cell, devices, args, clock)

    dev = res["device"]
    res["spans"] = spans
    res["trace"] = None
    breakdown = None
    if trace_dir is not None:
        raw = trace_reduce.load_xplane(trace_dir, span_names=set(spans.rows))
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if raw["devices"]:
            red = trace_reduce.reduce(raw)
            res["trace"] = red
            dev["busy_s"] = red.busy_s
            dev["window_s"] = red.window_s
            breakdown = trace_reduce.breakdown(red)
        elif not args.rehearse_cpu:
            raise RuntimeError("the trace holds no device plane")
    if not args.rehearse_cpu:
        res["peaks"] = device.peaks(dev["kind"])

    correct, checked = check.judge(res["numbers"], cell["limits"])
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    if not args.rehearse_cpu:
        for m in manifest.metrics_of(man, args.workload, group):
            if group == "end_to_end":
                value = res["end_to_end"][m["name"]]
            else:
                value = manifest.reader(m["name"])(res)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if args.rehearse_cpu:
        line["rehearsal"] = True
    elif res.get("counts") is not None:
        line["counts"] = res["counts"]
    line["checked"] = checked
    sys.stdout.flush()
    check.report(checked, correct)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
