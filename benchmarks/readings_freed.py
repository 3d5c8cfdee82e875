"""``readings.py`` for a serving cell whose weights fit the chip only
once: one seed a process, and the engine is freed before the reference
gets its copy of the weights.

    python3 benchmarks/readings_freed.py --workload <cell> --seed <n> --control 1 --out <file.json>

``readings.py`` keeps one engine over many seeds (set-up is long) and
makes the reference's weights beside it, which a ten-gigabyte model has
no room for.  This reads the same numbers by the same functions
(``serve_driver.closed_loop``, ``served_gap_of``): the program's
``served_logit_gap`` over a short ramp and window, and with ``--control
1`` the fp8 control's at the same positions.  Not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--warmup-finished", type=int, default=12)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import run as run_lib
    from benchmarks.harness import (device, manifest, serve_driver,
                                    spans as spans_lib)

    cell = manifest.cell(manifest.manifest(), args.workload)
    if args.rehearse_cpu:
        run_lib.apply_rehearsal(cell)
    devices = device.claim(int(cell["chips"]), args.rehearse_cpu)

    from mpi_tensorflow_tpu.utils import cache

    cache.enable_compile_cache()
    cell["traffic_data"]["warmup_finished"] = args.warmup_finished
    t0 = time.perf_counter()
    with jax.default_device(devices[0]):
        sc = serve_driver.ServeCell(cell, devices, args.seed, False)
        sc.prewarm()
        lo, hi, mine, built, _ = serve_driver.closed_loop(
            sc, spans_lib.Spans(annotate=False), args.seconds,
            contextlib.nullcontext, device.CompileCounter())
        finished = serve_driver.finished_in(sc, 0.0, hi)
        kind, make_params = sc.kind, sc.make_params
        n = int(sc.mix["check_requests"])
        sc.free()
        del sc
        stats = {"control": "fp8"} if args.control else None
        gap = serve_driver.served_gap_of(
            kind.reference_logits, make_params(jax.random.key(args.seed)),
            finished, n, args.seed, stats=stats)
    row = {"seed": args.seed, "program": {"served_logit_gap": gap},
           "finished": len(finished), "built_in_window": built,
           "seconds": time.perf_counter() - t0}
    if stats is not None:
        row["control_fp8"] = {"served_logit_gap": stats["control_gap"]}
        row["tokens_compared"] = stats["tokens"]
    print(f"[readings] {json.dumps(row)}", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": [row]}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
