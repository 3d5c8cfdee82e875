"""Reads the numbers that ``correct`` compares, for setting their limits.

    python3 benchmarks/readings.py --workload <cell> --seeds 12 --control-seeds 3 --out <file.json>

One process, one compiled program, many seeds (set-up is long): for every
seed the program's numbers against the reference (the lower reading), and
for the first ``--control-seeds`` of them the control's: the reference put
in the program's place in the next precision down (fp8 operands for a
bfloat16 configuration), and for training the planted faults (half of the
batch left out; on several chips, one chip's share alone, which is what the
exchange left out gives).  ``PERF.md`` section 2 records the readings and
the limits set from them.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def train_readings(cell, devices, seeds, n_control, say) -> list:
    from benchmarks.harness import check, spans as spans_lib, train_driver

    spans = spans_lib.Spans(annotate=False)
    tc = None
    rows = []
    for i, seed in enumerate(seeds):
        if tc is None:
            tc = train_driver.TrainCell(cell, devices, seed)
        else:
            tc.reseed(seed)
        n = int(tc.mix["check_steps"])
        prog = tc.first_steps(spans, n)
        tc.free()
        ref = tc.reference_steps(n)
        row = {"seed": seed,
               "program": check.training_numbers(prog, ref),
               "ref_losses": ref["losses"].tolist()}
        if i < n_control:
            faults = ["half_batch"]
            if len(devices) > 1:
                faults.append(f"one_shard_of_{len(devices)}")
            row["control_fp8"] = check.training_numbers(
                tc.reference_steps(n, precision="fp8"), ref)
            row["control_bf16"] = check.training_numbers(
                tc.reference_steps(n, precision="bf16"), ref)
            for f in faults:
                row["fault_" + f] = check.training_numbers(
                    tc.reference_steps(n, fault=f), ref)
        say(row)
        rows.append(row)
    return rows


def serve_readings(cell, devices, seeds, n_control, seconds, say,
                   warmup_finished=None) -> list:
    import contextlib

    import jax

    from benchmarks.harness import device, serve_driver, spans as spans_lib

    rows = []
    compiles = device.CompileCounter()
    if warmup_finished is not None:
        # a shorter ramp: the gap of a served token does not wait for
        # steady state, and a ramp costs minutes at the cell's size
        cell["traffic_data"]["warmup_finished"] = int(warmup_finished)
    with jax.default_device(devices[0]):
        sc = None
        for i, seed in enumerate(seeds):
            if sc is None:
                sc = serve_driver.ServeCell(cell, devices, seed, False)
                sc.prewarm()
            else:
                sc.reseed(seed)
            spans = spans_lib.Spans(annotate=False)
            lo, hi, mine, built, _ = serve_driver.closed_loop(
                sc, spans, seconds, contextlib.nullcontext, compiles)
            finished = serve_driver.finished_in(sc, 0.0, hi)
            stats = {"control": "fp8"} if i < n_control else None
            gap = serve_driver.served_gap_of(
                sc.kind.reference_logits,
                sc.make_params(jax.random.key(seed)), finished,
                int(sc.mix["check_requests"]), seed, stats=stats)
            row = {"seed": seed, "program": {"served_logit_gap": gap},
                   "finished": len(finished), "built_in_window": built}
            if stats is not None:
                row["control_fp8"] = {
                    "served_logit_gap": stats["control_gap"]}
                row["tokens_compared"] = stats["tokens"]
            say(row)
            rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--warmup-finished", type=int, default=None,
                    help="serving: requests finished before the window")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import run as run_lib
    from benchmarks.harness import device, manifest

    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    if args.rehearse_cpu:
        run_lib.apply_rehearsal(cell)
    devices = device.claim(int(cell["chips"]), args.rehearse_cpu)

    from mpi_tensorflow_tpu.utils import cache

    cache.enable_compile_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    t0 = time.perf_counter()

    def say(row):
        print(f"[readings +{time.perf_counter() - t0:.0f}s] "
              f"{json.dumps(row)}", flush=True)

    if cell["config_data"]["driver"] == "train":
        rows = train_readings(cell, devices, seeds, args.control_seeds, say)
    else:
        rows = serve_readings(cell, devices, seeds, args.control_seeds,
                              args.seconds, say, args.warmup_finished)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
