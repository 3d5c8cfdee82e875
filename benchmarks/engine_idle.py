"""Looks at a kept serving trace by hand: the device's idle time in the
window put down to the INNERMOST host span that covers it (the covering
span that started latest, ties to the shorter), with the engine's phase
spans (``serving/tracing.STEP_SPANS``) read beside the harness's, and each
host span's self time an iteration.  Run a serving cell with
``--trace 1 --keep-trace`` first.

    python3 benchmarks/engine_idle.py --workload <cell> [--out <file.json>]

The result line's ``breakdown`` names each idle gap by the harness span
of largest coverage instead, which over nested spans is always the
outermost (``serve_iterate``).  ``innermost`` is the rule meant to replace
that one inside ``harness/trace_reduce.reduce``; once it does, this file
goes, so that one rule names idle time.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HARNESS = ("window", "serve_iterate")


def innermost(host, gaps) -> dict:
    """``{span name: idle seconds}`` over ``gaps`` (``[(start, end)]``, ns),
    each instant charged to the innermost of ``host``'s spans
    (``[[name, start, dur]]``, ns) that covers it, else ``no_span``."""
    spans = sorted((s, s + d, n) for n, s, d in host)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    by: dict = {}
    for gs, ge in gaps:
        cov = [h for h in spans[bisect.bisect_left(starts, gs - longest):
                                bisect.bisect_left(starts, ge)] if h[1] > gs]
        cuts = sorted({gs, ge, *(x for s, e, _ in cov for x in (s, e)
                                 if gs < x < ge)})
        for p, q in zip(cuts, cuts[1:]):
            over = [h for h in cov if h[0] <= p and h[1] >= q]
            name = (max(over, key=lambda h: (h[0], h[0] - h[1]))[2]
                    if over else "no_span")
            by[name] = by.get(name, 0.0) + (q - p) / 1e9
    return dict(sorted(by.items(), key=lambda kv: -kv[1]))


def describe(raw: dict, window_span: str = "window") -> dict:
    """Busy and idle time of device 0 in the window, the idle by
    innermost span, and each host span's self ms an iteration
    (``serve_iterate``)."""
    from benchmarks.harness import trace_reduce as tr

    win = [(s, s + d) for n, s, d in raw["host"] if n == window_span]
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    busy = tr.merge(tr.clip([(s, s + d) for _, s, d in
                             raw["devices"][0]["ops"]], lo, hi))
    gaps = tr.subtract([(lo, hi)], busy)
    host = [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
            for n, s, d in raw["host"]
            if n != window_span and s + d > lo and s < hi]
    own: dict = {}
    steps = 0
    for n, _s, _e, self_ns, _leaf in tr.self_times(host):
        own[n] = own.get(n, 0.0) + self_ns
        steps += n == "serve_iterate"
    return {"window_s": (hi - lo) / 1e9, "busy_s": tr.total(busy) / 1e9,
            "idle_s": tr.total(gaps) / 1e9,
            "idle_innermost_s": innermost(host, gaps), "steps": steps,
            "self_ms_a_step": {n: v / 1e6 / max(steps, 1) for n, v in
                               sorted(own.items(), key=lambda kv: -kv[1])}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from benchmarks.harness import trace_reduce
    from mpi_tensorflow_tpu.serving.tracing import STEP_SPANS

    raw = trace_reduce.load_xplane(
        os.path.join(ROOT, ".bench_trace", args.workload),
        span_names=set(HARNESS) | set(STEP_SPANS))
    desc = describe(raw)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(desc, f, indent=1)
    print(json.dumps(desc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
