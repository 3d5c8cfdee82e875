"""Looks at a kept trace by hand: every plane and line of
``.bench_trace/<cell>`` with its event count and the names that took most
time.  Run a cell with ``--trace 1 --keep-trace`` first.

    python3 benchmarks/describe_trace.py --workload <cell> --out <file.json>
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)

    from benchmarks.harness import trace_reduce

    desc = trace_reduce.describe_xplane(
        os.path.join(ROOT, ".bench_trace", args.workload), top=args.top)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(desc, f, indent=1)
    for key, row in desc.items():
        print(key, row["events"], [r[0] for r in row["by_time"][:6]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
