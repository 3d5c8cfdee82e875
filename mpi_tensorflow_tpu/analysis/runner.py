"""Runner: collect sources, run every pass, apply the baseline ratchet.

``python -m mpi_tensorflow_tpu.analysis`` runs all four passes over
the package and prints one line per finding::

    mpi_tensorflow_tpu/serving/router.py:419: LOCK-HELD self.fleet_...

Exit status:

- 0 — no findings beyond the baseline;
- 1 — new findings (or a stale baseline entry count exceeded);
- 2 — usage / IO error.

The baseline (``analysis/baseline.json``) maps ``"PASS-ID:file"`` to a
suppressed count.  It is a RATCHET: the runner fails if the current
count for any key exceeds the baselined count, and
``--update-baseline`` refuses to write a baseline with any count
higher than the existing one.  Counts only go down; the shipped
baseline is empty because every real finding was fixed or annotated
in the PR that introduced the checker.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Dict, List

from mpi_tensorflow_tpu.analysis import (core, host_sync, jit_stability,
                                         locks, names)

PASSES = (jit_stability, host_sync, locks, names)

_DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                                 "baseline.json")


def run_all(sources: Dict[str, str]) -> List[core.Finding]:
    findings: List[core.Finding] = []
    for mod in PASSES:
        findings.extend(mod.run(sources))
    findings.sort(key=lambda f: (f.file, f.line, f.pass_id, f.message))
    return findings


def counts_by_key(findings: List[core.Finding]) -> Dict[str, int]:
    out: Dict[str, int] = collections.Counter()
    for f in findings:
        out[f.baseline_key] += 1
    return dict(out)


def load_baseline(path: str) -> Dict[str, int]:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        raw = json.load(fh)
    return {str(k): int(v) for k, v in raw.items()}


def compare(current: Dict[str, int],
            baseline: Dict[str, int]) -> Dict[str, int]:
    """Keys whose current count exceeds the baselined count (the
    failures), mapped to the excess."""
    over: Dict[str, int] = {}
    for key, n in current.items():
        allowed = baseline.get(key, 0)
        if n > allowed:
            over[key] = n - allowed
    return over


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m mpi_tensorflow_tpu.analysis",
        description="graft-lint: AST invariant checker for the repo's "
                    "hand-enforced contracts")
    parser.add_argument("--root", default=None,
                        help="repo root to scan (default: auto-detected "
                             "from the package location)")
    parser.add_argument("--baseline", default=_DEFAULT_BASELINE,
                        help="baseline suppression file")
    parser.add_argument("--update-baseline", action="store_true",
                        help="rewrite the baseline from current "
                             "findings (ratchet: refuses any count "
                             "increase)")
    args = parser.parse_args(argv)

    root = args.root or core.repo_root()
    sources = core.load_sources(root)
    if not sources:
        print(f"graft-lint: no Python sources under {root}",
              file=sys.stderr)
        return 2
    findings = run_all(sources)
    current = counts_by_key(findings)
    try:
        baseline = load_baseline(args.baseline)
    except (ValueError, OSError) as exc:
        print(f"graft-lint: bad baseline {args.baseline}: {exc}",
              file=sys.stderr)
        return 2

    if args.update_baseline:
        grew = {k: (baseline.get(k, 0), n) for k, n in current.items()
                if n > baseline.get(k, 0) and baseline}
        if grew:
            for key, (old, new) in sorted(grew.items()):
                print(f"graft-lint: ratchet: {key} would grow "
                      f"{old} -> {new}; fix or annotate instead of "
                      f"baselining", file=sys.stderr)
            return 1
        with open(args.baseline, "w", encoding="utf-8") as fh:
            json.dump(current, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"graft-lint: baseline written "
              f"({sum(current.values())} suppressed findings)")
        return 0

    over = compare(current, baseline)
    shown = 0
    budget = dict(baseline)
    for f in findings:
        if budget.get(f.baseline_key, 0) > 0:
            budget[f.baseline_key] -= 1     # suppressed by baseline
            continue
        print(f.format())
        shown += 1

    tighten = {k: v for k, v in baseline.items()
               if current.get(k, 0) < v}
    for key in sorted(tighten):
        print(f"graft-lint: baseline for {key} is stale "
              f"({current.get(key, 0)} < {tighten[key]}); run "
              f"--update-baseline to ratchet down", file=sys.stderr)

    if over:
        print(f"graft-lint: {shown} new finding(s) "
              f"({len(findings)} total, "
              f"{len(findings) - shown} baselined)", file=sys.stderr)
        return 1
    print(f"graft-lint: clean ({len(findings)} baselined finding(s))"
          if findings else "graft-lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
