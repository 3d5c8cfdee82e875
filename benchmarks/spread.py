"""How widely the runs of one cell spread, by the estimator the driver
refuses a benchmark on (``benchmark_too_noisy``).

    python3 benchmarks/spread.py setA.jsonl [setB.jsonl] [--sub 20]
    ... | python3 benchmarks/spread.py -

Each input holds the result lines of the runs of one set (one JSON object
a line, as ``benchmarks/run.py --trace 0`` prints last; other lines are
skipped, so a log of several runs will do).  Per end-to-end metric and
set: the median; the interquartile range over the median (what
``BENCHMARK.json``'s bounds were first set from); ``spread``, the range of
the set after leaving out the one run farthest from the median where that
narrows it; and ``share``, that spread over the metric's bound times the
median.  With two sets ``mean_share`` is the mean of the two shares and
``ok`` says it is at most 0.5: a new cell whose ``ok`` is false is refused.
``setup_s`` is judged by its median alone (the second set's against the
first's, the first run of each left out when ``--skip-first-setup``).
``--sub 20`` reads ``counts.sub_20`` of each line in place of ``metrics``
(``run.py --sub-windows``); ``--runs`` lists every run first, with what
its window held.  Run it on two sets of 8 before a PR that adds a serving
cell is sent.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OK_SHARE = 0.5


def result_lines(text: str) -> list:
    out = []
    for row in text.splitlines():
        row = row.strip()
        if row.startswith("{"):
            try:
                obj = json.loads(row)
            except ValueError:
                continue
            if isinstance(obj, dict) and "metrics" in obj:
                out.append(obj)
    return out


def values_of(lines: list, sub: str = None) -> dict:
    """``{metric: [value per run]}``: from ``metrics``, or with ``sub``
    from ``counts.sub_<sub>`` (the metrics that sub-window holds)."""
    out: dict = {}
    for line in lines:
        if sub is None:
            row = {k: v["value"] for k, v in line["metrics"].items()}
        else:
            row = line["counts"]["sub_" + sub]
        for k, v in row.items():
            if isinstance(v, (int, float)):
                out.setdefault(k, []).append(float(v))
    return out


def runs_table(lines: list, sub: str = None) -> str:
    """One row a run: its metrics and, where the line has them, what the
    window held per iteration."""
    rows = []
    for line in lines:
        c = line.get("counts") or {}
        if sub is not None:
            c = c["sub_" + sub]
        m = {k: v["value"] for k, v in line["metrics"].items()}
        m.update({k: v for k, v in c.items() if not isinstance(v, dict)})
        n = m.get("iterations")
        if n:
            m["span_ms_per_iteration"] = 1e3 * m["iterate_span_s"] / n
            m["ms_per_iteration"] = 1e3 * m["window_s"] / n
            m["tokens_per_iteration"] = m["tokens"] / n
        rows.append(" ".join(
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in m.items()) + f" correct={line.get('correct')}")
    return "\n".join(rows)


def spread(values) -> float:
    """Range of ``values``, or of ``values`` without the one farthest
    from their median where that is narrower."""
    v = sorted(float(x) for x in values)
    if len(v) < 2:
        return 0.0
    full = v[-1] - v[0]
    if len(v) < 3:
        return full
    med = statistics.median(v)
    far = max(v, key=lambda x: abs(x - med))
    rest = list(v)
    rest.remove(far)
    return min(full, rest[-1] - rest[0])


def iqr(values) -> float:
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def one_set(values, bound=None) -> dict:
    med = statistics.median(values)
    wide = spread(values)
    out = {"n": len(values), "median": med,
           "iqr_over_median": iqr(values) / med,
           "spread": wide, "spread_over_median": wide / med}
    if bound is not None:
        out["share"] = wide / (bound * med)
    return out


def report(sets: list, bounds: dict, skip_first_setup: bool = False) -> dict:
    """``sets``: one ``{metric: [values]}`` per set; ``bounds``:
    ``{metric: bound}`` of ``BENCHMARK.json``'s ``end_to_end``."""
    out = {}
    for name in sets[0]:
        if name not in bounds:
            continue
        if name == "setup_s":
            meds = [statistics.median(s[name][1:] if skip_first_setup
                                      and len(s[name]) > 1 else s[name])
                    for s in sets if name in s]
            row = {"medians": meds, "bound": bounds[name]}
            if len(meds) == 2:
                row["second_over_first"] = meds[1] / meds[0]
                row["ok"] = meds[1] <= meds[0] * (1 + bounds[name])
            out[name] = row
            continue
        per = [one_set(s[name], bounds[name]) for s in sets if name in s]
        row = {"bound": bounds[name], "sets": per}
        if len(per) == 2:
            row["mean_share"] = (per[0]["share"] + per[1]["share"]) / 2
            row["ok"] = row["mean_share"] <= OK_SHARE
        out[name] = row
    return out


def table(rep: dict) -> str:
    rows = []
    for name, row in rep.items():
        if name == "setup_s":
            rows.append(f"{name}: medians "
                        + ", ".join(f"{m:.6g}" for m in row["medians"])
                        + (f"; second/first {row['second_over_first']:.4f}"
                           f" ok={row['ok']}" if "ok" in row else ""))
            continue
        for i, s in enumerate(row["sets"]):
            rows.append(
                f"{name} set {'AB'[i]}: n={s['n']} median "
                f"{s['median']:.6g} iqr/median {100 * s['iqr_over_median']:.3f}%"
                f" spread {s['spread']:.6g} = "
                f"{100 * s['spread_over_median']:.3f}% of median, "
                f"{100 * s['share']:.1f}% of the bound "
                f"({100 * row['bound']:g}%)")
        if "ok" in row:
            rows.append(f"{name}: mean share {100 * row['mean_share']:.1f}% "
                        f"of the bound, ok={row['ok']} (at most "
                        f"{100 * OK_SHARE:g}%)")
    return "\n".join(rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("sets", nargs="+", help="one file a set; - is stdin")
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--sub", default=None)
    ap.add_argument("--skip-first-setup", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--runs", action="store_true")
    args = ap.parse_args(argv)
    if len(args.sets) > 2:
        ap.error("one or two sets")
    with open(args.manifest) as f:
        bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    sets = []
    for path in args.sets:
        text = sys.stdin.read() if path == "-" else open(path).read()
        lines = result_lines(text)
        if not lines:
            ap.error(f"{path}: no result line")
        sets.append(values_of(lines, args.sub))
        if args.runs:
            print(f"# {path}\n{runs_table(lines, args.sub)}")
    rep = report(sets, bounds, args.skip_first_setup)
    print(json.dumps(rep) if args.json else table(rep))
    return 0 if all(r.get("ok", True) for r in rep.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
