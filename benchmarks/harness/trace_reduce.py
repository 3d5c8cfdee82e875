"""From a profiler trace to numbers: busy union, per-op time, exposed
collectives, idle gaps joined to the harness's spans.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain dict
(``raw``), and ``reduce`` turns that into a ``Reduced``.  The split keeps
the arithmetic checkable on a hand-written ``raw`` (``benchmarks/tests``).

``raw`` = {"devices": [{"name": str, "ops": [[name, start_ns, dur_ns], ...]}],
           "host":    [[name, start_ns, dur_ns], ...]}

Device ops come from each ``/device:TPU:n`` plane's ``XLA Ops`` line; host
events are the ``TraceAnnotation`` spans of the harness, which the profiler
stamps on the device trace's clock.  Ops on one line can nest (a ``while``
holds its body), so per-op time is self time: an event's duration minus
its directly nested children.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
OPS_LINE = "XLA Ops"


def op_name(event_name: str) -> str:
    """The device trace names an op by its whole HLO line,
    ``%fusion.12 = (...) fusion(...)``: keep ``fusion.12``."""
    base = event_name.split(" = ", 1)[0].lstrip("%")
    if "tpu_custom_call" in event_name:
        base += "[tpu_custom_call]"        # a Mosaic (Pallas) kernel
    return base


def _profile(trace_dir: str):
    """The newest ``*.xplane.pb`` under ``trace_dir``, parsed."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def load_xplane(trace_dir: str, span_names=()) -> dict:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``.  Host events
    are kept only when their name is in ``span_names`` (the profiler also
    records JAX's own dispatch internals there)."""
    data = _profile(trace_dir)
    keep = set(span_names)
    raw = {"devices": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([op_name(e.name), float(e.start_ns),
                                float(e.duration_ns)] for e in line.events)
            raw["devices"].append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                raw["host"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name in keep)
    raw["devices"].sort(key=lambda d: d["name"])
    return raw


def describe_xplane(trace_dir: str, top: int = 40) -> dict:
    """Names of every plane and line with event counts, and the most
    frequent event names per line: for looking at a trace by hand."""
    from collections import Counter

    data = _profile(trace_dir)
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            names = Counter()
            dur = Counter()
            stats = {}
            n = 0
            for e in line.events:
                n += 1
                names[e.name] += 1
                dur[e.name] += e.duration_ns
                if e.name not in stats:
                    stats[e.name] = {k: str(v)[:200] for k, v in e.stats}
            out[f"{plane.name} | {line.name}"] = {
                "events": n,
                "by_time": [[k, v / 1e9, names[k], stats[k]]
                            for k, v in dur.most_common(top)]}
    return out


def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(intervals, cover) -> list:
    """Parts of ``intervals`` (disjoint, sorted) not inside ``cover``
    (disjoint, sorted)."""
    out = []
    j = 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(ops) -> list:
    """``[(name, start, end, self_ns, is_leaf)]``: each event's duration
    less the events directly nested in it, and whether it holds none."""
    evs = sorted(([n, s, s + d, d, True] for n, s, d in ops),
                 key=lambda r: (r[1], -r[2]))
    stack = []
    for ev in evs:
        while stack and stack[-1][2] <= ev[1]:
            stack.pop()
        if stack and ev[2] <= stack[-1][2]:
            stack[-1][3] -= ev[2] - ev[1]
            stack[-1][4] = False
        stack.append(ev)
    return [(n, s, e, max(d, 0.0), leaf) for n, s, e, d, leaf in evs]


@dataclasses.dataclass
class Reduced:
    window_s: float            # traced window
    busy_s: float              # mean over devices of the busy union
    n_devices: int
    op_seconds: dict           # op name -> self seconds, mean over devices
    op_counts: dict            # op name -> events, device 0
    collective_s: float        # inside collective events, mean over devices
    collective_exposed_s: float    # ... while no other op ran there
    gaps: list                 # device 0: [(start_s, end_s)] idle, longest first
    gap_names: list            # the harness span that covers each gap
    spans: dict                # span name -> [(start_s, end_s)] in the window

    def op_seconds_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_seconds.items() if rx.search(k))

    def op_count_matching(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_counts.items() if rx.search(k))


def reduce(raw: dict, window_span: str = "window") -> Reduced:
    """Reduce ``raw`` over the window: the host span named ``window_span``
    if the trace has one, else from the first device op to the last."""
    host = raw.get("host", [])
    win = [(s, s + d) for n, s, d in host if n == window_span]
    all_ops = [(s, s + d) for dev in raw["devices"] for _, s, d in dev["ops"]]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    elif all_ops:
        lo, hi = min(s for s, _ in all_ops), max(e for _, e in all_ops)
    else:
        raise ValueError("trace holds no device operation")
    ndev = len(raw["devices"])
    busy = 0.0
    coll = exposed = 0.0
    op_seconds: dict = {}
    op_counts: dict = {}
    gaps0 = []
    for i, dev in enumerate(raw["devices"]):
        ops = [(n, s, d) for n, s, d in dev["ops"] if s + d > lo and s < hi]
        union = merge(clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy += total(union)
        if i == 0:
            gaps0 = subtract([(lo, hi)], union)
        cint, oint = [], []
        for n, s, e, self_ns, leaf in self_times(ops):
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            share = self_ns * (ce - cs) / (e - s) if e > s else 0.0
            op_seconds[n] = op_seconds.get(n, 0.0) + share / 1e9 / ndev
            if i == 0:
                op_counts[n] = op_counts.get(n, 0) + 1
            if COLLECTIVE.search(n):
                cint.append((cs, ce))
            elif leaf:          # a container (while, call) is not work
                oint.append((cs, ce))
        cint = merge(cint)
        coll += total(cint)
        exposed += total(subtract(cint, merge(oint)))
    spans: dict = {}
    for n, s, d in host:
        if s + d > lo and s < hi:
            spans.setdefault(n, []).append(((s - lo) / 1e9,
                                            (s + d - lo) / 1e9))
    gaps0.sort(key=lambda g: g[0] - g[1])
    gap_names = []
    for s, e in gaps0:
        best, cover = "no_span", 0.0
        for n, rows in spans.items():
            if n == window_span:
                continue
            c = sum(max(0.0, min(e, lo + b * 1e9) - max(s, lo + a * 1e9))
                    for a, b in rows)
            if c > cover:
                best, cover = n, c
        gap_names.append(best)
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / max(ndev, 1),
        n_devices=ndev, op_seconds=op_seconds, op_counts=op_counts,
        collective_s=coll / 1e9 / max(ndev, 1),
        collective_exposed_s=exposed / 1e9 / max(ndev, 1),
        gaps=[((s - lo) / 1e9, (e - lo) / 1e9) for s, e in gaps0],
        gap_names=gap_names, spans=spans)


def op_kind(name: str) -> str:
    """``copy.294`` -> ``copy``: the instruction without its number."""
    return re.sub(r"\.\d+(?=$|\[)", "", name)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most self time — the first half by kind (``kind:copy`` sums every
    ``copy.N``), the second the single instructions — and the idle time by
    the harness span that covers it."""
    kinds: dict = {}
    for name, sec in red.op_seconds.items():
        k = "kind:" + op_kind(name)
        kinds[k] = kinds.get(k, 0.0) + sec
    half = top // 2
    ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:half] \
        + sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top - half]
    by_span: dict = {}
    for (s, e), n in zip(red.gaps, red.gap_names):
        by_span[n] = by_span.get(n, 0.0) + (e - s)
    idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
