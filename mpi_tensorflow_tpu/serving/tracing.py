"""Host-side structured tracing for the serving stack.

Every serving number a run reports (goodput-under-SLO, TTFT p99,
dispatch reduction, failover token-identity) is an end-of-run
aggregate; when a p99 regresses there was no way to see WHERE a
request spent its time.  This module is the phase-attribution layer
(DistServe / Sarathi-Serve style): it separates queueing from prefill
interference from decode latency, per request and per step.

Three pieces, all host-side and allocation-light:

- **Request lifecycle spans** (``Span``): one record per request,
  stamped arrive -> queued -> admitted -> prefill chunks -> first
  token -> decode -> terminal, including the fault transitions
  (eviction/restart, deadline sweep, drain cut, failover migration +
  replay).  Phase time lives in three accumulators (``queue_s`` /
  ``prefill_s`` / ``decode_s``) so a span that bounces between phases
  (evicted mid-decode, re-queued, re-prefilled) still sums to exactly
  its wall time: ``queue_s + prefill_s + decode_s == terminal - arrive``.
- **Step-phase timeline** (``TraceBuffer``): a bounded ring of
  per-iteration records — the self seconds of each engine phase
  (``STEP_SPANS``), the programs built during the step, plus the
  scheduler/pool gauges from ``engine.load_signals()``.  Fixed
  capacity, drop-oldest, with an explicit ``dropped`` counter — never
  unbounded.  The same records feed ``ScaleAdvisor.observe_step`` so
  autoscale advice is explainable from the trace.  Each phase is ONE
  measurement with two sinks: its self time in the step record, on
  ``time.perf_counter`` (the serve loop's clock), and a
  ``jax.profiler.TraceAnnotation`` of the same name, which a profiler
  session places on the device trace's clock — so an idle gap of the
  device can be put down to the phase the host was in.
- **Exports**: ``merge_spans`` folds harvests across replicas and
  failover incarnations (phase accumulators SUM, so a migrated
  request's queue time accumulates rather than resetting at
  re-admission), and ``write_chrome_trace`` emits Chrome trace-event
  (catapult) JSON — one pid per replica, request spans as async
  events, steps as duration events — loadable in Perfetto or
  chrome://tracing.

Hot-path contract: request spans use the serve loop's existing clock
values, phase spans ``time.perf_counter`` reads — zero device syncs,
zero allocations beyond small per-event records, and nothing here
touches a jitted function, so the graft-lint HOST-SYNC pass stays clean
with no annotations.  With tracing off the engine never constructs a
tracer and every instrumentation site is a ``tracer is None`` skip (no
helper call, no context object, no clock read): off is byte-for-byte
the untraced behavior.

Ownership: an ``EngineTracer`` is single-owner like the scheduler —
only the thread driving its engine may touch it.  The router archives
harvests from its own main thread (the ``_lat_archive`` idiom), so no
span state ever crosses the ``_GUARDED_BY`` lock.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

# Fixed ring capacity for step records.  Deliberately NOT a knob: the
# buffer exists to bound tracing memory, and a configurable bound is a
# bound someone sets to None.  At ~1 KB/record this is ~8 MB.
STEP_CAPACITY = 8192

#: The engine's phase spans (``EngineTracer.enter`` / ``exit``), each
#: timed where its work happens; a parent's self time is its duration
#: less its children's.  ``serve.step`` is one ``EngineLoop.iterate``
#: and holds ``serve.sweep`` (heap settle, deadline sweep),
#: ``serve.admit`` (admission, occupancy, partial copies),
#: ``serve.prefill`` (one chunk's host work), ``serve.decode`` (a decode,
#: verify or mixed dispatch's host work), ``serve.deliver`` (reading and
#: delivering tokens) and ``serve.account`` (the loop's emit and
#: eviction accounting).  ``serve.launch`` is ONE call into a compiled
#: program, argument transfer and enqueue included, inside the phase
#: that issues it; ``serve.wait`` the blocking token read, inside
#: ``serve.deliver``; ``serve.trace`` the tracer's own bookkeeping
#: (traced runs only), wherever it runs.
STEP_SPANS = ("serve.step", "serve.sweep", "serve.admit", "serve.prefill",
              "serve.decode", "serve.launch", "serve.deliver", "serve.wait",
              "serve.account", "serve.trace")

#: The ``jax.monitoring`` event every program built (or loaded from the
#: persistent cache in its place) fires once.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# programs built in this process since the first tracer was made: ONE
# process-wide listener (jax.monitoring cannot take one back), which a
# step record reads at its start and end
_compiles = 0
_listening = False


def _on_compile(name: str, secs: float, **_kw) -> None:
    global _compiles
    if name == COMPILE_EVENT:
        _compiles += 1


def _listen_for_compiles() -> None:
    global _listening
    if not _listening:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_compile)
        _listening = True


# Per-span event cap — a span's event list is the only per-request
# growth path (one entry per chunk/eviction/terminal), so bound it the
# same way the step ring is bounded.
SPAN_EVENT_CAP = 256

#: Phases a span's open clock can be attributed to.
PHASES = ("queue", "prefill", "decode")


class TraceBuffer:
    """Bounded drop-oldest ring for step records.

    ``append`` never grows past ``capacity``; once full, the oldest
    record is dropped and ``dropped`` increments — the counter is the
    contract that truncation is visible, never silent."""

    __slots__ = ("capacity", "dropped", "_buf")

    def __init__(self, capacity: int = STEP_CAPACITY):
        if capacity < 1:
            raise ValueError(f"TraceBuffer capacity must be >= 1, "
                             f"got {capacity}")
        self.capacity = int(capacity)
        self.dropped = 0
        self._buf: deque = deque(maxlen=self.capacity)

    def append(self, rec: dict) -> None:
        if len(self._buf) == self.capacity:
            self.dropped += 1
        self._buf.append(rec)

    def records(self) -> List[dict]:
        return list(self._buf)

    def __len__(self) -> int:
        return len(self._buf)

    def __iter__(self):
        return iter(self._buf)


class Span:
    """Lifecycle record for one request on one engine incarnation.

    The state machine: ``on_submit`` opens ``queue`` at arrival;
    admission closes ``queue`` and opens ``prefill``; the first
    delivered token closes ``prefill`` and opens ``decode``; a
    terminal closes whatever is open.  An eviction closes the open
    phase, VOIDS the first-token stamp (the pre-eviction first token
    is regenerated — the same rule as ``EngineLoop.first_emit``), and
    re-opens ``queue``.  Exactly one terminal transition ever lands:
    later terminal notifications for the same span are ignored."""

    __slots__ = ("rid", "arrive", "queue_s", "prefill_s", "decode_s",
                 "phase", "phase_t0", "first_token", "terminal",
                 "status", "chunks", "evictions", "replays",
                 "prefilled_seen", "events", "events_dropped")

    def __init__(self, rid: int, arrive: float):
        self.rid = rid
        self.arrive = arrive
        self.queue_s = 0.0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.phase: Optional[str] = "queue"
        self.phase_t0 = arrive
        self.first_token: Optional[float] = None
        self.terminal: Optional[float] = None
        self.status: Optional[str] = None
        self.chunks = 0
        self.evictions = 0
        self.replays = 0
        self.prefilled_seen = 0
        self.events: List[Tuple[float, str]] = []
        self.events_dropped = 0

    def event(self, t: float, name: str) -> None:
        if len(self.events) >= SPAN_EVENT_CAP:
            self.events_dropped += 1
            return
        self.events.append((t, name))

    def close_phase(self, now: float) -> None:
        """Fold the open phase's elapsed time into its accumulator."""
        if self.phase is None:
            return
        dt = max(0.0, now - self.phase_t0)
        if self.phase == "queue":
            self.queue_s += dt
        elif self.phase == "prefill":
            self.prefill_s += dt
        else:
            self.decode_s += dt
        self.phase = None

    def open_phase(self, phase: str, now: float) -> None:
        self.phase = phase
        self.phase_t0 = now

    def to_dict(self) -> dict:
        return {
            "rid": self.rid,
            "arrive": self.arrive,
            "queue_s": self.queue_s,
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "first_token": self.first_token,
            "terminal": self.terminal,
            "status": self.status,
            "chunks": self.chunks,
            "evictions": self.evictions,
            "replays": self.replays,
            "incarnations": 1,
            "events": [(t, n) for t, n in self.events],
            "events_dropped": self.events_dropped,
        }


class EngineTracer:
    """Per-engine span + step recorder, driven by ``EngineLoop``.

    The tracer never reads a clock of its own on the span path — every
    stamp is a ``now`` the serve loop already computed, so span times
    and the loop's stamped latencies (``first_emit``/``token_times``)
    are the SAME values, which is what makes the breakdown-vs-stamp
    cross-check exact.  Terminal hooks fire inside ``engine.step()``
    where no loop clock is in scope, so ``on_terminal`` only QUEUES
    the transition; ``flush_terminals`` lands it with the post-step
    ``now`` — after first-token stamping, so ``terminal >=
    first_token`` always holds.

    Step phases are spans (``STEP_SPANS``) the engine and loop open and
    close with ``enter`` / ``exit`` between ``begin_step`` and
    ``end_step``; their self seconds land in the step record's
    ``phases``."""

    def __init__(self, step_capacity: int = STEP_CAPACITY):
        from jax import profiler

        _listen_for_compiles()
        self.spans: Dict[int, Span] = {}
        self.buffer = TraceBuffer(step_capacity)
        self.pending_terminals: List[Tuple[int, str]] = []
        self.last_step: Optional[dict] = None
        self._annotation = profiler.TraceAnnotation
        # open phase spans, outermost first: [name, perf_counter at
        # entry, seconds of its closed children, its TraceAnnotation]
        self._open: list = []
        # where closed spans' self seconds go: the open step's record,
        # or (a span outside any step, e.g. a run's final delivery) a
        # sink nothing reads
        self._outside = dict.fromkeys(STEP_SPANS, 0.0)
        self.phases = self._outside
        self._step_t0 = 0.0
        self._step_compiles = 0
        self._last_now = 0.0
        self.moe: Optional[dict] = None  # routed-expert load as last
                                         # read (engine._read_counters)

    # ---- request lifecycle -------------------------------------------

    def on_submit(self, req, *, replay: bool = False) -> None:
        """Open (or re-open) the request's span at its arrival stamp.
        Called BEFORE scheduler admission so a synchronous rejection's
        terminal finds the span.  A re-submit of an id that already
        reached a terminal (a replayed incarnation landing on the same
        tracer) re-opens the span and keeps the accumulators — queue
        time ACCUMULATES across incarnations."""
        sp = self.spans.get(req.id)
        if sp is None:
            sp = Span(req.id, req.arrival)
            self.spans[req.id] = sp
        else:
            # re-incarnation on the same tracer: keep phase totals,
            # clear the terminal, restart the queue clock at the NEW
            # arrival (the gap between incarnations is dead time the
            # journal replay owns, not queueing)
            sp.close_phase(sp.terminal if sp.terminal is not None
                           else req.arrival)
            sp.terminal = None
            sp.status = None
            sp.first_token = None
            sp.prefilled_seen = 0
            sp.replays += 1
            sp.open_phase("queue", req.arrival)
        sp.event(req.arrival, "replay" if replay else "queued")

    def on_terminal(self, req, status: str) -> None:
        """Terminal hook body — clock-free by design (fires inside
        ``engine.step()``); the transition lands at the next flush."""
        self.pending_terminals.append((req.id, status))

    def flush_terminals(self, now: float) -> None:
        for rid, status in self.pending_terminals:
            sp = self.spans.get(rid)
            if sp is None or sp.status is not None:
                continue            # exactly one terminal per span
            sp.close_phase(now)
            sp.terminal = now
            sp.status = status
            sp.event(now, f"terminal:{status}")
        if self.pending_terminals:
            self.pending_terminals.clear()
        self._last_now = max(self._last_now, now)

    def observe(self, occupied: Iterable[Tuple[int, int, int]],
                emitted_ids: Iterable[int], now: float) -> None:
        """Post-step observation pass: detect admissions and prefill
        chunk advances from the scheduler's occupied slots, and
        first-token transitions from this step's emissions — all at
        the same post-step ``now`` the loop stamps latencies with."""
        for rid, prefilled, _generated in occupied:
            sp = self.spans.get(rid)
            if sp is None or sp.status is not None:
                continue
            if sp.phase == "queue":
                sp.close_phase(now)
                sp.open_phase("prefill", now)
                sp.event(now, "admitted")
            if sp.phase == "prefill" and prefilled > sp.prefilled_seen:
                sp.chunks += 1
                sp.prefilled_seen = prefilled
                sp.event(now, "prefill_chunk")
        for rid in emitted_ids:
            sp = self.spans.get(rid)
            if (sp is None or sp.status is not None
                    or sp.first_token is not None):
                continue
            if sp.phase == "queue":
                # admitted, prefilled AND emitted inside one step (a
                # terminal removed it from the slots before the
                # occupancy pass could see it)
                sp.event(now, "admitted")
            sp.close_phase(now)
            sp.first_token = now
            sp.event(now, "first_token")
            sp.open_phase("decode", now)

    def on_evict(self, rid: int, now: float) -> None:
        """Eviction voids delivered work: the first-token stamp clears
        (it will be regenerated — same rule as the latency clock) and
        the span re-queues."""
        sp = self.spans.get(rid)
        if sp is None or sp.status is not None:
            return
        sp.close_phase(now)
        sp.first_token = None
        sp.prefilled_seen = 0
        sp.evictions += 1
        sp.event(now, "evicted")
        sp.open_phase("queue", now)

    # ---- step timeline -----------------------------------------------

    def enter(self, name: str) -> None:
        """Open the phase span ``name`` (one of ``STEP_SPANS``) inside
        whichever is open."""
        ann = self._annotation(name)
        ann.__enter__()
        self._open.append([name, time.perf_counter(), 0.0, ann])

    def exit(self) -> float:
        """Close the innermost open span; its self seconds go to
        ``phases``, its whole length to its parent's children.  Returns
        that length."""
        t = time.perf_counter()
        name, t0, inner, ann = self._open.pop()
        ann.__exit__(None, None, None)
        dur = t - t0
        self.phases[name] += dur - inner
        if self._open:
            self._open[-1][2] += dur
        return dur

    def begin_step(self, now: float) -> None:
        """Open ``serve.step`` at the loop clock's ``now``.  Spans a
        step that raised left open are dropped."""
        self._open.clear()
        self.phases = dict.fromkeys(STEP_SPANS, 0.0)
        self._step_t0 = now
        self._step_compiles = _compiles
        self.enter("serve.step")

    def end_step(self, now: float, emitted: int, signals: dict) -> None:
        """Close ``serve.step`` and record it: ``t0`` and ``t1`` (the
        post-step ``now``) on the loop clock, ``phases`` (which sum to
        the span's length on ``time.perf_counter``), the programs built
        during it, the tokens emitted, the engine's signals.  The record
        also goes to the process-wide ``utils/dispatch_log`` under the
        step's ``time.perf_counter()`` start, where a benchmark's metric
        reader finds it after the engine is gone."""
        from mpi_tensorflow_tpu.utils import dispatch_log

        at = self._open[0][1]
        self.exit()
        rec = {
            "t0": self._step_t0,
            "t1": now,
            "phases": self.phases,
            "compiles": _compiles - self._step_compiles,
            "emitted": int(emitted),
            "signals": signals,
        }
        self.phases = self._outside
        self.buffer.append(rec)
        self.last_step = rec
        self._last_now = max(self._last_now, rec["t1"])
        dispatch_log.record_step(at, rec)

    # ---- harvest ------------------------------------------------------

    def harvest(self, now: Optional[float] = None, *,
                reason: Optional[str] = None) -> dict:
        """Freeze this tracer into a mergeable dict.  Open phases are
        closed at ``now`` (default: the last stamp this tracer saw) so
        a failover harvest charges the victim's spans up to the
        failure instant; ``reason`` (e.g. ``"migrated"``) is stamped
        on every span that was still open."""
        if now is None:
            now = self._last_now
        self.flush_terminals(now)
        spans = {}
        for rid, sp in self.spans.items():
            if sp.status is None and sp.phase is not None:
                sp.close_phase(now)
                if reason is not None:
                    sp.event(now, reason)
            spans[rid] = sp.to_dict()
        return {
            "spans": spans,
            "steps": self.buffer.records(),
            "steps_dropped": self.buffer.dropped,
            "moe": self.moe,
        }


def merge_spans(harvests: Iterable[dict]) -> Dict[int, dict]:
    """Fold span dicts across harvests (replicas and/or failover
    incarnations) by request id.  Phase accumulators SUM — this is the
    failover contract: a migrated request's queue time accumulates
    across incarnations instead of resetting at re-admission.  The
    first-token stamp min-merges (mirror of the router's
    ``_first_archive``), the terminal comes from whichever incarnation
    actually finished (latest wins), and ``arrive`` is the earliest
    incarnation's arrival so end-to-end attained latency spans the
    whole migration."""
    out: Dict[int, dict] = {}
    for h in harvests:
        for rid, d in h["spans"].items():
            m = out.get(rid)
            if m is None:
                m = dict(d)
                m["events"] = list(d["events"])
                out[rid] = m
                continue
            m["queue_s"] += d["queue_s"]
            m["prefill_s"] += d["prefill_s"]
            m["decode_s"] += d["decode_s"]
            m["arrive"] = min(m["arrive"], d["arrive"])
            firsts = [t for t in (m["first_token"], d["first_token"])
                      if t is not None]
            m["first_token"] = min(firsts) if firsts else None
            if d["status"] is not None:
                if (m["status"] is None or m["terminal"] is None
                        or (d["terminal"] is not None
                            and d["terminal"] >= m["terminal"])):
                    m["status"] = d["status"]
                    m["terminal"] = d["terminal"]
            m["chunks"] += d["chunks"]
            m["evictions"] += d["evictions"]
            m["replays"] += d["replays"]
            m["incarnations"] += d.get("incarnations", 1)
            m["events"] = sorted(m["events"] + list(d["events"]),
                                 key=lambda e: e[0])
            m["events_dropped"] += d["events_dropped"]
    return out


def _us(t: float) -> int:
    return max(0, int(round(t * 1e6)))


def write_chrome_trace(path: str, replicas: List[dict]) -> dict:
    """Write Chrome trace-event (catapult) JSON: one pid per replica,
    request spans as async ``b``/``n``/``e`` events (matched by
    ``cat``+``id``), steps as ``X`` duration events on tid 1.  Open
    the file in Perfetto (ui.perfetto.dev) or chrome://tracing.

    ``replicas`` entries are harvest dicts plus ``pid``/``label``
    (the engine emits one; the router one per replica, incarnations
    pre-merged).  Returns a small summary dict ``{path, events,
    requests, steps}`` for logging."""
    events: List[dict] = []
    n_req = n_step = 0
    for rep in replicas:
        pid = int(rep.get("pid", 0))
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "ts": 0,
                       "args": {"name": rep.get("label",
                                                f"replica{pid}")}})
        for rid in sorted(rep["spans"]):
            sp = rep["spans"][rid]
            name = f"request {sp['rid']}"
            base = {"name": name, "cat": "request", "id": int(sp["rid"]),
                    "pid": pid, "tid": 0}
            events.append({**base, "ph": "b", "ts": _us(sp["arrive"]),
                           "args": {"arrive_s": sp["arrive"]}})
            for t, ev in sp["events"]:
                events.append({**base, "ph": "n", "ts": _us(t),
                               "args": {"event": ev}})
            end = sp["terminal"]
            if end is None:
                end = (sp["arrive"] + sp["queue_s"] + sp["prefill_s"]
                       + sp["decode_s"])
            events.append({**base, "ph": "e", "ts": max(_us(end),
                                                        _us(sp["arrive"])),
                           "args": {
                               "status": sp["status"],
                               "queue_ms": sp["queue_s"] * 1e3,
                               "prefill_ms": sp["prefill_s"] * 1e3,
                               "decode_ms": sp["decode_s"] * 1e3,
                               "evictions": sp["evictions"],
                           }})
            n_req += 1
        for rec in rep.get("steps", ()):
            dur = max(1, _us(rec["t1"] - rec["t0"]))
            events.append({"name": "step", "cat": "step", "ph": "X",
                           "pid": pid, "tid": 1, "ts": _us(rec["t0"]),
                           "dur": dur,
                           "args": {
                               **{f"{k}_us": _us(v)
                                  for k, v in rec["phases"].items()},
                               "compiles": rec["compiles"],
                               "emitted": rec["emitted"],
                               "signals": rec["signals"],
                           }})
            n_step += 1
    # catapult tolerates unsorted input, but monotone-per-track is the
    # schema our tests (and humans reading the raw JSON) rely on
    events.sort(key=lambda e: (e["pid"], e["tid"], e["ts"]))
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return {"path": path, "events": len(events), "requests": n_req,
            "steps": n_step}
