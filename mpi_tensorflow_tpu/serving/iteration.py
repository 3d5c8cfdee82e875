"""The ONE per-iteration serving body engine.run and the replica router
share.

Before this module, ``ReplicaRouter.tick()`` MIRRORED the body of
``PagedDecodeEngine.run``'s loop (submit stamping, deadline sweep,
latency cadence, eviction sample-discard) without the guard/journal/
drain wiring — the ROADMAP item-1 drift hazard: two copies of the same
accounting that could only age apart, and a fleet whose replicas had
strictly weaker failure semantics than a single engine.  Now both
callers drive an ``EngineLoop`` per engine:

- ``submit``  stamps the default per-request TTL, journals the submit
  (with any replayed ``pre`` prefix), and runs admission — recording
  the latency-clock start only for accepted requests;
- ``iterate`` sweeps deadlines, steps the engine once, and does the
  emit/eviction accounting: a token's latency is the wall time since
  the SAME sequence's previous token (first token: since arrival,
  queueing included), and an eviction voids the samples delivered so
  far (they are regenerated; only the final delivered stream counts)
  while journaling the void so a replayed run forgets them too.

``DrainTracker`` is the graceful-drain state machine both loops run
against a ``PreemptionGuard``: SIGTERM stops admission, sheds queued
work, lets in-flight sequences finish inside ``drain_ms``, and cuts
the rest as ``drained`` at the budget's hard edge.

One-body-two-callers is also what keeps the prefix-cache token-identity
contract (v1 AND the v2 generated-block/partial-copy extensions) a
single proof: cache effects live entirely inside ``engine.step()`` /
the scheduler's admission+terminal paths, so a trace replayed through
``engine.run`` and through the fleet router crosses the SAME
accounting here and emits the same tokens.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional, Tuple

_HEAP_SETTLED = False


def settle_heap() -> None:
    """Once a process, at a serving loop's first iteration: one full
    collection, then ``gc.freeze()``.  By then the programs are traced
    (a prewarm, a warm-up replay) and what tracing leaves alive, with the
    imported modules, is millions of objects that live as long as the
    process: every later full collection, which the loop's own garbage
    triggers about once a minute, would walk them all (0.44 s inside a
    50 s window with a 32-layer model's 18 programs: PERF.md, PR 32,
    where it moved a p95 by 7%).  Frozen, they are passed by; what the
    loop allocates from here on is collected as before, and reference
    counts free what they always freed."""
    global _HEAP_SETTLED
    if not _HEAP_SETTLED:
        _HEAP_SETTLED = True
        gc.collect()
        gc.freeze()


class EngineLoop:
    """Per-engine iteration state + the shared per-iteration body.

    Owns the latency bookkeeping for one engine and wires the engine's
    token stream into ``journal`` (``engine.step()`` journals each token
    at delivery, BEFORE the terminal hook can fire — the durable order
    is tok-then-end).  Single-owner like the scheduler: only the thread
    driving the engine may touch a loop.

    ``engine.step()`` looks one dispatch ahead: the tokens an
    ``iterate`` call returns are those of the PREVIOUS call's dispatches,
    read after this call's were issued, and everything below that
    stamps, journals or voids does so at that delivery, in the order it
    always had.  A token is stamped when the host has it, so a gap or a
    time to first token counts the dispatch it waited.
    """

    def __init__(self, engine, journal=None):
        self.engine = engine
        self.journal = journal
        engine._journal = journal
        # the engine owns the tracer (rebuilt at engine.reset, like the
        # pools); None means tracing off and every stamp site below is
        # a skipped branch — off is byte-for-byte the untraced loop
        self.tracer = getattr(engine, "tracer", None)
        self.token_times: Dict[int, List[float]] = {}
        self.last_emit: Dict[int, float] = {}
        # first-token emit stamp per request (TTFT = stamp - arrival):
        # set once at the request's first delivered token; an eviction
        # clears it — the pre-eviction first token is regenerated, and
        # only the final delivered stream's timing counts (the same
        # rule as token_times)
        self.first_emit: Dict[int, float] = {}
        self.tokens = 0
        self.peak_queue = 0

    def submit(self, req, *, pre: Optional[List[int]] = None,
               front: bool = False):
        """Admit ``req``: stamp the default TTL (an explicit deadline
        wins), journal the submit, run admission control.  ``pre`` is a
        replayed request's already-delivered prefix (staged into the
        journal so the durable stream stays whole across engines);
        ``front`` queues ahead of earlier arrivals — migrated/replayed
        work already waited its turn once.  Returns the scheduler's
        ``RejectedRequest`` (terminal status recorded) or None."""
        eng = self.engine
        if eng.serve.deadline_ms is not None and req.deadline is None:
            req = dataclasses.replace(
                req, deadline=req.arrival + eng.serve.deadline_ms / 1e3)
        if self.journal is not None:
            self.journal.record_submit(req, pre=pre)
        tr = self.tracer
        if tr is not None:
            tr.on_submit(req, replay=req.replayed)
        rej = eng.sched.submit(req, front=front)
        if rej is not None:
            if tr is not None:
                # synchronous rejection: the terminal hook already
                # queued the transition; land it at arrival (zero
                # queue time — the request never waited)
                tr.flush_terminals(req.arrival)
            return rej
        self.last_emit[req.id] = req.arrival
        self.token_times[req.id] = []
        self.peak_queue = max(self.peak_queue, len(eng.sched.waiting))
        return None

    def iterate(self, now: float, time_fn, t0: float) \
            -> List[Tuple[int, int]]:
        """One engine iteration: deadline sweep BEFORE the step (expired
        work must not buy another dispatch's worth of pool time), one
        ``engine.step()``, then the emit/eviction accounting.  Returns
        the ``(request id, token)`` pairs delivered in this call."""
        settle_heap()
        eng = self.engine
        tr = self.tracer
        if tr is not None:
            step_t0 = now
            tr.begin_step()
            _m0 = time.monotonic()
        eng.sched.expire_deadlines(now)
        if tr is not None:
            tr.sweep_s += time.monotonic() - _m0
        emitted = eng.step()
        now = time_fn() - t0
        for rid, _tok in emitted:
            if rid in self.last_emit:
                self.token_times[rid].append(now - self.last_emit[rid])
                self.last_emit[rid] = now
                self.first_emit.setdefault(rid, now)
        self.tokens += len(emitted)
        if tr is not None:
            # span stamping uses the SAME post-step ``now`` as the
            # latency clock above, so span TTFT == stamped TTFT exactly
            tr.observe(eng.sched.occupied_view(),
                       {rid for rid, _tok in emitted}, now)
        # AFTER the emit accounting: an eviction discards the request's
        # samples so far — including a token emitted this very step
        # (prefill-final then evicted by a later slot's ensure_block);
        # only the final delivered stream counts, and the journal must
        # forget the voided tokens exactly like the latency clock does
        for rid in eng.sched.evicted_ids:
            if self.journal is not None:
                self.journal.record_evict(rid)
            self.token_times[rid] = []
            self.last_emit[rid] = now
            self.first_emit.pop(rid, None)
            if tr is not None:
                tr.on_evict(rid, now)
        eng.sched.evicted_ids.clear()
        if tr is not None:
            # terminals land AFTER first-token stamping (same ``now``),
            # so ``terminal >= first_token`` holds within every span
            tr.flush_terminals(now)
            tr.end_step(step_t0, now, len(emitted), eng.load_signals())
        return emitted

    def latencies(self) -> List[float]:
        return [x for ts in self.token_times.values() for x in ts]


class DrainTracker:
    """Graceful-drain state shared by the engine loop and the fleet
    router: ``start`` marks the SIGTERM moment (admission stops, queued
    work sheds), ``expired`` is the budget's hard edge past which
    in-flight work is cut as ``drained``.  ``drain_ms`` None = no
    budget (finish everything in flight)."""

    def __init__(self, drain_ms: Optional[float]):
        self.drain_ms = drain_ms
        self.draining = False
        self.t0 = 0.0
        self.shed = 0            # queued/pending work shed at drain start
        self.fin_at_start = 0    # completions before the stop request

    def start(self, now: float, finished_now: int = 0) -> None:
        self.draining = True
        self.t0 = now
        self.fin_at_start = finished_now

    def expired(self, now: float) -> bool:
        return (self.draining and self.drain_ms is not None
                and (now - self.t0) * 1e3 > self.drain_ms)

    def result(self, finished_total: int, cut: int) -> dict:
        """The canonical ``drain`` result block (requested / drained-to-
        completion / shed / cut / budget) both run loops emit."""
        return {
            "requested": self.draining,
            # finished after the stop request = drained to completion
            "drained": (finished_total - self.fin_at_start
                        if self.draining else 0),
            "shed": self.shed if self.draining else 0,
            "cut": int(cut),
            "budget_ms": self.drain_ms,
        }

    def result_counts(self, counts) -> dict:
        """The SAME canonical block, computed from per-status terminal
        counts recorded while draining — the fleet router's accounting
        (it observes terminals as hook notifications rather than one
        scheduler's finished-list delta).  Defined here, next to
        ``result``, so the block's shape lives in exactly one module."""
        return {
            "requested": self.draining,
            "drained": int(counts.get("ok", 0)) if self.draining else 0,
            "shed": int(counts.get("shed", 0)) if self.draining else 0,
            "cut": int(counts.get("drained", 0)),
            "budget_ms": self.drain_ms,
        }
