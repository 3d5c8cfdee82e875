"""Crash recovery for serving: host-side replay journal + supervision.

The training side already has a three-layer recovery story (preemption
guard -> durable checkpoint -> elastic restart, train/elastic.py); this
module is the serving equivalent.  The key asset is that greedy decode
is DETERMINISTIC: for a fixed model+params, the tokens following any
prompt are a pure function of the prompt.  So the durable state a
serving process needs is tiny and already on the host — each request's
prompt plus the prefix of tokens generated so far.  After a crash (or
an in-process transient device failure), a live request replays as a
fresh request whose prompt is ``original_prompt + generated_prefix``
and whose budget is the remaining tokens: chunked prefill re-ingests
the concatenation, the prefill-final argmax emits exactly the token the
lost process would have emitted next, and the delivered stream
``prefix + new_tokens`` is token-identical to an unfaulted run (pinned
by tests/test_serving_recovery.py and tests/test_fault_injection.py).

Layers:

- ``ReplayJournal``   append-only JSONL of submit/token/evict/end
                      records, mirrored in memory.  ``path=None`` keeps
                      it memory-only (in-process retry); a path makes it
                      durable across SIGKILL (line-buffered appends; a
                      torn final line from a mid-write crash is
                      ignored on load).
- ``run_with_replay`` the supervisor: runs an engine over the journal,
                      classifies failures with the SAME status-code-
                      first ``train/elastic.is_transient`` logic the
                      training supervisor uses, rebuilds pools/engine on
                      transient device loss, and replays live sequences.
                      Non-transient errors (shape bugs, OOM) re-raise
                      immediately — a deterministic bug replayed forever
                      is a worse failure mode.

Prefix cache x replay: the radix trie (serving/prefix_cache) indexes
content that lives in the DEVICE pool, so it dies with the engine and
is rebuilt from scratch by the replayed prefills themselves — a
replacement engine's trie starts empty, the re-rooted
``prompt + prefix`` prompts repopulate it as they prefill, and replayed
requests that share prefixes re-share blocks in the new pool.  Nothing
about the trie is journaled (journaling it would pin device state the
crash just lost); the journal's token streams stay the single durable
truth, and the replay is token-identical with the cache on or off.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import Counter
from typing import Callable, Dict, List, Optional

from mpi_tensorflow_tpu.serving.scheduler import Request
from mpi_tensorflow_tpu.train import elastic


@dataclasses.dataclass
class JournalEntry:
    """Replay state of one request: the submitted prompt, any tokens
    already delivered BEFORE this submit (``pre`` — non-empty only on a
    replay submit, whose prompt embeds them), tokens generated since,
    and the terminal status once one is recorded."""
    prompt: List[int]
    max_new_tokens: int
    arrival: float
    pre: List[int] = dataclasses.field(default_factory=list)
    toks: List[int] = dataclasses.field(default_factory=list)
    status: Optional[str] = None

    @property
    def delivered(self) -> List[int]:
        """The output stream as the client sees it so far."""
        return self.pre + self.toks


class ReplayJournal:
    """Append-only request journal, host-side, optionally durable.

    Record kinds (one JSON object per line):
      {"kind": "submit", "id", "prompt", "n", "arrival", "pre"}
      {"kind": "tok",    "id", "t"}
      {"kind": "evict",  "id"}          # restart-from-scratch: tokens
                                        # since the last submit are void
      {"kind": "end",    "id", "status"}

    Constructing with an existing ``path`` LOADS it first — the crash-
    recovery entry point — then appends.  All writes also update the
    in-memory state, so in-process retries need no reload.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.entries: Dict[int, JournalEntry] = {}
        self.statuses: Dict[int, str] = {}
        # delivered-so-far per replayed id, staged by replay_requests so
        # the engine's plain record_submit(req) journals the right "pre"
        self._pending_pre: Dict[int, List[int]] = {}
        self._fh = None
        if path is not None:
            if os.path.exists(path):
                self._load(path)
            self._fh = open(path, "a", buffering=1)   # line-buffered:
            # each record is durable as soon as the line completes

    # ---------------- load ----------------

    def _load(self, path: str) -> None:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue    # torn final line from a mid-write crash
                self._apply(rec)

    def _apply(self, rec: dict) -> None:
        kind, rid = rec.get("kind"), rec.get("id")
        if kind == "submit":
            self.entries[rid] = JournalEntry(
                prompt=list(rec["prompt"]), max_new_tokens=int(rec["n"]),
                arrival=float(rec.get("arrival", 0.0)),
                pre=list(rec.get("pre", ())))
        elif kind == "tok" and rid in self.entries:
            self.entries[rid].toks.append(int(rec["t"]))
        elif kind == "evict" and rid in self.entries:
            # restart-from-scratch preemption: the discarded tokens are
            # regenerated verbatim (greedy determinism), so the journal
            # forgets them exactly like the latency accounting does
            self.entries[rid].toks.clear()
        elif kind == "end":
            self.statuses[rid] = rec["status"]
            if rid in self.entries:
                self.entries[rid].status = rec["status"]

    # ---------------- write ----------------

    def _write(self, rec: dict) -> None:
        self._apply(rec)
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")

    def record_submit(self, req: Request,
                      pre: Optional[List[int]] = None) -> None:
        if pre is None:
            pre = self._pending_pre.pop(req.id, [])
        self._write({"kind": "submit", "id": req.id,
                     "prompt": list(req.prompt), "n": req.max_new_tokens,
                     "arrival": req.arrival, "pre": list(pre)})

    def record_token(self, rid: int, tok: int) -> None:
        self._write({"kind": "tok", "id": rid, "t": int(tok)})

    def record_evict(self, rid: int) -> None:
        self._write({"kind": "evict", "id": rid})

    def record_end(self, req: Request, status: str) -> None:
        self._write({"kind": "end", "id": req.id, "status": status})

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    # ---------------- replay assembly ----------------

    def replay_requests(self, requests: List[Request],
                        eos_id: Optional[int] = None) -> List[Request]:
        """The request list a replacement engine run should serve:
        never-journaled requests as-is; live (no terminal status)
        requests re-rooted at ``prompt + delivered`` with the remaining
        budget; terminated requests omitted.  Deadlines are dropped on
        replay — they were stamped on the dead process's clock, and the
        replacement run's clock restarts at zero (honoring stale stamps
        would mass-expire recovered work on arrival)."""
        out = []
        for req in requests:
            ent = self.entries.get(req.id)
            if ent is None:
                if req.id in self.statuses:
                    continue          # rejected/shed before ever admitted
                out.append(req)
                continue
            if ent.status is not None:
                continue
            rep, done = replay_one(ent, req, eos_id)
            if rep is None:
                # crashed between the final token and its end record
                self.record_end(req, "ok")
                continue
            self._pending_pre[req.id] = done
            out.append(rep)
        return out

    def outputs(self) -> Dict[int, List[int]]:
        """Delivered streams of every completed (``ok``) request."""
        return {rid: ent.delivered for rid, ent in self.entries.items()
                if ent.status == "ok"}


def replay_one(ent: JournalEntry, req: Request,
               eos_id: Optional[int] = None,
               arrival: float = 0.0) -> tuple:
    """Re-root ONE live journal entry as a fresh request — THE failover
    primitive shared by the single-engine supervisor (``replay_requests``)
    and the fleet router's replica migration: the replacement request's
    prompt embeds every delivered token with the remaining budget, so
    chunked prefill re-ingests the concatenation and the prefill-final
    argmax emits exactly the token the lost engine would have emitted
    next (greedy determinism).  The re-rooting is built from the ENTRY
    itself — ``ent.prompt`` already embeds the ``pre`` prefix of the
    submit it records, so ``ent.prompt + ent.toks`` is correct whether
    ``req`` is the original request OR an earlier replay's re-rooted
    one (a fault during a journal-resumed run; building from
    ``req.prompt + delivered`` there would double-embed the prefix).
    ``req`` contributes only identity (id, session).

    Returns ``(request, delivered)``; ``request`` is None when the
    stream is already complete (the engine died between the final token
    and its end record) — the caller records the terminal ``ok``.
    Deadlines are dropped (the caller's clock decides any fresh TTL);
    the session key survives so re-homed sticky placement still sees
    it."""
    done = ent.delivered
    if eos_id is not None and eos_id in done:
        done = done[:done.index(eos_id) + 1]
    remaining = ent.max_new_tokens + len(ent.pre) - len(done)
    if remaining <= 0 or (eos_id is not None and done
                          and done[-1] == eos_id):
        return None, done
    # tokens generated SINCE the recorded submit (done minus its pre,
    # after any EOS truncation above)
    since = done[len(ent.pre):]
    return Request(req.id, list(ent.prompt) + since, remaining,
                   arrival=arrival, replayed=True,
                   session=req.session), done


# ---------------- fleet journal assembly (serving/router) ----------------

def _entry_wins(a: JournalEntry, b: JournalEntry) -> bool:
    """Whether ``a`` is the more authoritative view of one request
    across per-replica journals: a terminal status beats a live entry
    (terminals fire exactly once fleet-wide), else the longer delivered
    stream wins (a migrated-to replica's entry embeds the donor's
    delivered prefix as ``pre``, so it strictly extends it)."""
    if (a.status is not None) != (b.status is not None):
        return a.status is not None
    return len(a.delivered) > len(b.delivered)


def merge_fleet_entries(journals) -> Dict[int, tuple]:
    """``{request id: (entry, owning journal)}`` — the authoritative
    per-request view across a fleet's per-replica journals."""
    best: Dict[int, tuple] = {}
    for j in journals:
        for rid, ent in j.entries.items():
            cur = best.get(rid)
            if cur is None or _entry_wins(ent, cur[0]):
                best[rid] = (ent, j)
    return best


def fleet_statuses(journals) -> Dict[int, str]:
    """Union of terminal statuses across per-replica journals (each
    request terminates exactly once fleet-wide, so no key collides)."""
    out: Dict[int, str] = {}
    for j in journals:
        out.update(j.statuses)
    return out


def fleet_outputs(journals) -> Dict[int, List[int]]:
    """Delivered streams of every completed request, fleet-wide —
    ``pre + toks`` of each request's authoritative entry, so a stream
    split across a failover (donor prefix + survivor suffix) comes back
    whole."""
    return {rid: ent.delivered
            for rid, (ent, _j) in merge_fleet_entries(journals).items()
            if ent.status == "ok"}


def fleet_replay_requests(journals, requests: List[Request],
                          eos_id: Optional[int] = None) -> tuple:
    """The request list a replacement FLEET run should serve, plus the
    ``{request id: delivered prefix}`` map the router stages into
    whichever replica's journal each replay lands on (per-replica
    journals can't pre-stage it — placement isn't known until route
    time).  Mirrors ``ReplayJournal.replay_requests`` over the merged
    per-replica view: never-journaled requests as-is, live requests
    re-rooted at ``prompt + delivered``, terminated requests omitted."""
    merged = merge_fleet_entries(journals)
    statuses = fleet_statuses(journals)
    todo: List[Request] = []
    pre: Dict[int, List[int]] = {}
    for req in requests:
        got = merged.get(req.id)
        if got is None:
            if req.id not in statuses:
                todo.append(req)
            continue
        ent, journal = got
        if ent.status is not None or req.id in statuses:
            # a terminal status ANYWHERE in the fleet wins over a stale
            # live entry in another journal (e.g. migrated off a dead
            # donor — whose on-disk entry stays live — then shed during
            # a drain before the survivor ever submitted it: the end
            # record is entry-less in the survivor's journal).  Each
            # request gets exactly ONE terminal status across runs.
            continue
        rep, done = replay_one(ent, req, eos_id)
        if rep is None:
            # crashed between the final token and its end record
            journal.record_end(req, "ok")
            continue
        todo.append(rep)
        pre[req.id] = done
    return todo, pre


def run_with_replay(make_engine: Callable[[], "object"],
                    requests: List[Request], *,
                    journal: Optional[ReplayJournal] = None,
                    journal_path: Optional[str] = None,
                    max_restarts: int = 3,
                    backoff_seconds: float = 0.0,
                    is_transient_fn: Callable[[BaseException],
                                              bool] = elastic.is_transient,
                    guard=None, time_fn=time.perf_counter) -> dict:
    """Serve ``requests`` through a journaled engine, surviving transient
    failures by rebuilding the engine (fresh pools — device state is
    presumed lost) and replaying live sequences through chunked prefill.

    ``make_engine`` is a zero-arg factory returning a fresh
    ``PagedDecodeEngine`` (the serving analogue of elastic's
    idempotent-from-checkpoint ``train_fn``).  Failure classification is
    ``train/elastic.is_transient`` — status-code-first, so a reworded
    device-loss message still replays while a deterministic shape bug
    still raises.  Returns the final run's stats dict with ``outputs``
    and ``statuses`` merged across every attempt (journal-complete) and
    ``faults`` aggregated, including the ``replays`` count.
    """
    if journal is None:
        journal = ReplayJournal(journal_path)
    totals: Counter = Counter()
    crash_harvests: List[dict] = []
    attempt = 0
    while True:
        engine = None
        try:
            # the rebuild itself can hit the still-recovering device —
            # it must be classified and retried like the run
            engine = make_engine()
            todo = journal.replay_requests(requests,
                                           eos_id=engine.serve.eos_id)
            res = engine.run(todo, journal=journal, guard=guard,
                             time_fn=time_fn)
            totals.update(engine.sched.counters)
            break
        except Exception as e:     # noqa: BLE001 — classified right below
            if engine is not None:
                totals.update(engine.sched.counters)
                if getattr(engine, "tracer", None) is not None:
                    # freeze the dying incarnation's spans at the last
                    # stamp its tracer saw; merged below so a replayed
                    # request's phase time accumulates across restarts
                    # instead of resetting (the failover span contract)
                    crash_harvests.append(
                        engine.tracer.harvest(reason="crashed"))
            if not is_transient_fn(e) or attempt >= max_restarts:
                raise
            attempt += 1
            print(f"[serving-recovery] transient failure ({e!r}); "
                  f"rebuilding engine, replay {attempt}/{max_restarts}")
            if backoff_seconds > 0:
                time.sleep(backoff_seconds)
    totals["replays"] += attempt
    if crash_harvests and res.get("trace") is not None:
        # spans survive crash/replay the same way they survive fleet
        # failover: merge every crashed incarnation's harvest with the
        # final attempt's, summing phase accumulators per request
        from mpi_tensorflow_tpu.serving.tracing import merge_spans

        harvests = crash_harvests + [res["trace"]["replicas"][0]]
        spans = merge_spans(harvests)
        steps = [r for h in harvests for r in h["steps"]]
        dropped = sum(h["steps_dropped"] for h in harvests)
        res["trace"] = {
            "enabled": True,
            "replicas": [{"pid": 0, "label": "engine", "spans": spans,
                          "steps": steps, "steps_dropped": dropped}],
            "spans": spans,
            "steps": len(steps),
            "steps_dropped": dropped,
        }
    res["outputs"] = journal.outputs()
    res["statuses"] = dict(journal.statuses)
    # res["tokens"]/elapsed_s/tokens_per_sec stay the FINAL attempt's own
    # (internally consistent throughput); the journal-merged stream total
    # across every attempt gets its own key
    res["delivered_tokens"] = sum(len(v) for v in res["outputs"].values())
    from mpi_tensorflow_tpu.utils.metrics_writer import faults_block

    res["faults"] = faults_block(totals)
    res["replays"] = attempt
    if "prefix" in res:
        # prefix-cache accounting merged across every attempt (each
        # attempt's counters were folded into ``totals`` above) — a
        # replayed prefill that re-hits the rebuilt trie counts, same
        # as the fault counters do.  Same constructor as the engine's
        # own prefix block, so the two shapes cannot drift
        from mpi_tensorflow_tpu.utils.metrics_writer import prefix_block

        res["prefix"] = prefix_block(
            totals, enabled=res["prefix"]["enabled"],
            trie_blocks=res["prefix"]["trie_blocks"])
    if "speculation" in res:
        # speculative-decoding accounting merged across attempts the
        # same way: drafts verified before a crash were real bandwidth
        # savings even though the replay regenerates their tokens
        from mpi_tensorflow_tpu.utils.metrics_writer import \
            speculation_block

        res["speculation"] = speculation_block(
            totals, enabled=res["speculation"]["enabled"],
            mode=res["speculation"]["mode"],
            draft_k=res["speculation"]["draft_k"],
            draft_auto=res["speculation"].get("draft_auto", "off"))
    return res
