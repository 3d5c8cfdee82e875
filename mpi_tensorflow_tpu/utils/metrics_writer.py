"""Structured metrics sink: TensorBoard event files + JSONL fallback.

The reference's only metrics channel is the 50-step stdout trace
(``/root/reference/mpipy.py:88``); utils/logging.py reproduces that format.
This module is the machine-readable counterpart (SURVEY.md §5 metrics row):
scalars stream to a TensorBoard event file when ``tensorboardX`` is
importable, and ALWAYS to ``<dir>/metrics.jsonl`` (one ``{"step": t,
"tag": ..., "value": ...}`` line per scalar) so a zero-dependency consumer
— or this repo's tests — can read the same stream.

Multi-host: only process 0 writes (the scalars passed in are already
globally reduced by the loops); other processes construct a writer that
no-ops, so call sites need no rank guard.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional


class MetricsWriter:
    """Scalar metrics sink; safe no-op when ``log_dir`` is None/empty."""

    def __init__(self, log_dir: Optional[str], *, enabled: bool = True):
        self._dir = log_dir
        self._enabled = bool(log_dir) and enabled
        self._tb = None
        self._jsonl = None
        if not self._enabled:
            return
        os.makedirs(log_dir, exist_ok=True)
        self._jsonl = open(os.path.join(log_dir, "metrics.jsonl"), "a",
                           buffering=1)
        try:
            from tensorboardX import SummaryWriter

            self._tb = SummaryWriter(log_dir)
        except Exception:
            self._tb = None   # JSONL alone is the contract

    @property
    def active(self) -> bool:
        return self._enabled

    def scalar(self, tag: str, value: float, step: int) -> None:
        if not self._enabled:
            return
        v = float(value)
        # NaN/Inf are not JSON; strict consumers (jq, JSON.parse) abort the
        # whole stream on one bad line — encode them as null instead
        jv = v if v == v and abs(v) != float("inf") else None
        self._jsonl.write(json.dumps(
            {"step": int(step), "tag": tag, "value": jv,
             "time": round(time.time(), 3)}) + "\n")
        if self._tb is not None:
            self._tb.add_scalar(tag, v, int(step))

    def scalars(self, values: dict, step: int) -> None:
        for tag, v in values.items():
            self.scalar(tag, v, step)

    def close(self) -> None:
        self._enabled = False   # scalar() after close() is a silent no-op
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def for_process(log_dir: Optional[str], process_index: int) -> MetricsWriter:
    """Writer that is active on process 0 only (scalars are global)."""
    return MetricsWriter(log_dir, enabled=process_index == 0)


#: canonical serving health-counter keys — THE shape of the ``faults``
#: block every consumer sees (engine result dicts, the recovery
#: supervisor's merged totals, the serving entry point's JSON).  One
#: definition so a dashboard keyed on these names never drifts from the
#: engine's accounting.
SERVING_FAULT_KEYS = ("rejected", "shed", "deadline_exceeded",
                      "evicted_too_often", "drained", "evictions",
                      "replays")


def faults_block(counters) -> dict:
    """Normalize a scheduler/supervisor counter mapping into the
    canonical serving ``faults`` block: every key present (0 when the
    counter never fired), values plain ints."""
    return {k: int(counters.get(k, 0)) for k in SERVING_FAULT_KEYS}


#: canonical fleet-level fault-tolerance counters (serving/router) —
#: THE shape of the ``fleet_faults`` block every consumer sees (router
#: result dicts, the entry point's JSON).  failovers = replica
#: faults handled; migrated_requests = live/queued requests re-homed to
#: survivors; replay_tokens = prompt+prefix tokens re-ingested through
#: chunked prefill to reconstruct migrated streams; ejections /
#: readmissions = circuit-breaker transitions; sticky_rehomed /
#: sticky_evicted = session-affinity map hygiene.
FLEET_FAULT_KEYS = ("failovers", "migrated_requests", "replay_tokens",
                    "ejections", "readmissions", "sticky_rehomed",
                    "sticky_evicted")


def fleet_faults_block(counters) -> dict:
    """Normalize a router counter mapping into the canonical
    ``fleet_faults`` block: every key present (0 when the counter never
    fired), values plain ints — same discipline as ``faults_block``."""
    return {k: int(counters.get(k, 0)) for k in FLEET_FAULT_KEYS}


def prefix_block(counters, *, enabled: bool, trie_blocks: int = 0,
                 router_prefix_hits: int = 0) -> dict:
    """Normalize scheduler/supervisor counters into the canonical
    serving ``prefix`` (radix prefix cache) accounting block — one
    constructor shared by engine results, the recovery supervisor's
    cross-attempt merge and router aggregation, so the key
    set and the hit-rate rounding can never drift between them.

    ``hit_rate`` counts FULL-BLOCK sharing only; partial tail-block
    rows ride separately as ``partial_copy_tokens``, and
    ``prefill_tokens_saved`` is the prefix-v2 headline — every prompt
    position served out of cache (full blocks + partial rows) instead
    of recomputed."""
    hit = int(counters.get("prefix_hit_tokens", 0))
    total = int(counters.get("prefix_prompt_tokens", 0))
    partial = int(counters.get("prefix_partial_copy_tokens", 0))
    return {
        "enabled": bool(enabled),
        "hit_tokens": hit,
        "prompt_tokens": total,
        "hit_rate": round(hit / total, 4) if total else 0.0,
        "shared_blocks": int(counters.get("prefix_shared_blocks", 0)),
        "cow_copies": int(counters.get("prefix_cow_copies", 0)),
        "trie_evictions": int(counters.get("prefix_trie_evictions", 0)),
        "trie_blocks": int(trie_blocks),
        # block-starved admissions served out of FIFO order because a
        # cached prefix made them fit (the scheduler's hit-aware
        # admission policy); 0 when the pool never came under pressure
        "hit_admissions": int(counters.get("prefix_hit_admissions", 0)),
        # prefix v2 (--prefix-gen): trie nodes adopted from
        # GENERATED output at request completion, and tail rows served
        # through the partial-copy dispatch instead of re-prefill
        "gen_inserted_blocks":
            int(counters.get("prefix_gen_inserted_blocks", 0)),
        "partial_copy_tokens": partial,
        "prefill_tokens_saved": hit + partial,
        # prefix v2 (--prefix-route): fleet placements the
        # router's prefix hint decided (always 0 for a single engine)
        "router_prefix_hits": int(router_prefix_hits),
    }


def speculation_block(counters, *, enabled: bool, mode: str = "off",
                      draft_k: int = 0, draft_auto: str = "off") -> dict:
    """Normalize scheduler/supervisor counters into the canonical
    serving ``speculation`` (speculative decoding) accounting block —
    one constructor shared by engine results, the recovery
    supervisor's cross-attempt merge, and the entry point's JSON.

    ``steps_saved`` is the bandwidth proxy the feature exists for:
    tokens emitted through the verify path minus verify forwards run —
    i.e. how many full KV-streaming decode passes speculation avoided
    (0 when nothing was ever accepted; vanilla decode is one forward
    per token by definition)."""
    drafted = int(counters.get("spec_drafted", 0))
    accepted = int(counters.get("spec_accepted", 0))
    forwards = int(counters.get("spec_verify_forwards", 0))
    emitted = int(counters.get("spec_emitted", 0))
    k_sum = int(counters.get("spec_k_sum", 0))
    k_steps = int(counters.get("spec_k_steps", 0))
    return {
        "enabled": bool(enabled),
        "mode": mode,
        "draft_k": int(draft_k),
        # the window the policy actually offered, averaged over verify
        # steps: == draft_k with auto-tuning off; under --draft-auto
        # on this is THE number the knob exists to report
        "draft_auto": draft_auto,
        "effective_k": (round(k_sum / k_steps, 2) if k_steps
                        else int(draft_k)),
        "draft_tokens": drafted,
        "accepted_tokens": accepted,
        "accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
        "verify_forwards": forwards,
        "emitted_tokens": emitted,
        "mean_accepted_len": (round(accepted / forwards, 4)
                              if forwards else 0.0),
        "steps_saved": emitted - forwards,
    }


#: canonical host-tier keys — THE shape of the ``tier`` block every
#: consumer sees (engine results, the entry point's JSON).  Tiering
#: (--kv-tier host) demotes cold prefix-cache blocks to host RAM
#: on eviction and promotes them back on a later trie match;
#: prefill_tokens_saved_tier = promotions * block_size is the prefill
#: work those re-admissions avoided re-paying.
TIER_KEYS = ("enabled", "mode", "demotions", "promotions",
             "host_blocks", "host_blocks_peak",
             "promote_latency_ms_total", "promote_latency_ms_mean",
             "prefill_tokens_saved_tier")


def tier_block(*, enabled: bool = False, mode: str = "off",
               demotions: int = 0, promotions: int = 0,
               host_blocks: int = 0, host_blocks_peak: int = 0,
               promote_ms_total: float = 0.0,
               block_size: int = 0) -> dict:
    """Normalize host-tier counters into the canonical serving ``tier``
    block — same discipline as the blocks above: every TIER_KEYS key
    present, plain types, derived rates computed (zero-safely) here."""
    return {
        "enabled": bool(enabled),
        "mode": mode,
        "demotions": int(demotions),
        "promotions": int(promotions),
        "host_blocks": int(host_blocks),
        "host_blocks_peak": int(host_blocks_peak),
        "promote_latency_ms_total": round(float(promote_ms_total), 3),
        "promote_latency_ms_mean": (
            round(float(promote_ms_total) / promotions, 3)
            if promotions else 0.0),
        "prefill_tokens_saved_tier": int(promotions * block_size),
    }


#: canonical routed-expert load keys — THE shape of the ``moe`` block
MOE_KEYS = ("enabled", "assignments", "experts_touched", "per_expert",
            "load_max_over_mean")


def moe_block(*, enabled: bool = False, per_expert=(),
              experts_touched: int = 0) -> dict:
    """Normalize the routed experts' device counters into the canonical
    serving ``moe`` block: assignments each held expert received,
    experts touched summed over calls and layers, and the busiest
    expert's load over the mean (1.0 = even; zero-safe)."""
    per_expert = [int(n) for n in per_expert]
    total = sum(per_expert)
    return {
        "enabled": bool(enabled),
        "assignments": total,
        "experts_touched": int(experts_touched),
        "per_expert": per_expert,
        "load_max_over_mean": (
            round(max(per_expert) * len(per_expert) / total, 4)
            if total else 0.0),
    }


#: canonical goodput-under-SLO keys — THE shape of the ``goodput``
#: block the serving entry point prints.  Goodput =
#: tokens (and requests) per second from requests that completed within
#: their latency budget (DistServe, arXiv:2401.09670) — the serving
#: number raw tokens/sec over-reports under load.
GOODPUT_KEYS = ("enabled", "requests", "ok_requests",
                "slo_met_requests", "slo_attainment",
                "goodput_tokens_per_sec", "goodput_requests_per_sec",
                "p50_attained_ms", "p99_attained_ms",
                "ttft_p50_ms", "ttft_p99_ms", "per_tenant")


def _percentile(vals, q: float) -> float:
    """Linear-interpolation percentile over a small sample (no numpy:
    this module stays importable by zero-dependency consumers)."""
    if not vals:
        return 0.0
    s = sorted(vals)
    k = (len(s) - 1) * q
    f = int(k)
    c = min(f + 1, len(s) - 1)
    return s[f] + (s[c] - s[f]) * (k - f)


def goodput_block(rows, *, elapsed_s: float, enabled=None) -> dict:
    """Aggregate per-request rows (serving/loadgen.per_request_rows:
    ``tenant`` / ``status`` / ``tokens`` / ``attained_ms`` / ``slo_ms``
    each) into the canonical ``goodput`` block, with a per-tenant
    breakdown keyed by tenant class.

    A row MEETS its SLO when it finished ``ok`` within ``slo_ms``
    (None = no budget, so every ``ok`` completion counts — goodput
    degenerates to raw delivered throughput).  Attained-latency
    percentiles cover completed requests only: an unfinished request
    has no whole-request latency, and its miss is already counted by
    ``slo_attainment``.  ``ttft_p50_ms``/``ttft_p99_ms`` cover every
    row carrying a ``ttft_ms`` stamp (any request that streamed at
    least one token) — time-to-first-token is the queueing + prefill
    latency mixed batching targets, visible even for requests that
    later failed their deadline."""
    rows = list(rows)
    if enabled is None:
        enabled = any(r.get("slo_ms") is not None for r in rows)

    def agg(sub: list) -> dict:
        ok = [r for r in sub if r.get("status") == "ok"]
        met = [r for r in ok
               if r.get("slo_ms") is None
               or (r.get("attained_ms") is not None
                   and r["attained_ms"] <= r["slo_ms"])]
        att = [r["attained_ms"] for r in ok
               if r.get("attained_ms") is not None]
        ttft = [r["ttft_ms"] for r in sub
                if r.get("ttft_ms") is not None]
        toks = sum(int(r.get("tokens", 0)) for r in met)
        return {
            "requests": len(sub),
            "ok_requests": len(ok),
            "slo_met_requests": len(met),
            "slo_attainment": (round(len(met) / len(sub), 4)
                               if sub else 0.0),
            "goodput_tokens_per_sec": (round(toks / elapsed_s, 2)
                                       if elapsed_s > 0 else 0.0),
            "goodput_requests_per_sec": (round(len(met) / elapsed_s, 4)
                                         if elapsed_s > 0 else 0.0),
            "p50_attained_ms": round(_percentile(att, 0.5), 2),
            "p99_attained_ms": round(_percentile(att, 0.99), 2),
            "ttft_p50_ms": round(_percentile(ttft, 0.5), 2),
            "ttft_p99_ms": round(_percentile(ttft, 0.99), 2),
        }

    tenants = sorted({r.get("tenant", "default") for r in rows})
    block = agg(rows)
    block["enabled"] = bool(enabled)
    block["per_tenant"] = {
        t: agg([r for r in rows if r.get("tenant", "default") == t])
        for t in tenants}
    return block


#: canonical phase-attribution keys — THE shape of the ``breakdown``
#: block the entry point prints with --trace on (serving/tracing
#: spans).  queue/prefill/decode percentiles are recomputed FROM SPANS
#: (not from the engine's scalar stamps); the two ``*_max_delta_ms``
#: keys are the cross-checks that pin the span clock to the stamped
#: clock: phase times sum to the attained whole-request latency, and
#: span TTFT equals the stamped first-token time.
BREAKDOWN_KEYS = ("enabled", "requests", "queue_ms_p50", "queue_ms_p99",
                  "prefill_ms_p50", "prefill_ms_p99", "decode_ms_p50",
                  "decode_ms_p99", "ttft_ms_p50", "ttft_ms_p99",
                  "phase_sum_vs_attained_max_delta_ms",
                  "ttft_vs_stamp_max_delta_ms", "steps", "steps_dropped",
                  "step_ms_p50", "device_wait_ms_p50", "lookahead_share")


def breakdown_block(trace, *, enabled=None, stamped_first_s=None) -> dict:
    """Aggregate a serving ``trace`` result block (engine/router
    ``res["trace"]``: fleet-merged spans + step-ring accounting) into
    the canonical ``breakdown`` block — per-phase latency percentiles
    over requests that finished ``ok``, with the span-vs-stamp
    consistency deltas.

    ``stamped_first_s`` is the run's ``request_first_token_s`` map;
    when given, ``ttft_vs_stamp_max_delta_ms`` reports the worst
    disagreement between a span's first-token stamp and the loop's —
    the loop stamps both from the same post-step clock read, so this
    should be ~0 and a drift means an instrumentation bug.

    The last three keys read the step ring and say which side sets the
    pace: ``step_ms_p50`` is an iteration's length, ``device_wait_ms_p50``
    the part of it the host stood waiting for the device's tokens (the
    step record's ``consume_s``: ~0 means the host is the slower side,
    ~the step's length the device), and ``lookahead_share`` the share of
    model dispatches issued while an earlier one's tokens were unread
    (the newest record's running counts; ~1 on the plain path, 0 where
    every dispatch is read at once).  Keys are always exactly
    ``BREAKDOWN_KEYS`` (zeros when disabled/empty)."""
    if enabled is None:
        enabled = bool(trace) and bool(trace.get("enabled"))
    out = {k: 0.0 for k in BREAKDOWN_KEYS}
    out["enabled"] = bool(enabled)
    out["requests"] = 0
    out["steps"] = 0
    out["steps_dropped"] = 0
    if not enabled or not trace:
        return out
    spans = trace.get("spans", {})
    ok = [d for d in spans.values() if d.get("status") == "ok"]
    queue = [d["queue_s"] * 1e3 for d in ok]
    prefill = [d["prefill_s"] * 1e3 for d in ok]
    decode = [d["decode_s"] * 1e3 for d in ok]
    ttft = [(d["first_token"] - d["arrive"]) * 1e3 for d in ok
            if d.get("first_token") is not None]
    phase_delta = [abs((d["queue_s"] + d["prefill_s"] + d["decode_s"])
                       - (d["terminal"] - d["arrive"])) * 1e3
                   for d in ok if d.get("terminal") is not None
                   # a migrated span's attained latency includes the
                   # inter-incarnation replay gap its phase clocks
                   # deliberately exclude — the sum contract holds per
                   # incarnation, so check single-incarnation spans
                   if d.get("incarnations", 1) == 1 and not d["replays"]]
    steps = [r for rep in trace.get("replicas", ())
             for r in rep.get("steps", ())]
    newest = max(steps, key=lambda r: r["t1"])["signals"] if steps else {}
    stamp_delta = [abs(d["first_token"] - stamped_first_s[d["rid"]]) * 1e3
                   for d in ok
                   if stamped_first_s is not None
                   and d.get("first_token") is not None
                   and d["rid"] in stamped_first_s]
    out.update({
        "requests": len(ok),
        "queue_ms_p50": round(_percentile(queue, 0.5), 3),
        "queue_ms_p99": round(_percentile(queue, 0.99), 3),
        "prefill_ms_p50": round(_percentile(prefill, 0.5), 3),
        "prefill_ms_p99": round(_percentile(prefill, 0.99), 3),
        "decode_ms_p50": round(_percentile(decode, 0.5), 3),
        "decode_ms_p99": round(_percentile(decode, 0.99), 3),
        "ttft_ms_p50": round(_percentile(ttft, 0.5), 3),
        "ttft_ms_p99": round(_percentile(ttft, 0.99), 3),
        "phase_sum_vs_attained_max_delta_ms": round(
            max(phase_delta), 3) if phase_delta else 0.0,
        "ttft_vs_stamp_max_delta_ms": round(
            max(stamp_delta), 3) if stamp_delta else 0.0,
        "steps": int(trace.get("steps", 0)),
        "steps_dropped": int(trace.get("steps_dropped", 0)),
        "step_ms_p50": round(_percentile(
            [(r["t1"] - r["t0"]) * 1e3 for r in steps], 0.5), 3),
        "device_wait_ms_p50": round(_percentile(
            [r["consume_s"] * 1e3 for r in steps], 0.5), 3),
        "lookahead_share": round(
            newest.get("lookahead_dispatches", 0)
            / max(1, newest.get("forward_dispatches", 0)), 4),
    })
    return out


def write_faults(writer: MetricsWriter, counters, step: int = 0,
                 prefix: str = "serving/faults/") -> dict:
    """Stream the normalized faults block through a MetricsWriter (one
    scalar per counter, ``serving/faults/<key>``) and return it — the
    emission path for a serve loop with a ``--metrics-dir``-style sink;
    it normalizes through ``faults_block`` so the scalar stream and a
    printed JSON block built from the same counters cannot disagree."""
    block = faults_block(counters)
    writer.scalars({prefix + k: v for k, v in block.items()}, step)
    return block
