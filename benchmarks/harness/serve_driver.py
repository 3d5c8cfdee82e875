"""Drives a serving cell: ``EngineLoop.submit`` / ``EngineLoop.iterate``
over a ``PagedDecodeEngine`` — admission, chunked prefill, decode through
``CausalLm.forward_paged`` and the paged-attention kernel, the LM head.

Traffic is a closed loop (``traffic.closed_loop_request``): every client
sends its next request when its last one reached a terminal status.  The
loop runs on the same engine from the first request on: set-up warms the
programs and ramps to steady state (until ``warmup_finished`` requests are
done), the window follows without a break, and after it no new request is
sent while those already submitted wait for their first token.

Once the window has closed and the engine is freed, the plain reference
runs a sample of the finished requests (drawn from the seed, the longest
among them) and ``check.served_gap`` reads how far a served token lies
below the reference's best.
"""

from __future__ import annotations

import time

import numpy as np

from . import check, flops, traffic
from ..reference import causal_lm as ref_lm
from ..reference import transformer as ref_tf

DRAIN_LIMIT_S = 60.0
SEQ_BUCKET = 256


class ServeCell:
    def __init__(self, cell: dict, devices, seed: int, traced: bool):
        import jax
        import jax.numpy as jnp

        from mpi_tensorflow_tpu.models import bert, gpt
        from mpi_tensorflow_tpu.serving import (PagedDecodeEngine,
                                                ServeConfig)

        cfg, mix = cell["config_data"], cell["traffic_data"]
        prog = cfg["program"]
        if prog["model"] != "causal_lm":
            raise ValueError(f"serve driver has no model {prog['model']!r}")
        if mix["kind"] != "closed_loop":
            raise ValueError(f"serve driver has no traffic kind "
                             f"{mix['kind']!r}")
        self.sz = ref_tf.sizes(cfg)
        self.mix, self.seed = mix, int(seed)
        dt = jnp.dtype(prog["compute_dtype"])
        pdt = jnp.dtype(prog["param_dtype"])
        bcfg = bert.BertConfig(
            vocab_size=self.sz["vocab"], hidden=self.sz["hidden"],
            layers=self.sz["layers"], heads=self.sz["heads"],
            mlp=self.sz["mlp"], max_positions=self.sz["positions"],
            dropout=0.0, dtype=dt)
        self.model = gpt.CausalLm(bcfg)
        self.make_params = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(pdt), ref_tf.init_params(self.sz, key)))
        params = self.make_params(jax.random.key(self.seed))
        check.require_weight_tree(self.model, params)
        eng = dict(mix["engine"])
        eng.update(mix.get("serve_overrides", {}))
        self.serve = ServeConfig(trace="on" if traced else "off", **eng)
        self.engine = PagedDecodeEngine(self.model, params, self.serve)
        self.kv_bytes = dt.itemsize
        self._new_loop()

    def _new_loop(self) -> None:
        from mpi_tensorflow_tpu.serving import EngineLoop

        self.loop = EngineLoop(self.engine)
        self.records: dict = {}
        self.next_k = [0] * int(self.mix["clients"])
        self.done = 0
        self._failed_seen = 0
        self.decode_calls: list = []     # (time, rows) per decode dispatch
        self.t0 = time.perf_counter()

    def reseed(self, seed: int) -> None:
        """New weights and an empty engine for ``seed``; the compiled
        programs stay."""
        import jax

        self.seed = int(seed)
        self.engine.params = self.make_params(jax.random.key(self.seed))
        self.engine.reset()
        self._new_loop()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def prewarm(self) -> None:
        """Build the decode and prefill programs the mix lists, on null
        tables (nothing real is touched).  The rest of what the ramp
        visits compiles as the ramp visits it."""
        import jax.numpy as jnp

        e, pw = self.engine, self.mix["prewarm"]
        width = self.serve.max_blocks_per_seq
        for chunk in pw["prefill_chunks"]:
            _, e.pools = e._prefill_fn(
                e.params, e.pools, jnp.zeros((1, chunk), jnp.int32),
                jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.zeros((1, width), jnp.int32))
        for slots in pw["decode_slots"]:
            for tables in pw["decode_tables"]:
                _, e.pools = e._decode_fn(
                    e.params, e.pools, jnp.zeros((slots,), jnp.int32),
                    jnp.zeros((slots,), jnp.int32),
                    jnp.zeros((slots, tables), jnp.int32))

    def submit(self, client: int) -> None:
        from mpi_tensorflow_tpu.serving import Request

        k = self.next_k[client]
        self.next_k[client] += 1
        prompt, olen = traffic.closed_loop_request(
            self.mix, self.sz["vocab"], self.seed, client, k)
        rid = len(self.records)
        t = self.now()
        rec = {"client": client, "k": k, "prompt": prompt, "olen": olen,
               "submit": t, "tokens": [], "times": [], "status": None,
               "end": None}
        self.records[rid] = rec
        rej = self.loop.submit(Request(id=rid, prompt=prompt,
                                       max_new_tokens=olen, arrival=t))
        if rej is not None:
            rec["status"], rec["end"] = rej.status, t

    def iterate(self, spans, resubmit: bool) -> None:
        """One engine iteration and its accounting; a client whose request
        ended sends its next one when ``resubmit``."""
        sched = self.engine.sched
        with spans.span("serve_iterate"):
            emitted = self.loop.iterate(self.now(), time.perf_counter,
                                        self.t0)
        t = self.now()
        ended = []
        n_first = 0
        for rid, tok in emitted:
            rec = self.records[rid]
            n_first += not rec["tokens"]
            rec["tokens"].append(int(tok))
            rec["times"].append(t)
            if rec["status"] is None and rid in sched.statuses:
                ended.append(rid)
        while self._failed_seen < len(sched.failed):
            rid = sched.failed[self._failed_seen].id
            self._failed_seen += 1
            if self.records[rid]["status"] is None:
                ended.append(rid)
        if len(emitted) > n_first:
            # rows of this iteration's decode dispatch
            self.decode_calls.append((t, len(emitted) - n_first))
        for rid in ended:
            rec = self.records[rid]
            rec["status"], rec["end"] = sched.statuses[rid], t
            self.done += 1
            if resubmit:
                self.submit(rec["client"])
        if not emitted and not self.engine._progressed:
            time.sleep(1e-3)


def _p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def _prefill_wait(tracer, records, lo, hi) -> list:
    """Submit to the first prefill chunk, per request submitted in the
    window, from the program's ``EngineTracer`` spans."""
    out = []
    for rid, sp in tracer.spans.items():
        rec = records.get(rid)
        if rec is None or not (lo <= rec["submit"] < hi):
            continue
        first = [t for t, name in sp.events if name == "prefill_chunk"]
        if first:
            out.append(first[0] - sp.arrive)
    return out


def _window_work(sc: ServeCell, lo: float, hi: float) -> dict:
    """Tokens, model flops and paged-attention bytes of the work whose
    tokens were emitted inside [lo, hi)."""
    chunk = int(sc.serve.prefill_chunk)
    tokens = 0
    fl = 0.0
    kv = 0.0
    gaps = []
    for rec in sc.records.values():
        tm = rec["times"]
        idx = [j for j, t in enumerate(tm) if lo <= t < hi]
        if not idx:
            continue
        tokens += len(idx)
        P = len(rec["prompt"])
        with_prompt = idx[0] == 0
        fl += flops.serve_request_flops(sc.sz, P, idx[0], idx[-1],
                                        with_prompt)
        ctx = [P + j for j in idx if j > 0]
        if with_prompt:
            ctx += [min(a + chunk, P) for a in range(0, P, chunk)]
        kv += flops.paged_attention_bytes(sc.sz, ctx, sc.kv_bytes)
        gaps += [tm[j] - tm[j - 1] for j in idx if j > 0]
    return {"tokens": tokens, "flops": fl, "paged_bytes": kv, "gaps": gaps}


def closed_loop(sc: ServeCell, spans, seconds: float, tracer, compiles,
                setup_done=None) -> tuple:
    """Ramp to steady state, the window, then the wait for first tokens.
    Returns ``(lo, hi, requests submitted in the window, programs built in
    the window, what setup_done() read when the window opened)``."""
    mix = sc.mix
    for c in range(int(mix["clients"])):
        sc.submit(c)
    while sc.done < int(mix["warmup_finished"]):
        sc.iterate(spans, resubmit=True)
    setup_s = setup_done() if setup_done is not None else None
    c0 = compiles.mark()[0]
    with tracer():
        lo = sc.now()
        with spans.span("window"):
            while sc.now() - lo < seconds:
                sc.iterate(spans, resubmit=True)
        hi = sc.now()
    built = compiles.mark()[0] - c0
    # no new requests; those submitted in the window wait for their
    # first token (an answer that comes late is late, not wrong)
    mine = [r for r in sc.records.values() if lo <= r["submit"] < hi]
    t_drain = sc.now()
    while any(not r["tokens"] and r["status"] is None for r in mine) \
            and sc.now() - t_drain < DRAIN_LIMIT_S:
        sc.iterate(spans, resubmit=False)
    return lo, hi, mine, built, setup_s


def finished_in(sc: ServeCell, lo: float, hi: float) -> list:
    return [r for r in sc.records.values()
            if r["status"] == "ok" and lo <= r["end"] < hi]


def run(cell: dict, devices, args, clock) -> dict:
    import jax

    spans = clock.spans
    mix = cell["traffic_data"]
    with jax.default_device(devices[0]):
        sc = ServeCell(cell, devices, args.seed, bool(args.trace))
        clock.phase("weights, engine, pool")
        if sc.engine.kernel != "pallas" and not args.rehearse_cpu:
            raise RuntimeError(f"kernel resolved to {sc.engine.kernel!r}, "
                               f"the cell measures 'pallas'")
        sc.prewarm()
        clock.phase("prewarm")

        def setup_done():
            return time.perf_counter() - clock.t_start

        seconds = float(args.seconds)
        if args.trace:
            seconds = min(seconds, float(mix["trace_seconds"]))
        lo, hi, mine, compiles, setup_s = closed_loop(
            sc, spans, seconds, clock.tracer, clock.compiles, setup_done)
        window_s = hi - lo
        clock.phase("ramp, window, wait for first tokens")
        device = clock.describe(devices)

        work = _window_work(sc, lo, hi)
        ttft = [r["times"][0] - r["submit"] for r in mine if r["tokens"]]
        failed = sum(1 for r in mine if not r["tokens"]
                     or r["status"] not in (None, "ok"))
        counters = {
            "compiles_in_window": compiles,
            "decode_rows": [n for t, n in sc.decode_calls if lo <= t < hi],
            "dispatch_shapes": sorted(sc.engine.dispatch_shapes),
            "kernel": sc.engine.kernel,
        }
        if sc.engine.tracer is not None:
            counters["prefill_wait_s"] = _prefill_wait(
                sc.engine.tracer, sc.records, lo, hi)
        finished = finished_in(sc, lo, hi)
        make_params, seed, t0 = sc.make_params, sc.seed, sc.t0
        # free the engine (pool, weights) before the reference runs
        sc.loop = sc.engine = None
        del sc

        numbers = {"served_logit_gap": served_gap_of(
            make_params(jax.random.key(seed)), finished,
            int(mix["check_requests"]), seed)}
        clock.phase("reference")
    return {
        "attempted": len(mine), "failed": failed, "numbers": numbers,
        "device": device, "window": (t0 + lo, t0 + hi),
        "end_to_end": {
            "serve_tokens_per_s": work["tokens"] / window_s,
            "ttft_p95_ms": 1e3 * _p95(ttft) if ttft else float("nan"),
            "token_gap_p95_ms": (1e3 * _p95(work["gaps"])
                                 if work["gaps"] else float("nan")),
            "setup_s": setup_s},
        "work": {"tokens": work["tokens"], "flops": work["flops"],
                 "paged_bytes": work["paged_bytes"], "window_s": window_s,
                 "requests_finished": len(finished), "chips": 1,
                 "compiles_in_window": compiles, **counters},
    }


def sample(finished: list, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, and the longest."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    order = sorted(range(len(finished)),
                   key=lambda i: (finished[i]["client"], finished[i]["k"]))
    pick = [order[i] for i in rng.choice(
        len(order), size=min(n, len(order)), replace=False)]
    longest = max(order, key=lambda i: len(finished[i]["prompt"])
                  + len(finished[i]["tokens"]))
    if longest not in pick:
        pick.append(longest)
    return [finished[i] for i in pick]


def served_gap_of(params, finished: list, n: int, seed: int,
                  stats: dict = None) -> float:
    """Run the reference once over each sampled prompt with its served
    tokens; the widest gap of a served token below the reference's best.
    With ``stats`` also the control's reading: at the same positions, the
    gap of the token that ``stats['control']`` precision puts first."""
    import jax.numpy as jnp

    widest = float("nan")
    for rec in sample(finished, n, seed):
        P, served = len(rec["prompt"]), rec["tokens"]
        seq = np.asarray(rec["prompt"] + served[:-1], np.int32)
        S = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        N = -(-len(served) // 128) * 128
        toks = np.zeros((S,), np.int32)
        toks[:len(seq)] = seq
        pos = np.full((N,), P - 1, np.int32)
        pos[:len(served)] = np.arange(P - 1, P - 1 + len(served))
        logits = np.asarray(ref_lm.next_token_logits(
            params, jnp.asarray(toks), jnp.asarray(pos)))[:len(served)]
        widest = np.nanmax([widest, check.served_gap(logits, served)])
        if stats is not None:
            low = np.asarray(ref_lm.next_token_logits(
                params, jnp.asarray(toks), jnp.asarray(pos),
                precision=stats["control"]))[:len(served)]
            stats["control_gap"] = float(np.nanmax([
                stats.get("control_gap", float("nan")),
                check.served_gap(logits, low.argmax(-1))]))
            stats["tokens"] = stats.get("tokens", 0) + len(served)
    return float(widest)
