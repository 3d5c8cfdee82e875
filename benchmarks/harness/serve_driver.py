"""Drives a serving cell: ``EngineLoop.submit`` / ``EngineLoop.iterate``
over a ``PagedDecodeEngine`` — admission, chunked prefill, decode through
the model's paged forward and its kernels, the LM head.  What belongs to
one model (sizes, the program's model object, weights, flops, cache bytes,
the plain reference) sits in ``models/<program.model>.py``; the window,
the counting, the sample and the check below are every serving cell's.

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

``counts`` in the result line says what a window held (iterations,
dispatches, requests, host time in ``iterate``, iterations that took over
twice the median and the time they lost, the interpreter's collections),
so that two runs can be told apart: equal counts per iteration and other
times is the machine (a pace, or with ``stalls`` a pause), other counts is
the cut of the window.  ``--sub-windows 20,40``
reads the same for the windows of those lengths that start where the
run's own does (``benchmarks/spread.py --sub``).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import check, models, traffic

DRAIN_LIMIT_S = 60.0
SEQ_BUCKET = 256


class ServeCell:
    def __init__(self, cell: dict, devices, seed: int, traced: bool):
        import jax
        import jax.numpy as jnp

        from mpi_tensorflow_tpu.serving import (PagedDecodeEngine,
                                                ServeConfig)

        cfg, mix = cell["config_data"], cell["traffic_data"]
        prog = cfg["program"]
        self.kind = kind = models.lookup(prog["model"])
        if mix["kind"] != "closed_loop":
            raise ValueError(f"serve driver has no traffic kind "
                             f"{mix['kind']!r}")
        self.sz = sz = kind.sizes(cfg)
        self.mix, self.seed = mix, int(seed)
        dt = jnp.dtype(prog["compute_dtype"])
        pdt = jnp.dtype(prog["param_dtype"])
        self.model = kind.build(sz, dt)
        self.make_params = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(pdt), kind.init_params(sz, key)))
        params = self.make_params(jax.random.key(self.seed))
        check.require_weight_tree(self.model, params)
        eng = dict(mix["engine"])
        eng.update(mix.get("serve_overrides", {}))
        self.serve = ServeConfig(trace="on" if traced else "off", **eng)
        self.engine = PagedDecodeEngine(self.model, params, self.serve)
        self.kv_bytes = dt.itemsize
        prefill = self.engine._prefill_fn

        def counted_prefill(*a):
            self.prefill_calls.append(self.now())
            return prefill(*a)
        self.engine._prefill_fn = counted_prefill
        self.gc_pauses: list = []        # (start, seconds, generation)
        gc.callbacks.append(self._on_gc)
        self._new_loop()

    def _on_gc(self, phase: str, info: dict) -> None:
        """Times the interpreter's collections (it changes none)."""
        if phase == "start":
            self._gc_t = self.now()
        else:
            self.gc_pauses.append((self._gc_t, self.now() - self._gc_t,
                                   info["generation"]))

    def _new_loop(self) -> None:
        from mpi_tensorflow_tpu.serving import EngineLoop

        self.loop = EngineLoop(self.engine)
        self.records: dict = {}
        self.next_k = [0] * int(self.mix["clients"])
        self.done = 0
        self._failed_seen = 0
        self.decode_calls: list = []     # (time, rows) per decode dispatch
        self.iter_ends: list = []        # time each iteration returned
        self.prefill_calls: list = []    # time of each prefill dispatch
        self.t0 = time.perf_counter()

    def reseed(self, seed: int) -> None:
        """New weights and an empty engine for ``seed``; the compiled
        programs stay."""
        import jax

        self.seed = int(seed)
        self.engine.params = self.make_params(jax.random.key(self.seed))
        self.engine.reset()
        self._new_loop()

    def free(self) -> None:
        """Drop the engine (pool, weights) before the reference runs."""
        self.loop = self.engine = None
        gc.callbacks.remove(self._on_gc)

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
        self.iter_ends.append(t)
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


def _prefill_wait(tracer, lo, hi) -> list:
    """Submit to the first prefill chunk, per request whose first chunk
    fell inside [lo, hi), from the program's ``EngineTracer`` spans.  In a
    steady loop the chunks that land in a window sample the queue fairly;
    a request still waiting at ``hi`` is charged nothing of what follows
    the close (stopping the profiler there takes many seconds)."""
    out = []
    for sp in tracer.spans.values():
        first = next((t for t, name in sp.events if name == "prefill_chunk"),
                     None)
        if first is not None and lo <= first < hi:
            out.append(first - sp.arrive)
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
        fl += sc.kind.request_flops(sc.sz, P, idx[0], idx[-1], with_prompt)
        ctx = [P + j for j in idx if j > 0]
        if with_prompt:
            ctx += [min(a + chunk, P) for a in range(0, P, chunk)]
        kv += sc.kind.cache_bytes(sc.sz, ctx, sc.kv_bytes)
        gaps += [tm[j] - tm[j - 1] for j in idx if j > 0]
    return {"tokens": tokens, "flops": fl, "paged_bytes": kv, "gaps": gaps}


def window_numbers(sc: ServeCell, spans, lo: float, hi: float) -> tuple:
    """``(work, end-to-end metrics, counts)`` of the window [lo, hi), read
    once the loop has run past ``hi`` and the first tokens are in."""
    work = _window_work(sc, lo, hi)
    mine = [r for r in sc.records.values() if lo <= r["submit"] < hi]
    ttft = [r["times"][0] - r["submit"] for r in mine if r["tokens"]]
    rows = work["decode_rows"] = [n for t, n in sc.decode_calls
                                  if lo <= t < hi]
    work["window_s"] = hi - lo
    iterate = np.asarray(spans.durations("serve_iterate", sc.t0 + lo,
                                         sc.t0 + hi))
    typical = float(np.median(iterate)) if len(iterate) else float("nan")
    stalled = iterate[iterate > 2 * typical]     # a pause, not a pace
    e2e = {
        "serve_tokens_per_s": work["tokens"] / (hi - lo),
        "ttft_p95_ms": 1e3 * _p95(ttft) if ttft else float("nan"),
        "token_gap_p95_ms": (1e3 * _p95(work["gaps"])
                             if work["gaps"] else float("nan"))}
    counts = {
        "seed": sc.seed, "window_s": hi - lo, "tokens": work["tokens"],
        "iterations": sum(1 for t in sc.iter_ends if lo <= t < hi),
        "iterate_span_s": float(iterate.sum()),
        "iterate_p50_ms": 1e3 * typical,
        "iterate_max_ms": 1e3 * float(iterate.max()) if len(iterate)
        else None,
        "stalls": len(stalled),
        "stall_s": float((stalled - typical).sum()),
        "decode_dispatches": len(rows),
        "decode_rows_mean": float(np.mean(rows)) if rows else None,
        "prefill_chunks": sum(1 for t in sc.prefill_calls if lo <= t < hi),
        "gc_pause_s": float(sum(d for t, d, g in sc.gc_pauses
                                if lo <= t < hi)),
        "gc_full_collections": sum(1 for t, d, g in sc.gc_pauses
                                   if lo <= t < hi and g == 2),
        "submitted": len(mine),
        "finished": len(finished_in(sc, lo, hi)), **e2e}
    return work, e2e, counts


def sub_window_end(sc: ServeCell, lo: float, seconds: float) -> float:
    """Where a window of ``seconds`` that opened at ``lo`` would have
    closed: just past the first iteration that returned at or after
    ``lo + seconds``."""
    return float(np.nextafter(
        next(t for t in sc.iter_ends if t - lo >= seconds), np.inf))


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
        clock.phase("ramp, window, wait for first tokens")
        device = clock.describe(devices)

        work, end_to_end, counts = window_numbers(sc, spans, lo, hi)
        for sub in args.sub_windows:
            if sub < seconds:
                counts[f"sub_{sub:g}"] = window_numbers(
                    sc, spans, lo, sub_window_end(sc, lo, sub))[2]
        failed = sum(1 for r in mine if not r["tokens"]
                     or r["status"] not in (None, "ok"))
        finished = finished_in(sc, lo, hi)
        work.update({
            "chips": 1, "compiles_in_window": compiles,
            "requests_finished": len(finished),
            "dispatch_shapes": sorted(sc.engine.dispatch_shapes),
            "kernel": sc.engine.kernel})
        if sc.engine.tracer is not None:
            work["prefill_wait_s"] = _prefill_wait(sc.engine.tracer, lo, hi)
        kind, make_params, seed, t0 = sc.kind, sc.make_params, sc.seed, sc.t0
        sc.free()
        del sc

        numbers = {"served_logit_gap": served_gap_of(
            kind.reference_logits, make_params(jax.random.key(seed)),
            finished, int(mix["check_requests"]), seed)}
        clock.phase("reference")
    return {
        "attempted": len(mine), "failed": failed, "numbers": numbers,
        "device": device, "window": (t0 + lo, t0 + hi),
        "end_to_end": {**end_to_end, "setup_s": setup_s},
        "work": work, "counts": counts,
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


def served_gap_of(reference_logits, params, finished: list, n: int,
                  seed: int, stats: dict = None) -> float:
    """Run the model's reference (``models/<model>.reference_logits``)
    once over each sampled prompt with its served tokens; the widest gap
    of a served token below the reference's best.
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
        logits = np.asarray(reference_logits(
            params, jnp.asarray(toks), jnp.asarray(pos)))[:len(served)]
        widest = np.nanmax([widest, check.served_gap(logits, served)])
        if stats is not None:
            low = np.asarray(reference_logits(
                params, jnp.asarray(toks), jnp.asarray(pos),
                precision=stats["control"]))[:len(served)]
            stats["control_gap"] = float(np.nanmax([
                stats.get("control_gap", float("nan")),
                check.served_gap(logits, low.argmax(-1))]))
            stats["tokens"] = stats.get("tokens", 0) + len(served)
    return float(widest)
