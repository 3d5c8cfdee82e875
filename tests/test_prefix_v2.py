"""Prefix sharing v2: generated-block caching, partial tail-block
sharing, and prefix-aware fleet routing.

Tier-1 anchors the ISSUE-14 acceptance names:
- generated-block insertion raises multi-turn hit rate with token
  identity pinned against prefix-gen-off AND generate();
- partial tail-block sharing charges admission only for the true
  unique suffix, through a pre-warmed one-compile copy dispatch;
- the router's prefix hint wins aggregate hit rate over least-load
  alone on a shared-prefix fleet trace, token-identically;
- the exact-repeat regression: a fully cached prompt (generated
  blocks included) still honors the ``len(prompt)-1`` match cap;
- a randomized interleaving of admission / generated-insert /
  partial-copy / eviction / release stays refcount-exact against a
  model derived from the trie + live-slot structures.
"""

import dataclasses

import numpy as np
import pytest

from _jitted import generate_ref as _generate_ref
from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (BlockAllocator, PagedDecodeEngine,
                                        PrefixCache, Request, Scheduler,
                                        ServeConfig)
from mpi_tensorflow_tpu.serving.paged_cache import init_pools, \
    partial_copy_block

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")


def _seed_trie(pc, stream):
    """Insert ``stream``'s full blocks the way a donor sequence does:
    alloc, insert (trie takes its own share refs), release."""
    a = pc.allocator
    from mpi_tensorflow_tpu.serving.paged_cache import blocks_for
    ids = a.alloc(len(stream) // pc.block_size)
    pc.insert(stream, ids)
    a.release(ids)
    del blocks_for


# ---------------------------------------------------------- trie units

@pytest.mark.quick
class TestMatchPartial:
    def _mk(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        _seed_trie(pc, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12])
        return a, pc

    def test_best_sibling_rows_and_pin(self):
        a, pc = self._mk()
        p = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 99, 100]
        cached, toks = pc.match_and_share(p)
        assert (len(cached), toks) == (2, 8)
        res = pc.match_partial(p, len(cached))
        assert res is not None
        block, rows = res
        # tail [9,10,99,100] shares 2 rows with child key (9,10,11,12)
        assert rows == 2
        # the returned block is PINNED: trie ref + the partial pin
        assert a.refcount(block) == 2
        a.release([block])
        a.release(cached)
        a.check()

    def test_rows_capped_at_len_tail_minus_one(self):
        a, pc = self._mk()
        # tail [9,10,11]: 3 shared rows available, but at least one
        # tail token must stay uncached (the match_and_share rule at
        # row granularity), so limit = len(tail)-1 = 2
        p = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]
        cached, _ = pc.match_and_share(p)
        block, rows = pc.match_partial(p, len(cached))
        assert rows == 2
        a.release([block])
        a.release(cached)

    def test_no_shared_row_returns_none(self):
        a, pc = self._mk()
        p = [1, 2, 3, 4, 5, 6, 7, 8, 99, 100]
        cached, _ = pc.match_and_share(p)
        assert pc.match_partial(p, len(cached)) is None
        # single-token tail: limit 0, nothing to copy
        p1 = [1, 2, 3, 4, 5, 6, 7, 8, 9]
        cached1, _ = pc.match_and_share(p1)
        assert pc.match_partial(p1, len(cached1)) is None
        a.release(cached)
        a.release(cached1)
        a.check()

    def test_rows_always_below_block_size(self):
        # a full-key tail match is impossible here by construction: the
        # main walk would have taken that child as a full-block hit
        a, pc = self._mk()
        p = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
        cached, toks = pc.match_and_share(p)
        assert toks == 12                       # all three blocks hit
        assert pc.match_partial(p, len(cached)) is None
        a.release(cached)

    def test_root_hook_fires_on_root_edge_only(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        events = []
        pc.root_hook = lambda key, present: events.append((key, present))
        _seed_trie(pc, [1, 2, 3, 4, 5, 6, 7, 8])
        # one insert event for the ROOT child only — the depth-2 node
        # is not a routing key
        assert events == [((1, 2, 3, 4), True)]
        evicted = pc.evict(2)
        assert evicted == 2
        assert events[-1] == ((1, 2, 3, 4), False)
        a.check()


# ------------------------------------------------- partial-copy device op

class TestPartialCopyOp:
    @pytest.mark.parametrize("kv_dtype", ["fp32", "int8", "int4"])
    def test_copies_leading_rows_only(self, kv_dtype):
        import jax.numpy as jnp

        pools = init_pools(TINY, num_blocks=6, block_size=4,
                           kv_dtype=kv_dtype)
        # paint src block 2 with ones, dst block 5 with twos
        painted = []
        for p in pools:
            painted.append({k: v.at[2].set(1).at[5].set(2)
                            for k, v in p.items()})
        out = partial_copy_block(painted, 2, 5, 3)
        for p in out:
            for k, v in p.items():
                arr = np.asarray(v, np.float32)
                assert (arr[5, :3] == 1).all(), k      # copied rows
                assert (arr[5, 3:] == 2).all(), k      # untouched tail
                assert (arr[2] == 1).all(), k          # src intact
                assert (arr[1] == 0).all(), k          # bystander


# ------------------------------------------------ scheduler accounting

@pytest.mark.quick
class TestSchedulerPartialAdmission:
    def _mk(self, blocks=24, slots=3, bs=4):
        a = BlockAllocator(blocks)
        pc = PrefixCache(a, bs)
        s = Scheduler(a, slots, bs, 8, prefix_cache=pc, prefix_gen=True)
        return a, pc, s

    def test_admission_charges_only_unique_suffix(self):
        a, pc, s = self._mk()
        stream = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
        _seed_trie(pc, stream)
        used0 = a.num_used
        p = stream[:10] + [99, 100, 101]        # 13 tokens
        s.submit(Request(0, p, 4))
        slot = s.admit()[0]
        seq = s.slots[slot]
        # 2 full-block hits + 2 partial rows: prefill starts at 10
        assert seq.prefix_cached == 10 and seq.prefilled == 10
        assert s.counters["prefix_hit_tokens"] == 8
        assert s.counters["prefix_partial_copy_tokens"] == 2
        assert seq.partial_src is not None
        assert seq.partial_dst == seq.block_ids[2]
        assert seq.partial_rows == 2
        # pool charge: only the unique suffix's fresh blocks
        # (blocks_for(14) - 2 cached = 2 fresh)
        assert a.num_used - used0 == 2
        s._release_partial(seq)
        s.fail_live(slot, "rejected")
        s.check_quiescent()

    def test_eviction_releases_partial_pin(self):
        a, pc, s = self._mk()
        stream = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
        _seed_trie(pc, stream)
        s.submit(Request(0, stream[:10] + [99, 100], 4))
        slot = s.admit()[0]
        seq = s.slots[slot]
        pin = seq.partial_src
        assert pin is not None and a.refcount(pin) == 2
        s.fail_live(slot, "rejected")          # pin must die with seq
        assert seq.partial_src is None
        assert a.refcount(pin) == 1            # the trie's own ref
        s.check_quiescent()

    def test_finish_gen_inserts_before_release(self):
        a, pc, s = self._mk()
        p = [1, 2, 3, 4, 5, 6, 7]
        s.submit(Request(0, p, 3))
        slot = s.admit()[0]
        s.slots[slot].prefilled = len(p)
        for t in (21, 22, 23):
            assert s.ensure_block(slot)
            s.record_token(slot, t)
        # stream [1..7,21,22,23][:9] = 2 full blocks adopted by the trie
        assert s.counters["prefix_gen_inserted_blocks"] == 2
        assert pc.num_blocks == 2
        cached, toks = pc.match_and_share(p + [21, 22, 23, 9])
        assert toks == 8                       # generated rows now hit
        a.release(cached)
        s.check_quiescent()


# --------------------------------------------------- engine end-to-end

class TestGenInsertEngine:
    def _engine(self, **kw):
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = ServeConfig(**{**dict(num_blocks=64, block_size=4,
                                      max_slots=4, max_seq_len=64,
                                      prefill_chunk=8,
                                      prefix_cache="on"), **kw})
        return model, params, PagedDecodeEngine(model, params, serve)

    def test_multi_turn_gen_caching_token_identical(self):
        model, params, eng_on = self._engine(prefix_gen="on")
        _, _, eng_off = self._engine(prefix_gen="off")
        rng = np.random.default_rng(2)
        prompts = [list(map(int, rng.integers(0, TINY.vocab_size, 9)))
                   for _ in range(3)]
        t1 = lambda: [Request(i, p, 8, arrival=0.0)
                      for i, p in enumerate(prompts)]
        r1on, r1off = eng_on.run(t1()), eng_off.run(t1())
        assert r1on["outputs"] == r1off["outputs"]
        for i, p in enumerate(prompts):
            assert r1on["outputs"][i] == _generate_ref(model, params, p, 8)
        assert r1on["prefix"]["gen_inserted_blocks"] > 0
        assert r1off["prefix"]["gen_inserted_blocks"] == 0
        # follow-up turn: prior prompt + answer + fresh suffix
        prompts2 = [p + r1on["outputs"][i] + [7, 8, 9]
                    for i, p in enumerate(prompts)]
        t2 = lambda: [Request(10 + i, p, 8, arrival=0.0)
                      for i, p in enumerate(prompts2)]
        r2on, r2off = eng_on.run(t2()), eng_off.run(t2())
        assert r2on["outputs"] == r2off["outputs"]
        for i, p in enumerate(prompts2):
            assert (r2on["outputs"][10 + i]
                    == _generate_ref(model, params, p, 8))
        # the acceptance inequality: generated blocks make turn 2 hit
        assert (r2on["prefix"]["hit_rate"]
                > r2off["prefix"]["hit_rate"])
        assert (r2on["prefix"]["prefill_tokens_saved"]
                > r2off["prefix"]["prefill_tokens_saved"])
        # one-compile partial dispatch: pre-warm only, no steady-state
        assert eng_on.compile_counts()["partial"] == 1
        assert eng_off.compile_counts()["partial"] == 0

    def test_partial_tail_block_sharing(self):
        model, params, eng = self._engine(prefix_gen="on")
        _, _, ref = self._engine(prefix_gen="off")
        base = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]
        # A finishes first (its generated tail block enters the trie),
        # THEN B arrives sharing base[:10] — a mid-block divergence
        a_req = lambda: [Request(0, base, 6, arrival=0.0)]
        b_req = lambda: [Request(1, base[:10] + [70, 71, 72], 6,
                                 arrival=0.0)]
        eng.run(a_req())
        out = eng.run(b_req())
        ref.run(a_req())
        out_ref = ref.run(b_req())
        assert out["outputs"] == out_ref["outputs"]
        # B's admission: 2 full-block hits + 2 rows copied from A's
        # cached tail block — the unique suffix is all it pays for
        assert out["prefix"]["partial_copy_tokens"] == 2
        assert out["prefix"]["hit_tokens"] >= 8
        assert eng.compile_counts()["partial"] == 1

    def test_exact_repeat_respects_match_cap(self):
        """A prompt whose EVERY block is cached (generated ones
        included) must still re-admit: the len(prompt)-1 cap leaves
        the final position to recompute, and the first output token
        must come out right."""
        model, params, eng = self._engine(prefix_gen="on")
        p = list(map(int, np.random.default_rng(3).integers(
            0, TINY.vocab_size, 9)))
        out1 = eng.run([Request(0, p, 6, arrival=0.0)])
        snap = dict(eng.compile_counts())
        out2 = eng.run([Request(1, p, 6, arrival=0.0)])
        assert out2["outputs"][1] == out1["outputs"][0]
        assert out2["outputs"][1] == _generate_ref(model, params, p, 6)
        assert out2["prefix"]["hit_tokens"] > 0
        assert dict(eng.compile_counts()) == snap   # steady state
        eng.sched.check_quiescent()


# ------------------------------------------------------ property test

@pytest.mark.quick
class TestPrefixV2RefcountProperty:
    def _model_counts(self, pc, sched, num_blocks):
        """Expected per-block refcount derived from the structures the
        allocator's counts must mirror: one per trie node, one per
        live-slot table entry, one per outstanding partial pin."""
        want = [0] * num_blocks
        stack = list(pc._root.children.values())
        while stack:
            n = stack.pop()
            want[n.block] += 1
            stack.extend(n.children.values())
        for seq in sched.slots:
            if seq is None:
                continue
            for b in seq.block_ids:
                want[b] += 1
            if seq.partial_src is not None:
                want[seq.partial_src] += 1
        return want

    def test_interleaved_ops_stay_refcount_exact(self):
        rng = np.random.default_rng(14)
        num_blocks, bs = 24, 4
        a = BlockAllocator(num_blocks)
        pc = PrefixCache(a, bs)
        s = Scheduler(a, 3, bs, 8, prefix_cache=pc, prefix_gen=True)
        stems = [list(map(int, rng.integers(0, 50, 12)))
                 for _ in range(3)]
        next_id = 0
        for _ in range(400):
            op = rng.integers(0, 5)
            if op == 0 and len(s.waiting) < 4:     # submit + admit
                stem = stems[rng.integers(0, len(stems))]
                k = int(rng.integers(0, 13))
                p = stem[:k] + list(map(int, rng.integers(
                    0, 50, int(rng.integers(1, 5)))))
                s.submit(Request(next_id, p, int(rng.integers(1, 4))))
                next_id += 1
                for slot in s.admit():
                    seq = s.slots[slot]
                    # simulate the engine's prefill completion: the
                    # prompt's full blocks register in the trie
                    seq.prefilled = len(seq.request.prompt)
                    pc.insert(seq.request.prompt, seq.block_ids)
            elif op == 1:                           # decode one token
                live = [i for i, q in enumerate(s.slots)
                        if q is not None
                        and q.prefilled >= len(q.request.prompt)]
                if live:
                    slot = live[rng.integers(0, len(live))]
                    if s.ensure_block(slot):
                        s.record_token(slot, int(rng.integers(0, 50)))
                    else:
                        s.fail_live(slot, "rejected")
            elif op == 2:                           # copy landed
                pinned = [q for q in s.slots
                          if q is not None and q.partial_src is not None]
                if pinned:
                    s._release_partial(
                        pinned[rng.integers(0, len(pinned))])
            elif op == 3:                           # trie pressure
                pc.evict(int(rng.integers(1, 3)))
            else:                                   # replica fault path
                live = [i for i, q in enumerate(s.slots)
                        if q is not None]
                if live:
                    s.fail_live(live[rng.integers(0, len(live))],
                                "rejected")
            got = [a.refcount(b) for b in range(num_blocks)]
            want = self._model_counts(pc, s, num_blocks)
            want[0] = got[0]                        # reserved null block
            assert got == want
            a.check()
            pc.check()
        for i, q in enumerate(s.slots):
            if q is not None:
                s.fail_live(i, "rejected")
        s.waiting.clear()
        s.check_quiescent()
        a.check()


# ------------------------------------------------------- fleet routing

class _VClock:
    """Deterministic virtual clock for router runs: service time is
    measured in time_fn calls, so arrival spacing in virtual seconds
    pins the idle-at-each-routing-decision regime on any machine."""

    def __init__(self, dt=0.02):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


class TestPrefixRouting:
    def test_hint_beats_least_load_token_identically(self):
        import jax

        from mpi_tensorflow_tpu.serving.router import ReplicaRouter

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = ServeConfig(num_blocks=64, block_size=4, max_slots=4,
                            max_seq_len=64, prefill_chunk=8,
                            prefix_cache="on", prefix_gen="on",
                            prefix_route="on")
        rng = np.random.default_rng(1)
        shared = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        rows = [(i, shared + list(map(int, rng.integers(
            0, TINY.vocab_size, 4))), 2, 1.0 * i) for i in range(6)]
        fresh = lambda: [Request(i, p, n, arrival=t)
                         for i, p, n, t in rows]
        engines = [PagedDecodeEngine(model, params, serve)
                   for _ in range(2)]
        warm = ReplicaRouter(engines, prefix_route=False)
        warm.run(fresh(), time_fn=_VClock(), parallel=False)
        snap = [dict(e.compile_counts()) for e in engines]

        r_on = ReplicaRouter(engines, prefix_route=True)
        r_on.reset()
        ron = r_on.run(fresh(), time_fn=_VClock(), parallel=False)
        st = r_on.stats()
        r_off = ReplicaRouter(engines, prefix_route=False)
        r_off.reset()
        roff = r_off.run(fresh(), time_fn=_VClock(), parallel=False)

        assert ron["outputs"] == roff["outputs"]        # token identity
        assert ron["prefix"]["router_prefix_hits"] > 0
        assert roff["prefix"]["router_prefix_hits"] == 0
        assert (ron["prefix"]["hit_rate"]
                > roff["prefix"]["hit_rate"])           # the hint's win
        assert [dict(e.compile_counts()) for e in engines] == snap
        # stats() surfaces the per-replica trie digests
        assert st["prefix_route"] is True
        assert st["router_prefix_hits"] == \
            ron["prefix"]["router_prefix_hits"]
        assert len(st["replica_tries"]) == 2
        on_replicas = [t for t in st["replica_tries"] if t["enabled"]]
        assert sum(t["inserted"] for t in on_replicas) > 0
        assert all(0.0 <= t["occupancy"] <= 1.0 for t in on_replicas)

    def test_hint_never_overrides_session_affinity(self):
        """A sessioned request follows its sticky replica even when
        another replica owns its prefix."""
        from mpi_tensorflow_tpu.serving.router import ReplicaRouter

        class _Eng:                       # routing-only stub fleet
            def __init__(self):
                self.serve = ServeConfig(num_blocks=16, block_size=4,
                                         max_slots=2, max_seq_len=32,
                                         prefix_cache="on",
                                         prefix_gen="on",
                                         prefix_route="on")
                self.prefix_cache = None
                self.sched = None

        from mpi_tensorflow_tpu.serving.router import HEALTHY

        r = ReplicaRouter.__new__(ReplicaRouter)
        r.engines = [_Eng(), _Eng()]
        import collections
        import threading

        r._lock = threading.RLock()
        r._sticky = collections.OrderedDict()
        r._prefix_owner = {}
        r._prefix_route = True
        r.fleet_counters = collections.Counter()
        r.placements = {}
        r._session_live = collections.Counter()
        r._routed = [0, 0]
        r.health = [type("H", (), {"state": HEALTHY})()
                    for _ in r.engines]
        r.routable = lambda: [0, 1]
        r.load_score = lambda i, d=0: 0.0
        prompt = [1, 2, 3, 4, 5]
        r._sticky["tenant"] = 1
        r._prefix_owner[(1, 2, 3, 4)] = 0
        got = r.route(Request(0, prompt, 2, session="tenant"))
        assert got == 1                   # sticky wins over the hint
        got2 = r.route(Request(1, prompt, 2))
        assert got2 == 0                  # sessionless follows the hint
        assert r.fleet_counters["router_prefix_hits"] == 1
