"""Int8 KV-cache quantization: composition pins.

The quantized pool is engine STATE, not a code path of its own — so the
tier-1 pin here is that every serving subsystem composes with it
unchanged: the radix prefix trie (same prompt => same quantized bytes,
CoW copies codes AND scale siblings), eviction + restart-from-scratch
under pool pressure, speculative draft/verify/rollback, and SIGKILL
journal replay (replayed prefills re-quantize to the SAME codes a
straight run writes, and scales equal to matmul rounding, because the
per-(block, head, slot) row scales make quantization write-granularity
independent).

Token identity in this file is WITHIN int8 mode (int8-with-feature vs
int8-without-feature): greedy decode over the same quantized pool is
deterministic, so every composition must be exact.  Int8 vs fp32 is a
token-match-RATE gate and lives in tests/test_paged_kernel.py and the
int8-vs-fp32 comparison of this file.

Host-RAM block tiering (--kv-tier host) rides the same
determinism contract: a demoted block's host bytes equal what a fresh
prefill of its token path would write, so promotion is byte-exact
re-admission — pinned below for both quantized rungs, under CoW, and
through SIGKILL journal replay.
"""

import dataclasses

import numpy as np
import pytest

from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, ReplayJournal,
                                        Request, ServeConfig,
                                        run_with_replay)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
ROPE = dataclasses.replace(TINY, pos_kind="rope")

SERVE = ServeConfig(num_blocks=48, block_size=4, max_slots=3,
                    max_seq_len=32, prefill_chunk=8, kv_dtype="int8")


@pytest.fixture(scope="module")
def model_params():
    import jax

    model = gpt.CausalLm(TINY)
    return model, model.init(jax.random.key(1))


def _trace(n=5, seed=2, lo=3, hi=13, budget_hi=9):
    rng = np.random.default_rng(seed)
    prompts = [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
               for s in rng.integers(lo, hi + 1, n)]
    budgets = [int(b) for b in rng.integers(2, budget_hi, n)]
    return [Request(i, p, b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]


def _shared_trace(n=6, seed=3, prefix=12, hi=6, budget_hi=7, vocab=None):
    """More requests than max_slots behind one shared system prompt (an
    exact block multiple), so later admissions hit the trie after the
    earlier prompts register — the shape that actually exercises
    sharing."""
    vocab = vocab or TINY.vocab_size
    rng = np.random.default_rng(seed)
    shared = list(map(int, rng.integers(0, vocab, prefix)))
    prompts = [shared + list(map(int, rng.integers(0, vocab, int(s))))
               for s in rng.integers(1, hi + 1, n)]
    budgets = [int(b) for b in rng.integers(2, budget_hi, n)]
    return [Request(i, p, b, arrival=0.0)
            for i, (p, b) in enumerate(zip(prompts, budgets))]


def _pool_bytes(engine):
    """Every pool leaf (codes AND scales) of every layer, minus the
    null block: dead decode lanes scatter garbage into block 0 and the
    number of decode dispatches legitimately differs across replay
    shapes, so block 0 is the one block with no byte contract."""
    return [{key: np.asarray(leaf)[1:] for key, leaf in p.items()}
            for p in engine.pools]


def _assert_pools_equal(a, b, scale_ulps=0):
    """Codes always byte-equal; the fp32 row scales byte-equal too unless
    ``scale_ulps`` allows them that many units in the last place."""
    for pa, pb in zip(a, b):
        assert pa.keys() == pb.keys()
        for key in pa:
            if scale_ulps and key.endswith("_scale"):
                np.testing.assert_array_max_ulp(pa[key], pb[key],
                                                maxulp=scale_ulps)
            else:
                np.testing.assert_array_equal(pa[key], pb[key])


# ------------------------------------------------------- determinism

class TestInt8PoolDeterminism:
    def test_same_trace_same_pool_bytes(self, model_params):
        """Two fresh int8 engines over the same trace finish with
        byte-identical pools — codes and scale siblings both.  The
        ground truth every replay/prefix pin below builds on."""
        model, params = model_params
        a = PagedDecodeEngine(model, params, SERVE)
        b = PagedDecodeEngine(model, params, SERVE)
        ra = a.run(_trace())
        rb = b.run(_trace())
        assert ra["outputs"] == rb["outputs"]
        _assert_pools_equal(_pool_bytes(a), _pool_bytes(b))


# ---------------------------------------------------- prefix trie/CoW

class TestInt8PrefixCache:
    def test_shared_prefix_token_identical_with_hits(self, model_params):
        """Prefix cache on over an int8 pool: trie hits land (shared
        QUANTIZED blocks — same prompt quantizes to the same bytes, so
        reuse is exact), outputs equal the cache-off int8 engine's, and
        the allocator/trie refcounts reconcile."""
        model, params = model_params
        off = PagedDecodeEngine(model, params, SERVE)
        on = PagedDecodeEngine(
            model, params, dataclasses.replace(SERVE, prefix_cache="on"))
        want = off.run(_shared_trace())
        got = on.run(_shared_trace())
        assert got["outputs"] == want["outputs"]
        assert got["prefix"]["hit_tokens"] > 0
        assert got["prefix"]["shared_blocks"] > 0
        on.allocator.check()
        assert on.allocator.num_used == on.prefix_cache.num_blocks

    def test_cow_copies_codes_and_scales(self, model_params):
        """A decode write landing inside a shared (refcount > 1) block
        triggers copy-on-write; the copy must carry the scale siblings
        with the codes or the copied rows dequantize wrong.  Identical
        exact-block-multiple prompts at max_slots=1: each later request
        fully shares the earlier one's blocks — including the final
        block its first generated token must write into — forcing the
        CoW path (the idiom tests/test_speculative.py pins on the fp32
        pool)."""
        model, params = model_params
        serve = dataclasses.replace(SERVE, max_slots=1,
                                    prefix_cache="on")
        on = PagedDecodeEngine(model, params, serve)
        off = PagedDecodeEngine(
            model, params, dataclasses.replace(serve, prefix_cache="off"))
        rng = np.random.default_rng(21)
        prompt = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        assert len(prompt) % serve.block_size == 0
        budgets = [6, 4, 2]
        reqs = lambda: [Request(i, list(prompt), n,       # noqa: E731
                                arrival=0.0)
                        for i, n in enumerate(budgets)]
        want = off.run(reqs())
        got = on.run(reqs())
        assert got["outputs"] == want["outputs"]
        assert got["prefix"]["cow_copies"] >= 1, \
            "the shared-final-block write was meant to trigger CoW"
        # greedy determinism: identical prompts stream identically, so
        # the CoW copies (codes + scales) reproduced the donor exactly
        for i, n in enumerate(budgets):
            assert got["outputs"][i] == got["outputs"][0][:n]
        on.allocator.check()


# ------------------------------------------------- eviction pressure

class TestInt8Eviction:
    def test_eviction_restart_token_identical(self, model_params):
        """Pool pressure forces an eviction + restart-from-scratch
        replay through the quantized pool: the re-quantized restart
        must continue the exact stream (per-row scales make the replay
        writes byte-identical to the originals)."""
        model, params = model_params
        tight = ServeConfig(num_blocks=9, block_size=2, max_slots=2,
                            max_seq_len=12, prefill_chunk=2,
                            kv_dtype="int8")
        roomy = ServeConfig(num_blocks=40, block_size=2, max_slots=2,
                            max_seq_len=12, prefill_chunk=2,
                            kv_dtype="int8")
        rng = np.random.default_rng(8)
        pa = list(map(int, rng.integers(0, TINY.vocab_size, 2)))
        pb = list(map(int, rng.integers(0, TINY.vocab_size, 11)))
        reqs = lambda: [Request(0, pa, 10, arrival=0.0),     # noqa: E731
                        Request(1, pb, 1, arrival=0.0)]
        engine = PagedDecodeEngine(model, params, tight)
        res = engine.run(reqs())
        assert engine.sched.evictions >= 1
        want = PagedDecodeEngine(model, params, roomy).run(reqs())
        assert res["outputs"] == want["outputs"]
        engine.sched.check_quiescent()


# -------------------------------------------- speculative rollback

class TestInt8Speculative:
    def test_ngram_accepts_and_stays_identical(self):
        """Speculation over the int8 pool on the recurrent (rope)
        stream: drafts land (accepted_tokens > 0, so the verify write +
        rollback machinery actually runs against quantized blocks) and
        outputs are exactly the speculation-off int8 engine's."""
        import jax

        model = gpt.CausalLm(ROPE)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, max_seq_len=64, num_blocks=96)
        off = PagedDecodeEngine(model, params, serve)
        spec = PagedDecodeEngine(model, params, dataclasses.replace(
            serve, speculative="ngram", draft_k=4))
        # the recurrent-regime trace shape test_speculative.py measures
        # a nonzero accept rate on: 8-token shared prefix, short unique
        # tails, a 32-token budget for the stream to settle into
        rng = np.random.default_rng(1)
        shared = list(map(int, rng.integers(0, ROPE.vocab_size, 8)))
        tails = rng.integers(1, 6, 4)
        trace = [Request(i, shared + list(map(int, rng.integers(
                     0, ROPE.vocab_size, int(s)))), 32, arrival=0.0)
                 for i, s in enumerate(tails)]
        want = off.run([dataclasses.replace(r) for r in trace])
        got = spec.run([dataclasses.replace(r) for r in trace])
        assert got["outputs"] == want["outputs"]
        sp = got["speculation"]
        assert sp["accepted_tokens"] > 0
        assert sp["draft_tokens"] > sp["accepted_tokens"] or \
            sp["accept_rate"] == 1.0     # rejections exercised rollback
        spec.sched.check_quiescent()


# -------------------------------------------------- journal replay

class TestInt8JournalReplay:
    def _flaky_factory(self, model, params, engines, fail_on_call=4):
        """First engine dies with a transient device-loss error on its
        Nth decode dispatch; rebuilt engines run clean.  Every engine
        built is appended to ``engines`` so the test can inspect the
        survivor's pools."""
        state = {"faulted": False}

        def make_engine():
            engine = PagedDecodeEngine(model, params, SERVE)
            engines.append(engine)
            if not state["faulted"]:
                state["faulted"] = True
                orig, calls = engine._decode_fn, {"n": 0}

                def flaky(*a, **k):
                    calls["n"] += 1
                    if calls["n"] == fail_on_call:
                        raise RuntimeError(
                            "UNAVAILABLE: simulated device loss")
                    return orig(*a, **k)

                engine._decode_fn = flaky
            return engine

        return make_engine

    def test_sigkill_replay_token_identical(self, model_params, tmp_path):
        """Simulated SIGKILL mid-decode over an int8 pool: only the
        journal file survives, the cold restart replays
        prompt + delivered prefix through chunked prefill — and the
        merged outputs exactly match an unfaulted int8 run."""
        model, params = model_params
        path = str(tmp_path / "journal.jsonl")
        want = PagedDecodeEngine(model, params, SERVE).run(_trace())
        engines = []
        factory = self._flaky_factory(model, params, engines)
        with pytest.raises(RuntimeError):
            factory().run(_trace(), journal=ReplayJournal(path))
        res = run_with_replay(
            lambda: PagedDecodeEngine(model, params, SERVE), _trace(),
            journal_path=path)
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())

    def test_replay_requantizes_identical_pool_bytes(self, model_params):
        """THE quantization-determinism pin: the replayed run's prefill
        re-quantizes ``prompt + delivered prefix`` in chunks, the
        original run wrote those rows one decode token at a time — the
        per-(block, head, slot) row scales make both write shapes land
        byte-identical CODES in the same blocks and slots, so the
        survivor engine's pool dequantizes to what a straight run's does
        (null block excluded: dead decode lanes scatter garbage there
        and the dispatch count legitimately differs).

        The scales are held to 5 ulps, not to the byte: a (1, 1, E) decode
        matmul and a (1, chunk, E) prefill matmul round the same K/V row
        differently in its last bits on XLA:CPU (an fp32 pool shows the
        same rows apart by the same amount, 7e-5 relative at most),
        ``max|row| / 127`` follows its row (4 ulps at most here; 5 is
        that plus one), and the codes absorb it on this trace: a value
        within 7e-5 of a rounding boundary would not, so a new seed or
        model here may need another.  Same write shape => same bytes is
        TestInt8PoolDeterminism's pin."""
        model, params = model_params
        one = [Request(0, [5, 6, 7, 8, 9], 12)]
        straight = PagedDecodeEngine(model, params, SERVE)
        want = straight.run([dataclasses.replace(r) for r in one])
        engines = []
        res = run_with_replay(
            self._flaky_factory(model, params, engines, fail_on_call=6),
            [dataclasses.replace(r) for r in one])
        assert res["replays"] == 1
        assert res["outputs"] == want["outputs"]
        _assert_pools_equal(_pool_bytes(straight),
                            _pool_bytes(engines[-1]), scale_ulps=5)
        engines[-1].sched.check_quiescent()


# ------------------------------------------------- host-RAM tiering

def _tier_serve(kv_dtype="int8"):
    """A pool tight enough that three distinct 3-block prefixes cannot
    all stay device-resident (9 usable blocks, 4 per in-flight request
    at max_slots=1): the third admission evicts — and with the tier on,
    demotes — the LRU trie leaf."""
    return ServeConfig(num_blocks=10, block_size=4, max_slots=1,
                       max_seq_len=32, prefill_chunk=4,
                       kv_dtype=kv_dtype, prefix_cache="on",
                       kv_tier="host")


def _tier_prompts(n=3, seed=5, tokens=12):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, TINY.vocab_size, tokens)))
            for _ in range(n)]


def _trie_node(cache, key):
    node = cache._root
    for chunk in key:
        node = node.children[chunk]
    return node


class TestHostTiering:
    def _pressure(self, engine, budget=2):
        """Run the demotion-forcing phase and return the DEEPEST demoted
        trie path (its prompt walks surviving device nodes, then
        promotes the rest of the chain)."""
        engine.run([Request(i, list(p), budget, arrival=0.0)
                    for i, p in enumerate(_tier_prompts())])
        assert engine.tier.demotions >= 1
        assert len(engine.tier) >= 1
        return sorted(engine.tier._store, key=len, reverse=True)[0]

    @pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
    def test_demote_promote_byte_identity(self, model_params, kv_dtype):
        """THE tiering pin, on both quantized rungs: the promoted
        device block holds exactly the bytes (codes AND scale siblings)
        the block carried to host at demotion — round-tripping through
        np.ndarray storage and the pre-warmed promote dispatch loses
        nothing."""
        model, params = model_params
        engine = PagedDecodeEngine(model, params, _tier_serve(kv_dtype))
        key = self._pressure(engine)
        saved = [{name: arr.copy() for name, arr in layer.items()}
                 for layer in engine.tier._store[key]]
        prompt = [t for chunk in key for t in chunk]
        engine.run([Request(99, prompt, 2, arrival=0.0)])
        assert engine.tier.promotions >= 1
        assert key not in engine.tier
        node = _trie_node(engine.prefix_cache, key)
        for layer, host in zip(engine.pools, saved):
            assert set(host) == set(layer.keys())
            for name in host:
                np.testing.assert_array_equal(
                    np.asarray(layer[name][node.block]), host[name])
        engine.sched.check_quiescent()

    def test_promote_under_cow_token_identical(self, model_params):
        """A re-sent exact-block-multiple prompt promotes its demoted
        tail block and then recomputes the final prompt position INSIDE
        it (the len-1 hit cap) — CoW on a freshly promoted shared block.
        Outputs must equal an untired roomy engine's, and the trie copy
        must survive the sequence's private write."""
        model, params = model_params
        engine = PagedDecodeEngine(model, params, _tier_serve())
        key = self._pressure(engine)
        prompt = [t for chunk in key for t in chunk]
        got = engine.run([Request(99, list(prompt), 4, arrival=0.0)])
        fresh = PagedDecodeEngine(model, params, SERVE)
        want = fresh.run([Request(99, list(prompt), 4, arrival=0.0)])
        assert got["outputs"][99] == want["outputs"][99]
        assert engine.prefix_cache.stats()["promoted"] >= 1
        assert got["prefix"]["cow_copies"] >= 1, \
            "the promoted-final-block recompute was meant to CoW"
        assert got["tier"]["enabled"] and got["tier"]["promotions"] >= 1
        assert got["tier"]["prefill_tokens_saved_tier"] > 0
        engine.sched.check_quiescent()

    def test_sigkill_replay_with_tiering(self, model_params, tmp_path):
        """Simulated SIGKILL mid-decode with tiering on: the cold
        restart rebuilds an empty tier (host blocks die with the
        process, like the device pool) and replays through the journal
        — merged outputs exactly match an unfaulted tiered run, which
        itself demotes AND promotes (the scenario bites)."""
        model, params = model_params
        serve = _tier_serve()
        prompts = _tier_prompts()

        def trace():
            reqs = [Request(i, list(p), 2, arrival=0.0)
                    for i, p in enumerate(prompts)]
            reqs.append(Request(3, list(prompts[0]), 2, arrival=0.0))
            return reqs

        straight = PagedDecodeEngine(model, params, serve)
        want = straight.run(trace())
        assert straight.tier.demotions >= 1
        assert straight.tier.promotions >= 1
        path = str(tmp_path / "journal.jsonl")
        state = {"faulted": False}

        def make_engine():
            engine = PagedDecodeEngine(model, params, serve)
            if not state["faulted"]:
                state["faulted"] = True
                orig, calls = engine._decode_fn, {"n": 0}

                def flaky(*a, **k):
                    calls["n"] += 1
                    # budget-2 requests take ~one decode dispatch each
                    # (the first token rides the prefill argmax): call 3
                    # lands mid-trace, after the demotions started
                    if calls["n"] == 3:
                        raise RuntimeError(
                            "UNAVAILABLE: simulated device loss")
                    return orig(*a, **k)

                engine._decode_fn = flaky
            return engine

        with pytest.raises(RuntimeError):
            make_engine().run(trace(), journal=ReplayJournal(path))
        res = run_with_replay(make_engine, trace(), journal_path=path)
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())
