"""Tensor-parallel paged decode (serving/tp) on a multi-device CPU mesh.

The conftest pins an 8-virtual-device CPU platform, so the real
shard_map path runs here — no TPU needed.  The pins mirror the ISSUE
acceptance: TP=2 greedy decode is token-identical to the single-device
engine AND to ``generate()`` (including prefix-cache CoW and eviction
mid-decode), the sharded path does zero steady-state recompiles, and
bad ``tp`` geometry is rejected loudly.
"""

import dataclasses

import numpy as np
import pytest

from _jitted import generate_ref as _generate_ref
from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, Request,
                                        ServeConfig)
from mpi_tensorflow_tpu.serving import tp as tp_lib

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
ROPE = dataclasses.replace(TINY, pos_kind="rope")
BASE = dict(num_blocks=40, block_size=4, max_slots=3, max_seq_len=24,
            prefill_chunk=8)


def _prompts(rng, n, lo=3, hi=13):
    return [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
            for s in rng.integers(lo, hi + 1, n)]


def _model(cfg=TINY, seed=0):
    import jax

    model = gpt.CausalLm(cfg)
    return model, model.init(jax.random.key(seed))


class TestTpGeometry:
    def test_non_divisible_heads_rejected(self):
        model, params = _model()
        # TINY has 4 heads / 128 mlp: 3 divides neither
        with pytest.raises(ValueError, match="divide"):
            PagedDecodeEngine(model, params,
                              ServeConfig(**BASE, tp=3))

    def test_tp_over_device_count_rejected(self):
        import jax

        model, params = _model()
        too_many = len(jax.devices()) + 1
        # check_geometry tests the device bound before divisibility,
        # so this trips on the device count whatever heads/mlp are
        with pytest.raises(ValueError, match="device"):
            tp_lib.make_tp_mesh(too_many)
        with pytest.raises(ValueError, match="device"):
            PagedDecodeEngine(model, params,
                              ServeConfig(**BASE, tp=too_many))

    def test_tp_below_one_rejected_at_serveconfig(self):
        with pytest.raises(ValueError, match="tp"):
            ServeConfig(**BASE, tp=0)

    def test_pools_and_params_shard_on_declared_axes(self):
        """The pool shards its heads, contiguous slices of the last
        axis of ``(num_blocks, block_size, H*D)``; a head-sharded weight
        (wq) splits, a replicated one (tok_emb) does not."""
        from jax.sharding import PartitionSpec as P

        model, params = _model()
        engine = PagedDecodeEngine(model, params,
                                   ServeConfig(**BASE, tp=2))
        assert engine.pools[0]["k"].sharding.spec == P(None, None, "tp")
        wq = engine.params["layers"][0]["wq"]
        assert wq.sharding.spec == P(None, "tp")       # (embed, heads, D)
        assert engine.params["tok_emb"].sharding.spec == P()


class TestTpEngine:
    @pytest.mark.parametrize("cfg", [TINY, ROPE], ids=["learned", "rope"])
    def test_tp2_token_identical_to_single_device_and_generate(self, cfg):
        """THE acceptance pin: the same mixed-length trace through a
        TP=2 engine and a single-device engine emits identical tokens,
        and both match generate()."""
        model, params = _model(cfg, seed=1)
        rng = np.random.default_rng(2)
        prompts = _prompts(rng, 5)
        budgets = [int(n) for n in rng.integers(1, 9, len(prompts))]
        reqs = lambda: [Request(i, p, n) for i, (p, n)       # noqa: E731
                        in enumerate(zip(prompts, budgets))]
        single = PagedDecodeEngine(model, params, ServeConfig(**BASE))
        tp2 = PagedDecodeEngine(model, params,
                                ServeConfig(**BASE, tp=2))
        r1 = single.run(reqs())
        r2 = tp2.run(reqs())
        assert r1["outputs"] == r2["outputs"], \
            "TP=2 diverged from the single-device engine"
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            assert r2["outputs"][i] == _generate_ref(model, params, p, n), \
                f"request {i} diverged from generate()"
        tp2.allocator.check()
        assert tp2.allocator.num_used == 0

    def test_tp2_zero_recompiles_after_bucket_warmup(self):
        """The sharded path honors the bucket contract: a second trace
        in the same envelope grows no jit cache."""
        model, params = _model()
        engine = PagedDecodeEngine(model, params,
                                   ServeConfig(**BASE, tp=2))
        shape_rng = np.random.default_rng(3)
        lens = shape_rng.integers(3, 16, 6)
        budgets = [int(n) for n in shape_rng.integers(1, 10, 6)]

        def trace(content_seed):
            r = np.random.default_rng(content_seed)
            return [Request(i, list(map(int, r.integers(
                        0, TINY.vocab_size, int(s)))), budgets[i])
                    for i, s in enumerate(lens)]

        engine.run(trace(0))
        warm = engine.compile_counts()
        assert warm["decode"] > 0 and warm["prefill"] > 0
        engine.reset()
        engine.run(trace(7))
        assert engine.compile_counts() == warm, \
            "TP steady-state serving recompiled"

    def test_tp2_prefix_cache_cow_and_eviction_stay_exact(self):
        """Sharing machinery on the sharded pool: shared-prefix batch
        with CoW (block-multiple shared prompt) under a pool tight
        enough to evict mid-decode — outputs still generate()-identical
        and equal to the single-device prefix-cache engine."""
        model, params = _model(seed=4)
        rng = np.random.default_rng(5)
        shared = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        # one fully-cached exact-block-multiple prompt (the CoW
        # structural trigger at block_size=4) + divergent-suffix mates
        prompts = [shared,
                   shared + _prompts(rng, 1, lo=2, hi=5)[0],
                   shared + _prompts(rng, 1, lo=2, hi=5)[0],
                   _prompts(rng, 1, lo=3, hi=6)[0]]
        budgets = [4, 6, 5, 4]
        serve = dict(num_blocks=14, block_size=4, max_slots=2,
                     max_seq_len=20, prefill_chunk=4,
                     prefix_cache="on")
        reqs = lambda: [Request(i, p, n, arrival=0.02 * i)  # noqa: E731
                        for i, (p, n)
                        in enumerate(zip(prompts, budgets))]
        tp2 = PagedDecodeEngine(model, params,
                                ServeConfig(**serve, tp=2))
        single = PagedDecodeEngine(model, params, ServeConfig(**serve))
        r2 = tp2.run(reqs())
        r1 = single.run(reqs())
        assert r2["outputs"] == r1["outputs"]
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            assert r2["outputs"][i] == _generate_ref(model, params, p, n)
        assert r2["prefix"]["hit_tokens"] > 0, \
            "trace was meant to exercise sharing"
        tp2.sched.check_quiescent()

    def test_tp2_speculative_ngram_token_identical(self):
        """Speculation composes with TP: the verify dispatch runs
        through the sharded forward, tokens stay identical to the
        spec-off TP engine."""
        model, params = _model(ROPE, seed=6)
        rng = np.random.default_rng(7)
        base = list(map(int, rng.integers(0, TINY.vocab_size, 4)))
        prompts = [base * 3, base * 2 + base[:2]]     # recurrent streams
        reqs = lambda: [Request(i, p, 8) for i, p     # noqa: E731
                        in enumerate(prompts)]
        on = PagedDecodeEngine(model, params, ServeConfig(
            **BASE, tp=2, speculative="ngram", draft_k=3))
        off = PagedDecodeEngine(model, params,
                                ServeConfig(**BASE, tp=2))
        r_on = on.run(reqs())
        r_off = off.run(reqs())
        assert r_on["outputs"] == r_off["outputs"]
