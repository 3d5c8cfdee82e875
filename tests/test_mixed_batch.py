"""Stall-free mixed batching: fused prefill+decode dispatch.

The acceptance pins for --mixed-batch:

- mixed-on greedy outputs are TOKEN-IDENTICAL to mixed-off and to
  ``generate()`` — across prefill budgets, prefix cache v2 (generated
  blocks + partial tail hits), mid-prefill eviction, int8 KV pools,
  TP=2, and crash-replay through the journal;
- zero steady-state recompiles: every (slot, chunk, table) bucket
  triple is pre-warmed at build, so a bursty arrival pattern never
  compiles in the serving loop (``compile_counts()["mixed"]`` probe);
- the win metric: mixed runs STRICTLY fewer model forwards per
  emitted token than the two-dispatch loop on the same trace;
- the budget carve-out and the scheduler's ``prefill_backlog_tokens``
  signal (satellite: the autoscale load input);
- TTFT stamps (``request_first_token_s``) and the goodput block's
  ttft percentiles (satellite: first-token observability).
"""

import dataclasses

import numpy as np
import pytest

from _jitted import generate_ref as _generate_ref
from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (BlockAllocator, PagedDecodeEngine,
                                        Request, Scheduler, ServeConfig,
                                        run_with_replay)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
# Geometry chosen for bucket-grid ECONOMY: every mixed engine pays a
# build-time pre-warm over the full (slot, chunk, table) bucket grid,
# so tier-1 wall-clock scales with the grid size — 2 slot buckets x
# <=3 chunk buckets x 3 table buckets here, vs 48 triples at the
# default geometry.
BASE = dict(num_blocks=24, block_size=4, max_slots=2, max_seq_len=16,
            prefill_chunk=4)


def _prompts(rng, n, lo=3, hi=9):
    return [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
            for s in rng.integers(lo, hi + 1, n)]


@pytest.fixture(scope="module")
def model_params():
    import jax

    model = gpt.CausalLm(TINY)
    return model, model.init(jax.random.key(0))


def _trace(n=6, seed=2, lo=3, hi=9, budget_hi=7):
    rng = np.random.default_rng(seed)
    prompts = _prompts(rng, n, lo=lo, hi=hi)
    budgets = [int(b) for b in rng.integers(1, budget_hi, n)]
    return [Request(i, p, b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]


# Engine cache: construction pays the pre-warm grid, so tests sharing a
# config share ONE engine — reset() restores fresh pools/scheduler/trie
# while the warmed jit caches survive (the contract the serving entry
# point leans on between its warm-up replay and the served pass).
_ENGINES = {}


def _engine(model_params, **kw):
    model, params = model_params
    key = tuple(sorted(kw.items()))
    eng = _ENGINES.get(key)
    if eng is None:
        eng = PagedDecodeEngine(model, params, ServeConfig(**kw))
        _ENGINES[key] = eng
    else:
        eng.reset()
    return eng


# ------------------------------------------------------------- config

@pytest.mark.quick
class TestMixedConfig:
    def test_bad_mixed_batch_value_rejected(self):
        with pytest.raises(ValueError, match="mixed_batch"):
            ServeConfig(**BASE, mixed_batch="maybe")

    def test_prefill_budget_below_one_rejected(self):
        with pytest.raises(ValueError, match="prefill_budget"):
            ServeConfig(**BASE, prefill_budget=0)

    def test_mixed_with_speculative_rejected(self):
        # both replace the decode dispatch with their own fused
        # forward; composing them is a contradiction, not a feature
        with pytest.raises(ValueError, match="do not compose"):
            ServeConfig(**BASE, mixed_batch="on", speculative="ngram")


# ----------------------------------------------------- token identity

class TestMixedTokenIdentity:
    @pytest.mark.parametrize("budget", [2, 64])
    def test_identical_to_off_and_generate(self, model_params, budget):
        """THE acceptance pin: the fused dispatch emits exactly the
        tokens the two-dispatch loop (and generate()) produce, at any
        prefill budget — sub-chunk (2 < prefill_chunk: every take is
        budget-capped) and effectively unbounded (64: every live
        mid-prefill sequence fuses a full chunk) slice prefill
        differently, but chunked-prefill math is position-exact."""
        model, params = model_params
        reqs = _trace()
        off = _engine(model_params, **BASE).run(_trace())
        on = _engine(model_params, **BASE, mixed_batch="on",
                     prefill_budget=budget).run(_trace())
        assert on["outputs"] == off["outputs"]
        for r in reqs:
            assert on["outputs"][r.id] == _generate_ref(
                model, params, r.prompt, r.max_new_tokens), \
                f"request {r.id} diverged from generate()"

    def test_prefix_gen_and_partial_hits_stay_exact(self, model_params):
        """Mixed batching composes with prefix cache v2: a shared
        prefix that is NOT a block multiple exercises full-block hits
        AND the partial tail-block copy path under the fused
        dispatch."""
        model, params = model_params
        rng = np.random.default_rng(5)
        shared = list(map(int, rng.integers(0, TINY.vocab_size, 6)))
        prompts = [shared + list(map(int, rng.integers(
            0, TINY.vocab_size, int(s))))
            for s in rng.integers(2, 7, 6)]
        reqs = [Request(i, p, 4) for i, p in enumerate(prompts)]

        def fresh():
            return [Request(r.id, list(r.prompt), r.max_new_tokens)
                    for r in reqs]

        serve_on = ServeConfig(**BASE, prefix_cache="on",
                               prefix_gen="on", mixed_batch="on",
                               prefill_budget=2)
        eng = PagedDecodeEngine(model, params, serve_on)
        on = eng.run(fresh())
        assert eng.sched.counters["prefix_hit_tokens"] > 0, \
            "trace was meant to exercise prefix hits"
        off = PagedDecodeEngine(model, params, dataclasses.replace(
            serve_on, mixed_batch="off")).run(fresh())
        assert on["outputs"] == off["outputs"]
        for r in reqs:
            assert on["outputs"][r.id] == _generate_ref(
                model, params, r.prompt, r.max_new_tokens)
        eng.allocator.check()

    def test_mid_prefill_eviction_stays_exact(self, model_params):
        """A tight pool evicts the younger sequence mid-prefill while
        the fused path is interleaving its chunks with decode rows;
        the stale prefill-queue entry must be dropped and the evicted
        request must still finish generate()-identically."""
        model, params = model_params
        serve = ServeConfig(num_blocks=9, block_size=2, max_slots=2,
                            max_seq_len=12, prefill_chunk=2,
                            mixed_batch="on", prefill_budget=4)
        engine = PagedDecodeEngine(model, params, serve)
        rng = np.random.default_rng(8)
        pa = list(map(int, rng.integers(0, TINY.vocab_size, 2)))
        pb = list(map(int, rng.integers(0, TINY.vocab_size, 11)))
        res = engine.run([Request(0, pa, 10, arrival=0.0),
                          Request(1, pb, 1, arrival=0.0)])
        assert engine.sched.evictions >= 1, \
            "trace was meant to exercise eviction"
        assert res["outputs"][0] == _generate_ref(model, params, pa, 10)
        assert res["outputs"][1] == _generate_ref(model, params, pb, 1)
        engine.allocator.check()
        assert engine.allocator.num_used == 0

    def test_int8_kv_identical_to_int8_off(self, model_params):
        """Quantized pools: int8 mixed-on must match int8 mixed-off
        exactly (the write granularity differs per step, but int8
        rows quantize per (block, head, slot) — independent of which
        dispatch wrote them)."""
        model, params = model_params
        off = PagedDecodeEngine(model, params, ServeConfig(
            **BASE, kv_dtype="int8")).run(_trace())
        on = PagedDecodeEngine(model, params, ServeConfig(
            **BASE, kv_dtype="int8", mixed_batch="on",
            prefill_budget=2)).run(_trace())
        assert on["outputs"] == off["outputs"]

    def test_tp2_identical_to_single_device(self, model_params):
        """The fused dispatch runs unchanged on the tensor-parallel
        engine (conftest pins an 8-virtual-device CPU platform)."""
        model, params = model_params
        single = _engine(model_params, **BASE).run(_trace())
        tp_on = PagedDecodeEngine(model, params, ServeConfig(
            **BASE, tp=2, mixed_batch="on",
            prefill_budget=2)).run(_trace())
        assert tp_on["outputs"] == single["outputs"]

    def test_journal_replay_after_mid_run_fault(self, model_params):
        """Crash recovery: a transient device loss mid-mixed-dispatch
        rebuilds the engine and replays the journal; outputs must
        match an unfaulted mixed-off run token-for-token."""
        model, params = model_params
        serve = ServeConfig(**BASE, mixed_batch="on", prefill_budget=2)
        want = _engine(model_params, **BASE).run(_trace())
        state = {"faults_left": 1}

        def make_engine():
            engine = PagedDecodeEngine(model, params, serve)
            if state["faults_left"] > 0:
                state["faults_left"] -= 1
                orig, calls = engine._mixed_fn, {"n": 0}

                def flaky(*a, **k):
                    calls["n"] += 1
                    if calls["n"] == 4:
                        raise RuntimeError(
                            "UNAVAILABLE: simulated device loss")
                    return orig(*a, **k)

                engine._mixed_fn = flaky
            return engine

        res = run_with_replay(make_engine, _trace())
        assert res["replays"] == 1
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())


# ------------------------------------------------- dispatch discipline

class TestMixedDispatchEconomy:
    def test_zero_recompiles_after_bucket_warmup(self, model_params):
        """Build-time pre-warm covers every (slot, chunk, table)
        bucket triple, so a DIFFERENT trace in the same envelope —
        hitting different triples, because which buckets a mixed step
        visits depends on arrival timing — never compiles."""
        engine = _engine(model_params, **BASE, mixed_batch="on",
                         prefill_budget=64)
        shape_rng = np.random.default_rng(3)
        lens = shape_rng.integers(3, 10, 6)
        budgets = [int(n) for n in shape_rng.integers(1, 8, 6)]

        def trace(content_seed):
            r = np.random.default_rng(content_seed)
            return [Request(i, list(map(int, r.integers(
                        0, TINY.vocab_size, int(s)))), budgets[i])
                    for i, s in enumerate(lens)]

        engine.run(trace(0))
        warm = engine.compile_counts()
        if warm["mixed"] is not None:
            assert warm["mixed"] > 0
        engine.reset()
        engine.run(trace(7))                  # new content, same envelope
        assert engine.compile_counts() == warm, \
            "steady-state mixed serving recompiled"

    def test_mixed_dispatch_shapes_are_bucketed_pow2(self, model_params):
        engine = _engine(model_params, **BASE, mixed_batch="on",
                         prefill_budget=64)
        engine.run(_trace(n=7, seed=4))
        mixed = [s for s in engine.dispatch_shapes if s[0] == "mixed"]
        assert mixed, "mixed-on never took the fused dispatch"
        for shape in mixed:
            for dim in shape[1:]:
                assert dim & (dim - 1) == 0, \
                    f"non-pow2 mixed dispatch {shape}"

    def test_strictly_fewer_dispatches_per_token_than_off(
            self, model_params):
        """THE win metric: the fused path folds the prefill forwards
        the off arm pays separately into the decode dispatch, so its
        forwards-per-emitted-token must be strictly lower on any trace
        with mid-prefill traffic."""
        off = _engine(model_params, **BASE).run(_trace())
        on = _engine(model_params, **BASE, mixed_batch="on",
                     prefill_budget=64).run(_trace())
        assert on["outputs"] == off["outputs"]
        assert on["dispatches_per_token"] < off["dispatches_per_token"]
        assert on["forward_dispatches"] < off["forward_dispatches"]

    def test_budget_caps_prefill_lanes_per_step(self, model_params):
        """No mixed dispatch's chunk bucket may exceed the bucketed
        budget cap: the carve-out bounds each decode token's latency
        cost by construction."""
        from mpi_tensorflow_tpu.serving.engine import _bucket

        model, params = model_params
        serve = ServeConfig(**BASE, mixed_batch="on", prefill_budget=1)
        engine = PagedDecodeEngine(model, params, serve)
        engine.run(_trace())
        cap = _bucket(min(serve.prefill_chunk, serve.prefill_budget),
                      serve.prefill_chunk)
        for shape in engine.dispatch_shapes:
            if shape[0] == "mixed":
                assert shape[2] <= cap, \
                    f"budget leak: chunk bucket {shape[2]} > cap {cap}"


# --------------------------------------- backlog + TTFT observability

class TestBacklogAndTtft:
    def test_prefill_backlog_tokens_property(self):
        sched = Scheduler(BlockAllocator(16), 2, 4, 4)
        assert sched.prefill_backlog_tokens == 0
        sched.submit(Request(0, [1] * 7, 2))
        sched.submit(Request(1, [1, 2], 2))
        sched.admit()
        assert sched.prefill_backlog_tokens == 9
        sched.slots[0].prefilled = 4          # mid-prefill: 3 left
        sched.slots[1].prefilled = 2          # fully prefilled: 0
        assert sched.prefill_backlog_tokens == 3

    def test_load_signals_report_backlog(self, model_params):
        engine = _engine(model_params, **BASE)
        assert engine.load_signals()["prefill_backlog"] == 0.0
        engine.sched.submit(Request(0, [1] * 12, 2))
        engine.sched.admit()
        # 12 unprefilled prompt tokens / prefill_chunk 4 = 3 chunks
        assert engine.load_signals()["prefill_backlog"] == 3.0

    def test_autoscale_load_counts_backlog(self):
        from mpi_tensorflow_tpu.serving.autoscale import ScaleAdvisor

        adv = ScaleAdvisor()
        base = adv.load(queue_depth=1.0, occupancy=0.5)
        assert adv.load(queue_depth=1.0, occupancy=0.5,
                        prefill_backlog=2.0) > base

    def test_first_token_stamps_in_result(self, model_params):
        engine = _engine(model_params, **BASE)
        res = engine.run(_trace())
        first, finish = (res["request_first_token_s"],
                         res["request_finish_s"])
        for rid, status in res["statuses"].items():
            if status == "ok":
                assert rid in first
                assert first[rid] <= finish[rid]

    def test_goodput_block_ttft_percentiles(self):
        from mpi_tensorflow_tpu.utils import metrics_writer

        rows = [{"tenant": "default", "status": "ok", "tokens": 4,
                 "attained_ms": 40.0, "slo_ms": None,
                 "ttft_ms": float(t)} for t in (10, 20, 30)]
        gp = metrics_writer.goodput_block(rows, elapsed_s=1.0)
        assert gp["ttft_p50_ms"] == 20.0
        assert gp["ttft_p99_ms"] == pytest.approx(29.8)
        # rows without a stamp (nothing streamed) are excluded, not
        # counted as zero
        gp2 = metrics_writer.goodput_block(
            rows + [{"tenant": "default", "status": "shed",
                     "tokens": 0, "attained_ms": None, "slo_ms": None,
                     "ttft_ms": None}], elapsed_s=1.0)
        assert gp2["ttft_p50_ms"] == 20.0
