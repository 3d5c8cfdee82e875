"""The latent-attention / routed-expert decoder (models/mla_moe) through
the paged engine, against its plain reference
(benchmarks/reference/pangu_ultra_moe.py: float32, non-absorbed, no
cache), on seeded weights at a tiny size.

- paged prefill-then-decode logits equal the reference's full forward,
  on the XLA path and through the Pallas kernel (interpreted);
- the absorbed form equals the non-absorbed one;
- greedy tokens do not depend on the prefill chunk, the prefix cache
  (copy-on-write on latent leaves), mixed batching, speculation or a
  forced preemption;
- the share: four shares of 4 of 16 experts, the shared expert counted
  once, add up to the uncut reference layer; a batch routed wholly to one
  held expert loses no token;
- what the family cannot do yet is refused in words;
- the device counter, ``moe_block`` and the dispatch log;
- the kernel and the engine's two programs compile for the chip with no
  pool-sized copy; the benchmark cell's CPU rehearsal ends ``correct``.
"""

import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import pangu_ultra_moe as ref
from mpi_tensorflow_tpu.models import bert, gpt, mla_moe
from mpi_tensorflow_tpu.ops import mla_attention as mla_ops
from mpi_tensorflow_tpu.ops import moe_experts
from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, Request,
                                        ServeConfig)
from mpi_tensorflow_tpu.serving import paged_cache
from mpi_tensorflow_tpu.utils import dispatch_log

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 256
SZ = {"vocab": VOCAB, "hidden": 64, "layers": 2, "dense_layers": 1,
      "heads": 4, "q_rank": 32, "kv_rank": 16, "nope": 16, "rope": 8,
      "v_dim": 16, "mlp": 128, "expert_mlp": 32, "router_width": 16,
      "experts_first": 0, "experts_held": 4, "top_k": 4,
      "norm_topk": True, "routed_scale": 2.5, "eps": 1e-5,
      "theta": 25_600_000.0, "positions": 512}


def make_model(sz=SZ, dtype=jnp.float32):
    return mla_moe.MlaMoeLm(mla_moe.MlaMoeConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        intermediate_size=sz["mlp"],
        moe_intermediate_size=sz["expert_mlp"],
        num_hidden_layers=sz["layers"],
        first_k_dense_replace=sz["dense_layers"],
        num_attention_heads=sz["heads"], q_lora_rank=sz["q_rank"],
        kv_lora_rank=sz["kv_rank"], qk_nope_head_dim=sz["nope"],
        qk_rope_head_dim=sz["rope"], v_head_dim=sz["v_dim"],
        n_routed_experts=sz["router_width"],
        num_experts_per_tok=sz["top_k"],
        routed_scaling_factor=sz["routed_scale"],
        max_position_embeddings=sz["positions"],
        experts_held=(sz["experts_first"], sz["experts_held"]),
        dtype=dtype))


@pytest.fixture(scope="module")
def model():
    return make_model()


@pytest.fixture(scope="module")
def params():
    # the benchmark's weights: the program must take the reference's tree
    p = jax.jit(lambda k: ref.init_params(SZ, k))(jax.random.key(3))
    want = jax.eval_shape(make_model().init, jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(p)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(p)))
    return p


PROMPTS = [np.random.default_rng(7).integers(0, VOCAB, n).tolist()
           for n in (5, 16, 16, 27)]
PROMPTS[2] = list(PROMPTS[1])        # a fully cached, block-aligned prompt
NEW = 6


def greedy_of(params, prompt, n):
    """The reference's greedy continuation (one padded forward per
    token, one compile for all)."""
    seq = list(prompt)
    for _ in range(n):
        toks = np.zeros((64,), np.int32)
        toks[:len(seq)] = seq
        lg = ref.next_token_logits(params, toks,
                                   np.asarray([len(seq) - 1]), SZ)
        seq.append(int(np.argmax(lg[0])))
    return seq[len(prompt):]


@pytest.fixture(scope="module")
def greedy(params):
    return [greedy_of(params, p, NEW) for p in PROMPTS]


def serve(model, params, **kw):
    base = dict(num_blocks=33, block_size=8, max_slots=4, max_seq_len=64,
                prefill_chunk=8, kernel="xla")
    base.update(kw)
    eng = PagedDecodeEngine(model, params, ServeConfig(**base))
    res = eng.run([Request(id=i, prompt=p, max_new_tokens=NEW,
                           arrival=0.0) for i, p in enumerate(PROMPTS)])
    return eng, res, [res["outputs"][i] for i in range(len(PROMPTS))]


class TestAgainstReference:
    @pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
    def test_paged_prefill_then_decode_logits(self, model, params, kernel):
        """Two prefill chunks, then three decode steps, through
        ``forward_paged`` and a 2-row block table: every position's
        logits equal the reference's full causal forward."""
        bs, nb_seq = 8, 4
        rng = np.random.default_rng(11)
        seqs = rng.integers(0, VOCAB, (2, 19)).astype(np.int32)
        pools = paged_cache.init_pools(model.cfg, 1 + 2 * nb_seq, bs,
                                       model=model)
        tables = jnp.asarray(1 + np.arange(2 * nb_seq, dtype=np.int32)
                             .reshape(2, nb_seq))
        fwd = jax.jit(lambda p, t, pl, ln: model.forward_paged(
            p, t, pl, tables, ln, kernel=kernel))
        got, at = [], 0
        for width in (8, 8, 1, 1, 1):
            lg, pools = fwd(params, jnp.asarray(seqs[:, at:at + width]),
                            pools, jnp.full((2,), at, jnp.int32))
            got.append(np.asarray(lg))
            at += width
        got = np.concatenate(got, axis=1)                  # (2, 19, V)
        for b in range(2):
            toks = np.zeros((64,), np.int32)
            toks[:19] = seqs[b]
            want = np.asarray(ref.next_token_logits(
                params, toks, np.arange(19), SZ))
            np.testing.assert_allclose(got[b], want, atol=2e-4, rtol=2e-4)

    def test_full_forward_equals_reference(self, model, params):
        toks = np.random.default_rng(5).integers(
            0, VOCAB, (1, 64)).astype(np.int32)
        got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(toks)))
        want = np.asarray(ref.next_token_logits(
            params, toks[0], np.arange(64), SZ))
        np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)

    def test_fp8_control_moves_the_logits(self, params):
        """The control the limit is set against: the same mathematics
        with every matmul's operands in e4m3 is far from the reference."""
        toks = np.random.default_rng(5).integers(
            0, VOCAB, (64,)).astype(np.int32)
        pos = np.arange(32, 64)
        want = np.asarray(ref.next_token_logits(params, toks, pos, SZ))
        low = np.asarray(ref.next_token_logits(params, toks, pos, SZ,
                                               precision="fp8"))
        assert np.abs(low - want).max() > 50 * 2e-4


class TestAbsorbedForm:
    def _case(self, S, lens):
        B, H, C, R, Dn, Dv, bs, NB = len(lens), 4, 16, 8, 16, 16, 8, 6
        k = jax.random.split(jax.random.key(0), 4)
        pool = jax.random.normal(
            k[0], (1 + B * NB, bs, mla_ops.pool_width(C, R)))
        bt = jnp.asarray(1 + np.arange(B * NB, dtype=np.int32)
                         .reshape(B, NB))
        return (jax.random.normal(k[1], (B, S, H, Dn)),
                jax.random.normal(k[2], (B, S, H, R)), pool, bt,
                jnp.asarray(lens, jnp.int32),
                jax.random.normal(k[3], (C, H, Dn + Dv)) * 0.2,
                (Dn + R) ** -0.5, jnp.float32)

    @pytest.mark.parametrize("S,lens", [(1, [0, 7, 20, 41]),
                                        (5, [3, 17]), (32, [9])])
    def test_three_ways_agree(self, S, lens):
        """Non-absorbed gather path, absorbed gather path, absorbed
        kernel over live blocks (decode, a ragged verify width, a
        multi-tile prefill chunk)."""
        a = self._case(S, lens)
        plain = mla_ops.attend_xla(*a)
        absorbed = mla_ops.attend_xla(*a, absorbed=True)
        kernel = jax.jit(lambda *x: mla_ops.attend(
            *x, a[5], a[6], a[7], kernel="pallas-interpret"))(*a[:5])
        np.testing.assert_allclose(absorbed, plain, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(kernel, plain, atol=2e-5, rtol=2e-5)

    def test_work_list_follows_live_blocks(self):
        """The kernel's grid is the live (row, tile, block) triples, not
        rows x table width."""
        lens = jnp.asarray([0, 7, 20, 300], jnp.int32)
        row, tile, blk, n, live = paged_ops.work_list(lens, 1, 1, 1, 8, 64)
        need = [1, 1, 3, 38]
        assert int(live) == sum(need) < 4 * 64
        assert np.asarray(row)[:int(live)].tolist() == sum(
            ([b] * c for b, c in enumerate(need)), [])
        assert np.asarray(blk)[:int(live)].tolist() == sum(
            (list(range(c)) for c in need), [])


class TestGreedyIdentity:
    """Token identity with the reference's greedy continuation, whatever
    the engine does on the way."""

    @pytest.mark.parametrize("kw", [
        {"prefill_chunk": 8}, {"prefill_chunk": 32},
        {"kernel": "pallas"},
        {"speculative": "ngram", "max_slots": 2},
        {"mixed_batch": "on", "max_slots": 2, "prefill_chunk": 4,
         "max_seq_len": 40},
    ], ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()))
    def test_engine_modes(self, model, params, greedy, kw):
        eng, res, got = serve(model, params, **kw)
        assert got == greedy
        if kw.get("kernel") == "pallas":
            assert eng.kernel == "pallas-interpret"

    def test_prefix_cache_copy_on_write_on_latent_leaves(
            self, model, params, greedy):
        """PROMPTS[2] repeats PROMPTS[1], two whole blocks: its last
        block is shared and must be copied before it is rewritten."""
        eng, res, got = serve(model, params, prefix_cache="on",
                              max_slots=1)
        assert got == greedy
        assert eng.sched.counters["prefix_cow_copies"] >= 1
        assert res["prefix"]["hit_tokens"] >= 16

    def test_forced_preemption(self, model, params):
        """Two sequences outgrow an 8-block pool: one is evicted, its
        latent rows are recomputed, and both still end as the reference
        has them (tests/test_serving.py's trace, on this model)."""
        eng = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=9, block_size=2, max_slots=2, max_seq_len=12,
            prefill_chunk=2))
        rng = np.random.default_rng(8)
        pa = rng.integers(0, VOCAB, 2).tolist()
        pb = rng.integers(0, VOCAB, 11).tolist()
        res = eng.run([Request(0, pa, 10, arrival=0.0),
                       Request(1, pb, 1, arrival=0.0)])
        assert eng.sched.evictions >= 1
        assert res["outputs"][0] == greedy_of(params, pa, 10)
        assert res["outputs"][1] == greedy_of(params, pb, 1)
        eng.allocator.check()
        assert eng.allocator.num_used == 0


class TestExpertShare:
    def _layer(self, key):
        sz = dict(SZ, experts_held=16)
        return ref.init_params(dict(sz, layers=2), key)["layers"][1]["moe"]

    def test_four_shares_add_up_to_the_uncut_layer(self):
        """Guide section 4: the routed parts of the four shares (4 of 16
        experts each) plus the shared expert, counted once, are the
        reference's whole layer."""
        mp = self._layer(jax.random.key(1))
        x = jax.random.normal(jax.random.key(2), (1, 24, SZ["hidden"]))
        whole = ref.routed(mp, x[0], dict(SZ, experts_held=16), "f32")
        shared = mla_moe.swiglu(mp["shared"], x)
        total = shared
        for first in (0, 4, 8, 12):
            m = make_model(dict(SZ, experts_first=first))
            part = {"moe": dict(mp, experts=jax.tree.map(
                lambda w: w[first:first + 4], mp["experts"]))}
            y, counts = jax.jit(lambda p, v: m._ffn(
                p, v, jnp.ones((1, 24), bool), "ragged"))(part, x)
            # the same share in the reference
            want = ref.routed(part["moe"], x[0], dict(
                SZ, experts_first=first), "f32")
            np.testing.assert_allclose(y[0], want, atol=2e-5, rtol=2e-5)
            total = total + (y - shared)
        np.testing.assert_allclose(total[0], whole, atol=5e-5, rtol=5e-5)

    @pytest.mark.parametrize("impl,T", [("ragged", 40), ("gmm-interpret", 40),
                                        ("ragged", 512), ("ragged", 520)])
    def test_batch_on_one_expert_loses_no_token(self, impl, T):
        """Every token chose held expert 2 (and three absent ones): the
        one group is the whole batch, and each token gets its gate times
        that expert.  From 512 tokens on, the pair bound (T x 4) tries a
        quarter of its rows first: 512 pairs just fit it, 520 do not."""
        E, F = 128, 256
        k = jax.random.split(jax.random.key(4), 5)
        x = jax.random.normal(k[0], (T, E))
        w = {"w_gate": jax.random.normal(k[1], (4, E, F)) * 0.05,
             "w_up": jax.random.normal(k[2], (4, E, F)) * 0.05,
             "w_down": jax.random.normal(k[3], (4, F, E)) * 0.05}
        experts = jnp.tile(jnp.asarray([[9, 2, 10, 11]], jnp.int32),
                           (T, 1))
        gates = jax.random.uniform(k[4], (T, 4), minval=0.1)
        y, counts = jax.jit(lambda *a: moe_experts.held_experts(
            *a, first=0, impl=impl))(x, experts, gates,
                                     jnp.ones((T,), bool), w)
        one = {key: v[2] for key, v in w.items()}
        want = gates[:, 1:2] * mla_moe.swiglu(one, x)
        np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
        assert np.asarray(counts).tolist() == [0, 0, T, 0, 1]

    def test_invalid_tokens_are_routed_nowhere(self):
        mp = self._layer(jax.random.key(1))
        x = jax.random.normal(jax.random.key(2), (16, SZ["hidden"]))
        ex, g = moe_experts.route(x, mp["router"], top_k=4, scale=2.5)
        valid = jnp.arange(16) < 10
        w = jax.tree.map(lambda v: v[:4], mp["experts"])
        y, counts = moe_experts.held_experts(x, ex, g, valid, w, first=0)
        assert float(jnp.abs(y[10:]).max()) == 0.0
        held = (np.asarray(ex)[:10] < 4).sum()
        assert int(np.asarray(counts)[:-1].sum()) == held


class TestRefusals:
    def test_quantised_pool(self, model, params):
        with pytest.raises(ValueError, match="latent pool has no "
                                             "quantised form"):
            PagedDecodeEngine(model, params, ServeConfig(kv_dtype="int8"))

    def test_tensor_parallel(self, model, params):
        with pytest.raises(ValueError, match="no head axis to shard"):
            PagedDecodeEngine(model, params, ServeConfig(tp=2))

    def test_draft_of_another_family(self, model, params):
        with pytest.raises(ValueError, match="draft model of its own "
                                             "family"):
            PagedDecodeEngine(model, params,
                              ServeConfig(speculative="draft-model"))
        tiny = gpt.CausalLm(bert.BertConfig(
            vocab_size=VOCAB, hidden=32, layers=1, heads=2, mlp=64,
            max_positions=64, dropout=0.0))
        with pytest.raises(ValueError, match="got CausalLm"):
            PagedDecodeEngine(
                model, params, ServeConfig(speculative="draft-model"),
                draft_model=tiny,
                draft_params=tiny.init(jax.random.key(0)))

    def test_config_refuses_what_is_not_built(self):
        with pytest.raises(ValueError, match="not a range"):
            mla_moe.MlaMoeConfig(experts_held=(250, 16))
        with pytest.raises(ValueError, match="sandwich-norm"):
            mla_moe.MlaMoeConfig(sandwich_norm=False)


class TestEngineLetsGo:
    """An engine that is dropped gives its pool and weights back at once
    (the benchmark frees a ten-gigabyte engine to make room for the
    reference's weights), and its jitted steps keep their names."""

    @pytest.mark.parametrize("kw", [{}, {"prefix_cache": "on",
                                         "kv_tier": "host",
                                         "trace": "on"}],
                             ids=["plain", "trie-tier-traced"])
    def test_dropped_engine_is_freed_without_a_collection(self, kw):
        import gc
        import weakref

        from mpi_tensorflow_tpu.serving import EngineLoop

        tiny = gpt.CausalLm(bert.BERT_TINY)
        weights = tiny.init(jax.random.key(0))
        gc.collect()
        gc.disable()
        try:
            eng = PagedDecodeEngine(tiny, weights, ServeConfig(
                num_blocks=17, block_size=4, max_slots=2, max_seq_len=32,
                prefill_chunk=4, **kw))
            res = eng.run([Request(id=0, prompt=[1, 2, 3, 4, 5],
                                   max_new_tokens=3, arrival=0.0)])
            loop = EngineLoop(eng)
            alive = weakref.ref(eng), weakref.ref(eng.pools[0]["k"])
            del eng, loop, res
            assert alive[0]() is None and alive[1]() is None
        finally:
            gc.enable()

    def test_jitted_steps_keep_their_names(self, model, params):
        eng = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=9, block_size=8, max_slots=2, max_seq_len=32))
        for fn, name in ((eng._decode_fn, "_decode_impl"),
                         (eng._prefill_fn, "_prefill_impl"),
                         (eng._cow_fn, "_cow_impl"),
                         (eng._mixed_fn, "_mixed_impl")):
            assert fn.__name__ == name


class TestCounters:
    def test_moe_block_and_reset(self, model, params):
        eng, res, _ = serve(model, params)
        moe = res["moe"]
        assert moe["enabled"] and len(moe["per_expert"]) == 4
        assert moe["assignments"] == sum(moe["per_expert"]) > 0
        assert moe["load_max_over_mean"] >= 1.0
        assert 0 < moe["experts_touched"] <= 4 * eng.forward_dispatches
        eng.reset()
        assert eng.moe_block()["assignments"] == 0
        # a K/V model declares no counter: the block is there and empty
        assert not paged_cache.read_counters(
            paged_cache.init_pools(bert.BERT_TINY, 3, 8))

    def test_dispatch_log_only_when_traced(self, model, params):
        dispatch_log.reset()
        serve(model, params)
        assert dispatch_log.snapshot() == {"dispatches": [], "totals": []}
        eng, res, _ = serve(model, params, trace="on")
        log = dispatch_log.snapshot()
        kinds = {r[1] for r in log["dispatches"]}
        assert kinds == {"prefill", "decode"}
        assert len(log["dispatches"]) == eng.forward_dispatches
        assert all(r[4] is not None for r in log["dispatches"])
        assert sum(r[4] for r in log["dispatches"]) \
            == res["moe"]["assignments"] == sum(log["totals"])
        # attended tokens: a 5-token first chunk sees 5 * 6 / 2 pairs
        first = log["dispatches"][0]
        assert first[1:4] == ["prefill", 5, 15]
        assert res["trace"]["replicas"][0]["moe"] == res["moe"]
        dispatch_log.reset()


@pytest.fixture(scope="module")
def tpu_device():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:   # no libtpu on this host
        pytest.skip(f"no deviceless TPU topology available: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


class TestCompilesForTheChip:
    """Mosaic and XLA:TPU, without a chip (compile only), at the
    benchmark cell's widths."""

    def test_kernel_at_the_served_geometry(self, tpu_device):
        mla_ops.probe_compile.cache_clear()
        mla_ops.probe_compile("bfloat16", 128, 512, 64, 512, 1024,
                              sharding=tpu_device)

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_program_takes_the_pool_in_place(self, tpu_device, program):
        """The engine's decode and 1,024-token prefill programs over the
        cell's 1,025-block pool (one dense and one expert layer): no
        ``copy`` or ``transpose`` of a pool-sized operand, the latent
        leaf row-major at its declared size, less scratch than a leaf,
        and the kernels by their stable names."""
        dev = tpu_device
        cfg = mla_moe.MlaMoeConfig(
            vocab_size=19200, num_hidden_layers=2, first_k_dense_replace=1,
            experts_held=(0, 16), dtype=jnp.bfloat16)
        model = mla_moe.MlaMoeLm(cfg)

        def on_chip(tree, floats=None):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, floats if floats is not None and jnp.issubdtype(
                    x.dtype, jnp.floating) else x.dtype, sharding=dev),
                tree)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

        params = on_chip(jax.eval_shape(model.init, jax.random.key(0)),
                         jnp.bfloat16)
        pools = on_chip(jax.eval_shape(lambda: paged_cache.init_pools(
            cfg, 1025, 512, model=model)))
        engine = types.SimpleNamespace(
            _paged_forward=lambda p, tokens, pools, tables, lengths, valid:
            model.forward_paged(p, tokens, pools, tables, lengths,
                                valid=valid, kernel="pallas"))
        if program == "decode":
            impl = PagedDecodeEngine._decode_impl
            rest = (ints(128), ints(128), ints(128, 18))
        else:
            impl = PagedDecodeEngine._prefill_impl
            rest = (ints(1, 1024), ints(), ints(), ints(1, 18))
        compiled = jax.jit(
            lambda params, pools, *a: impl(engine, params, pools, *a),
            donate_argnums=(1,)).lower(params, pools, *rest).compile()
        text = compiled.as_text()
        made = {}
        for dims, op in re.findall(
                r"= \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(", text):
            made.setdefault(tuple(map(int, dims.split(","))) if dims
                            else (), set()).add(op)
        leaf = (1025, 512, 640)
        assert tuple(pools[0]["latent"].shape) == leaf
        assert not made[leaf] & {"copy", "transpose"}, made[leaf]
        name = (mla_ops.DECODE_KERNEL if program == "decode"
                else mla_ops.PREFILL_KERNEL)
        assert len(re.findall(rf"%{name}(\.\d+)? = ", text)) == 2
        # three grouped matmuls; the 8,192-pair prefill bound compiles
        # them for a quarter of the rows too
        assert len(re.findall(rf"%{moe_experts.KERNEL_NAME}(\.\d+)? = ",
                              text)) == (3 if program == "decode" else 6)
        for layer in compiled.input_formats[0][1]:
            assert tuple(layer["latent"].layout.major_to_minor) == (0, 1, 2)
        mem = compiled.memory_analysis()
        logical = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(pools))
        assert logical <= mem.alias_size_in_bytes < logical + 4096
        assert mem.temp_size_in_bytes < int(np.prod(leaf)) * 2


def test_benchmark_cell_rehearses_correct():
    """The cell's CPU rehearsal: the serve driver, this model's harness
    file, the reference and the check, end to end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "openpangu_ultra_moe_718b.serve_closed128_p1k_8k", "--seed",
         "2200000011", "--seconds", "2", "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=150)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
