"""models/cohere2_moe.Cohere2MoeLm against its plain reference
(benchmarks/reference/cohere2_moe.py: float32, no cache, GPT-J's
interleaved rotary written out, masks written out) and through
``PagedDecodeEngine``: a parallel attention + MoE block, grouped-query
attention (4 query heads a KV head at this size) over a paged pool in the
full layer and over rings by slot in the three window layers, four shared
experts beside the held share of the routed ones; the grouped-query
kernels against their XLA anchor; the refusals.  Tiny sizes, seeded
weights, float32; the window (16) is shorter than every context, so
prompts cross it and wrap a ring.

Tolerances: float32 program against float32 reference differ by the
order of additions alone, 2.4e-7 to 3.0e-7 of logits whose spread is
~0.17 (0.02 embedding rows, 64 wide): ``TOL`` is 1e-5, over thirty
times that; bfloat16 compute misses it by 400 times (4.1e-3,
``test_bfloat16_misses_the_tolerance``).  The kernels against their
anchor agree to float32 rounding of one online softmax, 2e-5.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import cohere2_moe as ref
from mpi_tensorflow_tpu.models import cohere2_moe as cm
from mpi_tensorflow_tpu.ops import moe_experts
from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.ops import paged_attention_kernel as pk
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, Request,
                                        ServeConfig)
from mpi_tensorflow_tpu.serving import paged_cache
from mpi_tensorflow_tpu.utils import dispatch_log

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, WINDOW = 256, 16
SZ = {"vocab": VOCAB, "hidden": 64, "layers": 4, "heads": 8, "kv_heads": 2,
      "head_dim": 16, "expert_mlp": 32, "window": WINDOW,
      "window_layers": (0, 1, 2), "router_width": 16, "experts_first": 0,
      "experts_held": 4, "top_k": 4, "shared": 4, "norm_topk": True,
      "theta": 50000.0, "eps": 1e-5, "logit_scale": 1.0, "positions": 512}
TOL = dict(atol=1e-5, rtol=1e-5)


def make_model(cfg=cm.TINY):
    c = cfg              # the reference is given the same sizes, as SZ
    assert (c.vocab_size, c.hidden_size, c.num_hidden_layers,
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.intermediate_size, c.sliding_window, c.num_experts,
            c.num_experts_per_tok, c.num_shared_experts,
            c.experts_held) == (
        VOCAB, 64, 4, 8, 2, 16, 32, WINDOW, 16, 4, 4, (0, 4))
    assert [c.is_window(i) for i in range(4)] == [True] * 3 + [False]
    return cm.Cohere2MoeLm(c)


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


def reference(params, seq, positions=None, sz=SZ, precision="f32"):
    toks = np.zeros((256,), np.int32)
    toks[:len(seq)] = seq
    return ref.next_token_logits(
        params, toks, np.arange(len(seq)) if positions is None
        else np.asarray(positions), sz, precision=precision)


def served_gap(params, prompt, out):
    """How far the served tokens lie below the reference's best, at the
    reference's own logits of the served sequence (the benchmark's
    ``served_logit_gap``): 0 where every token is the reference's."""
    seq = list(prompt) + list(out[:-1])
    lg = reference(params, seq, range(len(prompt) - 1, len(seq)))
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def engine(model, params, **kw):
    base = dict(num_blocks=49, block_size=8, max_slots=4, max_seq_len=96,
                prefill_chunk=16, kernel="xla")
    base.update(kw)
    return PagedDecodeEngine(model, params, ServeConfig(**base))


RNG = np.random.default_rng(7)
PROMPTS = [RNG.integers(0, VOCAB, n).tolist() for n in (5, 23, 41, 17)]
NEW = 12


def paged_logits(model, params, seqs, widths, kernel, slots=(2, 0)):
    """Every position's logits of ``seqs`` (B, T) through
    ``forward_paged`` as the engine calls it, chunk by chunk of
    ``widths``, the rows in ``slots``."""
    B = len(slots)
    bs, nb = 8, 8
    pools = paged_cache.init_pools(model.cfg, 1 + B * nb, bs, model=model,
                                   max_slots=3)
    tables = jnp.asarray(1 + np.arange(B * nb, dtype=np.int32)
                         .reshape(B, nb))
    fwd = jax.jit(lambda p, t, pl, ln: model.forward_paged(
        p, t, pl, tables, ln, kernel=kernel,
        slots=jnp.asarray(slots, jnp.int32)))
    got, at = [], 0
    for width in widths:
        lg, pools = fwd(params, jnp.asarray(seqs[:, at:at + width]), pools,
                        jnp.full((B,), at, jnp.int32))
        got.append(np.asarray(lg))
        at += width
    return np.concatenate(got, axis=1)


class TestAgainstReference:
    def test_full_forward_equals_reference(self, model, params):
        toks = np.random.default_rng(5).integers(
            0, VOCAB, (1, 40)).astype(np.int32)
        got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(toks)))
        np.testing.assert_allclose(got[0], reference(params, toks[0]),
                                   **TOL)

    @pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
    def test_chunked_prefill_then_decode_logits(self, model, params,
                                                kernel):
        """Two rows in slots 2 and 0: a first chunk longer than the
        window, a second that starts past it (the ring wraps), a third of
        the window's length, then five decode steps through the caches.
        Every position's logits equal the reference's full forward."""
        seqs = np.random.default_rng(11).integers(
            0, VOCAB, (2, 53)).astype(np.int32)
        got = paged_logits(model, params, seqs, (24, 8, 16, 1, 1, 1, 1, 1),
                           kernel)
        for b in range(2):
            np.testing.assert_allclose(got[b], reference(params, seqs[b]),
                                       **TOL)

    def test_bfloat16_misses_the_tolerance(self, params):
        """The tolerance has teeth: the same stack computed in bfloat16
        (weights rounded, activations and pool in bfloat16) lies far
        outside it."""
        bf = make_model(dataclasses.replace(cm.TINY, dtype=jnp.bfloat16))
        seqs = np.random.default_rng(11).integers(
            0, VOCAB, (2, 40)).astype(np.int32)
        p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        got = paged_logits(bf, p16, seqs, (24, 16), "xla")
        err = np.abs(got[0] - reference(params, seqs[0])).max()
        assert err > 100 * TOL["atol"]

    def test_rotary_is_gptj_interleaved(self):
        """``_rotate`` (a fixed permutation, then ``bert.rope``'s
        half-split pairs) gives every score q.k that GPT-J's interleaved
        rotary (the reference's ``rotate``) gives."""
        rng = np.random.default_rng(2)
        S, D = 40, 16
        q = rng.normal(size=(S, 3, D)).astype(np.float32)
        k = rng.normal(size=(S, 3, D)).astype(np.float32)
        pos = jnp.arange(S)[None]

        def program(x):             # (S, H, D) -> (H, S, D) rotated
            return cm._rotate(jnp.moveaxis(jnp.asarray(x), 1, 0)[None],
                              pos, 50000.0)[0]
        got = jnp.einsum("hqd,hkd->hqk", program(q), program(k))
        rq, rk = ref.rotate(jnp.asarray(q), 50000.0), \
            ref.rotate(jnp.asarray(k), 50000.0)
        want = jnp.einsum("qhd,khd->hqk", rq, rk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-5)
        # and it is a rotation: scores depend on the offset alone
        assert not np.allclose(np.asarray(rq), q)

    @pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
    def test_window_teeth(self, params, kind):
        """A key W or more back: changing it leaves a window layer's
        output at the last position exactly as it was, through the plain
        forward and through the rings alike, and moves a full layer's
        visibly."""
        m = cm.Cohere2MoeLm(dataclasses.replace(
            cm.TINY, num_hidden_layers=1, layer_types=(kind,)))
        p1 = dict(params, layers=params["layers"][:1])
        seq = np.random.default_rng(9).integers(
            0, VOCAB, (1, WINDOW + 5)).astype(np.int32)
        other = seq.copy()
        other[0, 0] = (seq[0, 0] + 1) % VOCAB       # W + 4 back
        plain = [np.asarray(m.forward(p1, jnp.asarray(s)))[0, -1]
                 for s in (seq, other)]
        paged = [paged_logits(m, p1, np.concatenate([s, s]), (16, 4, 1),
                              "xla")[0, -1] for s in (seq, other)]
        for a, b in (plain, paged):
            if kind == "sliding_attention":
                np.testing.assert_array_equal(a, b)
            else:
                assert np.abs(a - b).max() > 100 * TOL["atol"]

    def test_shares_sum_to_the_whole_layer(self, model, params):
        """Eight chips' held-expert shares, the shared branch counted
        once: the program's ``_moe`` on each chip's 2 of 16 experts sums
        to the reference's layer that holds all 16."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(1, 24, 64)), jnp.float32)
        whole = dict(SZ, experts_held=16)
        mp = jax.jit(lambda k: ref.init_params(whole, k))(
            jax.random.key(6))["layers"][0]["moe"]
        want = ref.moe(mp, x[0], whole, "f32")
        shared = 0.5 * ref.swiglu(x[0], mp["shared"]["w_gate"],
                                  mp["shared"]["w_up"],
                                  mp["shared"]["w_down"], "f32") / 4
        total = -7 * shared
        valid = jnp.ones((1, 24), bool)
        for chip in range(8):
            c = dataclasses.replace(cm.TINY, experts_held=(2 * chip, 2))
            held = {k: v[2 * chip:2 * chip + 2]
                    for k, v in mp["experts"].items()}
            y, counts = cm.Cohere2MoeLm(c)._moe(dict(mp, experts=held), x,
                                                valid, "ragged")
            total = total + y[0]
        np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)

    def test_a_chunk_routes_apart(self):
        """At the rehearsal size of the benchmark's configuration, the
        tokens of one prefill chunk do not all go to the same experts in
        any layer.  Routing is uneven, as seeded weights make it: the
        first layer routes by the tokens' own rows, later ones also by
        what near-uniform attention adds to every token alike (PERF.md
        reads the chip's ``moe_expert_load_max_over_mean``)."""
        cfg = json.load(open(os.path.join(
            ROOT, "benchmarks/configs/command_a_plus_05_2026.json")))
        reh = cfg.pop("rehearsal")
        for k, v in reh.items():
            cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
        sz = ref.sizes(cfg)
        p = jax.jit(lambda k: ref.init_params(sz, k))(jax.random.key(1))
        from benchmarks.harness.models import cohere2_moe as seam

        m = seam.build(sz, jnp.float32)
        chosen = []
        route = moe_experts.route

        def spy(*a, **kw):
            out = route(*a, **kw)
            chosen.append(np.asarray(out[0]))
            return out
        toks = np.random.default_rng(3).integers(0, sz["vocab"], (1, 32))
        moe_experts.route = spy
        try:
            m.forward(p, jnp.asarray(toks, jnp.int32))
        finally:
            moe_experts.route = route
        assert len(chosen) == sz["layers"]
        for i, layer in enumerate(chosen):
            sets = {tuple(sorted(r)) for r in layer}
            load = np.bincount(layer.reshape(-1), minlength=16)
            assert len(sets) > 1 and (load > 0).sum() > sz["top_k"], layer
            if i == 0:
                assert len(sets) > 16 and (load > 0).sum() == 16

    def test_fp8_control_moves_the_logits(self, params):
        toks = np.random.default_rng(5).integers(0, VOCAB, 64)
        want = reference(params, toks)
        low = reference(params, toks, precision="fp8")
        assert np.abs(low - want).max() > 50 * TOL["atol"]


class TestThroughTheEngine:
    """Greedy serving: every served token is the reference's best at the
    reference's logits of the served sequence."""

    @pytest.mark.parametrize("kw", [
        {}, {"kernel": "pallas"}, {"prefill_chunk": 32}, {"max_slots": 2}],
        ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items())
        or "default")
    def test_interleaved_sequences_and_reused_slots(self, model, params,
                                                    kw):
        """Four requests of different lengths, interleaved; with two
        slots the third and fourth take over slots whose rings held
        another sequence.  Each gets what it gets alone."""
        eng = engine(model, params, **kw)
        res = eng.run([Request(id=i, prompt=p, max_new_tokens=NEW,
                               arrival=0.0) for i, p in enumerate(PROMPTS)])
        for i, p in enumerate(PROMPTS):
            assert served_gap(params, p, res["outputs"][i]) < 1e-4
        alone = engine(model, params, **kw).run(
            [Request(id=0, prompt=PROMPTS[3], max_new_tokens=NEW,
                     arrival=0.0)])["outputs"][0]
        assert res["outputs"][3] == alone
        # no prefill program under the family's least bucket
        floor = min(cm.RING_BLOCK, eng.serve.prefill_chunk)
        assert min(shape[1] for shape in eng.dispatch_shapes
                   if shape[0] == "prefill") >= floor

    def test_caches_by_kind_and_the_dispatch_log(self, model, params):
        """A ring a window layer and slot, one paged pool for the full
        layer, a counter every layer; traced runs log the keys each kind
        of layer read and what the window spared."""
        eng = engine(model, params, trace="on")
        names = [sorted(p) for p in eng.pools]
        assert names == [["expert_count", "win_k_slot", "win_v_slot"]] * 3 \
            + [["expert_count", "k", "v"]]
        c, rows = model.cfg, eng.serve.max_slots + 1
        assert eng.pools[0]["win_k_slot"].shape == (rows, WINDOW, c.kv_width)
        assert eng.pools[3]["k"].shape == (49, 8, c.kv_width)
        assert eng.cache_block()["cache_bytes"]["window"] \
            == 3 * 2 * rows * WINDOW * c.kv_width * 4
        dispatch_log.reset()
        eng.run([Request(id=0, prompt=PROMPTS[2], max_new_tokens=3,
                         arrival=0.0)])
        log = dispatch_log.snapshot()["dispatches"]
        assert [r[1] for r in log] == ["prefill"] * 3 + ["decode"] * 2
        extra = [r[6] for r in log]
        # a 41-token prompt in chunks of 16, 16, 9, then positions 41, 42
        assert [e["full_keys"] for e in extra] == [
            136, 392, 333, 42, 43]
        # window 16: positions 0..15 see 1..16 keys, every later one 16
        assert [e["window_keys"] for e in extra] == [136, 256, 144, 16, 16]
        assert [e["window_keys_skipped"] for e in extra] == [
            0, 136, 189, 26, 27]
        assert [e["full_rows"] for e in extra] == [16, 32, 41, 42, 43]
        assert [e["window_rows"] for e in extra] == [16, 31, 24, 16, 16]
        dispatch_log.reset()


def _kv_case(rng, lens, S, H, Hkv, D=8, bs=8, NB=8):
    B = len(lens)
    nblocks = 1 + B * NB
    kp = rng.normal(size=(nblocks, bs, Hkv * D)).astype(np.float32)
    vp = rng.normal(size=(nblocks, bs, Hkv * D)).astype(np.float32)
    bt = np.zeros((B, NB), np.int32)
    nxt = 1
    for b, n in enumerate(lens):
        live = max(1, -(-(n + S) // bs))
        bt[b, :live] = range(nxt, nxt + live)
        nxt += live
    q = rng.normal(size=(B, H, S, D)).astype(np.float32)
    return [jnp.asarray(x) for x in (q, kp, vp, bt,
                                     np.asarray(lens, np.int32))]


class TestGroupedKernels:
    """The shared paged kernel's grouped-query bodies (interpreted)
    against the XLA anchor, ``G`` query heads a KV head: 1 is gpt_base's
    path, unchanged."""

    @pytest.mark.parametrize("G", [1, 2, 16])
    @pytest.mark.parametrize("window", [None, 5])
    @pytest.mark.parametrize("S, lens", [
        (1, [0, 7, 30, 45]),        # decode rows, one past several groups
        (8, [0, 16, 40]),           # prefill rows; with W=5 the last one's
        (16, [3, 32])])             # first visible block is block 4
    def test_parity_with_the_anchor(self, G, window, S, lens):
        H = max(4, 2 * G)
        q, kp, vp, bt, ln = _kv_case(np.random.default_rng(G + S), lens, S,
                                     H, H // G)
        want = paged_ops.attend(q, kp, vp, bt, ln, jnp.float32,
                                kernel="xla", window=window)
        got = pk.paged_attention_kernel(q, kp, vp, bt, ln, interpret=True,
                                        window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window", [None, 6])
    def test_prefill_tiles(self, monkeypatch, window):
        """A chunk cut into query tiles (a small tile budget): each tile
        walks its own blocks, and under a window starts at its own first
        visible one."""
        monkeypatch.setattr(pk, "GQA_TILE_ROWS", 8)
        assert pk.query_tile(24, 2) == 4
        q, kp, vp, bt, ln = _kv_case(np.random.default_rng(1), [3, 37], 24,
                                     4, 2, NB=9)
        want = paged_ops.attend(q, kp, vp, bt, ln, jnp.float32,
                                kernel="xla", window=window)
        got = pk.paged_attention_kernel(q, kp, vp, bt, ln, interpret=True,
                                        window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_window_list_starts_at_the_first_visible_block(self):
        """``work_list`` under a window: a tile's blocks start at the one
        holding its first query's first visible key, and end where they
        ended without it."""
        lens = jnp.asarray([3, 40, 0], jnp.int32)
        plain = paged_ops.work_list(lens, 8, 4, 2, 8, 8)
        win = paged_ops.work_list(lens, 8, 4, 2, 8, 8, window=5)

        def triples(lst):
            row, tile, blk, end, live = (np.asarray(x) for x in lst)
            return [(int(r), int(t), int(b), int(e)) for r, t, b, e in
                    zip(row[:live], tile[:live], blk[:live], end[:live])]
        a, b = triples(plain), triples(win)
        # row 1, tile 0: queries at 40..43 see keys from 36 on: block 4
        assert [x[2] for x in b if x[:2] == (1, 0)] == [4, 5]
        assert [x[2] for x in a if x[:2] == (1, 0)] == list(range(6))
        assert {x[3] for x in a if x[:2] == (1, 1)} \
            == {x[3] for x in b if x[:2] == (1, 1)}
        assert len(b) < len(a)

    def test_a_pool_holds_whole_kv_heads(self):
        """A pool's width is whole KV heads that divide the query heads,
        or it is refused in words."""
        assert paged_ops.kv_heads(jnp.zeros((1, 4, 1, 8)),
                                  jnp.zeros((3, 8, 16))) == 2
        q = jnp.zeros((1, 4, 1, 8))
        with pytest.raises(ValueError, match="no whole number"):
            paged_ops.kv_heads(q, jnp.zeros((3, 8, 12)))


class TestRefusals:
    @pytest.mark.parametrize("kw, words", [
        ({"prefix_cache": "on"}, "prefix cache on: .* ring"),
        ({"prefix_cache": "on", "prefix_gen": "on"}, "prefix cache on"),
        ({"prefix_cache": "on", "kv_tier": "host"}, "prefix cache on"),
        ({"speculative": "ngram"}, "speculative ngram: .* rings"),
        ({"speculative": "draft-model"}, "no draft model of this family"),
        ({"mixed_batch": "on"}, "mixed_batch on: .* two phases"),
        ({"tp": 2}, "rings are addressed by slot"),
        ({"kv_dtype": "int8"}, "kv_dtype int8: .* no quantised form"),
        ({"kv_dtype": "int4"}, "kv_dtype int4"),
    ], ids=lambda x: "-".join(f"{k}={v}" for k, v in x.items())
        if isinstance(x, dict) else None)
    def test_refused_in_words(self, model, params, kw, words):
        with pytest.raises(ValueError, match=words):
            PagedDecodeEngine(model, params, ServeConfig(**kw))

    def test_config_refuses_what_is_not_built(self):
        with pytest.raises(ValueError, match="do not group"):
            cm.Cohere2MoeConfig(num_key_value_heads=7)
        with pytest.raises(ValueError, match="layer_types"):
            cm.Cohere2MoeConfig(num_hidden_layers=2,
                                layer_types=("dense", "full_attention"))
        with pytest.raises(ValueError, match="ring blocks"):
            cm.Cohere2MoeConfig(sliding_window=4000)
        with pytest.raises(ValueError, match="experts_held"):
            cm.Cohere2MoeConfig(experts_held=(120, 16))

    def test_forward_needs_the_slots(self, model, params):
        pools = paged_cache.init_pools(model.cfg, 5, 8, model=model,
                                       max_slots=1)
        with pytest.raises(ValueError, match="needs slots="):
            model.forward_paged(params, jnp.zeros((1, 1), jnp.int32), pools,
                                jnp.zeros((1, 4), jnp.int32),
                                jnp.zeros((1,), jnp.int32))


def test_serving_entry_point_serves_the_family():
    """``python -m mpi_tensorflow_tpu.serving --model cohere2_moe --tiny``:
    the normal path end to end, and a refused option exits 2 in the
    family's words."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "mpi_tensorflow_tpu.serving", "--model",
           "cohere2_moe", "--tiny", "--precision", "fp32", "--num-requests",
           "3", "--prompt-max", "40", "--output-max", "6", "--block-size",
           "8", "--prefill-chunk", "16"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=170)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["model"] == "cohere2_moe_tiny"
    assert set(line["statuses"].values()) == {"ok"}
    assert line["tokens"] == line["tokens_requested"]
    assert line["zero_recompile_steady_state"] is True
    bad = subprocess.run(cmd + ["--kv-dtype", "int8"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=170)
    assert bad.returncode == 2 and "no quantised form" in bad.stderr


def test_benchmark_cell_rehearses_correct():
    """The cell's CPU rehearsal: the serve driver, this model's harness
    file, the reference and the check, end to end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "command_a_plus_05_2026.serve_closed32_p4k_32k", "--seed",
         "3400000011", "--seconds", "2", "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=170)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
