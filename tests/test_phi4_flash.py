"""models/phi4_flash.Phi4FlashLm against its plain reference
(benchmarks/reference/phi4_flash.py: float32, no cache, the scan a plain
``lax.scan`` over tokens, masks written out) and through
``PagedDecodeEngine``: the three kinds of cache side by side (state by
slot, window rings, ONE paged pool that the cross layers read), the
cross-decoder skipped on every prefill lane but the taken one, the
decode kernel against its XLA anchor, the refusals, no recompile after
prewarm.  Tiny sizes, seeded weights, float32; the window (8) is shorter
than every context and the prefill chunk (8) cuts the scans.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4_flash as ref
from mpi_tensorflow_tpu.models import phi4_flash as pf
from mpi_tensorflow_tpu.ops import diff_attention as da
from mpi_tensorflow_tpu.ops.paged_attention import write_ring
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, Request,
                                        ServeConfig)
from mpi_tensorflow_tpu.serving import paged_cache
from mpi_tensorflow_tpu.utils import dispatch_log

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, WINDOW = 256, 8
SZ = {"vocab": VOCAB, "hidden": 64, "layers": 8, "heads": 4, "kv_heads": 2,
      "mlp": 128, "window": WINDOW, "eps": 1e-5, "positions": 512,
      "d_state": 16, "d_conv": 4, "expand": 2}
TOL = dict(atol=2e-5, rtol=2e-4)


def make_model():
    c = pf.TINY          # the reference is given the same sizes, as SZ
    assert (c.vocab_size, c.hidden_size, c.num_hidden_layers,
            c.num_attention_heads, c.num_key_value_heads,
            c.intermediate_size, c.sliding_window, c.d_state, c.d_conv,
            c.mamba_expand) == tuple(SZ[k] for k in (
                "vocab", "hidden", "layers", "heads", "kv_heads", "mlp",
                "window", "d_state", "d_conv", "expand"))
    return pf.Phi4FlashLm(c)


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


def reference(params, seq, positions=None):
    toks = np.zeros((64,), np.int32)
    toks[:len(seq)] = seq
    return ref.next_token_logits(
        params, toks, np.arange(len(seq)) if positions is None
        else np.asarray(positions), SZ)


def served_gap(params, prompt, out):
    """How far the served tokens lie below the reference's best, at the
    reference's own logits of the served sequence (the benchmark's
    ``served_logit_gap``): 0 where every token is the reference's."""
    seq = list(prompt) + list(out[:-1])
    lg = reference(params, seq, range(len(prompt) - 1, len(seq)))
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def engine(model, params, **kw):
    base = dict(num_blocks=33, block_size=4, max_slots=4, max_seq_len=64,
                prefill_chunk=8, kernel="xla")
    base.update(kw)
    return PagedDecodeEngine(model, params, ServeConfig(**base))


RNG = np.random.default_rng(7)
PROMPTS = [RNG.integers(0, VOCAB, n).tolist() for n in (5, 13, 21, 9)]
NEW = 12


class TestAgainstReference:
    def test_layout(self, model):
        kinds = [model.cfg.layer_kind(i) for i in range(8)]
        assert kinds == ["mamba", "window", "mamba", "window", "mamba",
                         "full", "gmu", "cross"]
        full = pf.Phi4FlashConfig()
        kinds = [full.layer_kind(i) for i in range(32)]
        assert [kinds.count(k) for k in
                ("mamba", "window", "full", "gmu", "cross")] \
            == [9, 8, 1, 7, 7]
        assert kinds[16:20] == ["mamba", "full", "gmu", "cross"]
        assert (full.d_inner, full.dt_rank, full.head_dim,
                full.kv_width) == (5120, 160, 64, 1280)

    def test_full_forward_equals_reference(self, model, params):
        toks = np.random.default_rng(5).integers(
            0, VOCAB, (1, 40)).astype(np.int32)
        got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(toks)))
        np.testing.assert_allclose(got[0], reference(params, toks[0]),
                                   **TOL)

    def test_fp8_control_moves_the_logits(self, params):
        toks = np.random.default_rng(5).integers(0, VOCAB, 64)
        want = reference(params, toks)
        low = ref.next_token_logits(params, toks.astype(np.int32),
                                    np.arange(64), SZ, precision="fp8")
        assert np.abs(low - want).max() > 50 * 2e-5

    @pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
    def test_chunked_prefill_then_decode_logits(self, model, params,
                                                kernel):
        """Two rows in slots 2 and 0: three prefill chunks of 8 (two
        chunk boundaries inside each scan, the context past the window
        from the second on), then six decode steps, through
        ``forward_paged`` as the engine calls it.  Every position's
        logits equal the reference's full causal forward: the chunks'
        through the all-lanes form, the decode steps' through the three
        caches."""
        bs, nb = 4, 8
        seqs = np.random.default_rng(11).integers(
            0, VOCAB, (2, 30)).astype(np.int32)
        pools = paged_cache.init_pools(model.cfg, 1 + 2 * nb, bs,
                                       model=model, max_slots=3)
        tables = jnp.asarray(1 + np.arange(2 * nb, dtype=np.int32)
                             .reshape(2, nb))
        slots = jnp.asarray([2, 0], jnp.int32)
        fwd = jax.jit(lambda p, t, pl, ln: model.forward_paged(
            p, t, pl, tables, ln, kernel=kernel, slots=slots))
        got, at = [], 0
        for width in (8, 8, 8, 1, 1, 1, 1, 1, 1):
            lg, pools = fwd(params, jnp.asarray(seqs[:, at:at + width]),
                            pools, jnp.full((2,), at, jnp.int32))
            got.append(np.asarray(lg))
            at += width
        got = np.concatenate(got, axis=1)
        for b in range(2):
            np.testing.assert_allclose(got[b], reference(params, seqs[b]),
                                       **TOL)

    def test_taken_lane_equals_the_all_lanes_form(self, model, params):
        """The skipped cross-decoder: with ``take`` the chunk's logits
        are those of the all-lanes form at that lane, the pools the
        same, and a chunk that takes no lane gives none."""
        seq = np.random.default_rng(13).integers(
            0, VOCAB, (1, 8)).astype(np.int32)
        tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        slots = jnp.asarray([1], jnp.int32)
        valid = jnp.arange(8)[None] < 6            # two padding lanes

        def run(take):
            pools = paged_cache.init_pools(model.cfg, 5, 4, model=model,
                                           max_slots=2)
            return jax.jit(lambda p, pl: model.forward_paged(
                p, jnp.asarray(seq), pl, tables, jnp.zeros((1,), jnp.int32),
                valid=valid, slots=slots, take=take))(params, pools)

        every, pools_all = run(None)
        one, pools_one = run(jnp.asarray([5], jnp.int32))
        none, _ = run(jnp.asarray([-1], jnp.int32))
        assert one.shape == (1, 1, VOCAB) and every.shape == (1, 8, VOCAB)
        np.testing.assert_allclose(np.asarray(one[0, 0]),
                                   np.asarray(every[0, 5]), **TOL)
        np.testing.assert_allclose(
            np.asarray(one[0, 0]), reference(params, seq[0, :6])[5], **TOL)
        assert not np.asarray(none).any()
        for a, b in zip(jax.tree.leaves(pools_all),
                        jax.tree.leaves(pools_one)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestThroughTheEngine:
    """Greedy serving: every served token is the reference's best at the
    reference's logits of the served sequence (a gap of 0 up to float32
    rounding: logits, not tokens, decide)."""

    @pytest.mark.parametrize("kw", [
        {}, {"kernel": "pallas"}, {"prefill_chunk": 32},
        {"max_slots": 2}], ids=lambda kw: "-".join(
            f"{k}={v}" for k, v in kw.items()) or "default")
    def test_interleaved_sequences_and_reused_slots(self, model, params,
                                                    kw):
        """Four requests of different lengths, interleaved; with two
        slots the third and fourth take over slots that held another
        sequence's state and rings.  Each gets what it gets alone."""
        eng = engine(model, params, **kw)
        res = eng.run([Request(id=i, prompt=p, max_new_tokens=NEW,
                               arrival=0.0) for i, p in enumerate(PROMPTS)])
        for i, p in enumerate(PROMPTS):
            assert served_gap(params, p, res["outputs"][i]) < 1e-5
        alone = engine(model, params, **kw).run(
            [Request(id=0, prompt=PROMPTS[3], max_new_tokens=NEW,
                     arrival=0.0)])["outputs"][0]
        assert res["outputs"][3] == alone
        assert res["caches"]["state_resets"] == len(PROMPTS)
        assert eng.load_signals()["state_resets"] == len(PROMPTS)

    def test_eviction_and_restart(self, model, params):
        """Two sequences outgrow an 8-block pool: one is evicted and
        re-prefilled from position 0, which starts its slot's state from
        zero again; both end as the reference has them."""
        eng = engine(model, params, num_blocks=9, block_size=2,
                     max_slots=2, max_seq_len=12, prefill_chunk=2)
        rng = np.random.default_rng(8)
        pa = rng.integers(0, VOCAB, 2).tolist()
        pb = rng.integers(0, VOCAB, 11).tolist()
        res = eng.run([Request(0, pa, 10, arrival=0.0),
                       Request(1, pb, 1, arrival=0.0)])
        assert eng.sched.evictions >= 1
        assert res["caches"]["state_resets"] >= 3
        assert served_gap(params, pa, res["outputs"][0]) < 1e-5
        assert served_gap(params, pb, res["outputs"][1]) < 1e-5
        eng.allocator.check()
        assert eng.allocator.num_used == 0

    def test_journal_replay_re_prefills(self, model, params, tmp_path):
        """Crash recovery replays by re-prefill from the journal (prompt
        plus delivered tokens, from position 0): state needs no
        snapshot for it."""
        from mpi_tensorflow_tpu.serving import recovery

        reqs = [Request(id=i, prompt=p, max_new_tokens=NEW, arrival=0.0)
                for i, p in enumerate(PROMPTS[:2])]
        path = str(tmp_path / "j")
        first = recovery.run_with_replay(
            lambda: engine(model, params), reqs, journal_path=path)
        again = recovery.run_with_replay(
            lambda: engine(model, params), reqs, journal_path=path)
        assert again["outputs"] == first["outputs"]
        for i, p in enumerate(PROMPTS[:2]):
            assert served_gap(params, p, first["outputs"][i]) < 1e-5

    def test_serving_loop_settles_the_heap(self, model, params):
        """The loop's first iteration freezes what tracing left alive, so
        a later full collection does not walk it."""
        import gc

        engine(model, params).run([Request(id=0, prompt=PROMPTS[0],
                                           max_new_tokens=2, arrival=0.0)])
        assert gc.get_freeze_count() > 100_000

    def test_no_recompile_after_prewarm(self, model, params):
        eng = engine(model, params)
        eng.prewarm_decode()
        S = 1
        while S <= eng.serve.prefill_chunk:
            _, eng.pools = eng._prefill_fn(
                eng.params, eng.pools, jnp.zeros((1, S), jnp.int32),
                jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.zeros((1, eng.serve.max_blocks_per_seq), jnp.int32))
            S *= 2
        warm = eng.compile_counts()
        assert None not in warm.values()
        eng.reset()
        eng.run([Request(id=i, prompt=p, max_new_tokens=NEW, arrival=0.0)
                 for i, p in enumerate(PROMPTS)])
        assert eng.compile_counts() == warm

    def test_caches_by_kind_and_the_dispatch_log(self, model, params):
        """One pool for the full layer (none a cross layer), a ring a
        window layer and a slot, state a mamba layer and a slot; traced
        runs log what each dispatch obliged of them."""
        eng = engine(model, params, trace="on")
        names = [sorted(p) for p in eng.pools]
        assert names == [["conv_slot", "ssm_slot"],
                         ["win_k_slot", "win_v_slot"]] * 2 \
            + [["conv_slot", "ssm_slot"], ["k", "v"], [], []]
        c, rows = model.cfg, eng.serve.max_slots + 1
        assert eng.pools[1]["win_k_slot"].shape == (rows, WINDOW, c.kv_width)
        assert eng.pools[0]["ssm_slot"].shape == (rows, c.d_state, c.d_inner)
        assert eng.cache_block()["cache_bytes"] == {
            "paged": 2 * 33 * 4 * c.kv_width * 4,
            "window": 2 * 2 * rows * WINDOW * c.kv_width * 4,
            "state": 3 * rows * (c.d_state + c.d_conv - 1) * c.d_inner * 4}
        dispatch_log.reset()
        res = eng.run([Request(id=0, prompt=PROMPTS[2], max_new_tokens=3,
                               arrival=0.0)])
        log = dispatch_log.snapshot()["dispatches"]
        assert [r[1] for r in log] == ["prefill"] * 3 + ["decode"] * 2
        extra = [r[6] for r in log]
        # a 21-token prompt in chunks of 8, 8, 5: the cross-decoder runs
        # on the last lane of the last chunk alone
        assert [e["skipped_lanes"] for e in extra] == [8, 8, 4, 0, 0]
        assert [e["scanned"] for e in extra] == [8, 8, 5, 1, 1]
        assert [e["full_keys"] for e in extra] == [0, 0, 21, 22, 23]
        # window 8: positions 0..7 see 1..8 keys, every later one 8
        assert [e["window_keys"] for e in extra] == [36, 64, 40, 8, 8]
        assert res["caches"]["pool_occupancy"] == 0.0
        dispatch_log.reset()


class TestKernel:
    @pytest.mark.parametrize("store", ["paged", "ring"])
    def test_decode_kernel_against_its_anchor(self, store):
        """``decode_attention`` (interpreted) against ``attention_xla``
        over gathered rows: a paged store with rows of uneven length and
        a slack row, and a ring (one block a row, addressed by slot)."""
        rng = np.random.default_rng(3)
        B, Hq, D, KW = 5, 8, 16, 4 * 16
        bs, NB = (4, 6) if store == "paged" else (16, 1)
        blocks = 1 + B * NB
        k = jnp.asarray(rng.normal(size=(blocks, bs, KW)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(blocks, bs, KW)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
        if store == "paged":
            lengths = np.asarray([0, 3, 23, 11, 0], np.int32)
            table = 1 + np.arange(B * NB, dtype=np.int32).reshape(B, NB)
            table[4] = 0                            # a slack row
        else:
            lengths = np.asarray([0, 15, 7, 15, 3], np.int32)
            table = np.asarray([[3], [1], [4], [2], [5]], np.int32)
        got = da.decode_attention(q, k, v, jnp.asarray(table),
                                  jnp.asarray(lengths), scale=D ** -0.5,
                                  interpret=True)
        want = da.decode_attend(q, k, v, jnp.asarray(table),
                                jnp.asarray(lengths), D ** -0.5,
                                kernel="xla")
        assert got.shape == (B, Hq, 2 * D)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_anchor_pairs_heads_as_the_equations_do(self):
        """Query head ``h`` against key head ``2 (h // 4) + h % 2`` and
        the value pair ``h // 4``, written out head by head."""
        rng = np.random.default_rng(4)
        S, Hq, D = 6, 8, 4
        q = rng.normal(size=(1, S, Hq, D)).astype(np.float32)
        k = rng.normal(size=(1, S, Hq // 2 * D)).astype(np.float32)
        v = rng.normal(size=(1, S, Hq // 2 * D)).astype(np.float32)
        vis = np.tril(np.ones((S, S), bool))[None]
        got = np.asarray(da.attention_xla(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(vis), 0.5))
        kh = k.reshape(S, Hq // 2, D)
        for h in range(Hq):
            s = q[0, :, h] @ kh[:, 2 * (h // 4) + h % 2].T * 0.5
            s = np.where(vis[0], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            pair = v[0, :, 2 * D * (h // 4):2 * D * (h // 4 + 1)]
            np.testing.assert_allclose(got[0, :, h], p @ pair, atol=1e-5)

    def test_ring_holds_the_window(self):
        """After a chunk is written, entry ``p % W`` holds position
        ``p`` for the last ``W`` positions; a chunk longer than the ring
        leaves its last ``W`` lanes; padding lanes touch the slack row
        alone."""
        W, KW = 4, 2
        ring = jnp.zeros((3, W, KW))
        rows = jnp.arange(1, 7, dtype=jnp.float32)[None, :, None] \
            * jnp.ones((1, 6, KW))
        pos = 3 + jnp.arange(6)[None]
        valid = jnp.arange(6)[None] < 5             # positions 3..7
        out = np.asarray(write_ring(ring, rows, jnp.asarray([1]), pos,
                                       valid))
        assert out[1, :, 0].tolist() == [2.0, 3.0, 4.0, 5.0]   # 4,5,6,7
        assert not out[0].any()
        held = np.asarray(da.ring_positions(jnp.asarray([0, 3, 8]), W))
        assert held.tolist() == [[-1] * 4, [0, 1, 2, -1], [4, 5, 6, 7]]


@pytest.fixture(scope="module")
def tpu_device():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:   # no libtpu on this host
        pytest.skip(f"no deviceless TPU topology available: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def test_kernel_compiles_at_the_served_geometry(tpu_device):
    """Mosaic, without a chip: the decode kernel over the cell's pool
    (blocks of 256) and rings (512), 40 query heads of 64, bfloat16."""
    da.probe_compile.cache_clear()
    da.probe_compile("bfloat16", 40, 64, 256, 512, sharding=tpu_device)


class TestRefusals:
    @pytest.mark.parametrize("kw, words", [
        ({"prefix_cache": "on"}, "prefix_cache on: .* no snapshot"),
        ({"prefix_cache": "on", "prefix_gen": "on"},
         "prefix_gen on: .* generated block's edge"),
        ({"prefix_cache": "on", "kv_tier": "host"},
         "kv_tier host: .* demoted block"),
        ({"speculative": "ngram"}, "speculative ngram: .* roll the "
                                   "state-space state back"),
        ({"speculative": "draft-model"}, "speculative draft-model"),
        ({"mixed_batch": "on"}, "mixed_batch on: .* one lane a row"),
        ({"tp": 2}, "scan runs over all of d_inner"),
        ({"kv_dtype": "int8"}, "kv_dtype int8: .* no quantised form"),
        ({"kv_dtype": "int4"}, "kv_dtype int4"),
    ], ids=lambda x: "-".join(f"{k}={v}" for k, v in x.items())
        if isinstance(x, dict) else None)
    def test_refused_in_words(self, model, params, kw, words):
        with pytest.raises(ValueError, match=words):
            PagedDecodeEngine(model, params, ServeConfig(**kw))

    def test_config_refuses_what_is_not_built(self):
        with pytest.raises(ValueError, match="pairs heads"):
            pf.Phi4FlashConfig(num_key_value_heads=40)
        with pytest.raises(ValueError, match="mb_per_layer 2"):
            pf.Phi4FlashConfig(mb_per_layer=4)

    def test_forward_needs_the_slots(self, model, params):
        pools = paged_cache.init_pools(model.cfg, 5, 4, model=model,
                                       max_slots=1)
        with pytest.raises(ValueError, match="needs slots="):
            model.forward_paged(params, jnp.zeros((1, 1), jnp.int32), pools,
                                jnp.zeros((1, 4), jnp.int32),
                                jnp.zeros((1,), jnp.int32))


def test_serving_entry_point_serves_the_family():
    """``python -m mpi_tensorflow_tpu.serving --model phi4_flash --tiny``:
    the normal path end to end, and a refused option exits 2 in the
    family's words."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "mpi_tensorflow_tpu.serving", "--model",
           "phi4_flash", "--tiny", "--precision", "fp32", "--num-requests",
           "3", "--prompt-max", "12", "--output-max", "6"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=170)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["model"] == "phi4_flash_tiny"
    assert set(line["statuses"].values()) == {"ok"}
    assert line["tokens"] == line["tokens_requested"]
    assert line["zero_recompile_steady_state"] is True
    bad = subprocess.run(cmd + ["--prefix-cache", "on"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=170)
    assert bad.returncode == 2 and "no snapshot" in bad.stderr


def test_benchmark_cell_rehearses_correct():
    """The cell's CPU rehearsal: the serve driver, this model's harness
    file, the reference and the check, end to end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "phi4_mini_flash_reasoning.serve_closed128_p256_o8k", "--seed",
         "2200000011", "--seconds", "2", "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=170)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
