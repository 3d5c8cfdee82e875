"""Fused Pallas paged-attention kernel: parity, masking, dispatch.

The kernel (ops/paged_attention_kernel) must be drop-in equivalent to
the XLA gather path (ops/paged_attention.attend kernel="xla") — the
tier-1 suite pins it in interpret mode on CPU across the engine's
bucket shapes, including the lanes the masking contract exists for:
null-block scatter targets, bucket-slack rows, ragged lengths, and
chunked prefill.  The end-to-end pin is greedy token-identity to
``CausalLm.generate`` with ``--kernel pallas``, and a jaxpr
inspection proving the jitted decode step materializes NO gathered
``(B, H, NB*block_size, D)`` view.

Numerics run in interpret mode on CPU; ``TestMosaicCompile`` runs the
REAL Mosaic compiler on every served variant through a deviceless TPU
topology (libtpu, no chip).  Numerics on the chip are
``chip_smoke.py``'s kernel phase.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jitted import generate_ref as _generate_ref
from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.ops import paged_attention_kernel as pk
from mpi_tensorflow_tpu.serving import PagedDecodeEngine, Request, ServeConfig
from mpi_tensorflow_tpu.serving.paged_cache import init_pools

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
ROPE = dataclasses.replace(TINY, pos_kind="rope")


def _case(rng, B, NB, bs, S, H=2, D=8, ragged=True, poison=0.0,
          lens=None):
    """One randomized kernel-vs-XLA input set, the pools in the stored
    geometry ``(nblocks, bs, H*D)``.  ``lens`` gives every row's length
    instead of the populations below.

    Rows cycle through the interesting populations: full table, ragged
    partial table (null-block tail), and — when B allows — a bucket-
    slack row (all-null table, length 0).  ``poison`` overwrites every
    lane the masking contract must hide (the null block, plus allocated
    lanes at positions >= length + S) with a huge finite value, so any
    masking drift becomes a loud numeric blowup instead of a subtle
    diff.
    """
    nblocks = 1 + B * NB
    k_pool = rng.normal(size=(nblocks, bs, H * D)).astype(np.float32)
    v_pool = rng.normal(size=(nblocks, bs, H * D)).astype(np.float32)
    bt = np.zeros((B, NB), np.int32)
    lengths = np.zeros((B,), np.int32)
    nxt = 1
    for b in range(B):
        if lens is not None:
            lengths[b] = lens[b]
        elif b == B - 1 and B > 2:
            continue                     # bucket-slack row: all-null, len 0
        elif ragged and b % 2 == 1:
            # ragged: a partial allocation with a null-block tail
            lengths[b] = int(rng.integers(0, max(1, (NB - 1) * bs - S + 1)))
        else:
            lengths[b] = NB * bs - S     # full table
        nb_live = max(1, -(-(lengths[b] + S) // bs))
        bt[b, :nb_live] = range(nxt, nxt + nb_live)
        nxt += nb_live
    if poison:
        k_pool[0] = v_pool[0] = poison   # the null block is never visible
        for b in range(B):
            for j in range(NB):
                if bt[b, j] == 0:
                    continue
                base = j * bs
                for o in range(bs):
                    if base + o >= lengths[b] + S:
                        k_pool[bt[b, j], o] = poison
                        v_pool[bt[b, j], o] = poison
    q = rng.normal(size=(B, H, S, D)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(bt), jnp.asarray(lengths))


def _assert_parity(q, k_pool, v_pool, bt, lengths, dead_rows=()):
    want = paged_ops.attend(q, k_pool, v_pool, bt, lengths, jnp.float32,
                            kernel="xla")
    got = pk.paged_attention_kernel(q, k_pool, v_pool, bt, lengths,
                                    interpret=True)
    w, g = np.array(want), np.array(got)      # copies: rows get zeroed
    for b in dead_rows:          # all-null rows emit garbage both ways;
        w[b] = g[b] = 0.0        # the engine discards them — exclude
    np.testing.assert_allclose(g, w, rtol=2e-6, atol=2e-6)


class TestKernelParity:
    """Interpret-mode kernel vs the XLA gather path, elementwise."""

    # block 16 is a group of 8 a step, block 4 of 32: table widths under
    # a group (1, 3; B = NB = 1 fills the list to its bound), of one
    # group, between groups (12: the last group's entries are clamped
    # to the table's edge) and of eight
    @pytest.mark.parametrize("B,NB,bs", [(1, 1, 4), (2, 2, 4), (4, 4, 4),
                                         (8, 2, 8), (2, 4, 16),
                                         (1, 1, 16), (4, 3, 16),
                                         (4, 8, 16), (4, 12, 16),
                                         (3, 64, 16), (4, 40, 4)])
    def test_decode_parity_across_bucket_shapes(self, B, NB, bs):
        rng = np.random.default_rng(B * 100 + NB * 10 + bs)
        dead = (B - 1,) if B > 2 else ()
        _assert_parity(*_case(rng, B, NB, bs, S=1), dead_rows=dead)

    @pytest.mark.parametrize("live", [1, 7, 8, 9, 16, 17])
    def test_decode_parity_at_group_edges(self, live):
        """Rows of fewer live blocks than a group, exactly one group,
        one block into the next: beside a full row, under a table the
        group does not divide, every hidden lane poisoned."""
        bs, NB = 16, 20
        G = paged_ops.step_blocks(1, jnp.zeros((1, bs, 16), jnp.float32))
        assert G == 8
        rng = np.random.default_rng(live)
        lens = [live * bs - 1, NB * bs - 1, (live - 1) * bs]
        case = _case(rng, 3, NB, bs, S=1, lens=lens, poison=1e30)
        steps = paged_ops.paged_work(case[4], 1, bs, NB, G)[3]
        assert int(steps) == -(-live // G) * 2 + -(-NB // G)
        _assert_parity(*case)

    @pytest.mark.parametrize("S", [2, 4, 8])
    def test_chunked_prefill_parity(self, S):
        rng = np.random.default_rng(S)
        q, kp, vp, bt, lens = _case(rng, 2, 4, 4, S=S)
        want = paged_ops.attend(q, kp, vp, bt, lens, jnp.float32,
                                kernel="xla")
        got = pk.paged_prefill_attention(q, kp, vp, bt, lens,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)

    def test_masked_lanes_cannot_leak(self):
        """Null-block lanes and beyond-length lanes hold a huge finite
        poison: any masking drift in either lowering explodes the
        outputs instead of shifting them by epsilon."""
        rng = np.random.default_rng(42)
        case = _case(rng, 4, 3, 4, S=1, poison=1e30)
        _assert_parity(*case, dead_rows=(3,))
        assert np.all(np.isfinite(np.asarray(
            pk.paged_attention_kernel(*case, interpret=True))))

    def test_a_groups_dead_tail_cannot_leak(self):
        """The decode body copies a whole group: a last group's dead
        tail comes from the null block and from a live block's lanes
        past the row's length, all poisoned here."""
        rng = np.random.default_rng(43)
        case = _case(rng, 4, 16, 16, S=1, poison=1e30)
        assert np.asarray(case[3])[1, -1] == 0      # a null-block tail
        _assert_parity(*case, dead_rows=(3,))
        assert np.all(np.isfinite(np.asarray(
            pk.paged_attention_kernel(*case, interpret=True))))

    def test_bucket_slack_rows_cost_one_block(self):
        """A slack row (all-null table, length 0) must not disturb live
        rows — and its garbage output is finite, exactly like the XLA
        path's."""
        rng = np.random.default_rng(7)
        q, kp, vp, bt, lens = _case(rng, 4, 4, 4, S=1)
        assert np.all(np.asarray(bt)[3] == 0)          # the slack row
        _assert_parity(q, kp, vp, bt, lens, dead_rows=(3,))

    def test_decode_wrapper_rejects_multi_token(self):
        rng = np.random.default_rng(0)
        q, kp, vp, bt, lens = _case(rng, 1, 1, 4, S=2)
        with pytest.raises(ValueError, match="one query token"):
            pk.paged_decode_attention(q, kp, vp, bt, lens, interpret=True)

    def test_kernel_matches_contiguous_reference(self):
        """Triangulation: kernel vs a straight dense fp32 softmax over
        the unpacked live lanes (no shared code with either paged
        path)."""
        rng = np.random.default_rng(3)
        B, NB, bs, H, D = 2, 3, 4, 2, 8
        q, kp, vp, bt, lens = _case(rng, B, NB, bs, S=1, ragged=True)
        got = np.asarray(pk.paged_attention_kernel(q, kp, vp, bt, lens,
                                                   interpret=True))
        kp, vp, bt, lens = map(np.asarray, (kp, vp, bt, lens))
        # a slot's row apart by head: (nblocks, bs, H, D)
        kp, vp = (x.reshape(x.shape[:2] + (H, D)) for x in (kp, vp))
        for b in range(B):
            L = int(lens[b]) + 1
            ks = np.concatenate([kp[bt[b, j]] for j in range(NB)])[:L]
            vs = np.concatenate([vp[bt[b, j]] for j in range(NB)])[:L]
            ks, vs = ks.swapaxes(0, 1), vs.swapaxes(0, 1)   # (H, L, D)
            s = np.einsum("hd,hld->hl", np.asarray(q)[b, :, 0], ks)
            s = s * (D ** -0.5)
            p = np.exp(s - s.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            ref = np.einsum("hl,hld->hd", p, vs)
            np.testing.assert_allclose(got[b, :, 0], ref,
                                       rtol=2e-5, atol=2e-5)


def _lengths_of(kind, rng, B, S, bs, NB):
    """Row lengths of one population; ``length + S`` is what a row must
    reach (its own tokens are in the pool before attention)."""
    cap = NB * bs
    if kind == "zero":                    # slack rows only
        return np.zeros((B,), np.int32)
    if kind == "ragged":
        return rng.integers(0, 2 * cap, B).astype(np.int32)
    if kind == "edge":                    # on, one short of, one past
        k = rng.integers(1, NB + 1, B) * bs
        return np.maximum(k + rng.integers(-1, 2, B) - S, 0).astype(np.int32)
    return np.full((B,), max(cap - S, 0), np.int32)      # full tables


class TestWorkList:
    """``ops/paged_attention.work_list`` — the one list both Pallas
    attention kernels walk — against a brute-force enumeration."""

    @pytest.mark.parametrize("kind", ["zero", "ragged", "edge", "full"])
    @pytest.mark.parametrize("NB", [4, 64])
    @pytest.mark.parametrize("bs", [8, 16])
    @pytest.mark.parametrize("S,tq", [(1, 1), (64, 64), (64, 8)])
    def test_matches_brute_force(self, S, tq, bs, NB, kind):
        B, NT = 5, -(-S // tq)
        rng = np.random.default_rng([S, tq, bs, NB, len(kind)])
        lens = _lengths_of(kind, rng, B, S, bs, NB)
        want = []
        for b in range(B):
            for t in range(NT):
                last = min((t + 1) * tq, S)
                need = min(max(-(-(int(lens[b]) + last) // bs), 1), NB)
                want += [(b, t, j, need) for j in range(need)]
        row, tile, blk, n, live = paged_ops.work_list(
            jnp.asarray(lens), S, tq, NT, bs, NB)
        assert row.shape == (B * NT * NB,) and int(live) == len(want)
        got = list(zip(*(np.asarray(x)[:len(want)].tolist()
                         for x in (row, tile, blk, n))))
        assert got == want
        if NT == 1:
            # the K/V kernel's view of it
            prow, pblk, pn, plive = paged_ops.paged_work(
                jnp.asarray(lens), S, bs, NB)
            assert int(plive) == int(live)
            for a, b in ((prow, row), (pblk, blk), (pn, n)):
                # one valid entry more: the pipeline reads a step ahead
                np.testing.assert_array_equal(a, np.append(b, 0))

    @pytest.mark.parametrize("kind", ["zero", "ragged", "edge", "full"])
    @pytest.mark.parametrize("NB", [1, 3, 8, 12, 64])
    @pytest.mark.parametrize("G", [1, 4, 8])
    def test_grouped_list_matches_brute_force(self, G, NB, kind):
        """``paged_work`` with ``G`` table entries a step: a row's live
        blocks in groups of ``G``, a slack row's one step, the list one
        entry longer than its bound."""
        B, bs = 5, 16
        rng = np.random.default_rng([G, NB, len(kind)])
        lens = _lengths_of(kind, rng, B, 1, bs, NB)
        want = []
        for b in range(B):
            blocks = min(max(-(-(int(lens[b]) + 1) // bs), 1), NB)
            groups = -(-blocks // G)
            want += [(b, j, groups) for j in range(groups)]
        row, grp, n, live = paged_ops.paged_work(jnp.asarray(lens), 1, bs,
                                                 NB, G)
        assert row.shape == (B * -(-NB // G) + 1,)
        assert int(live) == len(want)
        got = list(zip(*(np.asarray(x)[:len(want)].tolist()
                         for x in (row, grp, n))))
        assert got == want

    @pytest.mark.parametrize("bs,NB", [(16, 64), (256, 40)])
    def test_one_block_steps_are_the_list_as_it_was(self, bs, NB):
        """``ops/diff_attention`` calls ``paged_work(lengths, 1, bs,
        NB)`` and walks one block a step: entry for entry ``work_list``
        with one entry more, whatever the decode body's group."""
        from mpi_tensorflow_tpu.ops import diff_attention

        lens = jnp.asarray(_lengths_of(
            "ragged", np.random.default_rng(bs), 6, 1, bs, NB))
        row, _, blk, n, live = paged_ops.work_list(lens, 1, 1, 1, bs, NB)
        got = diff_attention.decode_work(lens, bs, NB)
        assert int(got[3]) == int(live)
        for a, b in zip(got[:3], (row, blk, n)):
            np.testing.assert_array_equal(a, np.append(b, 0))

    @pytest.mark.parametrize("bs,lanes,dtype,want", [
        (4, 16, jnp.float32, 32), (16, 768, jnp.bfloat16, 8),
        (64, 768, jnp.bfloat16, 2), (128, 768, jnp.bfloat16, 1),
        (256, 768, jnp.bfloat16, 1),
        # the four VMEM slots of 128 keys pass the budget: halved
        (16, 16384, jnp.float32, 2)])
    def test_step_blocks_follows_the_pool(self, bs, lanes, dtype, want):
        pool = jax.ShapeDtypeStruct((9, bs, lanes), dtype)
        assert paged_ops.step_blocks(1, pool) == want
        # prefill chunks and quantized pools stay on the pipeline
        assert paged_ops.step_blocks(4, pool) == 1
        codes = jax.ShapeDtypeStruct((9, bs, lanes), jnp.int8)
        assert paged_ops.step_blocks(1, codes, object()) == 1

    def test_step_blocks_refuses_a_block_past_vmem(self):
        pool = jax.ShapeDtypeStruct((9, 1024, 4096), jnp.bfloat16)
        with pytest.raises(ValueError, match="VMEM"):
            paged_ops.step_blocks(1, pool)

    def test_forward_builds_one_list_for_all_layers(self):
        """``forward_paged`` hands its one list down the ``attend``
        seam: one cumsum in the traced step, one kernel call a layer."""
        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        B, NB, bs = 4, 4, 4
        closed = jax.make_jaxpr(
            lambda p, pools, tok, lens, bt: model.forward_paged(
                p, tok, pools, bt, lens, kernel="pallas-interpret"))(
            params, init_pools(TINY, 1 + B * NB, bs),
            jnp.zeros((B, 1), jnp.int32), jnp.full((B,), 5, jnp.int32),
            jnp.ones((B, NB), jnp.int32))
        names = [e.primitive.name for e in _all_eqns(closed, into=(
            "pjit", "jit", "closed_call"))]
        assert names.count("pallas_call") == TINY.layers > 1
        assert names.count("cumsum") == 1
        # ... and the layers share ONE trace of the kernel's call, so a
        # program lowers the kernel to Mosaic once, not once a layer
        calls = [e for e in closed.jaxpr.eqns
                 if e.params.get("name") == "_paged_call"]
        assert len(calls) == TINY.layers
        assert len({id(e.params["jaxpr"]) for e in calls}) == 1


class TestPoolGeometry:
    """The stored geometry itself: ``(num_blocks, block_size, H*D)``
    token-major rows with the heads side by side, block id on axis 0 and
    slot on axis 1 of every leaf."""

    @pytest.mark.parametrize("kv_dtype", ["fp32", "int8", "int4"])
    def test_write_then_gather_returns_rows_in_position_order(
            self, kv_dtype):
        """A sequence written through ``write_kv*`` into blocks handed
        out in scrambled order, its length not a multiple of the block
        size, reads back through ``gather_kv`` head by head in position
        order — bit for bit what the storage variant keeps of it."""
        rng = np.random.default_rng(11)
        cfg = dataclasses.replace(TINY, hidden=24, heads=3)    # D = 8
        H, D, bs, L = cfg.heads, cfg.head_dim, 4, 10
        kv = jnp.asarray(rng.normal(size=(2, H, L, D)).astype(np.float32))
        bt = jnp.asarray([[5, 2, 7], [1, 6, 3]], jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (2, L))
        valid = jnp.ones((2, L), bool)
        leaf = init_pools(cfg, 8, bs, kv_dtype, kv_group=4)[0]
        assert leaf["k"].shape[:2] == (8, bs)
        assert all(x.shape[:2] == (8, bs) for x in leaf.values())
        if kv_dtype == "fp32":
            assert leaf["k"].shape == (8, bs, H * D)
            pool = paged_ops.write_kv(leaf["k"], kv, bt, pos, valid)
            got = paged_ops.gather_kv(pool, bt, H)
            want = kv
        else:
            write, quant, dequant = {
                "int8": (paged_ops.write_kv_quant, paged_ops.quantize_kv,
                         paged_ops.dequantize_kv),
                "int4": (paged_ops.write_kv_quant_int4,
                         functools.partial(paged_ops.quantize_kv_int4,
                                           group=4),
                         paged_ops.dequantize_kv_int4)}[kv_dtype]
            pool, scale = write(leaf["k"], leaf["k_scale"], kv, bt, pos,
                                valid)
            assert paged_ops.pool_mode(pool, scale) == kv_dtype
            got = paged_ops._gather_kv_dequant(pool, scale, bt, H,
                                               jnp.float32)
            want = dequant(*quant(kv), jnp.float32)
        assert got.shape == (2, H, 3 * bs, D)
        np.testing.assert_array_equal(np.asarray(got)[:, :, :L],
                                      np.asarray(want))
        # the slots past the length were never written
        assert not np.asarray(got)[:, :, L:].any()
        # and nothing landed in the null block
        assert not np.asarray(pool)[0].any()


# ------------------------------------------------------- dispatch seam

@pytest.mark.quick
class TestDispatch:
    def test_attend_rejects_unresolved_choice(self):
        rng = np.random.default_rng(0)
        case = _case(rng, 1, 1, 4, S=1)
        with pytest.raises(ValueError, match="auto"):
            paged_ops.attend(*case, jnp.float32, kernel="auto")

    def test_resolve_kernel_off_tpu(self):
        assert paged_ops.resolve_kernel("xla", TINY, 4) == "xla"
        # a forced kernel off TPU is the INTERPRETER and says so — no
        # result can carry "pallas" from an interpreted run
        assert paged_ops.resolve_kernel("pallas", TINY, 4) \
            == "pallas-interpret"
        # auto never picks the interpreter as a serving path
        assert paged_ops.resolve_kernel("auto", TINY, 4) == "xla"
        with pytest.raises(ValueError, match="auto"):
            paged_ops.resolve_kernel("fused", TINY, 4)

    @pytest.mark.parametrize("choice", ["auto", "pallas"])
    def test_compile_failure_propagates_on_tpu(self, monkeypatch, choice):
        """On TPU a selected kernel that does not compile RAISES out of
        resolve_kernel with the compiler's message — it never becomes
        the XLA path."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("MPI_TF_TPU_DISABLE_PAGED_KERNEL",
                           raising=False)

        def refuse(*a, **k):
            raise NotImplementedError("infer-vector-layout: boom")

        monkeypatch.setattr(pk, "_paged_call", refuse)
        pk.probe_compile.cache_clear()
        with pytest.raises(RuntimeError, match="infer-vector-layout"):
            paged_ops.resolve_kernel(choice, TINY, 4)

    def test_auto_on_tpu_means_the_kernel(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("MPI_TF_TPU_DISABLE_PAGED_KERNEL",
                           raising=False)
        monkeypatch.setattr(pk, "probe_compile", lambda *a, **k: None)
        assert paged_ops.resolve_kernel("auto", TINY, 4) == "pallas"
        assert paged_ops.resolve_kernel("pallas", TINY, 4) == "pallas"
        # the operator switch is a declared mapping, not a caught error
        monkeypatch.setenv("MPI_TF_TPU_DISABLE_PAGED_KERNEL", "1")
        assert paged_ops.resolve_kernel("auto", TINY, 4) == "xla"
        assert paged_ops.resolve_kernel("pallas", TINY, 4) == "pallas"

    def test_serve_config_validates_kernel(self):
        with pytest.raises(ValueError, match="kernel"):
            ServeConfig(kernel="mosaic")


# ----------------------------------------------- engine end to end

class TestEnginePallas:
    """The acceptance pins: greedy decode through the engine with
    ``--kernel pallas`` (interpret on CPU) is token-identical to
    ``generate`` under chunked prefill + slot recycling + eviction, and
    the kernel path honors the zero-recompile bucket contract."""

    @pytest.mark.parametrize("cfg", [TINY, ROPE], ids=["learned", "rope"])
    def test_greedy_token_identical_to_generate(self, cfg):
        model = gpt.CausalLm(cfg)
        params = model.init(jax.random.key(1))
        rng = np.random.default_rng(2)
        prompts = [list(map(int, rng.integers(0, cfg.vocab_size, int(s))))
                   for s in rng.integers(3, 14, 4)]
        budgets = [int(n) for n in rng.integers(1, 8, len(prompts))]
        engine = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=40, block_size=4, max_slots=3, max_seq_len=24,
            prefill_chunk=8, kernel="pallas"))
        assert engine.kernel == "pallas-interpret"
        res = engine.run([Request(i, p, n) for i, (p, n)
                          in enumerate(zip(prompts, budgets))])
        assert res["kernel"] == "pallas-interpret"
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            assert res["outputs"][i] == _generate_ref(model, params, p, n), \
                f"request {i} diverged from generate() under the kernel"

    def test_eviction_restart_token_identical(self):
        """The tightest parity corner: pool pressure forces an eviction
        + restart-from-scratch replay, all through the kernel."""
        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        engine = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=9, block_size=2, max_slots=2, max_seq_len=12,
            prefill_chunk=2, kernel="pallas"))
        rng = np.random.default_rng(8)
        pa = list(map(int, rng.integers(0, TINY.vocab_size, 2)))
        pb = list(map(int, rng.integers(0, TINY.vocab_size, 11)))
        res = engine.run([Request(0, pa, 10, arrival=0.0),
                          Request(1, pb, 1, arrival=0.0)])
        assert engine.sched.evictions >= 1
        assert res["outputs"][0] == _generate_ref(model, params, pa, 10)
        assert res["outputs"][1] == _generate_ref(model, params, pb, 1)

    def test_paged_counters_against_a_hand_count(self):
        """``paged_grid_steps`` / ``paged_live_blocks`` /
        ``paged_blocks_fetched`` over a run's decode dispatches, counted
        again by hand from the lengths and tables each dispatch was
        given: a step a group of 4 (block 32), dead tails fetched."""
        model = gpt.CausalLm(ROPE)          # no cap of 128 positions
        params = model.init(jax.random.key(0))
        bs = 32
        engine = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=1 + 2 * 8, block_size=bs, max_slots=4,
            max_seq_len=8 * bs, prefill_chunk=64, kernel="xla"))
        assert engine._decode_group == 4
        seen, decode = [], engine._decode_fn

        def spy(params, pools, tokens, lengths, tables, *rest):
            seen.append((np.asarray(lengths), np.asarray(tables).shape))
            return decode(params, pools, tokens, lengths, tables, *rest)

        engine._decode_fn = spy
        rng = np.random.default_rng(5)
        res = engine.run([
            Request(i, list(map(int, rng.integers(0, TINY.vocab_size, p))),
                    n) for i, (p, n) in enumerate([(150, 12), (30, 6)])])
        assert len(seen) > 10
        steps = live = fetched = bound = 0
        for lengths, (rows, width) in seen:
            for length in lengths.tolist():      # slack rows: length 0
                blocks = min(length // bs + 1, width)
                groups = -(-blocks // 4)
                live, steps = live + blocks, steps + groups
                fetched += 4 * groups
            bound += rows * -(-width // 4)
        assert (res["paged_grid_steps"], res["paged_live_blocks"],
                res["paged_blocks_fetched"], res["paged_grid_bound"]) \
            == (steps, live, fetched, bound)
        assert fetched > live > steps > 0
        signals = engine.load_signals()
        assert (signals["paged_grid_steps"], signals["paged_live_blocks"],
                signals["paged_blocks_fetched"]) == (steps, live, fetched)

    def test_zero_recompiles_after_warmup_with_kernel(self):
        """The zero-recompile probe extended to the kernel path: the
        pallas lowering must live inside the same bucketed jit cache
        discipline as the gather path."""
        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        engine = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=40, block_size=4, max_slots=4, max_seq_len=32,
            prefill_chunk=8, kernel="pallas"))
        rng = np.random.default_rng(3)
        lens = rng.integers(3, 16, 5)
        budgets = [int(n) for n in rng.integers(1, 8, 5)]

        def trace(seed):
            r = np.random.default_rng(seed)
            return [Request(i, list(map(int, r.integers(
                        0, TINY.vocab_size, int(s)))), budgets[i])
                    for i, s in enumerate(lens)]

        engine.run(trace(0))
        warm = engine.compile_counts()
        assert warm["decode"] > 0 and warm["prefill"] > 0
        engine.reset()
        engine.run(trace(7))
        assert engine.compile_counts() == warm, \
            "kernel path recompiled in steady state"


# ------------------------------------------- lowered-graph assertions

def _all_eqns(closed, into=None):
    """Every equation of the jaxpr, recursing into the sub-jaxprs of the
    equations whose primitive is named in ``into`` (default: all of
    them — scan/cond/pjit/pallas_call bodies)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subs(val):
        if isinstance(val, ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, Jaxpr):
            yield val
        elif isinstance(val, (list, tuple)):
            for x in val:
                yield from subs(x)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            if into is None or eqn.primitive.name in into:
                for p in eqn.params.values():
                    for sub in subs(p):
                        yield from walk(sub)

    yield from walk(closed.jaxpr)


def _all_avals(closed):
    """Every output aval in the jaxpr, sub-jaxprs included."""
    for eqn in _all_eqns(closed):
        for v in eqn.outvars:
            yield v.aval


class TestNoMaterializedGather:
    """The acceptance assertion: with the kernel enabled, the jitted
    decode step contains NO array shaped like the gathered KV view —
    neither the (B, NB, H, bs, D) pool gather nor its (B, H, L, D)
    reshape.  The same probe run on the XLA path DOES find one, so a
    passing kernel assertion cannot be vacuous."""

    def _decode_avals(self, kernel):
        cfg = TINY
        model = gpt.CausalLm(cfg)
        params = model.init(jax.random.key(0))
        B, NB, bs = 4, 4, 4
        pools = init_pools(cfg, 1 + B * NB, bs)
        tables = jnp.ones((B, NB), jnp.int32)
        lengths = jnp.full((B,), 5, jnp.int32)
        tokens = jnp.zeros((B, 1), jnp.int32)

        def step(params, pools, tokens, lengths, tables):
            return model.forward_paged(params, tokens, pools, tables,
                                       lengths, kernel=kernel)

        closed = jax.make_jaxpr(step)(params, pools, tokens, lengths,
                                      tables)
        L = NB * bs
        H, D = cfg.heads, cfg.head_dim
        gathered = {(B, NB, H, bs, D), (B, H, L, D), (B, L, H, D)}
        return [tuple(a.shape) for a in _all_avals(closed)
                if getattr(a, "shape", None)
                and tuple(a.shape) in gathered]

    def test_pallas_decode_never_materializes_the_gather(self):
        assert self._decode_avals("pallas-interpret") == []

    def test_xla_decode_does_materialize_it(self):
        """Probe validity: the same walk finds the gathered view on the
        XLA path — the pallas assertion above is not vacuously true."""
        assert self._decode_avals("xla") != []


# ------------------------------------------------- int8 quantization

def _by_head(quantize, pool, H=2):
    """Quantize a whole stored fp32 pool head by head — every
    ``quantize_kv*`` takes its rows along the last axis, whatever leads
    — and lay the codes and the scales out as the pool stores them:
    a slot's heads side by side."""
    rows = pool.reshape(pool.shape[:2] + (H, -1))
    return tuple(x.reshape(x.shape[:2] + (-1,)) for x in quantize(rows))


def _quantize_pools(kp, vp):
    """Whole fp32 pools to int8 (codes, scales) pairs."""
    return (_by_head(paged_ops.quantize_kv, kp)
            + _by_head(paged_ops.quantize_kv, vp))


class TestInt8Quantization:
    """The write-side contract: symmetric absmax codes, one fp32 scale
    per (block, head, slot) token row, and write-granularity
    independence — the property every downstream composition (chunked
    prefill, decode, speculative verify, journal replay) leans on."""

    def test_roundtrip_error_within_absmax_bound(self):
        """|dequant(quant(x)) - x| <= amax/127 per element — the error
        bound symmetric absmax quantization promises (round-to-nearest
        is within half a step; the bound allows a full step)."""
        rng = np.random.default_rng(0)
        # mix magnitudes: unit rows, tiny rows, huge rows — the
        # per-row scale must adapt to each independently
        x = rng.normal(size=(6, 2, 4, 8)).astype(np.float32)
        x[1] *= 1e-4
        x[2] *= 1e4
        codes, scale = paged_ops.quantize_kv(jnp.asarray(x))
        deq = np.asarray(paged_ops.dequantize_kv(codes, scale,
                                                 jnp.float32))
        amax = np.abs(x).max(-1)
        assert np.all(np.abs(deq - x) <= amax[..., None] / 127 + 1e-12)
        assert np.asarray(codes).dtype == np.int8
        assert np.asarray(scale).shape == x.shape[:-1]

    def test_zero_rows_quantize_inert(self):
        """All-zero rows (the freshly initialized pool, the null block)
        must produce zero codes and a zero scale — and dequantize back
        to exact zeros, never NaN (the safe-divisor contract)."""
        z = jnp.zeros((2, 2, 4, 8), jnp.float32)
        codes, scale = paged_ops.quantize_kv(z)
        assert np.all(np.asarray(codes) == 0)
        assert np.all(np.asarray(scale) == 0.0)
        deq = np.asarray(paged_ops.dequantize_kv(codes, scale,
                                                 jnp.float32))
        assert np.all(deq == 0.0) and np.all(np.isfinite(deq))

    def test_write_granularity_independent(self):
        """Writing S tokens in ONE dispatch vs one-at-a-time produces
        byte-identical codes AND scales: each row's quantization
        depends only on its own values, so chunked prefill, per-token
        decode, speculative verify, and journal replay all land the
        same pool bytes — the property the replay/prefix determinism
        pins build on."""
        rng = np.random.default_rng(5)
        H, bs, D, S = 2, 4, 8, 4
        kv = jnp.asarray(rng.normal(size=(1, H, S, D)).astype(np.float32))
        bt = jnp.asarray([[1, 2]], jnp.int32)

        def fresh():
            return (jnp.zeros((3, bs, H * D), jnp.int8),
                    jnp.zeros((3, bs, H), jnp.float32))

        pool_a, scale_a = fresh()
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        pool_a, scale_a = paged_ops.write_kv_quant(
            pool_a, scale_a, kv, bt, pos, jnp.ones((1, S), bool))
        pool_b, scale_b = fresh()
        for t in range(S):
            pool_b, scale_b = paged_ops.write_kv_quant(
                pool_b, scale_b, kv[:, :, t:t + 1], bt,
                jnp.asarray([[t]], jnp.int32), jnp.ones((1, 1), bool))
        np.testing.assert_array_equal(np.asarray(pool_a),
                                      np.asarray(pool_b))
        np.testing.assert_array_equal(np.asarray(scale_a),
                                      np.asarray(scale_b))

    def test_attend_rejects_one_sided_scales(self):
        rng = np.random.default_rng(0)
        q, kp, vp, bt, lens = _case(rng, 1, 1, 4, S=1)
        kc, ks, vc, _ = _quantize_pools(kp, vp)
        with pytest.raises(ValueError, match="both k_scale and v_scale"):
            paged_ops.attend(q, kc, vc, bt, lens, jnp.float32,
                             kernel="xla", k_scale=ks)


class TestInt8KernelParity:
    """Interpret-mode kernel vs the XLA gather path over the SAME
    quantized pools: both consume identical int8 codes + scales, so
    their in-register vs gathered dequantization must agree to fp32
    arithmetic tolerance — the same 2e-6 bar as the fp32 parity tests
    (quantization error cancels out of this comparison entirely)."""

    def _assert_parity_int8(self, q, kp, vp, bt, lens, dead_rows=()):
        kc, ks, vc, vs = _quantize_pools(kp, vp)
        want = paged_ops.attend(q, kc, vc, bt, lens, jnp.float32,
                                kernel="xla", k_scale=ks, v_scale=vs)
        got = pk.paged_attention_kernel(q, kc, vc, bt, lens,
                                        k_scale=ks, v_scale=vs,
                                        interpret=True)
        w, g = np.array(want), np.array(got)
        for b in dead_rows:
            w[b] = g[b] = 0.0
        np.testing.assert_allclose(g, w, rtol=2e-6, atol=2e-6)
        return got

    @pytest.mark.parametrize("B,NB,bs", [(1, 1, 4), (2, 2, 4),
                                         (4, 4, 4), (8, 2, 8)])
    def test_decode_parity_across_bucket_shapes(self, B, NB, bs):
        rng = np.random.default_rng(B * 100 + NB * 10 + bs)
        q, kp, vp, bt, lens = _case(rng, B, NB, bs, S=1)
        self._assert_parity_int8(q, kp, vp, bt, lens,
                                 dead_rows=(B - 1,) if B > 2 else ())

    @pytest.mark.parametrize("S", [2, 4, 8])
    def test_chunked_prefill_parity(self, S):
        rng = np.random.default_rng(S)
        q, kp, vp, bt, lens = _case(rng, 2, 4, 4, S=S)
        kc, ks, vc, vs = _quantize_pools(kp, vp)
        want = paged_ops.attend(q, kc, vc, bt, lens, jnp.float32,
                                kernel="xla", k_scale=ks, v_scale=vs)
        got = pk.paged_prefill_attention(q, kc, vc, bt, lens,
                                         k_scale=ks, v_scale=vs,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)

    def test_masked_lanes_cannot_leak(self):
        """Poisoned null-block / beyond-length lanes quantize to huge
        codes+scales — masking must hide them in BOTH int8 lowerings,
        and the kernel output stays finite."""
        rng = np.random.default_rng(42)
        q, kp, vp, bt, lens = _case(rng, 4, 3, 4, S=1, poison=1e30)
        got = self._assert_parity_int8(q, kp, vp, bt, lens,
                                       dead_rows=(3,))
        g = np.asarray(got)
        live = [b for b in range(4) if b != 3]
        assert np.all(np.isfinite(g[live]))

    def test_bucket_slack_rows_stay_inert(self):
        rng = np.random.default_rng(7)
        q, kp, vp, bt, lens = _case(rng, 4, 4, 4, S=1)
        assert np.all(np.asarray(bt)[3] == 0)
        self._assert_parity_int8(q, kp, vp, bt, lens, dead_rows=(3,))


class TestEngineInt8:
    """End-to-end int8 serving pins: deterministic, lowering-identical
    (int8-xla == int8-pallas), tracking fp32 at the token-match-rate
    gate, and zero-recompile."""

    def _run(self, model, params, prompts, budgets, **kw):
        base = dict(num_blocks=40, block_size=4, max_slots=3,
                    max_seq_len=24, prefill_chunk=8, kernel="xla",
                    kv_dtype="int8")
        base.update(kw)
        engine = PagedDecodeEngine(model, params, ServeConfig(**base))
        return engine.run([Request(i, p, n) for i, (p, n)
                           in enumerate(zip(prompts, budgets))])

    def test_int8_deterministic_and_tracks_fp32(self):
        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(1))
        rng = np.random.default_rng(2)
        prompts = [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
                   for s in rng.integers(3, 14, 4)]
        budgets = [int(n) for n in rng.integers(4, 8, len(prompts))]
        a = self._run(model, params, prompts, budgets)
        b = self._run(model, params, prompts, budgets)
        assert a["outputs"] == b["outputs"], "int8 run nondeterministic"
        c = self._run(model, params, prompts, budgets, kernel="pallas")
        assert c["outputs"] == a["outputs"], \
            "int8 kernel lowering diverged from the int8 gather path"
        ref = self._run(model, params, prompts, budgets, kv_dtype="fp32")
        matched = compared = 0
        for i in a["outputs"]:
            compared += max(len(ref["outputs"][i]), len(a["outputs"][i]))
            matched += sum(x == y for x, y in zip(ref["outputs"][i],
                                                  a["outputs"][i]))
        # int8 tracks fp32 but is NOT bit-identical to it: a lenient
        # floor (tiny untrained model, short budgets); no cell serves a
        # quantised pool at real widths yet (ROADMAP R-W6)
        assert compared > 0 and matched / compared >= 0.98, \
            f"int8 token match rate {matched}/{compared} below gate"

    def test_zero_recompiles_after_warmup_int8(self):
        """Quantized pools are fixed-shape engine state (codes + scale
        siblings), so the bucketed jit cache discipline must hold
        under kv_dtype=int8 exactly as under fp32."""
        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        engine = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=40, block_size=4, max_slots=4, max_seq_len=32,
            prefill_chunk=8, kernel="xla", kv_dtype="int8"))
        rng = np.random.default_rng(3)
        lens = rng.integers(3, 16, 5)
        budgets = [int(n) for n in rng.integers(1, 8, 5)]

        def trace(seed):
            r = np.random.default_rng(seed)
            return [Request(i, list(map(int, r.integers(
                        0, TINY.vocab_size, int(s)))), budgets[i])
                    for i, s in enumerate(lens)]

        engine.run(trace(0))
        warm = engine.compile_counts()
        assert warm["decode"] > 0 and warm["prefill"] > 0
        engine.reset()
        engine.run(trace(7))
        assert engine.compile_counts() == warm, \
            "int8 pool recompiled in steady state"

    def test_serve_config_validates_kv_dtype(self):
        with pytest.raises(ValueError, match="kv dtype"):
            ServeConfig(kv_dtype="int2")



def _quantize_pools_int4(kp, vp, group=4):
    """Quantize whole fp32 pools to int4 (packed codes, group scales)
    pairs; group=4 over the test D=8 gives two scale groups per row, so
    the group axis actually exercises multi-group dequantization."""
    quantize = functools.partial(paged_ops.quantize_kv_int4, group=group)
    return _by_head(quantize, kp) + _by_head(quantize, vp)


class TestInt4Quantization:
    """The int4 write-side contract: two codes per byte (split-half
    packing along D), one fp32 scale per group of ``group`` values, and
    the same write-granularity independence the int8 pins lean on."""

    def test_pack_unpack_roundtrip_exact(self):
        """Every representable nibble value (-8..7) survives the
        split-half pack + sign-extending unpack bit-exactly."""
        rng = np.random.default_rng(0)
        codes = jnp.asarray(rng.integers(-8, 8, size=(3, 2, 4, 8)),
                            jnp.int32)
        packed = paged_ops.pack_int4(codes)
        assert np.asarray(packed).dtype == np.uint8
        assert packed.shape == codes.shape[:-1] + (4,)
        np.testing.assert_array_equal(
            np.asarray(paged_ops.unpack_int4(packed)), np.asarray(codes))

    def test_roundtrip_error_within_group_absmax_bound(self):
        """|dequant(quant(x)) - x| <= group_amax/7 per element — the
        per-GROUP absmax bound (finer than a whole-row scale when
        magnitudes vary along D)."""
        rng = np.random.default_rng(1)
        x = rng.normal(size=(6, 2, 4, 8)).astype(np.float32)
        x[1] *= 1e-4
        x[2] *= 1e4
        x[3, :, :, :4] *= 1e3          # per-group adaptation along D
        codes, scale = paged_ops.quantize_kv_int4(jnp.asarray(x), 4)
        assert np.asarray(codes).dtype == np.uint8
        assert np.asarray(scale).shape == x.shape[:-1] + (2,)
        deq = np.asarray(paged_ops.dequantize_kv_int4(codes, scale,
                                                      jnp.float32))
        amax = np.abs(x.reshape(6, 2, 4, 2, 4)).max(-1)
        bound = np.repeat(amax / 7, 4, axis=-1) + 1e-12
        assert np.all(np.abs(deq - x) <= bound)

    def test_zero_rows_quantize_inert(self):
        z = jnp.zeros((2, 2, 4, 8), jnp.float32)
        codes, scale = paged_ops.quantize_kv_int4(z, 4)
        assert np.all(np.asarray(codes) == 0)
        assert np.all(np.asarray(scale) == 0.0)
        deq = np.asarray(paged_ops.dequantize_kv_int4(codes, scale,
                                                      jnp.float32))
        assert np.all(deq == 0.0) and np.all(np.isfinite(deq))

    def test_write_granularity_independent(self):
        """One S-token dispatch vs per-token writes land byte-identical
        packed codes AND group scales — group scales span only the head
        dim, never token rows, so every write shape quantizes each row
        independently (the property replay and the prefix trie pin)."""
        rng = np.random.default_rng(5)
        H, bs, D, S, G = 2, 4, 8, 4, 2
        kv = jnp.asarray(rng.normal(size=(1, H, S, D)).astype(np.float32))
        bt = jnp.asarray([[1, 2]], jnp.int32)

        def fresh():
            return (jnp.zeros((3, bs, H * D // 2), jnp.uint8),
                    jnp.zeros((3, bs, H * G), jnp.float32))

        pool_a, scale_a = fresh()
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        pool_a, scale_a = paged_ops.write_kv_quant_int4(
            pool_a, scale_a, kv, bt, pos, jnp.ones((1, S), bool))
        pool_b, scale_b = fresh()
        for t in range(S):
            pool_b, scale_b = paged_ops.write_kv_quant_int4(
                pool_b, scale_b, kv[:, :, t:t + 1], bt,
                jnp.asarray([[t]], jnp.int32), jnp.ones((1, 1), bool))
        np.testing.assert_array_equal(np.asarray(pool_a),
                                      np.asarray(pool_b))
        np.testing.assert_array_equal(np.asarray(scale_a),
                                      np.asarray(scale_b))

    def test_attend_rejects_one_sided_residual(self):
        rng = np.random.default_rng(0)
        q, kp, vp, bt, lens = _case(rng, 1, 1, 4, S=1)
        kc, ks, vc, vs = _quantize_pools_int4(kp, vp)
        with pytest.raises(ValueError, match="k_new and v_new"):
            paged_ops.attend(q, kc, vc, bt, lens, jnp.float32,
                             kernel="xla", k_scale=ks, v_scale=vs,
                             k_new=q)

    def test_attend_rejects_residual_on_row_scales(self):
        rng = np.random.default_rng(0)
        q, kp, vp, bt, lens = _case(rng, 1, 1, 4, S=1)
        kc, ks, vc, vs = _quantize_pools(kp, vp)
        with pytest.raises(ValueError, match="only apply to int4"):
            paged_ops.attend(q, kc, vc, bt, lens, jnp.float32,
                             kernel="xla", k_scale=ks, v_scale=vs,
                             k_new=q, v_new=q)


class TestInt4KernelParity:
    """Interpret-mode kernel vs the XLA gather path over the SAME int4
    pools — identical packed codes + group scales in, so in-register
    nibble unpack vs gathered dequantization must agree to fp32
    tolerance, with and without the fp-residual self lane."""

    def _assert_parity_int4(self, q, kp, vp, bt, lens, dead_rows=(),
                            residual=False):
        kc, ks, vc, vs = _quantize_pools_int4(kp, vp)
        kn = vn = None
        if residual:
            rng = np.random.default_rng(99)
            kn = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
            vn = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
        want = paged_ops.attend(q, kc, vc, bt, lens, jnp.float32,
                                kernel="xla", k_scale=ks, v_scale=vs,
                                k_new=kn, v_new=vn)
        got = pk.paged_attention_kernel(q, kc, vc, bt, lens,
                                        k_scale=ks, v_scale=vs,
                                        k_new=kn, v_new=vn,
                                        interpret=True)
        w, g = np.array(want), np.array(got)
        for b in dead_rows:
            w[b] = g[b] = 0.0
        np.testing.assert_allclose(g, w, rtol=2e-6, atol=2e-6)
        return got

    @pytest.mark.parametrize("B,NB,bs", [(1, 1, 4), (2, 2, 4),
                                         (4, 4, 4), (8, 2, 8)])
    def test_decode_parity_across_bucket_shapes(self, B, NB, bs):
        rng = np.random.default_rng(B * 100 + NB * 10 + bs)
        q, kp, vp, bt, lens = _case(rng, B, NB, bs, S=1)
        self._assert_parity_int4(q, kp, vp, bt, lens,
                                 dead_rows=(B - 1,) if B > 2 else ())

    @pytest.mark.parametrize("B,NB,bs", [(2, 2, 4), (4, 4, 4)])
    def test_decode_parity_with_residual_lane(self, B, NB, bs):
        """The engine's actual int4 decode dispatch: the in-step
        token's K/V ride in at full precision and override the self
        column inside the masked softmax — both lowerings must fold
        the lane identically."""
        rng = np.random.default_rng(B * 10 + bs)
        q, kp, vp, bt, lens = _case(rng, B, NB, bs, S=1)
        self._assert_parity_int4(q, kp, vp, bt, lens,
                                 dead_rows=(B - 1,) if B > 2 else (),
                                 residual=True)

    @pytest.mark.parametrize("S", [2, 4, 8])
    def test_chunked_prefill_parity(self, S):
        rng = np.random.default_rng(S)
        q, kp, vp, bt, lens = _case(rng, 2, 4, 4, S=S)
        kc, ks, vc, vs = _quantize_pools_int4(kp, vp)
        want = paged_ops.attend(q, kc, vc, bt, lens, jnp.float32,
                                kernel="xla", k_scale=ks, v_scale=vs)
        got = pk.paged_prefill_attention(q, kc, vc, bt, lens,
                                         k_scale=ks, v_scale=vs,
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)

    def test_masked_lanes_cannot_leak(self):
        """Poisoned null-block / beyond-length lanes quantize to huge
        nibbles + scales — masking must hide them in BOTH int4
        lowerings (residual variant: the self-lane override must not
        resurrect them), and the output stays finite."""
        rng = np.random.default_rng(42)
        q, kp, vp, bt, lens = _case(rng, 4, 3, 4, S=1, poison=1e30)
        got = self._assert_parity_int4(q, kp, vp, bt, lens,
                                       dead_rows=(3,), residual=True)
        g = np.asarray(got)
        live = [b for b in range(4) if b != 3]
        assert np.all(np.isfinite(g[live]))

    def test_bucket_slack_rows_stay_inert(self):
        rng = np.random.default_rng(7)
        q, kp, vp, bt, lens = _case(rng, 4, 4, 4, S=1)
        assert np.all(np.asarray(bt)[3] == 0)
        self._assert_parity_int4(q, kp, vp, bt, lens, dead_rows=(3,))


class TestWideTable:
    """What the live-only grid is for: a 64-block table over rows of one
    to three live blocks, slack rows between live ones, every masked
    lane poisoned.  The list walks 10 of the 384 (row, block) pairs; both
    kernel bodies and every pool mode must give the XLA path's rows."""

    B, NB, bs = 6, 64, 4
    SLACK = (1, 4)

    def _case(self, rng, S):
        bs, NB = self.bs, self.NB
        # tokens a row must reach (length + S): 1 block, 3 blocks, 2
        # blocks exactly on the edge, 1 token into a 2nd block
        reach = {0: max(S, 2), 2: 2 * bs + max(S, 3), 3: 2 * bs,
                 5: bs + 1}
        H, D = 2, 8
        kp = rng.normal(size=(1 + 3 * self.B, bs, H * D)).astype(np.float32)
        vp = rng.normal(size=kp.shape).astype(np.float32)
        kp[0] = vp[0] = 1e30                       # the null block
        bt = np.zeros((self.B, NB), np.int32)
        lens = np.zeros((self.B,), np.int32)
        for b, r in reach.items():
            lens[b] = r - S
            for j in range(-(-r // bs)):
                blk = bt[b, j] = 1 + 3 * b + j
                kp[blk, max(r - j * bs, 0):] = 1e30    # past the reach
                vp[blk, max(r - j * bs, 0):] = 1e30
        q = rng.normal(size=(self.B, H, S, D)).astype(np.float32)
        return tuple(map(jnp.asarray, (q, kp, vp, bt, lens)))

    @pytest.mark.parametrize("mode", ["fp32", "int8", "int4",
                                      "int4-residual"])
    @pytest.mark.parametrize("S", [1, 4])
    def test_parity_under_a_wide_table(self, S, mode):
        rng = np.random.default_rng(S)
        q, kp, vp, bt, lens = self._case(rng, S)
        _, _, n, live = paged_ops.paged_work(lens, S, self.bs, self.NB)
        assert int(live) == 10 and int(n.max()) == 3
        kw = {}
        if mode == "int8":
            kp, ks, vp, vs = _quantize_pools(kp, vp)
            kw = dict(k_scale=ks, v_scale=vs)
        elif mode != "fp32":
            kp, ks, vp, vs = _quantize_pools_int4(kp, vp)
            kw = dict(k_scale=ks, v_scale=vs)
            if mode == "int4-residual":
                kw.update(
                    k_new=jnp.asarray(rng.normal(size=q.shape), jnp.float32),
                    v_new=jnp.asarray(rng.normal(size=q.shape), jnp.float32))
        want = np.array(paged_ops.attend(q, kp, vp, bt, lens, jnp.float32,
                                         kernel="xla", **kw))
        got = np.array(pk.paged_attention_kernel(q, kp, vp, bt, lens,
                                                 interpret=True, **kw))
        for b in self.SLACK:
            want[b] = got[b] = 0.0
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


class TestEngineInt4:
    """End-to-end int4 serving pins: deterministic, lowering-identical,
    tracking fp32 at the token-match-rate gate, zero-recompile, and pool
    geometry guards."""

    def _run(self, model, params, prompts, budgets, **kw):
        base = dict(num_blocks=40, block_size=4, max_slots=3,
                    max_seq_len=24, prefill_chunk=8, kernel="xla",
                    kv_dtype="int4")
        base.update(kw)
        engine = PagedDecodeEngine(model, params, ServeConfig(**base))
        return engine.run([Request(i, p, n) for i, (p, n)
                           in enumerate(zip(prompts, budgets))])

    def test_int4_deterministic_and_tracks_fp32(self):
        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(1))
        rng = np.random.default_rng(2)
        prompts = [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
                   for s in rng.integers(3, 14, 4)]
        budgets = [int(n) for n in rng.integers(4, 8, len(prompts))]
        a = self._run(model, params, prompts, budgets)
        b = self._run(model, params, prompts, budgets)
        assert a["outputs"] == b["outputs"], "int4 run nondeterministic"
        c = self._run(model, params, prompts, budgets, kernel="pallas")
        assert c["outputs"] == a["outputs"], \
            "int4 kernel lowering diverged from the int4 gather path"
        ref = self._run(model, params, prompts, budgets, kv_dtype="fp32")
        matched = compared = 0
        for i in a["outputs"]:
            compared += max(len(ref["outputs"][i]), len(a["outputs"][i]))
            matched += sum(x == y for x, y in zip(ref["outputs"][i],
                                                  a["outputs"][i]))
        # int4 carries ~16x coarser codes than int8; the group scales
        # plus the fp-residual self lane keep greedy argmax on track —
        # a lenient floor here (no real-width cell yet: ROADMAP R-W6)
        assert compared > 0 and matched / compared >= 0.9, \
            f"int4 token match rate {matched}/{compared} below gate"

    def test_zero_recompiles_after_warmup_int4(self):
        """Packed codes + group-scale siblings are fixed-shape engine
        state, so the bucketed jit cache discipline must hold under
        kv_dtype=int4 exactly as under fp32/int8."""
        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        engine = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=40, block_size=4, max_slots=4, max_seq_len=32,
            prefill_chunk=8, kernel="xla", kv_dtype="int4"))
        rng = np.random.default_rng(3)
        lens = rng.integers(3, 16, 5)
        budgets = [int(n) for n in rng.integers(1, 8, 5)]

        def trace(seed):
            r = np.random.default_rng(seed)
            return [Request(i, list(map(int, r.integers(
                        0, TINY.vocab_size, int(s)))), budgets[i])
                    for i, s in enumerate(lens)]

        engine.run(trace(0))
        warm = engine.compile_counts()
        assert warm["decode"] > 0 and warm["prefill"] > 0
        engine.reset()
        engine.run(trace(7))
        assert engine.compile_counts() == warm, \
            "int4 pool recompiled in steady state"

    def test_init_pools_rejects_bad_geometry(self):
        cfg = dataclasses.replace(TINY, hidden=28)   # head_dim 7: odd
        with pytest.raises(ValueError, match="head_dim"):
            init_pools(cfg, 8, 4, "int4")
        with pytest.raises(ValueError, match="group"):
            init_pools(TINY, 8, 4, "int4", kv_group=3)

    def test_serve_config_validates_kv_group(self):
        with pytest.raises(ValueError, match="kv.group|kv_group"):
            ServeConfig(kv_group=0)

    def test_serve_config_couples_tier_to_prefix_cache(self):
        with pytest.raises(ValueError, match="prefix"):
            ServeConfig(kv_tier="host", prefix_cache="off")


# ------------------------------------------- real Mosaic, no chip

@pytest.fixture(scope="module")
def tpu_topology_device():
    """One device of a deviceless v5e topology: lowering against it runs
    libtpu's real Mosaic compiler with no chip attached."""
    import os

    from jax.experimental import topologies

    # libtpu wants these named on a host with no TPU metadata server
    for k, v in (("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                 ("TPU_WORKER_HOSTNAMES", "localhost"),
                 ("TPU_SKIP_MDS_QUERY", "true")):
        os.environ.setdefault(k, v)
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu on this host: nothing to compile with
        pytest.skip(f"no deviceless TPU topology available: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


class TestMosaicCompile:
    """Every variant the engine can select compiles under Mosaic at the
    served geometry (gpt_base: H=12, D=64, block 16) and its largest
    dispatches — decode over 128 slots, one row at every prefill bucket,
    both under 64-block tables, whose table and work list must fit
    scalar memory — the set ``resolve_kernel`` probes on the chip."""

    @pytest.mark.parametrize("dtype_name,kv_dtype", [
        ("bfloat16", "fp32"), ("float32", "fp32"), ("bfloat16", "int8"),
        ("bfloat16", "int4")])
    def test_served_geometry_compiles(self, tpu_topology_device,
                                      dtype_name, kv_dtype):
        pk.probe_compile.cache_clear()
        pk.probe_compile(dtype_name, 12, 64, 16, 64, kv_dtype, 32,
                         max_slots=128, max_blocks=64,
                         sharding=tpu_topology_device)

    def test_tp_shard_geometry_compiles(self, tpu_topology_device):
        """--tp 2 runs the kernel over H/2 local heads."""
        pk.probe_compile.cache_clear()
        pk.probe_compile("bfloat16", 6, 64, 16, 64, "fp32", 32,
                         max_slots=128, max_blocks=64,
                         sharding=tpu_topology_device)

    @pytest.mark.parametrize("window,table", [(None, 132), (4096, 24)])
    def test_grouped_query_geometry_compiles(self, tpu_topology_device,
                                             window, table):
        """Command A+'s share (benchmarks/configs/
        command_a_plus_05_2026.json): 128 query heads over 8 KV heads of
        128 — ``gqa_decode_attention`` over 32 rows and
        ``gqa_prefill_attention`` at every chunk from 256 to 2,048 —
        over the full layer's pool (tables of 132 blocks of 256) and a
        window layer's ring read as its 4,096 keys plus a chunk (24
        blocks), with the window bound."""
        pk.probe_compile.cache_clear()
        pk.probe_compile("bfloat16", 128, 128, 256, 2048, "fp32", 32,
                         max_slots=32, max_blocks=table,
                         sharding=tpu_topology_device, kv_heads=8,
                         window=window, min_chunk=256)

    @pytest.mark.parametrize("block_size,table", [(128, 8), (256, 4),
                                                  (16, 3)])
    def test_decode_body_compiles_across_groups(
            self, tpu_topology_device, block_size, table):
        """The decode body at one-block groups (blocks of 128 and 256
        tokens) and under a table its group of 8 does not divide."""
        pk.probe_compile.cache_clear()
        pk.probe_compile("bfloat16", 12, 64, block_size, 1, "fp32", 32,
                         max_slots=128, max_blocks=table,
                         sharding=tpu_topology_device)

    @pytest.mark.parametrize("kv_dtype,slots,table", [
        ("int8", 512, 128), ("fp32", 2048, 128)])
    def test_geometry_past_scalar_memory_is_refused(
            self, tpu_topology_device, kv_dtype, slots, table):
        """The table and the work list grow with slots x table width (the
        decode body's list with its groups: an eighth of it at block
        16); a geometry they cannot fit raises with the compiler's words
        and the dispatch it was probed at."""
        pk.probe_compile.cache_clear()
        with pytest.raises(RuntimeError,
                           match=f"{slots} rows x {table} table"):
            pk.probe_compile("bfloat16", 12, 64, 16, 1, kv_dtype, 32,
                             max_slots=slots, max_blocks=table,
                             sharding=tpu_topology_device)

    def test_block_past_the_vmem_slots_is_refused_in_words(
            self, tpu_topology_device):
        """The decode body holds two slots a pool in VMEM: a block they
        cannot fit stops the engine's build with the sizes named."""
        pk.probe_compile.cache_clear()
        with pytest.raises(RuntimeError, match="VMEM slots.*block_size"):
            pk.probe_compile("bfloat16", 32, 128, 1024, 1, "fp32", 32,
                             max_slots=8, max_blocks=4,
                             sharding=tpu_topology_device)


class TestNoPoolSizedCopy:
    """The structural witness of the pool geometry: the engine's decode
    program and its 64-token prefill program — the real ``forward_paged``
    at gpt_base widths over the benchmark cell's 8,193-block pool,
    donated, two layers (the copies were per leaf) — compiled for the
    deviceless v5e hold no ``copy``/``transpose`` of a pool-sized
    operand, keep every pool leaf at its unpadded size, and need less
    scratch than one leaf.  The head-major ``(num_blocks, H, bs, D)``
    pool failed all three: its default layout put ``num_blocks``
    minor-most, the scatter and the Mosaic call each wanted another, and
    every program copied every leaf three times (PERF.md, PR 25).
    Since PR 29 the Mosaic call's grid is the live (row, block) pairs of
    a list built once a program, not once a layer."""

    NUM_BLOCKS, BLOCK, SLOTS, TABLE, CHUNK = 8193, 16, 128, 64, 64

    @pytest.mark.parametrize("kv_dtype", ["fp32", "int8", "int4"])
    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_serving_program_takes_the_pool_in_place(
            self, tpu_topology_device, program, kv_dtype):
        import re
        import types

        dev = tpu_topology_device
        cfg = bert.BertConfig(vocab_size=50257, hidden=768, layers=2,
                              heads=12, mlp=3072, max_positions=1024,
                              dropout=0.0, dtype=jnp.bfloat16)
        model = gpt.CausalLm(cfg)

        def on_chip(tree, dtype=None):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, dtype or x.dtype, sharding=dev), tree)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

        params = on_chip(jax.eval_shape(model.init, jax.random.key(0)),
                         jnp.bfloat16)
        pools = on_chip(jax.eval_shape(lambda: init_pools(
            cfg, self.NUM_BLOCKS, self.BLOCK, kv_dtype)))
        # the engine's own step bodies over the engine's own forward seam
        engine = types.SimpleNamespace(
            _paged_forward=lambda p, tokens, pools, tables, lengths, valid:
            model.forward_paged(p, tokens, pools, tables, lengths,
                                valid=valid, kernel=paged_ops.PALLAS))
        if program == "decode":
            impl = PagedDecodeEngine._decode_impl
            rest = (ints(self.SLOTS), ints(self.SLOTS),
                    ints(self.SLOTS, self.TABLE))
        else:
            impl = PagedDecodeEngine._prefill_impl
            rest = (ints(1, self.CHUNK), ints(), ints(),
                    ints(1, self.TABLE))
        compiled = jax.jit(
            lambda params, pools, *a: impl(engine, params, pools, *a),
            donate_argnums=(1,)).lower(params, pools, *rest).compile()

        # every instruction of the optimised HLO, fused bodies included,
        # by the dims of what it makes
        made = {}
        for dims, op in re.findall(
                r"= \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(",
                compiled.as_text()):
            made.setdefault(tuple(map(int, dims.split(","))) if dims
                            else (), set()).add(op)
        codes = tuple(pools[0]["k"].shape)
        # fp pool: every leaf; quantized: the code leaves (their 6-12 MB
        # scale siblings still get a rotated default layout and a small
        # re-layout each; no cell serves a quantized pool yet)
        assert "scatter" in made[codes], made[codes]     # the probe sees
        assert not made[codes] & {"copy", "transpose"}, made[codes]
        assert compiled.as_text().count(
            'custom_call_target="tpu_custom_call"') == cfg.layers
        # one work list a program: the binary search of its one
        # ``searchsorted`` is the program's only loop (one row's list,
        # the prefill program's, needs no search)
        assert compiled.as_text().count(" while(") \
            == (1 if program == "decode" else 0)
        for layer in compiled.input_formats[0][1]:
            for key in ("k", "v"):
                assert tuple(layer[key].layout.major_to_minor) \
                    == (0, 1, 2), (key, layer[key])
        mem = compiled.memory_analysis()
        logical = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(pools))
        leaf_bytes = int(np.prod(codes)) * pools[0]["k"].dtype.itemsize
        # donated in, aliased out, at the unpadded size
        assert logical <= mem.alias_size_in_bytes <= 1.01 * logical
        if kv_dtype == "fp32":
            assert leaf_bytes == 8193 * 16 * 768 * 2        # 201.4 MB
            assert mem.alias_size_in_bytes - logical < 4096
            assert mem.temp_size_in_bytes < leaf_bytes
