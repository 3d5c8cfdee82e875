"""Flash attention — Pallas TPU kernels for the transformer hot op.

The reference delegates its hot ops to the TF runtime's fused C++ kernels
(SURVEY.md §2 E2); here the attention inner loop is hand-written Pallas:
Q/K/V stream HBM->VMEM in blocks, scores and the online softmax stay in
VMEM scratch, and the (S, S) score matrix is never materialized in HBM —
O(S) memory instead of O(S^2), with the matmuls on the MXU.

Forward AND backward are kernels (round 1 shipped only the forward):

- ``_flash_fwd_kernel``   online-softmax forward, also emitting the
                          per-row logsumexp needed by the backward;
- ``_flash_dq_kernel``    dq, streaming over kv blocks;
- ``_flash_dkdv_kernel``  dk and dv, streaming over q blocks.

Both backward kernels work in the transposed (block_k, block_q) score
orientation so the per-row statistics (lse, delta = rowsum(do*o)) enter as
(1, block_q) row vectors — broadcasts instead of sublane/lane relayouts —
and dq comes out of a dot_general contraction over the k dimension without
materializing a transpose.

Sequence lengths that are not multiples of the block size are padded and
masked (``s_valid``), so the kernels apply to any shape; ``interpret=True``
runs the same kernels on CPU for tests.

``blockwise_attention`` (pure-JAX online-softmax scan) is the portable
O(S) form; ``dense_attention`` (parallel/ring.py) is the reference
implementation.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e30

# Per-row statistics (logsumexp, delta) cannot leave/enter kernels as flat
# (1, block_q) rows: Mosaic requires a block's sublane dim to be divisible
# by 8 or equal to the array dim, which a 1-row block over a (B*H, S) array
# violates whenever B*H > 1 (the round-2 probe shape hid exactly this).
# The forward therefore EMITS lse lane-broadcast as (B*H, S, LSE_LANES)
# and the backward CONSUMES it sublane-broadcast as (B*H, LSE_SUBLANES, S)
# — the latter orientation puts q-position on lanes, so the transposed
# (block_k, block_q) backward kernels read a native (1, block_q) row.
LSE_LANES = 128      # official TPU flash kernel uses MIN_BLOCK_SIZE lanes
LSE_SUBLANES = 8     # f32 sublane tile


# ---------------------------------------------------------------------------
# pure-JAX blockwise online softmax (portable)
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None, block_k: int = 128):
    """O(S * block_k) memory attention via lax.scan.  q,k,v: (B, H, S, D)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    S = k.shape[2]
    block_k = min(block_k, S)
    nk = -(-S // block_k)
    pad = nk * block_k - S
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = kp.reshape(*k.shape[:2], nk, block_k, k.shape[-1])
    vb = vp.reshape(*v.shape[:2], nk, block_k, v.shape[-1])
    qpos = jnp.arange(q.shape[2])[:, None]

    def body(carry, blk):
        o, m, l = carry
        kblk, vblk, i = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        kpos = i * block_k + jnp.arange(block_k)[None, :]
        invalid = kpos >= S
        if causal:
            invalid = invalid | (kpos > qpos)
        s = jnp.where(invalid, NEG_BIG, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (o, m_new, l), None

    o0 = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    m0 = jnp.full(q.shape[:3], NEG_BIG, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    kb_t = jnp.moveaxis(kb, 2, 0)
    vb_t = jnp.moveaxis(vb, 2, 0)
    (o, m, l), _ = lax.scan(body, (o0, m0, l0),
                            (kb_t, vb_t, jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas forward kernel (emits out + logsumexp)
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr,
                      l_scr, *, scale: float, causal: bool, block_q: int,
                      block_k: int, s_valid: int, s_pad: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    last_k = nk - 1
    if causal:
        last_k = jnp.minimum(((qi + 1) * block_q - 1) // block_k, nk - 1)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(ki <= last_k)
    def _step():
        q = q_ref[0]                                   # (BQ, D)
        k = k_ref[0]                                   # (BK, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        # s_valid/s_pad are static: skip mask construction entirely on the
        # hot aligned non-causal path
        if causal or s_valid < s_pad:
            kpos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            invalid = kpos >= s_valid
            if causal:
                qpos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                invalid = invalid | (kpos > qpos)
            s = jnp.where(invalid, NEG_BIG, s)
        m_prev = m_scr[:, 0:1]                         # (BQ, 1)
        l_prev = l_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # (BQ, BK)
        corr = jnp.exp(m_prev - m_new)                 # (BQ, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == last_k)
    def _emit():
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        # lse leaves in a lane-broadcast (block_q, LSE_LANES) tile: Mosaic
        # rejects blocks whose sublane dim is 1 over a larger array dim, so
        # a flat (1, block_q) row per program cannot be written from here
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l_safe),
                                      lse_ref.shape[1:])


def _flash_forward(q, k, v, *, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool, s_valid: int):
    """Padded inputs (S multiple of blocks) -> (out, lse)."""
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, Dv)
    grid = (B * H, S // block_q, S // block_k)

    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               s_valid=s_valid, s_pad=S)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, LSE_LANES), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, LSE_LANES), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dv), lse[:, :, 0].reshape(B, H, S)


# ---------------------------------------------------------------------------
# Pallas backward kernels
# ---------------------------------------------------------------------------

def _scores_t(k, q, v, do, lse_row, dsum_row, *, scale, causal, s_valid,
              s_pad, qi, ki, block_q, block_k):
    """Shared backward math in the transposed (BK, BQ) orientation:
    returns (p_t, ds_t).  Masks are built only when statically needed."""
    s_t = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (BK, BQ)
    invalid = None
    if causal or s_valid < s_pad:
        kpos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        qpos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        if s_valid < s_pad:
            # padded q columns MUST be masked here: their lse is NEG_BIG,
            # so the exp would overflow to inf and 0*inf = NaN would
            # poison dk/dv
            invalid = (kpos >= s_valid) | (qpos >= s_valid)
        if causal:
            c = kpos > qpos
            invalid = c if invalid is None else (invalid | c)
    p_t = jnp.exp(s_t - lse_row)                           # (BK, BQ)
    if invalid is not None:
        p_t = jnp.where(invalid, 0.0, p_t)
    dp_t = jax.lax.dot_general(
        v, do, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (BK, BQ)
    ds_t = p_t * (dp_t - dsum_row) * scale
    return p_t, ds_t


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                     dq_ref, acc, *, scale: float, causal: bool,
                     block_q: int, block_k: int, s_valid: int,
                     s_pad: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    last_k = nk - 1
    if causal:
        last_k = jnp.minimum(((qi + 1) * block_q - 1) // block_k, nk - 1)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    @pl.when(ki <= last_k)
    def _step():
        _, ds_t = _scores_t(
            k_ref[0], q_ref[0], v_ref[0], do_ref[0],
            lse_ref[0, 0:1], dsum_ref[0, 0:1], scale=scale, causal=causal,
            s_valid=s_valid, s_pad=s_pad, qi=qi, ki=ki,
            block_q=block_q, block_k=block_k)
        # dq_block = ds^T @ k == contract ds_t's BK dim with k's BK dim
        acc[:] += jax.lax.dot_general(
            ds_t, k_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (BQ, D)

    @pl.when(ki == last_k)
    def _emit():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _flash_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                       dk_ref, dv_ref, acc_dk, acc_dv, *, scale: float,
                       causal: bool, block_q: int, block_k: int,
                       s_valid: int, s_pad: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    first_q = 0
    if causal:
        first_q = (ki * block_k) // block_q   # earlier q blocks are masked

    @pl.when(qi == 0)
    def _init():
        acc_dk[:] = jnp.zeros_like(acc_dk)
        acc_dv[:] = jnp.zeros_like(acc_dv)

    @pl.when(qi >= first_q)
    def _step():
        do = do_ref[0]
        p_t, ds_t = _scores_t(
            k_ref[0], q_ref[0], v_ref[0], do, lse_ref[0, 0:1],
            dsum_ref[0, 0:1],
            scale=scale, causal=causal, s_valid=s_valid, s_pad=s_pad,
            qi=qi, ki=ki, block_q=block_q, block_k=block_k)
        acc_dv[:] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (BK, Dv)
        acc_dk[:] += jax.lax.dot_general(
            ds_t, q_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (BK, D)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = acc_dk[:].astype(dk_ref.dtype)
        dv_ref[0] = acc_dv[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, *, causal: bool, scale: float,
                    block_q: int, block_k: int, interpret: bool,
                    s_valid: int):
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    dsum = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1)                                # (B, H, S)
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, Dv)
    dof = do.reshape(B * H, S, Dv)
    # sublane-broadcast the per-row stats (see LSE_SUBLANES note up top);
    # XLA fuses the broadcast into the feeding computation
    lsef = jnp.broadcast_to(lse.reshape(B * H, 1, S),
                            (B * H, LSE_SUBLANES, S))
    dsumf = jnp.broadcast_to(dsum.reshape(B * H, 1, S),
                             (B * H, LSE_SUBLANES, S))

    row_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),              # q
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),              # k
        pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),              # v
        pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),              # do
        pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda b, i, j: (b, 0, i),
                     memory_space=pltpu.VMEM),              # lse
        pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda b, i, j: (b, 0, i),
                     memory_space=pltpu.VMEM),              # dsum
    ]
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          s_valid=s_valid, s_pad=S),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        grid=(B * H, S // block_q, S // block_k),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, dsumf)

    col_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),              # q (by q step)
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),              # k (by k block)
        pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),              # v
        pl.BlockSpec((1, block_q, Dv), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),              # do
        pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda b, j, i: (b, 0, i),
                     memory_space=pltpu.VMEM),              # lse
        pl.BlockSpec((1, LSE_SUBLANES, block_q), lambda b, j, i: (b, 0, i),
                     memory_space=pltpu.VMEM),              # dsum
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          s_valid=s_valid, s_pad=S),
        out_shape=(jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, Dv), v.dtype)),
        grid=(B * H, S // block_k, S // block_q),
        in_specs=col_specs,
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, dsumf)
    return (dq.reshape(B, H, S, D), dk.reshape(B, H, S, D),
            dv.reshape(B, H, S, Dv))


# ---------------------------------------------------------------------------
# public entry: padding + custom VJP (Pallas forward AND backward)
# ---------------------------------------------------------------------------

def kernel_enabled() -> bool:
    """False only when the operator kill switch
    ``MPI_TF_TPU_DISABLE_FLASH=1`` is set (also the control arm for
    flash-vs-XLA A/B runs).  There is no compile probe: model code
    selects the kernel from what it can observe (platform, sequence
    length) and a Mosaic refusal raises from the train step's own
    compile with the compiler's message — it never degrades to the XLA
    attention path."""
    import os

    return os.environ.get("MPI_TF_TPU_DISABLE_FLASH", "") in ("", "0")


def _padded_len(S: int, block_q: int, block_k: int) -> int:
    """Pad to the lcm so BOTH grid dims divide evenly (padding to just
    the max would silently drop trailing blocks of the other size)."""
    blk = math.lcm(block_q, block_k)
    return -(-S // blk) * blk


def _pad_seq(x, S_pad):
    S = x.shape[2]
    if S == S_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Flash attention for any S (padded/masked to the block size).
    q,k,v: (B, H, S, D)."""
    out, _ = _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    S = q.shape[2]
    S_pad = _padded_len(S, block_q, block_k)
    out_p, lse = _flash_forward(
        _pad_seq(q, S_pad), _pad_seq(k, S_pad), _pad_seq(v, S_pad),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, s_valid=S)
    return out_p[:, :, :S], lse


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse_padded = _fwd_impl(q, k, v, causal, scale, block_q, block_k,
                                interpret)
    return out, (q, k, v, out, lse_padded)


def _bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse_padded = res   # lse keeps the padded length
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    S = q.shape[2]
    S_pad = _padded_len(S, block_q, block_k)
    dq, dk, dv = _flash_backward(
        _pad_seq(q, S_pad), _pad_seq(k, S_pad), _pad_seq(v, S_pad),
        _pad_seq(out, S_pad), lse_padded, _pad_seq(g, S_pad),
        causal=causal, scale=scale_, block_q=block_q, block_k=block_k,
        interpret=interpret, s_valid=S)
    return dq[:, :, :S], dk[:, :, :S], dv[:, :, :S]


flash_attention.defvjp(_fwd, _bwd)
