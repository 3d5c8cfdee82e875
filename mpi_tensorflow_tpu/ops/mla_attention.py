"""Latent (MLA) paged attention: one pool row per token, shared by all
heads.

Multi-head latent attention caches, per token and layer, the normalised
key/value latent ``c_kv`` (``kv_lora_rank`` wide) and the one rotated
rope key ``k_r`` (``qk_rope_head_dim`` wide) that all heads share, side
by side in one pool leaf ``(num_blocks, block_size, W)``: token-major and
lane-dense like the K/V pools of ops/paged_attention, so the row scatter
(``write_latent``), the runtime's default layout and the Mosaic operand
all take it in place.  ``W`` is ``C + R`` rounded up to whole 128-lane
tiles (``pool_width``: 576 -> 640, the pad lanes hold zeros): the runtime
keeps a leaf row-major only when its minor dimension fills the tiles, and
at 576 it rotated the token slot minor-most and every program copied the
pool (PERF.md, PR 28).  Block 0 is the null block, as there.

Two forms of the same attention, both over ``q_nope`` (B, S, H, Dn),
``q_rope`` (B, S, H, R) and the up-projection ``w_ukv`` (C, H, Dn + Dv):

- ``xla``: the equations as written.  Gather the row's blocks by its
  table, up-project the latents to per-head keys and values, masked
  fp32 softmax (``masked_softmax``).  The parity anchor, and what runs
  off the chip.
- ``pallas`` / ``pallas-interpret``: the ABSORBED form.  ``w_uk`` moves
  onto the query (``qt_h = q_nope_h W_uk_h^T``, C wide) and ``w_uv``
  onto the output, so the kernel reads nothing but the latent rows:
  ``score = qt_h . c_kv + q_rope_h . k_r``, ``ot_h = sum p c_kv``,
  ``o_h = ot_h W_uv_h``.  One kernel serves decode (S = 1) and chunked
  prefill: the (token, head) pairs of a row are its query rows, tiled
  ``q_tile`` tokens at a time.

The kernel's grid follows LIVE blocks.  A work list of (row, query
tile, kv block) triples is built on the device from ``lengths``
(``paged_attention.work_list``, the one list both Pallas attention
kernels walk) and rides in as scalar-prefetch operands; the grid's
one dimension is the list's live length, a traced value, so a
9,216-token table costs a 1,000-token row nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.ops.paged_attention import (PALLAS,
                                                    PALLAS_INTERPRET)

# the stable names of the Mosaic kernel in a device trace: one body, named
# apart by phase so that a trace can time decode and prefill separately
DECODE_KERNEL = "mla_decode_attention"
PREFILL_KERNEL = "mla_prefill_attention"
STAT_LANES = 128
Q_TILE = 8                       # query tokens per kernel tile (x H rows)


def pool_width(latent: int, rope: int) -> int:
    """Lanes of a pool row: ``latent + rope`` in whole 128-lane tiles."""
    return -(-(latent + rope) // 128) * 128


def write_latent(pool, latent, block_table, positions, valid):
    """Scatter per-token latent rows ``latent`` (B, S, C + R) into
    ``pool`` (num_blocks, block_size, W), zeros in the pad lanes; invalid
    lanes land in the null block (``paged_attention._slots``)."""
    blk, off = paged_ops._slots(pool, block_table, positions, valid)
    pad = pool.shape[-1] - latent.shape[-1]
    latent = jnp.pad(latent.astype(pool.dtype), ((0, 0), (0, 0), (0, pad)))
    return pool.at[blk, off].set(latent)


def gather_latent(pool, block_table):
    """(B, NB * block_size, W): the row's blocks in position order."""
    g = pool[block_table]
    return g.reshape(g.shape[0], -1, g.shape[-1])


def masked_softmax(s, vis):
    """fp32 softmax of scores ``s`` over the last axis with invisible
    lanes at ``finfo.min`` (exact 0.0 weight), as
    ``paged_attention.masked_softmax_attention`` does."""
    s = jnp.where(vis, s.astype(jnp.float32), jnp.finfo(jnp.float32).min)
    return jax.nn.softmax(s, axis=-1)


def attend_xla(q_nope, q_rope, pool, block_table, lengths, w_ukv, scale,
               dt, absorbed: bool = False):
    """The gather path.  ``absorbed`` computes the same attention in the
    kernel's form (tests pin the two forms to each other).  Returns
    (B, S, H, Dv)."""
    S, Dn = q_nope.shape[1], q_nope.shape[-1]
    g = gather_latent(pool, block_table).astype(dt)
    C, R = w_ukv.shape[0], q_rope.shape[-1]
    c, kr = g[..., :C], g[..., C:C + R]
    w_uk, w_uv = w_ukv[..., :Dn].astype(dt), w_ukv[..., Dn:].astype(dt)
    pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)
    vis = (jnp.arange(g.shape[1])[None, None, :]
           <= pos[:, :, None])[:, None]                 # (B, 1, S, L)
    s_rope = jnp.einsum("bshr,blr->bhsl", q_rope, kr)
    if absorbed:
        qt = jnp.einsum("bshd,chd->bshc", q_nope, w_uk)
        s = jnp.einsum("bshc,blc->bhsl", qt, c) + s_rope
        p = masked_softmax(s * scale, vis).astype(dt)
        ot = jnp.einsum("bhsl,blc->bshc", p, c)
        return jnp.einsum("bshc,chd->bshd", ot, w_uv)
    k_nope = jnp.einsum("blc,chd->blhd", c, w_uk)
    v = jnp.einsum("blc,chd->blhd", c, w_uv)
    s = jnp.einsum("bshd,blhd->bhsl", q_nope, k_nope) + s_rope
    p = masked_softmax(s * scale, vis).astype(dt)
    return jnp.einsum("bhsl,blhd->bshd", p, v)


def attend(q_nope, q_rope, pool, block_table, lengths, w_ukv, scale, dt,
           *, kernel: str = "xla"):
    """THE latent-attention seam: queries at positions
    ``[lengths[b], lengths[b] + S)`` whose latents ``write_latent``
    already scattered.  ``kernel`` is the resolved literal
    (``resolve_kernel``)."""
    if kernel == "xla":
        return attend_xla(q_nope, q_rope, pool, block_table, lengths,
                          w_ukv, scale, dt)
    if kernel not in (PALLAS, PALLAS_INTERPRET):
        raise ValueError(f"unresolved latent-attention kernel {kernel!r}")
    Dn = q_nope.shape[-1]
    w_uk, w_uv = w_ukv[..., :Dn].astype(dt), w_ukv[..., Dn:].astype(dt)
    qt = jnp.einsum("bshd,chd->bshc", q_nope, w_uk)
    ot = mla_paged_attention(qt, q_rope, pool, block_table, lengths,
                             scale=scale,
                             interpret=kernel == PALLAS_INTERPRET)
    return jnp.einsum("bshc,chd->bshd", ot, w_uv)


def _kernel(bt_ref, len_ref, row_ref, tile_ref, blk_ref, n_ref,
            q_ref, kv_ref, o_ref, acc, m_scr, l_scr, *, scale: float,
            heads: int, q_tile: int, latent: int):
    """One (row, query tile, kv block) step of the absorbed form.

    q_ref:  (1, q_tile * H, W) query rows, token-major then head: the
            absorbed query, the rope query, zeros in the pad lanes
    kv_ref: (1, bs, W) the pool block as stored
    o_ref:  (1, q_tile * H, C) weighted latents, written at the tile's
            last block
    scratch: acc (q_tile * H, C) f32, m/l (q_tile * H, STAT_LANES) f32
    """
    w = pl.program_id(0)
    b, t, j = row_ref[w], tile_ref[w], blk_ref[w]
    R, bs, C = q_ref.shape[1], kv_ref.shape[1], latent

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, jnp.finfo(jnp.float32).min)
        l_scr[:] = jnp.zeros_like(l_scr)

    kv = kv_ref[0]
    c = kv[:, :C]
    # one contraction over the whole row: latent and rope parts add up,
    # the pad lanes meet zeros
    s = lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)     # (R, bs)
    # visibility: key position <= the query token's position
    qpos = len_ref[b] + t * q_tile \
        + lax.broadcasted_iota(jnp.int32, (R, bs), 0) // heads
    col = j * bs + lax.broadcasted_iota(jnp.int32, (R, bs), 1)
    s = jnp.where(col <= qpos, s * scale, jnp.finfo(jnp.float32).min)
    m_prev, l_prev = m_scr[:, 0:1], l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc[:] = acc[:] * corr + lax.dot_general(
        p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (R, C)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_ref[w] - 1)
    def _emit():
        l = l_scr[:, 0:1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def mla_paged_attention(qt, q_rope, pool, block_table, lengths, *,
                        scale: float, interpret: bool = False):
    """Absorbed latent attention over pool blocks in place.

    qt:     (B, S, H, C) queries with ``w_uk`` absorbed
    q_rope: (B, S, H, R) rotated rope queries
    pool:   (num_blocks, block_size, W >= C + R)
    Returns the weighted latents (B, S, H, C) in ``qt.dtype``; the
    caller applies ``w_uv``.  Lanes past a row's real tokens are the
    caller's to mask (their output is discarded), as in
    ``paged_attention.attend``."""
    B, S, H, C = qt.shape
    W = pool.shape[-1]
    bs, NB = pool.shape[1], block_table.shape[1]
    tq = min(Q_TILE, 1 << (S - 1).bit_length())   # pow2 >= S, capped
    NT = -(-S // tq)
    q = jnp.concatenate([qt, q_rope.astype(qt.dtype)], axis=-1)
    q = jnp.pad(q, ((0, 0), (0, NT * tq - S), (0, 0),
                    (0, W - q.shape[-1])))
    q = q.reshape(B, NT * tq * H, W)
    lengths = lengths.astype(jnp.int32)
    row, tile, blk, n, live = paged_ops.work_list(lengths, S, tq, NT, bs, NB)
    R = tq * H

    def q_map(w, bt, lens, row, tile, blk, n):
        return (row[w], tile[w], 0)

    def kv_map(w, bt, lens, row, tile, blk, n):
        return (bt[row[w], blk[w]], 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=H, q_tile=tq,
                          latent=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(live,),
            in_specs=[pl.BlockSpec((1, R, W), q_map),
                      pl.BlockSpec((1, bs, W), kv_map)],
            out_specs=pl.BlockSpec((1, R, C), q_map),
            scratch_shapes=[pltpu.VMEM((R, C), jnp.float32),
                            pltpu.VMEM((R, STAT_LANES), jnp.float32),
                            pltpu.VMEM((R, STAT_LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, NT * R, C), qt.dtype),
        # the one axis carries the online-softmax accumulators in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name=DECODE_KERNEL if S == 1 else PREFILL_KERNEL,
    )(block_table.astype(jnp.int32), lengths, row, tile, blk, n, q, pool)
    return out.reshape(B, NT * tq, H, C)[:, :S]


@functools.lru_cache(maxsize=16)
def probe_compile(dtype_name: str, heads: int, latent: int, rope: int,
                  block_size: int, prefill_chunk: int,
                  sharding=None) -> None:
    """Compile the kernel for the geometry an engine is about to serve
    (decode and every pow2 prefill bucket up to ``prefill_chunk`` whose
    tile shape differs); a Mosaic refusal RAISES with the compiler's
    message, as ``paged_attention_kernel.probe_compile`` does."""
    dt = jnp.dtype(dtype_name)
    B, NB = 2, 4

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = arg((1 + B * NB, block_size, pool_width(latent, rope)), dt)
    S = 1
    while S <= min(prefill_chunk, 2 * Q_TILE):
        try:
            # graft-lint: jit-ok(compile probe: runs once at kernel resolve, not per step)
            jax.jit(functools.partial(
                mla_paged_attention, scale=1.0)).lower(
                arg((B, S, heads, latent), dt),
                arg((B, S, heads, rope), dt), pool,
                arg((B, NB), jnp.int32), arg((B,), jnp.int32)).compile()
        except Exception as e:
            raise RuntimeError(
                f"Pallas latent-attention kernel failed to compile for "
                f"{dtype_name}, H={heads}, C={latent}, R={rope}, "
                f"block_size={block_size}, S={S}: {e}") from e
        S *= 2


def resolve_kernel(choice: str, dtype, heads: int, latent: int, rope: int,
                   block_size: int, prefill_chunk: int) -> str:
    """``paged_attention.resolve_kernel``'s rules with this kernel's
    probe: "xla" stays, "pallas" is Mosaic on TPU and the interpreter
    elsewhere, "auto" is Mosaic on TPU and "xla" elsewhere; a kernel that
    is selected is compiled first and a refusal raises."""
    return paged_ops.resolve_choice(choice, lambda: probe_compile(
        jnp.dtype(dtype).name, heads, latent, rope, block_size,
        prefill_chunk))
