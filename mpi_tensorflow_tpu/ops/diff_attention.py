"""Differential attention over grouped heads, and the caches it reads.

The attention of the SambaY / phi4flash decoder (models/phi4_flash): no
positions, ``Hq`` query heads of width ``D`` over ``Hkv = Hq / 2`` key
and value heads, and heads in PAIRS (DIFF Transformer, arXiv:2410.05258).
KV pair ``j`` is KV heads ``(2j, 2j+1)``; it serves the two query pairs
``(4j, 4j+1)`` and ``(4j+2, 4j+3)``.  Query head ``h`` scores against
key head ``2 (h // 4) + h % 2`` and weighs the pair's values side by side
(``2D`` wide), so attention proper yields ``a`` of shape ``(.., Hq, 2D)``;
``combine`` then takes ``a_1 - lambda a_2`` a query pair, RMS-norms it
over ``2D`` and scales by ``1 - lambda_init``.

K and V live token-major and lane-dense, ``(.., tokens, Hkv * D)``, in
one of two stores that the decode kernel reads alike:

- the PAGED pool of the one full-attention layer, ``(num_blocks,
  block_size, Hkv * D)`` under the engine's block tables (written by
  ``write_paged``), which the cross layers read too;
- a window layer's RING, ``(slots + 1, window, Hkv * D)``: position ``p``
  of the sequence in slot ``s`` sits at ``[s, p % window]``
  (``paged_attention.write_ring``),
  so after a token's own write the ring holds exactly the keys
  ``p - window < j <= p`` it may see.  With no positional encoding the
  order of keys inside the softmax is free, so a ring IS a pool of
  one-block sequences: table ``[[slot]]``, block size ``window``, length
  ``min(p, window - 1)``.  Index ``slots`` is the slack row that padding
  rows of a dispatch write to.

``decode_attention`` is the Pallas kernel (one query token a row, the
serving hot path; ``name`` ``diff_attn_decode``) on
``paged_attention.paged_work``'s one-dimensional grid of live (row,
block) pairs; ``attention_xla`` is the anchor it is tested against and
the form every multi-token call takes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.ops.paged_attention import (PALLAS,
                                                    PALLAS_INTERPRET)

DECODE_KERNEL = "diff_attn_decode"
STAT_LANES = 128


def lambda_init(depth: int) -> float:
    """``0.8 - 0.6 exp(-0.3 * layer index)``."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


# ---------------- writes ----------------

def write_paged(pool, rows, block_table, positions, valid):
    """Scatter token rows ``(B, S, Hkv * D)`` into the paged pool at
    ``positions`` through the block table; invalid lanes land in the
    null block (``paged_attention.write_kv``'s contract)."""
    blk, off = paged_ops._slots(pool, block_table, positions, valid)
    return pool.at[blk, off].set(rows.astype(pool.dtype))


def ring_positions(start, W: int):
    """The position each ring entry holds before a chunk that starts at
    ``start`` (B,) is written: entry ``j`` has the largest ``p < start``
    with ``p % W == j``, or -1 where there is none."""
    j = jnp.arange(W, dtype=jnp.int32)[None]
    p = start[:, None] - 1 - ((start[:, None] - 1 - j) % W)
    return jnp.where(p >= 0, p, -1)


# ---------------- the XLA anchor ----------------

def attention_xla(q, k, v, vis, scale: float):
    """``q`` (B, S, Hq, D); ``k``, ``v`` (B, L, Hkv * D); ``vis`` bool
    (B, S, L).  Returns ``a`` (B, S, Hq, 2D) float32: each query head's
    softmax over its key head, applied to its pair's values."""
    B, S, Hq, D = q.shape
    L, P = k.shape[1], Hq // 4
    qg = q.reshape(B, S, P, 2, 2, D)            # pair j, query pair r, c
    kg = k.reshape(B, L, P, 2, D)
    vg = v.reshape(B, L, P, 2 * D)
    s = jnp.einsum("bsprcd,blpcd->bprcsl", qg, kg).astype(jnp.float32)
    s = jnp.where(vis[:, None, None, None], s * scale,
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    a = jnp.einsum("bprcsl,blpe->bsprce", p, vg,
                   preferred_element_type=jnp.float32)
    return a.reshape(B, S, Hq, 2 * D)


def gather_paged(pool, block_table):
    """(B, NB * bs, Hkv * D): a row's blocks in position order."""
    g = pool[block_table]
    return g.reshape(g.shape[0], -1, g.shape[-1])


def combine(a, lam, lam_init: float, subln, eps: float, dt):
    """``a`` (B, S, Hq, 2D) -> (B, S, Hq * D): per query pair
    ``RMSNorm(a_1 - lam a_2) * subln * (1 - lam_init)``."""
    B, S, Hq, W = a.shape
    a = a.astype(jnp.float32).reshape(B, S, Hq // 2, 2, W)
    o = a[..., 0, :] - lam * a[..., 1, :]
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * subln.astype(jnp.float32) * (1.0 - lam_init)
    return o.reshape(B, S, Hq // 2 * W).astype(dt)


# ---------------- the decode kernel ----------------

def _decode_kernel(bt_ref, len_ref, row_ref, blk_ref, n_ref,
                   q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *,
                   scale: float, block_size: int, pair_width: int):
    """One live (row, block) pair: all query heads in one pair of
    matmuls, as ``paged_attention_kernel._decode_kernel`` does.

    q_ref: (1, Hq, Hkv * D) the row's queries, head ``h`` in the lanes
           of ITS key head and exact zeros elsewhere
    k_ref, v_ref: (1, bs, Hkv * D) the block as stored
    o_ref: (1, Hq, 2D) float32, written at the row's last live block
    scratch: acc (Hq, Hkv * D) f32, m/l (Hq, STAT_LANES) f32

    ``q @ k.T`` over all lanes is head ``h``'s score in row ``h``;
    ``p @ v`` holds its pair's weighted values in lanes
    ``[2D (h // 4), 2D (h // 4 + 1))`` of row ``h``, the only lanes the
    emit keeps."""
    w = pl.program_id(0)
    b, j = row_ref[w], blk_ref[w]
    Hq, KW = q_ref.shape[1:]
    bs = block_size

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, jnp.finfo(jnp.float32).min)
        l_scr[:] = jnp.zeros_like(l_scr)

    v = v_ref[0]
    s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)    # (Hq, bs)
    col = j * bs + lax.broadcasted_iota(jnp.int32, (Hq, bs), 1)
    s = jnp.where(col <= len_ref[b], s * scale,
                  jnp.finfo(jnp.float32).min)
    m_prev, l_prev = m_scr[:, 0:1], l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc[:] = acc[:] * corr + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (Hq, KW)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_ref[w] - 1)
    def _emit():
        l = l_scr[:, 0:1]
        o = acc[:] / jnp.where(l == 0.0, 1.0, l)
        pair = lax.broadcasted_iota(jnp.int32, (Hq, pair_width), 0) // 4
        out = jnp.zeros((Hq, pair_width), jnp.float32)
        for g in range(KW // pair_width):
            out = out + jnp.where(
                pair == g, o[:, g * pair_width:(g + 1) * pair_width], 0.0)
        o_ref[0] = out


def decode_work(lengths, bs: int, NB: int):
    """``paged_attention.paged_work`` for one query token a row: built
    once a forward for each store (the pool's table, the rings' one
    block) and handed to every layer that reads it."""
    return paged_ops.paged_work(lengths, 1, bs, NB)


def decode_attention(q, k_store, v_store, table, lengths, *, scale: float,
                     interpret: bool = False, work=None):
    """One query token a row over a store's blocks in place.

    q:       (B, Hq, D)
    k/v_store: (blocks, bs, Hkv * D) — the paged pool, or a ring
    table:   (B, NB) int32 block ids in position order (a ring: the slot)
    lengths: (B,) int32: the row sees store positions ``<= lengths[b]``
             (its own token is already written)
    Returns ``a`` (B, Hq, 2D) float32."""
    B, Hq, D = q.shape
    bs, KW = k_store.shape[1:]
    NB = table.shape[1]
    lengths = lengths.astype(jnp.int32)
    row, blk, n, live = work if work is not None \
        else decode_work(lengths, bs, NB)
    # head h's query in the lanes of key head 2 (h // 4) + h % 2
    h = jnp.arange(Hq)
    own = (2 * (h // 4) + h % 2)[:, None] == jnp.arange(KW // D)[None]
    qbd = (q[:, :, None, :] * own[None, :, :, None].astype(q.dtype)) \
        .reshape(B, Hq, KW)

    def row_map(w, bt, lens, row, blk, n):
        return (row[w], 0, 0)

    def kv_map(w, bt, lens, row, blk, n):
        return (bt[row[w], blk[w]], 0, 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_size=bs,
                          pair_width=2 * D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(live,),
            in_specs=[pl.BlockSpec((1, Hq, KW), row_map),
                      pl.BlockSpec((1, bs, KW), kv_map),
                      pl.BlockSpec((1, bs, KW), kv_map)],
            out_specs=pl.BlockSpec((1, Hq, 2 * D), row_map),
            scratch_shapes=[pltpu.VMEM((Hq, KW), jnp.float32),
                            pltpu.VMEM((Hq, STAT_LANES), jnp.float32),
                            pltpu.VMEM((Hq, STAT_LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, 2 * D), jnp.float32),
        # the one axis carries each row's accumulators through its blocks
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=DECODE_KERNEL,
    )(table.astype(jnp.int32), lengths, row, blk, n, qbd, k_store, v_store)


def decode_attend(q, k_store, v_store, table, lengths, scale: float, *,
                  kernel: str, work=None):
    """THE one-token seam over a store: the kernel, or its XLA anchor
    (gather the row's blocks, mask by ``lengths``)."""
    if kernel in (PALLAS, PALLAS_INTERPRET):
        return decode_attention(q, k_store, v_store, table, lengths,
                                scale=scale, work=work,
                                interpret=kernel == PALLAS_INTERPRET)
    if kernel != "xla":
        raise ValueError(f"unresolved attention kernel {kernel!r}")
    k, v = gather_paged(k_store, table), gather_paged(v_store, table)
    vis = jnp.arange(k.shape[1])[None, None, :] <= lengths[:, None, None]
    return attention_xla(q[:, None], k, v, vis, scale)[:, 0]


@functools.lru_cache(maxsize=16)
def probe_compile(dtype_name: str, heads: int, head_dim: int,
                  block_size: int, window: int, sharding=None) -> None:
    """Compile the decode kernel over both stores of the geometry an
    engine is about to serve; a Mosaic refusal RAISES with the
    compiler's message and never selects another lowering."""
    dt = jnp.dtype(dtype_name)
    B, NB = 8, 4

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    KW = heads // 2 * head_dim
    for bs, nb in ((block_size, NB), (window, 1)):
        store = arg((1 + B * nb, bs, KW), dt)
        try:
            # graft-lint: jit-ok(compile probe: runs once at kernel resolve, not per step)
            jax.jit(functools.partial(decode_attention, scale=1.0)).lower(
                arg((B, heads, head_dim), dt), store, store,
                arg((B, nb), jnp.int32), arg((B,), jnp.int32)).compile()
        except Exception as e:
            raise RuntimeError(
                f"Pallas differential-attention decode kernel failed to "
                f"compile for {dtype_name}, Hq={heads}, D={head_dim}, "
                f"blocks of {bs}: {e}") from e


def resolve_kernel(choice: str, dtype, heads: int, head_dim: int,
                   block_size: int, window: int) -> str:
    """``paged_attention.resolve_kernel``'s rules with this kernel's
    probe."""
    return paged_ops.resolve_choice(
        choice, lambda: probe_compile(jnp.dtype(dtype).name, heads,
                                      head_dim, block_size, window))

