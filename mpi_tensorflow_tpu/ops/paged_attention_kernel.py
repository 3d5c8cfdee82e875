"""Fused Pallas paged-attention kernel — decode + chunked prefill.

The XLA paged path (ops/paged_attention.gather_kv + paged_attention)
materializes the whole padded contiguous KV view — two pool-sized
copies per layer per decode token, then a dense masked softmax over the
full bucketed table width.  This kernel reads the pool **blocks in
place** through the block table with an fp32 online softmax (the
PagedAttention / Flash-Decoding recipe, PAPERS.md): K and V blocks
stream HBM->VMEM a grid step at a time, scores and the running (m, l,
acc) statistics stay in VMEM scratch, and the ``(B, H, NB*block_size,
D)`` gathered view never exists.

Grid: one dimension, the LIVE (row, step) pairs of the dispatch.  A
row of length ``len_b`` has ``ceil((len_b + S) / block_size)`` live
blocks (at least one, at most the table's width) and a step attends
``G`` consecutive table entries of it (``ops/paged_attention.
step_blocks``: a group on the decode body, one elsewhere); the pairs,
row-major with a row's steps ascending, are built on the device from
``lengths`` (``ops/paged_attention.work_list``, the list the latent
kernel of ops/mla_attention walks too) and the grid's bound is their
count, a traced value.  The list and the block table ride in as
**scalar-prefetch** operands, so a call costs its live steps, not its
slots x table bucket.  The one axis carries each row's accumulators
through its steps in order (``"arbitrary"``): a v5e chip has one
TensorCore, so nothing is lost; a two-core chip would want the rows
split between the cores first.

Two bodies fetch the pool in two ways:

- ``_paged_kernel`` (prefill chunks, speculative verify, mixed rows,
  int8 and int4 pools) rides the **BlockSpec pipeline**, one block a
  step: step ``w``'s index maps pick the row's query block
  (``row[w]``) and the pool block to DMA (``bt[row[w], blk[w]]``)
  before the body runs.  The pipeline evaluates the maps of the step
  after the one it runs, so the list holds one valid entry past its
  bound (``paged_work``).
- ``_decode_kernel`` (one query token, an unquantized pool: the hot
  path) takes the pools in place in HBM (``memory_space=pl.ANY``) and
  **issues its copies by hand**: a pipeline step costs ~0.5 us
  whatever it moves, and a 16-token block is 49 KB, so a step takes a
  GROUP of ``G`` blocks (128 keys: one lane tile of scores) into one
  of two VMEM slots a pool, ``G`` async copies of K and ``G`` of V
  addressed from the table (``_group_ids``, ``_group_copies``).  Step
  ``w`` starts the copies of step ``w + 1`` before it waits for its
  own, so a group's fetch hides behind the step before it; queries,
  output and the statistics stay on their BlockSpecs.  A last group's
  dead tail — null-block entries past the row's allocation, entries
  clamped to the edge of a table ``G`` does not divide — is fetched
  like any block: the visibility test rejects its lanes (not issuing
  those copies measured slower: PERF.md, PR 33).

Masking contract (kept in LOCKSTEP with ops/paged_attention.
paged_attention — the parity suites in tests/test_paged_kernel.py and
tests/test_cohere2_moe.py pin it): a key lane at absolute position ``col
= j*block_size + offset`` is visible iff ``col <= q_position`` and,
under a sliding ``window`` W, ``q_position - W < col`` (``_visible``; the
list then starts a row's or tile's steps at its first block that holds
a visible key); invisible lanes score ``finfo(f32).min`` so their
softmax weight underflows to exact 0.0.

Grouped-query attention: an unquantized pool may hold ``Hkv`` KV heads
for ``H = G * Hkv`` query heads (KV head ``j`` serving ``[j*G,
(j+1)*G)``).  Such a call is named apart in a device trace: decode runs
``_decode_kernel`` with head-major queries, a KV head's ``G`` rows a
matmul (``gqa_decode_attention``), and a prefill chunk runs
``_grouped_kernel`` on a grid of (KV head, (row, query tile, block))
(``gqa_prefill_attention``).  ``G = 1`` lowers as it did.
Null-block (block 0) lanes and bucket-slack rows need no special
branch: null blocks only back table entries past a row's allocation,
whose positions the visibility test already rejects, and slack rows
(all-null table, length 0) produce garbage the engine discards —
exactly as on the XLA path.

Pool layout is token-major and lane-dense — ``(num_blocks, block_size,
H*D)`` — so a fetched block is ``(block_size, H*D)``: whole 128-lane
tiles, row-major, which is what a Mosaic operand must be AND what the
runtime's default layout and ``write_kv``'s row scatter already are, so
the pool reaches the kernel with no re-layout (a 64-wide minor ``D``
made the runtime rotate ``num_blocks`` minor-most, and every program
copied every leaf to and fro).  ``_paged_kernel`` takes head ``h`` out
of the row as the static lane slice ``[h*D, (h+1)*D)`` and runs the
same online softmax per head; ``H`` and ``D`` come from ``q``.  The
decode body skips even the slicing: block-diagonal queries meet the
whole row in one pair of matmuls.

``probe_compile()`` compiles the served geometry (decode + every
prefill bucket, each at its largest dispatch: the table and the list
must fit scalar memory, the decode body's slots VMEM) up front so a
refusal surfaces at engine build in words — it never selects another
lowering; ``interpret=True`` runs the same kernel on CPU for the tier-1
parity suite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_tensorflow_tpu.ops.paged_attention import (first_block,
                                                    kv_heads, paged_work,
                                                    pool_mode,
                                                    step_blocks,
                                                    work_list)

# stats rows are lane-broadcast to the f32 tile width, mirroring
# ops/flash_attention's LSE_LANES treatment of per-row statistics
STAT_LANES = 128
# the bodies' names in a device trace (``_paged_call`` is jitted, so
# without them every call of any would read ``_paged_call.N``); a
# grouped-query call (fewer KV heads than query heads) is named apart
DECODE_KERNEL, PAGED_KERNEL = "paged_decode_attention", "paged_attention"
GQA_DECODE_KERNEL = "gqa_decode_attention"
GQA_PREFILL_KERNEL = "gqa_prefill_attention"
# query rows (query heads of a group x the tile's tokens) one step of the
# grouped prefill body scores at once
GQA_TILE_ROWS = 1024


def _visible(col, qpos, window):
    """The masking contract: key ``col`` is visible to a query at
    ``qpos`` iff ``col <= qpos`` and, under a sliding window ``W``,
    ``qpos - W < col``."""
    vis = col <= qpos
    if window is not None:
        vis = vis & (col > qpos - window)
    return vis


def query_tile(S: int, G: int) -> int:
    """Tokens of a chunk one step of the grouped prefill body takes: the
    largest power of two dividing ``S`` whose ``G`` query heads fit
    ``GQA_TILE_ROWS`` rows (one row a token and head)."""
    tq = 1
    while tq * 2 <= max(1, GQA_TILE_ROWS // G) and S % (tq * 2) == 0:
        tq *= 2
    return tq


def _dequant_int4_block(codes, scales, dt):
    """In-register int4 dequant of one head of a fetched pool block —
    the exact ops/paged_attention.dequantize_kv_int4 contract (unpack
    split-half nibbles, sign-extend, scale per D-group).

    codes:  (bs, D//2) uint8 packed, scales: (bs, G) fp32.
    Returns (bs, D) in ``dt``.
    """
    c = codes.astype(jnp.int32)
    lo = c & 0xF
    hi = (c >> 4) & 0xF
    full = jnp.concatenate([lo, hi], axis=-1)          # (bs, D)
    full = full - jnp.where(full > 7, 16, 0)
    D = full.shape[-1]
    G = scales.shape[-1]
    # expand the (bs, G) group scales to (bs, D) by lane select:
    # Mosaic has no layout for the minor-dim split reshape
    # (bs, D) -> (bs, G, D//G) the XLA path uses, while a width-1
    # lane slice broadcast along lanes is native.  G is small and static
    group = lax.broadcasted_iota(jnp.int32, full.shape, 1) // (D // G)
    sc = jnp.broadcast_to(scales[:, 0:1], full.shape)
    for g in range(1, G):
        sc = jnp.where(group == g, scales[:, g:g + 1], sc)
    return (full.astype(jnp.float32) * sc).astype(dt)


def _head_block(ref, scale_ref, h: int, H: int, mode: str, dt):
    """Head ``h``'s ``(bs, D)`` K or V rows of the fetched block, in
    ``dt``: a static lane slice of the ``(1, bs, H*W)`` block (``W`` is
    D, or D//2 packed), dequantized from the same head's slice of the
    scale block where the pool holds codes."""
    W = ref.shape[-1] // H
    x = ref[0, :, h * W:(h + 1) * W]
    if mode == "int8":
        return (x.astype(jnp.float32)
                * scale_ref[0, :, h:h + 1]).astype(dt)
    if mode == "int4":
        G = scale_ref.shape[-1] // H
        return _dequant_int4_block(
            x, scale_ref[0, :, h * G:(h + 1) * G], dt)
    return x


def _paged_kernel(*refs, scale: float, block_size: int,
                  mode: str = "fp32", residual: bool = False,
                  window=None):
    """One live (row, kv-block) pair of the online softmax: step ``w``
    of the work list is block ``j = blk[w]`` of row ``b = row[w]``, which
    has ``n[w]`` live blocks.

    q_ref:  (1, H, S, D)   — the row's whole query block (revisited)
    k_ref:  (1, bs, H*D)   — pool block ``bt[b, j]``
    v_ref:  (1, bs, H*D)
    o_ref:  (1, H, S, D)   — written once, at the row's last live block
    scratch: acc (H, S, D) f32, m/l (H, S, STAT_LANES) f32

    The heads are a static loop: head ``h`` reads lanes
    ``[h*D, (h+1)*D)`` of the K and V rows (``_head_block``) and runs its
    own two matmuls and softmax update on 2-d tiles.

    ``mode`` selects the pool storage format the step consumes:

    - "int8" (--kv-dtype int8): k/v_ref hold int8 codes and two
      extra refs ride between them — ks_ref/vs_ref, the ``(1, bs, H)``
      fp32 row scales of the SAME pool block (their BlockSpec shares
      the kv index map, so code block and scale block can never skew).
      The codes dequantize IN REGISTER right here — ``(codes.astype(f32)
      * scale).astype(q.dtype)``, the exact ops/paged_attention.
      dequantize_kv contract the XLA gather path applies elementwise —
      before the unchanged fp32 matmul/softmax; no fp pool ever
      materializes.
    - "int4" (--kv-dtype int4): k/v_ref hold ``(1, bs, H*D//2)``
      nibble-packed uint8 codes, ks/vs_ref the ``(1, bs, H*G)`` fp32
      GROUP scales; ``_dequant_int4_block`` unpacks + dequantizes in
      register (the dequantize_kv_int4 contract).

    ``window`` W (a sliding-window layer) hides keys at or below
    ``qpos - W`` too; the list then starts a row at its first block that
    holds a visible key, where the statistics start.

    ``residual`` (int4 only) adds the KIVI fp-residual self lane: two
    more refs kn_ref/vn_ref — ``(1, H, S, D)`` fp K/V of exactly the
    query tokens (q_map-indexed, revisited each step).  Where a score
    column IS the query row's own position (``col == qpos``), the int4
    score is overridden with the exact fp dot product ``q · kn`` BEFORE
    scale+mask, and that column's probability weights ``vn`` instead of
    the dequantized pool V — the in-kernel mirror of
    ops/paged_attention.paged_attention_self_residual, so both
    lowerings agree within tolerance.  The self column lives in exactly
    one live grid step; the denominator (l) keeps its weight.
    """
    ks_ref = vs_ref = kn_ref = vn_ref = None
    _, len_ref, row_ref, blk_ref, n_ref = refs[:5]
    if mode == "int4" and residual:
        (q_ref, kn_ref, vn_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
         acc, m_scr, l_scr) = refs[5:]
    elif mode in ("int8", "int4"):
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
         acc, m_scr, l_scr) = refs[5:]
    else:
        q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr = refs[5:]
    w = pl.program_id(0)
    b, j = row_ref[w], blk_ref[w]
    H, S, D = q_ref.shape[1:]
    bs = block_size

    # a row's first step: 0, or under a window the list's own start
    first = 0 if window is None else first_block(len_ref[b], window, bs)

    @pl.when(j == first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, jnp.finfo(jnp.float32).min)
        l_scr[:] = jnp.zeros_like(l_scr)

    # visibility: key position <= query position (and inside the
    # window), exactly the XLA path's mask (q positions are
    # lengths[b] + [0, S))
    col = j * bs + lax.broadcasted_iota(jnp.int32, (S, bs), 1)
    qpos = len_ref[b] + lax.broadcasted_iota(jnp.int32, (S, bs), 0)
    self_m = col == qpos if residual else None     # (S, bs)
    for h in range(H):
        q = q_ref[0, h]                            # (S, D)
        k = _head_block(k_ref, ks_ref, h, H, mode, q.dtype)  # (bs, D)
        v = _head_block(v_ref, vs_ref, h, H, mode, q.dtype)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (S, bs)
        if residual:
            # fp self lane: exact q·k_new score for each row's own
            # column, overriding the int4 score BEFORE scale+mask
            s_self = jnp.sum(
                q.astype(jnp.float32)
                * kn_ref[0, h].astype(jnp.float32),
                axis=-1, keepdims=True)            # (S, 1)
            s = jnp.where(self_m, s_self, s)
        s = jnp.where(_visible(col, qpos, window), s * scale,
                      jnp.finfo(jnp.float32).min)
        m_prev = m_scr[h, :, 0:1]                  # (S, 1)
        l_prev = l_scr[h, :, 0:1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # (S, bs)
        corr = jnp.exp(m_prev - m_new)             # (S, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if residual:
            # the self column's weight multiplies the fp v_new row,
            # not the dequantized pool row; l keeps the full p sum
            p_self = jnp.sum(jnp.where(self_m, p, 0.0),
                             axis=-1, keepdims=True)   # (S, 1)
            p = jnp.where(self_m, 0.0, p)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (S, D)
        if residual:
            pv = pv + p_self * vn_ref[0, h].astype(jnp.float32)
        acc[h] = acc[h] * corr + pv
        m_scr[h] = jnp.broadcast_to(m_new, (S, STAT_LANES))
        l_scr[h] = jnp.broadcast_to(l_new, (S, STAT_LANES))

    @pl.when(j == n_ref[w] - 1)
    def _emit():
        l = l_scr[:, :, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)


def _group_ids(bt_ref, b, j, G: int):
    """The pool block ids of group ``j`` of row ``b``'s table — entries
    ``[j*G, (j+1)*G)`` — as ``G`` scalars.  A table whose width ``G``
    does not divide has its last group's entries clamped to the table's
    edge: the lanes those fill lie past ``NB * bs``, which no length
    reaches."""
    NB = bt_ref.shape[1]
    return [bt_ref[b, jnp.minimum(j * G + g, NB - 1) if NB % G
                   else j * G + g] for g in range(G)]


def _group_copies(ids, pool_ref, buf, sem):
    """The async copies of pool blocks ``ids`` from HBM into the VMEM
    slot ``buf`` ``(len(ids)*bs, lanes)``, block ``g`` at rows ``[g*bs,
    (g+1)*bs)``, all on the one DMA semaphore ``sem``: the hand-issued
    fetch of one grid step.  ``start()`` each to issue the group;
    ``wait()`` each before the slot is read — a wait takes the
    semaphore and the copy's size only, so the waiting side may rebuild
    the list from any ids (block 0 will do)."""
    bs = pool_ref.shape[1]
    return [pltpu.make_async_copy(pool_ref.at[i],
                                  buf.at[pl.ds(g * bs, bs)], sem)
            for g, i in enumerate(ids)]


def _decode_kernel(bt_ref, len_ref, row_ref, grp_ref, n_ref,
                   q_ref, k_hbm, v_hbm, o_ref, acc, m_scr, l_scr,
                   k_buf, v_buf, sems, *,
                   scale: float, head_dim: int, group: int,
                   heads_per_kv: int = 1, window=None):
    """A single query token over an unquantized pool — the decode hot
    path: step ``w`` of the work list attends GROUP ``j = grp[w]`` of
    row ``b = row[w]`` — ``group`` consecutive table entries, fetched by
    hand — with ALL heads in one pair of matmuls, or with grouped-query
    heads one pair a KV head (below).

    q_ref:  (1, H, H*D)  — the row's queries laid out block-diagonally:
            row ``h`` holds head ``h``'s query in lanes ``[h*D, (h+1)*D)``
            and exact zeros elsewhere (built by ``_paged_call``)
    k_hbm:  (num_blocks, bs, H*D), v_hbm: idem — the pools, in place
    o_ref:  (1, 1, H*D)  — the heads' outputs side by side
    scratch: acc (H, H*D) f32, m/l (H, STAT_LANES) f32; k_buf / v_buf
            (2, group*bs, H*D) — two slots a pool; sems (2, 2) DMA

    The pools never pass through the BlockSpec pipeline, whose step
    costs ~0.5 us whatever it moves (PERF.md, PR 29): step ``w`` starts
    the copies of step ``w + 1`` (its row and group from the list) into
    the other slot BEFORE it waits for its own, which step ``w - 1``
    started (step 0 starts its own).  Entries past a row's allocation
    are the null block and are fetched like any other: the visibility
    test rejects their lanes.

    ``q @ k.T`` over all ``H*D`` lanes is head ``h``'s score in row
    ``h`` (the other heads' lanes meet zeros), and ``p @ v`` is head
    ``h``'s output in lanes ``[h*D, (h+1)*D)`` of row ``h``; the other
    lanes of a row are another head's values under this head's weights
    and are dropped at the emit.  That spends H times the arithmetic on
    an idle MXU and saves the per-head loop: with one query row a head's
    tiles are a sublane each, and the loop's 2*H tiny matmuls and H
    softmax updates cost three times this step (PERF.md, PR 25).  Same
    visibility test and fp32 online softmax as ``_paged_kernel``.

    Grouped-query heads (``heads_per_kv`` G > 1: the pool holds ``Hkv =
    H / G`` heads): q_ref is ``(1, H, D)`` head-major, o_ref ``(1, H,
    D)``, acc ``(H, D)``, and KV head ``k``'s lanes ``[k*D, (k+1)*D)``
    of the slot are scored against ITS G query rows ``[k*G, (k+1)*G)``
    as one ``(G, D) x (D, keys)`` matmul, then weigh its values in
    another: no arithmetic spent on other heads' lanes.  ``window``
    (only where the pool is not already a window's ring) hides keys at
    or below ``p - W``; the list starts a row at its first group that
    holds a visible key.
    """
    w = pl.program_id(0)
    b, j = row_ref[w], grp_ref[w]
    H = q_ref.shape[1]
    keys = k_buf.shape[1]                          # group * bs
    slot = lax.rem(w, 2)
    stores = ((k_hbm, k_buf), (v_hbm, v_buf))

    def start(step, slot):
        ids = _group_ids(bt_ref, row_ref[step], grp_ref[step], group)
        for i, (pool, buf) in enumerate(stores):
            for c in _group_copies(ids, pool, buf.at[slot],
                                   sems.at[i, slot]):
                c.start()

    def wait(i):
        pool, buf = stores[i]
        for c in _group_copies([0] * group, pool, buf.at[slot],
                               sems.at[i, slot]):
            c.wait()

    @pl.when(w == 0)
    def _first():
        start(w, slot)

    @pl.when(w + 1 < pl.num_programs(0))
    def _ahead():
        start(w + 1, 1 - slot)

    first = 0 if window is None else first_block(len_ref[b], window, keys)

    @pl.when(j == first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, jnp.finfo(jnp.float32).min)
        l_scr[:] = jnp.zeros_like(l_scr)

    if heads_per_kv > 1:
        _grouped_decode_step(j, len_ref[b], q_ref, o_ref, acc, m_scr,
                             l_scr, k_buf.at[slot], v_buf.at[slot],
                             lambda: wait(0), lambda: wait(1),
                             n_ref[w], scale=scale, head_dim=head_dim,
                             heads_per_kv=heads_per_kv, window=window)
        return
    HD = q_ref.shape[2]
    wait(0)
    s = lax.dot_general(
        q_ref[0], k_buf[slot], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (H, G*bs)
    # visibility: key position <= query position (= lengths[b])
    col = j * keys + lax.broadcasted_iota(jnp.int32, (H, keys), 1)
    s = jnp.where(_visible(col, len_ref[b], window), s * scale,
                  jnp.finfo(jnp.float32).min)
    m_prev = m_scr[:, 0:1]                         # (H, 1)
    l_prev = l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                         # (H, G*bs)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    wait(1)
    v = v_buf[slot]                                # (G*bs, H*D)
    acc[:] = acc[:] * corr + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (H, H*D)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_ref[w] - 1)
    def _emit():
        l = l_scr[:, 0:1]
        o = acc[:] / jnp.where(l == 0.0, 1.0, l)       # (H, H*D)
        own = (lax.broadcasted_iota(jnp.int32, (H, HD), 1) // head_dim
               == lax.broadcasted_iota(jnp.int32, (H, HD), 0))
        o_ref[0] = jnp.sum(jnp.where(own, o, 0.0), axis=0,
                           keepdims=True).astype(o_ref.dtype)


def _grouped_decode_step(j, p, q_ref, o_ref, acc, m_scr, l_scr, k, v,
                         wait_k, wait_v, n, *, scale: float,
                         head_dim: int, heads_per_kv: int, window):
    """``_decode_kernel``'s step for grouped-query heads: group ``j`` of
    a row whose query sits at ``p`` (``k``, ``v``: the VMEM slot the
    step's copies land in, ``(G*bs, Hkv*D)``), each KV head against its
    own ``heads_per_kv`` query rows; V is waited for after every score."""
    D, G = head_dim, heads_per_kv
    Hkv = k.shape[1] // D
    keys = k.shape[0]
    col = j * keys + lax.broadcasted_iota(jnp.int32, (G, keys), 1)
    vis = _visible(col, p, window)
    wait_k()
    probs = []
    for h in range(Hkv):
        rows = slice(h * G, (h + 1) * G)
        s = lax.dot_general(
            q_ref[0, rows, :], k[:, h * D:(h + 1) * D],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (G, G*bs)
        s = jnp.where(vis, s * scale, jnp.finfo(jnp.float32).min)
        m_prev = m_scr[rows, 0:1]                  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[rows] = jnp.broadcast_to(
            l_scr[rows, 0:1] * corr + jnp.sum(pr, axis=-1, keepdims=True),
            (G, STAT_LANES))
        m_scr[rows] = jnp.broadcast_to(m_new, (G, STAT_LANES))
        probs.append((pr, corr))
    wait_v()
    for h, (pr, corr) in enumerate(probs):
        rows = slice(h * G, (h + 1) * G)
        vh = v[:, h * D:(h + 1) * D]               # (G*bs, D)
        acc[rows] = acc[rows] * corr + lax.dot_general(
            pr.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (G, D)

    @pl.when(j == n - 1)
    def _emit():
        l = l_scr[:, 0:1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _grouped_kernel(bt_ref, len_ref, row_ref, tile_ref, blk_ref, n_ref,
                    q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *,
                    scale: float, block_size: int, window):
    """Grouped-query attention of a prefill chunk: grid step ``(h, w)``
    is KV head ``h`` against list entry ``w`` — block ``j = blk[w]`` of
    tile ``t = tile[w]`` of row ``b = row[w]``, whose ``tq`` query tokens
    sit at ``lengths[b] + t*tq + [0, tq)``.

    q_ref:  (1, G, tq, D)  — the tile of KV head h's G query heads
    k_ref:  (1, bs, D)     — KV head h's lanes of pool block ``bt[b, j]``
    v_ref:  (1, bs, D)
    o_ref:  (1, G, tq, D)  — written at the tile's last block
    scratch: acc (G*tq, D) f32, m/l (G*tq, STAT_LANES) f32

    The G heads' tiles are ONE ``(G*tq, D) x (D, bs)`` matmul: row ``r``
    is head ``r // tq``'s query ``r % tq`` (``tq`` a power of two).  A
    chunk of S tokens and G heads would need ``G*S`` rows of scores and
    statistics at once; tiles keep a step's VMEM to a few MB whatever
    the chunk, and a tile's list stops at ITS last query's block (and,
    under a window, starts at its first query's first visible one)."""
    w = pl.program_id(1)
    b, t, j = row_ref[w], tile_ref[w], blk_ref[w]
    G, tq, D = q_ref.shape[1:]
    rows, bs = G * tq, block_size
    p0 = len_ref[b] + t * tq                       # the tile's first query
    first = 0 if window is None else first_block(p0, window, bs)

    @pl.when(j == first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, jnp.finfo(jnp.float32).min)
        l_scr[:] = jnp.zeros_like(l_scr)

    q = q_ref[0].reshape(rows, D)
    s = lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (rows, bs)
    col = j * bs + lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
    qpos = p0 + (lax.broadcasted_iota(jnp.int32, (rows, bs), 0)
                 & (tq - 1))
    s = jnp.where(_visible(col, qpos, window), s * scale,
                  jnp.finfo(jnp.float32).min)
    m_prev, l_prev = m_scr[:, 0:1], l_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0]
    acc[:] = acc[:] * corr + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (rows, D)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_ref[w] - 1)
    def _emit():
        l = l_scr[:, 0:1]
        o = acc[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.reshape(G, tq, D).astype(o_ref.dtype)


def _grouped_prefill_call(q, k_pool, v_pool, block_table, lengths, *,
                          scale: float, interpret: bool, window):
    """``_grouped_kernel`` over a chunk: the list is (row, tile, block)
    triples built here at the chunk's tile (``query_tile``), one entry
    past its bound for the pipeline's look-ahead, and every KV head
    walks it (the grid's outer axis)."""
    B, H, S, D = q.shape
    Hkv = k_pool.shape[-1] // D
    G, NB, bs = H // Hkv, block_table.shape[1], k_pool.shape[1]
    tq = query_tile(S, G)
    row, tile, blk, n, live = work_list(lengths, S, tq, S // tq, bs, NB,
                                        window)
    row, tile, blk, n = (jnp.pad(x, (0, 1)) for x in (row, tile, blk, n))

    def q_map(h, w, bt, lens, row, tile, blk, n):
        return (row[w], h, tile[w], 0)

    def kv_map(h, w, bt, lens, row, tile, blk, n):
        return (bt[row[w], blk[w]], 0, h)

    return pl.pallas_call(
        functools.partial(_grouped_kernel, scale=scale, block_size=bs,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(Hkv, live),
            in_specs=[pl.BlockSpec((1, G, tq, D), q_map),
                      pl.BlockSpec((1, bs, D), kv_map),
                      pl.BlockSpec((1, bs, D), kv_map)],
            out_specs=pl.BlockSpec((1, G, tq, D), q_map),
            scratch_shapes=[pltpu.VMEM((G * tq, D), jnp.float32),
                            pltpu.VMEM((G * tq, STAT_LANES), jnp.float32),
                            pltpu.VMEM((G * tq, STAT_LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        # both axes carry a tile's accumulators through its blocks in
        # order (a v5e chip has one TensorCore)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=GQA_PREFILL_KERNEL,
    )(block_table.astype(jnp.int32), lengths, row, tile, blk, n,
      q, k_pool, v_pool)


# jitted so that a forward's layers, which call it on equal shapes,
# trace it and lower its kernel to Mosaic once a program, not once a
# layer: the decode body's 3 x 2G copy descriptors made a program's
# lowering 0.2 s longer on the chip's host, forty programs a set-up
@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "mode", "window"))
def _paged_call(q, k_pool, v_pool, block_table, lengths, *,
                scale: float, interpret: bool, mode: str,
                k_scale=None, v_scale=None, k_new=None, v_new=None,
                work=None, window=None):
    B, H, S, D = q.shape
    NB = block_table.shape[1]
    bs = k_pool.shape[1]
    residual = k_new is not None
    lengths = lengths.astype(jnp.int32)
    Hkv = kv_heads(q, k_pool, k_scale)
    if S > 1 and Hkv < H:
        return _grouped_prefill_call(q, k_pool, v_pool, block_table,
                                     lengths, scale=scale,
                                     interpret=interpret, window=window)
    G = step_blocks(S, k_pool, k_scale)
    if work is None:
        work = paged_work(lengths, S, bs, NB, G, window)
    row, blk, n, live = work

    def kv_map(w, bt, lens, row, blk, n):
        return (bt[row[w], blk[w]], 0, 0)

    lane_dense = S == 1 and mode == "fp32"
    name = PAGED_KERNEL
    if lane_dense and Hkv < H:
        # grouped-query decode: head-major queries in and out, a KV
        # head's G query rows against its lanes (_grouped_decode_step)
        kernel = functools.partial(_decode_kernel, scale=scale,
                                   head_dim=D, group=G,
                                   heads_per_kv=H // Hkv, window=window)
        q = q.reshape(B, H, D)
        q_block = out_block = (1, H, D)
        lead, acc_shape, name = (H,), (H, D), GQA_DECODE_KERNEL
    elif lane_dense:
        # decode over an unquantized pool: block-diagonal queries in,
        # (1, H*D) rows out, the pools in place (_decode_kernel)
        kernel = functools.partial(_decode_kernel, scale=scale,
                                   head_dim=D, group=G, window=window)
        eye = jnp.eye(H, dtype=q.dtype)[None, :, :, None]
        q = (q[:, :, 0, None, :] * eye).reshape(B, H, H * D)
        q_block, out_block, lead = (1, H, H * D), (1, 1, H * D), (H,)
        acc_shape, name = (H, H * D), DECODE_KERNEL
    else:
        kernel = functools.partial(_paged_kernel, scale=scale,
                                   block_size=bs, mode=mode,
                                   residual=residual, window=window)
        q_block = out_block = (1, H, S, D)
        lead, acc_shape = (H, S), (H, S, D)

    def row_map(w, bt, lens, row, blk, n):
        return (row[w],) + (0,) * (len(q_block) - 1)

    in_specs = [pl.BlockSpec(q_block, row_map)]
    operands = [q]
    if residual:
        # fp residual K/V of the query tokens: row_map-indexed, so every
        # step of a row revisits the row's own (1, H, S, D) block
        in_specs += [pl.BlockSpec(q_block, row_map)] * 2
        operands += [k_new, v_new]
    scratch = [
        pltpu.VMEM(acc_shape, jnp.float32),
        pltpu.VMEM(lead + (STAT_LANES,), jnp.float32),
        pltpu.VMEM(lead + (STAT_LANES,), jnp.float32),
    ]
    if lane_dense:
        # the body copies its groups itself: two (G*bs, lanes) slots a
        # pool
        lanes = k_pool.shape[-1]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands += [k_pool, v_pool]
        scratch += [pltpu.VMEM((2, G * bs, lanes), k_pool.dtype),
                    pltpu.VMEM((2, G * bs, lanes), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2))]
    else:
        # every pool leaf is (num_blocks, bs, lanes): codes and, where
        # the pool is quantized, the scale rows of the SAME block id ride
        # in as whole (1, bs, lanes) blocks through the one index map
        for leaf in (k_pool, k_scale, v_pool, v_scale):
            if leaf is not None:
                in_specs.append(
                    pl.BlockSpec((1, bs, leaf.shape[-1]), kv_map))
                operands.append(leaf)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(live,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, row_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,) + out_block[1:], q.dtype),
        # the one axis carries each row's online-softmax accumulators
        # through its blocks in order (and the decode body's copies
        # from one step to the next)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(block_table.astype(jnp.int32), lengths, row, blk, n, *operands)
    if lane_dense:
        # (B, 1, H*D) rows, or (B, H, D) heads, back to (B, H, 1, D)
        out = jnp.moveaxis(out.reshape(B, S, H, D), 2, 1)
    return out


def paged_attention_kernel(q, k_pool, v_pool, block_table, lengths, *,
                           scale=None, interpret: bool = False,
                           k_scale=None, v_scale=None,
                           k_new=None, v_new=None, work=None,
                           window=None):
    """Fused paged attention over pool blocks — no gathered view.

    q:           (B, H, S, D) queries; S=1 decode, S=chunk prefill
    k_pool:      (num_blocks, block_size, Hkv*D) key pool (token-major,
                 ops/paged_attention.write_kv layout); an unquantized
                 pool may hold fewer KV heads than ``q`` has query heads
                 (grouped-query attention: ``gqa_decode_attention``,
                 ``gqa_prefill_attention``)
    v_pool:      idem, values
    block_table: (B, NB) int32 pool block ids, position order; entries
                 past a row's allocation must be the null block (0)
    lengths:     (B,) int32 cache entries already present per row; the
                 queries occupy absolute positions
                 [lengths[b], lengths[b] + S) and their K/V must already
                 be scattered into the pool (write_kv runs first)
    k/v_scale:   fp32 scales when the pools hold quantized codes (both
                 or neither): ``(num_blocks, block_size, H)`` row
                 scales beside int8 codes; ``(num_blocks, block_size,
                 H*G)`` group scales beside uint8 nibble-packed int4
                 codes (the code dtype discriminates, mirroring
                 attend).  The kernel streams them beside the code
                 blocks and dequantizes in register (see _paged_kernel)
    k/v_new:     (B, H, S, D) fp K/V of the query tokens (int4 only,
                 both or neither) — enables the fp-residual self lane
    work:        the dispatch's ``paged_attention.paged_work`` at this
                 call's ``step_blocks`` where the caller holds it (one
                 list serves every layer of a forward); None builds it
                 here.  A grouped prefill chunk always builds its own
                 (its list is of query tiles)
    window:      None, or a sliding window W (``_visible``)

    Returns (B, H, S, D) in q.dtype.  Numerically this is the online-
    softmax evaluation of ops/paged_attention.paged_attention over the
    gathered view — token-parity on the greedy decode path is pinned by
    tests/test_paged_kernel.py.

    MIXED-ROW CONTRACT (lockstep with ops/paged_attention.attend): the
    grid walks (row, kv block) pairs and every visibility test uses that
    row's own ``lengths[b]``, so one dispatch may mix decode rows
    (one real lane) with prefill rows carrying chunks at different
    offsets — the --mixed-batch fused step.  Slack lanes past a
    row's real count are the caller's to mask upstream (their K/V
    scatters to the null block); their output lanes are discarded on
    host.  tests/test_mixed_batch.py pins kernel-vs-XLA agreement on
    mixed batches in fp32 and int8.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("quantized pools need both k_scale and v_scale")
    if (k_new is None) != (v_new is None):
        raise ValueError("fp residual needs both k_new and v_new")
    mode = pool_mode(k_pool, k_scale)        # static: dtype and None-ness
    if k_new is not None and mode != "int4":
        raise ValueError(
            "fp-residual k_new/v_new only apply to int4 (group-scaled) "
            "pools")
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return _paged_call(q, k_pool, v_pool, block_table, lengths,
                       scale=scale, interpret=interpret, mode=mode,
                       k_scale=k_scale, v_scale=v_scale,
                       k_new=k_new, v_new=v_new, work=work, window=window)


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                           scale=None, interpret: bool = False,
                           k_scale=None, v_scale=None,
                           k_new=None, v_new=None, work=None,
                           window=None):
    """Single-token decode specialization (S must be 1) — the serving
    hot path.  Thin wrapper so call sites (and probes) name the phase
    they are on; the list's form is shared with chunked prefill, and so
    is the kernel body unless the pool is unquantized
    (``_decode_kernel``, a group of blocks a step)."""
    if q.shape[2] != 1:
        raise ValueError(f"decode takes one query token per row, got "
                         f"S={q.shape[2]} (use paged_prefill_attention)")
    return paged_attention_kernel(q, k_pool, v_pool, block_table,
                                  lengths, scale=scale,
                                  interpret=interpret,
                                  k_scale=k_scale, v_scale=v_scale,
                                  k_new=k_new, v_new=v_new, work=work,
                                  window=window)


def paged_prefill_attention(q, k_pool, v_pool, block_table, lengths, *,
                            scale=None, interpret: bool = False,
                            k_scale=None, v_scale=None,
                            k_new=None, v_new=None, work=None,
                            window=None):
    """Chunked-prefill variant: S = chunk queries per row at positions
    [lengths[b], lengths[b] + S), causal within the chunk and over the
    cache via the same visibility test (col <= q position)."""
    return paged_attention_kernel(q, k_pool, v_pool, block_table,
                                  lengths, scale=scale,
                                  interpret=interpret,
                                  k_scale=k_scale, v_scale=v_scale,
                                  k_new=k_new, v_new=v_new, work=work,
                                  window=window)


@functools.lru_cache(maxsize=16)
def probe_compile(dtype_name: str = "bfloat16", heads: int = 12,
                  head_dim: int = 64, block_size: int = 16,
                  prefill_chunk: int = 64, kv_dtype: str = "fp32",
                  kv_group: int = 32, max_slots: int = 8,
                  max_blocks: int = 4, sharding=None, kv_heads=None,
                  window=None, min_chunk: int = 2) -> None:
    """Compile the kernel for the geometry an engine is about to serve,
    on this backend's Mosaic, at the LARGEST dispatch of each kind:
    decode (S=1) over ``max_slots`` rows, and one row at EVERY pow2
    prefill bucket up to ``prefill_chunk`` — the exact S set the engine
    dispatches (engine._bucket), since S changes the kernel's tile
    shapes — both under a table of ``max_blocks`` blocks, in the pool
    storage variant ``kv_dtype`` selects (for int4 that is nibble-packed
    uint8 codes + group scales + the fp-residual k_new/v_new operands).
    The table and the work list live in scalar memory and grow with
    rows x table width, so a smaller dispatch fits wherever the largest
    does, and a geometry too large for scalar memory is refused here;
    so is a block whose four decode slots pass their share of VMEM
    (``paged_attention.step_blocks`` says so in words).

    Returns nothing; a refusal RAISES with the compiler's message, so a
    selected kernel that cannot compile stops the engine at build time
    instead of mid-traffic — and never turns into another lowering.
    Successes are cached per geometry (``lru_cache`` does not cache
    exceptions).

    ``kv_heads`` (default ``heads``) makes the pool grouped-query,
    ``window`` gives every call a sliding window, and ``min_chunk`` is
    the smallest prefill bucket the engine dispatches past one token.

    ``sharding`` places the abstract operands; None is the default
    device.  tests/test_paged_kernel.py passes a device of a deviceless
    TPU topology, which runs the real Mosaic compiler without a chip."""
    dt = jnp.dtype(dtype_name)
    NB, bs = max_blocks, block_size

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    kw = {}
    width = (kv_heads or heads) * head_dim
    nblocks = 1 + max_slots * NB
    if kv_dtype == "int4":
        g = min(kv_group, head_dim)
        pool = arg((nblocks, bs, width // 2), jnp.uint8)
        scales = arg((nblocks, bs, width // g), jnp.float32)
    elif kv_dtype == "int8":
        pool = arg((nblocks, bs, width), jnp.int8)
        scales = arg((nblocks, bs, heads), jnp.float32)
    else:
        pool = arg((nblocks, bs, width), dt)
        scales = None
    if scales is not None:
        kw.update(k_scale=scales, v_scale=scales)
    S = 1
    while S <= prefill_chunk:
        B = max_slots if S == 1 else 1
        q = arg((B, heads, S, head_dim), dt)
        if kv_dtype == "int4":
            kw.update(k_new=q, v_new=q)
        try:
            # graft-lint: jit-ok(compile probe: runs once at kernel resolve, not per step)
            jax.jit(functools.partial(paged_attention_kernel,
                                      window=window)).lower(
                q, pool, pool, arg((B, NB), jnp.int32),
                arg((B,), jnp.int32), **kw).compile()
        except Exception as e:
            raise RuntimeError(
                f"Pallas paged-attention kernel failed to compile for "
                f"{dtype_name} q, kv_dtype={kv_dtype}, H={heads}, "
                f"D={head_dim}, block_size={block_size}, S={S}, "
                f"{B} rows x {NB} table blocks: {e}") from e
        S = max(2 * S, min_chunk)
