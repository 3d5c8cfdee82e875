"""Paged KV-cache device ops: block-table gather/scatter + attention.

The serving path (serving/) stores K/V in a fixed pool of
``(num_blocks, block_size, heads * head_dim)`` blocks instead of one
contiguous ``(B, H, max_len, D)`` buffer per request batch
(models/gpt.init_cache).  Each live sequence owns an ordered list of
pool blocks (its block table); block ``j`` of a sequence holds absolute
positions ``[j*block_size, (j+1)*block_size)``, so a gather of the table
reconstructs the contiguous layout and the attention math can stay
IDENTICAL to the contiguous decode path — the token-parity guarantee
(tests/test_serving.py) rests on that: same einsum contraction order,
same fp32 masked softmax (``masked_softmax_attention``, the ONE
implementation both paths call), with padding lanes exactly zeroed
(``exp(finfo.min - max)`` underflows to 0.0, and 0-weighted V lanes add
exact 0.0 terms).

The pool is token-major and lane-dense: a block is ``(block_size,
H*D)``, one row per token slot with the heads side by side (head ``h``
in lanes ``[h*D, (h+1)*D)``).  That is the one geometry on which the
runtime's default device layout (row-major only when the minor dimension
fills the 128-lane tile; a 64-wide ``D`` gets ``num_blocks`` rotated
minor-most), the XLA scatter of ``write_kv`` (each update is one
contiguous row) and the Mosaic operand of the fused kernel
(ops/paged_attention_kernel, row-major) all agree, so no serving program
re-lays a pool leaf; the old ``(num_blocks, H, block_size, D)`` pool
cost three pool-sized copies per leaf and program (PERF.md, PR 25).

Block 0 is the NULL block: never allocated to a sequence, it absorbs
scatter writes from masked-out lanes (padded prefill tail, inactive
decode slots) so those lanes need no branching — garbage lands in
scratch, reads of it are masked by the causal visibility test.

``attend`` is THE dispatcher behind the paged-attention seam: the
``--kernel`` knob (CLI -> Config -> ServeConfig -> engine)
resolves through ``resolve_kernel`` to either

- ``pallas`` — the fused kernel compiled by Mosaic, reading pool
  blocks in place through the block table with an fp32 online softmax
  (TPU only),
- ``pallas-interpret`` — the same kernel under the Pallas interpreter
  (what a forced ``pallas`` resolves to off TPU: the tier-1 correctness
  vehicle, named apart so no result can pass for a Mosaic run), or
- ``xla``    — this module's gather + dense masked softmax (TPU-
  lowerable, CPU-exact).

Tensor parallelism (serving/tp): every op here treats H as a PURE
BATCH dimension — ``write_kv`` lays each head's row slice side by side,
``gather_kv``/``attend`` contract only within a head, and the head
count is read off ``kv``/``q``, never off the pool — so under a
head-sharded pool (contiguous ``H/tp`` heads of the last axis) each
shard runs these ops unchanged over its local heads with the SAME
replicated block table (a block id addresses the same slot of every
shard's pool).  Nothing in this module is tp-aware; the cross-shard
reduction lives in the model's row-parallel projections, not in
attention.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

NULL_BLOCK = 0

# resolved lowering literals (resolve_kernel): Mosaic-compiled vs the
# Pallas interpreter — distinct names so reports cannot confuse them
PALLAS = "pallas"
PALLAS_INTERPRET = "pallas-interpret"


def masked_softmax_attention(q, k, v, vis, dt, scale=None):
    """THE fp32 masked-softmax attention shared by the contiguous decode
    path (models/gpt.forward_with_cache) and the paged path
    (``paged_attention``) — one implementation, so the greedy
    token-parity guarantee between them holds by construction.

    q:    (B, H, S, D) queries
    k, v: (B, Hkv, L, D) position-ordered keys/values; ``Hkv`` divides
          ``H`` and KV head ``j`` serves query heads ``[j*G, (j+1)*G)``,
          ``G = H / Hkv`` (grouped-query attention; 1 is plain MHA)
    vis:  bool, broadcastable to (B, S, L) — True where the key lane is
          visible to the query row
    dt:   compute dtype for the probability @ V contraction

    Cast to fp32 BEFORE the scale, scale folded into the masked select,
    softmax in fp32, probabilities cast back to ``dt``.  Masked lanes
    score ``finfo(f32).min`` so their softmax weight underflows to
    exact 0.0.
    """
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    B, H, S, D = q.shape
    G = H // k.shape[1]
    if G > 1:
        # grouped-query heads: KV head j serves query heads [jG, (j+1)G),
        # whose S rows each stack into one (G*S)-row block of queries
        q = q.reshape(B, H // G, G * S, D)
        vis = jnp.tile(jnp.broadcast_to(vis, (B, 1, S, k.shape[2])),
                       (1, 1, G, 1))
    s = jnp.einsum("bhsd,bhld->bhsl", q, k).astype(jnp.float32)
    s = jnp.where(vis, s * scale, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    out = jnp.einsum("bhsl,bhld->bhsd", p, v)
    return out.reshape(B, H, S, D) if G > 1 else out


def write_kv(pool, kv, block_table, positions, valid):
    """Scatter per-token K or V vectors into the block pool.

    pool:        (num_blocks, block_size, H*D)
    kv:          (B, H, S, D)  — new keys or values, head-major like the
                 qkv projection emits
    block_table: (B, NB) int32 — pool block ids, position order
    positions:   (B, S) int32 — absolute position of each token
    valid:       (B, S) bool — False lanes scatter into the null block

    Returns the updated pool.  Lanes of distinct sequences never collide
    (the allocator hands each block to one sequence); invalid lanes all
    land in block 0, whose contents are never read unmasked.
    """
    blk, off = _slots(pool, block_table, positions, valid)
    # one whole (H*D,) row per token: pool[blk[b,s], off[b,s], :]
    return pool.at[blk, off].set(_token_rows(kv).astype(pool.dtype))


def write_ring(ring, rows, slots, positions, valid):
    """Put token rows ``(B, S, lanes)`` of a sliding-window layer at
    ``[slot, position % W]`` of its RING ``(slots + 1, W, lanes)``, so
    that after a token's own write the ring holds exactly the keys
    ``p - W < j <= p`` it may see.  Of a chunk longer than the window
    only the last ``W`` valid lanes are written (the others would be
    overwritten at once, in an order a scatter does not promise);
    invalid lanes go to the slack row, the last."""
    W = ring.shape[1]
    last = jnp.max(jnp.where(valid, positions, -1), axis=1, keepdims=True)
    keep = valid & (positions > last - W)
    where = jnp.where(keep, slots[:, None], ring.shape[0] - 1)
    return ring.at[where, positions % W].set(rows.astype(ring.dtype))


def _slots(pool, block_table, positions, valid):
    """(block id, slot) of each token of a write, both (B, S): the block
    comes from the row's table, invalid lanes go to the null block."""
    bs = pool.shape[1]
    nb = block_table.shape[1]
    blk_idx = jnp.clip(positions // bs, 0, nb - 1)
    blk = jnp.take_along_axis(block_table, blk_idx, axis=1)
    return jnp.where(valid, blk, NULL_BLOCK), positions % bs


def _token_rows(x):
    """(B, H, S, ...) per-head values -> (B, S, H * prod(...)) token rows
    with the heads side by side: the order of a pool row."""
    x = jnp.moveaxis(x, 1, 2)
    return x.reshape(x.shape[:2] + (-1,))


def quantize_kv(kv):
    """Symmetric absmax int8 quantization, one scale per (B, H, S) row.

    kv: (B, H, S, D) fp K or V vectors.  Returns ``(codes, scales)``:
    codes (B, H, S, D) int8, scales (B, H, S) fp32 with
    ``scale = max|row| / 127`` (0.0 for an all-zero row).

    The scale granularity is deliberately PER TOKEN ROW, not per whole
    block: a row's codes depend only on its own fp values, never on
    which other tokens share the block or on how many tokens the write
    dispatch carried.  That makes quantization GRANULARITY-INDEPENDENT —
    chunked prefill, single-token decode, speculative verify, and
    journal replay all produce bit-identical pool bytes for the same
    token stream (the determinism contract the int8 composition tests
    pin) — and gives the exact elementwise bound
    ``|dequant - x| <= max|row| / 127 / 2 * 2 = amax/127`` (half a
    quantization step from round-half-even, bounded by one step).

    Rounding is ``jnp.round`` (round-half-even, deterministic across
    backends); stochastic rounding would break replay byte-identity.
    """
    x = kv.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)                          # (B, H, S)
    scale = amax / 127.0
    safe = jnp.where(scale > 0.0, scale, 1.0)[..., None]
    codes = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    return codes, scale


def write_kv_quant(pool, pool_scale, kv, block_table, positions, valid):
    """``write_kv`` for the int8 pool: quantize the incoming rows
    (``quantize_kv``) and scatter codes AND scales through the same
    block/offset indexing.

    pool:        (num_blocks, block_size, H*D) int8 codes
    pool_scale:  (num_blocks, block_size, H) fp32 row scales
    kv/block_table/positions/valid: as ``write_kv``

    Returns ``(pool, pool_scale)`` updated.  Each write dispatch
    computes fresh scales for exactly the rows it writes — invalid
    lanes land codes and scales in the null block, never read unmasked.
    """
    blk, off = _slots(pool, block_table, positions, valid)
    codes, scale = quantize_kv(kv)
    return (pool.at[blk, off].set(_token_rows(codes)),
            pool_scale.at[blk, off].set(_token_rows(scale)))


def dequantize_kv(codes, scale, dt):
    """THE int8->fp dequantization, shared verbatim (in math) by the
    XLA gather path below and the Pallas kernel's in-register step
    (ops/paged_attention_kernel) so the two lowerings stay in lockstep:
    ``(codes.astype(f32) * scale).astype(dt)``, scale broadcast over the
    trailing D axis."""
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dt)


def pack_int4(codes):
    """Pack int4 codes (values in [-7, 7]) two-per-byte along D.

    codes: (..., D) integer codes.  Split-half layout: byte ``i`` holds
    code ``i`` in its low nibble and code ``i + D/2`` in its high
    nibble, so pack/unpack are two cheap vector ops (mask/shift +
    concat) with no interleaving shuffle — the layout the Pallas
    kernel's in-register unpack mirrors exactly.  Returns (..., D//2)
    uint8.
    """
    D = codes.shape[-1]
    lo = codes[..., :D // 2] & 0xF
    hi = codes[..., D // 2:] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed):
    """Invert ``pack_int4``: (..., D//2) uint8 -> (..., D) int32 codes
    in [-7, 7] (nibbles sign-extend: values > 7 are negatives)."""
    c = packed.astype(jnp.int32)
    lo = c & 0xF
    hi = (c >> 4) & 0xF
    codes = jnp.concatenate([lo, hi], axis=-1)
    return codes - jnp.where(codes > 7, 16, 0)


def quantize_kv_int4(kv, group: int):
    """Symmetric absmax int4 quantization with per-GROUP scales along D
    (the KIVI recipe, arXiv:2402.02750: sub-8-bit KV needs finer scale
    granularity than a whole row).

    kv: (B, H, S, D) fp K or V vectors.  ``group`` is the --kv-
    group knob; the effective group is ``min(group, D)`` (so the
    default 32 stays valid on tiny test heads) and must divide D.
    Returns ``(packed, scales)``: packed (B, H, S, D//2) uint8
    (pack_int4 layout), scales (B, H, S, D // g_eff) fp32 with
    ``scale = max|group| / 7`` (0.0 for an all-zero group).

    Like ``quantize_kv``, the scale granularity never crosses a token
    row: a group's codes depend only on its OWN fp values, so int4
    stays write-GRANULARITY-INDEPENDENT — chunked prefill, one-token
    decode, speculative verify, and journal replay all land
    bit-identical pool bytes for the same token stream.  Rounding is
    ``jnp.round`` (round-half-even, deterministic across backends).
    """
    x = kv.astype(jnp.float32)
    D = x.shape[-1]
    g = min(group, D)
    xg = x.reshape(x.shape[:-1] + (D // g, g))
    amax = jnp.max(jnp.abs(xg), axis=-1)              # (B, H, S, G)
    scale = amax / 7.0
    safe = jnp.where(scale > 0.0, scale, 1.0)[..., None]
    codes = jnp.clip(jnp.round(xg / safe), -7, 7).astype(jnp.int32)
    return pack_int4(codes.reshape(x.shape)), scale


def dequantize_kv_int4(packed, scale, dt):
    """THE int4->fp dequantization (XLA gather path and the Pallas
    kernel's in-register step share this math): unpack the nibbles,
    multiply each D-group by its fp32 scale, cast to ``dt``.

    packed: (..., D//2) uint8, scale: (..., G) fp32 where G divides D.
    """
    codes = unpack_int4(packed)                       # (..., D) int32
    D = codes.shape[-1]
    G = scale.shape[-1]
    x = codes.reshape(codes.shape[:-1] + (G, D // G)).astype(jnp.float32)
    x = x * scale[..., None]
    return x.reshape(codes.shape).astype(dt)


def write_kv_quant_int4(pool, pool_scale, kv, block_table, positions,
                        valid):
    """``write_kv`` for the int4 pool: group-quantize the incoming rows
    (``quantize_kv_int4``) and scatter packed codes AND group scales
    through the same block/offset indexing.

    pool:        (num_blocks, block_size, H*D//2) uint8 packed codes,
                 each head's D//2 bytes side by side
    pool_scale:  (num_blocks, block_size, H*G) fp32 group scales, each
                 head's G scales side by side
    kv/block_table/positions/valid: as ``write_kv``

    The group size is implied by the pool geometry (a row's ``H*D``
    values over its ``H*G`` scales), so the write path can never
    disagree with ``init_pools`` about it.
    Returns ``(pool, pool_scale)`` updated.
    """
    blk, off = _slots(pool, block_table, positions, valid)
    packed, scale = quantize_kv_int4(
        kv, pool.shape[-1] * 2 // pool_scale.shape[-1])
    return (pool.at[blk, off].set(_token_rows(packed)),
            pool_scale.at[blk, off].set(_token_rows(scale)))


def gather_kv(pool, block_table, heads: int):
    """Reassemble a (B, H, L, D) contiguous view from the pool.

    L = NB * block_size; entry ``l`` holds the sequence's absolute
    position ``l`` (block tables are position-ordered), so the causal
    visibility test against absolute query positions carries over
    unchanged from the contiguous path.
    """
    return _head_rows(pool[block_table], heads)  # (B, NB, bs, H*D) in


def _head_rows(g, heads: int):
    """Gathered blocks ``(B, NB, bs, H*W)`` -> ``(B, H, NB*bs, W)``: the
    inverse of ``_token_rows`` over a row's blocks in position order."""
    B, NB, bs, HW = g.shape
    g = g.reshape(B, NB * bs, heads, HW // heads)
    return jnp.moveaxis(g, 2, 1)


def paged_attention(q, ck, cv, q_positions, dt, window=None):
    """Masked causal attention over a gathered paged cache.

    q:           (B, H, S, D) query block (S=1 decode, S=chunk prefill)
    ck, cv:      (B, Hkv, L, D) gathered keys/values (gather_kv)
    q_positions: (B, S) absolute positions of the queries
    dt:          compute dtype for the probability @ V contraction
    window:      None, or W: a key at ``col`` is visible to a query at
                 ``p`` only while ``p - W < col`` (sliding window)

    The math IS models/gpt.forward_with_cache's attention
    (``masked_softmax_attention``): the greedy token-parity test pins
    this path to the contiguous one bit-for-bit on CPU.

    MIXED-ROW CONTRACT: visibility is evaluated PER ROW against that
    row's own ``q_positions`` — nothing couples rows, so one batch may
    freely mix phases (decode rows querying a single position beside
    prefill rows querying a chunk at their own offsets, the
    --mixed-batch fused dispatch).  Each row attends to exactly
    the prefix its positions admit, identical to what a single-phase
    dispatch would give it; tests/test_mixed_batch.py pins the fused
    and unfused paths token-identical in fp32 and int8.
    """
    L = ck.shape[2]
    col = jnp.arange(L)
    # (B, S, L): key position <= query position, per row
    vis = col[None, None, :] <= q_positions[:, :, None]
    if window is not None:
        vis = vis & (col[None, None, :] > q_positions[:, :, None] - window)
    return masked_softmax_attention(q, ck, cv, vis[:, None], dt)


def paged_attention_self_residual(q, ck, cv, q_positions, dt, k_new,
                                  v_new, scale=None):
    """``paged_attention`` with the KIVI fp-residual SELF lane: each
    query row's own key/value — the most recent token it can see — is
    taken from the in-register fp projections (``k_new``/``v_new``)
    instead of the quantized pool, folded into the SAME fp32 masked
    softmax so the lockstep with the kernel lowering holds.

    q, ck, cv, q_positions, dt: as ``paged_attention`` (ck/cv are the
    DEQUANTIZED gathered view of the int4 pool).
    k_new, v_new: (B, H, S, D) fp K/V of exactly the query tokens, the
    same tensors ``write_kv_quant_int4`` just scattered.  Query row
    ``s`` attends to its own position through these (exact fp score and
    value) and to every earlier position through the pool.

    Why the self lane only: by the time row ``s`` is a PAST lane of some
    later query, any fp window must have been re-derived from pool bytes
    to keep writes granularity-independent — but its own step still has
    the exact fp vectors in registers for free.  Each token is queried
    exactly once with them (prefix-cached positions are never
    re-queried), so the residual is dispatch-shape-invariant: chunked
    prefill, decode, and speculative verify score identically.

    The softmax denominator INCLUDES the self lane (it is the row's
    ``s == q_position`` column, overridden before scale+mask); rows
    whose position lies beyond the gathered view (q_pos >= L, a
    can't-happen guard) simply get no override.
    """
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    L = ck.shape[2]
    col = jnp.arange(L)
    vis = col[None, None, :] <= q_positions[:, :, None]          # (B, S, L)
    self_m = (col[None, None, :] ==
              q_positions[:, :, None])[:, None]                  # (B, 1, S, L)
    s = jnp.einsum("bhsd,bhld->bhsl", q, ck).astype(jnp.float32)
    s_self = jnp.einsum("bhsd,bhsd->bhs", q, k_new).astype(jnp.float32)
    s = jnp.where(self_m, s_self[..., None], s)
    s = jnp.where(vis[:, None], s * scale, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    p_self = jnp.sum(jnp.where(self_m, p, 0.0), axis=-1)         # (B, H, S)
    p_main = jnp.where(self_m, 0.0, p).astype(dt)
    return (jnp.einsum("bhsl,bhld->bhsd", p_main, cv)
            + p_self[..., None].astype(dt) * v_new.astype(dt))


def work_list(lengths, S: int, tq: int, NT: int, bs: int, NB: int,
              window=None):
    """The live (row, query tile, kv block) triples of one dispatch in
    execution order: THE work list of the Pallas attention kernels
    (ops/paged_attention_kernel; ops/mla_attention), built on the device
    from ``lengths`` and passed to the kernel as scalar-prefetch
    operands.

    Tile ``t`` of row ``b`` holds query tokens ``[t*tq, (t+1)*tq)`` and
    needs the blocks that hold positions up to its last real token: the
    step's own tokens were scattered into the pool before attention, so
    lanes up to ``length + S`` are real and everything past them is
    null-block padding.  A slack row (all-null table, length 0) keeps
    one step, and the garbage the engine discards.  Under a sliding
    ``window`` W a tile starts at its FIRST block that holds a key its
    first query sees (``first_block``): the blocks wholly below
    ``p - W + 1`` are never walked.
    Returns int32 arrays of the static bound ``B * NT * NB`` (row, tile,
    block, end of that tile's blocks) and the live count: entries past
    it are never run.  Without a window a tile's blocks start at 0, so
    its end is also its count."""
    B = lengths.shape[0]
    last = jnp.minimum((jnp.arange(NT, dtype=jnp.int32) + 1) * tq, S)
    need = jnp.clip((lengths[:, None] + last[None, :] + bs - 1) // bs,
                    1, NB).reshape(-1)                     # (B * NT,)
    count = need
    if window is not None:
        first = jnp.arange(NT, dtype=jnp.int32) * tq
        count = need - jnp.minimum(
            first_block(lengths[:, None] + first[None, :], window,
                        bs).reshape(-1), need - 1)
    ends = jnp.cumsum(count)
    w = jnp.arange(B * NT * NB, dtype=jnp.int32)
    pair = jnp.minimum(jnp.searchsorted(ends, w, side="right"),
                       B * NT - 1).astype(jnp.int32)
    n = need[pair]
    blk = jnp.clip(w - (ends[pair] - n), 0, NB - 1).astype(jnp.int32)
    return pair // NT, pair % NT, blk, n.astype(jnp.int32), ends[-1]


def first_block(p, window, keys: int):
    """The first step of ``keys`` keys that holds a key visible to a
    query at position ``p`` under a sliding ``window``: the one holding
    ``p - window + 1``, or 0.  The work list starts a tile there, and
    the kernels initialise a row's statistics there (``keys`` a power of
    two: a shift on the chip's scalar unit)."""
    return jax.lax.div(jnp.maximum(p - window + 1, 0), keys)


def paged_work(lengths, S: int, bs: int, NB: int, G: int = 1,
               window=None):
    """``work_list`` for the K/V kernel, a row's ``S`` queries being one
    tile and a step attending ``G`` consecutive table entries
    (``step_blocks``): (row, group, end of that row's groups, live
    count).  The same for every layer of a forward, which builds it once
    and hands it down the ``attend`` seam.

    Each array is one entry longer than the list's bound ``B *
    ceil(NB / G)``: the kernel's pipeline evaluates the index maps of
    the step AFTER the one it runs, to fetch ahead, so with every table
    full (always so at ``B = NB = 1``) it reads one entry past the list
    — out of the array, whatever scalar memory holds there, as a row and
    a block of the table; the chip halts on it."""
    row, _, blk, n, live = work_list(lengths.astype(jnp.int32), S, S, 1,
                                     bs * G, -(-NB // G), window)
    return tuple(jnp.pad(x, (0, 1)) for x in (row, blk, n)) + (live,)


# keys a decode grid step attends: one 128-lane tile of scores
DECODE_STEP_KEYS = 128
# VMEM the decode body's K and V slots (two a pool) may take, of the
# 16 MiB a Mosaic kernel has by default on a v5e
DECODE_SLOT_BYTES = 8 << 20


def step_blocks(S: int, pool, pool_scale=None) -> int:
    """Table entries ONE grid step of the K/V kernel attends, from what
    the kernel sees: a group on the decode body (one query token, an
    unquantized pool: ops/paged_attention_kernel._decode_kernel), which
    fetches its blocks itself, one wherever the BlockSpec pipeline
    fetches them.  THE rule, called by whoever builds the list
    (``forward_paged``, the kernel's own default) and by the kernel that
    walks it, so the two cannot disagree.

    A step's cost is its latency, not its bytes, until it moves some
    hundreds of KB (PERF.md, PR 29 and PR 32), so a step takes
    ``DECODE_STEP_KEYS`` keys, halved while the four VMEM slots they
    need (K and V, double-buffered) pass ``DECODE_SLOT_BYTES``: blocks
    of 128 tokens and up are a group of one."""
    if S != 1 or pool_mode(pool, pool_scale) != "fp32":
        return 1
    bs, row_bytes = pool.shape[1], pool.shape[2] * pool.dtype.itemsize
    G = max(1, DECODE_STEP_KEYS // bs)
    while G > 1 and 4 * G * bs * row_bytes > DECODE_SLOT_BYTES:
        G //= 2
    if 4 * G * bs * row_bytes > DECODE_SLOT_BYTES:
        raise ValueError(
            f"the decode kernel's four VMEM slots (K and V, double-"
            f"buffered) of one block each take 4 x block_size {bs} x "
            f"{row_bytes} B a row = {4 * bs * row_bytes} B, over the "
            f"{DECODE_SLOT_BYTES} B they may: serve a smaller block_size")
    return G


def attend(q, k_pool, v_pool, block_table, lengths, dt, *,
           kernel: str = "xla", k_scale=None, v_scale=None,
           k_new=None, v_new=None, work=None, window=None):
    """THE paged-attention dispatch seam: one entry point, two lowering
    strategies, identical greedy tokens (tests/test_paged_kernel.py).

    q:           (B, H, S, D) queries at positions [lengths[b],
                 lengths[b] + S) — their K/V already scattered into the
                 pools (write_kv runs first)
    k/v_pool:    (num_blocks, block_size, Hkv*D): ``Hkv`` KV heads, read
                 off an unquantized pool's width; KV head ``j`` serves
                 query heads ``[j*G, (j+1)*G)``, ``G = H / Hkv``
                 (grouped-query attention; quantized pools hold ``H``)
    block_table: (B, NB) int32
    lengths:     (B,) int32 cache entries already present per row
    kernel:      "xla" (gather + dense masked softmax), "pallas"
                 (fused blockwise online softmax compiled by Mosaic) or
                 "pallas-interpret" (the same kernel interpreted).
                 Callers resolve the knob BEFORE tracing via
                 ``resolve_kernel`` — this runs under jit, where the
                 choice must be static and must not consult the backend.
    k/v_scale:   fp32 scales when the pools hold quantized codes; both
                 or neither.  ``(num_blocks, block_size, H)`` row
                 scales beside int8 codes (--kv-dtype int8);
                 ``(num_blocks, block_size, H*G)`` group scales beside
                 uint8 nibble-packed codes (--kv-dtype int4) —
                 the CODE DTYPE is the discriminator (``pool_mode``), so
                 no new pool leaf key is needed and CoW/TP/partial-copy
                 stay generic.  Dequantization happens INSIDE the consume
                 path — in-register in the kernel, elementwise on the
                 gathered view here — so no fp pool ever materializes.
    k/v_new:     (B, H, S, D) fp K/V of the query tokens themselves
                 (the tensors the int4 write just quantized away) —
                 enables the fp-residual self lane
                 (``paged_attention_self_residual``).  int4 pools only;
                 both or neither.
    work:        the dispatch's ``paged_work`` where the caller built it
                 (a forward builds one for all its layers); None lets a
                 Pallas lowering build its own.  The XLA path has no use
                 for it.
    window:      None, or a sliding window W: a key at position ``col``
                 is visible to a query at ``p`` iff ``p - W < col <= p``
                 (the kernels' lists skip the blocks wholly below it)

    MIXED-ROW CONTRACT: ``lengths`` is per-row and the causal mask is
    built per row from it (``pos = lengths[:, None] + arange(S)``), so
    rows of ONE dispatch may sit at different phases — a decode row
    (one real lane) beside prefill rows carrying chunks at their own
    offsets, as the --mixed-batch fused step packs them.  Rows
    with fewer than S real lanes are the CALLER'S job to mask: slack
    lanes must be marked invalid upstream so write_kv lands them in
    the null block, and their attention output is garbage to be
    discarded on host.  Both lowerings honor this identically (the
    Pallas path masks by the same per-row positions), pinned in fp32
    and int8 by tests/test_mixed_batch.py.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("quantized pools need both k_scale and v_scale")
    if (k_new is None) != (v_new is None):
        raise ValueError("fp residual needs both k_new and v_new")
    if k_new is not None and pool_mode(k_pool, k_scale) != "int4":
        raise ValueError(
            "fp-residual k_new/v_new only apply to int4 (group-scaled) "
            "pools")
    if kernel in (PALLAS, PALLAS_INTERPRET):
        from mpi_tensorflow_tpu.ops import paged_attention_kernel as pk

        fused = (pk.paged_decode_attention if q.shape[2] == 1
                 else pk.paged_prefill_attention)
        return fused(q, k_pool, v_pool, block_table, lengths,
                     interpret=kernel == PALLAS_INTERPRET,
                     k_scale=k_scale, v_scale=v_scale,
                     k_new=k_new, v_new=v_new, work=work, window=window)
    if kernel != "xla":
        raise ValueError(
            f"unresolved paged-attention kernel {kernel!r}: callers "
            f"resolve the knob host-side via resolve_kernel before "
            f"tracing")
    H, S = q.shape[1:3]
    pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)
    if k_scale is not None:
        # dequantize the gathered rows elementwise, in lockstep with
        # the kernel's in-register step (dequantize_kv /
        # dequantize_kv_int4 are the shared contracts), BEFORE the
        # unchanged softmax
        ck = _gather_kv_dequant(k_pool, k_scale, block_table, H, q.dtype)
        cv = _gather_kv_dequant(v_pool, v_scale, block_table, H, q.dtype)
    else:
        Hkv = kv_heads(q, k_pool, k_scale)
        ck = gather_kv(k_pool, block_table, Hkv)
        cv = gather_kv(v_pool, block_table, Hkv)
    if k_new is not None:
        return paged_attention_self_residual(q, ck, cv, pos, dt,
                                             k_new, v_new)
    return paged_attention(q, ck, cv, pos, dt, window)


def kv_heads(q, pool, pool_scale=None) -> int:
    """The KV heads a pool holds for queries ``q`` (B, H, S, D): its
    width over the head's for an unquantized pool, which may group the
    query heads (``H`` a multiple of it), else ``H``.  Grouped heads over
    quantized codes are refused: their scales are per query head."""
    H, D = q.shape[1], q.shape[-1]
    if pool_scale is not None:
        return H
    Hkv = pool.shape[-1] // D
    if Hkv * D != pool.shape[-1] or H % Hkv:
        raise ValueError(
            f"a pool of {pool.shape[-1]} lanes holds no whole number of "
            f"{D}-wide KV heads dividing the {H} query heads")
    return Hkv


def _gather_kv_dequant(pool, pool_scale, block_table, heads: int, dt):
    """``gather_kv`` over a quantized pool: gather codes and scales
    through the same table, take both apart by head, dequantize (int8
    row scales or int4 group scales, ``pool_mode``) into the
    (B, H, L, D) view."""
    g = _head_rows(pool[block_table], heads)         # (B, H, L, D|D/2)
    gs = _head_rows(pool_scale[block_table], heads)  # (B, H, L, 1|G)
    if pool_mode(pool, pool_scale) == "int4":
        return dequantize_kv_int4(g, gs, dt)         # unpacks D/2 -> D
    return dequantize_kv(g, gs[..., 0], dt)


def pool_mode(pool, pool_scale) -> str:
    """The storage variant of a pool leaf, read off what it holds:
    "fp32" (no scales: blocks in the compute dtype), "int8" (int8 codes,
    one scale per head and row) or "int4" (uint8 nibble pairs, group
    scales)."""
    if pool_scale is None:
        return "fp32"
    return "int4" if pool.dtype == jnp.uint8 else "int8"


def resolve_for(model, choice: str, block_size: int,
                prefill_chunk: int = 64, kv_dtype: str = "fp32",
                kv_group: int = 32, cfg=None, max_slots: int = 8,
                max_blocks: int = 4) -> str:
    """Ask ``model`` what ``choice`` resolves to.  A model that brings
    its own attention kernel has ``resolve_kernel(choice, block_size,
    prefill_chunk)`` (models/mla_moe); the K/V models use this module's,
    over ``cfg`` (default ``model.cfg``; the per-shard config under
    TP) and the largest dispatch the caller serves (``max_slots`` rows
    under tables of ``max_blocks`` blocks)."""
    own = getattr(model, "resolve_kernel", None)
    if own is not None:
        return own(choice, block_size, prefill_chunk)
    return resolve_kernel(choice, cfg or model.cfg, block_size,
                          prefill_chunk, kv_dtype, kv_group, max_slots,
                          max_blocks)


def resolve_kernel(choice: str, cfg, block_size: int,
                   prefill_chunk: int = 64,
                   kv_dtype: str = "fp32",
                   kv_group: int = 32, max_slots: int = 8,
                   max_blocks: int = 4) -> str:
    """Resolve the ``--kernel`` knob to a static lowering literal.

    - "xla"    -> "xla"
    - "pallas" -> "pallas" on TPU, "pallas-interpret" elsewhere (the
                  test configuration — a row can never carry "pallas"
                  from an interpreted run)
    - "auto"   -> "pallas" on TPU, "xla" elsewhere (the interpreter is
                  a correctness vehicle, not a serving path);
                  ``MPI_TF_TPU_DISABLE_PAGED_KERNEL=1`` maps it to
                  "xla" on TPU too (the operator kill switch)

    Whenever the result is "pallas" the kernel is compiled for this
    geometry first, at its largest dispatch — ``max_slots`` rows under
    tables of ``max_blocks`` blocks, which size the kernel's scalar
    operands (paged_attention_kernel.probe_compile): a Mosaic refusal
    RAISES here with the compiler's message.  No failure ever selects
    the XLA path.

    Host-side, once per engine: the resolved literal is baked into the
    jitted decode/prefill steps, so kernel choice can never add dispatch
    shapes or recompiles, and ``engagement``/``paths`` report it as is.
    """
    def probe():
        from mpi_tensorflow_tpu.ops import paged_attention_kernel as pk

        pk.probe_compile(jnp.dtype(cfg.dtype).name, cfg.heads,
                         cfg.head_dim, block_size, prefill_chunk, kv_dtype,
                         kv_group, max_slots, max_blocks)
    return resolve_choice(choice, probe)


def resolve_choice(choice: str, probe) -> str:
    """The rules of ``resolve_kernel`` for any attention kernel: ``probe``
    compiles the caller's kernel at the served geometry and is called
    only when the result is Mosaic."""
    if choice == "xla":
        return choice
    if choice not in ("auto", PALLAS):
        raise ValueError(
            f"serve kernel must be auto|xla|pallas, got {choice!r}")
    if jax.default_backend() != "tpu":
        return PALLAS_INTERPRET if choice == PALLAS else "xla"
    if choice == "auto" and os.environ.get(
            "MPI_TF_TPU_DISABLE_PAGED_KERNEL", "") not in ("", "0"):
        print("[paged_attention] auto -> xla: kernel disabled via "
              "MPI_TF_TPU_DISABLE_PAGED_KERNEL", file=sys.stderr)
        return "xla"
    probe()
    return PALLAS
