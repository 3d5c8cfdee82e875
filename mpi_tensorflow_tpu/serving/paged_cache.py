"""Paged KV cache: host block allocator + device pool construction.

The device side is a fixed pool of blocks per transformer layer, and the
MODEL says what a block holds: a model with a ``pool_leaves`` method
declares its own leaves (models/mla_moe: one latent row per token), any
other model gets the ``(num_blocks, block_size, heads * head_dim)`` K and
V leaves below (ops/paged_attention reads/writes them through
per-sequence block tables).  Only the model's ``forward_paged`` and its
ops may assume what the leaves are called or hold; everything here and
in the engine (copy-on-write, partial copy, host tier, ``reset``) takes
whatever leaves it is given, and assumes only that  Token-major and
lane-dense is the one geometry that the runtime's default device layout
(row-major only when the minor dimension fills the 128-lane tile), the
K/V scatter (one contiguous row per token) and the Mosaic kernel's
operand (row-major) all take as it is stored, so no serving program
copies a pool leaf.  Every block leaf, scale siblings included, has the
block id on axis 0 and the token slot on axis 1.  A leaf whose name ends
in ``_count`` is a COUNTER the model accumulates on the device (routed
experts' load): it has no block axis, block copies pass it through
(``copy_block``), ``reset`` zeroes it with the rest, and the host reads
it only when asked (``read_counters``).  A leaf whose name ends in
``_slot`` is PER-SLOT state (models/phi4_flash: a state-space layer's
recurrent state, a window layer's K/V ring): axis 0 is the engine's slot,
``max_slots + 1`` rows, the last taking the writes of a dispatch's
padding rows; it is no block either, so block copies pass it through,
and only the model's forward reads or writes it (it is told each row's
slot).  ``cache_kind`` names what a leaf is for the byte counts of the
engine's result.  The host side —
this module — owns WHICH block belongs to WHOM: a refcounted free-list
allocator whose accounting the scheduler's admit/evict decisions hang
off.

Refcounts are what make PHYSICAL BLOCK SHARING safe (the PagedAttention
sharing/CoW design, arXiv:2309.06180): a prompt-prefix block cached by
the radix trie (serving/prefix_cache) is referenced by every sequence
whose table maps it PLUS the trie itself, and it returns to the free
list only when the last reference releases it.  ``alloc`` hands out
exclusive blocks (refcount 1), ``share`` adds a reference to a live
block, ``release`` drops one — all frees in the serving stack route
through ``release`` so releasing a sequence that shares prefix blocks
with live sequences can never corrupt them.

Block 0 is reserved as the null/scratch block (masked-lane scatter
target, ops/paged_attention.NULL_BLOCK) and is never handed out.
"""

from __future__ import annotations

from typing import Dict, List


class BlockAllocator:
    """Refcounted free-list allocator over pool block ids
    ``1..num_blocks-1``.

    Pure host Python (no jax import): the scheduler tests exercise
    admit/evict accounting without a device.  LIFO reuse keeps recently
    freed blocks hot in whatever cache hierarchy the pool lives in.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (block 0 is the reserved null block), "
                f"got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}     # block id -> refcount (>= 1)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return len(self._ref)

    def refcount(self, block: int) -> int:
        """Live references on ``block`` (0 = free / never allocated).
        A count > 1 means the block is SHARED — a writer must
        copy-on-write instead of scattering into it in place."""
        return self._ref.get(block, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` exclusive blocks (refcount 1); raises when the pool
        cannot cover them — callers gate on ``can_alloc`` (admission) or
        evict first."""
        if n > len(self._free):
            raise RuntimeError(
                f"block pool exhausted: want {n}, have {len(self._free)} "
                f"free of {self.num_blocks - 1}")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def share(self, ids: List[int]) -> None:
        """Add one reference to each live block — the prefix cache maps
        an already-cached block into a new sequence's table instead of
        recomputing it."""
        for b in ids:
            if b not in self._ref:
                raise ValueError(f"share of free / foreign block id {b}")
            self._ref[b] += 1

    def release(self, ids: List[int]) -> None:
        """Drop one reference per block; a block returns to the free
        list only at refcount zero.  THE one free path: callers never
        need to know whether a block is shared."""
        for b in ids:
            c = self._ref.get(b, 0)
            if c < 1:
                raise ValueError(f"double free / foreign block id {b}")
            if c == 1:
                del self._ref[b]
                self._free.append(b)
            else:
                self._ref[b] = c - 1

    # legacy name: every free is a refcounted release (a block alloc'd
    # once and never shared behaves exactly as the pre-refcount free)
    free = release

    def check(self) -> None:
        """Invariant: every non-null block is free xor referenced, once;
        every referenced block carries a positive refcount."""
        assert len(self._free) + len(self._ref) == self.num_blocks - 1
        assert len(set(self._free)) == len(self._free)
        assert not (set(self._free) & set(self._ref))
        assert 0 not in self._ref and 0 not in self._free
        assert all(c >= 1 for c in self._ref.values()), \
            f"non-positive refcount in {self._ref}"


def blocks_for(tokens: int, block_size: int) -> int:
    """Pool blocks needed to hold ``tokens`` cache entries."""
    return -(-tokens // block_size)


def is_counter(key: str) -> bool:
    """A pool entry's device counter (no block axis), by its name."""
    return key.endswith("_count")


def is_slot_state(key: str) -> bool:
    """A pool entry's per-slot state (slot on axis 0), by its name."""
    return key.endswith("_slot")


def is_block(key: str) -> bool:
    """A pool entry's block leaf (block id on axis 0, token slot on axis
    1): neither a counter nor per-slot state."""
    return not (is_counter(key) or is_slot_state(key))


def cache_kind(key: str) -> str:
    """``counter``, ``window`` (a per-slot K/V ring), ``state`` (other
    per-slot state) or ``paged`` (a block leaf)."""
    if is_counter(key):
        return "counter"
    if not is_slot_state(key):
        return "paged"
    return "window" if key.startswith("win_") else "state"


def copy_block(pools: list, src, dst) -> list:
    """Copy pool block ``src`` onto ``dst`` in every block leaf of every
    layer (codes and scale siblings alike); the rest pass through."""
    return [{key: leaf.at[dst].set(leaf[src]) if is_block(key) else leaf
             for key, leaf in p.items()} for p in pools]


def block_rows(pools: list, pick) -> list:
    """Per layer ``{key: pick(leaf)}`` over the block leaves only."""
    return [{key: pick(leaf) for key, leaf in p.items()
             if is_block(key)} for p in pools]


def write_block(pools: list, rows: list, dst) -> list:
    """Write one block's ``rows`` (``block_rows`` of some pool) into
    block ``dst``; the rest pass through."""
    return [{key: leaf.at[dst].set(r[key]) if is_block(key) else leaf
             for key, leaf in p.items()} for p, r in zip(pools, rows)]


def read_counters(pools: list) -> list:
    """The counter leaves on the host, one ``{key: np.ndarray}`` per
    layer that has any (a device sync: callers do this off the step's
    critical path, or once the step's tokens are already on the host)."""
    import jax

    return jax.device_get([
        {key: leaf for key, leaf in p.items() if is_counter(key)}
        for p in pools if any(is_counter(k) for k in p)])


def partial_copy_block(pools: list, src, dst, n) -> list:
    """Copy the first ``n`` token-slot rows of block ``src`` into block
    ``dst`` across every pool leaf, leaving rows ``>= n`` of ``dst``
    untouched — the device half of partial tail-block sharing
    (prefix v2): the trie matched ``n`` leading tokens of a sequence's
    tail block against a cached block, so those rows are copied out of
    the cache instead of re-prefilled, and the unique suffix lands on
    top.

    ``src``/``dst``/``n`` are TRACED int32 scalars — the caller jits
    this once (the ``_cow_fn`` discipline) and every (src, dst, n)
    triple reuses that one executable; ``n == 0`` with ``src == dst``
    is the no-op pre-warm dispatch.  The slot axis is axis 1 of every
    leaf (axis 0 of ``leaf[src]``), so quantized pools copy codes and
    scales together.
    """
    import jax.numpy as jnp

    out = []
    for p in pools:
        layer = {}
        for key, leaf in p.items():
            if not is_block(key):
                layer[key] = leaf
                continue
            rows = jnp.arange(leaf.shape[1]) < n
            mask = rows.reshape((-1,) + (1,) * (leaf.ndim - 2))
            layer[key] = leaf.at[dst].set(
                jnp.where(mask, leaf[src], leaf[dst]))
        out.append(layer)
    return out


def init_pools(cfg, num_blocks: int, block_size: int,
               kv_dtype: str = "fp32", kv_group: int = 32,
               model=None, max_slots: int = 0) -> list:
    """Per-layer block pools (zeros).  A ``model`` with ``pool_leaves``
    declares its own (``{name: ShapeDtypeStruct}`` per layer, counters
    and per-slot state of ``max_slots`` slots included; it refuses a
    ``kv_dtype`` it has no form for); otherwise
    per-layer K/V pools, mirroring the per-layer ``{"k", "v"}`` pytree
    shape of models/gpt.init_cache so the engine threads them through
    jit the same way.

    ``kv_dtype`` selects the pool storage format (--kv-dtype):

    - "fp32": blocks in the model compute dtype — byte-for-byte the
      pre-quantization pool (the parity reference);
    - "int8": blocks hold int8 codes, and each layer dict gains sibling
      ``{"k_scale", "v_scale"}`` arrays of shape ``(num_blocks,
      block_size, heads)`` fp32 — one symmetric-absmax scale per (block,
      token-slot, head) row (ops/paged_attention.quantize_kv).  The
      scale arrays share the pool's first two axes and keep the heads in
      order on the last, so block-table indexing, copy-on-write, and TP
      head-sharding treat them exactly like the code arrays.
    - "int4": blocks hold nibble-packed uint8 codes of shape
      ``(num_blocks, block_size, heads * head_dim // 2)`` — two codes
      per byte within a head (ops/paged_attention.pack_int4) — and the
      scale siblings hold a head's groups side by side:
      ``(num_blocks, block_size, heads * head_dim // g)`` fp32 with
      ``g = min(kv_group, head_dim)`` (the --kv-group knob,
      clamped so the default 32 stays valid on tiny heads; ``g`` must
      divide head_dim).  The uint8 code dtype is what the consume paths
      discriminate int4 on (ops/paged_attention.pool_mode) — no new leaf
      keys, so CoW/partial-copy/TP/journal stay dtype-agnostic.
    """
    import jax.numpy as jnp

    if kv_dtype not in ("fp32", "int8", "int4"):
        raise ValueError(
            f"serve kv dtype must be fp32|int8|int4, got {kv_dtype!r}")
    # every leaf is its OWN buffer: the engine donates the pools into
    # each step, and one zeros array shared between k and v (or across
    # layers) would be donated twice in a single Execute()
    declare = getattr(model, "pool_leaves", None)
    if declare is not None:
        return [{key: jnp.zeros(s.shape, s.dtype)
                 for key, s in layer.items()}
                for layer in declare(num_blocks, block_size, kv_dtype,
                                     max_slots)]
    width = cfg.heads * cfg.head_dim
    code_shape = (num_blocks, block_size, width)
    if kv_dtype == "fp32":
        return [{"k": jnp.zeros(code_shape, cfg.dtype),
                 "v": jnp.zeros(code_shape, cfg.dtype)}
                for _ in range(cfg.layers)]
    if kv_dtype == "int8":
        code_dt = jnp.int8
        scale_shape = code_shape[:2] + (cfg.heads,)
    else:
        g = min(kv_group, cfg.head_dim)
        if cfg.head_dim % 2 or g < 1 or cfg.head_dim % g:
            raise ValueError(
                f"int4 pool needs even head_dim divisible by the "
                f"effective group min(kv_group, head_dim); got "
                f"head_dim={cfg.head_dim}, kv_group={kv_group}")
        code_dt = jnp.uint8
        code_shape = code_shape[:2] + (width // 2,)
        scale_shape = code_shape[:2] + (width // g,)
    return [{"k": jnp.zeros(code_shape, code_dt),
             "v": jnp.zeros(code_shape, code_dt),
             "k_scale": jnp.zeros(scale_shape, jnp.float32),
             "v_scale": jnp.zeros(scale_shape, jnp.float32)}
            for _ in range(cfg.layers)]


class HostBlockStore:
    """Host-RAM tier for demoted KV blocks (--kv-tier host).

    When the prefix cache evicts an unreferenced trie leaf under pool
    pressure, the block's bytes are copied to host memory here instead
    of being lost; a later prompt that walks the same trie path
    PROMOTES the bytes back into a freshly allocated device block
    before its first dispatch (no recompute, no re-prefill).  KVQuant
    (arXiv:2401.18079) frames the cache as the long-context bottleneck;
    tiering is the rung that stops multi-turn sessions from re-paying
    prefill after their prefix ages out of the device pool.

    Keys are full trie TOKEN PATHS (tuple of per-block token tuples,
    root -> leaf), so an entry can only ever be re-admitted for the
    exact token stream that produced it — and because quantization is
    write-granularity independent, the stored bytes equal what a fresh
    prefill of that stream would write (the demote->promote byte-
    identity the tiering tests pin).  Values are per-layer dicts of
    host ``np.ndarray`` leaves, one row of each pool leaf (the block's
    codes + scales), dtype-agnostic.

    Pure host Python with no jax import (the allocator discipline):
    insertion-ordered dict, FIFO drop-oldest beyond ``capacity``
    (None = unbounded — host RAM is the budget), counters for the
    metrics ``tier`` block.
    """

    def __init__(self, capacity: int | None = None):
        if capacity is not None and capacity < 1:
            raise ValueError(
                f"host tier capacity must be >= 1 blocks, got {capacity}")
        self.capacity = capacity
        self._store: Dict[tuple, list] = {}
        self.demotions = 0
        self.promotions = 0
        self.dropped = 0
        self.host_blocks_peak = 0
        self.promote_ms_total = 0.0

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: tuple) -> bool:
        return key in self._store

    def put(self, key: tuple, leaves: list) -> None:
        """Admit a demoted block's host leaves under its trie path key.
        Re-demotion of the same path overwrites (byte-identical by the
        determinism contract, so this is a no-op in content)."""
        self._store.pop(key, None)
        self._store[key] = leaves
        self.demotions += 1
        if self.capacity is not None and len(self._store) > self.capacity:
            self._store.pop(next(iter(self._store)))
            self.dropped += 1
        self.host_blocks_peak = max(self.host_blocks_peak,
                                    len(self._store))

    def pop(self, key: tuple):
        """Take a block's leaves out for promotion (or None on miss).
        The entry leaves the store — after promotion the trie node
        again owns the canonical copy, on device."""
        leaves = self._store.pop(key, None)
        if leaves is not None:
            self.promotions += 1
        return leaves

    def stats(self) -> dict:
        return {"host_blocks": len(self._store),
                "host_blocks_peak": self.host_blocks_peak,
                "demotions": self.demotions,
                "promotions": self.promotions,
                "dropped": self.dropped,
                "promote_ms_total": self.promote_ms_total}
