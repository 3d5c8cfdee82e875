"""Radix prefix cache: cross-request reuse of shared-prefix KV blocks.

Production serving traffic is dominated by shared prefixes — system
prompts, few-shot templates, multi-turn history.  The block table
already gives every sequence per-block indirection into one physical
pool (serving/paged_cache), which is exactly the machinery
PagedAttention (Kwon et al., arXiv:2309.06180) identifies as enabling
PHYSICAL block sharing; SGLang's RadixAttention (Zheng et al.,
arXiv:2312.07104) extends it to automatic cross-request prefix reuse
through a radix tree over token sequences.  This module is that tree at
BLOCK granularity:

- A trie node represents one FULL block of prompt tokens in context —
  its key is the block's token tuple, its path from the root is the
  whole prefix, and it pins one physical pool block holding that
  prefix's KV.  (Token-exact keys, so a hash collision can never alias
  two different prefixes to one block.)
- ``match_and_share`` walks a new prompt's full blocks down the trie
  and maps every hit to the EXISTING physical block (one ``share`` ref
  each) instead of recomputing it: prefill is charged only for the
  unique suffix, and pool occupancy drops by one block per hit.
- ``insert`` runs when a sequence finishes prefill: the trie adopts the
  sequence's full-prompt blocks it has not seen before (its own
  ``share`` ref per node), making them matchable by later requests.
  With generated-block caching on (--prefix-gen, prefix v2) the
  scheduler ALSO inserts a finished sequence's full blocks spanning
  prompt + generated output, so a follow-up turn that embeds the prior
  answer maps those blocks instead of re-prefilling them
  (RadixAttention's generation-caching rule).  Either way only FULL,
  fully-written blocks enter the trie — a partial tail block that may
  still receive writes never does, so a cached block's content is
  immutable by construction and writes into shared blocks happen only
  on the engine's explicit copy-on-write path.
- ``match_partial`` (prefix v2) extends a full-block match into the
  tail: when the walk ends mid-block, the best-matching child's block
  donates its matched row prefix via the engine's one-compile
  partial-copy dispatch into the sequence's private tail block, so up
  to ``block_size - 1`` tokens per miss stop being recomputed.
- ``evict`` frees least-recently-used UNREFERENCED leaves (refcount 1:
  only the trie holds the block) under pool pressure, so sharing never
  starves admission.  Leaves only: an interior node's children encode
  prefixes that run THROUGH it, and evicting it would strand their
  references behind an unmatchable path.

Pure host Python, no jax import — the scheduler consumes it and the
unit tests exercise it without a device.  Determinism contract: a
matched block holds KV bit-identical to what re-prefilling those
positions would write (same tokens, same absolute positions, same
deterministic forward), so greedy decode with the cache on is
token-identical to cache-off (pinned by tests/test_serving.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from mpi_tensorflow_tpu.serving.paged_cache import BlockAllocator


class _Node:
    """One full token-block of prefix context pinning one pool block."""

    __slots__ = ("key", "block", "children", "parent", "last_used")

    def __init__(self, key: Tuple[int, ...], block: int,
                 parent: Optional["_Node"], last_used: int):
        self.key = key
        self.block = block
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.parent = parent
        self.last_used = last_used


class PrefixCache:
    """Block-granularity radix trie over prompt prefixes.

    Refcount model: the trie holds exactly ONE allocator reference per
    node (taken at ``insert``, dropped at eviction); every sequence
    whose block table maps a cached block holds its own.  So
    ``refcount == 1`` means "trie only" — evictable; ``> 1`` means live
    sequences read it — protected.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.allocator = allocator
        self.block_size = block_size
        self._root = _Node((), 0, None, 0)
        self._clock = 0              # monotone LRU stamp source
        self.num_blocks = 0          # nodes == distinct pool blocks held
        self.inserted = 0            # nodes ever adopted
        self.evicted = 0             # nodes LRU-evicted
        # Observer for ROOT-child membership (leading full-block keys):
        # called as root_hook(key, True) when a first-block node is
        # adopted and root_hook(key, False) when one is evicted.  The
        # replica router's prefix-aware placement feeds its owner map
        # from this digest; None (the default) costs nothing.
        self.root_hook = None
        # Host-RAM block tier (--kv-tier host): the engine wires
        # all three or none.  ``tier`` is a paged_cache.HostBlockStore;
        # ``demote_fetch(block) -> host leaves`` copies a pool block's
        # bytes to host (called just before eviction releases it);
        # ``promote_put(leaves, block)`` writes stored bytes into a
        # freshly allocated device block (called during match walks,
        # BEFORE the sequence's first dispatch).  Keys are full trie
        # token paths, so a promoted block is byte-identical to what
        # re-prefilling its positions would write — tier entries can
        # never go stale (same path => same bytes, the determinism
        # contract).  None (the default) keeps eviction pure-free.
        self.tier = None
        self.demote_fetch = None
        self.promote_put = None
        self.promoted = 0            # nodes re-admitted from the tier

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    # ---------------- lookup ----------------

    def match_and_share(self, prompt: List[int]) -> Tuple[List[int], int]:
        """Longest cached block-prefix of ``prompt``: returns the
        physical block ids (one ``share`` reference taken on each — the
        caller owns them like freshly allocated blocks and must
        ``release`` on any failure path) and the number of prompt
        tokens they serve.

        The served-token count is capped at ``len(prompt) - 1``: the
        prefill must recompute at least the final prompt position to
        emit the first output token (its argmax IS the first generated
        token).  When every full block hits and the prompt length is an
        exact block multiple, that recompute lands INSIDE the last
        shared block — the engine's copy-on-write path detects the
        shared write and gives the sequence a private copy.
        """
        node, ids, path = self._root, [], []
        bs = self.block_size
        for j in range(len(prompt) // bs):
            key = tuple(prompt[j * bs:(j + 1) * bs])
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._tick()
            ids.append(child.block)
            path.append(key)
            node = child
        self.allocator.share(ids)
        if self.tier is not None and self.promote_put is not None:
            node = self._promote_walk(node, prompt, ids, path)
        cached = len(ids) * bs
        if cached >= len(prompt):
            cached = len(prompt) - 1
        return ids, cached

    def _promote_walk(self, node: "_Node", prompt: List[int],
                      ids: List[int], path: List[Tuple[int, ...]]):
        """Extend a trie walk through the host tier: where the device
        trie ran out, demoted blocks whose token path continues the
        prompt are promoted back — a fresh device block is allocated,
        the host bytes land in it (``promote_put``, before the
        sequence's first dispatch), and a trie node is rebuilt in place.
        The re-admitted node takes the trie's own reference (the alloc)
        PLUS the sequence's share, exactly the accounting a normal hit
        leaves, so ``check``/quiescent invariants hold unchanged.
        ``ids``/``path`` are extended in place; promotion stops at the
        first tier miss, allocation failure, or prompt end."""
        bs = self.block_size
        for j in range(len(ids), len(prompt) // bs):
            key = tuple(prompt[j * bs:(j + 1) * bs])
            full = tuple(path) + (key,)
            # peek before pop: on allocation failure the entry must
            # survive for a later, less-pressured walk
            if full not in self.tier or not self.allocator.can_alloc(1):
                break
            bid = self.allocator.alloc(1)[0]        # the trie's own ref
            self.promote_put(self.tier.pop(full), bid)
            child = _Node(key, bid, node, self._tick())
            node.children[key] = child
            self.num_blocks += 1
            self.promoted += 1
            if node is self._root and self.root_hook is not None:
                self.root_hook(key, True)
            self.allocator.share([bid])             # the sequence's ref
            ids.append(bid)
            path.append(key)
            node = child
        return node

    def match_partial(self, prompt: List[int],
                      matched_blocks: int) -> Optional[Tuple[int, int]]:
        """Best mid-block extension of a full-block match: re-walks the
        trie to depth ``matched_blocks`` and, among that node's
        children, finds the block whose token key shares the longest
        ROW PREFIX with the prompt's tail.  Returns ``(block, rows)``
        with one ``share`` reference taken on ``block`` — the PIN that
        keeps trie eviction from freeing (and the allocator from
        recycling) the source before the engine's partial-copy dispatch
        reads it; the caller releases it after the copy.  None when no
        child shares at least one usable row.

        ``rows`` is capped at ``len(tail) - 1`` so the final prompt
        position always recomputes (the ``match_and_share`` rule: its
        argmax IS the first output token).  When the tail spans a full
        block a whole-key match is impossible here — the main walk
        would have taken it — so ``rows < block_size`` always holds and
        the copy never substitutes for a full-block share."""
        node, bs = self._root, self.block_size
        for j in range(matched_blocks):
            node = node.children.get(tuple(prompt[j * bs:(j + 1) * bs]))
            if node is None:          # concurrent eviction below a match
                return None
        tail = prompt[matched_blocks * bs:]
        limit = min(len(tail) - 1, bs)
        if limit <= 0:
            return None
        best, best_rows = None, 0
        for key, child in node.children.items():
            r = 0
            while r < limit and r < len(key) and key[r] == tail[r]:
                r += 1
            if r > best_rows:
                best, best_rows = child, r
        if best is None:
            return None
        best.last_used = self._tick()
        self.allocator.share([best.block])
        return best.block, best_rows

    # ---------------- registration ----------------

    def insert(self, prompt: List[int], block_ids: List[int]) -> int:
        """Register a FULLY PREFILLED prompt's full blocks; the trie
        adopts (one ``share`` ref) each block it has no node for yet.
        Blocks already cached keep their existing node — a sequence
        that recomputed a cached block privately (CoW, or an unaligned
        suffix) simply keeps its private copy.  Returns nodes added."""
        node, added = self._root, 0
        bs = self.block_size
        for j in range(len(prompt) // bs):
            key = tuple(prompt[j * bs:(j + 1) * bs])
            child = node.children.get(key)
            if child is None:
                self.allocator.share([block_ids[j]])
                child = _Node(key, block_ids[j], node, 0)
                node.children[key] = child
                self.num_blocks += 1
                self.inserted += 1
                added += 1
                if node is self._root and self.root_hook is not None:
                    self.root_hook(key, True)
            child.last_used = self._tick()
            node = child
        return added

    # ---------------- eviction ----------------

    def _path_key(self, node: "_Node") -> tuple:
        """Full trie token path of ``node`` (root -> node, one token
        tuple per block) — the host-tier key: token-exact, so a tier
        entry can only re-admit for the one prefix that produced it."""
        keys = []
        while node is not self._root:
            keys.append(node.key)
            node = node.parent
        return tuple(reversed(keys))

    def _leaves(self) -> List[_Node]:
        out, stack = [], list(self._root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        return out

    def evict(self, want_blocks: int) -> int:
        """Release up to ``want_blocks`` pool blocks by evicting
        least-recently-used UNREFERENCED leaves (allocator refcount 1).
        Evicting a leaf can expose its parent as the next candidate.
        Returns blocks actually freed — the caller falls back to
        sequence eviction for the remainder.  (Linear leaf scan per
        freed block: trie size is bounded by the pool, and eviction
        only runs under pool pressure.)"""
        freed = 0
        while freed < want_blocks:
            victims = [n for n in self._leaves()
                       if self.allocator.refcount(n.block) == 1]
            if not victims:
                break
            victim = min(victims, key=lambda n: n.last_used)
            assert not victim.children
            del victim.parent.children[victim.key]
            if self.tier is not None and self.demote_fetch is not None:
                # demote instead of discard: copy the block's bytes to
                # the host store under its full token path BEFORE the
                # release recycles the device block.  Children demote
                # before parents (leaves-only eviction), and promotion
                # walks parent-first, so chains round-trip intact.
                self.tier.put(self._path_key(victim),
                              self.demote_fetch(victim.block))
            self.allocator.release([victim.block])
            self.num_blocks -= 1
            self.evicted += 1
            freed += 1
            if victim.parent is self._root and self.root_hook is not None:
                self.root_hook(victim.key, False)
        return freed

    # ---------------- invariants / stats ----------------

    def check(self) -> None:
        """Every node pins a live, distinct pool block."""
        seen, stack = set(), list(self._root.children.values())
        while stack:
            n = stack.pop()
            assert self.allocator.refcount(n.block) >= 1, \
                f"trie node holds freed block {n.block}"
            assert n.block not in seen, \
                f"two trie nodes share physical block {n.block}"
            seen.add(n.block)
            stack.extend(n.children.values())
        assert len(seen) == self.num_blocks

    def stats(self) -> dict:
        return {"blocks": self.num_blocks, "inserted": self.inserted,
                "evicted": self.evicted, "promoted": self.promoted}
