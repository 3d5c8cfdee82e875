"""Serving subsystem: paged KV cache + continuous-batching engine.

The tier-1 anchors the ISSUE acceptance names:
- greedy decode through the paged path is TOKEN-IDENTICAL to
  CausalLm.generate for the same prompts (mixed lengths, chunked
  prefill, slot recycling all active);
- block alloc/free accounting and scheduler admit/evict invariants
  under a scripted request trace;
- steady-state serving performs zero recompiles after bucket warmup
  (jit cache-size probe).
"""

import dataclasses

import numpy as np
import pytest

from _jitted import generate_ref as _generate_ref
from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (BlockAllocator, PagedDecodeEngine,
                                        PrefixCache, Request, Scheduler,
                                        ServeConfig)
from mpi_tensorflow_tpu.serving.paged_cache import blocks_for, init_pools

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
ROPE = dataclasses.replace(TINY, pos_kind="rope")


def _prompts(rng, n, lo=4, hi=14, vocab=None):
    vocab = vocab or TINY.vocab_size
    return [list(map(int, rng.integers(0, vocab, int(s))))
            for s in rng.integers(lo, hi + 1, n)]


# ---------------------------------------------------------------- blocks

@pytest.mark.quick
class TestBlockAllocator:
    def test_null_block_never_handed_out(self):
        a = BlockAllocator(8)
        ids = a.alloc(7)
        assert 0 not in ids and sorted(ids) == list(range(1, 8))

    def test_alloc_free_roundtrip_accounting(self):
        a = BlockAllocator(16)
        x = a.alloc(5)
        y = a.alloc(3)
        assert a.num_free == 7 and a.num_used == 8
        assert not set(x) & set(y)
        a.free(x)
        assert a.num_free == 12 and a.num_used == 3
        a.check()

    def test_exhaustion_raises_and_leaves_state_clean(self):
        a = BlockAllocator(4)
        a.alloc(3)
        with pytest.raises(RuntimeError, match="exhausted"):
            a.alloc(1)
        a.check()
        assert a.num_free == 0 and a.num_used == 3

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        ids = a.alloc(2)
        a.free(ids)
        with pytest.raises(ValueError, match="double free"):
            a.free([ids[0]])

    def test_randomized_trace_preserves_partition(self):
        rng = np.random.default_rng(0)
        a = BlockAllocator(32)
        held = []
        for _ in range(200):
            if held and rng.random() < 0.45:
                held.remove(grp := held[rng.integers(len(held))])
                a.free(grp)
            else:
                n = int(rng.integers(1, 5))
                if a.can_alloc(n):
                    held.append(a.alloc(n))
            a.check()
        flat = [b for grp in held for b in grp]
        assert len(flat) == len(set(flat)) == a.num_used

    def test_share_release_refcount_semantics(self):
        """A shared block survives every release but the last; freeing
        happens exactly at refcount zero."""
        a = BlockAllocator(8)
        (b,) = a.alloc(1)
        a.share([b])
        a.share([b])
        assert a.refcount(b) == 3
        a.release([b])
        a.release([b])
        assert a.refcount(b) == 1 and a.num_used == 1
        a.check()
        a.release([b])
        assert a.refcount(b) == 0 and a.num_free == 7
        a.check()

    def test_share_of_free_block_raises(self):
        a = BlockAllocator(8)
        with pytest.raises(ValueError, match="share of free"):
            a.share([3])
        (b,) = a.alloc(1)
        a.release([b])
        with pytest.raises(ValueError, match="share of free"):
            a.share([b])

    def test_release_below_zero_raises(self):
        a = BlockAllocator(8)
        (b,) = a.alloc(1)
        a.share([b])
        a.release([b])
        a.release([b])
        with pytest.raises(ValueError, match="double free"):
            a.release([b])

    def test_randomized_share_release_property(self):
        """THE pool-leak property pin: a random interleaving of
        alloc/share/release against a model refcount map keeps the
        allocator's refcount/free-list accounting exact at every step
        and drains to empty."""
        rng = np.random.default_rng(7)
        a = BlockAllocator(24)
        refs = {}                       # model: block -> refcount
        for _ in range(600):
            r = rng.random()
            if r < 0.35 and a.can_alloc(1):
                (b,) = a.alloc(1)
                assert b not in refs
                refs[b] = 1
            elif r < 0.6 and refs:
                b = list(refs)[rng.integers(len(refs))]
                a.share([b])
                refs[b] += 1
            elif refs:
                b = list(refs)[rng.integers(len(refs))]
                a.release([b])
                refs[b] -= 1
                if refs[b] == 0:
                    del refs[b]
            a.check()
            assert a.num_used == len(refs)
            for b, c in refs.items():
                assert a.refcount(b) == c
        for b in sorted(refs):
            a.release([b] * refs[b])
        a.check()
        assert a.num_used == 0 and a.num_free == 23


# ---------------------------------------------------------- prefix trie

@pytest.mark.quick
class TestPrefixCache:
    def test_empty_trie_misses(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        ids, cached = pc.match_and_share(list(range(12)))
        assert ids == [] and cached == 0 and a.num_used == 0

    def test_insert_then_match_shares_full_blocks(self):
        """A cached prompt's full blocks map into a later request; the
        trie and the matcher each hold their own reference."""
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        prompt = list(range(10))             # 2 full blocks + 2 tail
        blocks = a.alloc(3)
        pc.insert(prompt, blocks)
        assert pc.num_blocks == 2            # tail block never cached
        assert a.refcount(blocks[0]) == a.refcount(blocks[1]) == 2
        assert a.refcount(blocks[2]) == 1
        ids, cached = pc.match_and_share(prompt + [99])
        assert ids == blocks[:2] and cached == 8
        assert a.refcount(blocks[0]) == 3
        pc.check()

    def test_full_prompt_match_caps_at_len_minus_one(self):
        """An exact-block-multiple prompt fully in cache still leaves
        ONE token to prefill (its argmax is the first output token);
        all matched blocks stay shared — the recompute write is the
        engine's CoW trigger."""
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        prompt = list(range(8))
        blocks = a.alloc(2)
        pc.insert(prompt, blocks)
        ids, cached = pc.match_and_share(list(prompt))
        assert ids == blocks and cached == 7
        a.release(ids)

    def test_match_stops_at_divergent_block(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        pc.insert(list(range(8)), a.alloc(2))
        ids, cached = pc.match_and_share([0, 1, 2, 3, 9, 9, 9, 9, 7])
        assert len(ids) == 1 and cached == 4
        a.release(ids)

    def test_lru_eviction_frees_only_unreferenced_leaves(self):
        """Eviction order is LRU over leaves whose block only the trie
        holds; blocks live sequences still map are untouchable."""
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        p1, p2 = [1] * 4, [2] * 4
        (b1,) = a.alloc(1)
        pc.insert(p1, [b1])
        (b2,) = a.alloc(1)
        pc.insert(p2, [b2])
        a.release([b1, b2])                  # donors finished: trie-only
        ids, _ = pc.match_and_share(p2 + [5])   # p2 recently used + pinned
        assert pc.evict(10) == 1             # only p1's block was free
        assert a.refcount(b1) == 0 and pc.num_blocks == 1
        a.release(ids)
        assert pc.evict(10) == 1             # now p2's is reclaimable
        assert pc.num_blocks == 0 and a.num_used == 0
        a.check()

    def test_lru_order_evicts_least_recent_first(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        (b1,) = a.alloc(1)
        pc.insert([1] * 4, [b1])
        (b2,) = a.alloc(1)
        pc.insert([2] * 4, [b2])
        a.release([b1, b2])
        ids, _ = pc.match_and_share([1] * 4 + [0])    # touch prefix 1
        a.release(ids)
        assert pc.evict(1) == 1
        assert a.refcount(b2) == 0, "LRU entry must go first"
        assert a.refcount(b1) == 1

    def test_eviction_is_leaf_first(self):
        """An interior node cannot be evicted while a child pins the
        path; evicting the leaf exposes it."""
        a = BlockAllocator(16)
        pc = PrefixCache(a, 4)
        prompt = list(range(8))              # parent + child chain
        blocks = a.alloc(2)
        pc.insert(prompt, blocks)
        a.release(blocks)                    # donor gone: both trie-only
        assert pc.evict(1) == 1
        # the LEAF (deeper block) went first; the parent remains
        assert a.refcount(blocks[1]) == 0 and a.refcount(blocks[0]) == 1
        assert pc.evict(1) == 1 and pc.num_blocks == 0
        a.check()


# ------------------------------------------------------------- scheduler

@pytest.mark.quick
class TestScheduler:
    def _mk(self, blocks=16, slots=2, bs=4, nb_per_seq=4):
        return Scheduler(BlockAllocator(blocks), slots, bs, nb_per_seq)

    def test_admit_needs_slot_and_blocks(self):
        s = self._mk(blocks=5, slots=2, bs=4)   # 4 usable blocks
        s.submit(Request(0, [1] * 8, 4))        # needs 3 blocks (9 toks)
        s.submit(Request(1, [1] * 8, 4))
        assert s.admit() == [0]                 # second: 3 > 1 free
        assert [r.id for r in s.waiting] == [1]
        s.allocator.check()

    def test_fifo_head_of_line_no_queue_jumping(self):
        s = self._mk(blocks=5, slots=2, bs=4)
        s.submit(Request(0, [1] * 12, 4))       # needs 4 blocks
        s.submit(Request(1, [1] * 2, 1))        # would fit, must wait
        s.allocator.alloc(2)                    # drain pool to 2 free
        assert s.admit() == []
        assert [r.id for r in s.waiting] == [0, 1]

    def test_budget_exhaustion_recycles_slot_and_blocks(self):
        s = self._mk()
        s.submit(Request(0, [1, 2, 3], 2))
        slot = s.admit()[0]
        s.slots[slot].prefilled = 3
        s.record_token(slot, 7)
        assert s.slots[slot] is not None
        s.record_token(slot, 8)
        assert s.slots[slot] is None
        assert s.allocator.num_used == 0
        assert s.finished[0].generated == [7, 8]

    def test_eos_recycles_slot(self):
        s = self._mk()
        s.submit(Request(0, [1, 2], 10))
        slot = s.admit()[0]
        s.slots[slot].prefilled = 2
        s.record_token(slot, 5, eos_id=99)
        assert s.slots[slot] is not None
        s.record_token(slot, 99, eos_id=99)
        assert s.slots[slot] is None and s.allocator.num_used == 0

    def test_eviction_frees_blocks_and_requeues_at_head(self):
        s = self._mk(blocks=7, slots=2, bs=4, nb_per_seq=4)  # 6 usable
        s.submit(Request(0, [1] * 7, 8, arrival=0.0))  # 2 blocks (8 cap)
        s.submit(Request(1, [1] * 7, 8, arrival=1.0))
        assert len(s.admit()) == 2
        for slot in (0, 1):
            s.slots[slot].prefilled = 7
        s.record_token(0, 3)                 # length 8: fits its blocks
        s.record_token(0, 4)                 # length 9: needs a 3rd
        s.allocator.alloc(2)                 # external pressure: 0 free
        assert s.ensure_block(0)             # -> evicts the YOUNGER seq
        assert s.slots[1] is None
        assert s.waiting[0].id == 1          # requeued at the HEAD
        assert s.evictions == 1
        s.allocator.check()

    def test_over_capacity_request_rejected_structured(self):
        """An infeasible request terminates with a structured status —
        it never raises into (or crashes) the engine."""
        s = self._mk(bs=4, nb_per_seq=2)     # cap 8 tokens
        rej = s.submit(Request(0, [1] * 6, 4))
        assert rej is not None and rej.reason == "infeasible"
        assert s.statuses[0] == "rejected"
        assert not s.waiting and s.counters["rejected"] == 1

    def test_bad_request_rejected_structured(self):
        s = self._mk()
        assert s.submit(Request(0, [], 4)).reason == "bad_request"
        assert s.submit(Request(1, [1, 2], 0)).reason == "bad_request"
        assert s.statuses == {0: "rejected", 1: "rejected"}

    def test_bounded_queue_sheds_newest(self):
        """Load shedding: a full waiting queue rejects the NEWEST submit
        with a queue_full reason; the oldest queued work keeps its
        place."""
        s = Scheduler(BlockAllocator(16), 1, 4, 4, queue_depth=2)
        for i in range(2):
            assert s.submit(Request(i, [1, 2], 2)) is None
        rej = s.submit(Request(2, [1, 2], 2))
        assert rej.reason == "queue_full" and rej.status == "shed"
        assert [r.id for r in s.waiting] == [0, 1]
        assert s.statuses[2] == "shed" and s.counters["shed"] == 1

    def test_deadline_expiry_frees_queue_and_slots(self):
        """Expired work stops occupying anything: waiting entries drop,
        live sequences free every block."""
        s = self._mk()
        s.submit(Request(0, [1, 2, 3], 4, arrival=0.0, deadline=1.0))
        s.submit(Request(1, [1, 2], 4, arrival=0.0, deadline=9.0))
        for slot in s.admit():
            s.slots[slot].prefilled = len(s.slots[slot].request.prompt)
        assert s.expire_deadlines(0.5) == []
        assert sorted(s.expire_deadlines(2.0)) == [0]
        assert s.statuses[0] == "deadline_exceeded"
        assert s.counters["deadline_exceeded"] == 1
        s.allocator.check()
        # the survivor still owns its blocks and finishes normally
        live = [i for i, q in enumerate(s.slots) if q is not None]
        assert [s.slots[i].request.id for i in live] == [1]

    def test_eviction_cap_fails_instead_of_requeueing(self):
        """The livelock guard: a request evicted more than max_evictions
        times terminates with evicted_too_often, blocks freed, queue
        clean."""
        s = Scheduler(BlockAllocator(7), 2, 4, 4, max_evictions=1)
        s.submit(Request(0, [1] * 7, 8, arrival=0.0))
        s.submit(Request(1, [1] * 7, 8, arrival=1.0))
        assert len(s.admit()) == 2
        for slot in (0, 1):
            s.slots[slot].prefilled = 7
        s.record_token(0, 3)
        s.record_token(0, 4)                 # length 9: needs a 3rd block
        s.allocator.alloc(2)                 # external pressure: 0 free
        assert s.ensure_block(0)             # eviction 1: requeued
        assert s.waiting[0].id == 1 and 1 not in s.statuses
        # re-admit the victim, then force a second eviction
        s.allocator.free([b for b in range(1, s.allocator.num_blocks)
                          if s.allocator.refcount(b)
                          and b not in s.slots[0].block_ids])
        for slot in s.admit():
            s.slots[slot].prefilled = 7
        s.record_token(0, 5)                 # length 10: 3 blocks cover
        s.record_token(0, 6)                 # length 11
        s.record_token(0, 7)                 # length 12
        s.allocator.alloc(s.allocator.num_free)   # drain the pool again
        s.record_token(0, 8)                 # length 13: needs a 4th
        assert s.ensure_block(0)             # eviction 2: over the cap
        assert s.statuses[1] == "evicted_too_often"
        assert not s.waiting
        assert s.counters["evicted_too_often"] == 1
        assert s.evict_counts[1] == 2

    def test_aging_guard_preempts_younger_for_starved_head(self):
        """A block-starved queue head (e.g. an evicted requeue) preempts
        sequences YOUNGER than itself after starvation_steps admit
        calls — a hot arrival stream cannot park old work forever; the
        victim requeues BEHIND the aged head."""
        s = Scheduler(BlockAllocator(9), 2, 4, 8, starvation_steps=3)
        s.submit(Request(1, [1] * 4, 2, arrival=1.0))   # younger, live
        assert s.admit() == [0]
        s.slots[0].prefilled = 4
        s.allocator.alloc(s.allocator.num_free - 1)     # 1 block free
        s.submit(Request(0, [1] * 8, 2, arrival=0.0))   # OLDER head,
        for _ in range(3):                              # needs 3 blocks
            assert s.admit() == []                      # starving...
        got = s.admit()             # guard fires: younger seq preempted,
        assert got                  # freeing the blocks the head needed
        assert s.slots[got[0]].request.id == 0 and s.evictions == 1
        assert [r.id for r in s.waiting] == [1], \
            "victim must requeue BEHIND the head it starved"

    def test_aging_guard_never_preempts_older_work(self):
        s = Scheduler(BlockAllocator(9), 2, 4, 8, starvation_steps=2)
        s.submit(Request(0, [1] * 4, 2, arrival=0.0))   # OLDER, live
        assert s.admit() == [0]
        s.slots[0].prefilled = 4
        s.allocator.alloc(s.allocator.num_free - 1)
        s.submit(Request(1, [1] * 8, 2, arrival=1.0))   # younger head
        for _ in range(10):
            assert s.admit() == []
        assert s.slots[0] is not None and s.evictions == 0

    def test_prefix_admission_charges_only_the_unique_suffix(self):
        """With a cached prefix, admission maps the shared blocks and
        allocates fresh ones for the suffix alone; prefill starts past
        the cached tokens."""
        a = BlockAllocator(32)
        pc = PrefixCache(a, 4)
        s = Scheduler(a, 2, 4, 8, prefix_cache=pc)
        p0 = list(range(8))
        s.submit(Request(0, p0, 4, arrival=0.0))
        (slot0,) = s.admit()
        seq0 = s.slots[slot0]
        assert seq0.prefix_cached == 0          # cold trie: full prefill
        seq0.prefilled = 8
        pc.insert(p0, seq0.block_ids)
        used_before = a.num_used
        s.submit(Request(1, p0 + [9, 9], 4, arrival=1.0))
        (slot1,) = s.admit()
        seq1 = s.slots[slot1]
        assert seq1.block_ids[:2] == seq0.block_ids[:2], \
            "cached prefix must map the SAME physical blocks"
        assert seq1.prefix_cached == 8 and seq1.prefilled == 8
        # 10+1 tokens need 3 blocks; 2 came from the cache -> 1 fresh
        assert a.num_used == used_before + 1
        assert a.refcount(seq0.block_ids[0]) == 3   # seq0 + trie + seq1
        assert s.counters["prefix_hit_tokens"] == 8
        a.check()
        pc.check()

    def test_evicting_sharing_sequence_cannot_corrupt_survivors(self):
        """THE refcount-release regression pin: evicting a sequence that
        shares prefix blocks with a live sequence (and the trie) only
        drops its references — the survivor's table and the cached
        content stay intact."""
        a = BlockAllocator(32)
        pc = PrefixCache(a, 4)
        s = Scheduler(a, 2, 4, 8, prefix_cache=pc)
        p0 = list(range(8))
        s.submit(Request(0, p0, 4, arrival=0.0))
        (slot0,) = s.admit()
        seq0 = s.slots[slot0]
        seq0.prefilled = 8
        pc.insert(p0, seq0.block_ids)
        s.submit(Request(1, p0 + [9], 6, arrival=1.0))
        (slot1,) = s.admit()
        shared = list(s.slots[slot1].block_ids[:2])
        s.slots[slot1].prefilled = 9            # mid-decode
        assert s._evict_youngest(protect=slot0)
        assert s.slots[slot1] is None
        for b in shared:
            assert a.refcount(b) == 2, \
                "survivor + trie references must survive the eviction"
        assert s.slots[slot0].block_ids[:2] == shared
        a.check()
        pc.check()

    def test_trie_eviction_unblocks_admission_before_preemption(self):
        """Pool full of trie-retained (reclaimable) blocks: admission
        reclaims them instead of reporting starvation — sharing never
        starves admission."""
        a = BlockAllocator(5)                   # 4 usable
        pc = PrefixCache(a, 4)
        s = Scheduler(a, 2, 4, 4, prefix_cache=pc)
        for i in range(3):                      # fill the pool with
            blocks = a.alloc(1)                 # finished prompts' cache
            pc.insert([10 + i] * 4, blocks)
            a.release(blocks)
        assert a.num_free == 1 and pc.num_blocks == 3
        s.submit(Request(0, [1] * 7, 4))        # needs 2 blocks
        assert s.admit(), "reclaimable cache blocked admission"
        assert s.counters["prefix_trie_evictions"] >= 1
        a.check()
        pc.check()

    def test_hit_aware_admission_only_under_pressure(self):
        """THE hit-aware admission pin: a cached-prefix request jumps
        an older uncached head ONLY when the head is block-starved —
        with room for everyone, admission stays strict FIFO."""
        # --- pressure: head cannot fit, the cached request can ---
        a = BlockAllocator(6)                   # 5 usable
        pc = PrefixCache(a, 4)
        s = Scheduler(a, 2, 4, 4, prefix_cache=pc)
        p0 = list(range(8))
        s.submit(Request(0, p0, 4, arrival=0.0))
        (slot0,) = s.admit()
        seq0 = s.slots[slot0]
        seq0.prefilled = 8
        pc.insert(p0, seq0.block_ids)           # 2 full blocks cached
        assert a.num_free == 2
        s.submit(Request(1, [7] * 11, 4, arrival=1.0))   # needs 3 > 2
        s.submit(Request(2, p0 + [9], 4, arrival=2.0))   # 2 cached + 1
        admitted = s.admit()
        assert len(admitted) == 1
        assert s.slots[admitted[0]].request.id == 2, \
            "cached-prefix request should bypass the starved head"
        assert s.waiting[0].id == 1, "the head keeps its place in line"
        assert s.counters["prefix_hit_admissions"] == 1
        assert s.slots[admitted[0]].prefix_cached == 8
        a.check()
        pc.check()

        # --- no pressure: strict FIFO, no queue jumping ---
        a2 = BlockAllocator(32)
        pc2 = PrefixCache(a2, 4)
        s2 = Scheduler(a2, 3, 4, 4, prefix_cache=pc2)
        p = list(range(8))
        s2.submit(Request(0, p, 4, arrival=0.0))
        (sl,) = s2.admit()
        s2.slots[sl].prefilled = 8
        pc2.insert(p, s2.slots[sl].block_ids)
        s2.submit(Request(1, [7] * 11, 4, arrival=1.0))  # uncached, older
        s2.submit(Request(2, p + [9], 4, arrival=2.0))   # cached, younger
        order = [s2.slots[i].request.id for i in s2.admit()]
        assert order == [1, 2], \
            "without pressure admission must stay arrival order"
        assert s2.counters["prefix_hit_admissions"] == 0

    def test_hit_aware_bypass_disabled_without_aging_guard(self):
        """The bypass's liveness story leans on the aging guard (the
        jumper's suffix consumes free blocks the head waits on); with
        starvation_steps=None the guard is off, so the bypass must be
        too — the pre-change FIFO liveness guarantee holds."""
        a = BlockAllocator(6)
        pc = PrefixCache(a, 4)
        s = Scheduler(a, 2, 4, 4, prefix_cache=pc,
                      starvation_steps=None)
        p0 = list(range(8))
        s.submit(Request(0, p0, 4, arrival=0.0))
        (slot0,) = s.admit()
        s.slots[slot0].prefilled = 8
        pc.insert(p0, s.slots[slot0].block_ids)
        s.submit(Request(1, [7] * 11, 4, arrival=1.0))   # starved head
        s.submit(Request(2, p0 + [9], 4, arrival=2.0))   # cached, fits
        assert s.admit() == []
        assert [r.id for r in s.waiting] == [1, 2]
        assert s.counters["prefix_hit_admissions"] == 0

    def test_hit_aware_bypass_requires_cache_hits(self):
        """An uncached candidate has no claim to jump a starved head —
        the bypass admits nothing and never evicts on its behalf."""
        a = BlockAllocator(6)
        pc = PrefixCache(a, 4)
        s = Scheduler(a, 2, 4, 4, prefix_cache=pc)
        p0 = list(range(8))
        s.submit(Request(0, p0, 4, arrival=0.0))
        (slot0,) = s.admit()
        s.slots[slot0].prefilled = 8
        pc.insert(p0, s.slots[slot0].block_ids)
        s.submit(Request(1, [7] * 11, 4, arrival=1.0))   # starved head
        s.submit(Request(2, [8] * 3, 4, arrival=2.0))    # fits, NO hits
        assert s.admit() == []
        assert [r.id for r in s.waiting] == [1, 2]
        assert s.counters["prefix_hit_admissions"] == 0
        assert s.evictions == 0
        a.check()

    def test_scripted_trace_invariants(self):
        """Admit/decode/finish churn: at every step the pool partitions
        into free + exactly-the-live-sequences' blocks."""
        rng = np.random.default_rng(1)
        s = self._mk(blocks=12, slots=3, bs=2, nb_per_seq=6)
        nxt = 0
        for step in range(300):
            if rng.random() < 0.3:
                s.submit(Request(nxt, [1] * int(rng.integers(1, 8)),
                                 int(rng.integers(1, 6)),
                                 arrival=float(step)))
                nxt += 1
            for slot in s.admit():
                s.slots[slot].prefilled = len(s.slots[slot].request.prompt)
            for slot in list(s.live_slots()):
                if s.slots[slot] is None:
                    continue
                assert s.ensure_block(slot)
                if s.slots[slot] is None:
                    continue
                s.record_token(slot, int(rng.integers(0, 50)))
            s.allocator.check()
            live_blocks = [b for seq in s.slots if seq is not None
                           for b in seq.block_ids]
            assert len(live_blocks) == len(set(live_blocks))
            assert len(live_blocks) == s.allocator.num_used
        assert s.finished                     # the trace actually served


# ------------------------------------------------- paged forward parity

class TestPagedForwardParity:
    @pytest.mark.parametrize("cfg", [TINY, ROPE], ids=["learned", "rope"])
    def test_prefill_logits_match_contiguous_cache(self, cfg):
        """Same prompt, same capacity: the paged forward must reproduce
        forward_with_cache's logits (same shared-layer math over a
        position-ordered cache view)."""
        import jax
        import jax.numpy as jnp

        model = gpt.CausalLm(cfg)
        params = model.init(jax.random.key(0))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 10)), jnp.int32)
        bs, nb = 4, 3                        # capacity 12 both paths
        want, _ = model.forward_with_cache(
            params, toks, model.init_cache(2, nb * bs), 0)
        pools = init_pools(cfg, 1 + 2 * nb, bs)
        tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        got, new_pools = model.forward_paged(
            params, toks, pools, tables, jnp.zeros((2,), jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("cfg", [TINY, ROPE], ids=["learned", "rope"])
    def test_greedy_decode_token_identical_to_generate(self, cfg):
        """THE acceptance pin: mixed prompt/output lengths served through
        chunked prefill + continuous batching emit exactly the tokens
        generate() produces per request."""
        import jax

        model = gpt.CausalLm(cfg)
        params = model.init(jax.random.key(1))
        rng = np.random.default_rng(2)
        prompts = _prompts(rng, 5, lo=3, hi=13, vocab=cfg.vocab_size)
        budgets = [int(n) for n in rng.integers(1, 9, len(prompts))]
        engine = PagedDecodeEngine(model, params, ServeConfig(
            num_blocks=40, block_size=4, max_slots=3, max_seq_len=24,
            prefill_chunk=8))
        res = engine.run([Request(i, p, n) for i, (p, n)
                          in enumerate(zip(prompts, budgets))])
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            assert res["outputs"][i] == _generate_ref(model, params, p, n), \
                f"request {i} diverged from generate()"
        engine.allocator.check()
        assert engine.allocator.num_used == 0


# ------------------------------------------------------------ the engine

class TestEngine:
    def _engine(self, **kw):
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = ServeConfig(**{**dict(num_blocks=40, block_size=4,
                                      max_slots=4, max_seq_len=32,
                                      prefill_chunk=8), **kw})
        return model, params, PagedDecodeEngine(model, params, serve)

    def test_zero_recompiles_after_bucket_warmup(self):
        """Warm the buckets on one trace, then serve a DIFFERENT trace in
        the same envelope: the jit caches must not grow — steady-state
        serving never recompiles."""
        _, _, engine = self._engine()
        shape_rng = np.random.default_rng(3)
        lens = shape_rng.integers(3, 16, 6)
        budgets = [int(n) for n in shape_rng.integers(1, 10, 6)]

        def trace(content_seed):
            r = np.random.default_rng(content_seed)
            return [Request(i, list(map(int, r.integers(
                        0, TINY.vocab_size, int(s)))), budgets[i])
                    for i, s in enumerate(lens)]

        engine.run(trace(0))
        warm = engine.compile_counts()
        assert warm["decode"] > 0 and warm["prefill"] > 0
        engine.reset()
        engine.run(trace(7))                  # new content, same envelope
        assert engine.compile_counts() == warm, \
            "steady-state serving recompiled"

    def test_dispatch_shapes_are_bucketed_powers_of_two(self):
        _, _, engine = self._engine()
        rng = np.random.default_rng(4)
        reqs = [Request(i, p, int(rng.integers(1, 8)))
                for i, p in enumerate(_prompts(rng, 7, lo=3, hi=15))]
        engine.run(reqs)
        for shape in engine.dispatch_shapes:
            for dim in shape[1:]:
                assert dim & (dim - 1) == 0, f"non-pow2 dispatch {shape}"

    def test_more_requests_than_slots_all_complete(self):
        _, _, engine = self._engine(max_slots=2)
        rng = np.random.default_rng(5)
        budgets = [int(n) for n in rng.integers(1, 7, 6)]
        reqs = [Request(i, p, budgets[i])
                for i, p in enumerate(_prompts(rng, 6, lo=3, hi=10))]
        res = engine.run(reqs)
        assert sorted(res["outputs"]) == list(range(6))
        for i, n in enumerate(budgets):
            assert len(res["outputs"][i]) == n
        assert engine.allocator.num_used == 0

    def test_eos_recycles_midstream(self):
        model, params, engine = self._engine()
        probe = engine.run([Request(0, [5, 6, 7], 6)])
        full = probe["outputs"][0]
        assert len(full) == 6
        eos = full[2]
        _, _, engine2 = self._engine(eos_id=eos)
        res = engine2.run([Request(0, [5, 6, 7], 6)])
        # greedy is deterministic: engine2 emits full's tokens until the
        # FIRST occurrence of the eos value, then recycles the slot
        assert res["outputs"][0] == full[:full.index(eos) + 1]
        assert engine2.allocator.num_used == 0

    def test_memory_scales_with_live_tokens_not_batch_times_maxlen(self):
        """The paged pool serves a workload whose static contiguous cache
        would need more memory: 4 slots x 32 max_len = 128 entries
        contiguous vs a 23-usable-block (92-entry) pool."""
        _, _, engine = self._engine(num_blocks=24)   # 23 usable = 92 toks
        rng = np.random.default_rng(6)
        reqs = [Request(i, p, 4)
                for i, p in enumerate(_prompts(rng, 8, lo=3, hi=10))]
        res = engine.run(reqs)
        assert sorted(res["outputs"]) == list(range(8))

    def test_eviction_under_pool_pressure_keeps_outputs_exact(self):
        """A tight pool forces the youngest sequence out mid-prefill
        (restart-from-scratch preemption); the evicted request must
        still complete with generate()-identical tokens, and a stale
        prefill-queue entry must never prefill the slot's NEW occupant."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = ServeConfig(num_blocks=9, block_size=2, max_slots=2,
                            max_seq_len=12, prefill_chunk=2)
        engine = PagedDecodeEngine(model, params, serve)
        rng = np.random.default_rng(8)
        pa = list(map(int, rng.integers(0, TINY.vocab_size, 2)))
        pb = list(map(int, rng.integers(0, TINY.vocab_size, 11)))
        res = engine.run([Request(0, pa, 10, arrival=0.0),
                          Request(1, pb, 1, arrival=0.0)])
        assert engine.sched.evictions >= 1, \
            "trace was meant to exercise eviction"
        assert res["outputs"][0] == _generate_ref(model, params, pa, 10)
        assert res["outputs"][1] == _generate_ref(model, params, pb, 1)
        engine.allocator.check()
        assert engine.allocator.num_used == 0

    def test_infeasible_request_never_crashes_the_engine(self):
        """THE satellite fix for the engine-killing pool-exhaustion
        raise: an infeasible request terminates with a structured
        status, every other stream completes generate()-identically."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        _, _, engine = self._engine()
        rng = np.random.default_rng(9)
        good = _prompts(rng, 3, lo=3, hi=8)
        reqs = [Request(i, p, 4) for i, p in enumerate(good)]
        # prompt+output over the per-sequence cap (32): infeasible
        reqs.insert(1, Request(99, list(map(int, rng.integers(
            0, TINY.vocab_size, 30))), 10))
        res = engine.run(reqs)
        assert res["statuses"][99] == "rejected"
        assert res["faults"]["rejected"] == 1
        assert 99 not in res["outputs"]
        for i, p in enumerate(good):
            assert res["outputs"][i] == _generate_ref(model, params, p, 4)
        assert engine.allocator.num_used == 0

    def test_deadline_expiry_is_terminal_not_fatal(self):
        """An expired request frees its slot and fails with
        deadline_exceeded; the engine keeps serving the rest."""
        _, _, engine = self._engine()
        clock = {"t": 0.0}

        def fake_time():
            clock["t"] += 0.01
            return clock["t"]

        # id 0 can never finish 64 tokens before its 50ms deadline
        res = engine.run(
            [Request(0, [1, 2, 3], 20, arrival=0.0, deadline=0.05),
             Request(1, [4, 5], 3, arrival=0.0)], time_fn=fake_time)
        assert res["statuses"][0] == "deadline_exceeded"
        assert res["statuses"][1] == "ok"
        assert len(res["outputs"][1]) == 3 and 0 not in res["outputs"]
        assert res["faults"]["deadline_exceeded"] == 1
        assert engine.allocator.num_used == 0

    def test_default_ttl_from_serve_config(self):
        """serve.deadline_ms stamps arrival+TTL on every request that
        has no explicit deadline — the --deadline-ms knob."""
        _, _, engine = self._engine(deadline_ms=50.0)
        clock = {"t": 0.0}

        def fake_time():
            clock["t"] += 0.01
            return clock["t"]

        res = engine.run([Request(0, [1, 2, 3], 20, arrival=0.0)],
                         time_fn=fake_time)
        assert res["statuses"][0] == "deadline_exceeded"

    def test_queue_depth_sheds_at_engine_level(self):
        _, _, engine = self._engine(max_slots=1, queue_depth=1)
        rng = np.random.default_rng(10)
        reqs = [Request(i, p, 3)
                for i, p in enumerate(_prompts(rng, 5, lo=3, hi=6))]
        res = engine.run(reqs)
        assert res["faults"]["shed"] >= 1
        for i in range(5):      # every request left with SOME terminal
            assert res["statuses"][i] in ("ok", "shed")
        done = [i for i, s in res["statuses"].items() if s == "ok"]
        assert sorted(res["outputs"]) == sorted(done)
        assert engine.allocator.num_used == 0

    def test_sigterm_drains_in_flight_and_sheds_queue(self):
        """The graceful-drain acceptance pin: a stop request mid-run
        stops admission, in-flight work finishes (budget permitting),
        un-admitted work sheds, and the result reports both counts."""
        from mpi_tensorflow_tpu.train.preemption import PreemptionGuard

        _, _, engine = self._engine(max_slots=2)
        rng = np.random.default_rng(11)
        prompts = _prompts(rng, 6, lo=3, hi=8)
        # late arrivals that a drain at t~0 must shed un-served
        reqs = [Request(i, p, 8, arrival=0.0 if i < 2 else 1e9)
                for i, p in enumerate(prompts)]
        guard = PreemptionGuard()          # no signal wiring needed:
        steps = {"n": 0}                   # request_stop == SIGTERM path

        def fake_time():
            steps["n"] += 1
            if steps["n"] == 6:
                guard.request_stop("SIGTERM")
            return steps["n"] * 1e-4

        res = engine.run(reqs, time_fn=fake_time, guard=guard)
        assert res["drain"]["requested"]
        assert res["drain"]["shed"] == 4
        assert res["drain"]["drained"] + res["drain"]["cut"] >= 1
        for i in range(2):
            assert res["statuses"][i] in ("ok", "drained")
        for i in range(2, 6):
            assert res["statuses"][i] == "shed"
        assert engine.allocator.num_used == 0

    def test_drain_budget_cuts_unfinished_work(self):
        """drain_ms = 0: the budget expires immediately — everything
        still in flight terminates as `drained`, blocks freed."""
        from mpi_tensorflow_tpu.train.preemption import PreemptionGuard

        _, _, engine = self._engine(drain_ms=0.0)
        guard = PreemptionGuard()
        clock = {"t": 0.0}

        def fake_time():
            clock["t"] += 0.01
            if clock["t"] > 0.2:
                guard.request_stop()
            return clock["t"]

        res = engine.run([Request(0, [1, 2, 3], 25, arrival=0.0)],
                         time_fn=fake_time, guard=guard)
        assert res["statuses"][0] == "drained"
        assert res["drain"]["cut"] == 1
        assert engine.allocator.num_used == 0

    def test_arrival_stamps_gate_admission(self):
        """A request with a later arrival must not be admitted before its
        stamp on the engine's clock — the run must outlast the stamp."""
        _, _, engine = self._engine()
        clock = {"t": 0.0}

        def fake_time():
            clock["t"] += 0.01
            return clock["t"]

        res = engine.run([Request(0, [1, 2, 3], 2, arrival=0.0),
                          Request(1, [4, 5], 2, arrival=0.5)],
                         time_fn=fake_time)
        assert sorted(res["outputs"]) == [0, 1]
        assert clock["t"] > 0.5

    def test_finish_stamps_and_advisor_observation(self):
        """engine.run records a final-token finish stamp per completed
        request (the goodput attained-latency seam) and feeds its load
        signals into a ScaleAdvisor when one is passed."""
        from mpi_tensorflow_tpu.serving.autoscale import ScaleAdvisor

        _, _, engine = self._engine()
        reqs = [Request(0, [1, 2, 3], 3, arrival=0.0),
                Request(1, [4, 5], 2, arrival=0.1)]
        res = engine.run(reqs)
        assert res["autoscale"] is None          # advisory layer is opt-in
        for r in reqs:
            assert res["statuses"][r.id] == "ok"
            assert res["request_finish_s"][r.id] >= r.arrival

        engine.reset()
        advisor = ScaleAdvisor()
        res2 = engine.run([Request(0, [1, 2, 3], 3, arrival=0.0)],
                          advisor=advisor)
        assert res2["autoscale"] == advisor.report()
        assert res2["autoscale"]["ticks"] > 0
        assert res2["autoscale"]["replicas_advised"] >= 1


# ----------------------------------------------------- prefix cache e2e

class TestPrefixCacheEngine:
    """The tentpole's determinism contract: under greedy decode,
    prefix-cache-on outputs are token-identical to cache-off (and to
    generate()) for every request — across shared-prefix batches, CoW
    divergence mid-block, and eviction under pressure."""

    def _engine(self, **kw):
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = ServeConfig(**{**dict(num_blocks=48, block_size=4,
                                      max_slots=3, max_seq_len=32,
                                      prefill_chunk=8,
                                      prefix_cache="on"), **kw})
        return model, params, PagedDecodeEngine(model, params, serve)

    def test_shared_prefix_batch_token_identical_with_hits(self):
        """Requests sharing a system prompt: later admissions map the
        cached blocks (hit_rate > 0) and every output still equals
        generate()'s."""
        model, params, engine = self._engine()
        rng = np.random.default_rng(20)
        shared = list(map(int, rng.integers(0, TINY.vocab_size, 12)))
        prompts = [shared + list(map(int, rng.integers(
            0, TINY.vocab_size, int(n)))) for n in rng.integers(1, 8, 7)]
        budgets = [int(n) for n in rng.integers(1, 7, len(prompts))]
        res = engine.run([Request(i, p, n, arrival=0.0) for i, (p, n)
                          in enumerate(zip(prompts, budgets))])
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            assert res["outputs"][i] == _generate_ref(model, params, p, n), \
                f"request {i} diverged with the prefix cache on"
        assert res["prefix"]["enabled"]
        assert res["prefix"]["hit_tokens"] > 0
        assert res["prefix"]["shared_blocks"] > 0
        # pool-leak invariant at quiescence: only the trie's own refs
        engine.allocator.check()
        assert engine.allocator.num_used == engine.prefix_cache.num_blocks

    def test_cow_on_fully_cached_block_multiple_prompt(self):
        """Identical prompts whose length is an exact block multiple:
        the follow-ups match EVERY block, recompute only the final
        position, and that write lands mid-block in a shared block —
        the copy-on-write trigger.  Outputs must stay exact and the
        donor's cached content uncorrupted."""
        # one slot: each request admits only after its predecessor (the
        # trie donor) finished prefill, so the follow-ups actually hit
        model, params, engine = self._engine(max_slots=1)
        rng = np.random.default_rng(21)
        prompt = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        assert len(prompt) % 4 == 0              # exact block multiple
        budgets = [6, 4, 2]                      # divergent stream lengths
        res = engine.run([Request(i, list(prompt), n, arrival=0.0)
                          for i, n in enumerate(budgets)])
        assert res["prefix"]["cow_copies"] >= 1, \
            "the shared-final-block recompute must trigger CoW"
        want = _generate_ref(model, params, prompt, max(budgets))
        for i, n in enumerate(budgets):
            assert res["outputs"][i] == want[:n], \
                f"request {i} diverged after CoW"

    def test_eviction_under_pressure_with_sharing_stays_exact(self):
        """A tight pool forces preemption while sequences share prefix
        blocks: evicting a sharer must not corrupt survivors, and every
        request still completes generate()-identically."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = ServeConfig(num_blocks=10, block_size=2, max_slots=2,
                            max_seq_len=12, prefill_chunk=2,
                            prefix_cache="on")
        engine = PagedDecodeEngine(model, params, serve)
        rng = np.random.default_rng(22)
        shared = list(map(int, rng.integers(0, TINY.vocab_size, 4)))
        pa = shared + list(map(int, rng.integers(0, TINY.vocab_size, 1)))
        pb = shared + list(map(int, rng.integers(0, TINY.vocab_size, 6)))
        res = engine.run([Request(0, pa, 7, arrival=0.0),
                          Request(1, pb, 1, arrival=0.0)])
        assert engine.sched.evictions + engine.prefix_cache.evicted >= 1, \
            "trace was meant to exercise eviction under pressure"
        assert res["outputs"][0] == _generate_ref(model, params, pa, 7)
        assert res["outputs"][1] == _generate_ref(model, params, pb, 1)
        engine.allocator.check()

    def test_zero_recompiles_with_prefix_cache_on(self):
        """The prefix cache (including its CoW copy dispatch) must not
        break the steady-state zero-recompile contract."""
        _, _, engine = self._engine()
        rng = np.random.default_rng(23)
        shared = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        lens = rng.integers(1, 8, 6)
        budgets = [int(n) for n in rng.integers(1, 8, 6)]

        def trace(seed):
            r = np.random.default_rng(seed)
            return [Request(i, shared + list(map(int, r.integers(
                        0, TINY.vocab_size, int(s)))), budgets[i])
                    for i, s in enumerate(lens)]

        engine.run(trace(0))
        warm = engine.compile_counts()
        assert warm["decode"] > 0 and warm["prefill"] > 0
        engine.reset()
        engine.run(trace(9))
        assert engine.compile_counts() == warm, \
            "prefix cache added steady-state recompiles"

    def test_off_mode_reports_disabled_and_shares_nothing(self):
        """--prefix-cache off (the default) must be byte-for-byte
        today's behavior: no trie, no sharing, no CoW dispatch use."""
        model, params, engine = self._engine(prefix_cache="off")
        rng = np.random.default_rng(24)
        p = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        res = engine.run([Request(0, list(p), 3, arrival=0.0),
                          Request(1, list(p), 3, arrival=0.0)])
        assert engine.prefix_cache is None
        assert res["prefix"] == {
            "enabled": False, "hit_tokens": 0, "prompt_tokens": 0,
            "hit_rate": 0.0, "shared_blocks": 0, "cow_copies": 0,
            "trie_evictions": 0, "trie_blocks": 0, "hit_admissions": 0,
            "gen_inserted_blocks": 0, "partial_copy_tokens": 0,
            "prefill_tokens_saved": 0, "router_prefix_hits": 0}
        assert res["outputs"][0] == res["outputs"][1] \
            == _generate_ref(model, params, p, 3)
        assert engine.allocator.num_used == 0


# ------------------------------------------------------------ cli guards

@pytest.mark.quick
class TestTrainingCliGuards:
    def test_virtual_stages_requires_interleaved_schedule(self):
        from mpi_tensorflow_tpu import cli

        with pytest.raises(SystemExit, match="virtual-stages"):
            cli.main(["--virtual-stages", "3"])

    def test_virtual_stages_accepted_with_interleaved(self):
        from mpi_tensorflow_tpu import cli

        args = cli.build_parser().parse_args(
            ["--virtual-stages", "3", "--pp-schedule", "1f1b_interleaved"])
        assert cli.config_from_args(args).virtual_stages == 3
