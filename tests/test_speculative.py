"""Speculative decoding: drafters, verify-once engine, multi-token scheduler.

The tier-1 anchors the ISSUE acceptance names:
- greedy outputs in ``ngram`` and ``draft-model`` modes are
  TOKEN-IDENTICAL to ``--speculative off`` and to
  ``CausalLm.generate`` — across shared-prefix batches, prefix-cache
  on/off, copy-on-write inside a draft window, eviction mid-draft,
  deadline expiry mid-draft, and SIGKILL journal replay;
- rejected draft tokens' blocks are rolled back (the pool never retains
  phantom entries) and ``check_quiescent()`` holds at end of run;
- steady-state speculative serving performs zero recompiles after the
  engine's verify pre-warm (jit cache-size probe).

ROPE geometry is used where the tests need a NON-ZERO accept rate: an
untrained learned-position model emits an aperiodic stream (~every
token unique), while rope dynamics are position-relative and fall into
the recurrent regime n-gram self-drafting targets.  Token identity is
asserted on BOTH geometries either way — acceptance only changes how
much work the verify path saves, never which tokens come out.

This file: drafters, scheduler generalization, token identity, CLI guards.
Prefix cache / CoW / eviction / deadline / replay are in
tests/test_speculative_faults.py; the draft window (auto-tune, rollback,
recompile discipline) in tests/test_speculative_window.py.
"""

import dataclasses

import numpy as np
import pytest

from _jitted import generate_ref as _generate_ref
from _speculative_common import ROPE, SERVE, TINY, _pair, _shared_trace
from mpi_tensorflow_tpu.models import gpt
from mpi_tensorflow_tpu.serving import (BlockAllocator, NgramDrafter,
                                        PagedDecodeEngine, Request, Scheduler)


# ------------------------------------------------------------- drafters

@pytest.mark.quick
class TestNgramDrafter:
    def test_novel_context_returns_no_draft(self):
        d = NgramDrafter()
        assert d.draft(0, [1, 2, 3, 4, 5], 4) == []

    def test_suffix_match_proposes_the_continuation(self):
        d = NgramDrafter()
        # suffix [1, 2] occurred earlier followed by [9, 7]
        assert d.draft(0, [5, 1, 2, 9, 7, 1, 2], 2) == [9, 7]

    def test_longer_ngram_wins_over_shorter(self):
        d = NgramDrafter()
        # the 2-gram [3, 4] picks the [3, 4] -> 8 continuation even
        # though the most recent 1-gram match ([4] at index 5) differs
        ctx = [3, 4, 8, 6, 3, 4, 9, 3, 4]
        assert d.draft(0, ctx, 1) == [9]

    def test_full_window_preferred_over_recent_partial(self):
        d = NgramDrafter(max_ngram=2)
        # suffix [1, 1]: the most recent match (idx 5) runs into the
        # end of ctx with only one following token; the match at idx 0
        # carries a full k window and wins
        ctx = [1, 1, 2, 3, 4, 1, 1, 1]
        assert d.draft(0, ctx, 3) == [2, 3, 4]

    def test_partial_window_returned_when_nothing_full(self):
        d = NgramDrafter(max_ngram=2)
        assert d.draft(0, [1, 2, 9, 1, 2], 4) == [9, 1, 2]

    def test_degenerate_inputs(self):
        d = NgramDrafter()
        assert d.draft(0, [7], 4) == []
        assert d.draft(0, [1, 2, 1, 2], 0) == []
        with pytest.raises(ValueError, match="min_ngram"):
            NgramDrafter(max_ngram=0)


# --------------------------------------------- scheduler generalization

@pytest.mark.quick
class TestSchedulerMultiToken:
    def _live(self, blocks=16, slots=2, bs=4, nb=4, prompt=3, budget=8):
        s = Scheduler(BlockAllocator(blocks), slots, bs, nb)
        s.submit(Request(0, [1] * prompt, budget))
        (slot,) = s.admit()
        s.slots[slot].prefilled = prompt
        return s, slot

    def test_record_tokens_appends_all_within_budget(self):
        s, slot = self._live(budget=8)
        assert s.record_tokens(slot, [7, 8, 9]) == 3
        assert s.slots[slot].generated == [7, 8, 9]

    def test_record_tokens_stops_at_budget(self):
        s, slot = self._live(budget=2)
        assert s.record_tokens(slot, [7, 8, 9, 10]) == 2
        assert s.slots[slot] is None
        assert s.finished[0].generated == [7, 8]
        assert s.allocator.num_used == 0

    def test_record_tokens_stops_at_eos(self):
        s, slot = self._live(budget=8)
        assert s.record_tokens(slot, [7, 99, 8], eos_id=99) == 2
        assert s.slots[slot] is None
        assert s.finished[0].generated == [7, 99]

    def test_extend_for_takes_only_free_blocks_no_eviction(self):
        s, slot = self._live(blocks=16, bs=4, nb=8, prompt=3)
        base = len(s.slots[slot].block_ids)
        # plenty free: full draft window granted
        assert s.extend_for(slot, 4 + 8) == (base + 2) * 4
        # drain the pool, then park a second sequence: extend_for must
        # neither evict it nor grow past what is free
        s.submit(Request(1, [1] * 3, 4, arrival=1.0))
        (other,) = s.admit()
        s.slots[other].prefilled = 3
        held = s.allocator.alloc(s.allocator.num_free)
        covered = s.extend_for(slot, 64)
        assert covered == (base + 2) * 4          # unchanged: no free
        assert s.slots[other] is not None, "extend_for must not preempt"
        s.allocator.free(held)
        s.allocator.check()

    def test_extend_for_caps_at_max_blocks_per_seq(self):
        s, slot = self._live(blocks=32, bs=4, nb=4, prompt=3)
        assert s.extend_for(slot, 10 ** 6) == 4 * 4

    def test_rollback_releases_trailing_blocks(self):
        """THE rollback unit pin: blocks allocated for a draft window
        whose tokens were rejected return to the pool, and the
        allocator's partition invariant still holds."""
        s, slot = self._live(blocks=16, bs=4, nb=8, prompt=3)
        seq = s.slots[slot]
        s.extend_for(slot, 4 + 12)                # window for 12 drafts
        assert len(seq.block_ids) == 4
        used = s.allocator.num_used
        assert s.rollback_blocks(slot, 5) == 2    # keep 2 blocks (5 toks)
        assert s.allocator.num_used == used - 2
        assert len(seq.block_ids) == 2
        s.allocator.check()
        assert s.rollback_blocks(slot, 5) == 0    # idempotent

    def test_rollback_never_touches_needed_blocks(self):
        s, slot = self._live(bs=4, prompt=3)
        assert s.rollback_blocks(slot, 4) == 0
        assert s.ensure_block(slot)


# ------------------------------------------------------ token identity

class TestSpeculativeParity:
    def test_ngram_token_identical_on_aperiodic_stream(self):
        """Learned positions: the untrained stream never repeats, so
        the drafter proposes little and accepts nothing — outputs must
        STILL be exactly off-mode's (the no-draft degenerate case is a
        plain decode step)."""
        model, params, off, spec = _pair(TINY, speculative="ngram",
                                         draft_k=4)
        rng = np.random.default_rng(0)
        reqs = _shared_trace(rng, n=5, budget=8)
        want = off.run([dataclasses.replace(r) for r in reqs])
        got = spec.run([dataclasses.replace(r) for r in reqs])
        assert got["outputs"] == want["outputs"]
        assert got["speculation"]["enabled"]
        assert got["speculation"]["verify_forwards"] > 0

    def test_ngram_accepts_on_recurrent_stream_and_stays_identical(self):
        """ROPE geometry: the stream is recurrent, the self-draft lands
        — accept_rate > 0, steps_saved > 0 (fewer verify forwards than
        emitted tokens), outputs still exactly off-mode's and
        generate()'s.  The CPU-measurable form of the ISSUE's
        bandwidth-proxy acceptance criterion."""
        model, params, off, spec = _pair(ROPE, speculative="ngram",
                                         draft_k=4)
        rng = np.random.default_rng(1)
        reqs = _shared_trace(rng, n=4, budget=32)
        want = off.run([dataclasses.replace(r) for r in reqs])
        got = spec.run([dataclasses.replace(r) for r in reqs])
        assert got["outputs"] == want["outputs"]
        sp = got["speculation"]
        assert sp["accepted_tokens"] > 0 and sp["accept_rate"] > 0
        assert sp["steps_saved"] > 0
        assert sp["verify_forwards"] < sp["emitted_tokens"]
        for r in reqs:
            assert got["outputs"][r.id] == _generate_ref(
                model, params, r.prompt, r.max_new_tokens)

    def test_draft_model_token_identical_with_fresh_drafter(self):
        """The default (untrained, fresh-init) tiny drafter disagrees
        with the target almost everywhere — every draft dies at verify,
        outputs must not move."""
        model, params, off, spec = _pair(TINY, speculative="draft-model",
                                         draft_k=3)
        rng = np.random.default_rng(2)
        reqs = _shared_trace(rng, n=4, budget=8)
        want = off.run([dataclasses.replace(r) for r in reqs])
        got = spec.run([dataclasses.replace(r) for r in reqs])
        assert got["outputs"] == want["outputs"]
        assert got["speculation"]["draft_tokens"] > 0
        spec.drafter.check_quiescent()

    def test_draft_model_self_draft_accepts_fully(self):
        """Drafter == target (injected): every draft token survives
        verification — accept_rate 1.0, the all-accept boundary of the
        acceptance rule — and outputs still match generate()."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, speculative="draft-model",
                                    draft_k=4)
        spec = PagedDecodeEngine(model, params, serve,
                                 draft_model=model, draft_params=params)
        rng = np.random.default_rng(3)
        reqs = _shared_trace(rng, n=4, budget=12)
        got = spec.run([dataclasses.replace(r) for r in reqs])
        sp = got["speculation"]
        assert sp["accept_rate"] == 1.0
        assert sp["steps_saved"] > 0
        for r in reqs:
            assert got["outputs"][r.id] == _generate_ref(
                model, params, r.prompt, r.max_new_tokens)

    def test_eos_inside_accepted_window_truncates_stream(self):
        """EOS emitted mid-window must end the stream exactly where
        one-token decode would — nothing past EOS streams or lands in
        the journal."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        probe = PagedDecodeEngine(model, params, SERVE)
        full = probe.run([Request(0, [5, 6, 7], 8)])["outputs"][0]
        eos = full[3]
        serve = dataclasses.replace(SERVE, speculative="draft-model",
                                    draft_k=4, eos_id=eos)
        spec = PagedDecodeEngine(model, params, serve,
                                 draft_model=model, draft_params=params)
        res = spec.run([Request(0, [5, 6, 7], 8)])
        assert res["outputs"][0] == full[:full.index(eos) + 1]
        spec.sched.check_quiescent()


# ---------------------------------------------------------------- drafter

@pytest.mark.quick
class TestMakeDrafter:
    def test_make_drafter_rejects_unknown_mode(self):
        from mpi_tensorflow_tpu.serving import make_drafter

        assert make_drafter("off", SERVE, None) is None
        with pytest.raises(ValueError, match="speculative"):
            make_drafter("turbo", SERVE, None)
