"""Speculative decoding, the draft window: auto-tuning of the effective k,
rollback of rejected draft blocks (the pool never retains phantom entries,
``check_quiescent()`` holds), and zero recompiles after the verify pre-warm.
See tests/test_speculative.py's docstring for the geometries."""

import dataclasses

import numpy as np

from _jitted import generate_ref as _generate_ref
from _speculative_common import (ROPE, SERVE, TINY, _WrongDrafter, _pair,
                                 _shared_trace)
from mpi_tensorflow_tpu.models import gpt
from mpi_tensorflow_tpu.serving import PagedDecodeEngine, Request


# ---------------------------------------------------- draft-window auto-tune

class TestDraftAutoTune:
    """--draft-auto on: the EFFECTIVE draft window follows the
    observed accept rate (EWMA, clamped to [1, draft_k]) while the
    verify dispatch width — and therefore the compile set — never
    changes, and emitted tokens never move."""

    def test_always_wrong_drafter_shrinks_window_to_floor(self):
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(11)
        prompts = [list(map(int, rng.integers(0, TINY.vocab_size, 5)))
                   for _ in range(3)]
        budget = 12
        truth = {i: _generate_ref(model, params, p, budget)
                 for i, p in enumerate(prompts)}
        serve = dataclasses.replace(SERVE, speculative="ngram",
                                    draft_k=4, draft_auto="on")
        engine = PagedDecodeEngine(model, params, serve)
        engine.drafter = _WrongDrafter(truth, dict(enumerate(prompts)),
                                       TINY.vocab_size)
        res = engine.run([Request(i, p, budget, arrival=0.0)
                          for i, p in enumerate(prompts)])
        # zero accepts: the EWMA decays and the window hits its floor —
        # 1, never 0 (a dead window could never observe a recovery)
        assert engine._draft_k_eff == 1
        sp = res["speculation"]
        assert sp["draft_auto"] == "on"
        assert sp["effective_k"] < serve.draft_k, \
            "auto-tuning never shrank the window"
        for i in truth:
            assert res["outputs"][i] == truth[i], \
                "auto-tuning changed emitted tokens"
        engine.sched.check_quiescent()

    def test_self_draft_all_accept_keeps_full_window(self):
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, speculative="draft-model",
                                    draft_k=4, draft_auto="on")
        spec = PagedDecodeEngine(model, params, serve,
                                 draft_model=model, draft_params=params)
        rng = np.random.default_rng(12)
        reqs = _shared_trace(rng, n=4, budget=12)
        got = spec.run([dataclasses.replace(r) for r in reqs])
        sp = got["speculation"]
        assert sp["accept_rate"] == 1.0
        assert spec._draft_k_eff == serve.draft_k, \
            "a fully-accepting drafter must keep the full window"
        assert sp["effective_k"] == float(serve.draft_k)
        for r in reqs:
            assert got["outputs"][r.id] == _generate_ref(
                model, params, r.prompt, r.max_new_tokens)

    def test_auto_off_reports_the_configured_k(self):
        model, params, off, spec = _pair(ROPE, key=5,
                                         speculative="ngram", draft_k=3)
        rng = np.random.default_rng(13)
        reqs = _shared_trace(rng, n=3, budget=10)
        got = spec.run([dataclasses.replace(r) for r in reqs])
        sp = got["speculation"]
        assert sp["draft_auto"] == "off"
        assert sp["effective_k"] == float(3)

    def test_zero_recompiles_with_auto_on(self):
        """Shrinking/growing the effective k only changes n_valid lane
        counts inside the FIXED draft_k+1 verify width — the jit caches
        must not grow across a second trace."""
        import jax

        model = gpt.CausalLm(ROPE)
        params = model.init(jax.random.key(1))
        serve = dataclasses.replace(SERVE, speculative="ngram",
                                    draft_k=4, draft_auto="on")
        engine = PagedDecodeEngine(model, params, serve)

        def trace(seed):
            r = np.random.default_rng(seed)
            return _shared_trace(r, n=4, budget=12)

        engine.run(trace(0))
        warm = engine.compile_counts()
        engine.reset()
        engine.run(trace(9))
        assert engine.compile_counts() == warm, \
            "draft-window auto-tuning recompiled"


# -------------------------------------------------------------- rollback

class TestRollback:
    def test_rejected_draft_blocks_released_and_quiescent(self):
        """THE rollback pin: with an always-wrong drafter, every verify
        window's trailing blocks are phantom storage — after each step
        they must be back in the pool (live blocks never exceed the
        off-mode requirement) and check_quiescent() holds at the end."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(9)
        prompts = [list(map(int, rng.integers(0, TINY.vocab_size, 5)))
                   for _ in range(3)]
        budget = 10
        truth = {i: _generate_ref(model, params, p, budget)
                 for i, p in enumerate(prompts)}

        serve = dataclasses.replace(SERVE, speculative="ngram", draft_k=4)
        engine = PagedDecodeEngine(model, params, serve)
        engine.drafter = _WrongDrafter(truth, dict(enumerate(prompts)),
                                       TINY.vocab_size)
        reqs = [Request(i, p, budget, arrival=0.0)
                for i, p in enumerate(prompts)]
        res = engine.run(reqs)
        assert engine.drafter.calls > 0
        sp = res["speculation"]
        assert sp["draft_tokens"] > 0 and sp["accepted_tokens"] == 0
        assert sp["steps_saved"] == 0
        for i, p in enumerate(prompts):
            assert res["outputs"][i] == truth[i], \
                "an all-rejected draft changed emitted tokens"
        # every draft-window block was rolled back: nothing leaked
        engine.sched.check_quiescent()
        assert engine.allocator.num_used == 0

    def test_rollback_frees_blocks_step_by_step(self):
        """Track the pool between steps: after a verify step with zero
        acceptance, the sequence holds exactly the blocks off-mode
        decode would (no phantom tail)."""
        import jax

        from mpi_tensorflow_tpu.serving.paged_cache import blocks_for

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        prompt = [3, 1, 4, 1, 5]
        truth = {0: _generate_ref(model, params, prompt, 8)}
        serve = dataclasses.replace(SERVE, speculative="ngram", draft_k=4)
        engine = PagedDecodeEngine(model, params, serve)
        engine.drafter = _WrongDrafter(truth, {0: prompt},
                                       TINY.vocab_size)
        engine.sched.submit(Request(0, prompt, 8, arrival=0.0))
        while not engine.sched.all_done():
            engine.step()
            for seq in engine.sched.slots:
                if seq is None or seq.prefilled < len(prompt):
                    continue
                assert len(seq.block_ids) <= blocks_for(
                    seq.length + 1, serve.block_size), \
                    "phantom draft blocks survived the step"
        assert engine.allocator.num_used == 0


# ------------------------------------------------- recompile discipline

class TestSpeculativeCompileDiscipline:
    def test_zero_recompiles_steady_state_ngram(self):
        """THE zero-recompile acceptance pin for speculative mode: the
        verify pre-warm covers every bucket at build, so a fresh trace
        with DIFFERENT content (hence different acceptance patterns,
        hence different bucket visits) adds no compiles."""
        import jax

        model = gpt.CausalLm(ROPE)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, speculative="ngram", draft_k=4)
        engine = PagedDecodeEngine(model, params, serve)
        warm0 = engine.compile_counts()
        assert warm0["verify"] > 0, "verify pre-warm did not compile"

        def trace(seed):
            # fixed tail LENGTHS across seeds: prefill bucket visits
            # depend on the trace envelope for off-mode and speculative
            # alike — only CONTENT (and hence acceptance, the thing the
            # verify pre-warm must cover) varies here
            r = np.random.default_rng(seed)
            return _shared_trace(r, n=5, budget=24,
                                 tail_lens=[1, 2, 3, 4, 5])

        engine.run(trace(0))
        warm = engine.compile_counts()
        engine.reset()
        engine.run(trace(13))                # new content, same envelope
        assert engine.compile_counts() == warm, \
            "speculative steady state recompiled"

    def test_zero_recompiles_steady_state_draft_model(self):
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, speculative="draft-model",
                                    draft_k=3)
        engine = PagedDecodeEngine(model, params, serve,
                                   draft_model=model, draft_params=params)
        assert engine.compile_counts()["draft"] > 0, \
            "drafter chunk-bucket pre-warm did not compile"

        def trace(seed):
            # fixed tail lengths: content-only variation (see ngram pin)
            r = np.random.default_rng(seed)
            return _shared_trace(r, n=4, budget=10,
                                 tail_lens=[1, 2, 3, 4])

        engine.run(trace(0))
        warm = engine.compile_counts()
        engine.reset()
        engine.run(trace(5))
        assert engine.compile_counts() == warm, \
            "draft-model steady state recompiled"

    def test_verify_dispatch_shapes_are_bucketed(self):
        import jax

        model = gpt.CausalLm(ROPE)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, speculative="ngram", draft_k=4)
        engine = PagedDecodeEngine(model, params, serve)
        rng = np.random.default_rng(14)
        engine.run(_shared_trace(rng, n=5, budget=12))
        kinds = {s[0] for s in engine.dispatch_shapes}
        assert "verify" in kinds and "decode" not in kinds, \
            "speculative mode must route all decode work through verify"
        caps = (serve.max_slots, serve.max_blocks_per_seq)
        for shape in engine.dispatch_shapes:
            for dim, cap in zip(shape[1:], caps):
                # pow2, or clamped at the configured cap (engine._bucket
                # rounds up then caps — same discipline as decode)
                assert dim & (dim - 1) == 0 or dim == cap, \
                    f"unbucketed dispatch {shape}"
