"""Speculative decoding under the engine's fault paths: prefix cache, CoW
inside a draft window, eviction and deadline expiry mid-draft, transient
device loss and SIGKILL journal replay — outputs stay TOKEN-IDENTICAL to
``--speculative off`` and ``CausalLm.generate``.  See
tests/test_speculative.py's docstring for the geometries."""

import dataclasses

import numpy as np
import pytest

from _jitted import generate_ref as _generate_ref
from _speculative_common import ROPE, SERVE, TINY, _pair, _shared_trace
from mpi_tensorflow_tpu.models import gpt
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, ReplayJournal,
                                        Request, ServeConfig,
                                        run_with_replay)


# ----------------------------------------- prefix cache / CoW / stress

class TestSpeculativeWithPrefixCache:
    def test_shared_prefix_cache_on_token_identical_with_hits(self):
        """Prefix cache AND speculation on together: trie hits land,
        drafts verify, outputs equal the everything-off engine's."""
        model, params, off, spec = _pair(
            ROPE, speculative="ngram", draft_k=4, prefix_cache="on")
        rng = np.random.default_rng(4)
        reqs = _shared_trace(rng, n=5, prefix=12, budget=24)
        want = off.run([dataclasses.replace(r) for r in reqs])
        got = spec.run([dataclasses.replace(r) for r in reqs])
        assert got["outputs"] == want["outputs"]
        assert got["prefix"]["hit_tokens"] > 0
        assert got["speculation"]["accepted_tokens"] > 0

    def test_cow_on_shared_block_inside_draft_window(self):
        """Identical exact-block-multiple prompts, one slot, drafter ==
        target: the verify window's FIRST write (the shared-final-block
        recompute) plus its accepted draft writes span a shared block —
        the CoW guard must privatize the whole range before the
        dispatch, and the donor's cached content must survive."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, max_slots=1,
                                    prefix_cache="on",
                                    speculative="draft-model", draft_k=4)
        spec = PagedDecodeEngine(model, params, serve,
                                 draft_model=model, draft_params=params)
        rng = np.random.default_rng(21)
        prompt = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        assert len(prompt) % serve.block_size == 0
        budgets = [6, 4, 2]
        res = spec.run([Request(i, list(prompt), n, arrival=0.0)
                        for i, n in enumerate(budgets)])
        assert res["prefix"]["cow_copies"] >= 1, \
            "the shared-final-block recompute must trigger CoW"
        assert res["speculation"]["accepted_tokens"] > 0, \
            "the draft window was meant to be live through the CoW"
        want = _generate_ref(model, params, prompt, max(budgets))
        for i, n in enumerate(budgets):
            assert res["outputs"][i] == want[:n], \
                f"request {i} diverged after CoW inside a draft window"

    def test_eviction_mid_draft_restarts_exact(self):
        """A tight pool preempts a sequence while speculation is live:
        restart-from-scratch replay (and the drafter's stale per-request
        state) must not perturb a single token."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = ServeConfig(num_blocks=9, block_size=2, max_slots=2,
                            max_seq_len=12, prefill_chunk=2,
                            speculative="draft-model", draft_k=3)
        engine = PagedDecodeEngine(model, params, serve,
                                   draft_model=model, draft_params=params)
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
        engine.drafter.check_quiescent()

    def test_deadline_expiry_mid_draft_is_terminal_not_fatal(self):
        """A deadline sweep that kills a sequence between draft windows
        frees its engine blocks AND its drafter state; survivors keep
        their exact streams."""
        import jax

        model = gpt.CausalLm(TINY)
        params = model.init(jax.random.key(0))
        serve = dataclasses.replace(SERVE, speculative="draft-model",
                                    draft_k=3)
        engine = PagedDecodeEngine(model, params, serve,
                                   draft_model=model, draft_params=params)
        clock = {"t": 0.0}

        def fake_time():
            clock["t"] += 0.01
            return clock["t"]

        res = engine.run(
            [Request(0, [1, 2, 3], 20, arrival=0.0, deadline=0.05),
             Request(1, [4, 5], 3, arrival=0.0)], time_fn=fake_time)
        assert res["statuses"][0] == "deadline_exceeded"
        assert res["statuses"][1] == "ok"
        assert res["outputs"][1] == _generate_ref(model, params, [4, 5], 3)
        assert engine.allocator.num_used == 0
        engine.drafter.check_quiescent()


# ---------------------------------------------------- replay / recovery

class TestSpeculativeReplay:
    def _flaky_verify_factory(self, model, params, serve, fail_on_call=3,
                              times=1, **eng_kw):
        state = {"faults_left": times}

        def make_engine():
            engine = PagedDecodeEngine(model, params, serve, **eng_kw)
            if state["faults_left"] > 0:
                state["faults_left"] -= 1
                orig, calls = engine._verify_fn, {"n": 0}

                def flaky(*a, **k):
                    calls["n"] += 1
                    if calls["n"] == fail_on_call:
                        raise RuntimeError(
                            "UNAVAILABLE: simulated device loss")
                    return orig(*a, **k)

                engine._verify_fn = flaky
            return engine

        return make_engine

    def test_transient_fault_replay_token_identical(self):
        """Mid-verify device loss -> engine (and draft pool) rebuilt ->
        replay: merged outputs equal an unfaulted OFF-mode run's, and
        the merged speculation block spans both attempts."""
        import jax

        model = gpt.CausalLm(ROPE)
        params = model.init(jax.random.key(1))
        rng = np.random.default_rng(11)
        reqs = _shared_trace(rng, n=4, budget=20)
        want = PagedDecodeEngine(model, params, SERVE).run(
            [dataclasses.replace(r) for r in reqs])
        serve = dataclasses.replace(SERVE, speculative="ngram", draft_k=4)
        res = run_with_replay(
            self._flaky_verify_factory(model, params, serve),
            [dataclasses.replace(r) for r in reqs])
        assert res["replays"] == 1
        assert res["outputs"] == want["outputs"]
        assert res["speculation"]["enabled"]
        assert res["speculation"]["verify_forwards"] > 0

    def test_sigkill_journal_holds_accepted_tokens_only(self, tmp_path):
        """Simulated SIGKILL mid-run: the journal on disk must contain,
        for every live request, a strict PREFIX of the true greedy
        stream — accepted tokens only, never a rejected draft — and a
        cold resume completes token-identically."""
        import jax

        model = gpt.CausalLm(ROPE)
        params = model.init(jax.random.key(1))
        rng = np.random.default_rng(12)
        reqs = _shared_trace(rng, n=4, budget=20)
        want = PagedDecodeEngine(model, params, SERVE).run(
            [dataclasses.replace(r) for r in reqs])
        path = str(tmp_path / "journal.jsonl")
        serve = dataclasses.replace(SERVE, speculative="ngram", draft_k=4)

        factory = self._flaky_verify_factory(model, params, serve,
                                             fail_on_call=4)
        with pytest.raises(RuntimeError):
            factory().run([dataclasses.replace(r) for r in reqs],
                          journal=ReplayJournal(path))

        mid = ReplayJournal(path)
        assert any(ent.toks for ent in mid.entries.values()), \
            "the crash was meant to land mid-stream"
        for rid, ent in mid.entries.items():
            n = len(ent.toks)
            assert ent.toks == want["outputs"][rid][:n], (
                f"request {rid}: journal holds non-accepted tokens "
                f"{ent.toks} vs true stream {want['outputs'][rid]}")
        mid.close()

        res = run_with_replay(
            lambda: PagedDecodeEngine(model, params, serve),
            [dataclasses.replace(r) for r in reqs], journal_path=path)
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())
