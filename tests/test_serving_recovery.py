"""Serving crash recovery: replay journal + transient-failure supervision.

The acceptance pin: under an injected transient decode failure the
engine is rebuilt and every surviving request's output is
TOKEN-IDENTICAL to an unfaulted run — greedy decode is deterministic,
so replaying ``prompt + generated_prefix`` through chunked prefill
continues the exact stream the lost pools were mid-way through.  The
SIGKILL-a-real-process variant lives in tests/test_fault_injection.py;
these are the in-process units.
"""

import dataclasses

import numpy as np
import pytest

from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, ReplayJournal,
                                        Request, ServeConfig,
                                        run_with_replay)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
SERVE = ServeConfig(num_blocks=40, block_size=4, max_slots=3,
                    max_seq_len=24, prefill_chunk=8)
PSERVE = dataclasses.replace(SERVE, prefix_cache="on")


@pytest.fixture(scope="module")
def model_params():
    import jax

    model = gpt.CausalLm(TINY)
    return model, model.init(jax.random.key(1))


def _trace(n=5, seed=2, lo=3, hi=13, budget_hi=9):
    rng = np.random.default_rng(seed)
    prompts = [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
               for s in rng.integers(lo, hi + 1, n)]
    budgets = [int(b) for b in rng.integers(2, budget_hi, n)]
    return [Request(i, p, b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]


def _shared_trace(n=6, seed=3, prefix=8, hi=6, budget_hi=7):
    """Shared-prefix variant: one common system prompt (an exact block
    multiple of PSERVE's block_size, so the fully-cached CoW path is in
    play) ahead of each unique tail."""
    rng = np.random.default_rng(seed)
    shared = list(map(int, rng.integers(0, TINY.vocab_size, prefix)))
    prompts = [shared + list(map(int, rng.integers(
        0, TINY.vocab_size, int(s)))) for s in rng.integers(1, hi + 1, n)]
    budgets = [int(b) for b in rng.integers(2, budget_hi, n)]
    return [Request(i, p, b)
            for i, (p, b) in enumerate(zip(prompts, budgets))]


# ------------------------------------------------------------- journal

@pytest.mark.quick
class TestReplayJournal:
    def test_roundtrip_through_disk(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = ReplayJournal(path)
        j.record_submit(Request(0, [1, 2, 3], 5, arrival=0.25))
        j.record_token(0, 7)
        j.record_token(0, 8)
        j.record_submit(Request(1, [4], 2))
        j.record_token(1, 9)
        j.record_token(1, 10)
        j.record_end(Request(1, [4], 2), "ok")
        j.close()

        j2 = ReplayJournal(path)
        assert j2.outputs() == {1: [9, 10]}
        live = j2.replay_requests([Request(0, [1, 2, 3], 5, arrival=0.25),
                                   Request(1, [4], 2)])
        assert len(live) == 1
        (r,) = live
        # prompt re-rooted at prompt+prefix, remaining budget, replayed
        # immediately (arrival 0 — the new process's clock restarts)
        assert (r.id, r.prompt, r.max_new_tokens, r.arrival) \
            == (0, [1, 2, 3, 7, 8], 3, 0.0)

    def test_eviction_voids_tokens_since_submit(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = ReplayJournal(path)
        j.record_submit(Request(0, [1, 2], 6))
        j.record_token(0, 5)
        j.record_evict(0)      # restart-from-scratch: 5 is regenerated
        j.close()
        live = ReplayJournal(path).replay_requests([Request(0, [1, 2], 6)])
        assert live[0].prompt == [1, 2] and live[0].max_new_tokens == 6

    def test_torn_final_line_ignored(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = ReplayJournal(path)
        j.record_submit(Request(0, [1], 3))
        j.record_token(0, 4)
        j.close()
        with open(path, "a") as f:
            f.write('{"kind": "tok", "id": 0, "t"')   # crash mid-write
        j2 = ReplayJournal(path)
        assert j2.entries[0].toks == [4]

    def test_replay_submit_pre_carries_delivered_prefix(self, tmp_path):
        """Second crash after a replay: the merged stream still
        reconstructs — the replay submit's ``pre`` anchors it."""
        path = str(tmp_path / "j.jsonl")
        orig = [Request(0, [1, 2], 6)]
        j = ReplayJournal(path)
        j.record_submit(orig[0])
        j.record_token(0, 5)
        j.record_token(0, 6)
        j.close()
        j2 = ReplayJournal(path)
        (r,) = j2.replay_requests(orig)
        assert r.prompt == [1, 2, 5, 6] and r.max_new_tokens == 4
        j2.record_submit(r)               # the replacement run admits it
        j2.record_token(0, 7)
        j2.close()
        j3 = ReplayJournal(path)          # and crashes again...
        (r2,) = j3.replay_requests(orig)
        assert r2.prompt == [1, 2, 5, 6, 7] and r2.max_new_tokens == 3
        j3.record_submit(r2)
        j3.record_token(0, 8)
        j3.record_token(0, 9)
        j3.record_token(0, 10)
        j3.record_end(orig[0], "ok")
        assert j3.outputs() == {0: [5, 6, 7, 8, 9, 10]}

    def test_replayed_requests_exempt_from_queue_shedding(self):
        """Recovered work passed admission control before the crash and
        carries delivered tokens — the bounded queue must not shed it on
        relaunch (that would orphan its prefix and break the
        token-identical recovery contract)."""
        from mpi_tensorflow_tpu.serving import BlockAllocator, Scheduler

        s = Scheduler(BlockAllocator(32), 1, 4, 4, queue_depth=1)
        j = ReplayJournal(None)
        for i in range(3):
            j.record_submit(Request(i, [1, 2], 4))
            j.record_token(i, 5 + i)
        reqs = j.replay_requests([Request(i, [1, 2], 4) for i in range(3)])
        assert all(r.replayed for r in reqs)
        for r in reqs:
            assert s.submit(r) is None, "replayed request was shed"
        # fresh work still gets the bounded-queue backpressure
        assert s.submit(Request(9, [1, 2], 4)).reason == "queue_full"

    def test_tok_records_precede_end_ok(self, model_params, tmp_path):
        """Durable ordering contract: a request's `end ok` record must
        come AFTER its final `tok` record — the reverse would let a
        crash in between replay a truncated stream as complete."""
        import json

        model, params = model_params
        path = str(tmp_path / "order.jsonl")
        engine = PagedDecodeEngine(model, params, SERVE)
        engine.run(_trace(), journal=ReplayJournal(path))
        last_tok, end_at = {}, {}
        for i, line in enumerate(open(path)):
            rec = json.loads(line)
            if rec["kind"] == "tok":
                last_tok[rec["id"]] = i
            elif rec["kind"] == "end" and rec["status"] == "ok":
                end_at[rec["id"]] = i
        assert end_at and set(end_at) <= set(last_tok)
        for rid, e in end_at.items():
            assert e > last_tok[rid], \
                f"request {rid}: end-ok at line {e} precedes its final tok"

    def test_memory_only_journal(self):
        j = ReplayJournal(None)
        j.record_submit(Request(0, [1], 2))
        j.record_token(0, 3)
        assert j.replay_requests([Request(0, [1], 2)])[0].prompt == [1, 3]


# ------------------------------------------------- replay determinism

class TestTransientReplay:
    def _flaky_factory(self, model, params, fail_on_call=4, times=1,
                       serve=SERVE):
        """Engine factory whose first ``times`` engines raise a
        transient device-loss error on their ``fail_on_call``-th decode
        dispatch — rebuilt engines run clean."""
        state = {"faults_left": times}

        def make_engine():
            engine = PagedDecodeEngine(model, params, serve)
            if state["faults_left"] > 0:
                state["faults_left"] -= 1
                orig, calls = engine._decode_fn, {"n": 0}

                def flaky(*a, **k):
                    calls["n"] += 1
                    if calls["n"] == fail_on_call:
                        raise RuntimeError(
                            "UNAVAILABLE: simulated device loss")
                    return orig(*a, **k)

                engine._decode_fn = flaky
            return engine

        return make_engine

    def test_outputs_token_identical_after_mid_decode_fault(
            self, model_params):
        """THE acceptance pin (in-process form): transient decode
        failure -> engine rebuilt -> replay -> outputs exactly match an
        unfaulted run's."""
        model, params = model_params
        want = PagedDecodeEngine(model, params, SERVE).run(_trace())
        res = run_with_replay(
            self._flaky_factory(model, params), _trace())
        assert res["replays"] == 1
        assert res["faults"]["replays"] == 1
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())

    def test_fault_with_a_dispatch_unread_replays_whole(
            self, model_params, tmp_path):
        """The engine looks one dispatch ahead, so a fault at a decode
        dispatch finds the previous one's tokens unread: they were
        computed and are in no journal.  The replay regenerates them —
        the journal holds delivered tokens only, tok before end — and
        the streams come out whole."""
        import json

        model, params = model_params
        want = PagedDecodeEngine(model, params, SERVE).run(_trace())
        seen = {}
        inner = self._flaky_factory(model, params)

        def make_engine():
            engine = inner()
            if not seen:
                flaky = engine._decode_fn

                def watched(*a, **k):
                    try:
                        return flaky(*a, **k)
                    except RuntimeError:
                        seen["unread"] = sum(
                            len(rows) for _nxt, rows, _rec
                            in engine._unread)
                        raise
                engine._decode_fn = watched
                seen["engine"] = engine
            return engine

        path = str(tmp_path / "unread.jsonl")
        res = run_with_replay(make_engine, _trace(), journal_path=path)
        assert res["replays"] == 1 and seen["unread"] > 0
        assert res["outputs"] == want["outputs"]
        toks, ended = {}, set()
        for line in open(path):
            rec = json.loads(line)
            if rec["kind"] == "tok":
                assert rec["id"] not in ended    # tok-then-end
                toks[rec["id"]] = toks.get(rec["id"], 0) + 1
            elif rec["kind"] == "end":
                ended.add(rec["id"])
        assert ended == set(want["outputs"])

    def test_repeated_faults_within_budget_still_identical(
            self, model_params):
        model, params = model_params
        want = PagedDecodeEngine(model, params, SERVE).run(_trace())
        res = run_with_replay(
            self._flaky_factory(model, params, fail_on_call=3, times=2),
            _trace(), max_restarts=3)
        assert res["replays"] == 2
        assert res["outputs"] == want["outputs"]

    def test_nontransient_error_raises_immediately(self, model_params):
        """A deterministic bug must NOT be replayed: status-code-first
        classification (train/elastic.is_transient) decides."""
        model, params = model_params

        def make_engine():
            engine = PagedDecodeEngine(model, params, SERVE)

            def broken(*a, **k):
                raise RuntimeError("INVALID_ARGUMENT: shape mismatch")

            engine._decode_fn = broken
            return engine

        with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
            run_with_replay(make_engine, _trace())

    def test_restart_budget_reraises_original(self, model_params):
        model, params = model_params
        res_factory = self._flaky_factory(model, params, fail_on_call=2,
                                          times=99)
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            run_with_replay(res_factory, _trace(), max_restarts=2)

    def test_durable_journal_survives_process_boundary(
            self, model_params, tmp_path):
        """Simulated SIGKILL: run half a trace with a journaling engine,
        throw everything away but the journal FILE, then finish from a
        cold start — merged outputs identical to an unfaulted run."""
        model, params = model_params
        path = str(tmp_path / "journal.jsonl")
        want = PagedDecodeEngine(model, params, SERVE).run(_trace())

        # "process 1": dies on its 4th decode dispatch, journal on disk
        factory = self._flaky_factory(model, params)
        with pytest.raises(RuntimeError):
            engine = factory()
            engine.run(_trace(), journal=ReplayJournal(path))

        # "process 2": fresh everything, resumes from the journal file
        res = run_with_replay(
            lambda: PagedDecodeEngine(model, params, SERVE), _trace(),
            journal_path=path)
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())


# -------------------------------------------- prefix cache x replay

class TestPrefixCacheReplay:
    """Journal compatibility for the radix prefix cache: the trie
    indexes device-pool content, so it dies with the engine and is
    rebuilt by the replayed prefills — delivered streams must stay
    token-identical to an unfaulted CACHE-OFF run (the strongest form
    of the determinism contract)."""

    _flaky_factory = TestTransientReplay._flaky_factory

    def test_replay_after_mid_decode_fault_token_identical(
            self, model_params):
        model, params = model_params
        want = PagedDecodeEngine(model, params, SERVE).run(_shared_trace())
        res = run_with_replay(
            self._flaky_factory(model, params, serve=PSERVE),
            _shared_trace())
        assert res["replays"] == 1
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())
        # the rebuilt trie re-served shared prefixes during the replay
        assert res["prefix"]["enabled"]
        assert res["prefix"]["hit_tokens"] > 0

    def test_durable_journal_with_prefix_cache_survives_sigkill(
            self, model_params, tmp_path):
        """THE satellite pin: a journaled run with the prefix cache on
        survives a simulated SIGKILL (only the journal file persists)
        and the merged streams equal an unfaulted cache-off run's —
        replayed ``prompt + prefix`` submissions rebuild and re-hit the
        trie without perturbing a single token."""
        model, params = model_params
        path = str(tmp_path / "journal.jsonl")
        want = PagedDecodeEngine(model, params, SERVE).run(_shared_trace())

        factory = self._flaky_factory(model, params, serve=PSERVE)
        with pytest.raises(RuntimeError):
            factory().run(_shared_trace(), journal=ReplayJournal(path))

        res = run_with_replay(
            lambda: PagedDecodeEngine(model, params, PSERVE),
            _shared_trace(), journal_path=path)
        assert res["outputs"] == want["outputs"]
        assert all(s == "ok" for s in res["statuses"].values())

    def test_replayed_prompts_re_root_through_the_trie(self, model_params):
        """A replayed request's prompt embeds its delivered prefix; the
        fresh engine's prefill of that concatenation both rebuilds the
        trie and (for requests sharing the original system prompt)
        re-shares blocks in the NEW pool — outputs exact either way."""
        model, params = model_params
        want = PagedDecodeEngine(model, params, SERVE).run(
            _shared_trace(prefix=12))
        res = run_with_replay(
            self._flaky_factory(model, params, fail_on_call=2, times=2,
                                serve=PSERVE),
            _shared_trace(prefix=12), max_restarts=3)
        assert res["replays"] == 2
        assert res["outputs"] == want["outputs"]
