"""Data-parallel replica router (serving/router).

Pins: placement can never change tokens (greedy determinism — routed
outputs equal a single-engine run), session affinity sticks, load-aware
placement steers new sessions away from loaded replicas, per-replica
metrics carry the scheduler's health signals, and the threaded mode
produces the same outputs as the deterministic sequential mode.

Fleet fault tolerance (ISSUE 9): killing a replica mid-decode (the
FaultPlan injection seam) migrates its live + queued work to survivors
by journal-prefix replay, and the fleet's greedy outputs stay
TOKEN-IDENTICAL to an unfaulted run — with every request reaching
exactly one terminal status, ``check_quiescent`` green on survivors
(asserted inside ``router.run``), the circuit breaker ejecting /
probing / readmitting on capped exponential backoff, permanent faults
staying dead, fleet-wide SIGTERM drain, and the sticky-session map
re-homed on ejection and LRU-bounded.  All determinism pins run
``parallel=False`` (this box has 1 usable core — ROADMAP); the
threaded-mode fault test is behavior-only (same outputs), not a
wall-clock claim.
"""

import dataclasses
import functools

import numpy as np
import pytest

from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (FaultPlan, PagedDecodeEngine,
                                        ReplicaFault, ReplicaRouter,
                                        Request, ServeConfig)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
BASE = dict(num_blocks=40, block_size=4, max_slots=3, max_seq_len=24,
            prefill_chunk=8)


def _model(seed=0):
    import jax

    model = gpt.CausalLm(TINY)
    return model, model.init(jax.random.key(seed))


def _trace(rng, n, sessions=None, budget_hi=8):
    prompts = [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
               for s in rng.integers(3, 13, n)]
    budgets = [int(b) for b in rng.integers(1, budget_hi + 1, n)]
    return [Request(i, p, b,
                    session=(sessions[i] if sessions else None))
            for i, (p, b) in enumerate(zip(prompts, budgets))]


def _fixed_trace(n=6, prompt_len=6, budget=6, sessions=True):
    """Deterministic burst: same-length prompts, same budgets, sessions
    alternating over 2 replicas — so a fault at a fixed tick always
    lands mid-decode with live AND queued work on the victim."""
    rng = np.random.default_rng(42)
    return [Request(i,
                    list(map(int, rng.integers(0, TINY.vocab_size,
                                               prompt_len))),
                    budget, session=(i % 2 if sessions else None))
            for i in range(n)]


class TestPlacement:
    def test_session_affinity_sticks(self):
        model, params = _model()
        router = ReplicaRouter([PagedDecodeEngine(model, params,
                                                  ServeConfig(**BASE))
                                for _ in range(3)])
        rng = np.random.default_rng(1)
        reqs = _trace(rng, 9, sessions=[i % 3 for i in range(9)])
        res = router.run(reqs, parallel=False)
        pl = res["placements"]
        for s in range(3):
            reps = {pl[i] for i in range(9) if i % 3 == s}
            assert len(reps) == 1, \
                f"session {s} split across replicas {reps}"
        assert res["sticky_sessions"] == 3

    def test_load_aware_routing_avoids_loaded_replica(self):
        """With replica 0 already holding queued work, a sessionless
        request must place on the idle replica 1."""
        model, params = _model()
        engines = [PagedDecodeEngine(model, params, ServeConfig(**BASE))
                   for _ in range(2)]
        router = ReplicaRouter(engines)
        rng = np.random.default_rng(2)
        filler = _trace(rng, 4)
        for req in filler:
            engines[0].sched.submit(req)          # queue depth 4 on r0
        probe = Request(99, [1, 2, 3], 2)
        assert router.route(probe) == 1
        assert router.load_score(0) > router.load_score(1)

    def test_router_needs_at_least_one_engine(self):
        with pytest.raises(ValueError, match="1 engine"):
            ReplicaRouter([])


class TestRoutedServing:
    def _single_and_router(self, n_replicas=2, seed=3, n_req=8,
                           sessions=None):
        model, params = _model(seed)
        rng = np.random.default_rng(seed + 10)
        reqs = _trace(rng, n_req, sessions=sessions)
        single = PagedDecodeEngine(model, params, ServeConfig(**BASE))
        router = ReplicaRouter([PagedDecodeEngine(model, params,
                                                  ServeConfig(**BASE))
                                for _ in range(n_replicas)])
        return single, router, reqs

    def test_outputs_token_identical_to_single_engine(self):
        """Placement is invisible to content: the routed fleet emits
        exactly the single engine's streams (greedy determinism)."""
        single, router, reqs = self._single_and_router(
            sessions=[i % 3 for i in range(8)])
        want = single.run(list(reqs))["outputs"]
        got = router.run(list(reqs), parallel=False)["outputs"]
        assert got == want

    def test_threaded_mode_matches_sequential(self):
        single, router, reqs = self._single_and_router(seed=4)
        want = single.run(list(reqs))["outputs"]
        seq = router.run(list(reqs), parallel=False)["outputs"]
        router.reset()
        par = router.run(list(reqs), parallel=True)["outputs"]
        assert seq == want and par == want

    def test_per_replica_metrics_and_aggregates(self):
        _, router, reqs = self._single_and_router(seed=5)
        res = router.run(list(reqs), parallel=False)
        assert res["num_replicas"] == 2
        assert len(res["replicas"]) == 2
        for blk in res["replicas"]:
            for key in ("requests_routed", "tokens", "tokens_per_sec",
                        "queue_depth_peak", "pool_occupancy_peak",
                        "shed", "shed_rate", "evictions", "faults"):
                assert key in blk, f"replica block missing {key}"
        assert sum(b["requests_routed"] for b in res["replicas"]) == 8
        assert sum(b["tokens"] for b in res["replicas"]) == res["tokens"]
        assert res["tokens"] == sum(len(v)
                                    for v in res["outputs"].values())

    def test_reset_clears_placements_and_serves_again(self):
        _, router, reqs = self._single_and_router(seed=6)
        r1 = router.run(list(reqs), parallel=False)
        router.reset()
        assert router.placements == {} and router._sticky == {}
        r2 = router.run(list(reqs), parallel=False)
        assert r1["outputs"] == r2["outputs"]

    def test_run_restores_engine_terminal_hooks(self):
        """The router chains its bookkeeping behind each engine's
        terminal hook for the run's duration only — a later standalone
        ``engine.run`` must not touch dead router state."""
        model, params = _model(11)
        eng = PagedDecodeEngine(model, params, ServeConfig(**BASE))
        router = ReplicaRouter([eng])
        reqs = _fixed_trace(n=2, sessions=False)
        router.run(list(reqs), parallel=False)
        assert eng.sched.on_terminal == eng._on_terminal
        solo = eng.run(_fixed_trace(n=2, sessions=False))
        assert set(solo["statuses"].values()) == {"ok"}

    def test_replica_shed_and_deadline_policies_apply_per_replica(self):
        """A bounded queue on each replica sheds under a burst, and the
        shed shows up in that replica's metrics block — the router's
        admission signal."""
        model, params = _model(7)
        serve = ServeConfig(**{**BASE, "max_slots": 1},
                            queue_depth=1)
        router = ReplicaRouter([PagedDecodeEngine(model, params, serve)])
        rng = np.random.default_rng(8)
        reqs = _trace(rng, 6, budget_hi=4)       # burst at t=0, 1 slot,
        res = router.run(reqs, parallel=False)   # queue bound 1
        blk = res["replicas"][0]
        assert blk["shed"] == res["faults"]["shed"] > 0
        assert blk["shed_rate"] > 0
        statuses = set(res["statuses"].values())
        assert "shed" in statuses and "ok" in statuses


@functools.cache
def _single(seed, serve_overrides):
    """The unfaulted single-engine reference for ``_fleet``: one engine per
    (seed, serve) for the whole module — its compiled programs are the same
    for every test; ``_fleet`` resets its pools and scheduler.
    (``failover_backoff_ms`` is a fleet knob no single engine reads.)"""
    model, params = _model(seed)
    return PagedDecodeEngine(
        model, params, ServeConfig(**BASE, **dict(serve_overrides)))


def _fleet(n_replicas=2, seed=3, backoff_ms=1e6, make_engine=False,
           **serve_overrides):
    """A router over fresh replicas + the matching single-engine
    reference.  ``backoff_ms`` defaults huge so an ejected replica
    stays out for the whole run (the survivors-only determinism pin);
    readmission tests shrink it."""
    model, params = _model(seed)
    serve = ServeConfig(**BASE, failover_backoff_ms=backoff_ms,
                        **serve_overrides)
    single = _single(seed, tuple(sorted(serve_overrides.items())))
    single.reset()
    factory = ((lambda: PagedDecodeEngine(model, params, serve))
               if make_engine else None)
    router = ReplicaRouter([PagedDecodeEngine(model, params, serve)
                            for _ in range(n_replicas)],
                           make_engine=factory)
    return single, router


class TestFailover:
    """THE fleet determinism contract: killing a replica mid-decode
    migrates its work and changes no tokens."""

    def test_transient_fault_outputs_token_identical(self):
        single, router = _fleet()
        reqs = _fixed_trace()
        want = single.run(list(reqs))["outputs"]
        plan = FaultPlan([ReplicaFault(0, at_step=4)])
        res = router.run(list(reqs), parallel=False, fault_plan=plan)
        assert plan.fired, "injected fault never fired"
        assert res["outputs"] == want, \
            "failover changed greedy outputs (determinism contract)"
        # every request reaches exactly ONE terminal status, all ok
        assert sorted(res["statuses"]) == [r.id for r in reqs]
        assert set(res["statuses"].values()) == {"ok"}
        ff = res["fleet_faults"]
        assert ff["failovers"] == 1 and ff["ejections"] == 1
        assert ff["migrated_requests"] >= 1
        assert ff["replay_tokens"] > 0, \
            "victim had live decoded work; replay must re-prefill it"
        # backoff is huge: the victim stays ejected, survivors finish
        assert res["health"][0] == "ejected"
        assert res["health"][1] == "healthy"
        # quiescence on the survivor (run() asserts it; re-assert here)
        router.engines[1].sched.check_quiescent()

    def test_permanent_fault_stays_dead(self):
        single, router = _fleet(backoff_ms=1.0)   # tiny backoff: a
        reqs = _fixed_trace()                     # DEAD replica must
        want = single.run(list(reqs))["outputs"]  # still never return
        plan = FaultPlan([ReplicaFault(0, at_step=4, kind="permanent")])
        res = router.run(list(reqs), parallel=False, fault_plan=plan)
        assert res["outputs"] == want
        assert res["health"][0] == "dead"
        assert res["fleet_faults"]["readmissions"] == 0
        assert set(res["statuses"].values()) == {"ok"}

    def test_transient_probe_readmission(self):
        """With a tiny backoff the ejected replica is rebuilt, probed,
        and readmitted — and the outputs still match."""
        single, router = _fleet(backoff_ms=1.0)
        reqs = _fixed_trace(n=8, budget=8)
        want = single.run(list(reqs))["outputs"]
        plan = FaultPlan([ReplicaFault(0, at_step=3)])
        res = router.run(list(reqs), parallel=False, fault_plan=plan)
        assert res["outputs"] == want
        ff = res["fleet_faults"]
        assert ff["failovers"] == 1
        assert ff["readmissions"] == 1, \
            "backoff elapsed mid-run; the probe must readmit"
        assert res["health"][0] == "healthy"
        # readmission breaks the fault streak: the next isolated fault
        # must pay base backoff, not an escalated one
        assert router.health[0].faults == 0

    def test_double_fault_after_readmission_no_duplicate_migration(self):
        """A readmitted replica faulting a SECOND time must migrate only
        its OWN current work — requests migrated at the first fault
        (still live on a survivor) must not be re-migrated off the
        donor's stale journal entries, or the duplicate replay would
        overwrite the live stream."""
        single, router = _fleet(backoff_ms=1.0)
        reqs = _fixed_trace(n=8, budget=10)
        want = single.run(list(reqs))["outputs"]
        plan = FaultPlan([ReplicaFault(0, at_step=3),
                          ReplicaFault(0, at_step=16)])
        res = router.run(list(reqs), parallel=False, fault_plan=plan)
        assert len(plan.fired) == 2, "both faults must fire"
        assert res["outputs"] == want, \
            "double fault corrupted a migrated stream"
        assert sorted(res["statuses"]) == [r.id for r in reqs]
        assert set(res["statuses"].values()) == {"ok"}
        assert res["fleet_faults"]["failovers"] == 2

    def test_donor_journal_live_entries_cleared_on_migration(self):
        """The direct pin of the double-fault hazard: after failover,
        the donor's journal must hold NO live entries — a re-migration
        off a stale entry would duplicate a request already live on a
        survivor."""
        _, router = _fleet()       # huge backoff: donor stays ejected
        res = router.run(_fixed_trace(), parallel=False,
                         fault_plan=FaultPlan(
                             [ReplicaFault(0, at_step=4)]))
        assert res["fleet_faults"]["migrated_requests"] >= 1
        stale = [rid for rid, ent in router._journals[0].entries.items()
                 if ent.status is None]
        assert stale == [], \
            f"migrated requests linger live in the donor journal: {stale}"

    def test_all_replicas_dead_raises(self):
        """A fleet with every replica permanently dead re-raises the
        last error instead of spinning forever."""
        _, router = _fleet(n_replicas=1)
        plan = FaultPlan([ReplicaFault(0, at_step=2, kind="permanent")])
        with pytest.raises(RuntimeError, match="FAILED_PRECONDITION"):
            router.run(_fixed_trace(sessions=False), parallel=False,
                       fault_plan=plan)

    def test_single_replica_transient_self_recovers(self):
        """n=1 + transient fault: the lone replica is its own failover
        target after backoff — the fleet supervisor subsumes the
        single-engine replay story."""
        single, router = _fleet(n_replicas=1, backoff_ms=1.0)
        reqs = _fixed_trace(sessions=False)
        want = single.run(list(reqs))["outputs"]
        plan = FaultPlan([ReplicaFault(0, at_step=4)])
        res = router.run(list(reqs), parallel=False, fault_plan=plan)
        assert res["outputs"] == want
        assert res["fleet_faults"]["migrated_requests"] >= 1

    def test_threaded_failover_matches_sequential(self):
        """Behavior-only threaded pin (1-core box: no wall-clock
        claim): a mid-run replica fault under parallel=True still
        yields the unfaulted outputs."""
        single, router = _fleet(backoff_ms=1.0)
        reqs = _fixed_trace()
        want = single.run(list(reqs))["outputs"]
        plan = FaultPlan([ReplicaFault(0, at_step=4)])
        res = router.run(list(reqs), parallel=True, fault_plan=plan)
        assert res["outputs"] == want
        assert set(res["statuses"].values()) == {"ok"}

    def test_zero_recompile_on_survivors_across_failover(self):
        """Migrated prefills re-enter through the existing pow2 chunk
        buckets and migrated decodes land in already-warm (slot, table)
        buckets: replaying the SAME faulted scenario after a reset adds
        no compile cache entries on any replica."""
        _, router = _fleet()
        reqs = _fixed_trace()
        router.run(list(reqs), parallel=False,
                   fault_plan=FaultPlan([ReplicaFault(0, at_step=4)]))
        warm = router.compile_counts()
        router.reset()
        res = router.run(list(reqs), parallel=False,
                         fault_plan=FaultPlan(
                             [ReplicaFault(0, at_step=4)]))
        steady = router.compile_counts()
        if all(v is not None for v in {**warm, **steady}.values()):
            assert warm == steady, (warm, steady)
        assert res["fleet_faults"]["failovers"] == 1


class TestCircuitBreaker:
    def test_backoff_doubles_and_caps(self):
        """Consecutive transient faults double the probe backoff from
        the ServeConfig base, capped at 64x; a permanent fault pins the
        replica dead."""
        _, router = _fleet(backoff_ms=100.0)
        router.run([], parallel=False)        # arm run state, no work
        err = RuntimeError("UNAVAILABLE: synthetic")
        seen = []
        for _ in range(9):
            router.health[0].state = "healthy"   # re-arm for the next
            router._loops[0] = None              # synthetic fault
            router._failover(0, err, now=0.0)
            seen.append(router.health[0].backoff_s)
            assert router.health[0].state == "ejected"
        assert seen[0] == pytest.approx(0.1)
        assert seen[1] == pytest.approx(0.2)
        assert seen[2] == pytest.approx(0.4)
        assert seen[-1] == pytest.approx(0.1 * 64), "cap is 64x base"
        assert seen[-1] == seen[-2], "capped: no further growth"
        router._failover(0, RuntimeError("INVALID_ARGUMENT: bug"),
                         now=0.0)
        assert router.health[0].state == "dead"

    def test_backoff_policy_flows_from_serve_config(self):
        _, router = _fleet(backoff_ms=250.0)
        assert router.backoff_base_s == pytest.approx(0.25)
        assert router.backoff_cap_s == pytest.approx(0.25 * 64)

    def test_bad_backoff_rejected_at_serve_config(self):
        with pytest.raises(ValueError, match="fault-tolerance"):
            ServeConfig(**BASE, failover_backoff_ms=0.0)


class TestFleetDrain:
    class _FlipGuard:
        """should_stop flips True after ``after`` polls — a SIGTERM
        landing mid-trace without real signals."""

        def __init__(self, after):
            self.polls, self.after = 0, after

        @property
        def should_stop(self):
            self.polls += 1
            return self.polls > self.after

    def test_sigterm_drains_whole_fleet_one_terminal_each(self):
        """Fleet drain: admission stops, queued work sheds, the zero
        budget cuts in-flight work as ``drained`` — and EVERY request
        still leaves with exactly one terminal status."""
        _, router = _fleet(drain_ms=0.0)
        reqs = _fixed_trace(n=10, budget=12)
        res = router.run(list(reqs), parallel=False,
                         guard=self._FlipGuard(after=6))
        assert res["drain"]["requested"]
        assert sorted(res["statuses"]) == [r.id for r in reqs], \
            "every request must reach exactly one terminal status"
        vals = set(res["statuses"].values())
        assert vals <= {"ok", "shed", "drained"}, vals
        assert "shed" in vals or "drained" in vals, \
            "drain landed too late to exercise anything"
        assert res["drain"]["cut"] + res["drain"]["shed"] \
            + res["drain"]["drained"] > 0
        for i in (0, 1):
            router.engines[i].sched.check_quiescent()

    def test_drain_after_failover_still_quiesces(self):
        """SIGTERM landing after a mid-run failover: the survivor
        drains, terminal statuses stay exactly-once, and quiescence
        holds on the surviving replica."""
        _, router = _fleet(drain_ms=0.0)
        reqs = _fixed_trace(n=8, budget=10)
        plan = FaultPlan([ReplicaFault(0, at_step=3)])
        res = router.run(list(reqs), parallel=False, fault_plan=plan,
                         guard=self._FlipGuard(after=14))
        assert res["fleet_faults"]["failovers"] == 1
        assert sorted(res["statuses"]) == [r.id for r in reqs]
        assert set(res["statuses"].values()) <= {"ok", "shed", "drained"}
        router.engines[1].sched.check_quiescent()


class TestStickyHygiene:
    def test_sticky_rehomed_on_ejection(self):
        """Ejecting a replica forgets its session placements; the
        sessions re-home to a survivor on their next request."""
        _, router = _fleet()
        reqs = _fixed_trace(n=8, budget=8)
        res = router.run(list(reqs), parallel=False,
                         fault_plan=FaultPlan(
                             [ReplicaFault(0, at_step=4)]))
        assert res["fleet_faults"]["sticky_rehomed"] >= 1
        assert router.stats()["sticky_rehomed"] >= 1
        # whatever affinity remains points at routable replicas only
        for sess, rep in router._sticky.items():
            assert router.health[rep].state in ("healthy", "probing")
        assert set(res["statuses"].values()) == {"ok"}

    def test_sticky_map_lru_bounded(self):
        """Terminal sessions must not pin affinity entries forever:
        past ``max_sticky`` the LRU sessions with no live requests are
        evicted (counter in router.stats())."""
        model, params = _model(9)
        serve = ServeConfig(**BASE)
        router = ReplicaRouter([PagedDecodeEngine(model, params, serve)
                                for _ in range(2)], max_sticky=3)
        rng = np.random.default_rng(10)
        reqs = _trace(rng, 9, sessions=[f"s{i}" for i in range(9)])
        res = router.run(reqs, parallel=False)
        assert set(res["statuses"].values()) == {"ok"}
        st = router.stats()
        assert st["sticky_sessions"] <= 3
        assert st["sticky_evicted"] > 0
        assert st["sticky_live_sessions"] == 0

    def test_fleet_faults_block_shape(self):
        """fleet_faults is the canonical metrics_writer block: every
        key present, zero-valued on a clean run."""
        from mpi_tensorflow_tpu.utils.metrics_writer import \
            FLEET_FAULT_KEYS

        _, router = _fleet()
        res = router.run(_fixed_trace(n=2), parallel=False)
        assert set(res["fleet_faults"]) == set(FLEET_FAULT_KEYS)
        assert all(v == 0 for v in res["fleet_faults"].values())


class TestFleetReplayHelpers:
    """Host-side pins of the recovery fleet helpers the failover and
    the entry point's resume path are built on."""

    def test_replay_one_no_double_embed_for_replayed_request(self):
        """A fault during a journal-RESUMED run re-roots from an entry
        whose prompt already embeds the first replay's prefix; the
        re-rooting must not embed it twice (the resume-then-fault
        corruption)."""
        from mpi_tensorflow_tpu.serving.recovery import (JournalEntry,
                                                         replay_one)

        orig_prompt, pre, toks = [1, 2, 3], [10, 11], [20]
        # the entry a RESUMED submit records: prompt embeds pre
        ent = JournalEntry(prompt=orig_prompt + pre, max_new_tokens=4,
                           arrival=0.0, pre=list(pre), toks=list(toks))
        # the request object the resumed run carries is the re-rooted
        # one, not the original
        resumed = Request(7, orig_prompt + pre, 4, replayed=True)
        rep, done = replay_one(ent, resumed)
        assert done == pre + toks
        assert rep.prompt == orig_prompt + pre + toks, \
            "delivered prefix double-embedded on resume-then-fault"
        assert rep.max_new_tokens == 3          # 6 total - 3 delivered
        # and the original-request case yields the identical re-rooting
        rep2, _ = replay_one(ent, Request(7, list(orig_prompt), 6))
        assert rep2.prompt == rep.prompt
        assert rep2.max_new_tokens == rep.max_new_tokens

    def test_fleet_replay_skips_request_terminal_elsewhere(self):
        """A terminal status recorded entry-less in one journal (e.g.
        shed at drain after migration off a dead donor) must beat the
        donor's stale on-disk live entry: the request is NOT replayed —
        exactly one terminal status across runs."""
        from mpi_tensorflow_tpu.serving import ReplayJournal
        from mpi_tensorflow_tpu.serving.recovery import \
            fleet_replay_requests

        reqs = [Request(1, [1, 2, 3], 4), Request(2, [4, 5, 6], 4)]
        donor, survivor = ReplayJournal(), ReplayJournal()
        donor.record_submit(reqs[0])
        donor.record_token(1, 9)                # live entry, no end
        survivor.record_end(reqs[0], "shed")    # entry-less terminal
        todo, pre = fleet_replay_requests([donor, survivor], reqs)
        assert [r.id for r in todo] == [2], \
            "request with a fleet-wide terminal status was resurrected"
        assert 1 not in pre
