"""Trace-driven load generation (serving/loadgen) + the autoscale
advisor (serving/autoscale): spec validation, the byte-identity pin
against the historical inline generator, arrival-process statistics
at a fixed seed, heavy-tail bounds, tenant mixes/SLOs/sessions, the
per-request goodput join, and ScaleAdvisor hysteresis/cooldown.

All host-side (no jax dispatch): the whole file rides the quick tier.
"""

import dataclasses as dc

import numpy as np
import pytest

from mpi_tensorflow_tpu.serving import autoscale, loadgen


def legacy_inline_trace(num_requests=24, rate_rps=4.0, prompt_max=32,
                        output_max=128, vocab=32000, prefix_tokens=0,
                        seed=0):
    """The pre-loadgen inline generator, verbatim —
    THE reference the refactor must replay byte-for-byte (same rng,
    same draw order, prefix drawn only when non-zero)."""
    rng = np.random.default_rng(seed)
    p_lo, o_lo = min(8, prompt_max), min(8, output_max)
    shared = (list(map(int, rng.integers(0, vocab, prefix_tokens)))
              if prefix_tokens else [])
    prompts = [shared + list(map(int, rng.integers(0, vocab, int(n))))
               for n in rng.integers(p_lo, prompt_max + 1, num_requests)]
    outputs = [int(n) for n in rng.integers(o_lo, output_max + 1,
                                            num_requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, num_requests))
    arrivals[0] = 0.0
    return prompts, outputs, arrivals


@pytest.mark.quick
class TestWorkloadSpec:
    def test_defaults_are_the_historical_trace(self):
        spec = loadgen.WorkloadSpec()
        assert spec.workload == "poisson"
        assert spec.length_dist == "uniform"
        assert spec.prefix_tokens == 0 and spec.slo_ms is None
        assert spec.tenants == () and spec.session_len == 1

    @pytest.mark.parametrize("kwargs,match", [
        (dict(workload="sinusoidal"), "workload must be"),
        (dict(num_requests=0), "serving trace needs"),
        (dict(prompt_max=0), "serving trace needs"),
        (dict(output_max=-1), "serving trace needs"),
        (dict(rate_rps=0.0), "arrival rate"),
        (dict(vocab_size=0), "vocab_size"),
        (dict(prefix_tokens=-1), "prefix_tokens must be"),
        (dict(length_dist="pareto"), "length_dist"),
        (dict(slo_ms=0.0), "slo_ms must be"),
        (dict(slo_ms=-5.0), "slo_ms must be"),
        (dict(burst_on_s=0.0), "dwell"),
        (dict(burst_boost=0.5), "burst_boost"),
        (dict(diurnal_period_s=0.0), "diurnal_period_s"),
        (dict(diurnal_floor=0.0), "diurnal_floor"),
        (dict(diurnal_floor=1.5), "diurnal_floor"),
        (dict(session_len=0), "session_len"),
    ])
    def test_spec_validation(self, kwargs, match):
        with pytest.raises(ValueError, match=match):
            loadgen.WorkloadSpec(**kwargs)

    def test_tenants_only_under_multi_tenant(self):
        t = loadgen.TenantClass("a", share=1.0)
        with pytest.raises(ValueError, match="multi-tenant"):
            loadgen.WorkloadSpec(workload="poisson", tenants=(t,))

    @pytest.mark.parametrize("kwargs,match", [
        (dict(name=""), "non-empty name"),
        (dict(name="a", share=0.0), "share"),
        (dict(name="a", share=1.0, prompt_max=0), "prompt_max"),
        (dict(name="a", share=1.0, output_max=0), "output_max"),
        (dict(name="a", share=1.0, slo_ms=0.0), "slo_ms"),
        (dict(name="a", share=1.0, session_len=0), "session_len"),
    ])
    def test_tenant_validation(self, kwargs, match):
        kwargs.setdefault("share", 1.0)
        with pytest.raises(ValueError, match=match):
            loadgen.TenantClass(**kwargs)


@pytest.mark.quick
class TestBuildTrace:
    def test_default_trace_byte_identical_to_legacy(self):
        """THE refactor pin: a default (poisson/uniform) spec replays
        the historical inline generator exactly — prompts, output
        budgets, and arrival stamps all byte-for-byte."""
        t = loadgen.build_trace(loadgen.WorkloadSpec())
        lp, lo, la = legacy_inline_trace()
        assert t.prompts == lp
        assert t.outputs == lo
        assert np.array_equal(t.arrivals, la)
        # and no SLO/tenant/session metadata sneaks in
        assert t.slos_ms == [None] * 24
        assert t.sessions == [None] * 24
        assert t.tenants == ["default"] * 24

    def test_prefix_trace_byte_identical_to_legacy(self):
        """The shared-prefix draw order (prefix first, only when > 0)
        is part of the pinned contract too."""
        spec = loadgen.WorkloadSpec(prefix_tokens=16)
        t = loadgen.build_trace(spec)
        lp, lo, la = legacy_inline_trace(prefix_tokens=16)
        assert t.prompts == lp and t.outputs == lo
        assert np.array_equal(t.arrivals, la)
        head = t.prompts[0][:16]
        assert all(p[:16] == head for p in t.prompts)

    @pytest.mark.parametrize("workload", loadgen.WORKLOADS)
    def test_same_spec_same_seed_reproduces(self, workload):
        """(spec, seed) is the reproducibility key across every
        workload: two builds from equal specs are identical, a
        different seed diverges."""
        spec = loadgen.WorkloadSpec(workload=workload, num_requests=32,
                                    slo_ms=250.0, seed=7)
        a = loadgen.build_trace(spec)
        b = loadgen.build_trace(loadgen.WorkloadSpec(
            workload=workload, num_requests=32, slo_ms=250.0, seed=7))
        assert a.prompts == b.prompts and a.outputs == b.outputs
        assert np.array_equal(a.arrivals, b.arrivals)
        assert a.tenants == b.tenants and a.sessions == b.sessions
        c = loadgen.build_trace(dc.replace(spec, seed=8))
        assert a.prompts != c.prompts

    def test_poisson_rate_statistics(self):
        """Mean inter-arrival over a long trace approaches 1/rate (wide
        tolerance: fixed seed, but the statistic must be in the right
        regime, not an off-by-1000 unit bug)."""
        t = loadgen.build_trace(loadgen.WorkloadSpec(
            num_requests=2000, rate_rps=10.0, seed=3))
        gaps = np.diff(t.arrivals)
        assert 0.08 < float(np.mean(gaps)) < 0.12

    def test_bursty_is_overdispersed_vs_poisson(self):
        """The MMPP trace's inter-arrival coefficient of variation must
        exceed Poisson's 1.0 — that burstiness is the point of the
        workload — and arrivals stay sorted starting at 0."""
        spec = loadgen.WorkloadSpec(workload="bursty", num_requests=2000,
                                    rate_rps=10.0, burst_boost=16.0,
                                    seed=5)
        t = loadgen.build_trace(spec)
        gaps = np.diff(t.arrivals)
        cv = float(np.std(gaps) / np.mean(gaps))
        assert cv > 1.1
        assert t.arrivals[0] == 0.0
        assert np.all(gaps >= 0)

    def test_diurnal_envelope_modulates_density(self):
        """Arrival density near the raised-cosine peak beats density
        near the trough (floor=0.1 → ~10x fewer accepts there)."""
        spec = loadgen.WorkloadSpec(workload="diurnal",
                                    num_requests=4000, rate_rps=50.0,
                                    diurnal_period_s=4.0,
                                    diurnal_floor=0.1, seed=11)
        t = loadgen.build_trace(spec)
        phase = np.mod(t.arrivals, 4.0) / 4.0
        near_peak = int(np.sum((phase > 0.35) & (phase < 0.65)))
        near_trough = int(np.sum((phase < 0.15) | (phase > 0.85)))
        assert near_peak > 2 * near_trough
        assert np.all(np.diff(t.arrivals) >= 0)

    def test_heavy_tail_lengths_bounded(self):
        """Lognormal/zipf lengths stay in [min(8, max), max] with the
        median pulled toward the floor — heavy tail, hard clamp."""
        for dist in ("lognormal", "zipf"):
            t = loadgen.build_trace(loadgen.WorkloadSpec(
                workload="bursty", length_dist=dist, num_requests=500,
                prompt_max=64, output_max=256, seed=2))
            plens = [len(p) for p in t.prompts]
            assert min(plens) >= 8 and max(plens) <= 64
            assert min(t.outputs) >= 8 and max(t.outputs) <= 256
            assert np.median(t.outputs) < 256 / 2   # tail, not uniform

    def test_multi_tenant_mix_and_slos(self):
        """The default tenant mix: ~70/30 interactive/batch split,
        interactive outputs capped at output_max//4, per-tenant SLOs
        (interactive = spec, batch = 4x), sticky sessions only for the
        interactive class."""
        spec = loadgen.WorkloadSpec(workload="multi-tenant",
                                    num_requests=400, output_max=128,
                                    slo_ms=100.0, seed=9)
        t = loadgen.build_trace(spec)
        n_int = t.tenants.count("interactive")
        assert 0.6 < n_int / 400 < 0.8
        for i in range(400):
            if t.tenants[i] == "interactive":
                assert t.outputs[i] <= 128 // 4
                assert t.slos_ms[i] == 100.0
                assert t.sessions[i] is not None
            else:
                assert t.slos_ms[i] == 4 * 100.0
                assert t.sessions[i] is None
        # sessions group consecutive same-tenant requests: > 1 request
        # per session on average, all ids namespaced by tenant
        sids = [s for s in t.sessions if s is not None]
        assert len(set(sids)) < len(sids)
        assert all(s.startswith("interactive:") for s in sids)

    def test_explicit_tenants_override_defaults(self):
        spec = loadgen.WorkloadSpec(
            workload="multi-tenant", num_requests=200,
            tenants=(loadgen.TenantClass("solo", share=1.0,
                                         slo_ms=42.0),), seed=1)
        t = loadgen.build_trace(spec)
        assert set(t.tenants) == {"solo"}
        assert all(s == 42.0 for s in t.slos_ms)

    def test_requests_stamp_deadlines_and_sessions(self):
        """Trace.requests(): deadline = arrival + slo/1e3 (absolute, on
        the run clock — the scheduler's existing TTL machinery), fresh
        objects per call, session keys riding along."""
        spec = loadgen.WorkloadSpec(workload="multi-tenant",
                                    num_requests=30, slo_ms=500.0,
                                    seed=4)
        t = loadgen.build_trace(spec)
        reqs = t.requests()
        for i, r in enumerate(reqs):
            assert r.id == i and r.arrival == float(t.arrivals[i])
            assert r.deadline == pytest.approx(
                r.arrival + t.slos_ms[i] / 1e3)
            assert r.session == t.sessions[i]
        assert reqs[0] is not t.requests()[0]   # fresh per arm
        # no SLO -> no deadline (engine default TTL may still apply)
        t2 = loadgen.build_trace(loadgen.WorkloadSpec(num_requests=4))
        assert all(r.deadline is None for r in t2.requests())


@pytest.mark.quick
class TestPerRequestRows:
    def test_join_against_run_result(self):
        spec = loadgen.WorkloadSpec(num_requests=3, slo_ms=1000.0)
        t = loadgen.build_trace(spec)
        arr = [float(a) for a in t.arrivals]
        result = {
            "statuses": {0: "ok", 1: "deadline_exceeded"},   # 2 missing
            "outputs": {0: [1, 2, 3], 1: [4]},
            "request_finish_s": {0: arr[0] + 0.25, 1: arr[1] + 9.0},
        }
        rows = loadgen.per_request_rows(t, result)
        assert [r["status"] for r in rows] == [
            "ok", "deadline_exceeded", "missing"]
        assert rows[0]["attained_ms"] == pytest.approx(250.0)
        assert rows[0]["tokens"] == 3 and rows[0]["slo_ms"] == 1000.0
        # non-ok rows never report an attained latency
        assert rows[1]["attained_ms"] is None
        assert rows[2]["attained_ms"] is None and rows[2]["tokens"] == 0


@pytest.mark.quick
class TestScaleAdvisor:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="watermarks"):
            autoscale.ScalePolicy(high_load=1.0, low_load=2.0)
        with pytest.raises(ValueError, match="damping"):
            autoscale.ScalePolicy(hold_ticks=0)
        with pytest.raises(ValueError, match="bounds"):
            autoscale.ScalePolicy(min_replicas=4, max_replicas=2)
        with pytest.raises(ValueError, match="replicas"):
            autoscale.ScaleAdvisor(replicas=0)

    def test_scale_up_needs_hysteresis(self):
        """High load must HOLD for hold_ticks consecutive observations
        before advice fires; a single spike does nothing."""
        pol = autoscale.ScalePolicy(high_load=2.0, hold_ticks=3,
                                    cooldown_ticks=0)
        adv = autoscale.ScaleAdvisor(pol)
        assert adv.observe(0.0, queue_depth=50, occupancy=1.0) is None
        assert adv.observe(0.1, queue_depth=0, occupancy=0.5) is None
        for k in range(2):
            assert adv.observe(0.2 + k, queue_depth=50,
                               occupancy=1.0) is None
        d = adv.observe(2.2, queue_depth=50, occupancy=1.0)
        assert d is not None and d["action"] == "up"
        assert d["replicas_before"] == 1 and d["replicas_after"] == 2
        assert adv.replicas == 2

    def test_cooldown_silences_advice(self):
        pol = autoscale.ScalePolicy(high_load=2.0, hold_ticks=1,
                                    cooldown_ticks=5, max_replicas=8)
        adv = autoscale.ScaleAdvisor(pol)
        assert adv.observe(0.0, queue_depth=100,
                           occupancy=1.0) is not None
        for k in range(5):      # cooldown ticks: streaks frozen
            assert adv.observe(0.1 * k, queue_depth=100,
                               occupancy=1.0) is None
        # first post-cooldown observation restarts the (1-tick) streak
        assert adv.observe(1.0, queue_depth=100,
                           occupancy=1.0) is not None
        assert adv.replicas == 3

    def test_scale_down_on_sustained_idle_respects_min(self):
        pol = autoscale.ScalePolicy(low_load=0.5, hold_ticks=2,
                                    cooldown_ticks=0, min_replicas=1)
        adv = autoscale.ScaleAdvisor(pol, replicas=2)
        assert adv.observe(0.0, queue_depth=0, occupancy=0.0) is None
        d = adv.observe(0.1, queue_depth=0, occupancy=0.0)
        assert d is not None and d["action"] == "down"
        assert adv.replicas == 1
        # at min_replicas: idle forever, never advises below the floor
        for k in range(10):
            assert adv.observe(0.2 + k, queue_depth=0,
                               occupancy=0.0) is None
        assert adv.replicas == 1

    def test_load_normalized_by_advised_replicas(self):
        adv = autoscale.ScaleAdvisor(replicas=4)
        one = autoscale.ScaleAdvisor(replicas=1)
        kw = dict(queue_depth=8.0, occupancy=1.0, shed_rate=0.5,
                  live_fraction=1.0)
        assert adv.load(**kw) == pytest.approx(one.load(**kw) / 4)

    def test_report_shape(self):
        adv = autoscale.ScaleAdvisor()
        adv.observe(0.0, queue_depth=1, occupancy=0.5)
        r = adv.report()
        assert set(r) == {"ticks", "peak_load", "replicas_advised",
                          "decisions", "policy"}
        assert r["ticks"] == 1 and r["decisions"] == []
        assert r["policy"]["high_load"] == 4.0


@pytest.mark.quick
class TestFollowupTurns:
    def test_zero_turns_default_stays_pinned(self):
        """followup draws come LAST in build_trace, so enabling them
        must not perturb turn 1 — and the default (0 turns) trace
        remains byte-identical to the legacy pin."""
        base = loadgen.build_trace(loadgen.WorkloadSpec())
        multi = loadgen.build_trace(
            loadgen.WorkloadSpec(followup_turns=2))
        assert multi.prompts == base.prompts
        assert multi.outputs == base.outputs
        assert np.array_equal(multi.arrivals, base.arrivals)
        assert base.followup_suffixes == [] and base.followup_gaps == []
        assert len(multi.followup_suffixes) == 2
        assert len(multi.followup_gaps) == 2

    def test_followup_prompt_composition_and_seeding(self):
        spec = loadgen.WorkloadSpec(num_requests=4, followup_turns=1,
                                    slo_ms=250.0)
        t = loadgen.build_trace(spec)
        prev = t.requests()
        outputs = {r.id: [900 + r.id, 901 + r.id] for r in prev}
        f = t.followup_requests(1, prev, outputs, id_base=100,
                                arrival_base=7.0)
        assert [r.id for r in f] == [100, 101, 102, 103]
        for i, (p, r) in enumerate(zip(prev, f)):
            assert r.prompt[:len(p.prompt)] == list(p.prompt)
            ans = r.prompt[len(p.prompt):len(p.prompt) + 2]
            assert ans == outputs[p.id]
            suffix = r.prompt[len(p.prompt) + 2:]
            assert suffix == t.followup_suffixes[0][i]
            assert len(suffix) >= 1
            assert r.max_new_tokens == t.outputs[i]
            assert r.arrival >= 7.0
            assert r.deadline == pytest.approx(r.arrival + 0.25)
        # (spec, seed) reproducibility covers the follow-up draws too
        t2 = loadgen.build_trace(spec)
        f2 = t2.followup_requests(1, prev, outputs, id_base=100,
                                  arrival_base=7.0)
        assert [r.prompt for r in f2] == [r.prompt for r in f]
        assert [r.arrival for r in f2] == [r.arrival for r in f]

    def test_missing_output_falls_back_to_prompt_only(self):
        spec = loadgen.WorkloadSpec(num_requests=2, followup_turns=1)
        t = loadgen.build_trace(spec)
        prev = t.requests()
        f = t.followup_requests(1, prev, {}, id_base=10)
        for p, r in zip(prev, f):
            assert r.prompt[:len(p.prompt)] == list(p.prompt)

    def test_out_of_range_turn_rejected(self):
        t = loadgen.build_trace(
            loadgen.WorkloadSpec(num_requests=2, followup_turns=1))
        with pytest.raises(ValueError, match="out of range"):
            t.followup_requests(2, t.requests(), {}, id_base=10)
        with pytest.raises(ValueError, match="out of range"):
            t.followup_requests(0, t.requests(), {}, id_base=10)

    def test_negative_turns_rejected(self):
        with pytest.raises(ValueError, match="followup_turns"):
            loadgen.WorkloadSpec(followup_turns=-1)
