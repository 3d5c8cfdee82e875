"""Host-side request tracing (serving/tracing) — ISSUE 17.

Pins: tracing OFF is byte-for-byte the untraced engine (token
identity, no ``trace`` result key, no tracer object); span
state-machine legality (queued before admitted, exactly one terminal,
phase clocks sum to wall time); the step ring is bounded with VISIBLE
drops; the Chrome trace-event export is schema-valid and monotone per
(pid, tid) track; the breakdown block's span-derived TTFT agrees with
the loop's stamped TTFT (both stamped from the SAME post-step clock
read — the budget is 1ms but the delta should be exactly 0); and
spans SURVIVE failover — a migrated request's queue time accumulates
across incarnations instead of resetting at re-admission on the
survivor (the ISSUE 17 bugfix).
"""

import dataclasses
import json

import numpy as np
import pytest

from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (FaultPlan, PagedDecodeEngine,
                                        ReplicaFault, ReplicaRouter,
                                        Request, ServeConfig,
                                        TraceBuffer)
from mpi_tensorflow_tpu.serving import loadgen, tracing
from mpi_tensorflow_tpu.utils.metrics_writer import (BREAKDOWN_KEYS,
                                                     breakdown_block)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
BASE = dict(num_blocks=40, block_size=4, max_slots=3, max_seq_len=24,
            prefill_chunk=8)


def _model(seed=0):
    import jax

    model = gpt.CausalLm(TINY)
    return model, model.init(jax.random.key(seed))


def _reqs(rng, n, budget_hi=8):
    prompts = [list(map(int, rng.integers(0, TINY.vocab_size, int(s))))
               for s in rng.integers(3, 13, n)]
    budgets = [int(b) for b in rng.integers(1, budget_hi + 1, n)]
    return [Request(i, p, b) for i, (p, b) in
            enumerate(zip(prompts, budgets))]


def _fixed_trace(n=6, prompt_len=6, budget=6):
    rng = np.random.default_rng(42)
    return [Request(i,
                    list(map(int, rng.integers(0, TINY.vocab_size,
                                               prompt_len))),
                    budget, session=i % 2)
            for i in range(n)]


def _engine(trace="off", seed=0, **kw):
    model, params = _model(seed)
    serve = ServeConfig(**{**BASE, **kw}, trace=trace)
    return PagedDecodeEngine(model, params, serve)


# ------------------------------------------------------------- off path

class TestOffPath:
    def test_off_is_token_identical_and_untraced(self):
        """THE zero-overhead contract: trace=off constructs no tracer,
        emits no trace block, and changes no tokens vs trace=on."""
        rng = np.random.default_rng(7)
        reqs = _reqs(rng, 8)
        off = _engine("off")
        on = _engine("on")
        res_off = off.run([dataclasses.replace(r) for r in reqs])
        res_on = on.run([dataclasses.replace(r) for r in reqs])
        assert res_off["outputs"] == res_on["outputs"], \
            "tracing changed greedy outputs"
        assert off.tracer is None
        assert "trace" not in res_off
        assert on.tracer is not None
        assert res_on["trace"]["enabled"] is True

    def test_off_rows_carry_no_phase_columns(self):
        """per_request_rows joins span phases ONLY when a trace block
        is present — off rows are byte-identical to the pre-tracing
        shape."""
        tr = loadgen.Trace(spec=None, prompts=[[1, 2, 3]], outputs=[2],
                          arrivals=np.array([0.0]), tenants=["t"],
                          slos_ms=[None], sessions=[None])
        base = {"statuses": {0: "ok"}, "outputs": {0: [4, 5]},
                "request_finish_s": {0: 0.5},
                "request_first_token_s": {0: 0.2}}
        off_rows = loadgen.per_request_rows(tr, base)
        assert "queue_ms" not in off_rows[0]
        span = {"rid": 0, "queue_s": 0.1, "prefill_s": 0.05,
                "decode_s": 0.2}
        on_rows = loadgen.per_request_rows(
            tr, {**base, "trace": {"spans": {0: span}}})
        assert on_rows[0]["queue_ms"] == pytest.approx(100.0)
        assert on_rows[0]["prefill_ms"] == pytest.approx(50.0)
        assert on_rows[0]["decode_ms"] == pytest.approx(200.0)


# ------------------------------------------------- span state machine

class TestSpanStateMachine:
    def test_span_legality_under_queue_pressure(self):
        """More requests than slots: every span walks the legal machine
        (queued -> admitted -> first_token -> terminal, stamps
        monotone, exactly one terminal) and its phase accumulators sum
        to its wall time."""
        eng = _engine("on", max_slots=2)
        rng = np.random.default_rng(11)
        reqs = _reqs(rng, 8)
        res = eng.run(reqs)
        spans = res["trace"]["spans"]
        assert sorted(spans) == [r.id for r in reqs]
        for rid, d in spans.items():
            names = [n for _t, n in d["events"]]
            times = [t for t, _n in d["events"]]
            assert times == sorted(times), f"span {rid} stamps regress"
            assert names[0] == "queued"
            if "admitted" in names:
                assert names.index("admitted") > names.index("queued")
            terminals = [n for n in names if n.startswith("terminal:")]
            assert len(terminals) == 1, \
                f"span {rid} has {len(terminals)} terminals"
            assert d["status"] == res["statuses"][rid]
            assert terminals[0] == f"terminal:{d['status']}"
            if d["first_token"] is not None:
                assert d["terminal"] >= d["first_token"] >= d["arrive"]
            # the sum contract: phase clocks partition wall time
            assert (d["queue_s"] + d["prefill_s"] + d["decode_s"]
                    == pytest.approx(d["terminal"] - d["arrive"],
                                     abs=1e-9))
            assert d["incarnations"] == 1
        # chunk advances are observed post-step, so a request that is
        # admitted, prefilled AND emits inside ONE step records none —
        # but queue pressure guarantees some request prefills across
        # steps
        assert any(d["chunks"] >= 1 for d in spans.values())

    def test_synchronous_rejection_lands_terminal(self):
        """A request the scheduler rejects at submit (infeasible: prompt
        longer than the envelope) still gets a span with exactly one
        terminal — the flush at the submit seam, not the step loop."""
        eng = _engine("on")
        res = eng.run([Request(0, list(range(2)), 4),
                       Request(1, list(range(64)), 4)])   # > max_seq_len
        spans = res["trace"]["spans"]
        assert spans[1]["status"] == res["statuses"][1] != "ok"
        assert sum(n.startswith("terminal:")
                   for _t, n in spans[1]["events"]) == 1
        assert spans[0]["status"] == "ok"


# ------------------------------------------------------- the step ring

class TestTraceBuffer:
    def test_bounded_drop_oldest_with_visible_drops(self):
        tb = TraceBuffer(capacity=4)
        for i in range(7):
            tb.append({"i": i})
        assert len(tb) == 4
        assert tb.dropped == 3
        assert [r["i"] for r in tb.records()] == [3, 4, 5, 6]

    def test_capacity_validated(self):
        with pytest.raises(ValueError, match="capacity"):
            TraceBuffer(capacity=0)

    def test_engine_run_records_steps_with_phase_durations(self):
        eng = _engine("on")
        rng = np.random.default_rng(13)
        res = eng.run(_reqs(rng, 4))
        tb = res["trace"]
        assert tb["steps"] > 0 and tb["steps_dropped"] == 0
        rec = tb["replicas"][0]["steps"][-1]
        assert rec["t1"] >= rec["t0"]
        assert rec["dispatch_s"] >= 0 and rec["consume_s"] >= 0
        assert set(rec["signals"]) >= {"queue_depth", "occupancy"}

    def test_step_record_says_how_far_the_host_looked_ahead(self):
        """Every step record carries the lookahead's running counts, and
        ``consume_s`` (the host waiting for the device's tokens) beside
        the step's length: together they say which side set the pace.
        The counts in the newest record are the run's."""
        eng = _engine("on")
        rng = np.random.default_rng(23)
        res = eng.run(_reqs(rng, 6))
        steps = res["trace"]["replicas"][0]["steps"]
        for rec in steps:
            sig = rec["signals"]
            assert 0 <= sig["lookahead_dispatches"] \
                <= sig["forward_dispatches"]
            assert sig["lookahead_discarded_rows"] >= 0
            assert rec["consume_s"] <= rec["t1"] - rec["t0"] + 1e-6
        counts = [r["signals"]["lookahead_dispatches"] for r in steps]
        assert counts == sorted(counts) and counts[-1] > 0
        assert counts[-1] == res["lookahead_dispatches"]
        assert steps[-1]["signals"]["forward_dispatches"] \
            == res["forward_dispatches"]
        bd = breakdown_block(res["trace"])
        assert bd["lookahead_share"] == pytest.approx(
            res["lookahead_dispatches"] / res["forward_dispatches"],
            abs=1e-3)
        assert 0 <= bd["device_wait_ms_p50"] <= bd["step_ms_p50"]


# --------------------------------------------------- chrome export

class TestChromeExport:
    def test_schema_and_monotone_tracks(self, tmp_path):
        eng = _engine("on")
        rng = np.random.default_rng(17)
        reqs = _reqs(rng, 6)
        res = eng.run(reqs)
        path = str(tmp_path / "trace.json")
        summary = tracing.write_chrome_trace(path,
                                             res["trace"]["replicas"])
        doc = json.loads(open(path).read())
        events = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        assert len(events) == summary["events"]
        assert summary["requests"] == len(reqs)
        assert summary["steps"] == res["trace"]["steps"]
        # monotone per (pid, tid) track
        keys = [(e["pid"], e["tid"], e["ts"]) for e in events]
        assert keys == sorted(keys)
        # one process_name metadata record per pid
        pids = {e["pid"] for e in events}
        metas = [e for e in events if e["ph"] == "M"]
        assert {e["pid"] for e in metas} == pids
        # every ok request opens and closes an async span
        ok = [r.id for r in reqs
              if res["statuses"][r.id] == "ok"]
        for ph in ("b", "e"):
            have = {e["id"] for e in events
                    if e["ph"] == ph and e["cat"] == "request"}
            assert set(ok) <= have, f"missing '{ph}' events"
        # steps are X duration events on their own track
        steps = [e for e in events if e["ph"] == "X"]
        assert steps and all(e["tid"] == 1 and e["dur"] >= 1
                             for e in steps)


# ------------------------------------------------------ breakdown

class TestBreakdown:
    def test_span_ttft_matches_loop_stamps(self):
        """Span first-token stamps and the loop's request_first_token_s
        are the SAME post-step clock read — the cross-check delta must
        be within the 1ms budget (and is exactly 0 by construction)."""
        eng = _engine("on", max_slots=2)
        rng = np.random.default_rng(19)
        res = eng.run(_reqs(rng, 8))
        bd = breakdown_block(res["trace"],
                             stamped_first_s=res["request_first_token_s"])
        assert tuple(bd) == BREAKDOWN_KEYS
        assert bd["enabled"] is True
        assert bd["requests"] == sum(
            1 for s in res["statuses"].values() if s == "ok")
        assert bd["ttft_vs_stamp_max_delta_ms"] <= 1.0
        assert bd["phase_sum_vs_attained_max_delta_ms"] <= 1.0
        assert bd["queue_ms_p99"] >= bd["queue_ms_p50"] >= 0
        assert bd["ttft_ms_p99"] >= bd["ttft_ms_p50"] > 0

    def test_normalized_shape_when_disabled_or_empty(self):
        for trace in (None, {}, {"enabled": False}):
            bd = breakdown_block(trace)
            assert tuple(bd) == BREAKDOWN_KEYS
            assert bd["enabled"] is False and bd["requests"] == 0
        bd = breakdown_block({"enabled": True, "spans": {}, "steps": 3,
                              "steps_dropped": 1})
        assert tuple(bd) == BREAKDOWN_KEYS
        assert bd["requests"] == 0 and bd["steps"] == 3
        assert bd["steps_dropped"] == 1


# ------------------------------------------------- failover survival

class TestFailoverSpans:
    def test_migrated_span_accumulates_queue_across_incarnations(self):
        """THE ISSUE 17 bugfix pin: kill replica 0 mid-decode; the
        migrated requests' fleet-merged spans must carry BOTH
        incarnations — queue time sums across the migration instead of
        resetting when the survivor re-admits the replayed request —
        and tokens stay identical with tracing on."""
        model, params = _model(3)
        serve = ServeConfig(**BASE, failover_backoff_ms=1e6, trace="on")
        single = PagedDecodeEngine(model, params, serve)
        reqs = _fixed_trace()
        want = single.run([dataclasses.replace(r) for r in reqs])

        def fleet():
            return ReplicaRouter([PagedDecodeEngine(model, params, serve)
                                  for _ in range(2)])

        clean = fleet().run([dataclasses.replace(r) for r in reqs],
                            parallel=False)
        plan = FaultPlan([ReplicaFault(0, at_step=4)])
        res = fleet().run([dataclasses.replace(r) for r in reqs],
                          parallel=False, fault_plan=plan)
        assert plan.fired, "injected fault never fired"
        assert res["outputs"] == want["outputs"], \
            "tracing + failover changed greedy outputs"

        merged = res["trace"]["spans"]
        victim = res["trace"]["replicas"][0]["spans"]
        survivor = res["trace"]["replicas"][1]["spans"]
        migrated = [rid for rid, d in merged.items()
                    if d["incarnations"] >= 2]
        assert migrated, "fault migrated no live work"
        for rid in migrated:
            m = merged[rid]
            assert m["status"] == "ok"
            assert sum(n.startswith("terminal:")
                       for _t, n in m["events"]) == 1
            # the victim's harvest closed the span open (no terminal)
            # and stamped the migration transition
            assert victim[rid]["status"] is None
            assert any(n == "migrated" for _t, n in m["events"])
            # queue time is the SUM of both incarnations, not the
            # survivor's alone — the accumulate-not-reset contract
            assert m["queue_s"] == pytest.approx(
                victim[rid]["queue_s"] + survivor[rid]["queue_s"])
            assert m["queue_s"] >= survivor[rid]["queue_s"]

        # the victims' breakdown is no cheaper than the unfaulted
        # fleet's for the same requests: migration re-queues work that
        # the clean run admitted once
        faulted_q = sum(merged[r]["queue_s"] for r in migrated)
        clean_q = sum(clean["trace"]["spans"][r]["queue_s"]
                      for r in migrated)
        assert faulted_q >= clean_q
