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
import time

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
        assert tuple(rec["phases"]) == tracing.STEP_SPANS
        assert all(v >= 0 for v in rec["phases"].values())
        assert rec["compiles"] >= 0
        assert set(rec["signals"]) >= {"queue_depth", "occupancy"}

    def test_step_record_says_how_far_the_host_looked_ahead(self):
        """Every step record carries the lookahead's running counts, and
        ``phases["serve.wait"]`` (the host waiting for the device's
        tokens) beside the step's length: together they say which side
        set the pace.
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
            assert rec["phases"]["serve.wait"] \
                <= rec["t1"] - rec["t0"] + 1e-6
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


# ------------------------------------------------------ phase spans

# each span's parent as tracing.STEP_SPANS' comment gives it; None = no
# span open (a run's last delivery comes after its last step)
NEST = {
    "serve.step": {None},
    "serve.sweep": {"serve.step"},
    "serve.admit": {"serve.step"},
    "serve.prefill": {"serve.step"},
    "serve.decode": {"serve.step"},
    "serve.launch": {"serve.admit", "serve.prefill", "serve.decode"},
    "serve.deliver": {"serve.step", None},
    "serve.wait": {"serve.deliver"},
    "serve.account": {"serve.step"},
    "serve.trace": {"serve.step", "serve.account", "serve.prefill",
                    "serve.decode", "serve.deliver"},
}

# the engine's compiled programs on the serving path
COMPILED = ("_prefill_fn", "_decode_fn", "_take_fn", "_keep_fn", "_cow_fn",
            "_partial_fn", "_verify_fn", "_mixed_fn")

# one run of each dispatch path: plain, the prefix cache (a fully cached
# prompt of whole blocks recomputes its shared last block: copy-on-write),
# speculative verify, the fused mixed step
PATHS = {
    "plain": (dict(), False),
    "prefix": (dict(prefix_cache="on", max_slots=1), True),
    "verify": (dict(speculative="ngram"), False),
    "mixed": (dict(mixed_batch="on"), False),
}


def _innermost(tr):
    return tr._open[-1][0] if tr._open else None


@pytest.fixture(scope="module", params=sorted(PATHS))
def watched(request):
    """A traced run with every span's parent, for every compiled call
    the innermost span open at the call, and each ``serve.step``'s
    length as its ``exit`` measured it."""
    kw, repeat = PATHS[request.param]
    eng = _engine("on", **kw)
    tr = eng.tracer
    opened, calls, lengths = [], [], []
    enter, exit_ = tr.enter, tr.exit

    def watch_enter(name):
        opened.append((name, _innermost(tr)))
        enter(name)

    def watch_exit():
        name = _innermost(tr)
        dur = exit_()
        if name == "serve.step":
            lengths.append(dur)
        return dur
    tr.enter, tr.exit = watch_enter, watch_exit
    for attr in COMPILED:
        def call(*a, _fn=getattr(eng, attr)):
            calls.append(_innermost(tr))
            return _fn(*a)
        setattr(eng, attr, call)
    rng = np.random.default_rng(29)
    reqs = _reqs(rng, 6)
    if repeat:
        prompt = list(map(int, rng.integers(0, TINY.vocab_size, 8)))
        reqs = [Request(i, list(prompt), 4) for i in range(3)]
    res = eng.run(reqs)
    return request.param, res, opened, calls, lengths


class TestStepSpans:
    def test_spans_nest_as_documented(self, watched):
        path, _res, opened, _calls, _lengths = watched
        assert {name for name, _ in opened} <= set(tracing.STEP_SPANS)
        bad = {(n, p) for n, p in opened if p not in NEST[n]}
        assert not bad, f"{path}: spans opened inside the wrong parent"
        assert {"serve.step", "serve.sweep", "serve.admit",
                "serve.launch", "serve.deliver", "serve.wait",
                "serve.account", "serve.trace"} \
            <= {name for name, _ in opened}

    def test_one_launch_span_per_compiled_call(self, watched):
        path, _res, opened, calls, _lengths = watched
        assert calls, path
        # every compiled call runs inside a launch span of its own
        assert set(calls) == {"serve.launch"}, path
        assert sum(n == "serve.launch" for n, _ in opened) == len(calls)
        if path == "prefix":
            assert _res["prefix"]["cow_copies"] > 0

    def test_phase_self_times_partition_the_step(self, watched):
        """A step's phase self times are >= 0 and sum to its
        ``serve.step`` span's length."""
        path, res, _opened, _calls, lengths = watched
        steps = res["trace"]["replicas"][0]["steps"]
        assert steps and len(steps) == len(lengths)
        for rec, length in zip(steps, lengths):
            assert tuple(rec["phases"]) == tracing.STEP_SPANS
            assert min(rec["phases"].values()) >= -1e-12, path
            assert sum(rec["phases"].values()) == pytest.approx(length,
                                                               abs=1e-9)

    def test_step_bounds_are_read_on_the_loop_clock(self):
        """``t0`` / ``t1`` are the loop clock's pre- and post-step
        ``now`` whatever that clock is, never a ``time.perf_counter``
        length added to it: on a clock that ticks whole seconds both
        are whole numbers."""
        ticks = iter(range(1_000_000))
        eng = _engine("on")
        res = eng.run(_reqs(np.random.default_rng(43), 4),
                      time_fn=lambda: float(next(ticks)))
        steps = res["trace"]["replicas"][0]["steps"]
        assert steps
        for rec in steps:
            assert rec["t0"].is_integer() and rec["t1"].is_integer()
            assert rec["t1"] > rec["t0"]

    def test_wait_holds_the_blocking_read(self):
        """The one blocking token read is ``serve.wait``: a device that
        takes 20 ms to hand a dispatch's tokens over shows there, and
        not in the delivery around it."""
        eng = _engine("on")
        tr = eng.tracer
        reads = []

        class SlowTokens:
            def __init__(self, a):
                self.a = a

            def __array__(self, dtype=None, copy=None):
                reads.append((_innermost(tr),
                              bool(tr._open) and tr._open[0][0]
                              == "serve.step"))
                time.sleep(0.02)
                return np.asarray(self.a, dtype)

        deliver = eng._deliver

        def slow_deliver(n):
            eng._unread[:n] = [(SlowTokens(nxt), rows, rec)
                               for nxt, rows, rec in eng._unread[:n]]
            return deliver(n)
        eng._deliver = slow_deliver
        res = eng.run(_reqs(np.random.default_rng(31), 4))
        assert reads and {span for span, _ in reads} == {"serve.wait"}
        in_steps = sum(inside for _, inside in reads)
        steps = res["trace"]["replicas"][0]["steps"]
        wait = sum(r["phases"]["serve.wait"] for r in steps)
        deliver_self = sum(r["phases"]["serve.deliver"] for r in steps)
        assert wait >= 0.02 * in_steps
        assert deliver_self < 0.01 * in_steps

    def test_step_records_count_the_programs_built(self):
        """A step that builds a program says so; once every shape the
        requests reach is built, a second pass builds none."""
        eng = _engine("on")
        reqs = _reqs(np.random.default_rng(37), 4)
        eng.run([dataclasses.replace(r) for r in reqs])
        first = len(eng.tracer.buffer)
        eng.run([dataclasses.replace(r) for r in reqs])
        steps = eng.tracer.buffer.records()
        assert sum(r["compiles"] for r in steps[:first]) > 0
        assert sum(r["compiles"] for r in steps[first:]) == 0

    def test_step_records_reach_the_dispatch_log(self):
        """Traced, every step record is also in the process-wide
        ``utils/dispatch_log`` under its perf_counter start, where a
        benchmark's metric reader finds it once the engine is gone;
        untraced, nothing is written there."""
        from mpi_tensorflow_tpu.utils import dispatch_log

        reqs = _reqs(np.random.default_rng(41), 4)
        dispatch_log.reset()
        _engine("off").run([dataclasses.replace(r) for r in reqs])
        assert dispatch_log.steps() == []
        eng = _engine("on")
        t0 = time.perf_counter()
        eng.run([dataclasses.replace(r) for r in reqs])
        logged = dispatch_log.steps()
        dispatch_log.reset()
        assert [rec for _, rec in logged] == eng.tracer.buffer.records()
        starts = [at for at, _ in logged]
        assert starts == sorted(starts) and starts[0] >= t0

    def test_tracing_adds_no_device_read(self):
        """The spans read host clocks only: the HOST-SYNC pass's marks
        in the serving loop are the engine's four reads of a device
        value and its one cold-block demotion, none in the loop or the
        tracer."""
        from pathlib import Path

        root = Path(tracing.__file__).parent
        marks = {name: (root / name).read_text().count("sync-ok")
                 for name in ("engine.py", "iteration.py", "tracing.py")}
        assert marks == {"engine.py": 5, "iteration.py": 0,
                         "tracing.py": 0}


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
        # ... whose args are the step's phases, in microseconds
        assert all({f"{n}_us" for n in tracing.STEP_SPANS} | {"compiles"}
                   <= set(e["args"]) for e in steps)


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
