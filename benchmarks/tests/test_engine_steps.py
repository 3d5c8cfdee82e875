"""The serving host's phase metrics (``serve_host_ms``, ``serve_launch_ms``)
on hand-written step records, on a program that keeps no step log, and on
a traced rehearsal of ``gpt_base``'s cell."""

import contextlib

import pytest

from benchmarks.harness import device, manifest, serve_driver, spans

READERS = ("serve_host_ms", "serve_launch_ms")


def _step(t0, length, launch, wait, trace):
    # t1 is the loop's post-step clock read, which comes before the step's
    # accounting and bookkeeping end: the span's length is its phases' sum
    return {"t0": t0, "t1": t0 + length / 2, "compiles": 0, "emitted": 1,
            "signals": {},
            "phases": {"serve.step": length - launch - wait - trace,
                       "serve.launch": launch, "serve.wait": wait,
                       "serve.trace": trace}}


@pytest.fixture
def log():
    from mpi_tensorflow_tpu.utils import dispatch_log

    dispatch_log.reset()
    yield dispatch_log
    dispatch_log.reset()


def test_readers_average_the_steps_inside_the_window(log):
    log.record_step(0.5, _step(0.0, 9e-3, 1e-3, 1e-3, 1e-3))   # before
    log.record_step(1.0, _step(0.5, 4e-3, 2e-3, 1e-3, 0.5e-3))
    log.record_step(1.5, _step(1.0, 6e-3, 3e-3, 0.0, 0.5e-3))
    log.record_step(2.0, _step(1.5, 9e-3, 1e-3, 1e-3, 1e-3))   # at hi
    run = {"window": (1.0, 2.0)}
    host = manifest.reader("serve_host_ms")(run)
    launch = manifest.reader("serve_launch_ms")(run)
    # (4 - 1 - 0.5 + 6 - 0 - 0.5) / 2 and (2 + 3) / 2
    assert host == pytest.approx(4.0)
    assert launch == pytest.approx(2.5)


def test_readers_find_nothing_without_steps(log, monkeypatch):
    run = {"window": (0.0, 1.0)}
    for name in READERS:
        assert manifest.reader(name)(run) is None, name
    log.record_step(5.0, _step(4.0, 1e-3, 0.0, 0.0, 0.0))
    for name in READERS:
        assert manifest.reader(name)(run) is None, name
    # a program whose dispatch log keeps no steps (before the phase spans)
    monkeypatch.delattr(log, "steps")
    for name in READERS:
        assert manifest.reader(name)(run) is None, name


def test_a_traced_rehearsal_reports_both(log):
    """The harness's traced serving cell (CPU sizes) leaves step records
    in the window, and the host's own work covers its launches."""
    import jax

    cell = manifest.cell(manifest.manifest(), "gpt_base.serve_closed128")
    from benchmarks.run import apply_rehearsal

    apply_rehearsal(cell)
    sc = serve_driver.ServeCell(cell, jax.devices()[:1], 7, True)
    sc.prewarm()
    sp = spans.Spans(False)
    lo, hi, *_ = serve_driver.closed_loop(
        sc, sp, 1.0, contextlib.nullcontext, device.CompileCounter())
    run = {"window": (sc.t0 + lo, sc.t0 + hi), "spans": sp}
    host = manifest.reader("serve_host_ms")(run)
    launch = manifest.reader("serve_launch_ms")(run)
    step = manifest.reader("serve_step_ms")(run)
    assert 0 < launch < host <= step


# ------------------------------------------- idle by the innermost span

def test_idle_goes_to_the_innermost_covering_span():
    """A gap split between a parent and the child that opens inside it:
    each instant goes to the span that started latest; past every span,
    to ``no_span``."""
    from benchmarks import engine_idle

    raw = {"devices": [{"name": "/device:TPU:0",
                        "ops": [["a", 0, 50], ["b", 400, 500]]}],
           "host": [["window", 0, 1000], ["serve_iterate", 0, 950],
                    ["serve.step", 10, 930], ["serve.decode", 100, 200],
                    ["serve.launch", 150, 100], ["serve.wait", 300, 250],
                    ["serve.trace", 300, 20]]}
    d = engine_idle.describe(raw)
    assert d["busy_s"] == pytest.approx(550e-9)
    idle = d["idle_innermost_s"]
    # 50..100 step, 100..150 decode, 150..250 launch, 250..300 decode,
    # 300..320 trace (same start as the wait, shorter), 320..400 wait,
    # 900..940 step, 940..950 iterate, 950..1000 nothing
    assert idle == pytest.approx({
        "serve.step": 90e-9, "serve.decode": 100e-9,
        "serve.launch": 100e-9, "serve.trace": 20e-9, "serve.wait": 80e-9,
        "serve_iterate": 10e-9, "no_span": 50e-9})
    assert sum(idle.values()) == pytest.approx(d["idle_s"])
    assert d["steps"] == 1
    assert d["self_ms_a_step"]["serve.launch"] == pytest.approx(100e-6)
    assert d["self_ms_a_step"]["serve.step"] == pytest.approx(
        (930 - 200 - 250) * 1e-6)


def test_with_harness_spans_alone_it_names_the_covering_one():
    """Over the harness's own spans, where every gap lies wholly inside
    one span or outside all, it gives what ``trace_reduce``'s
    largest-cover rule gives (a gap the rule names whole, it splits)."""
    from benchmarks import engine_idle
    from benchmarks.harness import trace_reduce as tr

    raw = {"devices": [{"name": "/device:TPU:0",
                        "ops": [["a", 0, 100], ["b", 300, 300],
                                ["c", 700, 150]]}],
           "host": [["window", 0, 1000], ["serve_iterate", 50, 400],
                    ["serve_iterate", 500, 350]]}
    old = tr.breakdown(tr.reduce(raw))["idle_gaps"]
    assert engine_idle.describe(raw)["idle_innermost_s"] == \
        pytest.approx(dict(old))
