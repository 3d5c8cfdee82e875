"""The serving entry point (``python -m mpi_tensorflow_tpu.serving``).

A serving option is ONE ``ServeConfig`` (or ``WorkloadSpec``) field: the
flag is derived from it, the only validation is the dataclass's own, and
the entry point serves a trace once.  Three things are pinned:

- every derived flag lands in the dataclass the engine is built from;
- every rule of ``ServeConfig.__post_init__`` (and each ``WorkloadSpec``
  rule a flag can reach) refuses through ``main`` with exit code 2 and
  the rule's own words;
- the package behaviours the entry point composes (plain, journaled,
  routed, and each feature against its off run) on ``--tiny``.

A ``main(argv)`` run builds and warms its own engine (5-20 s here), so
runs are cached per argv for the module and every comparison shares the
``BASE`` run.
"""

import dataclasses
import json
import os

import pytest

from mpi_tensorflow_tpu.serving import ReplayJournal, loadgen
from mpi_tensorflow_tpu.serving import __main__ as entry
from mpi_tensorflow_tpu.serving.engine import SERVE_HELP, ServeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _flag(name):
    return "--" + name.replace("_", "-")


# ---------------------------------------------------- flag -> field

# a non-default value per field, with the companions its rules ask for
SERVE_VALUES = {
    "num_blocks": (77, ()), "block_size": (8, ()), "max_slots": (3, ()),
    "max_seq_len": (1024, ()), "prefill_chunk": (32, ()),
    "eos_id": (7, ()), "kernel": ("pallas", ()),
    "prefix_cache": ("on", ()),
    "prefix_gen": ("on", ("--prefix-cache", "on")),
    "prefix_route": ("on", ("--prefix-cache", "on")),
    "speculative": ("draft-model", ()), "draft_k": (6, ()),
    "draft_auto": ("on", ("--speculative", "ngram")),
    "mixed_batch": ("on", ()), "prefill_budget": (16, ()),
    "kv_dtype": ("int4", ()), "kv_group": (16, ()),
    "kv_tier": ("host", ("--prefix-cache", "on")), "tp": (2, ()),
    "deadline_ms": (250.0, ()), "queue_depth": (16, ()),
    "max_evictions": (3, ()), "drain_ms": (500.0, ()),
    "failover_backoff_ms": (20.0, ()), "trace": ("on", ()),
    "trace_out": ("t.json", ("--trace", "on")),
}
WORKLOAD_VALUES = {
    "workload": "diurnal", "num_requests": 5, "rate_rps": 9.5,
    "prompt_max": 20, "output_max": 11, "prefix_tokens": 32,
    "slo_ms": 1500.0, "seed": 3,
}


def _plan(argv):
    return entry.plan(entry.build_parser().parse_args(list(argv)))


@pytest.mark.parametrize("field", [f.name for f in
                                   dataclasses.fields(ServeConfig)])
def test_flag_reaches_its_field(field):
    value, companions = SERVE_VALUES[field]
    assert value != getattr(ServeConfig, field)      # the field's default
    _, cfg = _plan(["--tiny", _flag(field), str(value), *companions])
    assert getattr(cfg, field) == value
    assert type(getattr(cfg, field)) is type(value)


@pytest.mark.parametrize("field", list(loadgen.WORKLOAD_HELP))
def test_workload_flag_reaches_its_field(field):
    value = WORKLOAD_VALUES[field]
    assert value != getattr(loadgen.WorkloadSpec(), field)
    trace, _ = _plan(["--tiny", _flag(field), str(value)])
    assert getattr(trace.spec, field) == value
    assert type(getattr(trace.spec, field)) is type(value)


def test_unset_pool_is_sized_from_the_trace():
    """Longest request rounded up to a power of two; every slot fits
    one; a flag given wins."""
    trace, cfg = _plan(["--tiny", "--max-slots", "4"])
    longest = max(len(p) + o for p, o in zip(trace.prompts, trace.outputs))
    assert cfg.max_seq_len >= longest > cfg.max_seq_len // 2
    assert cfg.num_blocks == 4 * cfg.max_blocks_per_seq + 1
    _, cfg = _plan(["--tiny", "--num-blocks", "40", "--max-seq-len", "512"])
    assert (cfg.num_blocks, cfg.max_seq_len) == (40, 512)


# ------------------------------------------------ refused, in words

# one case per raise of ServeConfig.__post_init__, in its order, then
# one per WorkloadSpec rule a flag reaches
REFUSALS = {
    "geometry": (["--block-size", "0"], "bad pool geometry: block_size 0"),
    "kernel": (["--kernel", "mosaic"], "kernel must be auto|xla|pallas"),
    "prefix_cache": (["--prefix-cache", "maybe"],
                     "prefix cache must be off|on"),
    "prefix_gen": (["--prefix-gen", "maybe"], "prefix_gen must be off|on"),
    "prefix_route": (["--prefix-route", "maybe"],
                     "prefix_route must be off|on"),
    "gen_needs_cache": (["--prefix-gen", "on"],
                        "prefix_gen extends the radix prefix cache"),
    "route_needs_cache": (["--prefix-route", "on"],
                          "there is no trie to hint from"),
    "speculative": (["--speculative", "turbo"],
                    "speculative must be off|ngram|draft-model"),
    "draft_k": (["--draft-k", "0"], "draft_k must be >= 1"),
    "draft_auto": (["--draft-auto", "sometimes"],
                   "draft_auto must be off|on"),
    "auto_needs_drafter": (["--draft-auto", "on"],
                           "pick a drafter or drop it"),
    "mixed_batch": (["--mixed-batch", "maybe"],
                    "mixed_batch must be off|on"),
    "prefill_budget": (["--prefill-budget", "0"],
                       "prefill_budget must be >= 1"),
    "mixed_with_speculative": (["--mixed-batch", "on", "--speculative",
                                "ngram"], "they do not compose"),
    "kv_dtype": (["--kv-dtype", "int2"], "kv dtype must be fp32|int8|int4"),
    "kv_group": (["--kv-group", "0"], "kv_group must be >= 1"),
    "kv_tier": (["--kv-tier", "disk"], "kv_tier must be off|host"),
    "tier_needs_cache": (["--kv-tier", "host"],
                         "no trie paths to key the host store by"),
    "tp": (["--tp", "0"], "tp must be >= 1"),
    "fault_policy": (["--queue-depth", "0", "--drain-ms", "-1"],
                     "bad fault-tolerance policy: queue_depth 0 (>= 1), "
                     "drain_ms -1.0 (>= 0)"),
    "trace": (["--trace", "maybe"], "trace must be off|on"),
    "trace_out_needs_trace": (["--trace-out", "t.json"],
                              "there would be no trace to write"),
    "pool_holds_one_sequence": (["--num-blocks", "4", "--max-seq-len",
                                 "512"], "cannot hold one max_seq_len=512"),
    "workload": (["--workload", "sinusoidal"], "workload must be one of"),
    "trace_size": (["--num-requests", "0"], "serving trace needs >= 1"),
    "rate": (["--rate-rps", "0"], "arrival rate must be > 0"),
    "prefix_tokens": (["--prefix-tokens", "-1"],
                      "prefix_tokens must be >= 0"),
    "slo": (["--slo-ms", "0"], "slo_ms must be > 0"),
    # the engine's own refusals, where the model's widths are known
    "positions": (["--max-seq-len", "256"],
                  "max_seq_len 256 (table capacity 256) exceeds "
                  "max_positions 128"),
    "tp_geometry": (["--tp", "3"], "tp"),
    "no_replica": (["--replicas", "0"], "needs >= 1 engine replica"),
}


def test_one_refusal_per_post_init_rule():
    import inspect

    src = inspect.getsource(ServeConfig.__post_init__)
    assert src.count("raise ValueError") == 23
    assert list(REFUSALS).index("workload") == 23


@pytest.mark.parametrize("rule", list(REFUSALS))
def test_refused_in_words(rule, capsys):
    argv, words = REFUSALS[rule]
    with pytest.raises(SystemExit) as exc:
        entry.main(["--tiny", "--precision", "fp32", *argv])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert words in err, err
    assert "Traceback" not in err


def test_a_fault_past_the_flags_keeps_its_traceback(monkeypatch):
    """Only what a flag can make wrong is a usage error: a ``ValueError``
    from building or serving is the program's, and is not swallowed."""
    def broken(*a, **kw):
        raise ValueError("a shape bug")
    monkeypatch.setattr(entry, "_build", broken)
    with pytest.raises(ValueError, match="a shape bug"):
        entry.main(["--tiny", "--precision", "fp32", "--num-requests", "1",
                    "--prompt-max", "8", "--output-max", "8"])


# --------------------------------------------------- the two surfaces

def test_training_surface_has_no_serving_option():
    from mpi_tensorflow_tpu import cli
    from mpi_tensorflow_tpu.config import Config

    assert not [f.name for f in dataclasses.fields(Config)
                if f.name.startswith("serve")]
    assert not [s for a in cli.build_parser()._actions
                for s in a.option_strings if s.startswith("--serve")]


def test_every_flag_is_a_dataclass_field_or_a_deployment_setting():
    flags = {s for a in entry.build_parser()._actions
             for s in a.option_strings} - {"-h", "--help"}
    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    assert set(SERVE_HELP) == fields, "one line of help per field"
    assert set(loadgen.WORKLOAD_HELP) <= {
        f.name for f in dataclasses.fields(loadgen.WorkloadSpec)}
    derived = {_flag(n) for n in fields | set(loadgen.WORKLOAD_HELP)}
    assert derived <= flags
    assert flags - derived == {"--precision", "--model", "--tiny",
                               "--journal", "--replicas"}
    assert not [a for a in entry.build_parser()._actions if a.choices]


def test_docs_options_table_is_the_parser():
    """docs/SERVING.md's options table is the parser's own rows: every
    flag, with the dataclass's default and the help table's sentence."""
    rows = ["| `%s` | %s |" % (a.option_strings[0],
                               a.help.replace("|", "\\|"))
            for a in entry.build_parser()._actions
            if a.option_strings[0] != "-h"]
    with open(os.path.join(REPO, "docs", "SERVING.md")) as f:
        section = f.read().split("\n## Options\n")[1].split("\n## ")[0]
    docs = [ln for ln in section.splitlines() if ln.startswith("| `--")]
    assert docs == rows, "paste into docs/SERVING.md:\n" + "\n".join(rows)


# ------------------------------------------------------------ serves

BASE = ("--tiny", "--precision", "fp32", "--num-requests", "6",
        "--prompt-max", "12", "--output-max", "16", "--rate-rps", "1000",
        "--max-slots", "4")


def _main(argv, capsys):
    capsys.readouterr()
    assert entry.main(list(argv)) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


_RUNS = {}


def run(capsys, *extra):
    """The JSON line of ``main(BASE + extra)``, served once a module."""
    if extra not in _RUNS:
        _RUNS[extra] = _main(BASE + extra, capsys)
    return _RUNS[extra]


def _served_clean(d, n=6):
    assert d["status_counts"] == {"ok": n}
    assert d["tokens"] == d["tokens_requested"] > 0


class TestServes:
    def test_default_trace(self, capsys):
        d = run(capsys)
        _served_clean(d)
        assert d["zero_recompile_steady_state"] is True
        assert d["compiles_after_warmup"] == d["compiles_after_served"]
        assert d["kernel"] == d["paths"]["paged_attention"] == "xla"
        assert (d["platform"], d["model"]) == ("cpu", "gpt_tiny")
        assert d["faults"]["rejected"] == 0 and d["goodput"]["requests"] == 6

    def test_pallas_resolves_as_asked_and_serves_the_same_tokens(
            self, capsys):
        d = run(capsys, "--kernel", "pallas")
        _served_clean(d)
        assert d["kernel"] == d["paths"]["paged_attention"] \
            == "pallas-interpret"
        assert d["zero_recompile_steady_state"] is True
        assert d["outputs"] == run(capsys)["outputs"]

    def test_journal_resumes_token_identical(self, capsys, tmp_path):
        """A journal left by a killed run (request 0 three tokens in,
        request 1 submitted) is resumed, not restarted."""
        want = run(capsys)["outputs"]
        path = str(tmp_path / "j.jsonl")
        trace, _ = _plan(BASE)
        reqs = trace.requests()
        j = ReplayJournal(path)
        j.record_submit(reqs[0])
        for tok in want["0"][:3]:
            j.record_token(0, tok)
        j.record_submit(reqs[1])
        j.close()
        d = _main(BASE + ("--journal", path), capsys)
        _served_clean(d)
        assert d["outputs"] == want
        assert d["zero_recompile_steady_state"] is None   # no warm-up
        assert d["goodput"] is None
        with open(path) as f:
            assert sum('"tok"' in ln for ln in f) == d["tokens"]

    def test_two_replicas_equal_the_single_engine(self, capsys):
        d = run(capsys, "--replicas", "2")
        _served_clean(d)
        assert d["outputs"] == run(capsys)["outputs"]
        assert [r["replica"] for r in d["replicas"]] == [0, 1]
        assert sum(r["requests_routed"] for r in d["replicas"]) == 6
        # no zero-recompile claim for a fleet: placement follows load,
        # so a replica may meet a prompt bucket its warm-up did not
        assert d["zero_recompile_steady_state"] is None
        assert set(d["compiles_after_served"]) >= {"r0/decode", "r1/prefill"}

    def test_two_replicas_journaled(self, capsys, tmp_path):
        path = str(tmp_path / "fleet.jsonl")
        d = _main(BASE + ("--replicas", "2", "--journal", path), capsys)
        _served_clean(d)
        assert d["outputs"] == run(capsys)["outputs"]
        assert os.path.exists(path + ".r0") and os.path.exists(path + ".r1")
        # a relaunch finds every request terminal: nothing is served twice
        again = _main(BASE + ("--replicas", "2", "--journal", path), capsys)
        assert again["outputs"] == d["outputs"]

    def test_prefix_cache_hits_and_changes_no_token(self, capsys):
        shared = ("--prefix-tokens", "64")
        on = run(capsys, *shared, "--prefix-cache", "on")
        off = run(capsys, *shared)
        _served_clean(on)
        assert on["prefix"]["enabled"] and on["prefix"]["hit_tokens"] > 0
        assert not off["prefix"]["enabled"]
        assert on["outputs"] == off["outputs"]

    def test_ngram_speculation_equals_off(self, capsys):
        d = run(capsys, "--speculative", "ngram")
        _served_clean(d)
        assert d["speculation"]["enabled"]
        assert d["speculation"]["verify_forwards"] > 0
        assert d["outputs"] == run(capsys)["outputs"]

    def test_mixed_batch_equals_off(self, capsys):
        d = run(capsys, "--mixed-batch", "on")
        _served_clean(d)
        assert d["compiles_after_served"]["mixed"] > 0
        assert d["outputs"] == run(capsys)["outputs"]

    def test_int8_pool_serves(self, capsys):
        d = run(capsys, "--kv-dtype", "int8")
        _served_clean(d)
        assert d["serve"]["kv_dtype"] == "int8"
        assert d["zero_recompile_steady_state"] is True

    def test_bursty_workload_with_slo_fills_goodput(self, capsys):
        d = run(capsys, "--workload", "bursty", "--slo-ms", "60000")
        _served_clean(d)
        gp = d["goodput"]
        assert gp["enabled"] and gp["requests"] == 6
        assert gp["slo_attainment"] == 1.0
        assert d["workload"]["workload"] == "bursty"

    def test_trace_out_is_a_loadable_chrome_trace(self, capsys, tmp_path):
        path = str(tmp_path / "trace.json")
        d = _main(BASE + ("--trace", "on", "--trace-out", path), capsys)
        _served_clean(d)
        assert d["trace"]["spans"] == 6 and d["trace"]["steps"] > 0
        assert d["trace"]["chrome_trace"]["path"] == path
        assert d["breakdown"]["enabled"]
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        assert {e["ph"] for e in events} >= {"b", "e", "X"}
        assert d["outputs"] == run(capsys)["outputs"]

    def test_draft_model_speculation_equals_off(self, capsys):
        d = run(capsys, "--speculative", "draft-model", "--draft-k", "2")
        _served_clean(d)
        assert d["speculation"]["mode"] == "draft-model"
        assert d["outputs"] == run(capsys)["outputs"]

    def test_host_tier_rides_the_prefix_cache(self, capsys):
        d = run(capsys, "--prefix-tokens", "64", "--prefix-cache", "on",
                "--kv-tier", "host")
        _served_clean(d)
        assert d["tier"]["enabled"] and d["tier"]["mode"] == "host"
        assert d["outputs"] == run(capsys, "--prefix-tokens", "64")["outputs"]

    def test_smaller_prefill_chunk_changes_no_token(self, capsys):
        d = run(capsys, "--prefill-chunk", "4")
        _served_clean(d)
        assert ["prefill", 4] in d["dispatch_shapes"]
        assert d["outputs"] == run(capsys)["outputs"]

    def test_eos_id_ends_a_sequence_early(self, capsys):
        want = run(capsys)["outputs"]
        eos = want["0"][2]
        d = run(capsys, "--eos-id", str(eos))
        assert d["status_counts"] == {"ok": 6}
        assert d["outputs"]["0"] == want["0"][:want["0"].index(eos) + 1]
        assert d["tokens"] < d["tokens_requested"]

    def test_bounded_queue_sheds_the_newest(self, capsys):
        """Six arrivals at once (a million a second: the first iteration
        finds them all, however fast the host turns), four slots, one
        queue place: the rest are shed with a status, and what was
        admitted is served whole."""
        d = run(capsys, "--queue-depth", "1", "--rate-rps", "1000000")
        assert d["faults"]["shed"] > 0
        assert d["status_counts"]["ok"] + d["faults"]["shed"] == 6
        assert len(d["statuses"]) == 6
        want = run(capsys)["outputs"]
        assert all(d["outputs"][rid] == want[rid]
                   for rid, st in d["statuses"].items() if st == "ok")

    def test_deadline_fails_late_work_with_a_status(self, capsys):
        d = run(capsys, "--deadline-ms", "0.001")
        assert d["faults"]["deadline_exceeded"] > 0
        assert len(d["statuses"]) == 6
        assert d["tokens"] < d["tokens_requested"]
