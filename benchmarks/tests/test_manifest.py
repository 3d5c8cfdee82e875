"""``BENCHMARK.json`` against the contract's rules a test can hold: names
and units, files, and each ``moves`` naming an end-to-end metric that every
listed cell reports."""

import os
import re

from benchmarks.harness import manifest

MAN = manifest.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_names_units():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in MAN["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 4)
    assert "setup_s" in {m["name"] for m in MAN["end_to_end"]}


def test_files_exist_and_lie_under_paths():
    for c in MAN["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in MAN["paths"]))
        data = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
    for w in MAN["workloads"]:
        cell = manifest.cell(MAN, w["name"])
        assert cell["limits"], f"{w['name']} has no limits file"
    for m in MAN["per_layer"]:
        assert callable(manifest.reader(m["name"]))


def test_every_cell_reports_what_the_contract_asks():
    for w in MAN["workloads"]:
        e2e = [m["name"] for m in
               manifest.metrics_of(MAN, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_of(MAN, w["name"], "per_layer")


def test_moves_names_an_end_to_end_metric_each_listed_cell_reports():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    cells = [w["name"] for w in MAN["workloads"]]
    for m in MAN["per_layer"]:
        target = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in cells
            assert "workloads" not in target or c in target["workloads"], \
                (m["name"], c)
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)
