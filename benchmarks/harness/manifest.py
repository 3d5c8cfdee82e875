"""Reads ``BENCHMARK.json`` and finds what belongs to one cell by name:
``configs/<config>.json`` (through the manifest's ``file``),
``traffic/<traffic>.json``, ``limits/<cell>.json`` and, for each metric,
``metrics/<metric>.py``.  A new cell, configuration, mix or metric is new
files plus new manifest entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(man: dict, name: str) -> dict:
    """The workload ``name`` with its configuration, traffic mix and
    limits loaded."""
    for w in man["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: "
                       f"{[w['name'] for w in man['workloads']]})")
    for c in man["configs"]:
        if c["name"] == w["config"]:
            break
    else:
        raise KeyError(f"workload {name!r} names unknown config "
                       f"{w['config']!r}")
    out = dict(w)
    out["config_data"] = load_json(os.path.join(ROOT, c["file"]))
    out["traffic_data"] = load_json(
        os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = os.path.join(BENCH, "limits", name + ".json")
    out["limits"] = load_json(limits)["limits"] if os.path.exists(limits) \
        else {}
    return out


def _listed(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def metrics_of(man: dict, cell_name: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in man[group] if _listed(m, cell_name)]


def reader(metric_name: str):
    """The ``read(run)`` function of ``metrics/<metric_name>.py``."""
    path = os.path.join(BENCH, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
