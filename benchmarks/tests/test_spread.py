"""``benchmarks/spread.py``: the driver's estimator on hand-made sets."""

import json

import pytest

from benchmarks import spread


@pytest.mark.parametrize("values, want", [
    ([100, 101, 102, 110], 2),          # the far run goes
    ([100, 101, 102, 90], 2),           # on either side
    ([1, 1, 5, 9, 9], 8),               # leaving one out narrows nothing
    ([3, 3, 3], 0),
    ([7], 0),
    ([1, 4], 3),                        # two runs: nothing to leave out
])
def test_spread_leaves_out_the_farthest_run_only_where_that_narrows(
        values, want):
    assert spread.spread(values) == want
    assert spread.spread(list(reversed(values))) == want


def test_iqr_is_pythons_quartiles():
    import statistics

    v = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert spread.iqr(v) == q3 - q1


A = [99.75, 100, 100, 100.25, 103]      # spread 0.5 without 103
B = [99.25, 100, 100, 100.75, 90]       # spread 1.5 without 90


def test_two_sets_mean_share_and_ok_at_exactly_a_half():
    rep = spread.report([{"m": A}, {"m": B}], {"m": 0.02})["m"]
    assert [s["spread"] for s in rep["sets"]] == [0.5, 1.5]
    assert [s["share"] for s in rep["sets"]] == [0.25, 0.75]
    assert rep["mean_share"] == 0.5 and rep["ok"] is True
    worse = B[:3] + [100.76, 90]
    rep = spread.report([{"m": A}, {"m": worse}], {"m": 0.02})["m"]
    assert rep["mean_share"] > 0.5 and rep["ok"] is False


def test_one_set_has_a_share_and_no_verdict():
    rep = spread.report([{"m": A}], {"m": 0.02})["m"]
    assert rep["sets"][0]["share"] == 0.25 and "ok" not in rep


def test_setup_is_judged_by_its_medians_alone():
    sets = [{"setup_s": [170.0, 76.0, 77.0, 78.0]},
            {"setup_s": [171.0, 80.0, 81.0, 82.0]}]
    rep = spread.report(sets, {"setup_s": 0.1}, skip_first_setup=True)
    assert rep["setup_s"]["medians"] == [77.0, 81.0]
    assert rep["setup_s"]["ok"] is True
    sets[1]["setup_s"] = [171.0, 90.0, 91.0, 92.0]
    rep = spread.report(sets, {"setup_s": 0.1}, skip_first_setup=True)
    assert rep["setup_s"]["ok"] is False


def _line(value, sub=None):
    line = {"correct": True, "metrics": {"m": {"value": value, "unit": "x"}}}
    if sub is not None:
        line["counts"] = {"sub_20": {"m": sub, "iterations": 3,
                                     "decode_rows_mean": None}}
    return json.dumps(line)


def test_reads_result_lines_out_of_a_log_and_sub_windows(tmp_path, capsys):
    log = "\n".join(["[setup] noise", "{not json", _line(100, 1.0),
                     '{"other": 1}', _line(101, 2.0), _line(102, 3.0)])
    lines = spread.result_lines(log)
    assert spread.values_of(lines)["m"] == [100.0, 101.0, 102.0]
    assert spread.values_of(lines, "20")["m"] == [1.0, 2.0, 3.0]
    man = tmp_path / "BENCHMARK.json"
    man.write_text(json.dumps({"end_to_end": [{"name": "m", "bound": 0.02}]}))
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    a.write_text("\n".join(_line(v) for v in A))
    b.write_text("\n".join(_line(v) for v in B))
    assert spread.main([str(a), str(b), "--manifest", str(man)]) == 0
    assert "ok=True" in capsys.readouterr().out
    b.write_text("\n".join(_line(v) for v in B[:3] + [101.5, 90]))
    assert spread.main([str(a), str(b), "--manifest", str(man),
                        "--json"]) == 1
    assert json.loads(capsys.readouterr().out)["m"]["ok"] is False
