"""``trace_reduce`` on a small hand-written trace, against hand-counted
busy, idle and exposed-collective times (see the fixture's comment)."""

import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

RAW = json.load(open(os.path.join(os.path.dirname(__file__), "fixtures",
                                  "small_trace.json")))


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert tr.subtract([(0, 10)], [[2, 3], [5, 12]]) == [(0, 2), (3, 5)]
    assert tr.total(tr.clip([(0, 5), (8, 20)], 2, 10)) == 5


def test_self_time_of_nested_ops():
    by = {n: s for n, _, _, s, _ in tr.self_times(RAW["devices"][0]["ops"])}
    assert by["while.2"] == 400 - 100 - 200
    assert by["fusion.3"] == 100 and by["all-reduce.4"] == 200


def test_reduce_matches_hand_count():
    red = tr.reduce(RAW)
    assert red.n_devices == 2
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx((700 + 600) / 2 * 1e-9)
    assert red.collective_s == pytest.approx((200 + 200) / 2 * 1e-9)
    assert red.collective_exposed_s == pytest.approx((200 + 100) / 2 * 1e-9)
    # idle gaps of device 0, longest first, named by the covering span
    assert [(round(a * 1e9), round(b * 1e9)) for a, b in red.gaps] == \
        [(700, 900), (0, 100)]
    assert red.gap_names == ["feed", "feed"]
    # fusion.5 is clipped to the window: half of its 200 ns
    assert red.op_seconds["fusion.5"] == pytest.approx(100e-9 / 2)
    assert red.op_seconds_matching(r"^fusion") == pytest.approx(
        (200 + 100 + 100 + 500) / 2 * 1e-9)


def test_breakdown_is_short_and_sorted():
    bd = tr.breakdown(tr.reduce(RAW))
    assert len(bd["device_ops"]) <= 10
    kinds = {k: s for k, s in bd["device_ops"] if k.startswith("kind:")}
    # fusion.1 on both devices, fusion.3, and fusion.5 clipped to half
    assert kinds["kind:fusion"] == pytest.approx(
        (200 + 100 + 100 + 500) / 2 * 1e-9)
    assert tr.op_kind("copy.294") == "copy"
    assert tr.op_kind("_decode_impl.21[tpu_custom_call]") == \
        "_decode_impl[tpu_custom_call]"
    assert dict(bd["idle_gaps"])["feed"] == pytest.approx(300e-9)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"devices": [{"name": "d", "ops": []}], "host": []})


def test_op_name_keeps_the_instruction_and_tags_mosaic_kernels():
    hlo = ('%_decode_impl.21 = bf16[128,12,1,64]{3,2,1,0} custom-call(s32[128,64]'
           '{1,0} %copy-done.40), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={}')
    assert tr.op_name(hlo) == "_decode_impl.21[tpu_custom_call]"
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.12"
    assert tr.op_name("all-reduce.4") == "all-reduce.4"


def test_roofline_reader_finds_only_the_mosaic_calls():
    from benchmarks.harness import manifest

    read = manifest.reader("paged_attn_roofline")
    raw = {"devices": [{"name": "/device:TPU:0", "ops": [
        ["_decode_impl.21[tpu_custom_call]", 0, 500], ["copy.1", 500, 500]]}],
        "host": [["window", 0, 1000]]}
    run = {"trace": tr.reduce(raw), "work": {"paged_bytes": 819.0 * 100},
           "peaks": {"hbm_bytes_per_s": 819.0e9}}
    # 81,900 bytes need 100 ns at the peak; the kernel took 500 ns
    assert read(run) == pytest.approx(20.0)
    run["trace"] = tr.reduce({"devices": [{"name": "d", "ops": [
        ["copy.1", 0, 10]]}], "host": []})
    assert read(run) is None
