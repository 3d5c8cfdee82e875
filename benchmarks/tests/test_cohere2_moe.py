"""``harness/models/cohere2_moe.py``: the six functions of the seam, the
catalog's numbers in the configuration file, and the parameters, flops
and bytes against hand arithmetic at the published widths."""

import contextlib
import json

import pytest

from benchmarks import run as run_lib
from benchmarks.harness import device, manifest, models, serve_driver, spans

CELL = "command_a_plus_05_2026.serve_closed32_p4k_32k"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog entry's ``config`` (model-configs/architectures.jsonl)
CATALOG = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}


def _cell(rehearse=False):
    cell = manifest.cell(manifest.manifest(), CELL)
    if rehearse:
        run_lib.apply_rehearsal(cell)
    return cell


@pytest.fixture(scope="module")
def kind():
    return models.lookup("cohere2_moe")


@pytest.fixture()
def sz(kind):
    return kind.sizes(_cell()["config_data"])


def test_the_seam_gives_the_six_functions(kind):
    for name in ("sizes", "build", "init_params", "request_flops",
                 "cache_bytes", "reference_logits"):
        assert callable(getattr(kind, name)), name


def test_the_file_holds_the_catalog_but_for_what_reduced_lists():
    cfg = _cell()["config_data"]
    differs = {k for k, v in CATALOG.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: CATALOG[k] for k in cfg["reduced"]}
    dep = cfg["deployment"]
    assert dep["router_width"] == CATALOG["num_experts"]
    assert dep["experts_held"] == [0, cfg["num_experts"]] == [0, 16]
    assert dep["vocab_rows_held"] == [0, cfg["vocab_size"]]
    assert dep["chips_per_layer"] * cfg["vocab_size"] \
        == CATALOG["vocab_size"]
    assert dep["chips_per_layer"] * cfg["num_experts"] \
        == CATALOG["num_experts"]
    # the cut is one whole period of the published layer types
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == PERIOD
    man = manifest.manifest()
    entry = next(c for c in man["configs"]
                 if c["name"] == "command_a_plus_05_2026")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    for key in ("average", "expert_width", "nope_full_layers", "layernorm",
                "rotary", "weights"):
        assert key in cfg["assumed"], key


def test_sizes_and_the_share(sz):
    assert (sz["router_width"], sz["experts_first"], sz["experts_held"],
            sz["top_k"], sz["shared"]) == (128, 0, 16, 8, 4)
    assert (sz["layers"], sz["window_layers"], sz["vocab"],
            sz["window"]) == (4, (0, 1, 2), 32768, 4096)
    assert (sz["heads"], sz["kv_heads"], sz["head_dim"],
            sz["expert_mlp"]) == (128, 8, 128, 4096)


def test_parameters_by_hand(kind, sz):
    """ISSUE 34's table: 344.5 M a layer outside the routed experts, 50.3 M
    an expert, 1.150 B a layer as this chip holds it, 9.47 GB of weights;
    the published model 218 B."""
    attn = 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert kind.attention_params(sz) == attn == 142_606_336
    assert kind.expert_params(sz) == 3 * 4096 * 4096 == 50_331_648
    outside = attn + 128 * 4096 + 4 * 50_331_648
    assert outside == 344_457_216
    assert kind.layer_params(sz) == outside + 16 * 50_331_648 \
        == 1_149_763_584
    total = 4 * kind.layer_params(sz) + 32768 * 4096
    assert round(2 * total / 1e7) / 100 == 9.47
    published = 32 * (outside + 128 * 50_331_648) + 262144 * 4096
    assert round(published / 1e9) == 218
    # what one token touches: the held share of a routed expert is
    # 8 x 16 / 128 = one expert
    assert kind.token_matmul_flops(sz) == 2.0 * 4 * (outside + 50_331_648)


def test_request_flops_by_hand(kind, sz):
    mm = kind.token_matmul_flops(sz)
    pair = 4 * 128 * 128
    assert kind.pair_flops(sz) == pair
    head = 2 * 32768 * 4096
    # a 4-token prompt whose outputs 0..1 fell in the window: 3 prompt
    # tokens that emit nothing (1 + 2 + 3 pairs in each of the 4 layers:
    # far inside the window), then the tokens at positions 3 and 4
    want = 3 * mm + pair * 6 * 4 + 2 * (mm + head) + pair * 9 * 4
    assert kind.request_flops(sz, 4, 0, 1, True) == want
    # past the window: a token at position 5000 sees 5001 keys in the
    # full layer and 4096 in each window layer
    assert kind.request_flops(sz, 5001, 0, 0, False) \
        == mm + head + pair * (5001 + 3 * 4096)
    assert kind.window_pairs(4090, 4100, 4096) \
        == sum(min(p + 1, 4096) for p in range(4090, 4100))


def test_cache_and_kernel_bounds_by_hand(kind, sz):
    row = 2 * 8 * 128 * 2                      # K and V of a token, bf16
    assert kind.kv_row_bytes(sz) == row == 4096
    assert kind.cache_bytes(sz, [10000, 24], 2) \
        == (10024 + 3 * (4096 + 24)) * row
    assert kind.gqa_decode_least_s(sz, 1e6, 2e6, PEAKS) \
        == (1e6 + 3 * 2e6) * row / 819e9
    work = {"full_keys": 1e9, "window_keys": 5e8, "full_rows": 4e4,
            "window_rows": 1e4, "queries": 2048}
    fl = 4 * 128 * 128 * (1e9 + 3 * 5e8) / 197e12
    by = ((4e4 + 3 * 1e4) * row + 2048 * 4 * 2 * 128 * 128 * 2) / 819e9
    assert kind.gqa_prefill_least_s(sz, work, PEAKS) == max(fl, by) == fl


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def op_seconds_matching(self, pattern):
        import re

        return sum(v for k, v in self.seconds.items()
                   if re.search(pattern, k))


def test_the_roofline_readers(kind, sz, monkeypatch):
    """Without a log the readers return None; with one they divide the
    obliged work by their kernel's time, found by name."""
    run = {"window": (0.0, 1.0), "trace": None, "work": {}, "peaks": PEAKS}
    for name in ("gqa_decode_roofline", "gqa_prefill_roofline"):
        assert manifest.reader(name)(run) is None, name
    extra = {"full_keys": 10, "window_keys": 5, "full_rows": 10,
             "window_rows": 5, "window_keys_skipped": 5}
    log = [[0.5, "decode", 2, 10, None, None, extra],
           [0.6, "prefill", 4, 10, None, None, extra],
           [1.5, "decode", 2, 10, None, None, extra]]
    monkeypatch.setattr(kind, "window_log",
                        lambda run: (sz, [r for r in log if r[0] < 1.0]))
    run["trace"] = _Trace({"gqa_decode_attention.3[tpu_custom_call]": 1e-6,
                           "gqa_prefill_attention[tpu_custom_call]": 1e-6,
                           "fusion.1": 5.0})
    got = manifest.reader("gqa_decode_roofline")(run)
    assert got == pytest.approx(
        100 * kind.gqa_decode_least_s(sz, 10, 5, PEAKS) / 1e-6)
    work = dict(extra, queries=4)
    got = manifest.reader("gqa_prefill_roofline")(run)
    assert got == pytest.approx(
        100 * kind.gqa_prefill_least_s(sz, work, PEAKS) / 1e-6)


def test_the_ramp_serves_and_the_reference_agrees():
    import jax

    sc = serve_driver.ServeCell(_cell(rehearse=True), jax.devices()[:1], 1,
                                False)
    sc.prewarm()
    serve_driver.closed_loop(sc, spans.Spans(False), 0.0,
                             contextlib.nullcontext, device.CompileCounter())
    fin = [r for r in sc.records.values() if r["status"] == "ok"]
    assert len(fin) >= 8
    gap = serve_driver.served_gap_of(
        sc.kind.reference_logits, sc.make_params(jax.random.key(1)), fin, 4,
        1, stats := {"control": "fp8"})
    assert gap < 1e-3 < stats["control_gap"]
    assert json.dumps(sorted(sc.engine.dispatch_shapes))
