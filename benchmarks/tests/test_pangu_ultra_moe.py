"""``harness/models/pangu_ultra_moe.py``: the six functions of the seam,
the catalog's numbers in the configuration file, and the flops and bytes
against hand arithmetic at the published widths."""

import contextlib
import json

import pytest

from benchmarks import run as run_lib
from benchmarks.harness import device, manifest, models, serve_driver, spans

CELL = "openpangu_ultra_moe_718b.serve_closed128_p1k_8k"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog entry's ``config`` (model-configs/architectures.jsonl)
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


def _cell(rehearse=False):
    cell = manifest.cell(manifest.manifest(), CELL)
    if rehearse:
        run_lib.apply_rehearsal(cell)
    return cell


@pytest.fixture(scope="module")
def kind():
    return models.lookup("pangu_ultra_moe")


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
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert cfg["published"] == {k: CATALOG[k] for k in cfg["reduced"]}
    man = manifest.manifest()
    entry = next(c for c in man["configs"]
                 if c["name"] == "openpangu_ultra_moe_718b")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_sizes_and_the_share(sz):
    assert (sz["router_width"], sz["experts_first"], sz["experts_held"],
            sz["top_k"]) == (256, 0, 16, 8)
    assert (sz["layers"], sz["dense_layers"], sz["vocab"]) == (5, 1, 19200)


def test_parameters_by_hand(kind, sz):
    """ISSUE 28's table: 196.6 M of attention a layer, 424.7 M of dense
    MLP, 47.2 M an expert, 804.1 M an expert layer, 4,919 M in all."""
    attn = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
            + 512 * 128 * 256 + 128 * 128 * 7680)
    assert kind.attention_params(sz) == attn == 196_575_232
    assert kind.expert_params(sz) == 3 * 7680 * 2048 == 47_185_920
    layer = 256 * 7680 + 17 * 47_185_920
    total = 5 * attn + 3 * 7680 * 18432 + 4 * layer + 2 * 19200 * 7680
    assert round(total / 1e6) == 4919
    # what one token touches: the held share of a routed expert is
    # 8 x 16 / 256 = half an expert
    touched = 5 * attn + 3 * 7680 * 18432 \
        + 4 * (256 * 7680 + 1.5 * 47_185_920)
    assert kind.token_matmul_flops(sz) == 2.0 * touched


def test_request_flops_by_hand(kind, sz):
    mm = kind.token_matmul_flops(sz)
    pair = 2 * (128 + 64 + 128) * 128 * 5
    assert kind.pair_flops(sz) == pair == 409_600
    head = 2 * 19200 * 7680
    # a 4-token prompt whose outputs 0..1 fell in the window: 3 prompt
    # tokens that emit nothing (1 + 2 + 3 pairs), then the tokens at
    # positions 3 and 4 (contexts 4 and 5) with the head
    want = 3 * mm + pair * 6 + 2 * (mm + head) + pair * 9
    assert kind.request_flops(sz, 4, 0, 1, True) == want
    # decode only, outputs 2..3: contexts 6 and 7
    assert kind.request_flops(sz, 4, 2, 3, False) \
        == 2 * (mm + head) + pair * 13


def test_cache_and_kernel_bounds_by_hand(kind, sz):
    assert kind.cache_bytes(sz, [1000, 24], 2) == 1024 * 576 * 2 * 5
    # decode: the byte bound and the absorbed form's flop bound are
    # within 1 % of each other on this chip
    by = 1e6 * 576 * 2 * 5 / 819e9
    fl = 1e6 * 2 * 128 * (576 + 512) * 5 / 197e12
    assert abs(by / fl - 1) < 0.01
    assert kind.mla_decode_least_s(sz, 1e6, PEAKS) == max(by, fl)
    assert kind.mla_prefill_least_s(sz, 1e6, PEAKS) \
        == 1e6 * 409_600 / 197e12
    # experts: 64 touched, 4 assignments each
    by = (64 * 47_185_920 * 2 + 256 * 2 * 7680 * 2) / 819e9
    fl = 256 * 6 * 7680 * 2048 / 197e12
    assert kind.moe_experts_least_s(sz, 256, 64, PEAKS) == max(by, fl) == by


def test_new_metric_readers_read_nothing_without_a_log():
    """On a program without the dispatch log, or a run that logged
    nothing, the readers return None and do not raise."""
    run = {"window": (0.0, 1.0), "trace": None, "work": {}, "peaks": PEAKS}
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "moe_experts_roofline", "moe_expert_load_max_over_mean"):
        assert manifest.reader(name)(run) is None, name


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
