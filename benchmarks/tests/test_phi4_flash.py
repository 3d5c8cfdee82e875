"""``harness/models/phi4_flash.py``: the six functions of the seam, the
catalog's numbers in the configuration file, the parameter count from the
built tree, and the flops and bytes against hand arithmetic at the
published widths."""

import contextlib
import json

import pytest

from benchmarks import run as run_lib
from benchmarks.harness import device, manifest, models, serve_driver, spans

CELL = "phi4_mini_flash_reasoning.serve_closed128_p256_o8k"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# the catalog entry's ``config`` (model-configs/architectures.jsonl)
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def _cell(rehearse=False):
    cell = manifest.cell(manifest.manifest(), CELL)
    if rehearse:
        run_lib.apply_rehearsal(cell)
    return cell


@pytest.fixture(scope="module")
def kind():
    return models.lookup("phi4_flash")


@pytest.fixture()
def sz(kind):
    return kind.sizes(_cell()["config_data"])


def test_the_seam_gives_the_six_functions(kind):
    for name in ("sizes", "build", "init_params", "request_flops",
                 "cache_bytes", "reference_logits"):
        assert callable(getattr(kind, name)), name


def test_the_file_holds_the_catalog_and_reduces_nothing():
    cfg = _cell()["config_data"]
    assert {k: cfg.get(k) for k in CATALOG} == CATALOG
    assert cfg["reduced"] == []
    man = manifest.manifest()
    entry = next(c for c in man["configs"]
                 if c["name"] == "phi4_mini_flash_reasoning")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    work = next(w for w in man["workloads"] if w["name"] == CELL)
    assert work["chips"] == 1


def test_the_mix_is_the_issues(kind):
    mix = _cell()["traffic_data"]
    assert (mix["kind"], mix["clients"], mix["think_time_s"],
            mix["shared_prefix_tokens"]) == ("closed_loop", 128, 0.0, 0)
    assert mix["prompt"] == {"dist": "lognormal", "lo": 256, "hi": 2048}
    assert mix["output"] == {"dist": "lognormal", "lo": 512, "hi": 8192}
    assert mix["engine"] == {"max_slots": 128, "max_seq_len": 10240,
                             "block_size": 256, "num_blocks": 1537,
                             "prefill_chunk": 512}
    assert mix["serve_overrides"] == {} and mix["warmup_finished"] == 128
    assert (mix["check_requests"], mix["trace_seconds"]) == (4, 4)
    others = [manifest.cell(manifest.manifest(), w["name"])["traffic_data"]
              .get("length_seed") for w in manifest.manifest()["workloads"]
              if w["name"] != CELL]
    assert mix["length_seed"] not in others


def test_parameters_from_the_built_tree(kind, sz):
    """ISSUE 32's table from the program's own ``init``: 3.85 B +- 1%,
    and every matrix where the hand count has it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tree = jax.eval_shape(kind.build(sz, jnp.bfloat16).init,
                          jax.random.key(0))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert abs(total / 3.852e9 - 1) < 0.01
    p = kind.mixer_params(sz)
    assert p["mlp"] == 3 * 2560 * 10240 == 78_643_200
    assert p["window"] == p["full"] == 2560 * 5120 + 2560 * 2560
    assert p["cross"] == 2 * 2560 * 2560 and p["gmu"] == 2 * 2560 * 5120
    assert p["mamba"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 \
        + 5120 * 2560
    assert kind.layer_counts(sz) == {"mamba": 9, "window": 8, "full": 1,
                                     "gmu": 7, "cross": 7}
    mats = kind.matrix_params(sz)
    assert mats == 200064 * 2560 + 32 * p["mlp"] + 9 * p["mamba"] \
        + 9 * p["window"] + 7 * p["cross"] + 7 * p["gmu"]
    # what is no matrix: norms, biases, A_log, D, convolution, lambdas
    assert 0 < total - mats < 0.002 * total
    pools = kind.build(sz, jnp.bfloat16).pool_leaves(1537, 256, "fp32", 128)
    held = {}
    for layer in pools:
        for key, s in layer.items():
            held[key] = held.get(key, 0) \
                + int(np.prod(s.shape)) * s.dtype.itemsize
    assert held["k"] + held["v"] == 1537 * 256 * 5120          # 2.01 GB
    assert held["win_k_slot"] + held["win_v_slot"] \
        == 8 * 129 * 512 * 5120                                # 2.71 GB
    assert held["ssm_slot"] + held["conv_slot"] \
        == 9 * 129 * 5120 * (16 * 4 + 3 * 2)                   # 0.42 GB


def test_request_flops_by_hand(kind, sz):
    own, cross = kind.token_flops(sz)
    p = kind.mixer_params(sz)
    kv = 2560 * 2560
    assert own == 2.0 * (9 * p["mamba"] + 8 * p["window"] + 17 * p["mlp"]
                         + kv) + 9 * (8 + 96) * 5120
    assert cross == 2.0 * (p["full"] - kv + 7 * p["cross"] + 7 * p["gmu"]
                           + 15 * p["mlp"])
    pair = 6 * 40 * 64
    assert kind.pair_flops(sz) == pair
    head = 2 * 200064 * 2560
    # a 4-token prompt whose outputs 0..1 fell in the window: 3 prompt
    # tokens that emit nothing run the self-decoder alone (1 + 2 + 3
    # window pairs a layer), then the tokens at positions 3 and 4 emit:
    # 4 + 5 keys in each window layer and in each of the 8 passes over
    # the full layer's cache
    want = 3 * own + pair * 8 * 6 \
        + 2 * (own + cross + head) + pair * (8 * 9 + 8 * 9)
    assert kind.request_flops(sz, 4, 0, 1, True) == want
    # decode only, far past the window: outputs 1000..1001 of a
    # 600-token prompt sit at positions 1599 and 1600
    want = 2 * (own + cross + head) + pair * (8 * 2 * 512
                                              + 8 * (1600 + 1601))
    assert kind.request_flops(sz, 600, 1000, 1001, False) == want


def test_cache_and_kernel_bounds_by_hand(kind, sz):
    row = 2 * 1280 * 2
    state = 9 * 5120 * (16 * 4 + 3 * 2)
    assert kind.state_bytes(sz) == state
    assert kind.cache_bytes(sz, [1400, 300], 2) \
        == 1700 * row * 8 + (512 + 300) * row * 8 + 2 * 2 * state
    # ISSUE 32's step: 128 rows at 1,400 live tokens
    assert kind.cache_bytes(sz, [1400] * 128, 2) \
        == 128 * (1400 + 512) * row * 8 + 2 * 128 * state
    assert abs(kind.cache_bytes(sz, [1400] * 128, 2) / 1e9
               - (7.34 + 2.68 + 0.83)) < 0.01
    assert kind.diff_attn_decode_least_s(sz, 1e6, 2e5, PEAKS) \
        == (1e6 * 8 + 2e5 * 8) * row / 819e9


def test_new_metric_readers_read_nothing_without_a_log():
    """On a program without the dispatch log's extras (the parent's), or
    a run that logged nothing, the readers return None and do not
    raise."""
    run = {"window": (0.0, 1.0), "trace": None, "work": {}, "peaks": PEAKS}
    for name in ("diff_attn_decode_roofline", "prefill_cross_skipped_pct"):
        assert manifest.reader(name)(run) is None, name


def test_new_metric_readers_read_the_log(kind, sz):
    from mpi_tensorflow_tpu.utils import dispatch_log

    class Trace:
        def op_seconds_matching(self, pattern):
            assert pattern.startswith("^diff_attn_decode")
            return 0.5

    dispatch_log.reset()
    dispatch_log.record(0.1, "prefill", 512, 0, {
        "scanned": 512, "window_keys": 10, "full_keys": 0,
        "skipped_lanes": 512})
    dispatch_log.record(0.2, "prefill", 88, 0, {
        "scanned": 88, "window_keys": 10, "full_keys": 600,
        "skipped_lanes": 87})
    dispatch_log.record(0.3, "decode", 2, 0, {
        "scanned": 2, "window_keys": 1024, "full_keys": 3000,
        "skipped_lanes": 0})
    dispatch_log.record(5.0, "decode", 2, 0, {          # past the window
        "scanned": 2, "window_keys": 1024, "full_keys": 3000,
        "skipped_lanes": 0})
    run = {"window": (0.0, 1.0), "trace": Trace(), "work": {},
           "peaks": PEAKS}
    assert manifest.reader("prefill_cross_skipped_pct")(run) \
        == 100.0 * 599 / 600
    least = (3600 * 8 + 1024 * 8) * 5120 / 819e9
    assert manifest.reader("diff_attn_decode_roofline")(run) \
        == 100.0 * least / 0.5
    dispatch_log.reset()


def test_the_ramp_serves_and_the_reference_agrees():
    import jax

    sc = serve_driver.ServeCell(_cell(rehearse=True), jax.devices()[:1], 1,
                                False)
    sc.prewarm()
    serve_driver.closed_loop(sc, spans.Spans(False), 0.0,
                             contextlib.nullcontext, device.CompileCounter())
    fin = [r for r in sc.records.values() if r["status"] == "ok"]
    assert len(fin) >= 8
    assert sc.engine.sched.evictions == 0
    gap = serve_driver.served_gap_of(
        sc.kind.reference_logits, sc.make_params(jax.random.key(1)), fin, 4,
        1, stats := {"control": "fp8"})
    assert gap < 1e-3 < stats["control_gap"]
    assert json.dumps(sorted(sc.engine.dispatch_shapes))
