"""Model-flops accounting (utils/flops.py) — the MFU numbers bench.py
reports.  Golden values computed by hand from the documented formulas so
a silent formula change shows up as a test diff, not a quietly wrong
utilization claim."""

import dataclasses as dc

import pytest

from mpi_tensorflow_tpu.models import bert
from mpi_tensorflow_tpu.utils import flops as fl

pytestmark = pytest.mark.quick


def test_bert_base_flagship_golden():
    # E=768 L=12 M=3072 V=30522, B=64 S=128, packed capacity 32:
    # enc  = 6*64*128*12*(4*768^2 + 2*768*3072) = 4.175e12
    # attn = 12*12*64*128^2*768                 = 1.160e11
    # head = 6*64*32*(768^2 + 30522*768)        = 2.953e11
    f = fl.transformer_train_flops(bert.BERT_BASE, 64, 128)
    assert f == pytest.approx(4.586e12, rel=1e-3)


def test_causal_counts_every_head_position():
    f_packed = fl.transformer_train_flops(bert.BERT_BASE, 64, 128)
    f_all = fl.transformer_train_flops(bert.BERT_BASE, 64, 128,
                                       head_positions=128)
    # head cost scales 32 -> 128 positions; the rest is identical
    assert f_all - f_packed == pytest.approx(
        6 * 64 * (128 - 32) * (768**2 + 30522 * 768))


def test_attention_term_is_quadratic_in_seq():
    cfg = dc.replace(bert.BERT_BASE, ce_positions="all")
    b, s = 4, 512

    def attn_only(S):
        full = fl.transformer_train_flops(cfg, b, S, head_positions=0)
        # subtract the linear-in-S encoder matmul term
        layer_mm = 4 * cfg.hidden**2 + 2 * cfg.hidden * cfg.mlp
        return full - 6 * b * S * cfg.layers * layer_mm

    assert attn_only(2 * s) == pytest.approx(4 * attn_only(s))


def test_encdec_flops_accounting():
    cfg = dc.replace(bert.BERT_TINY, ce_positions="all")
    B, S, T, n_dec = 4, 16, 12, 2
    f = fl.encdec_train_flops(cfg, n_dec, B, S, T)
    E, M, V = cfg.hidden, cfg.mlp, cfg.vocab_size
    enc = fl.transformer_train_flops(cfg, B, S, head_positions=0)
    dec_mm = 6 * n_dec * (B * T * (6 * E * E + 2 * E * M)
                          + B * S * 2 * E * E)
    attn = 12 * n_dec * B * E * (T * T + T * S)
    head = 6 * B * T * V * E
    assert f == pytest.approx(enc + dec_mm + attn + head)
    # the cross-attention term scales with T*S: doubling S adds exactly
    # the cross + encoder + cross-KV deltas, nothing quadratic in T
    f2 = fl.encdec_train_flops(cfg, n_dec, B, 2 * S, T)
    enc2 = fl.transformer_train_flops(cfg, B, 2 * S, head_positions=0)
    want_delta = (enc2 - enc) + 12 * n_dec * B * E * T * S \
        + 6 * n_dec * B * S * 2 * E * E
    assert f2 - f == pytest.approx(want_delta)


def test_image_flops_and_unknown_model():
    assert fl.image_train_flops("resnet50", 32) == \
        pytest.approx(3 * 8.2e9 * 32)
    assert fl.image_train_flops("not_a_model", 32) is None


def test_mfu_pct():
    from types import SimpleNamespace

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    # 98.5 TFLOP/s of bf16 on a 197 TFLOP/s chip = 50%
    assert fl.mfu_pct(98.5e12 * 0.1, 0.1, "bf16", v5e) == pytest.approx(50.0)
    assert fl.mfu_pct(None, 0.1, "bf16", v5e) is None
    # no published peak for the precision -> no percentage
    assert fl.mfu_pct(1e12, 0.1, "fp32", v5e) is None
    # a CPU run must not claim an MFU
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    assert fl.mfu_pct(1e12, 0.1, "bf16", cpu) is None


def test_unknown_device_kind_raises():
    """A TPU that is not in the peaks table is an error, never scored
    against another chip's peak."""
    from types import SimpleNamespace

    with pytest.raises(fl.UnknownDeviceError, match="TPU v9"):
        fl.device_peaks("TPU v9")
    v9 = SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(fl.UnknownDeviceError):
        fl.mfu_pct(1e12, 0.1, "bf16", v9)
    assert fl.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0


def test_bench_detail_carries_flops_and_gates_mfu_by_platform(monkeypatch):
    import bench

    monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
    r = bench.measure_bert(batch_size=2, steps=2, precision="fp32",
                           scan_steps=1, seq_len=32)
    assert r["model_flops_per_step"] > 0
    # raw flops always recorded; the percentage only against the real chip
    assert r["mfu_pct"] is None      # tests run on the CPU mesh
