"""Model-flops accounting and the published device peaks (utils/flops.py).
Golden values computed by hand from the documented formulas so a silent
formula change shows up as a test diff, not a quietly wrong utilization
claim: benchmarks/tests/test_flops.py holds the benchmark's copy
(``train_step_mfu_pct``) to ``transformer_train_flops``, and these pin it."""

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


def test_unknown_device_kind_raises():
    """A TPU that is not in the peaks table is an error, never scored
    against another chip's peak."""
    with pytest.raises(fl.UnknownDeviceError, match="TPU v9"):
        fl.device_peaks("TPU v9")
    assert fl.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
