"""Analytic per-step FLOP accounting for the benchmark families.

Used by bench.py to report model FLOPS utilization (MFU) next to every
throughput number — raw flops are recorded too, so any peak can re-derive
the percentage.  Analytic (not compiler-reported) on purpose: XLA's cost
model counts implementation flops (rematerialization, fused epilogues),
while MFU is defined against MODEL flops — the work the math requires,
not the work the compiler chose to do.

Formulas (standard accounting, e.g. the PaLM appendix convention):
- a dense matmul with N parameters costs ``2·N`` flops per token forward,
  ``6·N`` forward+backward (backward does two matmuls per forward one);
- attention scores + weighted values cost ``4·B·S²·E`` forward per layer
  (2 for QKᵀ, 2 for AV), ``12·B·S²·E`` with backward;
- the embedding gather is free; the TIED vocab decoder is a real matmul
  and is counted at the positions that reach the head (the packed
  capacity for the MLM families, every position for the causal family).
"""

from __future__ import annotations

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# A device that is not here is an error, and a precision with no
# published peak has no percentage — never a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and
    # 819 GB/s of HBM bandwidth per chip.  No fp32 matmul peak is
    # published.
    "TPU v5 lite": {"tflops": {"bf16": 197.0}, "hbm_gbps": 819.0},
}


class UnknownDeviceError(ValueError):
    """The device's ``device_kind`` has no entry in ``DEVICE_PEAKS``."""


def device_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises for a device that
    is not in the table (add it with its source, do not guess)."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks recorded for device_kind "
            f"{device_kind!r} (known: {sorted(DEVICE_PEAKS)}); add it to "
            f"utils/flops.DEVICE_PEAKS with its source") from None


# fwd-only GFLOPs per image at the bench input geometry (canonical
# published MACs x 2).  fwd+bwd = 3x.
_IMAGE_FWD_GFLOPS = {
    "resnet50": 8.2,      # 4.09 GMAC @ 224x224
    "resnet20": 0.082,    # 41 MMAC @ 32x32
    "mnist_cnn": 0.024,   # 2 convs + fc on 28x28 (computed from geometry)
}


def transformer_train_flops(cfg, batch: int, seq_len: int,
                            head_positions: int | None = None) -> float:
    """Model flops for ONE fwd+bwd train step of the shared transformer
    stack (models/bert.py geometry).  ``head_positions``: tokens reaching
    the MLM head per sequence (packed capacity; default = the model's
    ce_capacity rule for the MLM families, S for causal)."""
    E, L, M, V = cfg.hidden, cfg.layers, cfg.mlp, cfg.vocab_size
    B, S = batch, seq_len
    # per-layer matmul params: QKV + out proj (4·E²) + MLP (2·E·M)
    layer_mm = 4 * E * E + 2 * E * M
    enc = 6 * B * S * L * layer_mm          # encoder matmuls, fwd+bwd
    attn = 12 * L * B * S * S * E           # scores + AV, fwd+bwd
    if head_positions is None:
        if getattr(cfg, "ce_positions", "all") == "masked":
            from mpi_tensorflow_tpu.models.bert import ce_capacity

            head_positions = ce_capacity(cfg, S)
        else:
            head_positions = S
    P = B * head_positions
    head = 6 * P * (E * E + V * E)          # transform + tied decoder
    return float(enc + attn + head)


def encdec_train_flops(cfg, n_dec: int, batch: int, src_len: int,
                       tgt_len: int) -> float:
    """One fwd+bwd step of the encoder-decoder family (models/encdec.py):
    the shared encoder accounting (head zeroed) + decoder layers
    (self-attn QKV/out and MLP at T; cross q/out at T; cross k/v at S)
    + causal self-attention (T²), cross-attention (T·S), and the tied
    vocab head over every target position."""
    E, M, V = cfg.hidden, cfg.mlp, cfg.vocab_size
    B, S, T = batch, src_len, tgt_len
    enc = transformer_train_flops(cfg, B, S, head_positions=0)
    dec_mm = 6 * n_dec * (B * T * (6 * E * E + 2 * E * M)
                          + B * S * 2 * E * E)
    attn = 12 * n_dec * B * E * (T * T + T * S)
    head = 6 * B * T * V * E
    return float(enc + dec_mm + attn + head)


def vit_train_flops(vcfg, batch: int) -> float:
    """One fwd+bwd step of the ViT family (models/vit.py): the SHARED
    encoder-layer accounting (transformer_train_flops with the vocab
    head zeroed — ViT drives the same layers, so the same coefficients)
    at sequence N = patches + CLS, plus the patch projection; the
    classification head is negligible."""
    from types import SimpleNamespace

    N = vcfg.num_patches + 1
    body = transformer_train_flops(
        SimpleNamespace(hidden=vcfg.hidden, layers=vcfg.layers,
                        mlp=vcfg.mlp, vocab_size=0),
        batch, N, head_positions=0)
    patch = 6 * batch * vcfg.num_patches \
        * (vcfg.patch ** 2 * vcfg.channels) * vcfg.hidden
    return float(body + patch)


def image_train_flops(model_name: str, batch: int) -> float | None:
    """Model flops for one fwd+bwd step of an image family, or None when
    the model has no canonical number recorded."""
    g = _IMAGE_FWD_GFLOPS.get(model_name)
    if g is None:
        return None
    return 3.0 * g * 1e9 * batch


def mfu_pct(flops_per_step: float | None, step_seconds: float,
            precision: str, device) -> float | None:
    """Achieved model-flops rate as % of ``device``'s published peak for
    ``precision`` ("bf16" | "fp32").  ``device`` is a JAX device (its
    ``platform`` and ``device_kind`` are read).  None when the flops are
    unknown, when no peak is published for the precision, or off TPU (a
    CPU run has no MFU to claim); a TPU whose kind is not in
    ``DEVICE_PEAKS`` raises rather than being scored against another
    chip's peak."""
    if device.platform != "tpu" or not flops_per_step or step_seconds <= 0:
        return None
    peak = device_peaks(device.device_kind)["tflops"].get(precision)
    if not peak:
        return None
    return 100.0 * flops_per_step / step_seconds / (peak * 1e12)
