"""Published per-chip peaks and the analytic model flops of one train step.

``chip_smoke.py`` refuses to start on a device that is not in the peaks
table.  ``transformer_train_flops`` is the program's own statement of the
formula behind the benchmark's ``train_step_mfu_pct``: the benchmark keeps
a copy (``benchmarks/harness/flops.py``) and
``benchmarks/tests/test_flops.py`` holds the copy to this one; the golden
values in ``tests/test_flops.py`` pin it.  Analytic (not compiler-reported)
on purpose: XLA's cost model counts implementation flops
(rematerialization, fused epilogues), while MFU is defined against MODEL
flops — the work the math requires, not the work the compiler chose to do.

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
