"""Operations and bytes the algorithms need, computed from shapes.

Model flops, not compiler flops: a matmul with N parameters costs 2 N per
token forward and 6 N forward and backward; attention scores and weighted
values cost 4 x context x hidden per token and layer forward.  The
embedding gather is free; the tied decoder is a real matmul, counted at
the positions that reach it.  (``train_step_flops`` is a copy of
``mpi_tensorflow_tpu/utils/flops.transformer_train_flops``.)
"""

from __future__ import annotations


def train_step_flops(sz: dict, batch: int, seq_len: int,
                     head_positions: int) -> float:
    """One forward+backward step.  ``head_positions``: tokens per sequence
    that reach the head (the packed capacity for masked-LM)."""
    E, L, M, V = sz["hidden"], sz["layers"], sz["mlp"], sz["vocab"]
    layer_mm = 4 * E * E + 2 * E * M
    enc = 6 * batch * seq_len * L * layer_mm
    attn = 12 * L * batch * seq_len * seq_len * E
    head = 6 * batch * head_positions * (E * E + V * E)
    return float(enc + attn + head)


def serve_token_flops(sz: dict, context: int, with_head: bool) -> float:
    """Forward flops of one token that attends over ``context`` cached
    positions (itself included); the head counts where a token is
    emitted."""
    E, L, M, V = sz["hidden"], sz["layers"], sz["mlp"], sz["vocab"]
    f = 2 * L * (4 * E * E + 2 * E * M) + 4 * L * context * E
    if with_head:
        f += 2 * (E * E + V * E)
    return float(f)


def serve_request_flops(sz: dict, prompt_len: int, first: int,
                        last: int, with_prompt: bool) -> float:
    """Flops of one request's work inside a window: its prompt (when the
    prefill fell inside) and its output tokens number ``first`` to
    ``last`` (0-based, inclusive).  Output token ``j`` is emitted by the
    forward of the token at position ``prompt_len - 1 + j``."""
    E, L, M, V = sz["hidden"], sz["layers"], sz["mlp"], sz["vocab"]
    mm = 2 * L * (4 * E * E + 2 * E * M)
    head = 2 * (E * E + V * E)
    total = 0.0
    if with_prompt and prompt_len > 1:
        n = prompt_len - 1          # prompt tokens that emit nothing
        total += n * mm + 4 * L * E * (n * (n + 1) // 2)
    if last >= first:
        n = last - first + 1
        ctx_lo = prompt_len + first
        ctx_sum = n * ctx_lo + n * (n - 1) // 2
        total += n * (mm + head) + 4 * L * E * ctx_sum
    return float(total)


def paged_attention_bytes(sz: dict, contexts, kv_bytes: int = 2) -> float:
    """K and V bytes one paged-attention pass over all layers must read
    for rows whose live contexts are ``contexts`` (cached positions per
    row): 2 x hidden x ``kv_bytes`` per position and layer."""
    return float(sum(contexts)) * 2 * sz["hidden"] * kv_bytes * sz["layers"]
