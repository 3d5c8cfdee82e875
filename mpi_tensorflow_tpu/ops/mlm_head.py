"""Chunked tied-decoder softmax cross-entropy for MLM heads.

Role: the loss of the reference is a mean softmax-CE over class logits
(``/root/reference/mpipy.py:55-56``); BERT-MLM scales that to a 30k-class
vocabulary, where the naive formulation materializes a (B, S, V) fp32 logits
tensor (~1 GB at the shape 64x128x30522) that is written to HBM in the
forward pass and re-read three times (logsumexp, label gather, backward).
That HBM round-trip — not FLOPs — dominates the head's cost on TPU.

This op never materializes the full logits: an online-logsumexp
``lax.scan`` walks the tied decoder matrix in vocab chunks, keeping only a
(B, S) running (max, sumexp) pair in fp32, and the gold logit comes from a
direct gather of the label embedding rows.  The scan body is rematerialized
(``jax.checkpoint``) so the backward pass recomputes each chunk's logits
instead of saving them — peak live memory for the head is one
(B, S, chunk) tile.  Gradients flow through the scan by autodiff and are
mathematically the standard softmax-CE gradients.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_BIG = -1e30   # bias for padded vocab rows: exp() underflows to exactly 0


def tied_softmax_ce(t, emb, out_b, labels, *, chunk: int = 2048,
                    dtype=None):
    """Per-position cross entropy of ``logits = t @ emb.T + out_b``.

    t:      (B, S, E) transformed hidden states (compute dtype, e.g. bf16)
    emb:    (V, E)    tied decoder matrix (the token embedding)
    out_b:  (V,)      output bias
    labels: (B, S)    int gold token ids
    Returns (B, S) fp32 ``logsumexp(logits) - logits[labels]`` without ever
    materializing an (..., V) array.  ``chunk`` is the vocab tile width.
    """
    B, S, E = t.shape
    V = emb.shape[0]
    dt = dtype or t.dtype
    nc = -(-V // chunk)
    vp = nc * chunk

    t = t.astype(dt)
    emb_c = jnp.pad(emb, ((0, vp - V), (0, 0))).astype(dt) \
        .reshape(nc, chunk, E)
    bias_c = jnp.pad(out_b.astype(jnp.float32), (0, vp - V),
                     constant_values=_NEG_BIG).reshape(nc, chunk)

    @jax.checkpoint
    def body(carry, xs):
        m, s = carry
        ec, bc = xs
        # one (B, S, chunk) logits tile; matmul in the compute dtype (MXU),
        # reduction bookkeeping in fp32
        lg = jnp.einsum("bse,ce->bsc", t, ec).astype(jnp.float32) + bc
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
        s = s * jnp.exp(m - m_new) \
            + jnp.sum(jnp.exp(lg - m_new[..., None]), axis=-1)
        return (m_new, s), None

    init = (jnp.full((B, S), -jnp.inf, jnp.float32),
            jnp.zeros((B, S), jnp.float32))
    (m, s), _ = lax.scan(body, init, (emb_c, bias_c))
    logz = m + jnp.log(s)

    # gold logit: gather the label rows and contract — (B, S, E) transient,
    # same order of magnitude as the activations themselves
    gold = jnp.einsum("bse,bse->bs", t, emb[labels].astype(dt)) \
        .astype(jnp.float32) + out_b[labels].astype(jnp.float32)
    return logz - gold


def masked_mean_ce(ce, mask):
    """Mean CE over masked positions (mask: (B, S) bool/float)."""
    w = mask.astype(jnp.float32)
    return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)


def gather_masked_rows(h, labels, mask, capacity: int):
    """Pack each row's masked positions into a fixed-width buffer.

    MLM computes loss only at masked positions (~15% of tokens), yet the
    naive head pays the vocab decoder at every position.  This packs row
    ``b``'s masked positions, first-come, into ``packed[b, :capacity]`` so
    the head transform + decoder run on ``capacity/S`` of the tokens — the
    TPU-shaped equivalent of BERT's ``max_predictions_per_seq``.  Working
    per row keeps the batch dim intact, so data-parallel sharding needs no
    cross-shard communication.  Positions beyond ``capacity`` get weight 0
    (choose ``capacity`` above the mask rate's tail and none are dropped).

    h: (B, S, E), labels/mask: (B, S).  Returns ``(packed_h (B, P, E),
    packed_labels (B, P), weights (B, P) fp32)``.
    """
    B, S, _ = h.shape
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1      # nth masked
    keep = mask & (pos < capacity)
    slot = jnp.where(keep, pos, capacity)                     # overflow col
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    cols = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    idx = jnp.zeros((B, capacity + 1), jnp.int32) \
        .at[rows, slot].set(cols)[:, :capacity]               # source col
    w = jnp.zeros((B, capacity + 1), jnp.bool_) \
        .at[rows, slot].set(keep)[:, :capacity]
    packed = jnp.take_along_axis(h, idx[..., None], axis=1)
    plabels = jnp.take_along_axis(labels, idx, axis=1)
    return packed, plabels, w.astype(jnp.float32)
