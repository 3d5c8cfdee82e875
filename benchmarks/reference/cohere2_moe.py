"""Plain reference of the ``cohere2_moe`` decoder (Command A+): one full
causal forward in float32, no cache, no kernels, nothing imported from the
program.

The block, as the configuration's ``assumed`` states it (no bias
anywhere; LayerNorm has a scale and no bias, epsilon ``layer_norm_eps``):

- every layer is ONE parallel block: ``h = LN(x)``, ``x <- x + Attn(h) +
  MoE(h)``; a final LayerNorm, then logits ``x E^T * logit_scale`` over
  the tied embedding (the vocabulary slice the weights hold);
- attention: ``q = h W_q`` (``heads`` of ``head_dim``), ``k = h W_k``,
  ``v = h W_v`` (``kv_heads`` of ``head_dim``), query head ``i`` reading
  KV head ``i // (heads / kv_heads)``, scores ``q.k / sqrt(head_dim)``,
  softmax, ``concat(p v) W_o``; layer ``i`` is ``layer_types[i]``: a
  ``sliding_attention`` layer rotates q and k on the whole head, GPT-J's
  interleaved pairs (features ``2j, 2j+1`` turned by ``pos *
  theta^(-2j/head_dim)``) and its query at ``p`` sees keys ``p - W < j <=
  p``; a ``full_attention`` layer is causal with no positions;
- ``MoE = 1/2 (sum_e g_e E_e(h) + 1/n sum_s S_s(h))``: ``s = sigmoid(h
  W_r)`` over the router's whole width, the ``top_k`` largest chosen,
  ``g = s / sum of the chosen`` (``norm_topk_prob``), each ``E`` and
  ``S`` a SwiGLU ``W_down(silu(h W_gate) * (h W_up))`` of width
  ``intermediate_size``, the ``n`` shared experts stored side by side as
  one SwiGLU of ``n`` times the width.  THE SHARE: the routed sum runs
  over the chosen experts in ``[first, first + held)`` only (the weights
  hold no others), ``g`` still normalised over all the chosen.

Weights come in as the driver rounds them (bfloat16) and are upcast one
matrix, one expert, one slice of ``COLS`` columns at a time; attention
runs a KV head's group of query heads and a block of queries at a time,
and a window layer's block reads only the band of keys its window can
reach, so that a 33,792-token request fits beside nine gigabytes of
weights.  Lengths are padded to a few buckets (the stack is causal, so
padding never reaches a real position).  ``fp8`` computes the same
mathematics with every matmul's operands rounded to e4m3, the
lower-precision control (``transformer.mm``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .transformer import mm

BUCKETS = (256, 1024, 4608, 8192, 16384, 24576)
COLS = 4096               # feed-forward columns upcast at a time
Q_BLOCK = 256
HEAD_BLOCK = 512          # positions the head scores at a time


def sizes(cfg: dict) -> dict:
    """Sizes from the configuration file's keys (``config.json`` names).
    ``num_experts`` there is the count held here; the router's published
    width and the first held expert are in ``deployment``.  A cut keeps
    the first ``num_hidden_layers`` of the published ``layer_types``."""
    dep = cfg["deployment"]
    first, held = (int(v) for v in dep["experts_held"])
    if held != int(cfg["num_experts"]):
        raise ValueError("deployment.experts_held and num_experts "
                         "disagree")
    layers = int(cfg["num_hidden_layers"])
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "layers": layers, "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "expert_mlp": int(cfg["intermediate_size"]),
        "window": int(cfg["sliding_window"]),
        "window_layers": tuple(i for i in range(layers) if
                               cfg["layer_types"][i] == "sliding_attention"),
        "router_width": int(dep["router_width"]),
        "experts_first": first, "experts_held": held,
        "top_k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["num_shared_experts"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["layer_norm_eps"]),
        "logit_scale": float(cfg["logit_scale"]),
        "positions": int(cfg["max_position_embeddings"])}


def init_params(sz: dict, key, std: float = 0.02):
    """Seeded weights in the program's tree (``check.require_weight_tree``
    holds the two together): normal(0, std) matrices and embedding rows
    (the tied head reads them too: unit rows would make every token's own
    row outscore the rest by ~hidden / sqrt(hidden), and greedy decoding
    copy its input whatever the stack computes), router rows normal(0,
    hidden^-0.5), unit LayerNorm scales.  Traceable."""
    E, D, F = sz["hidden"], sz["head_dim"], sz["expert_mlp"]

    def mat(k, *shape, s=std):
        return jax.random.normal(k, shape, jnp.float32) * s

    def ffn(k, lead, width):
        kg, ku, kd = jax.random.split(k, 3)
        return {"w_gate": mat(kg, *lead, E, width),
                "w_up": mat(ku, *lead, E, width),
                "w_down": mat(kd, *lead, width, E)}

    def layer(k):
        ks = jax.random.split(k, 7)
        return {"ln": jnp.ones((E,), jnp.float32),
                "attn": {"w_q": mat(ks[0], E, sz["heads"] * D),
                         "w_k": mat(ks[1], E, sz["kv_heads"] * D),
                         "w_v": mat(ks[2], E, sz["kv_heads"] * D),
                         "w_o": mat(ks[3], sz["heads"] * D, E)},
                "moe": {"router": mat(ks[4], sz["router_width"], E,
                                      s=E ** -0.5),
                        "shared": ffn(ks[5], (), sz["shared"] * F),
                        "experts": ffn(ks[6], (sz["experts_held"],), F)}}

    k_tok, k_layers = jax.random.split(key)
    return {"tok_emb": mat(k_tok, sz["vocab"], E),
            "final_ln": jnp.ones((E,), jnp.float32),
            "layers": [layer(k) for k in jax.random.split(
                k_layers, sz["layers"])]}


def bucket(n: int, window: int) -> int:
    """A padded length; a window layer's key band (``window + Q_BLOCK``)
    has to fit it."""
    for b in BUCKETS:
        if n <= b:
            break
    else:
        b = -(-n // 2048) * 2048
    if n <= Q_BLOCK:
        return b
    return -(-max(b, window + Q_BLOCK) // Q_BLOCK) * Q_BLOCK


def layernorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rotate(x, theta: float):
    """GPT-J rotary on ``x`` (S, H, D) at positions ``0..S-1``: feature
    pair ``(2j, 2j+1)`` turned by ``pos * theta^(-2j/D)``."""
    S, _, D = x.shape
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def swiglu(x, w_gate, w_up, w_down, precision: str):
    """``W_down(silu(x W_gate) * (x W_up))`` summed over slices of
    ``COLS`` columns, so one slice is upcast at a time."""
    F = w_gate.shape[1]
    cols = min(COLS, F)
    assert F % cols == 0

    def part(acc, j):
        g = mm("se,ef->sf", x, lax.dynamic_slice_in_dim(
            w_gate, j * cols, cols, 1), precision)
        u = mm("se,ef->sf", x, lax.dynamic_slice_in_dim(
            w_up, j * cols, cols, 1), precision)
        return acc + mm("sf,fe->se", jax.nn.silu(g) * u,
                        lax.dynamic_slice_in_dim(w_down, j * cols, cols, 0),
                        precision), None

    out, _ = lax.scan(part, jnp.zeros(x.shape, jnp.float32),
                      jnp.arange(F // cols))
    return out


def attention(ap, x, window, sz: dict, precision: str):
    """Grouped-query attention over the whole sequence ``x`` (S, E);
    ``window`` None is a full layer (causal, no positions).  One KV head
    and its query heads at a time, each group's output projected and
    summed, so that only one group's queries are ever held."""
    S, E = x.shape
    H, Hkv, D = sz["heads"], sz["kv_heads"], sz["head_dim"]
    G = H // Hkv
    k = mm("se,ef->sf", x, ap["w_k"], precision).reshape(S, Hkv, D)
    v = mm("se,ef->sf", x, ap["w_v"], precision).reshape(S, Hkv, D)
    if window is not None:
        k = rotate(k, sz["theta"])
    qb = min(Q_BLOCK, S)
    # a window layer's block of queries reads only the keys its window
    # reaches: [i*qb - window, (i+1)*qb), clamped into the sequence
    band = S if window is None else min(S, window + qb)

    def head(acc, g):
        w_q = lax.dynamic_slice_in_dim(ap["w_q"], g * G * D, G * D, 1)
        qg = mm("se,ef->sf", x, w_q, precision).reshape(S, G, D)
        if window is not None:
            qg = rotate(qg, sz["theta"])
        kg = lax.dynamic_index_in_dim(k, g, 1, keepdims=False)   # (S, D)
        vg = lax.dynamic_index_in_dim(v, g, 1, keepdims=False)

        def block(i):
            qi = lax.dynamic_slice_in_dim(qg, i * qb, qb, 0)
            lo = jnp.clip((i + 1) * qb - band, 0, S - band)
            ki = lax.dynamic_slice_in_dim(kg, lo, band, 0)
            vi = lax.dynamic_slice_in_dim(vg, lo, band, 0)
            s = mm("qhd,kd->hqk", qi, ki, precision) * D ** -0.5
            rows = i * qb + jnp.arange(qb)
            cols = lo + jnp.arange(band)
            seen = cols[None, :] <= rows[:, None]
            if window is not None:
                seen &= cols[None, :] > rows[:, None] - window
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.swapaxes(mm("hqk,kd->hqd", p, vi, precision), 0, 1)

        o = lax.map(block, jnp.arange(S // qb)).reshape(S, G * D)
        w_o = lax.dynamic_slice_in_dim(ap["w_o"], g * G * D, G * D, 0)
        return acc + mm("sf,fe->se", o, w_o, precision), None

    out, _ = lax.scan(head, jnp.zeros((S, E), jnp.float32),
                      jnp.arange(Hkv))
    return out


def moe(mp, x, sz: dict, precision: str):
    """The average of this share's part of the routed sum and the shared
    experts' mean."""
    s = jax.nn.sigmoid(mm("se,ne->sn", x, mp["router"], precision))
    top, chosen = lax.top_k(s, sz["top_k"])
    if sz["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    ex = mp["experts"]

    def one(acc, e):
        gate = jnp.sum(jnp.where(chosen == sz["experts_first"] + e, top,
                                 0.0), axis=-1)
        out = swiglu(x, ex["w_gate"][e], ex["w_up"][e], ex["w_down"][e],
                     precision)
        return acc + gate[:, None] * out, None

    routed, _ = lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                         jnp.arange(sz["experts_held"]))
    shared = swiglu(x, mp["shared"]["w_gate"], mp["shared"]["w_up"],
                    mp["shared"]["w_down"], precision)
    return 0.5 * (routed + shared / sz["shared"])


@functools.partial(jax.jit, static_argnames=("szt", "window", "precision"))
def _layer(lp, h, szt, window, precision):
    sz = dict(szt)
    x = layernorm(h, lp["ln"], sz["eps"])
    return h + attention(lp["attn"], x, window, sz, precision) \
        + moe(lp["moe"], x, sz, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(params, h, positions, eps, precision):
    t = layernorm(h[positions], params["final_ln"], eps)
    return mm("ne,ve->nv", t, params["tok_emb"], precision)


def next_token_logits(params, tokens, positions, sz: dict,
                      precision: str = "f32"):
    """``tokens`` (S,) int32, padded past the real length with anything;
    ``positions`` (N,) int32 indexes of the rows wanted.  Returns float32
    logits (N, vocab held) on the host: row ``i`` scores the token that
    follows ``tokens[positions[i]]``."""
    with jax.default_matmul_precision("highest"):
        S = int(tokens.shape[0])
        tokens = jnp.pad(jnp.asarray(tokens),
                         (0, bucket(S, sz["window"]) - S))
        szt = tuple(sorted(sz.items()))
        h = params["tok_emb"][tokens].astype(jnp.float32)
        for i, lp in enumerate(params["layers"]):
            window = sz["window"] if i in sz["window_layers"] else None
            h = _layer(lp, h, szt, window, precision)
        positions = np.asarray(positions)
        hb = min(HEAD_BLOCK, len(positions))
        pad = -len(positions) % hb
        positions = np.concatenate([positions, positions[:1].repeat(pad)])
        out = [np.asarray(_head(params, h, jnp.asarray(positions[j:j + hb]),
                                sz["eps"], precision)) * sz["logit_scale"]
               for j in range(0, len(positions), hb)]
        return np.concatenate(out)[:len(positions) - pad]
