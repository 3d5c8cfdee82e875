"""Plain reference of the ``pangu_ultra_moe`` decoder (openPangu-Ultra-MoE):
one full causal forward in float32, no cache, no kernels, nothing
imported from the program.

The block, as the configuration's ``assumed`` states it (x is a row of
the residual stream, no bias anywhere, RMSNorm epsilon from the config):

- sandwich norm: ``h += N2(MLA(N1(h)))``, ``h += N4(FFN(N3(h)))``; a final
  RMSNorm, then the untied head;
- MLA, non-absorbed: ``c_q = RMSNorm(x W_dq)``, ``q = c_q W_uq`` (per head
  ``nope | rope``); ``[c_kv | k_r] = x W_dkv``, ``c_kv = RMSNorm(c_kv)``;
  ``q_rope`` and the one shared ``k_r`` rotated at absolute positions
  (halves paired: feature i with i + d/2); ``[k_nope_h | v_h] = c_kv W_ukv``;
  scores ``(q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope)``, causal
  softmax, ``concat_h(p v_h) W_o``;
- FFN: SwiGLU ``W_down(silu(x W_gate) * (x W_up))``, dense in the first
  ``first_k_dense_replace`` layers; after them a shared expert plus the
  routed ones: ``s = sigmoid(x W_r)`` over the router's whole width, the
  ``top_k`` largest chosen, ``g_i = scale * s_i / (sum of chosen + 1e-20)``,
  and THE SHARE: the sum runs over the chosen experts in
  ``[first, first + held)`` only (the weights hold no others), ``g`` still
  normalised over all the chosen.  The vocabulary is the slice the
  weights hold.

Weights come in as the driver rounds them (bfloat16) and are upcast one
matrix, one expert, one 2048-column slice at a time; attention runs a
group of heads and a block of queries at a time, so that a 9,216-token
request fits beside ten gigabytes of weights.  Lengths are padded to a
few buckets (the stack is causal, so padding never reaches a real
position).  ``fp8`` computes the same mathematics with every matmul's
operands rounded to e4m3, the lower-precision control
(``transformer.mm``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .transformer import mm

BUCKETS = (256, 1024, 2048, 4096, 8192)
COLS = 2048               # feed-forward columns upcast at a time
Q_BLOCK = 256
HEAD_GROUPS = 4


def sizes(cfg: dict) -> dict:
    """Sizes from the configuration file's keys (``config.json`` names).
    ``n_routed_experts`` there is the count held here; the router's
    published width and the first held expert are in ``deployment``."""
    dep = cfg["deployment"]
    first, held = (int(v) for v in dep["experts_held"])
    if held != int(cfg["n_routed_experts"]):
        raise ValueError("deployment.experts_held and n_routed_experts "
                         "disagree")
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "heads": int(cfg["num_attention_heads"]),
        "q_rank": int(cfg["q_lora_rank"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "mlp": int(cfg["intermediate_size"]),
        "expert_mlp": int(cfg["moe_intermediate_size"]),
        "router_width": int(dep["router_width"]),
        "experts_first": first, "experts_held": held,
        "top_k": int(cfg["num_experts_per_tok"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "positions": int(cfg["max_position_embeddings"])}


def init_params(sz: dict, key, std: float = 0.02):
    """Seeded weights in the program's tree (``check.require_weight_tree``
    holds the two together): normal(0, std) matrices, embedding rows
    normal(0, 1) (under sandwich norms every branch enters the stream at
    unit RMS; a 0.02 embedding would be a fiftieth of it, and every token
    of a sequence would carry the same vector to the same experts), router
    rows normal(0, hidden^-0.5), unit norm scales.  Traceable."""
    E, H = sz["hidden"], sz["heads"]
    Dn, Dr, Dv = sz["nope"], sz["rope"], sz["v_dim"]
    C, held = sz["kv_rank"], sz["experts_held"]

    def mat(k, *shape, s=std):
        return jax.random.normal(k, shape, jnp.float32) * s

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def ffn(k, lead, width):
        kg, ku, kd = jax.random.split(k, 3)
        return {"w_gate": mat(kg, *lead, E, width),
                "w_up": mat(ku, *lead, E, width),
                "w_down": mat(kd, *lead, width, E)}

    def layer(k, i):
        ks = jax.random.split(k, 9)
        lp = {"n1": ones(E), "n2": ones(E), "n3": ones(E), "n4": ones(E),
              "attn": {"w_dq": mat(ks[0], E, sz["q_rank"]),
                       "q_norm": ones(sz["q_rank"]),
                       "w_uq": mat(ks[1], sz["q_rank"], H, Dn + Dr),
                       "w_dkv": mat(ks[2], E, C + Dr),
                       "kv_norm": ones(C),
                       "w_ukv": mat(ks[3], C, H, Dn + Dv),
                       "w_o": mat(ks[4], H, Dv, E)}}
        if i < sz["dense_layers"]:
            lp["mlp"] = ffn(ks[5], (), sz["mlp"])
        else:
            lp["moe"] = {
                "router": mat(ks[6], sz["router_width"], E, s=E ** -0.5),
                "shared": ffn(ks[7], (), sz["expert_mlp"]),
                "experts": ffn(ks[8], (held,), sz["expert_mlp"])}
        return lp

    k_tok, k_head, k_layers = jax.random.split(key, 3)
    return {"tok_emb": mat(k_tok, sz["vocab"], E, s=1.0),
            "head": mat(k_head, sz["vocab"], E),
            "final_norm": ones(E),
            "layers": [layer(k, i) for i, k in enumerate(
                jax.random.split(k_layers, sz["layers"]))]}


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, positions, theta: float):
    """Rotate ``x`` (S, ..., D) at ``positions`` (S,): feature ``i`` pairs
    with ``i + D/2``, angle ``pos * theta^(-i / (D/2))``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def swiglu(x, w_gate, w_up, w_down, precision: str):
    """``W_down(silu(x W_gate) * (x W_up))`` summed over slices of
    ``COLS`` intermediate columns, so one slice is upcast at a time."""
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


def attention(ap, x, sz: dict, precision: str):
    """MLA over the whole sequence ``x`` (S, E), non-absorbed."""
    S = x.shape[0]
    H, Dn, C = sz["heads"], sz["nope"], sz["kv_rank"]
    pos = jnp.arange(S)
    cq = rmsnorm(mm("se,er->sr", x, ap["w_dq"], precision), ap["q_norm"],
                 sz["eps"])
    ckr = mm("se,ec->sc", x, ap["w_dkv"], precision)
    ckv = rmsnorm(ckr[:, :C], ap["kv_norm"], sz["eps"])
    kr = rope(ckr[:, C:], pos, sz["theta"])                    # (S, Dr)
    G = HEAD_GROUPS if H % HEAD_GROUPS == 0 else 1
    hg = H // G
    qb = min(Q_BLOCK, S)
    scale = (Dn + sz["rope"]) ** -0.5

    def group(acc, g):
        w_uq = lax.dynamic_slice_in_dim(ap["w_uq"], g * hg, hg, 1)
        w_ukv = lax.dynamic_slice_in_dim(ap["w_ukv"], g * hg, hg, 1)
        w_o = lax.dynamic_slice_in_dim(ap["w_o"], g * hg, hg, 0)
        q = mm("sr,rhd->shd", cq, w_uq, precision)
        q_nope, q_rope = q[..., :Dn], rope(q[..., Dn:], pos, sz["theta"])
        kv = mm("sc,chd->shd", ckv, w_ukv, precision)
        k_nope, v = kv[..., :Dn], kv[..., Dn:]

        def block(i):
            qn = lax.dynamic_slice_in_dim(q_nope, i * qb, qb, 0)
            qr = lax.dynamic_slice_in_dim(q_rope, i * qb, qb, 0)
            s = mm("qhd,khd->hqk", qn, k_nope, precision) \
                + mm("qhr,kr->hqk", qr, kr, precision)
            rows = i * qb + jnp.arange(qb)
            s = jnp.where(pos[None, :] > rows[:, None], -jnp.inf,
                          s * scale)
            o = mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision)
            return mm("qhd,hde->qe", o, w_o, precision)

        out = lax.map(block, jnp.arange(S // qb))
        return acc + out.reshape(S, -1), None

    out, _ = lax.scan(group, jnp.zeros(x.shape, jnp.float32),
                      jnp.arange(G))
    return out


def routed(mp, x, sz: dict, precision: str):
    """Shared expert plus this share's part of the routed sum."""
    s = jax.nn.sigmoid(mm("se,ne->sn", x, mp["router"], precision))
    top, chosen = lax.top_k(s, sz["top_k"])
    if sz["norm_topk"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    gates = top * sz["routed_scale"]
    y = swiglu(x, mp["shared"]["w_gate"], mp["shared"]["w_up"],
               mp["shared"]["w_down"], precision)
    ex = mp["experts"]

    def one(acc, e):
        gate = jnp.sum(jnp.where(chosen == sz["experts_first"] + e, gates,
                                 0.0), axis=-1)
        out = swiglu(x, ex["w_gate"][e], ex["w_up"][e], ex["w_down"][e],
                     precision)
        return acc + gate[:, None] * out, None

    y, _ = lax.scan(one, y, jnp.arange(sz["experts_held"]))
    return y


@functools.partial(jax.jit, static_argnames=("szt", "precision"))
def _layer(lp, h, szt, precision):
    sz = dict(szt)
    a = attention(lp["attn"], rmsnorm(h, lp["n1"], sz["eps"]), sz,
                  precision)
    h = h + rmsnorm(a, lp["n2"], sz["eps"])
    x = rmsnorm(h, lp["n3"], sz["eps"])
    if "mlp" in lp:
        f = swiglu(x, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                   lp["mlp"]["w_down"], precision)
    else:
        f = routed(lp["moe"], x, sz, precision)
    return h + rmsnorm(f, lp["n4"], sz["eps"])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(params, h, positions, eps, precision):
    t = rmsnorm(h[positions], params["final_norm"], eps)
    return mm("ne,ve->nv", t, params["head"], precision)


def next_token_logits(params, tokens, positions, sz: dict,
                      precision: str = "f32"):
    """``tokens`` (S,) int32, padded past the real length with anything;
    ``positions`` (N,) int32 indexes of the rows wanted.  Returns float32
    logits (N, vocab held): row ``i`` scores the token that follows
    ``tokens[positions[i]]``."""
    with jax.default_matmul_precision("highest"):
        S = int(tokens.shape[0])
        tokens = jnp.pad(jnp.asarray(tokens), (0, bucket(S) - S))
        szt = tuple(sorted(sz.items()))
        h = params["tok_emb"][tokens].astype(jnp.float32)
        for lp in params["layers"]:
            h = _layer(lp, h, szt, precision)
        return _head(params, h, jnp.asarray(positions), sz["eps"],
                     precision)
