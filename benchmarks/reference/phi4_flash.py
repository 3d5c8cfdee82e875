"""Plain reference of the ``phi4flash`` decoder (Phi-4-mini-flash-reasoning:
SambaY, arXiv:2507.06607, with differential attention, arXiv:2410.05258):
one full causal forward in float32, no cache, no kernels, nothing
imported from the program.

The model, as the configuration's ``assumed`` states it (x is a row of
``LN1(h)``; LayerNorm has scale and bias; no positional encoding):

- every layer ``h += Mixer(LN1(h)); h += MLP(LN2(h))``, a final
  LayerNorm, logits ``h E^T`` over the tied embedding;
  ``MLP: [g | u] = x W_1, (u * silu(g)) W_2``;
- with ``half = layers / 2``: layers below ``half`` are Mamba (even) and
  window attention (odd); layer ``half`` is Mamba, and its scan output
  ``y`` is the memory ``m``; layer ``half + 1`` is full causal attention
  whose K/V every later attention layer reads; after it GMU (even) and
  cross-attention (odd);
- Mamba-1: ``[u | z] = x W_in``; ``u = silu(conv_4(u) + b_c)`` (causal,
  depthwise); ``[dt | B | C] = u W_x``; ``delta = softplus(dt W_dt +
  b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(delta_t A) s_{t-1} + (delta_t
  u_t) B_t``; ``y_t = s_t . C_t + D u_t``; out ``(y * silu(z)) W_out``: a
  plain ``lax.scan`` over tokens;
- GMU: ``(m * silu(x W_g)) W_o``, ``m`` of the same token;
- differential attention: ``q = x W_q + b`` (Hq heads of D), and in
  window and full layers ``k, v`` (Hq / 2 heads of D each); KV pair ``j``
  is KV heads ``(2j, 2j+1)`` and serves query pairs ``2j`` and ``2j+1``,
  query pair ``i`` being heads ``(2i, 2i+1)``; for a query pair
  ``a_c = softmax(q_c k_c^T / sqrt(D) + mask) [v_1 | v_2]``,
  ``lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 depth)``, ``o = RMSNorm_2D(a_1 -
  lambda a_2) * g * (1 - lambda_init)``, output ``concat(o) W_o + b``;
  the mask is causal, and in window layers ``i - window < j <= i``.

Weights come in as the driver rounds them (bfloat16) and are upcast a
matrix (a slice of the MLP's columns) at a time; attention runs a block of
queries at a time, and the head a block of positions at a time, so that a
10,240-token request fits beside 7.7 GB of weights.  Lengths are padded
to a few buckets (the stack is causal, padding never reaches a real
position).  ``fp8`` computes the same with every matmul's operands
rounded to e4m3, the lower-precision control (``transformer.mm``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .transformer import mm

BUCKETS = (256, 1024, 2048, 4096, 8192)
COLS = 2560               # MLP columns upcast at a time
Q_BLOCK = 256
HEAD_BLOCK = 512          # positions the head scores at a time


def sizes(cfg: dict) -> dict:
    """Sizes from the configuration file's keys (``config.json`` names)
    and, for what the published config has no key for, ``assumed.sizes``."""
    a = cfg["assumed"]["sizes"]
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "mlp": int(cfg["intermediate_size"]),
        "window": int(cfg["sliding_window"]),
        "eps": float(cfg["layer_norm_eps"]),
        "positions": int(cfg["max_position_embeddings"]),
        "d_state": int(a["d_state"]), "d_conv": int(a["d_conv"]),
        "expand": int(a["mamba_expand"])}


def derived(sz: dict) -> dict:
    E = sz["hidden"]
    return {"D": E // sz["heads"], "KW": sz["kv_heads"] * (E // sz["heads"]),
            "Di": sz["expand"] * E, "R": -(-E // 16), "half": sz["layers"] // 2}


def layer_kind(sz: dict, i: int) -> str:
    half = sz["layers"] // 2
    if i <= half:
        return "mamba" if i % 2 == 0 or i == half else "window"
    if i == half + 1:
        return "full"
    return "gmu" if (i - half) % 2 == 0 else "cross"


def init_params(sz: dict, key, std: float = 0.02):
    """Seeded weights in the program's tree (``check.require_weight_tree``
    holds the two together): normal(0, std) matrices, zero biases, unit
    LayerNorm scales; Mamba as its authors initialise it (``A_log =
    log(1..d_state)``, ``D = 1``, ``b_dt`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1], ``w_dt`` uniform in +- dt_rank^-0.5, the
    convolution uniform in +- d_conv^-0.5: the scan is then stable and
    its steps in the range Mamba trains at); lambda vectors normal(0,
    0.1), sub-layer norm scale 1.  Traceable."""
    d = derived(sz)
    E, F, D, KW, Di, R = sz["hidden"], sz["mlp"], d["D"], d["KW"], d["Di"], \
        d["R"]
    Hq, N, K = sz["heads"], sz["d_state"], sz["d_conv"]

    def mat(k, *shape, s=std):
        return jax.random.normal(k, shape, jnp.float32) * s

    def uni(k, shape, bound):
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

    def ln():
        return {"scale": jnp.ones((E,), jnp.float32),
                "bias": jnp.zeros((E,), jnp.float32)}

    def mixer(k, kind):
        ks = jax.random.split(k, 6)
        if kind == "mamba":
            step = jnp.exp(jax.random.uniform(ks[4], (Di,))
                           * (math.log(0.1) - math.log(1e-3))
                           + math.log(1e-3))
            return {"w_in": mat(ks[0], E, 2 * Di),
                    "conv_w": uni(ks[1], (K, Di), K ** -0.5),
                    "conv_b": jnp.zeros((Di,), jnp.float32),
                    "w_x": mat(ks[2], Di, R + 2 * N),
                    "w_dt": uni(ks[3], (R, Di), R ** -0.5),
                    "b_dt": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                        1, N + 1, dtype=jnp.float32))[:, None], (N, Di)),
                    "d": jnp.ones((Di,), jnp.float32),
                    "w_out": mat(ks[5], Di, E)}
        if kind == "gmu":
            return {"w_g": mat(ks[0], E, Di), "w_o": mat(ks[1], Di, E)}
        wide = Hq * D + (0 if kind == "cross" else 2 * KW)
        lam = jax.random.split(ks[2], 4)
        return {"w_qkv": mat(ks[0], E, wide),
                "b_qkv": jnp.zeros((wide,), jnp.float32),
                "w_o": mat(ks[1], Hq * D, E),
                "b_o": jnp.zeros((E,), jnp.float32),
                "subln": jnp.ones((2 * D,), jnp.float32),
                "lambda_q1": mat(lam[0], D, s=0.1),
                "lambda_k1": mat(lam[1], D, s=0.1),
                "lambda_q2": mat(lam[2], D, s=0.1),
                "lambda_k2": mat(lam[3], D, s=0.1)}

    def layer(k, i):
        km, k1, k2 = jax.random.split(k, 3)
        return {"ln1": ln(), "ln2": ln(),
                "mixer": mixer(km, layer_kind(sz, i)),
                "mlp": {"w1": mat(k1, E, 2 * F), "w2": mat(k2, F, E)}}

    k_tok, k_layers = jax.random.split(key)
    return {"tok_emb": mat(k_tok, sz["vocab"], E), "final_ln": ln(),
            "layers": [layer(k, i) for i, k in enumerate(
                jax.random.split(k_layers, sz["layers"]))]}


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


def layernorm(x, p, eps: float):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32) \
        + p["bias"].astype(jnp.float32)


def mlp(p, x, precision: str):
    """``(u * silu(g)) W_2`` summed over slices of ``COLS`` intermediate
    columns, so one slice of each matrix is upcast at a time."""
    F = p["w2"].shape[0]
    cols = math.gcd(COLS, F)

    def part(acc, j):
        g = mm("se,ef->sf", x, lax.dynamic_slice_in_dim(
            p["w1"], j * cols, cols, 1), precision)
        u = mm("se,ef->sf", x, lax.dynamic_slice_in_dim(
            p["w1"], F + j * cols, cols, 1), precision)
        return acc + mm("sf,fe->se", u * jax.nn.silu(g),
                        lax.dynamic_slice_in_dim(p["w2"], j * cols, cols, 0),
                        precision), None

    out, _ = lax.scan(part, jnp.zeros(x.shape, jnp.float32),
                      jnp.arange(F // cols))
    return out


def mamba(mp, x, sz: dict, precision: str):
    """(output, scan output ``y``) over the whole sequence ``x`` (S, E)."""
    d = derived(sz)
    Di, R, N, K = d["Di"], d["R"], sz["d_state"], sz["d_conv"]
    S = x.shape[0]
    uz = mm("se,ef->sf", x, mp["w_in"], precision)
    u, z = uz[:, :Di], uz[:, Di:]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    conv = mp["conv_b"].astype(jnp.float32) + sum(
        padded[k:k + S] * mp["conv_w"][k].astype(jnp.float32)
        for k in range(K))
    u = jax.nn.silu(conv)
    dbc = mm("sf,fr->sr", u, mp["w_x"], precision)
    delta = jax.nn.softplus(mm("sr,rf->sf", dbc[:, :R], mp["w_dt"],
                               precision)
                            + mp["b_dt"].astype(jnp.float32))
    A = -jnp.exp(mp["a_log"].astype(jnp.float32))            # (N, Di)

    def token(s, row):
        dlt, ut, bt, ct = row
        s = jnp.exp(dlt[None, :] * A) * s + (dlt * ut)[None, :] * bt[:, None]
        return s, jnp.sum(s * ct[:, None], axis=0)

    _, y = lax.scan(token, jnp.zeros((N, Di), jnp.float32),
                    (delta, u, dbc[:, R:R + N], dbc[:, R + N:]))
    y = y + mp["d"].astype(jnp.float32) * u
    return mm("sf,fe->se", y * jax.nn.silu(z), mp["w_out"], precision), y


def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def attention(ap, x, kv, lam_init, window, sz: dict, precision: str):
    """Differential attention of ``x`` (S, E): over its own K/V (returned
    too) or, where the layer has none, over ``kv`` of the full layer.
    ``lam_init`` is ``lambda_init(depth)`` (traced: one program a kind of
    layer, not one a depth)."""
    d = derived(sz)
    Hq, D, KW = sz["heads"], d["D"], d["KW"]
    S = x.shape[0]
    qkv = mm("se,ef->sf", x, ap["w_qkv"], precision) \
        + ap["b_qkv"].astype(jnp.float32)
    q = qkv[:, :Hq * D].reshape(S, Hq // 2, 2, D)      # pair i, which c
    if qkv.shape[1] > Hq * D:
        kv = (qkv[:, Hq * D:Hq * D + KW], qkv[:, Hq * D + KW:])
    k = kv[0].reshape(S, Hq // 4, 2, D)                # KV pair j, which c
    v = kv[1].reshape(S, Hq // 4, 2 * D)
    # query pair i reads KV pair i // 2
    k = jnp.repeat(k, 2, axis=1)                       # (S, Hq/2, 2, D)
    v = jnp.repeat(v, 2, axis=1)                       # (S, Hq/2, 2D)
    f = jnp.float32
    lam = jnp.exp(jnp.sum(ap["lambda_q1"].astype(f)
                          * ap["lambda_k1"].astype(f))) \
        - jnp.exp(jnp.sum(ap["lambda_q2"].astype(f)
                          * ap["lambda_k2"].astype(f))) + lam_init
    qb = min(Q_BLOCK, S)
    cols = jnp.arange(S)

    def block(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = mm("qpcd,kpcd->pcqk", qi, k, precision) * D ** -0.5
        rows = i * qb + jnp.arange(qb)
        seen = cols[None, :] <= rows[:, None]
        if window is not None:
            seen &= cols[None, :] > rows[:, None] - window
        a = mm("pcqk,kpe->qpce",
               jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v,
               precision)
        o = a[:, :, 0] - lam * a[:, :, 1]               # (qb, Hq/2, 2D)
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + sz["eps"])
        o = o * ap["subln"].astype(f) * (1.0 - lam_init)
        return o.reshape(qb, Hq * D)

    o = lax.map(block, jnp.arange(S // qb)).reshape(S, Hq * D)
    return mm("sf,fe->se", o, ap["w_o"], precision) \
        + ap["b_o"].astype(f), kv


@functools.partial(jax.jit, static_argnames=("szt", "kind", "precision"))
def _layer(lp, h, memory, kv, lam_init, szt, kind, precision):
    sz = dict(szt)
    mp = lp["mixer"]
    x = layernorm(h, lp["ln1"], sz["eps"])
    if kind == "mamba":
        o, memory = mamba(mp, x, sz, precision)
    elif kind == "gmu":
        g = mm("se,ef->sf", x, mp["w_g"], precision)
        o = mm("sf,fe->se", memory * jax.nn.silu(g), mp["w_o"], precision)
    else:
        o, kv = attention(mp, x, kv, lam_init,
                          sz["window"] if kind == "window" else None, sz,
                          precision)
    h = h + o
    h = h + mlp(lp["mlp"], layernorm(h, lp["ln2"], sz["eps"]), precision)
    return h, memory, kv


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(params, h, positions, eps, precision):
    t = layernorm(h[positions], params["final_ln"], eps)
    return mm("ne,ve->nv", t, params["tok_emb"], precision)


def next_token_logits(params, tokens, positions, sz: dict,
                      precision: str = "f32"):
    """``tokens`` (S,) int32, padded past the real length with anything;
    ``positions`` (N,) int32 indexes of the rows wanted.  Returns float32
    logits (N, vocab) on the host: row ``i`` scores the token that follows
    ``tokens[positions[i]]``."""
    with jax.default_matmul_precision("highest"):
        S = int(tokens.shape[0])
        tokens = jnp.pad(jnp.asarray(tokens), (0, bucket(S) - S))
        szt = tuple(sorted(sz.items()))
        h = params["tok_emb"][tokens].astype(jnp.float32)
        Di = derived(sz)["Di"]
        memory = jnp.zeros((h.shape[0], Di), jnp.float32)
        kv = None
        for i, lp in enumerate(params["layers"]):
            kind = layer_kind(sz, i)
            if kind not in ("cross", "gmu"):
                kv = None          # only the full layer's K/V is handed on
            h, memory, kv = _layer(lp, h, memory, kv, lambda_init(i), szt,
                                   kind, precision)
        positions = np.asarray(positions)
        hb = min(HEAD_BLOCK, len(positions))
        pad = -len(positions) % hb
        positions = np.concatenate([positions, positions[:1].repeat(pad)])
        out = [np.asarray(_head(params, h, jnp.asarray(positions[j:j + hb]),
                                sz["eps"], precision))
               for j in range(0, len(positions), hb)]
        return np.concatenate(out)[:len(positions) - pad]
