"""Plain reference of the transformer block both configurations run.

Straightforward ``jax.numpy``: no kernels, no cache, no batching tricks,
no import from the program.  Float32 with ``precision=HIGHEST`` is the
reference; ``bf16`` and ``fp8`` compute the same mathematics with the
matmul operands rounded to that type (float32 accumulation) and are the
lower-precision controls of ``benchmarks/tests`` and ``PERF.md``.

Layout (one dict per layer): ``wq/wk/wv`` (E, H, D), ``bq/bk/bv`` (H, D),
``wo`` (H, D, E), ``bo`` (E,), ``w1`` (E, F), ``b1``, ``w2`` (F, E), ``b2``,
``ln1/ln2`` {scale, bias}; around them ``tok_emb`` (V, E), ``pos_emb``
(P, E), ``emb_ln`` and the head ``mlm`` {w (E, E), b, ln, out_b (V,)} whose
vocabulary projection is tied to ``tok_emb``.

Departures from the published models, stated in each configuration's
``assumed``: post-LN blocks and learned positions for both families
(GPT-2 is pre-LN), the tanh approximation of GELU, LayerNorm epsilon 1e-12,
and a dense+GELU+LayerNorm head transform in front of the tied decoder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-12
PRECISIONS = ("f32", "bf16", "fp8")


def sizes(cfg: dict) -> dict:
    """Model sizes from a configuration file's keys (BERT's or GPT-2's
    ``config.json`` names)."""
    if "n_embd" in cfg:
        e = int(cfg["n_embd"])
        return {"vocab": int(cfg["vocab_size"]), "hidden": e,
                "layers": int(cfg["n_layer"]), "heads": int(cfg["n_head"]),
                "mlp": int(cfg.get("n_inner") or 4 * e),
                "positions": int(cfg["n_positions"])}
    return {"vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "heads": int(cfg["num_attention_heads"]),
            "mlp": int(cfg["intermediate_size"]),
            "positions": int(cfg["max_position_embeddings"])}


def init_params(sz: dict, key, std: float = 0.02):
    """Seeded weights: normal(0, std) matrices, zero biases, unit LayerNorm
    scales.  Traceable, so a caller makes the whole tree in one jitted
    call; the layers are drawn by one ``vmap`` over per-layer keys."""
    V, E, L, H, F, P = (sz["vocab"], sz["hidden"], sz["layers"], sz["heads"],
                        sz["mlp"], sz["positions"])
    D = E // H
    k_tok, k_pos, k_head, k_layers = jax.random.split(key, 4)

    def mat(k, *shape):
        return jax.random.normal(k, shape, jnp.float32) * std

    def ln():
        return {"scale": jnp.ones((E,), jnp.float32),
                "bias": jnp.zeros((E,), jnp.float32)}

    def one_layer(k):
        kq, kk, kv, ko, k1, k2 = jax.random.split(k, 6)
        return {
            "wq": mat(kq, E, H, D), "wk": mat(kk, E, H, D),
            "wv": mat(kv, E, H, D),
            "bq": jnp.zeros((H, D), jnp.float32),
            "bk": jnp.zeros((H, D), jnp.float32),
            "bv": jnp.zeros((H, D), jnp.float32),
            "wo": mat(ko, H, D, E), "bo": jnp.zeros((E,), jnp.float32),
            "ln1": ln(),
            "w1": mat(k1, E, F), "b1": jnp.zeros((F,), jnp.float32),
            "w2": mat(k2, F, E), "b2": jnp.zeros((E,), jnp.float32),
            "ln2": ln()}

    stacked = jax.vmap(one_layer)(jax.random.split(k_layers, L))
    return {"tok_emb": mat(k_tok, V, E), "pos_emb": mat(k_pos, P, E),
            "emb_ln": ln(),
            "layers": [jax.tree.map(lambda x: x[i], stacked)
                       for i in range(L)],
            "mlm": {"w": mat(k_head, E, E),
                    "b": jnp.zeros((E,), jnp.float32),
                    "ln": ln(), "out_b": jnp.zeros((V,), jnp.float32)}}


def stack_layers(layers: list):
    """The per-layer dicts as one dict of (L, ...) arrays, for ``scan``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def _round(x, precision: str):
    if precision == "f32":
        return x.astype(jnp.float32)
    if precision == "bf16":
        return x.astype(jnp.bfloat16)
    if precision == "fp8":
        # the chip multiplies no fp8: round the operand to e4m3 and let
        # the product run on the bf16 path, which holds every e4m3 value
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    raise ValueError(f"precision must be one of {PRECISIONS}, "
                     f"got {precision!r}")


def mm(spec: str, a, b, precision: str):
    """``einsum`` with both operands in ``precision``, float32 out."""
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layernorm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def embed(params, tokens, positions):
    h = params["tok_emb"][tokens] + params["pos_emb"][positions]
    return layernorm(h.astype(jnp.float32), params["emb_ln"])


def layer(lp, h, *, causal: bool, precision: str, drop=None):
    """One post-LN block on ``h`` (B, S, E) float32.  ``drop`` is a pair of
    functions applied to the attention and MLP outputs (dropout), or
    None.  Callers ``lax.scan`` it over ``stack_layers``: the same block L
    times is one small program, not L copies."""
    q = mm("bse,ehd->bhsd", h, lp["wq"], precision) + lp["bq"][None, :, None]
    k = mm("bse,ehd->bhsd", h, lp["wk"], precision) + lp["bk"][None, :, None]
    v = mm("bse,ehd->bhsd", h, lp["wv"], precision) + lp["bv"][None, :, None]
    s = mm("bhqd,bhkd->bhqk", q, k, precision) * (q.shape[-1] ** -0.5)
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.arange(n)[None, :] > jnp.arange(n)[:, None],
                      -jnp.inf, s)
    a = mm("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v, precision)
    a = mm("bhsd,hde->bse", a, lp["wo"], precision) + lp["bo"]
    if drop is not None:
        a = drop[0](a)
    h = layernorm(h + a, lp["ln1"])
    m = gelu(mm("bse,ef->bsf", h, lp["w1"], precision) + lp["b1"])
    m = mm("bsf,fe->bse", m, lp["w2"], precision) + lp["b2"]
    if drop is not None:
        m = drop[1](m)
    return layernorm(h + m, lp["ln2"])


def head_logits(params, h, precision: str):
    """Head transform and tied decoder on ``h`` (..., E) -> (..., V)."""
    t = gelu(mm("...e,ef->...f", h, params["mlm"]["w"], precision)
             + params["mlm"]["b"])
    t = layernorm(t, params["mlm"]["ln"])
    return mm("...e,ve->...v", t, params["tok_emb"], precision) \
        + params["mlm"]["out_b"]
