"""Plain reference of the served decoder: one full forward, no cache.

``next_token_logits`` runs a whole sequence (prompt followed by the served
tokens) through the causal stack and returns the logits at the requested
positions, which is what prefill followed by paged decode has to agree
with.  See ``transformer.py`` for the block and the departures from GPT-2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import transformer as tf


@functools.partial(jax.jit, static_argnames=("precision",))
def next_token_logits(params, tokens, positions, precision: str = "f32"):
    """``tokens`` (S,) int32, padded past the real length with anything
    (the stack is causal, so padding never reaches an earlier position);
    ``positions`` (N,) int32 indexes of the rows wanted.  Returns float32
    logits (N, V): row ``i`` scores the token that follows
    ``tokens[positions[i]]``."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    S = tokens.shape[0]
    h = tf.embed(params, tokens[None], jnp.arange(S)[None])
    h, _ = jax.lax.scan(
        lambda h, lp: (tf.layer(lp, h, causal=True, precision=precision),
                       None),
        h, tf.stack_layers(params["layers"]))
    return tf.head_logits(params, h[0][positions], precision)
