"""Reference computations under ``jax.jit``, shared by the identity tests.

Called eagerly, a shard_mapped pipeline schedule or ``CausalLm.generate``'s
prefill dispatches (and compiles) primitive by primitive, anew for every
shape: one GPipe-vs-1F1B identity cost 75 s of a test's 114 s that way and
10 s as two jitted programs; one five-prompt ``generate`` reference 324
compiles against 17.  The jitted program is also the one the trainer runs
(``gspmd.make_gspmd_train_step``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def loss(model, params, batch, targets, **kw):
    """``model.loss(params, None, batch, targets, **kw)[0]``, jitted."""
    return jax.jit(
        lambda p: model.loss(p, None, batch, targets, **kw)[0])(params)


def loss_and_grads(model, params, batch, targets, **kw):
    """The same loss and its gradient w.r.t. ``params`` from ONE program."""
    return jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, None, batch, targets, **kw)[0]))(params)


@functools.cache
def _generate_fn(model, n):
    # one jitted function per (model, n) — models are frozen dataclasses,
    # equal by config — so prompts of one length share one compile
    return jax.jit(lambda p, toks: model.generate(p, toks, n))


def generate_ref(model, params, prompt, n):
    """The serving tests' parity anchor: the ``n`` tokens greedy
    ``model.generate`` continues the one ``prompt`` with, as a list."""
    out = np.asarray(_generate_fn(model, n)(
        params, jnp.asarray([prompt], jnp.int32)))
    return list(map(int, out[0, len(prompt):]))
