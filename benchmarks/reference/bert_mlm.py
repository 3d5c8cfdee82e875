"""Plain reference of masked-LM training: loss, gradients, clipped AdamW.

``run_steps`` follows the first steps of a training run from the same
weights, batches and dropout keys as the program and returns what the
benchmark compares: each step's loss, the per-leaf norms of the first
(clipped) gradient, and the per-leaf norms of the parameters' change.

The batch is walked in blocks of rows so that float32 activations fit
beside whatever else the process holds; the loss is a sum over masked
positions divided by one global count, so the blocks' gradients add.

Semantics stated by the configuration (``program`` block of the config
file) and implemented here independently of the program:

- inverted dropout at three kinds of site (embedding output, attention
  output, MLP output), site ``i`` of step ``t`` drawing
  ``bernoulli(fold_in(fold_in(key, t), i), 1 - rate, (B, S, E))`` with the
  embedding at ``i = 1`` and layer ``l`` at ``2 l + 2`` and ``2 l + 3``;
- the loss counts, in each row, the first ``capacity`` masked positions
  (``capacity = 0.25 * S`` rounded up to a multiple of 8) and divides by
  the number of ALL masked positions;
- ``clip_by_global_norm(1.0)``, then AdamW (0.9, 0.999, 1e-8, weight decay
  0.01 on matrices and embeddings only), learning rate rising linearly
  from 0 over the first tenth of ``schedule_steps``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as tf

B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8
DECAYED = {"tok_emb", "pos_emb", "wq", "wk", "wv", "wo", "w1", "w2", "w"}


def capacity(seq_len: int, frac: float = 0.25) -> int:
    return min(seq_len, max(8, -(-int(frac * seq_len) // 8) * 8))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))


def decay_mask(params):
    return jax.tree_util.tree_map_with_path(
        lambda p, x: _leaf_name(p) in DECAYED, params)


def learning_rate(step: int, base_lr: float, schedule_steps: int) -> float:
    """Rate used by update number ``step`` (0-based): linear warm-up over
    the first tenth of the schedule, then linear decay to 0."""
    warm = max(1, int(0.1 * schedule_steps))
    if step < warm:
        return base_lr * step / warm
    frac = (step - warm) / max(schedule_steps - warm, 1)
    return base_lr * (1.0 - min(max(frac, 0.0), 1.0))


def dropout_masks(key, step: int, n_layers: int, shape, rate: float):
    """Keep-masks of one step for the whole batch, (2L+1, B, S, E) bool,
    site order: embedding, then (attention, MLP) per layer."""
    k = jax.random.fold_in(key, step)
    return jnp.stack([
        jax.random.bernoulli(jax.random.fold_in(k, i), 1.0 - rate, shape)
        for i in range(1, 2 * n_layers + 2)])


def block_loss_sum(params, tokens, targets, mask, keep, *, rate: float,
                   cap: int, precision: str):
    """Sum of cross-entropies over the counted masked positions of a block
    of rows.  ``keep``: (2L+1, b, S, E) bool dropout keep-masks, or None."""
    def drop(keep_mask):
        if keep_mask is None or rate == 0.0:
            return lambda x: x
        return lambda x: jnp.where(keep_mask, x / (1.0 - rate), 0.0)

    b, S = tokens.shape
    L = len(params["layers"])
    h = tf.embed(params, tokens, jnp.arange(S)[None])
    if keep is None:
        body = lambda h, lp: (tf.layer(  # noqa: E731
            lp, h, causal=False, precision=precision), None)
        xs = tf.stack_layers(params["layers"])
    else:
        h = drop(keep[0])(h)
        body = lambda h, x: (tf.layer(  # noqa: E731
            x[0], h, causal=False, precision=precision,
            drop=(drop(x[1][0]), drop(x[1][1]))), None)
        xs = (tf.stack_layers(params["layers"]),
              keep[1:].reshape((L, 2) + keep.shape[1:]))
    h, _ = jax.lax.scan(body, h, xs)
    counted = mask & (jnp.cumsum(mask, axis=1) - 1 < cap)
    # the first `cap` counted positions of each row, packed to the front
    order = jnp.argsort(~counted, axis=1, stable=True)[:, :cap]
    w = jnp.take_along_axis(counted, order, axis=1).astype(jnp.float32)
    hp = jnp.take_along_axis(h, order[..., None], axis=1)
    gold = jnp.take_along_axis(targets, order, axis=1)
    logits = tf.head_logits(params, hp, precision)
    ce = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, gold[..., None], axis=-1)[..., 0]
    return jnp.sum(ce * w)


@functools.partial(jax.jit, static_argnames=("rate", "cap", "precision"))
def _block_value_and_grad(params, tokens, targets, mask, keep, *, rate, cap,
                          precision):
    return jax.value_and_grad(block_loss_sum)(
        params, tokens, targets, mask, keep, rate=rate, cap=cap,
        precision=precision)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def loss_and_grad(params, batch, key, step: int, *, rate: float,
                  precision: str = "f32", block_rows: int = 32,
                  rows=None):
    """Mean masked-LM loss of ``batch`` and its gradient, by blocks.
    ``rows`` restricts the mean to those rows of the batch (the dropout
    masks stay those of the whole batch) — it plants the half-batch fault
    in ``benchmarks/tests``."""
    tokens, targets, mask = (np.asarray(batch[k]) for k in
                             ("tokens", "targets", "mask"))
    B, S = tokens.shape
    E = params["tok_emb"].shape[1]
    L = len(params["layers"])
    keep_all = (dropout_masks(key, step, L, (B, S, E), rate)
                if rate > 0.0 else None)
    rows = np.arange(B) if rows is None else np.asarray(rows)
    total, grads = 0.0, None
    for lo in range(0, len(rows), block_rows):
        r = rows[lo:lo + block_rows]
        keep = None if keep_all is None else keep_all[:, r]
        v, g = _block_value_and_grad(
            params, tokens[r], targets[r], mask[r], keep, rate=rate,
            cap=capacity(S), precision=precision)
        total = total + v
        grads = g if grads is None else _add(grads, g)
    n = max(float(mask[rows].sum()), 1.0)
    return total / n, jax.tree.map(lambda g: g / n, grads)


@jax.jit
def clip_by_global_norm(grads, max_norm: float = 1.0):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * scale, grads)


@jax.jit
def adamw_update(params, grads, mu, nu, count, lr, weight_decay=0.01):
    """One AdamW update; ``count`` is the 1-based number of this update."""
    mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
    c1 = 1 - B1 ** count
    c2 = 1 - B2 ** count

    def upd(p, m, v, decayed):
        u = (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)
        return p - lr * (u + (weight_decay * p if decayed else 0.0))

    params = jax.tree.map(upd, params, mu, nu, decay_mask(params))
    return params, mu, nu


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def diff_norms(a, b):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def run_steps(params, batches, key, *, rate: float, base_lr: float,
              schedule_steps: int, precision: str = "f32",
              block_rows: int = 32, fault: str | None = None) -> dict:
    """Follow ``len(batches)`` training steps.  Returns ``losses`` (one
    per step), ``grad_norms`` (per leaf, the first step's clipped
    gradient) and ``change_norms`` (per leaf, parameters after the last
    step minus before the first), as numpy arrays in leaf order.

    ``fault`` plants one of the faults the benchmark's tests read:
    ``half_batch`` (the mean taken over the first half of the rows),
    ``one_shard_of_<n>`` (over the first ``1/n`` of them: what one chip of
    ``n`` holds when the gradient exchange is left out) or ``frozen`` (the
    state returned unchanged)."""
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches):
        rows = None
        if fault == "half_batch":
            rows = np.arange(len(batch["tokens"]) // 2)
        elif fault and fault.startswith("one_shard_of_"):
            rows = np.arange(len(batch["tokens"])
                             // int(fault.rsplit("_", 1)[1]))
        loss, grads = loss_and_grad(params, batch, key, t, rate=rate,
                                    precision=precision,
                                    block_rows=block_rows, rows=rows)
        grads = clip_by_global_norm(grads)
        losses.append(float(loss))
        if t == 0:
            grad_norms = np.asarray(leaf_norms(grads))
        if fault != "frozen":
            params, mu, nu = adamw_update(
                params, grads, mu, nu, t + 1,
                learning_rate(t, base_lr, schedule_steps))
    return {"losses": np.asarray(losses), "grad_norms": grad_norms,
            "change_norms": np.asarray(diff_norms(params, start))}
