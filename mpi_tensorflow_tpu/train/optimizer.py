"""Optimizer and LR schedule matching the reference's TF-v1 semantics.

Reference (mpipy.py:59-66):
- global step: a float32 variable ``iter_`` incremented per apply;
- LR: ``tf.train.exponential_decay(0.01, iter_*batch_size,
  decay_steps=local_train_size, 0.95, staircase=True)`` — i.e.
  ``0.01 * 0.95 ** floor(step * batch_size / local_train_size)`` (one decay
  per local epoch);
- ``tf.train.MomentumOptimizer(lr, 0.9)``: ``accum = m*accum + grad;
  var -= lr * accum`` (lr applied at update time, not folded into the
  accumulator).

Everything here is pure and jit-safe (runs in-graph on TPU — the schedule is
computed on device, no host round-trip per step).  An ``optax`` adapter is
provided so the rest of the ecosystem's optimizers slot into the same train
step.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


def exponential_decay(base_lr, step, batch_size, decay_steps, rate,
                      staircase=True):
    """``tf.train.exponential_decay`` with the reference's arguments
    (mpipy.py:60-64).  ``step`` may be a traced scalar."""
    progress = step * batch_size / decay_steps
    if staircase:
        progress = jnp.floor(progress)
    return base_lr * jnp.power(rate, progress)


class MomentumState(NamedTuple):
    velocity: dict      # same pytree structure as params
    step: jnp.ndarray   # float32 scalar, like the reference's ``iter_``
                        # (mpipy.py:59 declares it float32)


def momentum_init(params) -> MomentumState:
    return MomentumState(
        velocity=jax.tree.map(jnp.zeros_like, params),
        step=jnp.zeros((), jnp.float32),
    )


def momentum_apply(params, grads, state: MomentumState, lr, momentum=0.9):
    """One TF ``MomentumOptimizer`` update: v = m*v + g; p -= lr*v."""
    new_v = jax.tree.map(lambda v, g: momentum * v + g, state.velocity, grads)
    new_p = jax.tree.map(lambda p, v: p - lr * v, params, new_v)
    return new_p, MomentumState(new_v, state.step + 1.0)


def reference_schedule(config, local_train_size: int):
    """The reference's LR schedule closed over a run's local train size."""
    def schedule(step):
        return exponential_decay(config.base_lr, step, config.batch_size,
                                 local_train_size, config.lr_decay,
                                 staircase=True)
    return schedule


def make_optax(config, local_train_size: int) -> optax.GradientTransformation:
    """The reference optimizer expressed as an optax chain, for models that
    want the optax ecosystem (ResNet/BERT runs may swap in adamw etc.)."""
    schedule = reference_schedule(config, local_train_size)
    return optax.chain(
        optax.trace(decay=config.momentum, nesterov=False),
        optax.scale_by_learning_rate(schedule),  # also negates
    )


def adamw(learning_rate=1e-4, weight_decay=0.01, **kw):
    """Convenience passthrough for transformer runs (BASELINE config 5)."""
    return optax.adamw(learning_rate, weight_decay=weight_decay, **kw)


# ---------------------------------------------------------------------------
# transformer-family schedules (no counterpart in the reference, whose only
# schedule is the exponential decay above — mpipy.py:60-64; BERT/GPT
# training needs warmup to survive adam's early variance)
# ---------------------------------------------------------------------------

def warmup_linear(base_lr: float, warmup_steps: int, total_steps: int,
                  end_fraction: float = 0.0):
    """BERT's schedule: LR ramps 0 -> ``base_lr`` linearly over
    ``warmup_steps``, then decays linearly to ``end_fraction * base_lr`` at
    ``total_steps`` (flat afterwards).  Pure and jit-safe; ``step`` may be
    a traced scalar."""
    def schedule(step):
        step = jnp.asarray(step, jnp.float32)
        warm = step / jnp.maximum(float(warmup_steps), 1.0)
        frac = (step - warmup_steps) \
            / jnp.maximum(float(total_steps - warmup_steps), 1.0)
        decay = 1.0 - (1.0 - end_fraction) * jnp.clip(frac, 0.0, 1.0)
        return base_lr * jnp.where(step < warmup_steps, warm, decay)
    return schedule


def warmup_cosine(base_lr: float, warmup_steps: int, total_steps: int,
                  end_fraction: float = 0.0):
    """Linear warmup then cosine decay to ``end_fraction * base_lr`` (the
    GPT-family default)."""
    def schedule(step):
        step = jnp.asarray(step, jnp.float32)
        warm = step / jnp.maximum(float(warmup_steps), 1.0)
        frac = jnp.clip((step - warmup_steps)
                        / jnp.maximum(float(total_steps - warmup_steps), 1.0),
                        0.0, 1.0)
        decay = end_fraction + (1.0 - end_fraction) \
            * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
        return base_lr * jnp.where(step < warmup_steps, warm, decay)
    return schedule


# x? prefix: the enc-dec family's cross-attention biases (xbq/xbk/xbv/xbo,
# models/encdec.py) are 2-D (heads, head_dim), so the ndim guard does not
# exclude them either — without the prefix they silently weight-decayed
# (found in round 3's review)
_BIAS_NAME = __import__("re").compile(r"^x?(b[a-z0-9]?|eb\d)$")


def _leaf_name(path) -> str:
    """Last dict key on a tree path ('' for pure-sequence paths)."""
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            return key
    return ""


def decay_mask(params):
    """The BERT-recipe weight-decay mask: decay weight matrices, skip
    LayerNorm scales/biases and every bias — by NAME, not just ndim,
    because the MoE family's per-expert biases (``eb1``: (E, mlp),
    ``eb2``: (E, hidden)) and the enc-dec family's cross-attention biases
    (``xbq``/``xbk``/``xbv``: (heads, head_dim)) are 2-D and a structural
    rule would silently decay them.  Bias-like names across the families:
    ``b``/``bq``/``bk``/``bv``/``bo``/``b1``/``b2``, ``eb1``/``eb2``,
    ``xbq``/``xbk``/``xbv``/``xbo``, ``*_b`` (``out_b``, ``patch_b``,
    ``head_b``), and the ``scale``/``bias`` LayerNorm leaves.
    Decaying norms/biases is a silent recipe deviation that costs
    convergence at scale."""
    def decayable(path, p):
        name = _leaf_name(path)
        if name in ("scale", "bias") or name.endswith("_b") \
                or _BIAS_NAME.match(name):
            return False
        return jnp.ndim(p) >= 2

    return jax.tree_util.tree_map_with_path(decayable, params)


def transformer_tx(base_lr: float, num_steps: int, *,
                   schedule: str = "warmup_linear",
                   warmup_fraction: float = 0.1,
                   weight_decay: float = 0.01,
                   grad_clip_norm: float = 1.0,
                   optimizer: str = "adamw") -> optax.GradientTransformation:
    """The transformer-family optimizer under the named schedule — the
    default for the BERT/GPT loops (constant LR remains available as
    ``schedule="constant"``).

    ``optimizer``: "adamw" (default) or "lamb" — LAMB layer-wise trust
    ratios (You et al. 2019) are the standard recipe once data-parallel
    scale-out pushes the global batch past ~1k sequences, where adamw's
    single LR stops fitting every layer.

    ``grad_clip_norm``: global-norm gradient clipping applied before the
    update (the canonical BERT/GPT recipe clips at 1.0 — it is what
    lets warmup survive the early loss-spike regime); 0 disables."""
    warmup = max(1, int(warmup_fraction * num_steps))
    if schedule == "constant":
        lr = base_lr
    elif schedule == "warmup_linear":
        lr = warmup_linear(base_lr, warmup, num_steps)
    elif schedule == "warmup_cosine":
        lr = warmup_cosine(base_lr, warmup, num_steps)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    if optimizer == "adamw":
        tx = optax.adamw(lr, weight_decay=weight_decay, mask=decay_mask)
    elif optimizer == "lamb":
        tx = optax.lamb(lr, weight_decay=weight_decay, mask=decay_mask)
    else:
        raise ValueError(f"unknown optimizer {optimizer!r}")
    if grad_clip_norm and grad_clip_norm > 0:
        return optax.chain(optax.clip_by_global_norm(grad_clip_norm), tx)
    return tx
