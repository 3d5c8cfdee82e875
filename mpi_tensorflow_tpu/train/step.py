"""The jit-compiled train/eval steps — the heart of the framework.

One donated-buffer jitted function replaces components 7, 9, 10 and 11 of the
reference (SURVEY.md §2): loss+optimizer graph (mpipy.py:55-66), session
execution (mpipy.py:72-74, 85), and parameter synchronization
(mpipy.py:95-153).  All host<->device and MPI crossings of the reference's
stacks 3.3/3.4 collapse into an in-graph ``pmean`` over the mesh's ``data``
axis riding ICI.

Two synchronization strategies:

- ``psum`` (default): per-step gradient allreduce — true synchronous SGD,
  the semantics BASELINE.json directs ("replace the per-step MPI.Allreduce
  gradient sum with jax.lax.psum over the ICI mesh").  Parameters stay
  replicated and bit-identical across shards.

- ``avg50``: the reference's actual strategy — independent per-shard SGD with
  periodic parameter averaging (mpipy.py:95-153) — with its rank-0-only bug
  fixed: every shard receives the mean (the reference's ``bcast_parameters``
  never broadcasts; ranks != 0 diverge freely, SURVEY.md §2 #11).  Parameter
  state carries a leading shard axis and lives sharded over ``data``.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mpi_tensorflow_tpu.models.base import l2_loss
from mpi_tensorflow_tpu.parallel import collectives
from mpi_tensorflow_tpu.train.optimizer import (
    MomentumState,
    momentum_apply,
    momentum_init,
    reference_schedule,
)


class TrainState(NamedTuple):
    params: Any
    opt: MomentumState
    model_state: Any = {}   # e.g. BatchNorm running stats; {} when stateless


def init_state(model, rng) -> TrainState:
    params = model.init(rng)
    from mpi_tensorflow_tpu.models import base

    return TrainState(params, momentum_init(params),
                      base.init_model_state(model))


def make_loss_fn(model, config):
    """Mean sparse-softmax-CE + L2 on the model's regularized subset
    (mpipy.py:55-58).  Returns ``(loss, new_model_state)``."""
    from mpi_tensorflow_tpu.models import base

    def loss_fn(params, model_state, batch, labels, rng):
        logits, new_state = base.run_model(model, params, model_state, batch,
                                           train=True, rng=rng)
        ce = jnp.mean(optax_softmax_ce(logits, labels))
        reg = config.weight_decay * sum(l2_loss(p) for p in model.l2_params(params))
        return ce + reg, new_state

    return loss_fn


def optax_softmax_ce(logits, labels):
    """``tf.nn.sparse_softmax_cross_entropy_with_logits`` (mpipy.py:55-56)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return logz - gold


def _sync_step_body(model, config, schedule):
    """The per-step body shared by the one-step and scan (multi-step)
    compilations: per-shard grads -> allreduce -> momentum update.

    ``config.grad_accum > 1`` splits the per-shard batch into that many
    microbatches and accumulates their mean gradient in an on-device
    ``lax.scan`` before the (single) allreduce and update — same update
    semantics, 1/A the activation memory (the standard way to hold the
    global batch when activations don't fit HBM)."""
    loss_fn = make_loss_fn(model, config)
    accum = int(getattr(config, "grad_accum", 1) or 1)

    # differentiate w.r.t. a 'data'-varying view of the params so the
    # backward pass yields LOCAL grads, then allreduce ONCE, explicitly
    # (lax.psum below).  Both accum paths share the pattern; relying on
    # the autodiff transpose of replicated params to emit the psum would
    # tie the gradient semantics to shard_map's replication machinery
    to_varying = lambda t: jax.tree.map(
        lambda x: lax.pcast(x, "data", to="varying"), t)

    def grads_of(params, model_state, batch, labels, rng):
        if accum <= 1:
            (loss, new_ms), g = jax.value_and_grad(loss_fn, has_aux=True)(
                to_varying(params), model_state, batch, labels, rng)
            g = jax.tree.map(lambda x: lax.psum(x, "data"), g)
            return (loss, new_ms), g
        n = batch.shape[0]
        if n % accum:
            raise ValueError(
                f"per-shard batch {n} not divisible by grad_accum {accum}")
        mb = batch.reshape(accum, n // accum, *batch.shape[1:])
        ml = labels.reshape(accum, n // accum, *labels.shape[1:])
        p_local = to_varying(params)

        def micro(carry, xs):
            g_acc, l_acc, mstate = carry
            b, l, i = xs
            (loss, new_ms), g = jax.value_and_grad(loss_fn, has_aux=True)(
                p_local, mstate, b, l, jax.random.fold_in(rng, i))
            return (jax.tree.map(jnp.add, g_acc, g), l_acc + loss,
                    new_ms), None

        # accumulators carry the 'data'-varying type the body produces —
        # cf. the same pattern in parallel/ring.py
        zeros = to_varying(jax.tree.map(jnp.zeros_like, params))
        (g, l, ms), _ = lax.scan(
            micro, (zeros, to_varying(jnp.zeros(())),
                    to_varying(model_state)),
            (mb, ml, jnp.arange(accum)))
        g = jax.tree.map(lambda x: lax.psum(x / accum, "data"), g)
        return ((l / accum, ms), g)

    def step(state: TrainState, batch, labels, rng):
        # distinct dropout stream per shard and per step (derived in-graph —
        # the host passes one base key for the whole run)
        rng = jax.random.fold_in(rng, lax.axis_index("data"))
        rng = jax.random.fold_in(rng, state.opt.step.astype(jnp.int32))
        (loss, new_mstate), grads = grads_of(
            state.params, state.model_state, batch, labels, rng)
        # grads_of allreduces explicitly (this IS the reference's intended
        # MPI.Allreduce): grads hold sum_s(local-mean grad_s); normalize
        # by the axis size to get the global-batch mean gradient.
        grads = jax.tree.map(lambda g: g / lax.axis_size("data"), grads)
        loss = collectives.allreduce_mean(loss, "data")
        # cross-replica batch-stat averaging keeps model state replicated
        new_mstate = jax.tree.map(
            lambda x: collectives.allreduce_mean(x, "data"), new_mstate)
        lr = schedule(state.opt.step)
        params, opt = momentum_apply(state.params, grads, state.opt, lr,
                                     config.momentum)
        return TrainState(params, opt, new_mstate), {"loss": loss, "lr": lr}

    return step


def make_train_step(model, config, mesh, decay_steps: int):
    """Synchronous-SGD step: per-shard grads -> ``pmean`` over ``data`` ->
    identical momentum update on every shard.  Returns a jitted function
    ``(state, batch, labels, rng) -> (state, metrics)`` with the state buffer
    donated."""
    schedule = reference_schedule(config, decay_steps)
    step = _sync_step_body(model, config, schedule)

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P("data"), P("data"), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded, donate_argnums=0)


def make_multi_train_step(model, config, mesh, decay_steps: int,
                          masked: bool = False):
    """K synchronous-SGD steps per dispatch via an on-device ``lax.scan``.

    The reference pays a host round-trip every step (``sess.run`` with a
    feed_dict, mpipy.py:85); the one-step path above already removes the data
    copies but still dispatches once per step.  For small models the dispatch
    latency dominates the device time, so the loop can stage K batches on
    device — ``batches: (K, global_b, ...)``, ``labels: (K, global_b)`` — and
    scan the identical step body K times with zero host involvement.
    Semantically equivalent to K calls of ``make_train_step``'s function
    (pinned by tests/test_train_step.py); metrics come back stacked (K,).

    ``masked=True`` adds a trailing ``n_valid`` argument: only scan indices
    ``< n_valid`` apply their update (``lax.cond`` skips the rest), so every
    window — full, trace-aligned, or tail — reuses ONE compiled shape.
    Variable-length windows would otherwise each trigger a fresh XLA compile
    inside the timed run (measured: a hidden 8x slowdown on short runs).
    """
    schedule = reference_schedule(config, decay_steps)
    step = _sync_step_body(model, config, schedule)

    def multi(state: TrainState, batches, labels, rng, n_valid=None):
        def body(s, xs):
            b, l, j = xs
            if n_valid is None:
                return step(s, b, l, rng)
            return lax.cond(
                j < n_valid,
                lambda s, b, l: step(s, b, l, rng),
                # skipped (padding) step: state unchanged, zero metrics —
                # both replicated-typed like the real step's outputs
                lambda s, b, l: (s, {"loss": jnp.float32(0.0),
                                     "lr": jnp.float32(0.0)}),
                s, b, l)

        K = batches.shape[0]
        return lax.scan(body, state,
                        (batches, labels, jnp.arange(K)))

    if masked:
        sharded = jax.shard_map(
            multi, mesh=mesh,
            in_specs=(P(), P(None, "data"), P(None, "data"), P(), P()),
            out_specs=(P(), P()),
        )
        return jax.jit(sharded, donate_argnums=0)

    sharded = jax.shard_map(
        lambda s, b, l, r: multi(s, b, l, r), mesh=mesh,
        in_specs=(P(), P(None, "data"), P(None, "data"), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded, donate_argnums=0)


def make_eval_step(model, config, mesh):
    """Sharded batched inference -> softmax predictions (the reference's
    ``eval_prediction``, mpipy.py:68 — minus its eval-dropout bug)."""
    from mpi_tensorflow_tpu.models import base

    def fwd(params, model_state, batch):
        logits, _ = base.run_model(model, params, model_state, batch,
                                   train=False)
        return jax.nn.softmax(logits)

    sharded = jax.shard_map(
        fwd, mesh=mesh, in_specs=(P(), P(), P("data")), out_specs=P("data"))
    return jax.jit(sharded)


def make_multi_eval_step(model, config, mesh):
    """All eval windows in ONE dispatch: ``(params, model_state, windows
    (K, B, ...)) -> (K, B, C)`` softmax probs via an on-device scan (pairs
    with evaluation.eval_in_batches_fused; per-dispatch latency otherwise
    dominates batchwise eval on small models)."""
    from mpi_tensorflow_tpu.models import base

    def fwd(params, model_state, windows):
        def body(carry, b):
            logits, _ = base.run_model(model, params, model_state, b,
                                       train=False)
            return carry, jax.nn.softmax(logits)

        _, probs = lax.scan(body, 0, windows)
        return probs

    sharded = jax.shard_map(
        fwd, mesh=mesh, in_specs=(P(), P(), P(None, "data")),
        out_specs=P(None, "data"))
    return jax.jit(sharded)


def make_stacked_eval_step(model, config, mesh):
    """Eval for avg50 mode: each shard predicts with its OWN diverged params
    (each MPI rank evaluates its own replica in the reference)."""
    from mpi_tensorflow_tpu.models import base

    def fwd(params, model_state, batch):
        params = jax.tree.map(lambda x: x[0], params)
        model_state = jax.tree.map(lambda x: x[0], model_state)
        logits, _ = base.run_model(model, params, model_state, batch,
                                   train=False)
        return jax.nn.softmax(logits)

    sharded = jax.shard_map(
        fwd, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=P("data"))
    return jax.jit(sharded)


# --------------------------------------------------------------------------
# avg50 fidelity mode: independent per-shard SGD + periodic averaging
# --------------------------------------------------------------------------

def stack_state(state: TrainState, n: int) -> TrainState:
    """Replicate state with a leading shard axis (each shard will evolve its
    own copy, as each MPI rank does in the reference)."""
    stack = lambda x: jnp.broadcast_to(x, (n,) + x.shape)
    return jax.tree.map(stack, state)


def unstack_shard0(state: TrainState) -> TrainState:
    return jax.tree.map(lambda x: x[0], state)


def make_local_train_step(model, config, mesh, decay_steps: int):
    """Per-shard independent update — NO cross-shard communication, exactly
    like the reference between syncs (mpipy.py:79-91)."""
    schedule = reference_schedule(config, decay_steps)
    loss_fn = make_loss_fn(model, config)

    def step(state: TrainState, batch, labels, rng):
        state = jax.tree.map(lambda x: x[0], state)  # strip shard axis block
        rng = jax.random.fold_in(rng, lax.axis_index("data"))
        rng = jax.random.fold_in(rng, state.opt.step.astype(jnp.int32))
        (loss, new_mstate), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.model_state, batch, labels, rng)
        lr = schedule(state.opt.step)
        params, opt = momentum_apply(state.params, grads, state.opt, lr,
                                     config.momentum)
        new = TrainState(params, opt, new_mstate)
        new = jax.tree.map(lambda x: x[None], new)
        return new, {"loss": loss[None], "lr": lr[None]}

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P()),
        out_specs=(P("data"), P("data")),
    )
    return jax.jit(sharded, donate_argnums=0)


def make_average_step(mesh):
    """The corrected ``bcast_parameters``: average parameters across shards
    and deliver the mean to EVERY shard (the reference gathers to rank 0,
    averages, and assigns only there — mpipy.py:95-153; the missing Bcast is
    the bug SURVEY.md §2 #11 documents).  Optimizer velocity is averaged too
    so shards restart from a common state."""

    def avg(state: TrainState):
        def mean_keep_step(x):
            return lax.pmean(x, "data")
        new = jax.tree.map(mean_keep_step, state)
        return new

    sharded = jax.shard_map(
        avg, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    return jax.jit(sharded, donate_argnums=0)
