"""GSPMD train step: multi-axis (DP x TP x SP) training for transformers.

The explicit ``shard_map`` step in ``train/step.py`` reproduces the
reference's data-parallel semantics with a hand-placed allreduce.  For the
transformer families the idiomatic TPU path is compiler-side partitioning:
parameters are *placed* per the logical sharding rules
(parallel/sharding_rules.py), activations are constrained inside the model,
and XLA GSPMD inserts every collective (gradient allreduce over ``data``,
row-parallel psums over ``model``) — except ring attention, which is
inherently manual and runs as an inner ``shard_map`` over ``seq``
(parallel/ring.py).

One jitted, donated-buffer function is the full training step on any mesh
shape from a single chip to a pod slice.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_tensorflow_tpu.models import base
from mpi_tensorflow_tpu.parallel import fsdp as fsdp_lib
from mpi_tensorflow_tpu.parallel import mesh as meshlib
from mpi_tensorflow_tpu.parallel import sharding_rules as rules_lib


class GspmdState(NamedTuple):
    params: Any
    opt: Any
    model_state: Any
    step: jnp.ndarray


class MasterOpt(NamedTuple):
    """Mixed-precision optimizer state: fp32 master weights + the inner
    optimizer's state (which lives on the masters)."""
    master: Any
    inner: Any


def init_gspmd_state(model, tx: optax.GradientTransformation, rng,
                     mesh: Mesh, rules: Optional[dict] = None,
                     param_dtype=None) -> GspmdState:
    """Initialize and *place* the train state: params go to their mesh
    shards; optimizer moments inherit the param shardings (zeros_like
    preserves sharding).

    ``param_dtype`` (e.g. ``jnp.bfloat16``) stores the *live* parameters in
    that dtype — halving weight HBM traffic per matmul — while the
    optimizer keeps fp32 master copies and applies updates to them
    (``MasterOpt``).  When the model's COMPUTE dtype is bf16 this leaves
    compute numerics unchanged (the model casts weights to bf16 at use
    either way); pairing bf16 params with fp32 compute changes what the
    matmuls see, so callers pair bf16 params with bf16 compute only.
    """
    params = model.init(rng)
    params = rules_lib.shard_tree(params, model.logical_axes(), mesh, rules)
    mstate = base.init_model_state(model)
    if param_dtype is None:
        opt = tx.init(params)
        return GspmdState(params, opt, mstate, jnp.zeros((), jnp.int32))
    master = params   # fp32, placed
    live = jax.tree.map(
        lambda x: x.astype(param_dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    opt = MasterOpt(master=master, inner=tx.init(master))
    return GspmdState(live, opt, mstate, jnp.zeros((), jnp.int32))


def _place_replicated(tree: Any, mesh: Mesh) -> Any:
    """Pin any leaf without an explicit mesh placement to full replication
    (optimizer step counters, model state, the step scalar)."""
    rep = meshlib.replicated(mesh)

    def place(x):
        if isinstance(getattr(x, "sharding", None), NamedSharding):
            return x
        return jax.device_put(jnp.asarray(x), rep)

    return jax.tree.map(place, tree)


def init_fsdp_state(model, tx: optax.GradientTransformation, rng,
                    mesh: Mesh, rules: Optional[dict] = None,
                    axis: str = "data",
                    min_size: int = fsdp_lib.DEFAULT_MIN_SIZE) -> GspmdState:
    """ZeRO/FSDP initialization: parameters — and therefore the optimizer
    moments created from them — live sharded along ``axis``.  TP axes from
    the model's logical rules are kept; FSDP claims a second dimension
    (parallel/fsdp.py)."""
    params = model.init(rng)
    logical = model.logical_axes() if hasattr(model, "logical_axes") else None
    specs = fsdp_lib.fsdp_tree_specs(params, mesh, logical, rules,
                                     axis=axis, min_size=min_size)
    params = fsdp_lib.shard_params(params, mesh, specs)
    opt = _place_replicated(tx.init(params), mesh)
    mstate = _place_replicated(base.init_model_state(model), mesh)
    step = jax.device_put(jnp.zeros((), jnp.int32), meshlib.replicated(mesh))
    return GspmdState(params, opt, mstate, step)


def init_zero1_state(model, tx: optax.GradientTransformation, rng,
                     mesh: Mesh, rules: Optional[dict] = None,
                     axis: str = "data",
                     min_size: int = fsdp_lib.DEFAULT_MIN_SIZE) -> GspmdState:
    """ZeRO-1 initialization: parameters keep their rule-table placement
    (pipe-sharded stages, TP axes, data-replicated weights) — so the
    manual pipeline schedules' shard_map in_specs still hold — while the
    optimizer moments are additionally sharded over ``axis``
    (parallel/fsdp.py::zero1_shard_opt).  Pass the result as
    ``state_template`` to pin the moments to their shards across steps."""
    st = init_gspmd_state(model, tx, rng, mesh, rules)
    # shard_tree leaves un-ruled leaves (layernorm scales, counters)
    # unplaced; a state used as ``state_template`` must carry an explicit
    # mesh placement on EVERY leaf or out_shardings conflicts
    params = _place_replicated(st.params, mesh)
    opt = fsdp_lib.zero1_shard_opt(_place_replicated(st.opt, mesh),
                                   mesh, axis=axis, min_size=min_size)
    mstate = _place_replicated(st.model_state, mesh)
    step = jax.device_put(st.step, meshlib.replicated(mesh))
    return GspmdState(params, opt, mstate, step)


def grad_accum_dtype(opt_state) -> Optional[Any]:
    """Accumulation dtype for scanned microbatch gradients: fp32 when the
    optimizer keeps fp32 masters (live params — and thus per-microbatch
    grads — are low precision), None (= grad dtype) otherwise."""
    return jnp.float32 if isinstance(opt_state, MasterOpt) else None


def shard_batch(tree: Any, mesh: Mesh):
    """Place host batch arrays: leading dim over ``data``, second dim over
    ``seq`` when the mesh has one (token grids are (B, S))."""
    def place(x):
        axes = [None, None]
        if mesh.shape.get("data", 1) > 1:
            axes[0] = "data"
        if x.ndim >= 2 and mesh.shape.get("seq", 1) > 1 \
                and x.shape[1] % mesh.shape["seq"] == 0:
            axes[1] = "seq"
        return jax.device_put(x, NamedSharding(mesh, P(*axes[:x.ndim])))

    return jax.tree.map(place, tree)


def make_gspmd_train_step(model, mesh: Mesh,
                          tx: optax.GradientTransformation,
                          state_template: Optional[GspmdState] = None,
                          grad_accum: int = 1):
    """Full training step: loss -> grads -> optax update, all under one jit.

    ``model.loss(params, model_state, batch, labels, rng=..., train=True)``
    supplies the objective (the MLM loss for BERT).

    ``state_template`` (an initialized, placed state) pins the output state
    back to its input shardings — required for FSDP, where the compiler
    must re-scatter parameters and moments after the update instead of
    leaving them gathered.

    ``grad_accum > 1`` splits the batch into that many microbatches and
    accumulates their mean gradient in an on-device ``lax.scan`` before the
    single optimizer update (same semantics, 1/A the activation memory).
    """
    accum = max(1, int(grad_accum))

    def step(state: GspmdState, batch, labels, rng):
        rng = jax.random.fold_in(rng, state.step)

        def lf(params, b, l, r):
            loss, ms = model.loss(params, state.model_state, b, l,
                                  rng=r, train=True)
            return loss, ms

        if accum == 1:
            (loss, ms), grads = jax.value_and_grad(lf, has_aux=True)(
                state.params, batch, labels, rng)
        else:
            def split(x):
                if x.shape[0] % accum:
                    raise ValueError(
                        f"batch dim {x.shape[0]} not divisible by "
                        f"grad_accum {accum}")
                return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])

            mb = jax.tree.map(split, batch)
            ml = jax.tree.map(split, labels)

            # with bf16 live params the per-microbatch grads come out bf16;
            # accumulate in fp32 or small contributions are swallowed —
            # exactly the error mode the fp32 masters exist to avoid
            acc_dtype = grad_accum_dtype(state.opt)

            def up(g):
                if acc_dtype and jnp.issubdtype(g.dtype, jnp.floating):
                    return g.astype(acc_dtype)
                return g

            def micro(carry, xs):
                g_acc, l_acc, mstate = carry
                b, l, i = xs

                def lf_ms(params, b, l, r):
                    # thread the running model state microbatch-to-
                    # microbatch (matches the accum=1 path and the psum
                    # implementation in step.py)
                    return model.loss(params, mstate, b, l, rng=r,
                                      train=True)

                (loss, ms), g = jax.value_and_grad(lf_ms, has_aux=True)(
                    state.params, b, l, jax.random.fold_in(rng, i))
                return (jax.tree.map(lambda a, x: a + up(x), g_acc, g),
                        l_acc + loss, ms), None

            zeros = jax.tree.map(lambda x: jnp.zeros_like(up(x)),
                                 state.params)
            (grads, loss, ms), _ = lax.scan(
                micro, (zeros, jnp.zeros(()), state.model_state),
                (mb, ml, jnp.arange(accum)))
            grads = jax.tree.map(lambda x: x / accum, grads)
            loss = loss / accum

        if isinstance(state.opt, MasterOpt):
            # mixed precision: grads (param dtype) -> fp32, update the fp32
            # masters, re-emit the live params in their storage dtype
            g32 = jax.tree.map(
                lambda g: g.astype(jnp.float32)
                if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
            updates, inner = tx.update(g32, state.opt.inner,
                                       state.opt.master)
            master = optax.apply_updates(state.opt.master, updates)
            params = jax.tree.map(
                lambda m, p: m.astype(p.dtype), master, state.params)
            return (GspmdState(params, MasterOpt(master, inner), ms,
                               state.step + 1), {"loss": loss})
        updates, opt = tx.update(grads, state.opt, state.params)
        params = optax.apply_updates(state.params, updates)
        return (GspmdState(params, opt, ms, state.step + 1),
                {"loss": loss})

    if state_template is None:
        return jax.jit(step, donate_argnums=0)
    out_shardings = (fsdp_lib.state_out_shardings(state_template),
                     {"loss": meshlib.replicated(mesh)})
    return jax.jit(step, donate_argnums=0, out_shardings=out_shardings)


def make_gspmd_multi_step(model, mesh: Mesh,
                          tx: optax.GradientTransformation,
                          state_template: Optional[GspmdState] = None,
                          grad_accum: int = 1):
    """K GSPMD train steps per dispatch via ``lax.scan`` over stacked
    batches — the transformer counterpart of train/step.py's
    ``make_multi_train_step`` (amortizes per-dispatch latency; used by the
    benchmark harness).  ``batches``/``labels`` carry a leading (K,) axis on
    every leaf.  ``state_template`` as in ``make_gspmd_train_step`` — pins
    output shardings so FSDP states stay sharded across the scan."""
    one = make_gspmd_train_step(model, mesh, tx,
                                state_template=state_template,
                                grad_accum=grad_accum)

    def multi(state: GspmdState, batches, labels, rng):
        def body(s, xs):
            b, l = xs
            return one(s, b, l, rng)

        return lax.scan(body, state, (batches, labels))

    if state_template is None:
        return jax.jit(multi, donate_argnums=0)
    out_shardings = (fsdp_lib.state_out_shardings(state_template),
                     {"loss": meshlib.replicated(mesh)})
    return jax.jit(multi, donate_argnums=0, out_shardings=out_shardings)


def make_gspmd_eval_step(model, mesh: Mesh):
    """Forward-only logits (eval mode)."""

    def fwd(state: GspmdState, tokens):
        return model.apply(state.params, tokens, train=False)

    return jax.jit(fwd)
