"""Dropless routed experts for a chip that holds a SHARE of them.

The layer is told which experts it holds (``first``, ``count`` of the
router's ``n_routed``), routes every token over all of them, and
computes its own experts' part of the result: the sum over the chosen
experts that live here, with gate weights normalised over ALL the chosen
ones.  What the absent experts would add is another chip's to compute;
a token none of whose experts is held gets nothing from this function
(the caller adds the shared expert).  No capacity, no dropped token:
the (token, expert) pairs that land on held experts are sorted by
expert, run through one grouped matmul per projection over the experts
held, and gathered back in token order.  models/moe.py's capacity-
dropping layer stays what the trainer uses.

Two grouped-matmul lowerings, one literal resolved before tracing
(``resolve_impl``): ``gmm`` (the Pallas grouped matmul of
``jax.experimental.pallas.ops.tpu.megablox``, whose Mosaic kernel is
named ``gmm`` in a device trace: it visits only the row tiles that
groups touch, so an expert nobody chose costs no weight read) and
``ragged`` (``lax.ragged_dot``, what runs off the chip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# the Mosaic kernel's name in a device trace (megablox's own)
KERNEL_NAME = "gmm"
ROW_TILE = 128
SPLIT_ROWS = 2048        # pair bounds from here up try a quarter first


def resolve_impl(kernel: str) -> str:
    """The grouped matmul that goes with a resolved attention kernel
    literal: Mosaic where that is Mosaic, ``lax.ragged_dot`` elsewhere
    (the interpreter is kept for the kernel's own test)."""
    return "gmm" if kernel == "pallas" else "ragged"


def route(x, router, *, top_k: int, scale: float, norm_topk: bool = True):
    """Sigmoid routing in float32.

    x (T, E) any float dtype, router (N, E).  Returns ``(experts (T, k)
    int32, gates (T, k) float32)``: the ``top_k`` largest of the N
    sigmoid scores and ``scale * s_i / (sum of the chosen + 1e-20)``."""
    logits = jnp.einsum("te,ne->tn", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores, experts = lax.top_k(jax.nn.sigmoid(logits), top_k)
    if norm_topk:
        scores = scores / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), scores * scale


def _grouped(lhs, rhs, sizes, impl: str):
    """``lhs`` rows, sorted by group, times each group's ``rhs[g]``."""
    if impl == "ragged":
        return lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    k, n = rhs.shape[1:]
    return gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
               tiling=(ROW_TILE, _tile(k), _tile(n)),
               interpret=impl == "gmm-interpret")


def _tile(dim: int, cap: int = 1024) -> int:
    """Largest multiple of 128 that divides ``dim`` and is at most
    ``cap`` (``dim`` itself when 128 does not divide it)."""
    if dim % 128:
        return dim
    t = min(cap, dim) // 128 * 128
    while dim % t:
        t -= 128
    return t


def held_experts(x, experts, gates, valid, weights, *, first: int,
                 impl: str = "ragged"):
    """The held experts' part of the routed sum.

    x:       (T, E) tokens
    experts: (T, k) chosen expert ids over the router's whole width
    gates:   (T, k) float32 weights
    valid:   (T,) bool; an invalid token (padding) is routed nowhere
    weights: {"w_gate", "w_up": (n_held, E, F), "w_down": (n_held, F, E)}
    first:   id of the first held expert (they are contiguous)

    Returns ``(y (T, E) float32, counts (n_held + 1,) int32)``: counts
    holds the assignments each held expert received and, last, how many
    of them received any.
    """
    T, k = experts.shape
    n_held = weights["w_gate"].shape[0]
    local = experts - first
    held = (local >= 0) & (local < n_held) & valid[:, None]
    # sort the pairs by held expert; pairs that land elsewhere sort last
    key = jnp.where(held, local, n_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    dt = x.dtype
    total = jnp.sum(sizes)

    def run(rows: int):
        """The grouped matmuls over the first ``rows`` sorted pairs
        (every held pair is among them when ``total <= rows``)."""
        rows_p = rows if impl == "ragged" \
            else -(-rows // ROW_TILE) * ROW_TILE
        # padding rows point past the last pair: gathered from the last
        # token, weighted 0, dropped by the scatter back
        first_rows = jnp.pad(order[:rows], (0, rows_p - rows),
                             constant_values=T * k)
        pair = jnp.minimum(first_rows, T * k - 1)
        xs = x[pair // k]                                    # (rows, E)
        with jax.named_scope("moe_experts"):
            g = _grouped(xs, weights["w_gate"].astype(dt), sizes, impl)
            u = _grouped(xs, weights["w_up"].astype(dt), sizes, impl)
            hmid = (jax.nn.silu(g) * u).astype(dt)
            out = _grouped(hmid, weights["w_down"].astype(dt), sizes, impl)
        # rows past the groups hold whatever the lowering left there
        live = jnp.arange(rows_p) < total
        w = jnp.where(held, gates, 0.0).reshape(-1)[pair]
        out = jnp.where(live[:, None], out * w[:, None], 0.0).astype(dt)
        # back to token order: a pair's row in ``out``, or the zero row
        slot = jnp.full((T * k,), rows_p, jnp.int32).at[first_rows].set(
            jnp.arange(rows_p, dtype=jnp.int32), mode="drop")
        slot = jnp.where(held.reshape(-1), slot, rows_p)
        out = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), dt)])
        return jnp.sum(out[slot].reshape(T, k, -1).astype(jnp.float32),
                       axis=1)

    # at most min(k, n_held) pairs of a token can land here.  That bound
    # is what dropless costs when every pair does; a share of the experts
    # sees a fraction of it, so a large batch first asks whether a
    # quarter of the rows holds them all (both arms are compiled, one
    # runs, none drops a pair)
    rows = T * min(k, n_held)
    if rows >= SPLIT_ROWS:
        y = lax.cond(total <= rows // 4, lambda: run(rows // 4),
                     lambda: run(rows))
    else:
        y = run(rows)
    counts = jnp.concatenate(
        [sizes, jnp.sum(sizes > 0, dtype=jnp.int32)[None]])
    return y, counts
