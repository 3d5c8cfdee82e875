"""Tensor-parallel paged decode: shard the serving stack over a ``tp``
mesh axis.

The serving engine's device state is one paged KV pool per layer,
token-major ``(num_blocks, block_size, H*D)`` with the heads side by
side on the last axis.  Heads are embarrassingly
parallel through attention (every head attends independently; the only
cross-head contractions are the row-parallel output projections), so the
Megatron split carries over to serving unchanged:

- the POOL shards on its last axis, whose contiguous ``tp``-ths are
  whole heads: each of the ``tp`` shards holds ``H / tp`` heads of
  every block — aggregate KV capacity in
  tokens is unchanged per pool, but the HBM for it is spread over the
  mesh, and (the point) per-chip attention/projection work drops
  ``tp``-fold;
- the QKV projections split column-parallel on their ``heads`` output
  dim and the MLP up-projection on ``mlp``, so each shard computes only
  its local heads' K/V (which land in its local pool shard) and its
  local MLP slice;
- the attention out-proj and MLP down-proj are row-parallel: each shard
  contributes a partial ``(B, S, E)`` product and ONE ``lax.psum`` per
  projection (two per layer) rebuilds the replicated residual stream —
  the ``reduce`` hook ``models/gpt.forward_paged`` threads into
  ``attn_out_proj`` / ``gelu_mlp``;
- the BLOCK TABLE, tokens, and lengths replicate: a table indexes
  blocks, not heads, so the host-side scheduler/allocator/prefix-trie
  machinery is completely unaware of ``tp`` — one block id means the
  same block slot in every pool shard, copy-on-write copies every
  shard's rows of a block with the same traced ids, and eviction frees
  the same id everywhere.

Each shard runs the EXISTING ``ops/paged_attention.attend`` dispatch
(XLA gather or the fused Pallas kernel) over its local heads — ``H`` is
a pure batch dimension in both lowerings — and the logits every shard
computes after the psum points are identical, so greedy serving under
TP is token-identical to the single-device engine (pinned by
tests/test_serving_tp.py on a multi-device CPU mesh via the virtual
device platform).

Everything here is resolved ONCE at engine construction: the mesh, the
param/pool placements, and the shard_map-wrapped forward are all static
under the engine's jitted steps, so TP adds no dispatch shapes and the
zero-recompile contract holds exactly as on one device.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_tensorflow_tpu.parallel import sharding_rules as rules_lib

#: the mesh axis name the serving TP split lives on
TP_AXIS = "tp"

#: every pool leaf is (num_blocks, block_size, heads * width): heads
#: shard as contiguous slices of the last axis
_POOL_SPEC = P(None, None, TP_AXIS)


def _check_device_count(tp: int) -> None:
    """THE device-count rule, shared by ``check_geometry`` and
    ``make_tp_mesh`` so the two entry points cannot drift."""
    ndev = len(jax.devices())
    if tp > ndev:
        raise ValueError(
            f"--tp {tp} exceeds the {ndev} visible device(s)")


def check_geometry(cfg, tp: int) -> None:
    """Reject a ``tp`` the model/mesh cannot honor — the one place the
    head/mlp divisibility and device-count rules are stated (engine
    construction routes through here)."""
    if tp < 1:
        raise ValueError(f"--tp must be >= 1, got {tp}")
    if tp == 1:
        return
    refusal = getattr(cfg, "tp_refusal", None)
    if refusal is not None:
        raise ValueError(f"--tp {tp}: {refusal}")
    _check_device_count(tp)
    if cfg.heads % tp or cfg.mlp % tp:
        raise ValueError(
            f"--tp {tp} must divide both heads ({cfg.heads}) and "
            f"mlp ({cfg.mlp}): the pool shards by head and the "
            f"MLP up-projection on its hidden axis")


def make_tp_mesh(tp: int) -> Mesh:
    """A 1-D ``(tp,)`` mesh over the first ``tp`` devices (guarded:
    slicing past the device list would silently build a smaller
    mesh)."""
    _check_device_count(tp)
    return Mesh(np.asarray(jax.devices()[:tp]), (TP_AXIS,))


def param_specs(model, mesh: Mesh):
    """PartitionSpec pytree for the model parameters under the serving
    TP rules (heads/mlp over ``tp``, everything else replicated)."""
    return rules_lib.tree_specs(model.logical_axes(), mesh,
                                rules_lib.SERVING_TP_RULES)


def pool_specs(layers: int, kv_dtype: str = "fp32"):
    """PartitionSpec pytree for the per-layer K/V pools: the last axis
    of ``(num_blocks, block_size, H*D)`` over ``tp``, a contiguous
    ``H/tp`` heads to a shard.  A quantized pool's scale siblings —
    ``(num_blocks, block_size, H)`` int8 row scales or ``(num_blocks,
    block_size, H*G)`` int4 group scales — and int4's packed
    ``H*D//2`` codes lay the heads out in the same order on the same
    axis, so one spec serves every leaf."""
    keys = ("k", "v") + (("k_scale", "v_scale")
                         if kv_dtype in ("int8", "int4") else ())
    return [dict.fromkeys(keys, _POOL_SPEC) for _ in range(layers)]


def shard_params(model, params, mesh: Mesh):
    """Place the parameter pytree onto the mesh per the TP rules."""
    return rules_lib.shard_tree(params, model.logical_axes(), mesh,
                                rules_lib.SERVING_TP_RULES)


def shard_pools(pools, mesh: Mesh):
    """Place freshly initialized (host-built) pools onto the mesh,
    head-sharded on the last axis — generic over the layer dict's leaves
    (codes and their scale siblings all lay the heads side by side
    there).  The pool is token-major so that no serving program re-lays
    it (serving/paged_cache); a shard's slice is still whole heads."""
    s = NamedSharding(mesh, _POOL_SPEC)
    return [{key: jax.device_put(leaf, s) for key, leaf in p.items()}
            for p in pools]


def make_paged_forward(model, mesh: Mesh, kernel: str,
                       kv_dtype: str = "fp32"):
    """The shard_map-wrapped ``forward_paged``: params and pools enter
    pre-sharded (heads/mlp/the pool's last axis over ``tp``), tokens / block
    tables / lengths / valid masks replicated.  Each shard runs the full
    per-layer math over its local heads with ``lax.psum`` over ``tp`` as
    the row-parallel reduce hook, so the returned logits are replicated
    (identical on every shard) and the returned pools stay head-sharded.

    Same signature as the engine's single-device forward seam:
    ``(params, tokens, pools, tables, lengths, valid) -> (logits,
    pools)``.
    """
    specs = param_specs(model, mesh)
    pspec = pool_specs(model.cfg.layers, kv_dtype)
    rep = P()

    def inner(params, tokens, pools, tables, lengths, valid):
        red = lambda x: jax.lax.psum(x, TP_AXIS)       # noqa: E731
        return model.forward_paged(params, tokens, pools, tables,
                                   lengths, valid=valid, kernel=kernel,
                                   reduce=red)

    # check_vma off: the psum points make the logits replicated by
    # construction — the train-step call sites' convention
    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(specs, rep, pspec, rep, rep, rep),
                         out_specs=(rep, pspec), check_vma=False)
