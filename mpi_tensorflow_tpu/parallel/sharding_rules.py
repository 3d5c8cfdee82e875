"""Logical-axis sharding rules: how parameters and activations map to the mesh.

Models annotate every parameter with *logical* axis names (``("embed",
"mlp")`` etc.); a rule table maps logical names to mesh axes.  Swapping the
rule table re-lays-out the same model for a different mesh (pure DP, DP+TP,
DP+TP+SP) without touching model code — the TPU-native replacement for the
reference's hard-wired single-strategy replication (SURVEY.md §2 checklist:
TP/SP absent from the reference; required by the framework goal).

Default rules implement the Megatron layout: attention heads and MLP hidden
sharded over ``model`` (column-parallel in, row-parallel out), batch over
``data``, sequence over ``seq`` for ring attention.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical axis -> mesh axis (None = replicate)
DEFAULT_RULES: dict[str, Optional[str]] = {
    "batch": "data",
    "seq": "seq",
    "embed": None,       # hidden/residual stream replicated
    "heads": "model",    # attention heads tensor-parallel
    "head_dim": None,
    "mlp": "model",      # MLP hidden tensor-parallel
    "vocab": "model",    # embedding/LM-head vocab-parallel
    "pos": None,
    "classes": None,
    "expert": "expert",  # MoE expert stacks expert-parallel (models/moe.py)
    "expert_classes": None,   # router output dim (small) replicated
    "capacity": None,    # per-expert token buffer dim (models/moe.py)
    "stage": "pipe",     # pipeline-stage stacks (parallel/pipeline.py)
    "layer": None,       # within-stage layer dim (models/bert_pipeline.py)
    "vchunk": None,      # interleaved virtual-chunk dim (1f1b_interleaved)
}

# Serving tensor-parallel rules (serving/tp): ONLY the head- and
# mlp-sharded dims map to the ``tp`` axis — the Megatron column/row split
# of attention and MLP.  embed/vocab/pos stay replicated so after the two
# per-layer psum points (attention out-proj, MLP down-proj) every shard
# holds the identical residual stream and computes identical logits; the
# paged KV pool follows ``heads`` (slices of its last axis), which is
# why a block table that indexes BLOCKS, not heads, replicates cleanly.
SERVING_TP_RULES: dict[str, Optional[str]] = {
    "heads": "tp",
    "mlp": "tp",
}


def spec_for(logical_axes: tuple, rules: Mapping[str, Optional[str]],
             mesh: Mesh) -> PartitionSpec:
    """PartitionSpec for one tensor: map each logical axis through the rules,
    dropping mesh axes the mesh doesn't have (or that are size 1)."""
    out = []
    for ax in logical_axes:
        mesh_ax = rules.get(ax)
        if mesh_ax is not None and mesh.shape.get(mesh_ax, 1) > 1:
            out.append(mesh_ax)
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def tree_specs(logical_tree: Any, mesh: Mesh,
               rules: Optional[Mapping[str, Optional[str]]] = None) -> Any:
    """Pytree of logical-axis tuples -> pytree of PartitionSpecs."""
    rules = rules if rules is not None else DEFAULT_RULES
    return jax.tree.map(lambda axes: spec_for(axes, rules, mesh),
                        logical_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def shard_tree(tree: Any, logical_tree: Any, mesh: Mesh,
               rules: Optional[Mapping[str, Optional[str]]] = None) -> Any:
    """Place a pytree of arrays onto the mesh per the rules."""
    specs = tree_specs(logical_tree, mesh, rules)
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        tree, specs)


def constrain(x, logical_axes: tuple, mesh: Mesh,
              rules: Optional[Mapping[str, Optional[str]]] = None):
    """``with_sharding_constraint`` by logical axes, inside jit."""
    rules = rules if rules is not None else DEFAULT_RULES
    spec = spec_for(logical_axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
