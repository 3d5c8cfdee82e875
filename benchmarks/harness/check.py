"""The comparison that decides ``correct``: numbers read from the timed
path beside the plain reference's, each held to a limit of its own from
``benchmarks/limits/<cell>.json``.

Training (``training_numbers``): relative gap of each followed step's
loss; worst-leaf gap between the program's and the reference's norm of the
first clipped gradient; worst-leaf gap between their norms of the
parameters' change over the followed steps.  A leaf's gap is measured
against the reference's norm of that leaf or of the median leaf, whichever
is larger.  Leaves whose reference gradient is under a thousandth of the
median leaf's (a key's bias under softmax) move under Adam by round-off
alone and are left out of the change.

Serving (``serving_numbers``): the widest gap by which a served token's
reference logit lies below the reference's best logit at that position.
"""

from __future__ import annotations

import json
import sys

import numpy as np


def require_weight_tree(model, params) -> None:
    """The benchmark makes the weights; they must have the structure and
    shapes of the program's own ``model.init``."""
    import jax

    want = jax.eval_shape(model.init, jax.random.key(0))
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            a.shape != b.shape for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        raise RuntimeError("the benchmark's weight tree is not the "
                           "program's: update reference/transformer.py")


def leaf_gap(prog, ref, keep=None) -> float:
    """Worst leaf of ``|prog - ref| / max(ref, median(ref))``."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(scale, 1e-30)
    if keep is not None:
        gap = gap[np.asarray(keep)]
    return float(gap.max())


def moving_leaves(ref_grad_norms) -> np.ndarray:
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= 1e-3 * np.median(g)


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: ``losses``, ``grad_norms``, ``change_norms``
    (see ``reference/bert_mlm.run_steps``)."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_step{i + 1}_rel"] = float(abs(a - b) / abs(b))
    out["grad_norm_gap"] = leaf_gap(prog["grad_norms"], ref["grad_norms"])
    out["change_norm_gap"] = leaf_gap(
        prog["change_norms"], ref["change_norms"],
        keep=moving_leaves(ref["grad_norms"]))
    return out


def served_gap(ref_logits, served_tokens) -> float:
    """Widest ``max(logits) - logits[served]`` over the positions."""
    ref_logits = np.asarray(ref_logits, np.float64)
    served = np.asarray(served_tokens)
    got = ref_logits[np.arange(len(served)), served]
    return float((ref_logits.max(axis=-1) - got).max())


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, checked)``: ``checked`` maps each number to its value
    and its limit.  Every limit needs its number; a number without a limit
    is shown and not compared."""
    checked = {}
    ok = True
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"limit set for {name!r}, which this run did "
                           f"not read (read: {sorted(numbers)})")
    for name, value in numbers.items():
        limit = limits.get(name)
        checked[name] = {"value": value, "limit": limit}
        if limit is not None and not (value <= limit):   # NaN fails
            ok = False
    return ok, checked


def report(checked: dict, correct: bool) -> None:
    """Each number beside its limit, as the last lines of stderr."""
    print(f"[check] correct={json.dumps(bool(correct))}", file=sys.stderr)
    for name, row in checked.items():
        print(f"[check] {name} value={row['value']:.6g} "
              f"limit={row['limit']}", file=sys.stderr)
    sys.stderr.flush()
