"""``program.model`` ``cohere2_moe``: the repo's ``Cohere2MoeLm`` (parallel
attention + MoE blocks, grouped-query attention over a paged pool in the
full layers and over rings by slot in the window layers, a share of the
sigmoid-routed experts beside the shared ones), served through
``forward_paged``; its reference is ``reference/cohere2_moe.py``.

Flops are the REFERENCE's form and count what the architecture obliges:
2 x parameters a token touches (attention projections, router, the shared
experts, and the held share ``top_k x held / router_width`` of a routed
expert), ``4 x heads x head_dim`` a query-key pair a layer (a window
layer's query at ``p`` sees ``min(p + 1, W)`` keys, a full layer's ``p +
1``), and the head over the held vocabulary at the tokens that emit one
(the program runs it on those alone).  The functions below the six give
the obliged bytes and operations of the grouped-query kernels
(``metrics/gqa_*_roofline.py``).
"""

from __future__ import annotations

from ...reference import cohere2_moe as ref

init_params = ref.init_params
_SIZES: dict = {}        # the last ``sizes()``: the driver calls it first,
                         # and ``reference_logits`` is given no sizes


def sizes(cfg: dict) -> dict:
    _SIZES.clear()
    _SIZES.update(ref.sizes(cfg))
    return dict(_SIZES)


def build(sz: dict, dtype):
    from mpi_tensorflow_tpu.models import cohere2_moe

    kinds = tuple(cohere2_moe.WINDOW if i in sz["window_layers"]
                  else cohere2_moe.FULL for i in range(sz["layers"]))
    return cohere2_moe.Cohere2MoeLm(cohere2_moe.Cohere2MoeConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        intermediate_size=sz["expert_mlp"], num_hidden_layers=sz["layers"],
        num_attention_heads=sz["heads"], num_key_value_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], sliding_window=sz["window"],
        layer_types=kinds, num_experts=sz["router_width"],
        num_experts_per_tok=sz["top_k"], num_shared_experts=sz["shared"],
        norm_topk_prob=sz["norm_topk"], rope_theta=sz["theta"],
        layer_norm_eps=sz["eps"], logit_scale=sz["logit_scale"],
        max_position_embeddings=sz["positions"],
        experts_held=(sz["experts_first"], sz["experts_held"]),
        dtype=dtype))


def layer_counts(sz: dict) -> dict:
    window = len(sz["window_layers"])
    return {"window": window, "full": sz["layers"] - window}


def attention_params(sz: dict) -> int:
    E, D = sz["hidden"], sz["head_dim"]
    return 2 * E * sz["heads"] * D + 2 * E * sz["kv_heads"] * D


def expert_params(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["expert_mlp"]


def layer_params(sz: dict) -> int:
    """One layer as this chip holds it: attention, router, the shared
    experts and the held routed ones (LayerNorm scales left out)."""
    return attention_params(sz) + sz["router_width"] * sz["hidden"] \
        + (sz["shared"] + sz["experts_held"]) * expert_params(sz)


def token_matmul_flops(sz: dict) -> float:
    """2 x parameters one token touches in the stack (no head)."""
    share = sz["top_k"] * sz["experts_held"] / sz["router_width"]
    per_layer = attention_params(sz) + sz["router_width"] * sz["hidden"] \
        + (sz["shared"] + share) * expert_params(sz)
    return 2.0 * sz["layers"] * per_layer


def pair_flops(sz: dict) -> float:
    """One query-key pair in one attention layer: 2 D of score and 2 D of
    weighted values a query head."""
    return 4.0 * sz["heads"] * sz["head_dim"]


def window_pairs(lo: int, hi: int, W: int) -> int:
    """Keys the queries at positions ``[lo, hi)`` see in one window
    layer: ``min(p + 1, W)`` each."""
    ramp = min(hi, W)
    below = (ramp * (ramp + 1) - lo * (lo + 1)) // 2 if lo < ramp else 0
    return below + max(0, hi - max(lo, W)) * W


def _pairs(sz: dict, lo: int, hi: int) -> float:
    """Query-key pairs of the queries at ``[lo, hi)`` over all layers."""
    n = layer_counts(sz)
    full = (hi * (hi + 1) - lo * (lo + 1)) // 2
    return n["full"] * full + n["window"] * window_pairs(lo, hi,
                                                         sz["window"])


def request_flops(sz: dict, prompt_len: int, first: int, last: int,
                  with_prompt: bool) -> float:
    """``flops.serve_request_flops``'s contract for this block."""
    mmf, pair = token_matmul_flops(sz), pair_flops(sz)
    head = 2.0 * sz["vocab"] * sz["hidden"]
    total = 0.0
    if with_prompt and prompt_len > 1:
        n = prompt_len - 1
        total += n * mmf + pair * _pairs(sz, 0, n)
    if last >= first:
        m = last - first + 1
        lo = prompt_len - 1 + first          # the first emitter's position
        total += m * (mmf + head) + pair * _pairs(sz, lo, lo + m)
    return float(total)


def kv_row_bytes(sz: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    return 2 * sz["kv_heads"] * sz["head_dim"] * kv_bytes


def cache_bytes(sz: dict, contexts, kv_bytes: int = 2) -> float:
    """Bytes of cache one attention pass over all layers must read for
    rows with these live contexts: a full layer's whole context, a window
    layer's window."""
    n = layer_counts(sz)
    full = sum(contexts) * n["full"]
    window = sum(min(c, sz["window"]) for c in contexts) * n["window"]
    return float((full + window) * kv_row_bytes(sz, kv_bytes))


def reference_logits(params, toks, pos, precision=None):
    return ref.next_token_logits(params, toks, pos, dict(_SIZES),
                                 precision=precision or "f32")


# ---- obliged work of the grouped-query kernels ----

def window_log(run):
    """``(sizes, records)``: the traced run's dispatch records (the
    program's ``utils/dispatch_log``) inside its window that carry this
    family's extras, with the sizes of the model that ran; None where the
    program keeps no such log, the run was not traced, or nothing was
    logged."""
    try:
        from mpi_tensorflow_tpu.utils import dispatch_log
    except ImportError:
        return None
    lo, hi = run["window"]
    rows = [r for r in dispatch_log.snapshot()["dispatches"]
            if lo <= r[0] < hi and len(r) > 6 and r[6]
            and "window_keys" in r[6]]
    if not rows or not _SIZES or run["trace"] is None:
        return None
    return dict(_SIZES), rows


def gqa_decode_least_s(sz: dict, full_keys: float, window_keys: float,
                       peaks: dict, kv_bytes: int = 2) -> float:
    """Least time of the decode calls: K and V of every key they attend
    (``full_keys`` a full layer, ``window_keys`` a window layer), each
    read once, over the published bandwidth.  Their flops (4 x 128 x 128
    a key and layer: 16 a byte) are under a tenth of the peak's share at
    that rate, so the bytes bind."""
    n = layer_counts(sz)
    by = kv_row_bytes(sz, kv_bytes) * (full_keys * n["full"]
                                       + window_keys * n["window"])
    return by / peaks["hbm_bytes_per_s"]


def gqa_prefill_least_s(sz: dict, work: dict, peaks: dict,
                        kv_bytes: int = 2) -> float:
    """Least time of the prefill calls over ``work`` (the dispatch log's
    extras, summed): the larger of the visible (query, key) pairs' flops
    over the published peak and their bytes — each key a chunk's queries
    see read once, the queries read and the outputs written once — over
    the published bandwidth."""
    n = layer_counts(sz)
    fl = pair_flops(sz) * (work["full_keys"] * n["full"]
                           + work["window_keys"] * n["window"])
    q_row = 2 * sz["heads"] * sz["head_dim"] * kv_bytes   # q in, o out
    by = kv_row_bytes(sz, kv_bytes) * (work["full_rows"] * n["full"]
                                       + work["window_rows"] * n["window"]) \
        + q_row * work["queries"] * (n["full"] + n["window"])
    return max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"])
