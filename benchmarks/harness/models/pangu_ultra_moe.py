"""``program.model`` ``pangu_ultra_moe``: the repo's ``MlaMoeLm`` (latent
attention over a latent paged pool, sandwich-norm blocks, a share of the
sigmoid-routed experts), served through ``forward_paged``; its reference
is ``reference/pangu_ultra_moe.py``.

Flops are the REFERENCE's form, so no share is flattered by the absorbed
attention's extra arithmetic: 2 x parameters touched a token (attention
projections; dense MLP, or shared expert + router + the held share
``top_k x held / router_width`` of a routed expert), 2 x (nope + rope +
v) x heads a query-key pair a layer, and the head over the held
vocabulary at emitted tokens.  The functions below the six also give
the obliged operations and bytes of the two kernels this model brought
(``metrics/mla_*_roofline.py``, ``metrics/moe_experts_roofline.py``).
"""

from __future__ import annotations

from ...reference import pangu_ultra_moe as ref

init_params = ref.init_params
_SIZES: dict = {}        # the last ``sizes()``: the driver calls it first,
                         # and ``reference_logits`` is given no sizes


def sizes(cfg: dict) -> dict:
    _SIZES.clear()
    _SIZES.update(ref.sizes(cfg))
    return dict(_SIZES)


def build(sz: dict, dtype):
    from mpi_tensorflow_tpu.models import mla_moe

    return mla_moe.MlaMoeLm(mla_moe.MlaMoeConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        intermediate_size=sz["mlp"],
        moe_intermediate_size=sz["expert_mlp"],
        num_hidden_layers=sz["layers"],
        first_k_dense_replace=sz["dense_layers"],
        num_attention_heads=sz["heads"], q_lora_rank=sz["q_rank"],
        kv_lora_rank=sz["kv_rank"], qk_nope_head_dim=sz["nope"],
        qk_rope_head_dim=sz["rope"], v_head_dim=sz["v_dim"],
        n_routed_experts=sz["router_width"],
        num_experts_per_tok=sz["top_k"], norm_topk_prob=sz["norm_topk"],
        routed_scaling_factor=sz["routed_scale"], rms_norm_eps=sz["eps"],
        rope_theta=sz["theta"], max_position_embeddings=sz["positions"],
        experts_held=(sz["experts_first"], sz["experts_held"]),
        dtype=dtype))


def attention_params(sz: dict) -> int:
    E, H = sz["hidden"], sz["heads"]
    return (E * sz["q_rank"] + sz["q_rank"] * H * (sz["nope"] + sz["rope"])
            + E * (sz["kv_rank"] + sz["rope"])
            + sz["kv_rank"] * H * (sz["nope"] + sz["v_dim"])
            + H * sz["v_dim"] * E)


def expert_params(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["expert_mlp"]


def token_matmul_flops(sz: dict) -> float:
    """2 x parameters one token touches in the stack (no head)."""
    dense, L = sz["dense_layers"], sz["layers"]
    share = sz["top_k"] * sz["experts_held"] / sz["router_width"]
    moe = sz["router_width"] * sz["hidden"] \
        + (1 + share) * expert_params(sz)
    return 2.0 * (L * attention_params(sz)
                  + dense * 3 * sz["hidden"] * sz["mlp"]
                  + (L - dense) * moe)


def pair_flops(sz: dict) -> float:
    """One query-key pair over all layers, reference form."""
    return 2.0 * (sz["nope"] + sz["rope"] + sz["v_dim"]) * sz["heads"] \
        * sz["layers"]


def request_flops(sz: dict, prompt_len: int, first: int, last: int,
                  with_prompt: bool) -> float:
    """``flops.serve_request_flops``'s contract for this block."""
    mmf, pair = token_matmul_flops(sz), pair_flops(sz)
    head = 2.0 * sz["vocab"] * sz["hidden"]
    total = 0.0
    if with_prompt and prompt_len > 1:
        n = prompt_len - 1
        total += n * mmf + pair * (n * (n + 1) // 2)
    if last >= first:
        n = last - first + 1
        ctx_sum = n * (prompt_len + first) + n * (n - 1) // 2
        total += n * (mmf + head) + pair * ctx_sum
    return float(total)


def cache_bytes(sz: dict, contexts, kv_bytes: int = 2) -> float:
    """Latent bytes one attention pass over all layers must read."""
    return float(sum(contexts)) * (sz["kv_rank"] + sz["rope"]) * kv_bytes \
        * sz["layers"]


def reference_logits(params, toks, pos, precision=None):
    return ref.next_token_logits(params, toks, pos, dict(_SIZES),
                                 precision=precision or "f32")


# ---- obliged work of the kernels this model brought ----

def window_log(run):
    """``(sizes, records)``: the traced run's dispatch records (the
    program's ``utils/dispatch_log``) inside its window, with the sizes
    of the model that ran; None where the program keeps no such log, the
    run was not traced, or nothing was logged."""
    try:
        from mpi_tensorflow_tpu.utils import dispatch_log
    except ImportError:
        return None
    lo, hi = run["window"]
    rows = [r for r in dispatch_log.snapshot()["dispatches"]
            if lo <= r[0] < hi]
    if not rows or not _SIZES or run["trace"] is None:
        return None
    return dict(_SIZES), rows


def mla_decode_least_s(sz: dict, attended: float, peaks: dict,
                       kv_bytes: int = 2) -> float:
    """Least time of the absorbed decode kernel over ``attended`` cached
    tokens (summed over rows), all layers: the larger of reading each
    latent row once per row and the absorbed form's scores + weighted
    latents."""
    width = sz["kv_rank"] + sz["rope"]
    by = attended * width * kv_bytes * sz["layers"]
    fl = attended * 2.0 * sz["heads"] * (width + sz["kv_rank"]) \
        * sz["layers"]
    return max(by / peaks["hbm_bytes_per_s"], fl / peaks["bf16_flops"])


def mla_prefill_least_s(sz: dict, pairs: float, peaks: dict) -> float:
    """Least time of prefill attention over ``pairs`` causal query-key
    pairs: the non-absorbed form's flops (it does less than the absorbed
    form the kernel runs, so the share says what that choice costs)."""
    return pairs * pair_flops(sz) / peaks["bf16_flops"]


def moe_experts_least_s(sz: dict, assignments: float, touched: float,
                        peaks: dict, w_bytes: int = 2) -> float:
    """Least time of the grouped matmuls: the larger of reading the
    weights of the experts touched (summed over calls and layers) plus a
    row in and a row out per assignment, and 6 x hidden x width flops an
    assignment."""
    by = touched * expert_params(sz) * w_bytes \
        + assignments * 2 * sz["hidden"] * w_bytes
    fl = assignments * 2.0 * expert_params(sz)
    return max(by / peaks["hbm_bytes_per_s"], fl / peaks["bf16_flops"])
