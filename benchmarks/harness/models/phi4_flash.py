"""``program.model`` ``phi4_flash``: the repo's ``Phi4FlashLm`` (state-space
state by slot, window rings, one paged K/V pool that eight layers read,
gated memory units, differential attention), served through
``forward_paged``; its reference is ``reference/phi4_flash.py``.

Flops are the REFERENCE's form and count what the architecture obliges:
the self-decoder (layers up to the memory layer, and the full layer's K/V
projection) on every token; the cross-decoder (the full layer's queries,
attention and MLP, every later layer, the head) on the tokens that emit
one, which is every output token and a prompt's last.  2 x parameters a
matmul touches, ``6 x heads x head width`` a query-key pair a layer (64
wide scores, 128 wide values), ``2 x 4 x d_inner`` the convolution and
``6 x d_inner x d_state`` the scan a token.  The functions below the six
give the obliged bytes of the kernel this model brought
(``metrics/diff_attn_decode_roofline.py``).
"""

from __future__ import annotations

from ...reference import phi4_flash as ref

init_params = ref.init_params
_SIZES: dict = {}        # the last ``sizes()``: the driver calls it first,
                         # and ``reference_logits`` is given no sizes


def sizes(cfg: dict) -> dict:
    _SIZES.clear()
    _SIZES.update(ref.sizes(cfg))
    return dict(_SIZES)


def build(sz: dict, dtype):
    from mpi_tensorflow_tpu.models import phi4_flash

    return phi4_flash.Phi4FlashLm(phi4_flash.Phi4FlashConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        intermediate_size=sz["mlp"], num_hidden_layers=sz["layers"],
        num_attention_heads=sz["heads"],
        num_key_value_heads=sz["kv_heads"], sliding_window=sz["window"],
        layer_norm_eps=sz["eps"], max_position_embeddings=sz["positions"],
        d_state=sz["d_state"], d_conv=sz["d_conv"],
        mamba_expand=sz["expand"], dtype=dtype))


def layer_counts(sz: dict) -> dict:
    kinds = [ref.layer_kind(sz, i) for i in range(sz["layers"])]
    return {k: kinds.count(k) for k in
            ("mamba", "window", "full", "gmu", "cross")}


def mixer_params(sz: dict) -> dict:
    """Matrix parameters of one mixer of each kind (biases, norms and
    the lambda vectors left out: they are no matmul)."""
    d = ref.derived(sz)
    E, Di, R, N = sz["hidden"], d["Di"], d["R"], sz["d_state"]
    q = sz["heads"] * d["D"]
    return {"mamba": E * 2 * Di + Di * (R + 2 * N) + R * Di + Di * E,
            "window": E * (q + 2 * d["KW"]) + q * E,
            "full": E * (q + 2 * d["KW"]) + q * E,
            "cross": 2 * E * q, "gmu": 2 * E * Di,
            "mlp": 3 * E * sz["mlp"]}


def matrix_params(sz: dict) -> int:
    """Every matrix of the model, the tied embedding once."""
    n, p = layer_counts(sz), mixer_params(sz)
    return sz["vocab"] * sz["hidden"] + sz["layers"] * p["mlp"] \
        + sum(n[k] * p[k] for k in n)


def token_flops(sz: dict) -> tuple:
    """``(self, cross)``: flops one token obliges of the self-decoder
    (every token) and of the cross-decoder (emitting tokens), attention
    pairs and the head apart."""
    n, p, d = layer_counts(sz), mixer_params(sz), ref.derived(sz)
    kv_proj = sz["hidden"] * 2 * d["KW"]
    self_layers = n["mamba"] + n["window"]
    scan = n["mamba"] * (2 * sz["d_conv"] + 6 * sz["d_state"]) * d["Di"]
    own = 2.0 * (n["mamba"] * p["mamba"] + n["window"] * p["window"]
                 + self_layers * p["mlp"] + kv_proj) + scan
    cross = 2.0 * (p["full"] - kv_proj + n["cross"] * p["cross"]
                   + n["gmu"] * p["gmu"]
                   + (sz["layers"] - self_layers) * p["mlp"])
    return own, cross


def pair_flops(sz: dict) -> float:
    """One query-key pair in one attention layer: 2 D of score and 4 D of
    weighted values a query head."""
    return 6.0 * sz["heads"] * ref.derived(sz)["D"]


def _window_pairs(lo: int, hi: int, W: int) -> int:
    """Keys the queries at positions ``[lo, hi)`` see in one window
    layer: ``min(p + 1, W)`` each."""
    ramp = min(hi, W)
    below = (ramp * (ramp + 1) - lo * (lo + 1)) // 2 if lo < ramp else 0
    return below + max(0, hi - max(lo, W)) * W


def request_flops(sz: dict, prompt_len: int, first: int, last: int,
                  with_prompt: bool) -> float:
    """``flops.serve_request_flops``'s contract for this model: the
    prompt's tokens that emit nothing cost the self-decoder alone (the
    cross-decoder on them is NOT obliged, whatever a program runs)."""
    n = layer_counts(sz)
    own, cross = token_flops(sz)
    pair, W = pair_flops(sz), sz["window"]
    head = 2.0 * sz["vocab"] * sz["hidden"]
    total = 0.0
    if with_prompt and prompt_len > 1:
        m = prompt_len - 1
        total += m * own + pair * n["window"] * _window_pairs(0, m, W)
    if last >= first:
        m = last - first + 1
        lo = prompt_len - 1 + first            # the first emitter's position
        ctx_sum = m * (lo + 1) + m * (m - 1) // 2
        total += m * (own + cross + head) \
            + pair * (n["window"] * _window_pairs(lo, lo + m, W)
                      + (n["full"] + n["cross"]) * ctx_sum)
    return float(total)


def state_bytes(sz: dict, kv_bytes: int = 2) -> int:
    """One sequence's state-space state over all mamba layers."""
    d = ref.derived(sz)
    return layer_counts(sz)["mamba"] * d["Di"] * (
        sz["d_state"] * 4 + (sz["d_conv"] - 1) * kv_bytes)


def cache_bytes(sz: dict, contexts, kv_bytes: int = 2) -> float:
    """Bytes of cache one step must move for rows with these live
    contexts: the full layer's K/V once a pass (itself and every cross
    layer), a window of K/V a window layer, the state read and written."""
    n, d = layer_counts(sz), ref.derived(sz)
    row = 2 * d["KW"] * kv_bytes
    full = sum(contexts) * row * (n["full"] + n["cross"])
    window = sum(min(c, sz["window"]) for c in contexts) * row * n["window"]
    return float(full + window + 2 * len(contexts) * state_bytes(sz, kv_bytes))


def reference_logits(params, toks, pos, precision=None):
    return ref.next_token_logits(params, toks, pos, dict(_SIZES),
                                 precision=precision or "f32")


# ---- obliged work of the kernel this model brought ----

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
            if lo <= r[0] < hi and len(r) > 6 and r[6]]
    if not rows or not _SIZES or run["trace"] is None:
        return None
    return dict(_SIZES), rows


def diff_attn_decode_least_s(sz: dict, full_keys: float,
                             window_keys: float, peaks: dict,
                             kv_bytes: int = 2) -> float:
    """Least time of the decode kernel's calls: K and V rows of the keys
    attended, the full layer's cache once a pass (``full_keys`` a pass),
    a window layer's ring once a layer (``window_keys`` a layer), over
    the published bandwidth.  Its flops (6 x 40 x 64 a key) are 1/50 of
    the peak's share at that rate, so the bytes bind."""
    n, d = layer_counts(sz), ref.derived(sz)
    row = 2 * d["KW"] * kv_bytes
    by = row * (full_keys * (n["full"] + n["cross"])
                + window_keys * n["window"])
    return by / peaks["hbm_bytes_per_s"]
