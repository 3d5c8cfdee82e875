"""Parallel attention + mixture-of-experts decoder: the ``cohere2_moe``
family (Command A+).

A fourth decoder family beside ``CausalLm``, ``MlaMoeLm`` and
``Phi4FlashLm``.  Every layer is ONE parallel block on one LayerNorm (a
scale, no bias):

    h = LN(x);   x <- x + Attn(h) + MoE(h)

then a final LayerNorm and logits over the tied embedding (times
``logit_scale``).  Attention is grouped-query (``num_attention_heads``
query heads over ``num_key_value_heads`` KV heads of ``head_dim``, no
bias, no qk-norm, scale ``1/sqrt(head_dim)``); layers follow
``layer_types``: a ``sliding_attention`` layer sees keys ``p - W < j <=
p`` and rotates q and k on the whole head (GPT-J's interleaved pairs,
``rope_theta``), a ``full_attention`` layer is causal and has no
positions (NoPE).  ``MoE(h) = 1/2 (sum_e g_e E_e(h) + 1/n sum_s S_s(h))``
(``shared_expert_combination_strategy`` "average"): sigmoid scores over
the router's whole width, the top ``k`` renormalised to sum 1, and ``n``
shared SwiGLU experts, which run as ONE SwiGLU of ``n`` times the width
(exactly their sum).

The interleaved rotary is ``bert.rope`` (pairs ``(i, i + D/2)``) applied
after one fixed permutation of the head's features, evens then odds
(``_rotate``): the permutation maps GPT-J's pair ``(2i, 2i+1)`` onto
``(i, i + D/2)`` at the same frequency, and is applied to q and k alike,
so every score ``q . k`` is GPT-J's (tests/test_cohere2_moe.py holds the
two forms together).  Keys are cached rotated, in that order.

Serving only.  ``forward_paged`` has ``Phi4FlashLm``'s contract (the
engine passes each row's ``slots`` and the prefill lane it ``take``s):

- the full layers keep K and V in the paged pool (``k``, ``v``: ``(
  num_blocks, block_size, Hkv*D)``), read by ops/paged_attention's
  grouped-query kernels;
- a window layer keeps a RING a slot (``win_k_slot``, ``win_v_slot``:
  ``(slots + 1, W, Hkv*D)``, ``paged_attention.write_ring``).  A decode
  token is written first and then reads its ring as a sequence of
  ``RING_BLOCK``-token blocks of the ring leaf itself (table ``slot*nb +
  [0, nb)``, length ``min(p, W - 1)``): keys are stored rotated at their
  absolute positions, so their order inside the softmax is free.  A
  prefill chunk attends to the ring AS IT STOOD before the chunk, put in
  position order, followed by the chunk's own keys (``_ring_and_chunk``),
  under the window bound; then the chunk is written.  A chunk may be
  longer than the window;
- every layer routes, so every layer declares the ``expert_count``
  counter (serving/paged_cache) beside its cache.

The chip's share: ``experts_held = (first, count)`` of the router's
``num_experts``, computed by ops/moe_experts.held_experts; the vocabulary
is whatever rows the embedding holds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi_tensorflow_tpu.models.bert import rope
from mpi_tensorflow_tpu.ops import moe_experts
from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.utils import engagement

COUNTER = "expert_count"
WINDOW, FULL = "sliding_attention", "full_attention"
# tokens of a ring one block of the kernels' view holds
RING_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096        # one expert's width
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL) * 8
    num_experts: int = 128               # the router's width, never cut
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) held here
    dtype: Any = jnp.float32

    def __post_init__(self):
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{self.num_experts} routed experts")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group "
                f"over {self.num_key_value_heads} KV heads")
        kinds = set(self.layer_types[:self.num_hidden_layers])
        if len(self.layer_types) < self.num_hidden_layers \
                or not kinds <= {WINDOW, FULL}:
            raise ValueError(
                f"layer_types must name {WINDOW} or {FULL} for each of "
                f"the {self.num_hidden_layers} layers")
        W = self.sliding_window
        if WINDOW in kinds and W % min(RING_BLOCK, W):
            raise ValueError(
                f"a window of {W} tokens is no whole number of "
                f"{RING_BLOCK}-token ring blocks")

    # what the serving engine asks of any model's config
    pos_kind = "rope"

    @property
    def max_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def is_window(self, i: int) -> bool:
        """Layer ``i`` of the stack is a sliding-window layer: the
        published ``layer_types`` name the layers of the whole model, of
        which a cut keeps the first ``num_hidden_layers``."""
        return self.layer_types[i] == WINDOW

    @property
    def ring_block(self) -> int:
        return min(RING_BLOCK, self.sliding_window)

    @property
    def tp_refusal(self) -> str:
        return ("the window rings are addressed by slot and the routed "
                "experts are not spread over a mesh yet: serve this model "
                "with tp 1")

    def serve_refusal(self, serve) -> Optional[str]:
        """What of ``serve`` this family cannot do yet, in words (None:
        nothing).  ``engine.check_model`` raises it."""
        rings = "a window layer's ring is addressed by slot, not by block"
        if serve.kv_dtype != "fp32":
            return _kv_refusal(serve.kv_dtype)
        if serve.prefix_cache != "off" or serve.prefix_gen != "off":
            return (f"prefix cache on: {rings}, so a cached prefix's "
                    f"blocks do not hold the window layers' keys and the "
                    f"trie cannot resume from them; serve this model with "
                    f"prefix_cache off and prefix_gen off")
        if serve.kv_tier != "off":
            return (f"kv_tier {serve.kv_tier}: {rings}, so a demoted "
                    f"block cannot bring it back; serve this model with "
                    f"kv_tier off")
        if serve.speculative != "off":
            return (f"speculative {serve.speculative}: a rejected draft "
                    f"would have to take its keys back out of the rings, "
                    f"and there is no draft model of this family; serve "
                    f"this model with speculative off")
        if serve.mixed_batch != "off":
            return ("mixed_batch on: the fused dispatch takes logits of "
                    "every lane and writes rings for rows of two phases, "
                    "which this family does not; serve this model with "
                    "mixed_batch off")
        return None


#: the CPU size (tests, ``python -m mpi_tensorflow_tpu.serving --tiny``):
#: the published shape of the block at a few dozen lanes, a query group
#: of 4, a window of 16 tokens, 4 of 16 experts held
TINY = Cohere2MoeConfig(vocab_size=256, hidden_size=64, intermediate_size=32,
                        num_hidden_layers=4, num_attention_heads=8,
                        num_key_value_heads=2, head_dim=16, sliding_window=16,
                        num_experts=16, num_experts_per_tok=4,
                        max_position_embeddings=512, experts_held=(0, 4))


#: one chip's share of the 8-chip deployment the benchmark serves
#: (benchmarks/configs/command_a_plus_05_2026.json): one period of four
#: layers, 16 of the 128 routed experts, 1/8 of the vocabulary
CHIP_SHARE = Cohere2MoeConfig(num_hidden_layers=4, vocab_size=32768,
                              experts_held=(0, 16))


def _kv_refusal(kv_dtype: str) -> str:
    return (f"kv_dtype {kv_dtype}: the grouped-query pool and the window "
            f"rings have no quantised form yet; serve this model with "
            f"kv_dtype fp32")


def layernorm(x, scale, eps: float):
    """LayerNorm with a scale and no bias."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def swiglu(p, x):
    dt = x.dtype
    g = jnp.einsum("...e,ef->...f", x, p["w_gate"].astype(dt))
    u = jnp.einsum("...e,ef->...f", x, p["w_up"].astype(dt))
    return jnp.einsum("...f,fe->...e", jax.nn.silu(g) * u,
                      p["w_down"].astype(dt))


def _rotate(x, pos, theta: float):
    """GPT-J rotary on ``x`` (B, H, S, D) at ``pos`` (B, S): the features
    put in the order evens then odds, then ``bert.rope``'s half-split
    pairs (the module docstring says why that is the same score).  The
    permutation moves activations: left free, XLA moves it into the
    projection's weight and copies 134 MB of ``w_q`` a layer and decode
    step."""
    D = x.shape[-1]
    x = lax.optimization_barrier(x)
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (D // 2, 2)), -1, -2)
    return rope(x.reshape(x.shape[:-2] + (D,)), pos, theta)


@dataclasses.dataclass(frozen=True)
class Cohere2MoeLm:
    cfg: Cohere2MoeConfig

    #: the engine passes ``slots`` (the rings) and ``take`` (the one lane
    #: of a prefill chunk whose logits are wanted) to ``forward_paged``
    slot_state = True
    #: ... and decode tables at full width: the full layers' kernel grid
    #: follows the live blocks, so table buckets would only add programs
    full_tables = True
    #: the least prefill bucket: a prompt's short last chunk costs a
    #: ring block's worth of lanes at most, and the grouped kernel's tiles
    #: stay whole
    prefill_floor = RING_BLOCK

    # ---------------- weights ----------------

    def init(self, key, std: float = 0.02):
        """normal(0, std) matrices and embedding rows, router rows
        normal(0, hidden^-0.5), unit norm scales; the tree
        ``benchmarks/reference/cohere2_moe.py`` makes."""
        c = self.cfg
        E, D, F = c.hidden_size, c.head_dim, c.intermediate_size
        held = c.experts_held[1]

        def mat(k, *shape, s=std):
            return jax.random.normal(k, shape, jnp.float32) * s

        def ffn(k, lead, width):
            kg, ku, kd = jax.random.split(k, 3)
            return {"w_gate": mat(kg, *lead, E, width),
                    "w_up": mat(ku, *lead, E, width),
                    "w_down": mat(kd, *lead, width, E)}

        def layer(k):
            ks = jax.random.split(k, 7)
            return {"ln": jnp.ones((E,)),
                    "attn": {"w_q": mat(ks[0], E, c.num_attention_heads * D),
                             "w_k": mat(ks[1], E, c.kv_width),
                             "w_v": mat(ks[2], E, c.kv_width),
                             "w_o": mat(ks[3], c.num_attention_heads * D, E)},
                    "moe": {"router": mat(ks[4], c.num_experts, E,
                                          s=E ** -0.5),
                            "shared": ffn(ks[5], (),
                                          c.num_shared_experts * F),
                            "experts": ffn(ks[6], (held,), F)}}

        k_tok, k_layers = jax.random.split(key)
        return {"tok_emb": mat(k_tok, c.vocab_size, E),
                "final_ln": jnp.ones((E,)),
                "layers": [layer(k) for k in jax.random.split(
                    k_layers, c.num_hidden_layers)]}

    # ---------------- what the engine asks ----------------

    def pool_leaves(self, num_blocks: int, block_size: int,
                    kv_dtype: str = "fp32", max_slots: int = 0) -> list:
        """Per layer ``{name: ShapeDtypeStruct}``: the paged K and V pool
        of a full layer or the rings by slot of a window layer, and the
        expert counter ([0] decode calls, [1] the rest; per held expert,
        then experts touched)."""
        c = self.cfg
        if kv_dtype != "fp32":
            raise ValueError(_kv_refusal(kv_dtype))
        counter = jax.ShapeDtypeStruct((2, c.experts_held[1] + 1),
                                       jnp.int32)
        ring = jax.ShapeDtypeStruct(
            (max_slots + 1, c.sliding_window, c.kv_width), c.dtype)
        pool = jax.ShapeDtypeStruct((num_blocks, block_size, c.kv_width),
                                    c.dtype)
        return [{"win_k_slot": ring, "win_v_slot": ring, COUNTER: counter}
                if c.is_window(i) else
                {"k": pool, "v": pool, COUNTER: counter}
                for i in range(c.num_hidden_layers)]

    def resolve_kernel(self, choice: str, block_size: int,
                       prefill_chunk: int) -> str:
        """``paged_attention.resolve_kernel``'s rules with this family's
        probe: the grouped-query kernels over the pool, and over the
        rings' blocks with the window bound."""
        c = self.cfg

        def probe():
            from mpi_tensorflow_tpu.ops import paged_attention_kernel as pk

            dt = jnp.dtype(c.dtype).name
            for bs, window in ((block_size, None), (c.ring_block,
                                                    c.sliding_window)):
                pk.probe_compile(dt, c.num_attention_heads, c.head_dim, bs,
                                 prefill_chunk, kv_heads=c.num_key_value_heads,
                                 window=window, min_chunk=self.prefill_floor)
        return paged_ops.resolve_choice(choice, probe)

    def dispatch_extra(self, kind: str, starts, counts, taken: int) -> dict:
        """What one dispatch obliges of the attention caches, from the
        host's scheduler state (``starts``: each row's position before
        the dispatch; ``counts``: its real tokens): the (query, key)
        pairs of a full layer and of a window layer, the keys each kind
        of layer reads at least once, and the pairs a window layer is
        spared (keys below the window, which a causal pass would see)."""
        import numpy as np

        W = self.cfg.sliding_window
        lo = np.asarray(starts, np.int64)
        hi = lo + np.asarray(counts, np.int64)
        # a query at position p sees p + 1 keys, or min(p + 1, W)
        full = (hi * (hi + 1) - lo * (lo + 1)) // 2
        ramp = np.minimum(hi, W)
        window = np.where(lo < ramp, (ramp * (ramp + 1) - lo * (lo + 1))
                          // 2, 0) + np.maximum(0, hi - np.maximum(lo, W)) * W
        return {"full_keys": int(full.sum()),
                "window_keys": int(window.sum()),
                "window_keys_skipped": int((full - window).sum()),
                "full_rows": int(hi.sum()),
                "window_rows": int((np.minimum(lo, W - 1) + hi - lo).sum())}

    # ---------------- the block ----------------

    def _ring_and_chunk(self, ring, rows, slots, start):
        """A prefill chunk's view of a window layer: per row, the ring as
        it stood before the chunk in position order — the ``n = min(start,
        W)`` keys before ``start`` — followed by the chunk's own rows at
        ``n``, as ``(B * nb, ring_block, lanes)`` blocks with each row's
        table.  Local position ``l`` holds absolute position ``l`` or, from
        ``start >= W`` on, ``start - W + l``; the queries then sit at ``n +
        [0, S)`` and the window bound hides what lies below it."""
        c = self.cfg
        W, bs = c.sliding_window, c.ring_block
        B, S, L = rows.shape
        span = W + -(-S // bs) * bs
        n = jnp.minimum(start, W)

        def one(r, chunk, n, shift):
            r = jnp.roll(r, -shift, axis=0)
            buf = jnp.concatenate(
                [r, jnp.zeros((span - W, L), r.dtype)], axis=0)
            return lax.dynamic_update_slice(buf, chunk.astype(r.dtype),
                                            (n, 0))

        bufs = jax.vmap(one)(ring[slots], rows, n,
                             jnp.maximum(start - W, 0) % W)
        nb = span // bs
        table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
        return bufs.reshape(B * nb, bs, L), table, n

    def _attention(self, ap, x, pool, i, slots, pos, valid, tables,
                   lengths, kernel, ring_work):
        """Layer ``i``'s attention of ``x`` (B, S, E): (output, the new
        cache entry)."""
        c = self.cfg
        dt = x.dtype
        B, S, _ = x.shape
        D, Hq, Hkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        q = jnp.einsum("bse,ef->bsf", x, ap["w_q"].astype(dt))
        k = jnp.einsum("bse,ef->bsf", x, ap["w_k"].astype(dt))
        v = jnp.einsum("bse,ef->bsf", x, ap["w_v"].astype(dt))
        q = jnp.moveaxis(q.reshape(B, S, Hq, D), 2, 1)        # (B, Hq, S, D)
        if c.is_window(i):
            q = _rotate(q, pos, c.rope_theta)
            k = _rotate(jnp.moveaxis(k.reshape(B, S, Hkv, D), 2, 1), pos,
                        c.rope_theta)
            k = jnp.moveaxis(k, 1, 2).reshape(B, S, Hkv * D)
            a, entry = self._window(q, k, v, pool, slots, pos, valid,
                                    lengths, kernel, ring_work)
        else:
            def heads(t):
                return jnp.moveaxis(t.reshape(B, S, Hkv, D), 2, 1)
            kp = paged_ops.write_kv(pool["k"], heads(k), tables, pos, valid)
            vp = paged_ops.write_kv(pool["v"], heads(v), tables, pos, valid)
            a = paged_ops.attend(q, kp, vp, tables, lengths, dt,
                                 kernel=kernel)
            entry = {"k": kp, "v": vp}
        o = jnp.moveaxis(a, 1, 2).reshape(B, S, Hq * D)
        return jnp.einsum("bsf,fe->bse", o, ap["w_o"].astype(dt)), entry

    def _window(self, q, k, v, pool, slots, pos, valid, lengths, kernel,
                ring_work):
        c = self.cfg
        W, bs = c.sliding_window, c.ring_block
        rk, rv = pool["win_k_slot"], pool["win_v_slot"]
        new = {"win_k_slot": paged_ops.write_ring(rk, k, slots, pos, valid),
               "win_v_slot": paged_ops.write_ring(rv, v, slots, pos, valid)}
        if q.shape[2] == 1:
            # the token's own write first; the ring then holds exactly
            # the keys it may see, read as blocks of the ring leaf
            nb = W // bs

            def view(r):
                return r.reshape(-1, bs, r.shape[-1])
            table = slots[:, None] * nb + jnp.arange(nb, dtype=jnp.int32)
            a = paged_ops.attend(
                q, view(new["win_k_slot"]), view(new["win_v_slot"]), table,
                jnp.minimum(lengths, W - 1), q.dtype, kernel=kernel,
                work=ring_work)
            return a, new
        kb, table, n = self._ring_and_chunk(rk, k, slots, lengths)
        vb, _, _ = self._ring_and_chunk(rv, v, slots, lengths)
        a = paged_ops.attend(q, kb, vb, table, n, q.dtype, kernel=kernel,
                             window=W)
        return a, new

    def _moe(self, mp, x, valid, impl: str):
        """``x`` (B, S, E) -> (the average of the routed share and the
        shared experts, counts)."""
        c = self.cfg
        B, S, E = x.shape
        flat = x.reshape(B * S, E)
        with jax.named_scope("moe_router"):
            experts, gates = moe_experts.route(
                flat, mp["router"], top_k=c.num_experts_per_tok, scale=1.0,
                norm_topk=c.norm_topk_prob)
        with jax.named_scope("shared_experts"):
            shared = swiglu(mp["shared"], flat).astype(jnp.float32)
        routed, counts = moe_experts.held_experts(
            flat, experts, gates, valid.reshape(-1), mp["experts"],
            first=c.experts_held[0], impl=impl)
        y = 0.5 * (routed + shared / c.num_shared_experts)
        return y.astype(x.dtype).reshape(B, S, E), counts

    # ---------------- serving ----------------

    def forward_paged(self, params, tokens, pools, block_tables, lengths,
                      valid=None, kernel: str = "xla", reduce=None,
                      slots=None, take=None):
        """``Phi4FlashLm.forward_paged``'s contract: row ``b`` of
        ``tokens`` (B, S_in) sits at positions ``[lengths[b], lengths[b] +
        S_in)`` of the sequence in slot ``slots[b]``; returns (fp32 logits
        of every lane (B, S_in, V) with ``take=None``, else of lane
        ``take[b]`` alone (B, 1, V), updated pools).  Every layer runs on
        every lane; only the head is spared."""
        if reduce is not None:
            raise ValueError(self.cfg.tp_refusal)
        if slots is None:
            raise ValueError("this family keeps its window rings by slot: "
                             "forward_paged needs slots=")
        c = self.cfg
        dt = c.dtype
        B, S = tokens.shape
        lengths = jnp.asarray(lengths, jnp.int32)
        slots = jnp.asarray(slots, jnp.int32)
        pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)
        if valid is None:
            valid = jnp.ones((B, S), bool)
        impl = moe_experts.resolve_impl(kernel)
        engagement.record("paged_attention", kernel)
        engagement.record("moe_experts", impl)
        ring_work = None
        if S == 1 and kernel != "xla" and any(
                c.is_window(i) for i in range(c.num_hidden_layers)):
            # one list for every window layer's decode call
            ring = jax.ShapeDtypeStruct((1, c.ring_block, c.kv_width), dt)
            ring_work = paged_ops.paged_work(
                jnp.minimum(lengths, c.sliding_window - 1), 1, c.ring_block,
                c.sliding_window // c.ring_block,
                paged_ops.step_blocks(1, ring))
        h = params["tok_emb"][tokens].astype(dt)
        new_pools = []
        for i, (lp, pool) in enumerate(zip(params["layers"], pools)):
            x = layernorm(h, lp["ln"], c.layer_norm_eps)
            kind = "window_attn" if c.is_window(i) else "full_attn"
            with jax.named_scope(kind):
                a, entry = self._attention(lp["attn"], x, pool, i, slots,
                                           pos, valid, block_tables,
                                           lengths, kernel, ring_work)
            f, counts = self._moe(lp["moe"], x, valid, impl)
            h = h + a + f
            entry[COUNTER] = pool[COUNTER].at[int(S != 1)].add(counts)
            new_pools.append(entry)
        if take is not None and S > 1:
            lane = jnp.maximum(jnp.asarray(take, jnp.int32), 0)
            h = jnp.take_along_axis(h, lane[:, None, None], axis=1)
        return self._logits(params, h), new_pools

    def _logits(self, params, h):
        c = self.cfg
        h = layernorm(h, params["final_ln"], c.layer_norm_eps)
        logits = jnp.einsum("bse,ve->bsv", h,
                            params["tok_emb"].astype(h.dtype))
        return logits.astype(jnp.float32) * c.logit_scale

    def forward(self, params, tokens):
        """Plain causal forward of whole sequences ``tokens`` (B, S): no
        cache, masks written out.  For tests."""
        c = self.cfg
        dt = c.dtype
        B, S = tokens.shape
        D, Hq, Hkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        valid = jnp.ones((B, S), bool)
        i_, j_ = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        causal = j_ <= i_
        h = params["tok_emb"][tokens].astype(dt)
        for i, lp in enumerate(params["layers"]):
            ap = lp["attn"]
            x = layernorm(h, lp["ln"], c.layer_norm_eps)

            def heads(w, n):
                t = jnp.einsum("bse,ef->bsf", x, ap[w].astype(dt))
                return jnp.moveaxis(t.reshape(B, S, n, D), 2, 1)
            q, k, v = heads("w_q", Hq), heads("w_k", Hkv), heads("w_v", Hkv)
            vis = causal
            if c.is_window(i):
                q, k = (_rotate(t, pos, c.rope_theta) for t in (q, k))
                vis = causal & (j_ > i_ - c.sliding_window)
            a = paged_ops.masked_softmax_attention(
                q, k, v, jnp.broadcast_to(vis, (B, 1, S, S)), dt)
            o = jnp.moveaxis(a, 1, 2).reshape(B, S, Hq * D)
            f, _ = self._moe(lp["moe"], x, valid, "ragged")
            h = h + jnp.einsum("bsf,fe->bse", o, ap["w_o"].astype(dt)) + f
        return self._logits(params, h)
