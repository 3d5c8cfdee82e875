"""Decoder with latent attention and a share of routed experts.

A decoder family that is not ``BertMlm``'s: RMSNorm, sandwich-norm
residuals (``h += N2(attn(N1(h)))``, ``h += N4(ffn(N3(h)))``), multi-head
latent attention (MLA) with rotary positions on a slice of each head,
SwiGLU feed-forwards — dense in the leading layers, then a shared expert
plus sigmoid-routed experts — a final RMSNorm and an untied head.  No
bias anywhere.  The config's fields are the published ``config.json``
keys of the DeepSeek-V3 lineage (``pangu_ultra_moe`` among them).

Serving only.  ``forward_paged`` has ``CausalLm.forward_paged``'s
signature and return, so ``PagedDecodeEngine`` drives it unchanged; what
differs is declared to the engine, not branched on there:

- ``pool_leaves``: per layer ONE latent leaf ``(num_blocks, block_size,
  kv_lora_rank + qk_rope_head_dim`` in whole lane tiles``)``
  (ops/mla_attention) where the K/V
  models have two per-head leaves, and beside it, in layers with routed
  experts, the counter leaf ``expert_count`` (serving/paged_cache: block
  copies skip a counter, ``reset`` zeroes it);
- ``resolve_kernel``: the latent-attention kernel's probe;
- ``cfg.tp_refusal`` and ``require_draft``: what this family cannot do
  yet, said in words at engine construction.

The chip's share of the experts: ``experts_held = (first, count)`` beside
``n_routed_experts``.  The router keeps its published width and top-k;
the layer computes the held experts' part of the routed sum
(ops/moe_experts) and the weights hold only those experts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from mpi_tensorflow_tpu.models.bert import rope
from mpi_tensorflow_tpu.ops import mla_attention as mla_ops
from mpi_tensorflow_tpu.ops import moe_experts
from mpi_tensorflow_tpu.utils import engagement

COUNTER = "expert_count"


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256       # the router's width, never cut
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25_600_000.0
    max_position_embeddings: int = 131072
    sandwich_norm: bool = True        # the only residual form built
    experts_held: Tuple[int, int] = (0, 256)   # (first, count) held here
    dtype: Any = jnp.float32

    def __post_init__(self):
        first, count = self.experts_held
        if first < 0 or count < 1 \
                or first + count > self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{self.n_routed_experts} routed experts")
        if self.n_shared_experts != 1 or not self.sandwich_norm:
            raise ValueError("this block has one shared expert and "
                             "sandwich-norm residuals, nothing else yet")

    # what the serving engine asks of any model's config
    pos_kind = "rope"

    @property
    def max_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        return mla_ops.pool_width(self.kv_lora_rank,
                                  self.qk_rope_head_dim)

    @property
    def tp_refusal(self) -> str:
        return ("the latent pool is one row per token shared by every "
                "head, so there is no head axis to shard it by, and the "
                "routed experts are not yet spread over a mesh: serve "
                "this model with tp 1")


def rmsnorm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def swiglu(p, x):
    dt = x.dtype
    g = jnp.einsum("...e,ef->...f", x, p["w_gate"].astype(dt))
    u = jnp.einsum("...e,ef->...f", x, p["w_up"].astype(dt))
    return jnp.einsum("...f,fe->...e", jax.nn.silu(g) * u,
                      p["w_down"].astype(dt))


@dataclasses.dataclass(frozen=True)
class MlaMoeLm:
    cfg: MlaMoeConfig

    # ---------------- weights ----------------

    def init(self, key, std: float = 0.02):
        """normal(0, std) matrices, unit-variance embedding rows (the
        sandwich norms add every branch at unit RMS, so a token's own
        vector has to be of that size to tell tokens apart), router rows
        normal(0, hidden^-0.5), unit norm scales."""
        c = self.cfg
        E, H = c.hidden_size, c.num_attention_heads
        Dn, Dr, Dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        n_held = c.experts_held[1]

        def mat(k, *shape, s=std):
            return jax.random.normal(k, shape, jnp.float32) * s

        def ffn(k, lead, width):
            kg, ku, kd = jax.random.split(k, 3)
            return {"w_gate": mat(kg, *lead, E, width),
                    "w_up": mat(ku, *lead, E, width),
                    "w_down": mat(kd, *lead, width, E)}

        def layer(k, i):
            ks = jax.random.split(k, 9)
            lp = {"n1": jnp.ones((E,)), "n2": jnp.ones((E,)),
                  "n3": jnp.ones((E,)), "n4": jnp.ones((E,)),
                  "attn": {
                      "w_dq": mat(ks[0], E, c.q_lora_rank),
                      "q_norm": jnp.ones((c.q_lora_rank,)),
                      "w_uq": mat(ks[1], c.q_lora_rank, H, Dn + Dr),
                      "w_dkv": mat(ks[2], E, c.latent_width),
                      "kv_norm": jnp.ones((c.kv_lora_rank,)),
                      "w_ukv": mat(ks[3], c.kv_lora_rank, H, Dn + Dv),
                      "w_o": mat(ks[4], H, Dv, E)}}
            if i < c.first_k_dense_replace:
                lp["mlp"] = ffn(ks[5], (), c.intermediate_size)
            else:
                lp["moe"] = {
                    "router": mat(ks[6], c.n_routed_experts, E,
                                  s=E ** -0.5),
                    "shared": ffn(ks[7], (), c.moe_intermediate_size),
                    "experts": ffn(ks[8], (n_held,),
                                   c.moe_intermediate_size)}
            return lp

        k_tok, k_head, k_layers = jax.random.split(key, 3)
        return {"tok_emb": mat(k_tok, c.vocab_size, E, s=1.0),
                "head": mat(k_head, c.vocab_size, E),
                "final_norm": jnp.ones((E,)),
                "layers": [layer(k, i) for i, k in enumerate(
                    jax.random.split(k_layers, c.num_hidden_layers))]}

    # ---------------- what the engine asks ----------------

    def pool_leaves(self, num_blocks: int, block_size: int,
                    kv_dtype: str = "fp32", max_slots: int = 0) -> list:
        """Per layer, the leaves of its pool entry as
        ``{name: ShapeDtypeStruct}``: the latent rows, and the expert
        counter where the layer routes ([0] decode calls, [1] the rest;
        per held expert, then experts touched)."""
        c = self.cfg
        if kv_dtype != "fp32":
            raise ValueError(
                f"serve kv dtype {kv_dtype!r}: the latent pool has no "
                f"quantised form yet (its rows are shared by every head, "
                f"so the per-head scales of the int8/int4 K/V pools do "
                f"not apply); serve this model with kv_dtype fp32")
        latent = jax.ShapeDtypeStruct(
            (num_blocks, block_size, c.pool_width), c.dtype)
        counter = jax.ShapeDtypeStruct((2, c.experts_held[1] + 1),
                                       jnp.int32)
        return [{"latent": latent} if i < c.first_k_dense_replace
                else {"latent": latent, COUNTER: counter}
                for i in range(c.num_hidden_layers)]

    def resolve_kernel(self, choice: str, block_size: int,
                       prefill_chunk: int) -> str:
        c = self.cfg
        return mla_ops.resolve_kernel(
            choice, c.dtype, c.num_attention_heads, c.kv_lora_rank,
            c.qk_rope_head_dim, block_size, prefill_chunk)

    def require_draft(self, draft_model) -> None:
        """``speculative == "draft-model"`` needs a drafter of this
        family (its pool is declared the same way); the tiny K/V-pool
        stand-in the engine would build is not one."""
        if not isinstance(draft_model, MlaMoeLm):
            raise ValueError(
                "speculative draft-model: this target needs a draft "
                "model of its own family (latent pool, same vocabulary), "
                f"got {type(draft_model).__name__}; pass draft_model= and "
                "draft_params=, or use speculative ngram")

    # ---------------- the block ----------------

    def _queries_and_latent(self, ap, x, pos):
        """``x`` (B, S, E) normed input -> q_nope (B, S, H, Dn), rotated
        q_rope (B, S, H, Dr), the cache row (B, S, C + Dr)."""
        c = self.cfg
        dt = x.dtype
        Dn, C = c.qk_nope_head_dim, c.kv_lora_rank
        cq = rmsnorm(jnp.einsum("bse,er->bsr", x, ap["w_dq"].astype(dt)),
                     ap["q_norm"], c.rms_norm_eps)
        q = jnp.einsum("bsr,rhd->bshd", cq, ap["w_uq"].astype(dt))
        ckr = jnp.einsum("bse,ec->bsc", x, ap["w_dkv"].astype(dt))
        ckv = rmsnorm(ckr[..., :C], ap["kv_norm"], c.rms_norm_eps)
        q_rope = jnp.moveaxis(rope(jnp.moveaxis(q[..., Dn:], 2, 1), pos,
                                   c.rope_theta), 1, 2)
        kr = rope(ckr[:, None, :, C:], pos, c.rope_theta)[:, 0]
        return q[..., :Dn], q_rope, jnp.concatenate([ckv, kr], -1)

    @property
    def _scale(self) -> float:
        c = self.cfg
        return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5

    def _ffn(self, lp, x, valid, impl: str):
        """``x`` (B, S, E) normed input -> (output, counts or None)."""
        c = self.cfg
        if "mlp" in lp:
            return swiglu(lp["mlp"], x), None
        mp = lp["moe"]
        B, S, E = x.shape
        flat = x.reshape(B * S, E)
        with jax.named_scope("moe_router"):
            experts, gates = moe_experts.route(
                flat, mp["router"], top_k=c.num_experts_per_tok,
                scale=c.routed_scaling_factor,
                norm_topk=c.norm_topk_prob)
        with jax.named_scope("shared_expert"):
            y = swiglu(mp["shared"], flat)
        routed, counts = moe_experts.held_experts(
            flat, experts, gates, valid.reshape(-1), mp["experts"],
            first=c.experts_held[0], impl=impl)
        return (y + routed.astype(y.dtype)).reshape(B, S, E), counts

    def forward_paged(self, params, tokens, pools, block_tables, lengths,
                      valid=None, kernel: str = "xla", reduce=None):
        """``CausalLm.forward_paged``'s contract over the latent pool:
        row ``b`` of ``tokens`` (B, S_in) sits at positions
        ``[lengths[b], lengths[b] + S_in)``; returns (fp32 logits
        (B, S_in, V), updated pools).  ``pools`` is what ``pool_leaves``
        declares; the counter leaves come back advanced by this call's
        valid tokens."""
        if reduce is not None:
            raise ValueError(self.cfg.tp_refusal)
        c = self.cfg
        dt = c.dtype
        B, S = tokens.shape
        lengths = jnp.asarray(lengths, jnp.int32)
        pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)
        if valid is None:
            valid = jnp.ones((B, S), bool)
        impl = moe_experts.resolve_impl(kernel)
        engagement.record("mla_attention", kernel)
        engagement.record("moe_experts", impl)
        h = params["tok_emb"][tokens].astype(dt)
        new_pools = []
        for lp, pool in zip(params["layers"], pools):
            ap = lp["attn"]
            x = rmsnorm(h, lp["n1"], c.rms_norm_eps)
            with jax.named_scope("mla_attn"):
                q_nope, q_rope, latent = self._queries_and_latent(
                    ap, x, pos)
                lat = mla_ops.write_latent(pool["latent"], latent,
                                           block_tables, pos, valid)
                o = mla_ops.attend(q_nope, q_rope, lat, block_tables,
                                   lengths, ap["w_ukv"], self._scale, dt,
                                   kernel=kernel)
                a = jnp.einsum("bshd,hde->bse", o, ap["w_o"].astype(dt))
            h = h + rmsnorm(a, lp["n2"], c.rms_norm_eps)
            f, counts = self._ffn(lp, rmsnorm(h, lp["n3"], c.rms_norm_eps),
                                  valid, impl)
            h = h + rmsnorm(f, lp["n4"], c.rms_norm_eps)
            entry = {"latent": lat}
            if counts is not None:
                entry[COUNTER] = pool[COUNTER].at[int(S != 1)].add(counts)
            new_pools.append(entry)
        h = rmsnorm(h, params["final_norm"], c.rms_norm_eps)
        logits = jnp.einsum("bse,ve->bsv", h, params["head"].astype(dt))
        return logits.astype(jnp.float32), new_pools

    def forward(self, params, tokens):
        """Plain causal forward of whole sequences ``tokens`` (B, S): no
        cache, attention as the equations write it.  For tests."""
        c = self.cfg
        dt = c.dtype
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        valid = jnp.ones((B, S), bool)
        causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
        C, Dn = c.kv_lora_rank, c.qk_nope_head_dim
        h = params["tok_emb"][tokens].astype(dt)
        for lp in params["layers"]:
            ap = lp["attn"]
            q_nope, q_rope, latent = self._queries_and_latent(
                ap, rmsnorm(h, lp["n1"], c.rms_norm_eps), pos)
            kv = jnp.einsum("blc,chd->blhd", latent[..., :C],
                            ap["w_ukv"].astype(dt))
            s = jnp.einsum("bshd,blhd->bhsl", q_nope, kv[..., :Dn]) \
                + jnp.einsum("bshr,blr->bhsl", q_rope, latent[..., C:])
            p = mla_ops.masked_softmax(s * self._scale, causal).astype(dt)
            o = jnp.einsum("bhsl,blhd->bshd", p, kv[..., Dn:])
            a = jnp.einsum("bshd,hde->bse", o, ap["w_o"].astype(dt))
            h = h + rmsnorm(a, lp["n2"], c.rms_norm_eps)
            f, _ = self._ffn(lp, rmsnorm(h, lp["n3"], c.rms_norm_eps),
                             valid, "ragged")
            h = h + rmsnorm(f, lp["n4"], c.rms_norm_eps)
        h = rmsnorm(h, params["final_norm"], c.rms_norm_eps)
        return jnp.einsum("bse,ve->bsv", h,
                          params["head"].astype(dt)).astype(jnp.float32)
