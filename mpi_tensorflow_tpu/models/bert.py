"""BERT-base masked-LM — BASELINE.json config 5 ("stress allreduce bandwidth").

The reference has no transformer, no attention, no sequence axis (SURVEY.md
§2 checklist); BERT-MLM is the directed scale-out family that exercises the
framework's transformer stack: multi-axis sharding (DP x TP x SP) and ring
attention for long sequences.

Architecture: original BERT-base encoder (post-LN): token+position
embeddings -> 12 x [MHA + residual/LN, GELU-MLP + residual/LN] -> tied-weight
MLM head over the vocab.  Hyperparameters configurable; ``BERT_BASE`` is the
canonical 110M-param config.

Sharding (parallel/sharding_rules.py, Megatron layout):
- attention QKV column-parallel over ``model`` (heads sharded), output
  projection row-parallel;
- MLP in column-parallel / out row-parallel over ``model``;
- embedding + LM head vocab-parallel over ``model``;
- activations batch-sharded over ``data``, sequence-sharded over ``seq``;
- attention runs as ring attention (parallel/ring.py) via an inner
  ``shard_map`` when the mesh has a ``seq`` axis >1, dense otherwise.
All other collectives are inserted by XLA GSPMD from the constraints.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mpi_tensorflow_tpu.parallel import ring, sharding_rules as rules_lib
from mpi_tensorflow_tpu.utils import engagement


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp: int = 3072
    max_positions: int = 512
    dropout: float = 0.1
    dtype: Any = jnp.float32      # compute dtype; bfloat16 for TPU throughput
    sp_impl: str = "ring"         # sequence-parallel attention: "ring"
                                  # (ppermute K/V hops, any head count) or
                                  # "ulysses" (2 all-to-alls, needs heads
                                  # divisible by the seq axis) —
                                  # parallel/ring.py vs parallel/ulysses.py
    remat: bool = False           # jax.checkpoint each encoder layer:
                                  # recompute activations in the backward
                                  # pass — peak activation HBM drops from
                                  # O(layers) to O(1) residual streams
    remat_policy: str = "full"    # what a rematted layer SAVES: "full"
                                  # = nothing (maximum recompute, minimum
                                  # HBM); "dots" = keep matmul outputs
                                  # (jax.checkpoint_policies.
                                  # dots_with_no_batch_dims_saveable) and
                                  # recompute only the cheap elementwise —
                                  # the usual TPU sweet spot: the MXU work
                                  # is not repeated, and saved dot outputs
                                  # are the activations XLA would keep
                                  # anyway at ~half the HBM of no-remat
    ce_impl: str = "auto"         # MLM loss: "chunked" = online-logsumexp
                                  # over vocab tiles, never materializing
                                  # (B,S,V) fp32 logits (ops/mlm_head.py);
                                  # "dense" = full logits; "auto" = chunked
                                  # unless the vocab is tensor-parallel
                                  # sharded (then GSPMD's sharded dense
                                  # logits are already memory-bounded)
    ce_chunk: int = 2048          # vocab tile width for the chunked CE
    ce_positions: str = "masked"  # "masked": pack each row's masked
                                  # positions (<= ce_capacity_frac * S of
                                  # them) before the MLM head, so the head
                                  # transform + vocab decoder run on ~15-25%
                                  # of tokens (BERT's
                                  # max_predictions_per_seq, TPU-shaped);
                                  # "all": head over every position
    ce_capacity_frac: float = 0.25  # per-row packed-buffer width / S
    fused_qkv: bool = False       # compute q,k,v via ONE (E, 3HD) matmul
                                  # on stacked weights instead of three
                                  # (E, HD) matmuls — fewer, larger MXU
                                  # dispatches; parameters stay separate
                                  # (checkpoints/sharding rules unchanged)
    pos_kind: str = "learned"     # position encoding: "learned" absolute
                                  # embeddings (the BERT convention) or
                                  # "rope" rotary (applied to q/k right
                                  # before the attention dispatch, so
                                  # dense/flash/ring/Ulysses and the
                                  # KV-cache decode all inherit it; the
                                  # pos_emb table stays in the pytree
                                  # unused, keeping checkpoint layout
                                  # stable across the knob)
    flash_min_seq: int = 4096     # engage the Pallas flash kernel only at
                                  # sequence length >= this.  The kernel
                                  # lost to XLA's fused dense attention
                                  # at S=128 and S=2048 on a v5e under
                                  # the previous toolchain (ROADMAP.md,
                                  # "What the chip has actually said");
                                  # not re-measured on the installed
                                  # JAX — ROADMAP S7/D5 decide its fate.
                                  # 0 = always engage (kernel A/B arms)

    def __post_init__(self):
        # a misspelled value ("rotary", "Rope") would silently fall back
        # to learned positions at one site and skip rotation at another;
        # fail at construction instead
        if self.pos_kind not in ("learned", "rope"):
            raise ValueError(f"pos_kind must be 'learned' or 'rope', "
                             f"got {self.pos_kind!r}")

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


BERT_BASE = BertConfig()
BERT_TINY = BertConfig(vocab_size=1024, hidden=64, layers=2, heads=4, mlp=128,
                       max_positions=128, dropout=0.0)


def _norm_init(key, shape, stddev=0.02):
    return jax.random.normal(key, shape) * stddev


def init_encoder_layer(k, c) -> dict:
    """One encoder layer's parameters (``k``: a key iterator, 6 keys
    consumed).  Shared by BertMlm.init and the ViT family so the layer
    pytree structure — which ``_run_layers``, the pipeline stages, and
    the sharding rules all assume — has exactly one definition."""
    return {
        "wq": _norm_init(next(k), (c.hidden, c.heads, c.head_dim)),
        "wk": _norm_init(next(k), (c.hidden, c.heads, c.head_dim)),
        "wv": _norm_init(next(k), (c.hidden, c.heads, c.head_dim)),
        "bq": jnp.zeros((c.heads, c.head_dim)),
        "bk": jnp.zeros((c.heads, c.head_dim)),
        "bv": jnp.zeros((c.heads, c.head_dim)),
        "wo": _norm_init(next(k), (c.heads, c.head_dim, c.hidden)),
        "bo": jnp.zeros((c.hidden,)),
        "ln1": {"scale": jnp.ones((c.hidden,)),
                "bias": jnp.zeros((c.hidden,))},
        "w1": _norm_init(next(k), (c.hidden, c.mlp)),
        "b1": jnp.zeros((c.mlp,)),
        "w2": _norm_init(next(k), (c.mlp, c.hidden)),
        "b2": jnp.zeros((c.hidden,)),
        "ln2": {"scale": jnp.ones((c.hidden,)),
                "bias": jnp.zeros((c.hidden,))},
    }


def ce_capacity(cfg, S: int) -> int:
    """Packed-buffer width for the masked-position head: per-row capacity
    ``ce_capacity_frac * S`` rounded up to a multiple of 8 (lane-friendly),
    floored at 8, capped at S.  The ONE definition shared by BertMlm.loss
    and the pipelined 1F1B microbatch loss — the schedules' loss parity
    depends on both computing the identical cap."""
    return min(S, max(8, -(-int(cfg.ce_capacity_frac * S) // 8) * 8))


def rope(x, positions, base: float = 10000.0):
    """Rotary position embedding: rotate each (even, odd-half) feature
    pair of ``x`` (B, H, S, D) by an angle proportional to its ABSOLUTE
    position, so dot products depend only on RELATIVE offsets
    (rope(q,p1)·rope(k,p2) == rope(q,p1+d)·rope(k,p2+d) — pinned by
    test).  ``positions``: (S,) int/float absolute positions, or (B, S)
    per-row positions (the paged decode path, where every sequence sits
    at its own offset).  Angles in fp32, output in x.dtype; D must be
    even."""
    D = x.shape[-1]
    half = D // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.asarray(positions, jnp.float32)
    ang = pos[..., None] * freqs                    # (..., S, half)
    if pos.ndim == 2:
        cos = jnp.cos(ang)[:, None]                 # (B, 1, S, half)
        sin = jnp.sin(ang)[:, None]
    else:
        cos = jnp.cos(ang)[None, None]              # (1, 1, S, half)
        sin = jnp.sin(ang)[None, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], -1).astype(x.dtype)


def remat_policy_fn(cfg):
    """Resolve ``cfg.remat_policy`` to a ``jax.checkpoint`` policy —
    the ONE mapping shared by the encoder stack and the pipeline
    schedules (a policy honored on one path and silently ignored on
    another would make ``remat_policy`` a per-path lie).  ``None`` =
    save nothing (the "full" recompute)."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "full":
        return None
    raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}")


def dropout_mask(x, rate: float, key):
    """Inverted dropout: zero with prob ``rate``, scale survivors by
    1/keep.  The single implementation shared by BertMlm's keyed streams
    and the pipelined model's fold-derived keys."""
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def _layernorm(x, p, eps=1e-12):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


# -- shared per-layer math -------------------------------------------------
# One definition serves the GSPMD encoder (BertMlm._encode_aux), the
# pipelined stage (bert_pipeline._plain_layer), and the KV-cache decode
# path (gpt.forward_with_cache): a change to the block cannot silently
# diverge one of them.

def qkv_proj(lp, h, dt, fused: bool = False):
    """(B, S, E) -> per-head q, k, v, each (B, H, S, D).

    ``fused``: stack the three weights at trace time and run one
    (E, 3HD) matmul — one MXU dispatch instead of three.  The stack is a
    3.5 MB bf16 copy per layer that XLA typically folds into the matmul
    operand layout; parameters remain separate leaves either way."""
    if fused:
        w = jnp.stack([lp["wq"], lp["wk"], lp["wv"]]).astype(dt)
        b = jnp.stack([lp["bq"], lp["bk"], lp["bv"]]).astype(dt)
        qkv = jnp.einsum("bse,cehd->cbhsd", h, w) \
            + b[:, None, :, None, :]
        return qkv[0], qkv[1], qkv[2]
    q = jnp.einsum("bse,ehd->bhsd", h, lp["wq"].astype(dt)) \
        + lp["bq"].astype(dt)[None, :, None, :]
    k = jnp.einsum("bse,ehd->bhsd", h, lp["wk"].astype(dt)) \
        + lp["bk"].astype(dt)[None, :, None, :]
    v = jnp.einsum("bse,ehd->bhsd", h, lp["wv"].astype(dt)) \
        + lp["bv"].astype(dt)[None, :, None, :]
    return q, k, v


def attn_out_proj(lp, a, dt, reduce=None):
    """Row-parallel attention output projection: (B, H, S, D) -> (B, S, E).
    ``reduce``: applied to the partial product BEFORE the bias — the
    manual-TP psum hook (the bias must be added exactly once)."""
    out = jnp.einsum("bhsd,hde->bse", a, lp["wo"].astype(dt))
    if reduce is not None:
        out = reduce(out)
    return out + lp["bo"].astype(dt)


def gelu_mlp(lp, h, dt, constrain=None, reduce=None):
    """Position-wise GELU MLP; ``constrain`` optionally annotates the
    (B, S, mlp) intermediate with sharding; ``reduce`` is the manual-TP
    psum hook on the row-parallel output (pre-bias)."""
    m = jax.nn.gelu(jnp.einsum("bse,ef->bsf", h, lp["w1"].astype(dt))
                    + lp["b1"].astype(dt))
    if constrain is not None:
        m = constrain(m)
    out = jnp.einsum("bsf,fe->bse", m, lp["w2"].astype(dt))
    if reduce is not None:
        out = reduce(out)
    return out + lp["b2"].astype(dt)


@dataclasses.dataclass(frozen=True)
class BertMlm:
    cfg: BertConfig = BERT_BASE
    mesh: Optional[Any] = None            # when set, activations/attention are
    rules: Optional[dict] = None          # sharded per the rule table
    use_flash: bool = True                # Pallas flash kernel on TPU
    causal: bool = False                  # autoregressive mask everywhere
                                          # (models/gpt.py sets True) —
                                          # threaded through dense/ring/
                                          # Ulysses/flash alike

    # ---------------- init ----------------

    def init(self, rng):
        c = self.cfg
        k = iter(jax.random.split(rng, 16 + 16 * c.layers))
        params = {
            "tok_emb": _norm_init(next(k), (c.vocab_size, c.hidden)),
            "pos_emb": _norm_init(next(k), (c.max_positions, c.hidden)),
            "emb_ln": {"scale": jnp.ones((c.hidden,)),
                       "bias": jnp.zeros((c.hidden,))},
            "layers": [],
            "mlm": {
                "w": _norm_init(next(k), (c.hidden, c.hidden)),
                "b": jnp.zeros((c.hidden,)),
                "ln": {"scale": jnp.ones((c.hidden,)),
                       "bias": jnp.zeros((c.hidden,))},
                "out_b": jnp.zeros((c.vocab_size,)),
            },
        }
        for _ in range(c.layers):
            params["layers"].append(init_encoder_layer(k, c))
        return params

    def logical_axes(self):
        """Pytree (matching ``init``) of logical axis tuples for the rules."""
        ln = {"scale": ("embed",), "bias": ("embed",)}
        layer = {
            "wq": ("embed", "heads", "head_dim"),
            "wk": ("embed", "heads", "head_dim"),
            "wv": ("embed", "heads", "head_dim"),
            "bq": ("heads", "head_dim"), "bk": ("heads", "head_dim"),
            "bv": ("heads", "head_dim"),
            "wo": ("heads", "head_dim", "embed"), "bo": ("embed",),
            "ln1": ln,
            "w1": ("embed", "mlp"), "b1": ("mlp",),
            "w2": ("mlp", "embed"), "b2": ("embed",),
            "ln2": ln,
        }
        return {
            "tok_emb": ("vocab", "embed"),
            "pos_emb": ("pos", "embed"),
            "emb_ln": ln,
            "layers": [dict(layer) for _ in range(self.cfg.layers)],
            "mlm": {"w": ("embed", "embed"), "b": ("embed",), "ln": ln,
                    "out_b": ("vocab",)},
        }

    # ---------------- forward ----------------

    def _constrain(self, x, axes):
        if self.mesh is None:
            return x
        return rules_lib.constrain(x, axes, self.mesh, self.rules)

    def _attention(self, q, k, v):
        """q,k,v: (B, H, S, D).  Sequence-parallel attention (ring or
        Ulysses per ``cfg.sp_impl``) over the seq axis when the mesh shards
        it; otherwise the Pallas flash kernel on TPU for sequences at or
        above ``cfg.flash_min_seq``, XLA's fused dense attention below it
        (the measured winner at short/medium S — see flash_min_seq)."""
        from mpi_tensorflow_tpu.ops import flash_attention as fa

        on_tpu = jax.devices()[0].platform == "tpu"
        causal = self.causal
        # captured OUTSIDE shard_map: the threshold compares the FULL
        # sequence length, not a shard's slice of it
        S_full = q.shape[2]
        # selection is by what can be observed (platform, length, the
        # operator switch) — never by whether the kernel compiles: a
        # Mosaic refusal raises from the step's compile
        flash_ok = self.use_flash and on_tpu \
            and S_full >= self.cfg.flash_min_seq and fa.kernel_enabled()
        if self.mesh is not None and self.mesh.shape.get("seq", 1) > 1:
            specs = P("data" if self.mesh.shape.get("data", 1) > 1 else None,
                      "model" if self.mesh.shape.get("model", 1) > 1 else None,
                      "seq")

            def inner(q, k, v):
                if self.cfg.sp_impl == "ulysses":
                    from mpi_tensorflow_tpu.parallel import ulysses

                    inner_attn = None
                    if flash_ok:
                        # post-all-to-all each shard sees the FULL sequence
                        # for its head slice — S_full is the right length
                        # for the kernel threshold
                        def inner_attn(q, k, v, causal=False, scale=None):
                            return fa.flash_attention(q, k, v, causal, scale)
                    engagement.record(
                        "attention", "ulysses+flash" if inner_attn is not None
                        else "ulysses+xla")
                    return ulysses.ulysses_attention(q, k, v, "seq",
                                                     causal=causal,
                                                     inner=inner_attn)
                engagement.record("attention", "ring")
                return ring.ring_attention(q, k, v, "seq", causal=causal)

            # check_vma=False: pallas_call (the flash inner) cannot declare
            # varying-mesh-axes metadata on its outputs
            return jax.shard_map(inner, mesh=self.mesh,
                                 in_specs=(specs, specs, specs),
                                 out_specs=specs, check_vma=False)(q, k, v)
        if flash_ok:
            # any S: the kernel pads/masks to the block size internally
            engagement.record("attention", "flash")
            return fa.flash_attention(q, k, v, causal)
        engagement.record("attention", "xla_dense")
        return ring.dense_attention(q, k, v, causal=causal)

    def _mlp_block(self, lp, h, idx: int):
        """Position-wise MLP for layer ``idx`` -> (out, aux_loss).  The
        dense column/row-parallel MLP; MoE (models/moe.py) overrides this
        with routed experts on its MoE layers."""
        m = gelu_mlp(lp, h, self.cfg.dtype,
                     constrain=lambda m: self._constrain(
                         m, ("batch", "seq", "mlp")))
        return m, jnp.zeros((), jnp.float32)

    def _aux_weight(self) -> float:
        """Weight of the auxiliary loss accumulated by ``_mlp_block`` (0 for
        the dense model; the MoE load-balance weight in models/moe.py)."""
        return 0.0

    def encode(self, params, tokens, *, train: bool = False, rng=None):
        """Embeddings + encoder stack.  ``tokens``: int ids (B, S).
        Returns hidden states (B, S, E) in the compute dtype."""
        return self._encode_aux(params, tokens, train=train, rng=rng)[0]

    def _encode_aux(self, params, tokens, *, train: bool = False, rng=None):
        """Encoder returning ``(hidden, summed aux loss)``."""
        c = self.cfg
        B, S = tokens.shape
        h = params["tok_emb"][tokens]
        if c.pos_kind != "rope":
            h = h + params["pos_emb"][None, :S]
        h = _layernorm(h, params["emb_ln"])
        if train and c.dropout > 0.0:
            if rng is None:
                raise ValueError("dropout needs an rng in train mode")
            h = dropout_mask(h, c.dropout, jax.random.fold_in(rng, 1))
        h = h.astype(c.dtype)
        h = self._constrain(h, ("batch", "seq", "embed"))
        # layer dropout streams continue from index 1 (the embedding site)
        return self._run_layers(params, h, train=train, rng=rng,
                                drop_start=1)

    def _run_layers(self, params, h, *, train: bool = False, rng=None,
                    drop_start: int = 0):
        """The encoder layer stack on an already-embedded ``h`` (B, S, E)
        in the compute dtype.  Shared by the token path above and the
        ViT patch path (models/vit.py).  ``drop_start``: first unused
        dropout stream index — layer sites fold rng on drop_start+1, ...
        (stable across a remat recomputation)."""
        import functools

        c = self.cfg
        dt = c.dtype
        drop_i = drop_start

        def drop_with(i, x):
            """Dropout keyed by an explicit stream index (stable across a
            remat recomputation)."""
            if not train or c.dropout == 0.0:
                return x
            if rng is None:
                raise ValueError("dropout needs an rng in train mode")
            return dropout_mask(x, c.dropout, jax.random.fold_in(rng, i))

        def layer(h, lp, keys, mlp_fn):
            # --- attention (column-parallel QKV, row-parallel out) ---
            q, k, v = qkv_proj(lp, h, dt, fused=c.fused_qkv)
            if c.pos_kind == "rope":
                # before the attention dispatch AND before shard_map, so
                # every impl (dense/flash/ring/Ulysses) sees rotated q/k
                pos = jnp.arange(q.shape[2])
                q, k = rope(q, pos), rope(k, pos)
            q = self._constrain(q, ("batch", "heads", "seq", "head_dim"))
            k = self._constrain(k, ("batch", "heads", "seq", "head_dim"))
            v = self._constrain(v, ("batch", "heads", "seq", "head_dim"))
            a = self._attention(q, k, v)
            a = attn_out_proj(lp, a, dt)
            h = _layernorm(h + drop_with(keys[0], a), lp["ln1"]).astype(dt)
            h = self._constrain(h, ("batch", "seq", "embed"))
            # --- MLP (dense column/row parallel, or routed experts) ---
            m, aux = mlp_fn(lp, h)
            h = _layernorm(h + drop_with(keys[1], m), lp["ln2"]).astype(dt)
            return self._constrain(h, ("batch", "seq", "embed")), aux

        if c.remat:
            # trade FLOPs for HBM: drop each layer's activations after the
            # forward pass and recompute them during the backward pass —
            # peak activation memory goes from O(layers) to O(1) residuals
            # (plus saved dot outputs under the "dots" policy)
            layer = jax.checkpoint(layer, static_argnums=(3,),
                                   policy=remat_policy_fn(c))
        aux_total = jnp.zeros((), jnp.float32)
        for i, lp in enumerate(params["layers"]):
            # dropout keys derived OUTSIDE the (possibly rematted) layer so
            # the recomputation replays identical masks
            drop_i += 2
            h, aux = layer(h, lp, (drop_i - 1, drop_i),
                           functools.partial(self._mlp_block, idx=i))
            aux_total = aux_total + aux
        return h, aux_total

    def head_hidden(self, params, h):
        """MLM head transform (dense + GELU + LN) — the (B, S, E) input to
        the tied vocab decoder."""
        dt = self.cfg.dtype
        t = jax.nn.gelu(h @ params["mlm"]["w"].astype(dt)
                        + params["mlm"]["b"].astype(dt))
        return _layernorm(t, params["mlm"]["ln"]).astype(dt)

    def apply(self, params, batch, *, train: bool = False, rng=None):
        """``batch``: int token ids (B, S) (already masked for MLM).
        Returns vocab logits (B, S, V)."""
        dt = self.cfg.dtype
        h = self.encode(params, batch, train=train, rng=rng)
        t = self.head_hidden(params, h)
        logits = jnp.einsum("bse,ve->bsv", t, params["tok_emb"].astype(dt)) \
            + params["mlm"]["out_b"]
        logits = self._constrain(logits, ("batch", "seq", "vocab"))
        return logits.astype(jnp.float32)

    # ---------------- loss ----------------

    def _packs_positions(self) -> bool:
        """Whether the loss packs masked positions before the head (the MLM
        families).  The causal family computes CE at every position and
        overrides this to False."""
        return self.cfg.ce_positions == "masked"

    def _use_chunked_ce(self) -> bool:
        if self.cfg.ce_impl == "dense":
            return False
        if self.cfg.ce_impl == "chunked":
            return True
        # auto: with masked-position packing the logits are (B, S/4, V) —
        # small enough that XLA's dense path wins; chunking is the rescue
        # for full-position logits, unless the vocab axis is TP-sharded
        # (then dense logits are already sharded V/tp per device and GSPMD
        # places the logsumexp collectives)
        if self._packs_positions():
            return False
        return self.mesh is None or self.mesh.shape.get("model", 1) == 1

    def _ce(self, params, t, labels):
        """Per-position CE (B, S) fp32 from head hidden ``t``."""
        dt = self.cfg.dtype
        if self._use_chunked_ce():
            from mpi_tensorflow_tpu.ops import mlm_head

            engagement.record("ce", f"chunked:{self.cfg.ce_chunk}")
            return mlm_head.tied_softmax_ce(
                t, params["tok_emb"], params["mlm"]["out_b"], labels,
                chunk=self.cfg.ce_chunk)
        engagement.record("ce", "dense")
        logits = jnp.einsum("bse,ve->bsv", t, params["tok_emb"].astype(dt)) \
            + params["mlm"]["out_b"]
        logits = self._constrain(
            logits, ("batch", "seq", "vocab")).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return logz - gold

    def loss(self, params, model_state, batch, labels, *, rng=None,
             train: bool = False):
        """Masked-LM loss: mean CE over masked positions only.

        ``batch``: dict with ``tokens`` (B,S) int32 (mask token substituted)
        and ``mask`` (B,S) bool; ``labels``: (B,S) int32 original ids.
        """
        h, aux = self._encode_aux(params, batch["tokens"], train=train,
                                  rng=rng)
        mask = batch["mask"]
        if self.cfg.ce_positions == "masked":
            from mpi_tensorflow_tpu.ops import mlm_head

            engagement.record("ce_positions", "masked_packed")
            packed, plabels, w = mlm_head.gather_masked_rows(
                h, labels, mask.astype(jnp.bool_),
                ce_capacity(self.cfg, h.shape[1]))
            t = self.head_hidden(params, packed)
            ce = self._ce(params, t, plabels)
            weights = w
        else:
            engagement.record("ce_positions", "all")
            t = self.head_hidden(params, h)
            ce = self._ce(params, t, labels)
            weights = mask.astype(jnp.float32)
        # denominator = ALL masked positions (overflow-dropped ones count),
        # so the two ce_positions modes agree exactly when nothing overflows
        loss = jnp.sum(ce * weights) \
            / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        return loss + self._aux_weight() * aux, model_state

    def l2_params(self, params) -> list:
        return []   # transformer runs use decoupled weight decay (adamw)
