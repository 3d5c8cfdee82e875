"""Encoder-decoder LM (T5-shaped) — the cross-attention family.

The framework's transformer families cover bidirectional encoding (BERT),
autoregressive decoding (GPT), routed experts (MoE), pipeline stages, and
patches (ViT) — all built from self-attention blocks.  This family adds
the one block type missing from that set: CROSS-attention, composed from
the same primitives (``bert.qkv_proj`` / ``attn_out_proj`` /
``gelu_mlp`` / ``_layernorm``) so the math has one definition.

Shape: token encoder (the SHARED ``bert._run_layers`` stack,
bidirectional) -> decoder layers of [causal self-attn, cross-attn over
the encoder output, GELU MLP], post-LN residuals like the sibling
families, tied token embedding for encoder input, decoder input, and the
output head.  Positions are learned absolute embeddings (the framework
convention) rather than T5's relative bias — a documented divergence;
the family is named EncDecLm, not T5.

Loss: teacher-forced next-token CE on the decoder side.  Inference:
``generate`` encodes once, then runs the KV-cache decoder loop
(self-attn cache per layer; the cross-attn K/V are computed once from
the encoder output and reused every step — the standard enc-dec serving
shape).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from mpi_tensorflow_tpu.models import bert as bert_lib
from mpi_tensorflow_tpu.models.bert import (_layernorm, _norm_init,
                                            attn_out_proj, gelu_mlp,
                                            qkv_proj)
from mpi_tensorflow_tpu.parallel import ring


@dataclasses.dataclass(frozen=True)
class EncDecLm:
    """Encoder-decoder LM on the shared transformer primitives.

    ``cfg`` is a ``bert.BertConfig``; ``dec_layers`` defaults to
    ``cfg.layers`` (symmetric stacks, the T5 convention)."""
    cfg: bert_lib.BertConfig = bert_lib.BERT_TINY
    dec_layers: Optional[int] = None

    @property
    def n_dec(self) -> int:
        return self.dec_layers or self.cfg.layers

    def _encoder(self) -> bert_lib.BertMlm:
        return bert_lib.BertMlm(self.cfg)

    # ---------------- init ----------------

    def __post_init__(self):
        if self.cfg.pos_kind != "learned":
            # the decoder embeds learned positions; mixing a rope encoder
            # with a learned decoder would be a silent semantic fork —
            # guard at construction so checkpoint-restore paths that skip
            # init() are covered too
            raise ValueError(
                f"the encoder-decoder family supports pos_kind='learned' "
                f"only (got {self.cfg.pos_kind!r})")

    def init(self, rng):
        c = self.cfg
        # key budget: 3 embeddings + 6 per encoder layer + 10 per decoder
        # layer (init_encoder_layer's 6 + xq/xk/xv/xo); over-allocating is
        # harmless, running out raises StopIteration mid-init
        k = iter(jax.random.split(rng, 4 + 6 * c.layers + 10 * self.n_dec))
        params = {
            "tok_emb": _norm_init(next(k), (c.vocab_size, c.hidden)),
            "pos_emb": _norm_init(next(k), (c.max_positions, c.hidden)),
            "emb_ln": {"scale": jnp.ones((c.hidden,)),
                       "bias": jnp.zeros((c.hidden,))},
            "layers": [bert_lib.init_encoder_layer(k, c)
                       for _ in range(c.layers)],
            "dec_pos_emb": _norm_init(next(k), (c.max_positions, c.hidden)),
            "dec_emb_ln": {"scale": jnp.ones((c.hidden,)),
                           "bias": jnp.zeros((c.hidden,))},
            "dec_layers": [self._init_dec_layer(k, c)
                           for _ in range(self.n_dec)],
            "out_b": jnp.zeros((c.vocab_size,)),
        }
        return params

    @staticmethod
    def _init_dec_layer(k, c) -> dict:
        """Self-attn block + cross-attn block + MLP (9 keys)."""
        lp = bert_lib.init_encoder_layer(k, c)     # self-attn + MLP (6)
        lp["xq"] = _norm_init(next(k), (c.hidden, c.heads, c.head_dim))
        lp["xk"] = _norm_init(next(k), (c.hidden, c.heads, c.head_dim))
        lp["xv"] = _norm_init(next(k), (c.hidden, c.heads, c.head_dim))
        lp["xbq"] = jnp.zeros((c.heads, c.head_dim))
        lp["xbk"] = jnp.zeros((c.heads, c.head_dim))
        lp["xbv"] = jnp.zeros((c.heads, c.head_dim))
        lp["xo"] = _norm_init(next(k), (c.heads, c.head_dim, c.hidden))
        lp["xbo"] = jnp.zeros((c.hidden,))
        lp["lnx"] = {"scale": jnp.ones((c.hidden,)),
                     "bias": jnp.zeros((c.hidden,))}
        return lp

    def logical_axes(self):
        """Logical sharding axes (parallel/sharding_rules.py): the
        encoder layers reuse BertMlm's table; decoder cross-attention
        projections follow the same column/row-parallel layout (heads
        over ``model``)."""
        enc = bert_lib.BertMlm(self.cfg)
        layer = enc.logical_axes()["layers"][0]
        ln = {"scale": ("embed",), "bias": ("embed",)}
        dec_layer = dict(layer)
        dec_layer.update({
            "xq": ("embed", "heads", "head_dim"),
            "xk": ("embed", "heads", "head_dim"),
            "xv": ("embed", "heads", "head_dim"),
            "xbq": ("heads", "head_dim"), "xbk": ("heads", "head_dim"),
            "xbv": ("heads", "head_dim"),
            "xo": ("heads", "head_dim", "embed"), "xbo": ("embed",),
            "lnx": ln,
        })
        return {
            "tok_emb": ("vocab", "embed"),
            "pos_emb": ("pos", "embed"),
            "emb_ln": ln,
            "layers": [dict(layer) for _ in range(self.cfg.layers)],
            "dec_pos_emb": ("pos", "embed"),
            "dec_emb_ln": ln,
            "dec_layers": [dict(dec_layer) for _ in range(self.n_dec)],
            "out_b": ("vocab",),
        }

    # ---------------- forward ----------------

    def encode(self, params, src, *, train: bool = False, rng=None):
        """Bidirectional encoding of ``src`` (B, S) ids -> (B, S, E)."""
        c = self.cfg
        S = src.shape[1]
        h = params["tok_emb"][src] + params["pos_emb"][None, :S]
        h = _layernorm(h, params["emb_ln"])
        # embedding-site dropout on stream index 1, exactly as BertMlm
        # applies it (round 3: this site was silently skipped, quietly
        # diverging the family's regularization from its siblings)
        if train and c.dropout > 0.0:
            if rng is None:
                raise ValueError("dropout needs an rng in train mode")
            h = bert_lib.dropout_mask(h, c.dropout,
                                      jax.random.fold_in(rng, 1))
        h = h.astype(c.dtype)
        enc = self._encoder()
        h, _ = enc._run_layers({"layers": params["layers"]}, h,
                               train=train, rng=rng, drop_start=1)
        return h

    def _dec_embed(self, params, tgt_in, offset=0):
        c = self.cfg
        S = tgt_in.shape[1]
        pos = lax.dynamic_slice(params["dec_pos_emb"],
                                (offset, 0), (S, c.hidden))
        h = params["tok_emb"][tgt_in] + pos[None]
        return _layernorm(h, params["dec_emb_ln"]).astype(c.dtype)

    def _cross_kv(self, params, enc_out):
        """Per-decoder-layer cross-attention K/V from the encoder output —
        computed ONCE per source (prefill and every decode step reuse
        them)."""
        dt = self.cfg.dtype
        kv = []
        for lp in params["dec_layers"]:
            k = jnp.einsum("bse,ehd->bhsd", enc_out,
                           lp["xk"].astype(dt)) \
                + lp["xbk"].astype(dt)[None, :, None, :]
            v = jnp.einsum("bse,ehd->bhsd", enc_out,
                           lp["xv"].astype(dt)) \
                + lp["xbv"].astype(dt)[None, :, None, :]
            kv.append({"k": k, "v": v})
        return kv

    def _dec_layer(self, lp, h, xkv, *, self_attn, drop=None):
        """One decoder layer: residual self-attn (impl injected — dense
        causal for training, cache-backed for decoding), residual
        cross-attn, residual MLP.  Post-LN like the sibling families.
        ``drop``: ``drop(site_idx, x)`` dropout hook (None = eval)."""
        dt = self.cfg.dtype
        d = drop if drop is not None else (lambda i, x: x)
        a = d(0, self_attn(lp, h))
        h = _layernorm(h + a, lp["ln1"]).astype(dt)
        # cross-attention: queries from the decoder, K/V from the encoder
        q = jnp.einsum("bse,ehd->bhsd", h, lp["xq"].astype(dt)) \
            + lp["xbq"].astype(dt)[None, :, None, :]
        x = ring.dense_attention(q, xkv["k"], xkv["v"], causal=False)
        x = jnp.einsum("bhsd,hde->bse", x, lp["xo"].astype(dt)) \
            + lp["xbo"].astype(dt)
        h = _layernorm(h + d(1, x), lp["lnx"]).astype(dt)
        m = gelu_mlp(lp, h, dt)
        return _layernorm(h + d(2, m), lp["ln2"]).astype(dt)

    def _dec_self_attn_impl(self):
        """Decoder self-attention dispatch: the SHARED BertMlm._attention
        with causal=True — flash engages above cfg.flash_min_seq exactly
        as on the GPT path, and engagement records the choice.  Cross-
        attention stays XLA dense by design: its (T, S) score block is
        rectangular and the flash kernels are square-block; dense is the
        measured-correct choice at rectangular shapes."""
        return bert_lib.BertMlm(self.cfg, causal=True)._attention

    def _dec_drop(self, li: int, train: bool, rng):
        """Decoder dropout hook for layer ``li``: stream indices continue
        AFTER the encoder's (embed site 1 + 2 per encoder layer) and the
        decoder-embed site (index 2 + 2*enc_layers), 3 sites per decoder
        layer — disjoint fold_in keys across the model."""
        c = self.cfg
        if not train or c.dropout == 0.0:
            return None
        if rng is None:
            raise ValueError("dropout needs an rng in train mode")
        base = 3 + 2 * c.layers + 3 * li

        def drop(site, x):
            return bert_lib.dropout_mask(
                x, c.dropout, jax.random.fold_in(rng, base + site))
        return drop

    def decode_hidden(self, params, enc_out, tgt_in, *,
                      train: bool = False, rng=None):
        """Teacher-forced decoder pass -> hidden states (B, T, E) in the
        compute dtype (the input to the tied vocab head)."""
        c = self.cfg
        dt = c.dtype
        h = self._dec_embed(params, tgt_in)
        # decoder embedding-site dropout on the reserved stream index
        # right after the encoder's (see _dec_drop); generate() never
        # trains, so the site lives here rather than in _dec_embed
        if train and c.dropout > 0.0:
            if rng is None:
                raise ValueError("dropout needs an rng in train mode")
            h = bert_lib.dropout_mask(
                h, c.dropout,
                jax.random.fold_in(rng, 2 + 2 * c.layers)).astype(dt)
        xkvs = self._cross_kv(params, enc_out)
        attn = self._dec_self_attn_impl()

        def self_attn(lp, h):
            q, k, v = qkv_proj(lp, h, dt, fused=self.cfg.fused_qkv)
            return attn_out_proj(lp, attn(q, k, v), dt)

        def layer(h, lp, xkv, li):
            return self._dec_layer(lp, h, xkv, self_attn=self_attn,
                                   drop=self._dec_drop(li, train, rng))

        if self.cfg.remat:
            # same remat semantics as the encoder stack (the dropout keys
            # fold deterministically, so recomputation replays identical
            # masks); the policy mapping is the shared one
            layer = jax.checkpoint(
                layer, static_argnums=(3,),
                policy=bert_lib.remat_policy_fn(self.cfg))
        for li, (lp, xkv) in enumerate(zip(params["dec_layers"], xkvs)):
            h = layer(h, lp, xkv, li)
        return h

    def _head_logits(self, params, h):
        dt = self.cfg.dtype
        logits = jnp.einsum("bse,ve->bsv", h,
                            params["tok_emb"].astype(dt)) + params["out_b"]
        return logits.astype(jnp.float32)

    def decode_train(self, params, enc_out, tgt_in, *,
                     train: bool = False, rng=None):
        """Teacher-forced decoder pass -> fp32 logits (B, T, V)."""
        return self._head_logits(params, self.decode_hidden(
            params, enc_out, tgt_in, train=train, rng=rng))

    def apply(self, params, batch, *, train: bool = False, rng=None):
        """``batch``: {"src": (B, S), "tgt": (B, T)} int ids.  Returns
        decoder logits (B, T, V) (position t predicts tgt[t+1])."""
        enc_out = self.encode(params, batch["src"], train=train, rng=rng)
        return self.decode_train(params, enc_out, batch["tgt"],
                                 train=train, rng=rng)

    def loss(self, params, model_state, batch, labels=None, *, rng=None,
             train: bool = False):
        """Teacher-forced next-token CE over the target side: position t
        is supervised by tgt[t+1]; the final position is unsupervised.
        Matches CausalLm's loss shape so the gspmd step drives it
        unchanged.  The CE follows ``cfg.ce_impl`` like the sibling
        families: chunked online-logsumexp by default (every position
        carries loss — (B, T, V) fp32 logits would cost ~1 GB at
        64 x 128 x 30522), dense on request."""
        from mpi_tensorflow_tpu.utils import engagement

        tgt = batch["tgt"]
        enc_out = self.encode(params, batch["src"], train=train, rng=rng)
        h = self.decode_hidden(params, enc_out, tgt, train=train, rng=rng)
        targets = jnp.concatenate(
            [tgt[:, 1:], jnp.zeros_like(tgt[:, :1])], axis=1)
        if self.cfg.ce_impl != "dense":
            from mpi_tensorflow_tpu.ops import mlm_head

            engagement.record("ce", f"chunked:{self.cfg.ce_chunk}")
            ce = mlm_head.tied_softmax_ce(
                h, params["tok_emb"], params["out_b"], targets,
                chunk=self.cfg.ce_chunk)
        else:
            engagement.record("ce", "dense")
            logits = self._head_logits(params, h)
            logz = jax.nn.logsumexp(logits, axis=-1)
            ce = logz - jnp.take_along_axis(
                logits, targets[..., None], axis=-1)[..., 0]
        w = jnp.ones_like(ce).at[:, -1].set(0.0)
        return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0), model_state

    def l2_params(self, params) -> list:
        return []

    # ---------------- inference ----------------

    def generate(self, params, src, max_new_tokens: int, *,
                 bos_id: int = 0):
        """Greedy decode conditioned on ``src``: encode once, then a
        KV-cache decoder loop (static (B, H, L, D) self-attn cache per
        layer; the cross K/V are computed once).  Returns (B,
        max_new_tokens) generated ids, starting AFTER the BOS seed."""
        if max_new_tokens < 1:
            raise ValueError("generate needs max_new_tokens >= 1")
        c = self.cfg
        if max_new_tokens > c.max_positions:
            # _dec_embed's dynamic_slice clamps its start index, so
            # decoding past the learned dec_pos_emb table would silently
            # reuse the last row's embedding — mirror CausalLm.init_cache
            # and raise instead
            raise ValueError(
                f"max_new_tokens {max_new_tokens} exceeds max_positions "
                f"{c.max_positions}")
        dt = c.dtype
        B = src.shape[0]
        L = max_new_tokens
        enc_out = self.encode(params, src)
        xkvs = self._cross_kv(params, enc_out)
        z = jnp.zeros((B, c.heads, L, c.head_dim), dt)
        cache0 = [{"k": z, "v": z} for _ in range(self.n_dec)]
        col = jnp.arange(L)

        def step_token(carry, i):
            cache, token = carry
            h = self._dec_embed(params, token[:, None], offset=i)
            new_cache = []

            def self_attn_factory(li):
                def self_attn(lp, hq):
                    q, k, v = qkv_proj(lp, hq, dt, fused=c.fused_qkv)
                    cc = cache[li]
                    ck = lax.dynamic_update_slice(cc["k"], k,
                                                  (0, 0, i, 0))
                    cv = lax.dynamic_update_slice(cc["v"], v,
                                                  (0, 0, i, 0))
                    new_cache.append({"k": ck, "v": cv})
                    s = jnp.einsum("bhsd,bhld->bhsl", q, ck) \
                        .astype(jnp.float32)
                    vis = (col <= i)[None, None, None, :]
                    s = jnp.where(vis, s * c.head_dim ** -0.5,
                                  jnp.finfo(jnp.float32).min)
                    p = jax.nn.softmax(s, axis=-1).astype(dt)
                    a = jnp.einsum("bhsl,bhld->bhsd", p, cv)
                    return attn_out_proj(lp, a, dt)
                return self_attn

            for li, (lp, xkv) in enumerate(zip(params["dec_layers"],
                                               xkvs)):
                h = self._dec_layer(lp, h, xkv,
                                    self_attn=self_attn_factory(li))
            logits = jnp.einsum("bse,ve->bsv", h,
                                params["tok_emb"].astype(dt)) \
                + params["out_b"]
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (new_cache, nxt), nxt

        bos = jnp.full((B,), bos_id, jnp.int32)
        _, toks = lax.scan(step_token, (cache0, bos), jnp.arange(L))
        return toks.T    # (B, L)
