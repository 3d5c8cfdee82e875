"""Decoder-only causal LM (GPT-style) — the autoregressive family.

The reference has no transformer at all; BASELINE.json's directed scale-out
stops at BERT-base MLM.  This family demonstrates the framework's
generality beyond the directed set: the SAME encoder blocks, sharding
rules, attention kernels (causal flash / causal ring / causal Ulysses),
loss machinery (chunked CE), optimizer, loops, and checkpointing drive an
autoregressive LM — only the attention mask and the loss targets change.

Implementation: subclasses ``BertMlm`` with ``causal=True`` (the mask is
threaded through BertMlm._attention's dense/ring/Ulysses/flash paths — one
implementation, no copied override) and
- next-token loss: CE of position t against token t+1, over ALL positions
  (no mask packing — every position carries loss), using the same chunked
  online-logsumexp CE so (B, S, V) logits never materialize;
- untied LM head option is intentionally omitted: weight tying matches the
  MLM family and keeps vocab-parallel TP identical.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from mpi_tensorflow_tpu.models import bert as bert_lib
from mpi_tensorflow_tpu.models import bert_pipeline
from mpi_tensorflow_tpu.models.bert import _layernorm
from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
from mpi_tensorflow_tpu.utils import engagement


def _shift_targets(tokens):
    """THE next-token supervision definition, shared by the plain and
    pipelined causal families (they are not linked by MRO): targets are
    the inputs shifted left padded with 0, and the final position's
    weight is 0 (unsupervised).  Returns ``(targets, weights)``."""
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    w = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    return targets, w


@dataclasses.dataclass(frozen=True)
class CausalLm(bert_lib.BertMlm):
    """GPT-style causal LM on the shared transformer stack."""
    causal: bool = True

    def loss(self, params, model_state, batch, labels=None, *, rng=None,
             train: bool = False):
        """Next-token CE.  ``batch``: dict with ``tokens`` (B, S) (or the
        raw (B, S) int array); ``labels`` is ignored — targets are the
        inputs shifted left, with the final position unsupervised."""
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        h, aux = self._encode_aux(params, tokens, train=train, rng=rng)
        t = self.head_hidden(params, h)
        targets, w = _shift_targets(tokens)
        ce = self._ce(params, t, targets)                       # (B, S)
        loss = jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)
        return loss + self._aux_weight() * aux, model_state

    def _packs_positions(self) -> bool:
        return False   # every position carries loss — no mask packing

    # ------------------------------------------------------------------
    # autoregressive inference: KV cache + generate()
    #
    # The reference ships batched (non-autoregressive) inference only
    # (mpipy.py:169-183); decoding extends that role to this family.
    # TPU-shaped: the cache is a STATIC (B, H, max_len, D) buffer per
    # layer updated with lax.dynamic_update_slice, the decode loop is a
    # lax.scan — no data-dependent Python control flow, one compilation.
    # ------------------------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int) -> list:
        """Per-layer K/V buffers (zeros).  ``max_len`` caps prompt+output;
        under learned positions it must fit the pos_emb table — rope has
        no table and decodes to any length."""
        c = self.cfg
        if c.pos_kind == "learned" and max_len > c.max_positions:
            raise ValueError(
                f"max_len {max_len} exceeds max_positions {c.max_positions}")
        z = jnp.zeros((batch_size, c.heads, max_len, c.head_dim), c.dtype)
        return [{"k": z, "v": z} for _ in range(c.layers)]

    def forward_with_cache(self, params, tokens, cache, offset):
        """Forward ``tokens`` (B, S_in) occupying absolute positions
        [offset, offset+S_in), reading/writing the KV cache.

        One implementation serves both phases: prefill (S_in = prompt
        length, offset 0) and single-token decode (S_in = 1, traced
        offset).  Returns (fp32 logits (B, S_in, V), updated cache).

        Distributed decode: when the model carries a mesh, the same
        logical-axis constraints as training apply — batch over ``data``,
        attention heads (and therefore the KV cache's H dim) over
        ``model`` with GSPMD inserting the row-parallel psum in
        ``attn_out_proj``; the cache length dim stays replicated
        (``pos``) so the traced-offset dynamic_update_slice never crosses
        a shard boundary.  Math is kept in lockstep with the training
        stack — pinned by the incremental-vs-full parity test and the
        sharded-vs-single-device decode test (tests/test_gpt.py)."""
        c = self.cfg
        dt = c.dtype
        B, S_in = tokens.shape
        L = cache[0]["k"].shape[2]
        offset = jnp.asarray(offset, jnp.int32)

        if c.pos_kind == "rope":
            h = params["tok_emb"][tokens]
        else:
            pos_emb = lax.dynamic_slice(
                params["pos_emb"], (offset, 0), (S_in, c.hidden))
            h = params["tok_emb"][tokens] + pos_emb[None]
        h = _layernorm(h, params["emb_ln"]).astype(dt)
        h = self._constrain(h, ("batch", "seq", "embed"))

        pos = offset + jnp.arange(S_in)                    # (S_in,) absolute
        col = jnp.arange(L)
        # causal visibility over the cache: key position <= query position
        vis = col[None, :] <= pos[:, None]                 # (S_in, L)

        qkv_axes = ("batch", "heads", "seq", "head_dim")
        cache_axes = ("batch", "heads", "pos", "head_dim")
        new_cache = []
        for lp, cc in zip(params["layers"], cache):
            q, k, v = bert_lib.qkv_proj(lp, h, dt, fused=c.fused_qkv)
            if c.pos_kind == "rope":
                # rotate at ABSOLUTE positions; keys enter the cache
                # already rotated, so cached entries never re-rotate
                q = bert_lib.rope(q, pos)
                k = bert_lib.rope(k, pos)
            q = self._constrain(q, qkv_axes)
            ck = lax.dynamic_update_slice(cc["k"], k, (0, 0, offset, 0))
            cv = lax.dynamic_update_slice(cc["v"], v, (0, 0, offset, 0))
            ck = self._constrain(ck, cache_axes)
            cv = self._constrain(cv, cache_axes)
            new_cache.append({"k": ck, "v": cv})
            # the ONE fp32 masked-softmax implementation, shared with the
            # paged path (ops/paged_attention) — token parity between the
            # two holds by construction, not by review discipline
            a = paged_ops.masked_softmax_attention(
                q, ck, cv, vis[None, None], dt)
            a = bert_lib.attn_out_proj(lp, a, dt)
            h = _layernorm(h + a, lp["ln1"]).astype(dt)
            h = self._constrain(h, ("batch", "seq", "embed"))
            m = bert_lib.gelu_mlp(
                lp, h, dt,
                constrain=lambda m_: self._constrain(
                    m_, ("batch", "seq", "mlp")))
            h = _layernorm(h + m, lp["ln2"]).astype(dt)
            h = self._constrain(h, ("batch", "seq", "embed"))

        t = self.head_hidden(params, h)
        logits = jnp.einsum("bse,ve->bsv", t, params["tok_emb"].astype(dt)) \
            + params["mlm"]["out_b"]
        logits = self._constrain(logits, ("batch", "seq", "vocab"))
        return logits.astype(jnp.float32), new_cache

    def forward_paged(self, params, tokens, pools, block_tables, lengths,
                      valid=None, kernel: str = "xla", reduce=None):
        """Forward ``tokens`` (B, S_in) through the PAGED KV cache: row
        ``b`` occupies absolute positions [lengths[b], lengths[b]+S_in),
        reading/writing the per-layer block pools (serving/paged_cache)
        through its block table.  One implementation serves both serving
        phases — chunked prefill (S_in = chunk) and single-token decode
        (S_in = 1) — mirroring how ``forward_with_cache`` serves
        prefill+decode on the contiguous path.

        pools:        per-layer [{"k", "v"}] block pools, each
                      (num_blocks, block_size, H*D) — token-major,
                      ops/paged_attention's layout.  An int8 pool
                      (--kv-dtype int8) additionally carries
                      {"k_scale", "v_scale"} (num_blocks, block_size, H)
                      fp32 row scales (serving/paged_cache.init_pools);
                      writes then quantize on store and attention
                      dequantizes inside the consume path
        block_tables: (B, NB) int32 pool block ids, position order;
                      entries beyond a row's allocation must be the null
                      block (0)
        lengths:      (B,) int32 cache entries already written per row
        valid:        optional (B, S_in) bool; False lanes (padded
                      prefill tail, inactive decode slots) scatter into
                      the null block and their outputs are garbage the
                      caller discards
        kernel:       "xla" (gather + dense masked softmax) or "pallas"
                      (fused Pallas kernel streaming pool blocks in
                      place) — a STATIC choice resolved host-side
                      (ops/paged_attention.resolve_kernel); per-row
                      ``lengths`` flow into the attention op either way,
                      so the kernel can bound its block walk by live
                      tokens instead of relying on the visibility mask
                      alone
        reduce:       manual-TP allreduce hook applied to each layer's
                      row-parallel partial outputs (attention out-proj
                      and MLP down-proj) BEFORE their bias — the
                      serving tensor-parallel path (serving/tp) calls
                      this under shard_map with heads/mlp (and the
                      pool's head axis) sharded over a ``tp`` mesh axis
                      and passes ``lax.psum`` here; None keeps the
                      single-shard math byte-for-byte

        Returns (fp32 logits (B, S_in, V), updated pools).  The math
        shares ``forward_with_cache``'s layers AND its attention
        (``ops/paged_attention.masked_softmax_attention`` on the XLA
        path; the Pallas kernel's online softmax is pinned against it by
        tests/test_paged_kernel.py) — so greedy decode through this path
        is token-identical to ``generate`` (tests/test_serving.py).
        """
        c = self.cfg
        dt = c.dtype
        B, S_in = tokens.shape
        lengths = jnp.asarray(lengths, jnp.int32)
        pos = lengths[:, None] + jnp.arange(S_in, dtype=jnp.int32)  # (B, S)
        if valid is None:
            valid = jnp.ones((B, S_in), bool)

        if c.pos_kind == "rope":
            h = params["tok_emb"][tokens]
        else:
            # same rows dynamic_slice would fetch, but gathered per-row
            # (each sequence sits at its own offset); clip covers padded
            # lanes whose nominal position runs past the table
            h = params["tok_emb"][tokens] \
                + params["pos_emb"][jnp.clip(pos, 0, c.max_positions - 1)]
        h = _layernorm(h, params["emb_ln"]).astype(dt)
        h = self._constrain(h, ("batch", "seq", "embed"))

        qkv_axes = ("batch", "heads", "seq", "head_dim")
        engagement.record("paged_attention", kernel)
        # the kernel's live (row, group of blocks) pairs depend on
        # nothing but the lengths, the table's width and the pool's
        # shape: one list for every layer
        work = None
        if kernel in (paged_ops.PALLAS, paged_ops.PALLAS_INTERPRET):
            k0 = pools[0]["k"]
            work = paged_ops.paged_work(
                lengths, S_in, k0.shape[1], block_tables.shape[1],
                paged_ops.step_blocks(S_in, k0, pools[0].get("k_scale")))
        new_pools = []
        for lp, pl in zip(params["layers"], pools):
            q, k, v = bert_lib.qkv_proj(lp, h, dt, fused=c.fused_qkv)
            if c.pos_kind == "rope":
                # rotate at ABSOLUTE per-row positions; keys enter the
                # pool already rotated (as on the contiguous path)
                q = bert_lib.rope(q, pos)
                k = bert_lib.rope(k, pos)
            q = self._constrain(q, qkv_axes)
            mode = paged_ops.pool_mode(pl["k"], pl.get("k_scale"))
            if mode == "int4":
                # int4 pool (--kv-dtype int4, uint8 nibble codes):
                # group-quantize on store, consume through attend's
                # dequantizing paths WITH the fp-residual self lane —
                # the in-register k/v of this step's own tokens give
                # each query an exact fp score/value for its own
                # position (KIVI); the fp K/V still never touch the pool
                pk, ks = paged_ops.write_kv_quant_int4(
                    pl["k"], pl["k_scale"], k, block_tables, pos, valid)
                pv, vs = paged_ops.write_kv_quant_int4(
                    pl["v"], pl["v_scale"], v, block_tables, pos, valid)
                new_pools.append({"k": pk, "v": pv,
                                  "k_scale": ks, "v_scale": vs})
                a = paged_ops.attend(q, pk, pv, block_tables, lengths,
                                     dt, kernel=kernel,
                                     k_scale=ks, v_scale=vs,
                                     k_new=k, v_new=v, work=work)
            elif mode == "int8":
                # int8 pool (--kv-dtype int8): quantize on store —
                # codes and per-row scales scatter through the same
                # block/offset indexing — and consume through attend's
                # dequantizing paths; the fp K/V never touch the pool
                pk, ks = paged_ops.write_kv_quant(
                    pl["k"], pl["k_scale"], k, block_tables, pos, valid)
                pv, vs = paged_ops.write_kv_quant(
                    pl["v"], pl["v_scale"], v, block_tables, pos, valid)
                new_pools.append({"k": pk, "v": pv,
                                  "k_scale": ks, "v_scale": vs})
                a = paged_ops.attend(q, pk, pv, block_tables, lengths,
                                     dt, kernel=kernel,
                                     k_scale=ks, v_scale=vs, work=work)
            else:
                pk = paged_ops.write_kv(pl["k"], k, block_tables, pos,
                                        valid)
                pv = paged_ops.write_kv(pl["v"], v, block_tables, pos,
                                        valid)
                new_pools.append({"k": pk, "v": pv})
                a = paged_ops.attend(q, pk, pv, block_tables, lengths,
                                     dt, kernel=kernel, work=work)
            a = bert_lib.attn_out_proj(lp, a, dt, reduce=reduce)
            h = _layernorm(h + a, lp["ln1"]).astype(dt)
            h = self._constrain(h, ("batch", "seq", "embed"))
            m = bert_lib.gelu_mlp(
                lp, h, dt,
                constrain=lambda m_: self._constrain(
                    m_, ("batch", "seq", "mlp")),
                reduce=reduce)
            h = _layernorm(h + m, lp["ln2"]).astype(dt)
            h = self._constrain(h, ("batch", "seq", "embed"))

        t = self.head_hidden(params, h)
        logits = jnp.einsum("bse,ve->bsv", t, params["tok_emb"].astype(dt)) \
            + params["mlm"]["out_b"]
        logits = self._constrain(logits, ("batch", "seq", "vocab"))
        return logits.astype(jnp.float32), new_pools

    def generate(self, params, prompt, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, rng=None,
                 cache_len: int | None = None):
        """Autoregressive decode: greedy (``temperature == 0``) or
        temperature sampling, optionally filtered by ``top_k`` (keep the k
        highest-probability tokens) and/or ``top_p`` (nucleus: keep the
        smallest prefix of the probability-sorted vocab whose mass reaches
        p).  ``prompt``: (B, S0) int ids.  Returns
        (B, S0 + max_new_tokens) — the prompt with the continuation.

        Prefill computes the whole prompt in one batched forward (MXU-
        friendly); the per-token loop is a ``lax.scan`` over a static
        cache, so the whole call is one ``jit`` compilation.

        ``cache_len`` overrides the KV-cache capacity (default: exactly
        prompt + new tokens).  Every decode step attends over the full
        (masked) cache buffer, so per-step cost scales with the CAPACITY,
        not the occupancy — benchmark arms comparing different generation
        lengths must pin the same cache_len or the comparison is
        apples-to-oranges."""
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature sampling needs an rng")
        if (top_k > 0 or top_p < 1.0) and temperature <= 0.0:
            raise ValueError(
                "top_k/top_p filter the sampling distribution; they have "
                "no effect under greedy decoding (temperature 0) — pass "
                "temperature > 0")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, "
                             f"got {max_new_tokens}")
        if max_new_tokens == 0:
            return prompt
        B, S0 = prompt.shape
        total = S0 + max_new_tokens
        if cache_len is not None and cache_len < total:
            raise ValueError(f"cache_len {cache_len} < prompt + "
                             f"max_new_tokens ({total})")
        cache = self.init_cache(B, cache_len or total)
        logits, cache = self.forward_with_cache(params, prompt, cache, 0)
        first = self._sample(logits[:, -1], temperature, rng, 0,
                             top_k=top_k, top_p=top_p)

        def step(carry, i):
            cache, token, key = carry
            logits, cache = self.forward_with_cache(
                params, token[:, None], cache, S0 + i)
            nxt = self._sample(logits[:, 0], temperature, key, i + 1,
                               top_k=top_k, top_p=top_p)
            return (cache, nxt, key), token

        (_, last, _), toks = lax.scan(
            step, (cache, first, rng if rng is not None
                   else jax.random.key(0)),
            jnp.arange(max_new_tokens - 1))
        out = jnp.concatenate([toks.T, last[:, None]], axis=1) \
            if max_new_tokens > 1 else first[:, None]
        return jnp.concatenate([prompt, out], axis=1)

    def beam_search(self, params, prompt, max_new_tokens: int, *,
                    num_beams: int = 4, length_penalty: float = 0.0,
                    cache_len: int | None = None):
        """Fixed-length beam search over the KV-cache decode path.

        ``prompt``: (B, S0) int ids.  Returns ``(sequences, scores)``:
        sequences (B, num_beams, S0 + max_new_tokens) sorted by score
        descending, scores (B, num_beams) = sum of chosen-token log-probs
        divided by ``(new_tokens) ** length_penalty`` (0 = pure sum, the
        default; >0 favors longer... equal-length here, so it only
        rescales uniformly — exposed for API parity with samplers).

        TPU-shaped like ``generate``: beams fold into the batch dimension
        for the forward pass ((B*beam, 1) tokens per step), the per-step
        beam reindex is a ``take_along_axis`` gather over a (B, beam, ...)
        view of every cache leaf, and the whole loop is one ``lax.scan``
        — static shapes, one compilation.  No EOS semantics: the LM
        families train on streams without a terminator token, so beams
        always extend to the full length.

        ``cache_len`` pins the KV-cache capacity, exactly as in
        ``generate`` (decode cost scales with capacity, not occupancy —
        timing arms at different lengths must share one capacity)."""
        if max_new_tokens < 1:
            raise ValueError("beam_search needs max_new_tokens >= 1")
        if num_beams < 1:
            raise ValueError(f"num_beams must be >= 1, got {num_beams}")
        B, S0 = prompt.shape
        K = num_beams
        total = S0 + max_new_tokens
        if cache_len is not None and cache_len < total:
            raise ValueError(f"cache_len {cache_len} < prompt + "
                             f"max_new_tokens ({total})")
        V = self.cfg.vocab_size

        # prefill once at batch B, then tile the cache K-fold
        cache = self.init_cache(B, cache_len or total)
        logits, cache = self.forward_with_cache(params, prompt, cache, 0)
        logp0 = jax.nn.log_softmax(logits[:, -1], axis=-1)      # (B, V)
        scores, first = lax.top_k(logp0, K)                     # (B, K)
        cache = jax.tree.map(
            lambda c: jnp.repeat(c, K, axis=0), cache)          # (B*K, ...)

        def step(carry, i):
            cache, scores, token = carry                # token: (B, K)
            logits, cache = self.forward_with_cache(
                params, token.reshape(B * K, 1), cache, S0 + i)
            logp = jax.nn.log_softmax(
                logits[:, 0].reshape(B, K, V), axis=-1)
            cand = scores[..., None] + logp             # (B, K, V)
            scores, flat = lax.top_k(cand.reshape(B, K * V), K)
            parent = flat // V                          # which beam (B, K)
            nxt = (flat % V).astype(jnp.int32)
            # reindex every cache leaf to the surviving beams
            def reindex(c):
                v = c.reshape(B, K, *c.shape[1:])
                idx = parent.reshape(B, K, *([1] * (v.ndim - 2)))
                return jnp.take_along_axis(v, idx, axis=1) \
                    .reshape(B * K, *c.shape[1:])
            cache = jax.tree.map(reindex, cache)
            return (cache, scores, nxt), (parent, nxt)

        if max_new_tokens > 1:
            (_, scores, _), (parents, toks) = lax.scan(
                step, (cache, scores, first),
                jnp.arange(max_new_tokens - 1))
            # backtrack: follow parent pointers from the final beam slots.
            # At reverse position t the carry indexes step-(t+1) slots:
            # the token emitted there is toks[t][slot], and the chain
            # continues at parents[t][slot] (a step-t slot).
            def backtrack(beam_idx, xs):
                parent, tok = xs                         # (B, K) each
                cur_tok = jnp.take_along_axis(tok, beam_idx, 1)
                prev_idx = jnp.take_along_axis(parent, beam_idx, 1)
                return prev_idx, cur_tok

            beam_idx0 = jnp.tile(jnp.arange(K)[None], (B, 1))
            final_idx, rev = lax.scan(
                backtrack, beam_idx0, (parents, toks), reverse=True)
            # reverse=True stacks ys at their forward indices: rev[t] is
            # the token at generated position t+1 on each final beam
            mid = jnp.moveaxis(rev, 0, -1)               # (B, K, T-1)
            root = jnp.take_along_axis(first, final_idx, 1)  # (B, K)
            out = jnp.concatenate([root[..., None], mid], axis=-1)
        else:
            out = first[..., None]                       # (B, K, 1)
        seqs = jnp.concatenate(
            [jnp.broadcast_to(prompt[:, None], (B, K, S0)), out], axis=-1)
        if length_penalty:
            scores = scores / (float(max_new_tokens) ** length_penalty)
        return seqs, scores

    def _sample(self, logits, temperature, rng, i, *, top_k: int = 0,
                top_p: float = 1.0):
        """(B, V) fp32 logits -> (B,) token ids.

        The top-k / top-p filters run in DESCENDING-SORTED logit space and
        the categorical draw happens there too — the winning sorted slot
        is then mapped back through the sort's index vector.  Sampling in
        sorted space keeps every step gather-shaped (no (B, V) scatter,
        which XLA:TPU would serialize)."""
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key = jax.random.fold_in(rng, i)
        logits = logits / temperature
        V = logits.shape[-1]
        if top_k <= 0 and top_p >= 1.0:
            return jax.random.categorical(
                key, logits, axis=-1).astype(jnp.int32)
        # full descending sort (lax.top_k of the whole vocab)
        srt, idx = lax.top_k(logits, V)
        neg = jnp.finfo(srt.dtype).min
        if top_k > 0:
            keep_k = jnp.arange(V) < min(top_k, V)          # (V,)
            srt = jnp.where(keep_k[None], srt, neg)
        if top_p < 1.0:
            probs = jax.nn.softmax(srt, axis=-1)
            # exclusive cumulative mass BEFORE each slot: slot survives if
            # the mass above it is still < p (the top slot always survives)
            cum = jnp.cumsum(probs, axis=-1) - probs
            srt = jnp.where(cum < top_p, srt, neg)
        choice = jax.random.categorical(key, srt, axis=-1)  # sorted slot
        return jnp.take_along_axis(
            idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class PipelinedCausalLm(bert_pipeline.PipelinedBertMlm):
    """Causal LM under pipeline parallelism: the decoder-only stack
    pipelined over the mesh's ``pipe`` axis (GPipe or 1F1B,
    bert_pipeline.PipelinedBertMlm), every stage layer attending with the
    autoregressive mask (``causal=True`` flows into the stage body's
    ``dense_attention`` exactly as on the non-pipelined path).

    Loss: next-token CE over every position (final position
    unsupervised), expressed through the inherited pipelined loss by
    passing shifted targets as labels and the position weights as the
    mask — ``cfg.ce_positions`` must be "all" (guarded at construction:
    the pipelined loss consults the config directly, and masked-position
    packing is an MLM concept)."""
    causal: bool = True

    def __post_init__(self):
        super().__post_init__()
        if self.cfg.ce_positions != "all":
            raise ValueError(
                "PipelinedCausalLm computes next-token CE at every "
                "position; construct it with ce_positions='all' "
                f"(got {self.cfg.ce_positions!r}) rather than silently "
                "ignoring the packing config")

    def loss(self, params, model_state, batch, labels=None, *, rng=None,
             train: bool = False):
        """``batch``: dict with ``tokens`` (B, S) or the raw array;
        ``labels`` is ignored — targets are the inputs shifted left."""
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        targets, w = _shift_targets(tokens)
        return super().loss(params, model_state,
                            {"tokens": tokens, "mask": w}, targets,
                            rng=rng, train=train)
