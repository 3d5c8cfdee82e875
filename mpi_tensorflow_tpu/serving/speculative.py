"""Speculative decoding drafters: propose k tokens, verify in ONE forward.

PR 3's roofline block showed the decode hot path is BANDWIDTH-bound:
per emitted token the engine streams every live KV block past the MXU
once, and the matmuls on one query token nowhere near cover the read.
Speculative decoding (Leviathan et al., arXiv:2211.17192; Chen et al.,
arXiv:2302.01318) converts that idle compute into throughput: a cheap
DRAFTER proposes ``k`` tokens, the target model verifies all of them in
one batched forward (the chunked-prefill machinery already computes
logits at every position of a multi-token dispatch for free), and the
engine accepts the longest prefix whose greedy argmax chain matches the
draft — then emits the model's OWN token at the first mismatch.  Under
greedy decode the accepted stream is therefore token-identical to
vanilla one-token decoding BY CONSTRUCTION: every emitted token is an
argmax of target-model logits over exactly the context vanilla decode
would have used.  One KV-streaming pass is amortized over up to ``k+1``
emitted tokens; the engine-side accounting reports the win as
``accept_rate`` / ``mean_accepted_len`` / ``steps_saved``.

Two drafter backends behind one protocol (``--speculative``):

- ``NgramDrafter``   — n-gram SELF-draft: match the sequence's current
                       suffix against its own earlier prompt+generated
                       tokens and propose the continuation that followed
                       last time.  Zero extra model, zero device state;
                       strong on the templated / shared-prefix / looping
                       traffic the radix prefix cache already targets.
- ``DraftModelDrafter`` — a tiny ``CausalLm`` (BERT_TINY geometry by
                       default) running ahead of the target through its
                       OWN small paged pool, reusing the same bucketed
                       forward_paged dispatch discipline as the engine
                       (pow2 chunk buckets, fixed table width, zero
                       steady-state recompiles).

Both are HOST-side policy objects: the engine asks ``draft(rid, ctx,
k)`` for up to ``k`` proposals, reports lifecycle with ``release(rid)``
(request terminal) and ``reset()`` (engine pools rebuilt), and audits
``check_quiescent()`` at end of run.  A drafter may always return fewer
than ``k`` tokens — or none, in which case the verify dispatch
degenerates to an exact one-token decode step for that row, so a cold
or unlucky drafter can never change emitted tokens, only the speedup.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

from mpi_tensorflow_tpu.serving.paged_cache import (BlockAllocator,
                                                    blocks_for, init_pools)


class Drafter:
    """The drafter protocol (default = stateless no-op lifecycle).

    ``draft(rid, ctx, k)`` returns UP TO ``k`` proposed continuation
    tokens for request ``rid`` whose verified context (prompt + all
    accepted tokens, INCLUDING the still-pending one) is ``ctx``.
    Proposals are hints, never promises: the engine verifies every one
    through the target model and discards the rejected tail, so a
    drafter cannot affect correctness — only the accept rate.
    """

    def draft(self, rid: int, ctx: List[int], k: int) -> List[int]:
        raise NotImplementedError

    def release(self, rid: int) -> None:
        """Request ``rid`` left the engine (any terminal status)."""

    def reset(self) -> None:
        """The engine rebuilt its pools (reset / crash recovery)."""

    def check_quiescent(self) -> None:
        """End-of-run leak audit (pairs with Scheduler.check_quiescent)."""

    def compile_counts(self) -> Dict[str, object]:
        """Jit-cache entry counts for the drafter's own dispatches,
        merged into ``engine.compile_counts()`` — any drafter that jits
        device work must report it here or its recompiles escape the
        zero-recompile probe.  Host-only drafters report nothing."""
        return {}


class NgramDrafter(Drafter):
    """Suffix-match self-draft: propose the continuation that followed
    the current suffix the LAST time it occurred in this sequence's own
    prompt+generated stream.

    For n from ``max_ngram`` down to ``min_ngram``: find the most recent
    earlier occurrence of the context's final n-gram and propose the
    tokens that followed it.  Occurrences with a full ``k``-token
    continuation window are preferred (a repeating template yields the
    whole window); otherwise the longest partial continuation wins.
    Repetitive streams — templated answers, copy-from-prompt spans, the
    token loops small greedy models fall into — hit at high rates;
    novel text simply returns no draft and costs one ordinary decode.

    Linear scan per call (O(len(ctx) * max_ngram)): context is bounded
    by ``max_seq_len`` and the scan is host-side python, far from the
    device dispatch critical path at test scale.  A production port
    would keep a rolling hash index per sequence.
    """

    def __init__(self, max_ngram: int = 4, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram, got "
                             f"[{min_ngram}, {max_ngram}]")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def draft(self, rid: int, ctx: List[int], k: int) -> List[int]:
        L = len(ctx)
        if k < 1 or L < self.min_ngram + 1:
            return []
        for n in range(min(self.max_ngram, L - 1), self.min_ngram - 1, -1):
            suffix = ctx[L - n:]
            best: List[int] = []
            for i in range(L - n - 1, -1, -1):
                if ctx[i:i + n] != suffix:
                    continue
                cont = ctx[i + n:i + n + k]
                if len(cont) == k:
                    return cont          # most recent FULL window
                if len(cont) > len(best):
                    best = cont
            if best:
                return best
        return []


@dataclasses.dataclass
class _DraftState:
    """One request's footprint in the draft pool: its block table and
    how many VERIFIED context tokens have KV in it.  Drafted tokens'
    KV is written during drafting but never counted as cached —
    ``cached`` only ever covers tokens the target model accepted, so
    the next sync pass overwrites any stale speculative entries."""
    blocks: List[int] = dataclasses.field(default_factory=list)
    cached: int = 0
    last_used: int = 0


class DraftModelDrafter(Drafter):
    """Tiny-model drafter over its own paged KV pool.

    The draft model runs the SAME ``forward_paged`` path as the target
    engine, against a private pool sized for the same contexts: per
    call it syncs the unseen context tokens through pow2-bucketed
    chunk dispatches (the engine's prefill discipline — at most
    ``log2(chunk)+1`` compiled shapes, fixed full-width table), then
    autoregressively extends ``k`` tokens taking the argmax each step.
    Because context prefixes never change for a request id (greedy
    decode is deterministic, and an evicted request regenerates the
    exact same stream), cached draft KV stays valid across calls and
    even across target-engine evictions — the sync pass only ever
    appends or overwrites stale speculative positions.

    Pool pressure: when the draft pool cannot cover ``ctx + k``, other
    requests' draft state is dropped LRU-first (their KV is a pure
    cache — dropping it costs a re-sync, never correctness), and ``k``
    shrinks to whatever coverage remains.  A request's state is
    released the moment the engine reports it terminal.
    """

    def __init__(self, model, params, *, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int, chunk: int = 16,
                 kernel: str = "xla", kv_dtype: str = "fp32",
                 kv_group: int = 32):
        import jax

        if chunk < 1:
            raise ValueError(f"draft chunk must be >= 1, got {chunk}")
        self.model = model
        self.params = params
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.chunk = chunk
        self.kernel = kernel
        # the draft pool inherits the fleet kv_dtype (the PR 12 marked
        # extension): with the target pool quantized, an fp32 shadow
        # pool would dominate the drafter's HBM footprint.  Draft
        # tokens are verified by the target model before emission, so
        # draft-side quantization can only change WHICH tokens get
        # drafted, never correctness
        self.kv_dtype = kv_dtype
        self.kv_group = kv_group
        self._feed_fn = jax.jit(self._feed_impl, donate_argnums=(1,))
        self._clock = 0
        self.reset()

    def reset(self) -> None:
        self.pools = init_pools(self.model.cfg, self.num_blocks,
                                self.block_size, self.kv_dtype,
                                self.kv_group, model=self.model)
        self.allocator = BlockAllocator(self.num_blocks)
        self._state: Dict[int, _DraftState] = {}

    # ---------------- jitted feed ----------------

    def _feed_impl(self, params, pools, tokens, length, n_real, tables):
        """One (1, chunk-bucket) dispatch through the draft model: write
        the chunk's KV, return the greedy token after the last REAL
        lane — the engine's ``_prefill_impl`` shape discipline, reused
        for both the context sync and each 1-token draft extension."""
        import jax.numpy as jnp

        S = tokens.shape[1]
        valid = jnp.arange(S)[None] < n_real
        logits, pools = self.model.forward_paged(
            params, tokens, pools, tables, length[None], valid=valid,
            kernel=self.kernel)
        nxt = jnp.argmax(logits[0, jnp.maximum(n_real - 1, 0)], axis=-1)
        return nxt.astype(jnp.int32), pools

    def warmup(self) -> None:
        """Pre-pay every chunk-bucket compile with all-null-table
        dispatches (n_real=0: every lane scatters into the null block,
        the returned token is discarded) so a draft inside a timed
        steady-state window can never register as a recompile."""
        import jax.numpy as jnp
        import numpy as np

        tables = jnp.zeros((1, self.max_blocks_per_seq), jnp.int32)
        c = 1
        while True:
            self._feed(np.zeros((c,), np.int32), 0, 0, tables, bucket=c)
            if c >= self.chunk:
                break
            c *= 2

    def _feed(self, toks, length: int, n_real: int, tables, *,
              bucket: int):
        import jax.numpy as jnp
        import numpy as np

        buf = np.zeros((1, bucket), np.int32)
        buf[0, :len(toks)] = toks
        nxt, self.pools = self._feed_fn(
            self.params, self.pools, jnp.asarray(buf),
            jnp.asarray(length, jnp.int32),
            jnp.asarray(n_real, jnp.int32), tables)
        return int(nxt)

    # ---------------- pool management ----------------

    def _evict_lru(self, protect: int) -> bool:
        """Drop the least-recently-used OTHER request's draft state —
        pure cache, so the only cost is that request's next re-sync."""
        victims = [(st.last_used, rid) for rid, st in self._state.items()
                   if rid != protect and st.blocks]
        if not victims:
            return False
        _, rid = min(victims)
        self.release(rid)
        return True

    def release(self, rid: int) -> None:
        st = self._state.pop(rid, None)
        if st is not None and st.blocks:
            self.allocator.release(st.blocks)

    def check_quiescent(self) -> None:
        assert self.allocator.num_used == 0, (
            f"draft pool leak: {self.allocator.num_used} blocks still "
            f"referenced after every request terminated")
        self.allocator.check()

    def compile_counts(self) -> Dict[str, object]:
        try:
            return {"draft": int(self._feed_fn._cache_size())}
        except Exception:
            return {"draft": None}

    # ---------------- the draft call ----------------

    def draft(self, rid: int, ctx: List[int], k: int) -> List[int]:
        import jax.numpy as jnp
        import numpy as np

        st = self._state.setdefault(rid, _DraftState())
        self._clock += 1
        st.last_used = self._clock
        if st.cached >= len(ctx):
            # the target restarted this request (eviction replay): the
            # regenerated stream is identical (greedy determinism), so
            # the cached prefix stays valid — just re-feed the tail to
            # recover the logits cursor
            st.cached = len(ctx) - 1
        # never draft past the table capacity the pool can address
        k = min(k, self.max_blocks_per_seq * self.block_size - len(ctx))
        if k < 1:
            return []
        need = blocks_for(len(ctx) + k, self.block_size)
        while len(st.blocks) < need:
            # a successful LRU eviction always frees at least one block
            # (draft blocks are never shared), so one retry suffices
            if not self.allocator.can_alloc(1) and not self._evict_lru(rid):
                break
            st.blocks.extend(self.allocator.alloc(1))
        k = min(k, len(st.blocks) * self.block_size - len(ctx))
        if k < 1:
            return []
        tables = np.zeros((1, self.max_blocks_per_seq), np.int32)
        tables[0, :len(st.blocks)] = st.blocks
        tables = jnp.asarray(tables)
        # sync the unseen verified context through chunk buckets
        last = None
        pos = st.cached
        while pos < len(ctx):
            part = ctx[pos:pos + self.chunk]
            b = 1
            while b < len(part):
                b *= 2
            last = self._feed(part, pos, len(part), tables, bucket=b)
            pos += len(part)
        st.cached = len(ctx)
        # autoregressive extension: each drafted token is fed back at
        # the next position (its KV entry is speculative — ``cached``
        # stays at len(ctx), so the next sync overwrites it)
        out = [last]
        for i in range(k - 1):
            out.append(self._feed([out[-1]], len(ctx) + i, 1, tables,
                                  bucket=1))
        return out


def make_drafter(mode: str, serve, target_model, *, draft_model=None,
                 draft_params=None):
    """Build the drafter the ``--speculative`` mode names.

    ``draft-model`` uses the supplied ``draft_model``/``draft_params``
    when given (the parity tests inject the TARGET model to pin the
    all-accept path); otherwise it builds a BERT_TINY-geometry
    ``CausalLm`` on the target's vocab with deterministically seeded
    fresh parameters — the zero-training stand-in that exercises the
    full draft/verify machinery until a distilled drafter checkpoint
    exists.  Rope positions so draft capacity never hits a learned
    position-table bound the target does not share.
    """
    if mode == "off":
        return None
    if mode == "ngram":
        return NgramDrafter()
    if mode != "draft-model":
        raise ValueError(
            f"speculative mode must be off|ngram|draft-model, got {mode!r}")
    require = getattr(target_model, "require_draft", None)
    if require is not None:
        require(draft_model)     # a family the default draft is not of
    if draft_model is None:
        import jax

        from mpi_tensorflow_tpu.models import bert as bert_lib
        from mpi_tensorflow_tpu.models import gpt as gpt_lib

        cfg = dataclasses.replace(
            bert_lib.BERT_TINY, vocab_size=target_model.cfg.vocab_size,
            dtype=target_model.cfg.dtype, pos_kind="rope",
            ce_positions="all", dropout=0.0)
        draft_model = gpt_lib.CausalLm(cfg)
        draft_params = draft_model.init(jax.random.key(7))
    elif draft_params is None:
        raise ValueError("draft_model given without draft_params")
    from mpi_tensorflow_tpu.ops import paged_attention as paged_ops

    return DraftModelDrafter(
        draft_model, draft_params,
        num_blocks=serve.num_blocks, block_size=serve.block_size,
        max_blocks_per_seq=serve.max_blocks_per_seq,
        chunk=min(16, serve.prefill_chunk),
        kernel=paged_ops.resolve_for(
            draft_model, serve.kernel, serve.block_size,
            min(16, serve.prefill_chunk), serve.kv_dtype,
            serve.kv_group, max_slots=1,     # the drafter feeds one row
            max_blocks=serve.max_blocks_per_seq),
        kv_dtype=serve.kv_dtype, kv_group=serve.kv_group)
