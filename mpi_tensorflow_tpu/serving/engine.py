"""Continuous-batching decode engine over the paged KV cache.

Drives models/gpt.CausalLm.forward_paged with iteration-level
scheduling: every engine step advances ONE prefill chunk (if a newly
admitted sequence is mid-prefill) and ONE decode token for every live
sequence.  Chunked prefill keeps a long new prompt from stalling
in-flight decodes; slot recycling keeps finished sequences from burning
device cycles on masked rows.

One-step lookahead: ``step()`` issues iteration n+1's dispatches
before it reads iteration n's tokens.  Assembling a batch needs counts
(which rows decode, their lengths and tables, the budget test), never a
token's value: each slot's last token stays in a device array that the
dispatches scatter into and gather from, ``Scheduler.advance`` counts a
token when it is dispatched and ``Scheduler.deliver`` takes its value a
dispatch later, so admission, table assembly, token accounting and the
caller's work between two ``iterate`` calls run while the device works.
What only the value can say (EOS) and what the host decides meanwhile
(eviction, a deadline, a failure, a drain cut) reach a sequence one
dispatch late: the row it still has in flight is dropped at delivery
(``lookahead_discarded_rows``).  Where the next dispatch's SHAPE hangs
on the values — a drafter's accepted count, the mixed dispatch's — the
step reads at once (docs/SERVING.md, "The iteration's contract").

Compile discipline: device dispatches run at a SMALL FIXED SET of
bucketed shapes —

- decode:  (slot bucket, table-width bucket), both powers of two, so at
  most ``(log2 max_slots + 1) * (log2 max_blocks_per_seq + 1)`` shapes;
- prefill: (1, chunk bucket) with the full table width, at most
  ``log2 prefill_chunk + 1`` shapes;
- mixed (``--mixed-batch on``): (slot bucket, chunk bucket,
  table-width bucket) for the ONE fused prefill+decode forward per
  step — every triple pre-warmed at build, like speculative verify,
  because which buckets a mixed step hits depends on arrival timing

— so steady-state serving performs ZERO recompiles after bucket warmup
(pinned by tests/test_serving.py via the jit cache-size probe).  The
block pools are donated through every dispatch on TPU, so the cache
updates in place instead of ping-ponging two pool-sized buffers.

Tensor parallelism (``--tp N``): the jitted steps below run the
forward through a shard_map seam (serving/tp) that partitions the
pool (by head), QKV/O, and MLP over a ``tp`` mesh axis with one psum
per row-parallel projection.  Block tables index blocks, not heads, so
everything host-side in this file is tp-unaware; the seam is resolved
once at construction, so TP adds no dispatch shapes and the
zero-recompile contract holds unchanged.  Scale-OUT (whole-engine
replicas) lives above this file in serving/router.

Prefix sharing (``--prefix-cache on``): admission walks each
prompt through a radix trie of cached full blocks
(serving/prefix_cache) and maps hits to EXISTING physical blocks, so
prefill computes only the unique suffix; the engine contributes the
device half — a copy-on-write block copy before any dispatch would
write into a shared block, and trie registration when a prompt finishes
prefill.  Prefix sharing v2 (``--prefix-gen on``) extends the
trie with a finishing request's generated blocks (multi-turn reuse)
and serves mid-block misses through a pre-warmed one-compile partial
tail-block copy (``_partial_fn``, the ``_cow_fn`` discipline), applied
between admission and the first prefill chunk.  Greedy outputs with
the cache on are token-identical to cache-off for every request (the
determinism contract the serving tests pin), and v2-on is
token-identical to v2-off.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import numpy as np

from mpi_tensorflow_tpu.serving import paged_cache, \
    scheduler as sched_lib, tracing


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-pool geometry, feature switches and fault-tolerance
    policy: THE place a serving option lives.  One field here, its line
    in ``SERVE_HELP`` below and, where it needs one, a rule in
    ``__post_init__`` make an option; ``python -m
    mpi_tensorflow_tpu.serving`` derives its flag from the field."""
    num_blocks: int = 128
    block_size: int = 16
    max_slots: int = 8
    max_seq_len: int = 512
    prefill_chunk: int = 64
    eos_id: Optional[int] = None
    kernel: str = "auto"
    prefix_cache: str = "off"
    prefix_gen: str = "off"
    prefix_route: str = "off"
    speculative: str = "off"
    draft_k: int = 4
    draft_auto: str = "off"
    mixed_batch: str = "off"
    prefill_budget: int = 64
    kv_dtype: str = "fp32"
    kv_group: int = 32
    kv_tier: str = "off"
    tp: int = 1
    # --- fault-tolerance policy (None = feature off / unbounded) ---
    deadline_ms: Optional[float] = None
    queue_depth: Optional[int] = None
    max_evictions: Optional[int] = None
    drain_ms: Optional[float] = None
    failover_backoff_ms: float = 50.0
    trace: str = "off"
    trace_out: Optional[str] = None

    @property
    def max_blocks_per_seq(self) -> int:
        return paged_cache.blocks_for(self.max_seq_len, self.block_size)

    def __post_init__(self):
        bad = [f"{k} {getattr(self, k)} ({rule})" for k, lo, rule in (
            ("num_blocks", 2, ">= 2: block 0 is reserved"),
            ("block_size", 1, ">= 1"), ("max_slots", 1, ">= 1"),
            ("max_seq_len", 1, ">= 1"), ("prefill_chunk", 1, ">= 1"))
            if getattr(self, k) < lo]
        if bad:
            raise ValueError("bad pool geometry: " + ", ".join(bad))
        if self.kernel not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"serve kernel must be auto|xla|pallas, "
                f"got {self.kernel!r}")
        if self.prefix_cache not in ("off", "on"):
            raise ValueError(
                f"serve prefix cache must be off|on, "
                f"got {self.prefix_cache!r}")
        if self.prefix_gen not in ("off", "on"):
            raise ValueError(
                f"serve prefix_gen must be off|on, "
                f"got {self.prefix_gen!r}")
        if self.prefix_route not in ("off", "on"):
            raise ValueError(
                f"serve prefix_route must be off|on, "
                f"got {self.prefix_route!r}")
        if self.prefix_gen == "on" and self.prefix_cache == "off":
            raise ValueError(
                "serve prefix_gen extends the radix prefix cache; with "
                "prefix_cache off it would be silently ignored — turn "
                "the cache on or drop it")
        if self.prefix_route == "on" and self.prefix_cache == "off":
            raise ValueError(
                "serve prefix_route biases placement toward cached "
                "prefixes; with prefix_cache off there is no trie to "
                "hint from — turn the cache on or drop it")
        if self.speculative not in ("off", "ngram", "draft-model"):
            raise ValueError(
                f"serve speculative must be off|ngram|draft-model, "
                f"got {self.speculative!r}")
        if self.draft_k < 1:
            raise ValueError(
                f"serve draft_k must be >= 1, got {self.draft_k}")
        if self.draft_auto not in ("off", "on"):
            raise ValueError(
                f"serve draft_auto must be off|on, got {self.draft_auto!r}")
        if self.draft_auto == "on" and self.speculative == "off":
            raise ValueError(
                "serve draft_auto tunes the speculative draft window; "
                "with speculative off it would be silently ignored — "
                "pick a drafter or drop it")
        if self.mixed_batch not in ("off", "on"):
            raise ValueError(
                f"serve mixed_batch must be off|on, "
                f"got {self.mixed_batch!r}")
        if self.prefill_budget < 1:
            raise ValueError(
                f"serve prefill_budget must be >= 1, "
                f"got {self.prefill_budget}")
        if self.mixed_batch == "on" and self.speculative != "off":
            raise ValueError(
                "serve mixed_batch and speculative each replace the "
                "decode dispatch with their own fused forward; they do "
                "not compose — pick one")
        if self.kv_dtype not in ("fp32", "int8", "int4"):
            raise ValueError(
                f"serve kv dtype must be fp32|int8|int4, "
                f"got {self.kv_dtype!r}")
        if self.kv_group < 1:
            raise ValueError(
                f"serve kv_group must be >= 1, got {self.kv_group}")
        if self.kv_tier not in ("off", "host"):
            raise ValueError(
                f"serve kv_tier must be off|host, got {self.kv_tier!r}")
        if self.kv_tier == "host" and self.prefix_cache == "off":
            raise ValueError(
                "serve kv_tier demotes/promotes radix-trie blocks; with "
                "prefix_cache off there are no trie paths to key the "
                "host store by — turn the cache on or drop the tier")
        if self.tp < 1:
            raise ValueError(f"serve tp must be >= 1, got {self.tp}")
        bad = [f"{k} {getattr(self, k)} ({rule})" for k, ok, rule in (
            ("deadline_ms", lambda v: v > 0, "> 0"),
            ("queue_depth", lambda v: v >= 1, ">= 1"),
            ("max_evictions", lambda v: v >= 1, ">= 1"),
            ("drain_ms", lambda v: v >= 0, ">= 0"),
            ("failover_backoff_ms", lambda v: v > 0, "> 0"))
            if getattr(self, k) is not None and not ok(getattr(self, k))]
        if bad:
            raise ValueError("bad fault-tolerance policy: "
                             + ", ".join(bad))
        if self.trace not in ("off", "on"):
            raise ValueError(
                f"serve trace must be off|on, got {self.trace!r}")
        if self.trace_out is not None and self.trace != "on":
            raise ValueError(
                "serve trace_out names a Chrome-trace output but trace "
                "is off — there would be no trace to write; turn trace "
                "on or drop the path")
        if self.num_blocks - 1 < self.max_blocks_per_seq:
            # a lone max-length sequence must fit, or the scheduler can
            # deadlock with nothing left to evict
            raise ValueError(
                f"pool of {self.num_blocks - 1} usable blocks cannot hold "
                f"one max_seq_len={self.max_seq_len} sequence "
                f"({self.max_blocks_per_seq} blocks of {self.block_size})")


#: One line of help per ServeConfig field: what the option does and what
#: it needs.  The serving entry point's ``--help`` and the options table
#: of docs/SERVING.md are this table (tests/test_serve_entry.py).
SERVE_HELP = {
    "num_blocks": "paged KV pool size in blocks; block 0 is reserved as "
                  "the null block, and one max_seq_len sequence must fit "
                  "in the rest",
    "block_size": "cache entries per pool block",
    "max_slots": "concurrent sequences: the decode batch cap",
    "max_seq_len": "per-request prompt + output cap; sizes the "
                   "per-sequence block table",
    "prefill_chunk": "most prompt tokens one prefill dispatch takes, so "
                     "a long prompt cannot stall in-flight decodes",
    "eos_id": "token id that ends a sequence and recycles its slot "
              "(unset: the output budget alone ends it; the LM families "
              "train on streams with no terminator)",
    "kernel": "paged-attention lowering, auto|xla|pallas, resolved once "
              "at engine construction: auto takes the Pallas kernel on "
              "TPU when its compile probe passes and the XLA gather "
              "path otherwise; pallas off TPU is the interpreter",
    "prefix_cache": "off|on: radix prefix cache; on maps already-cached "
                    "full prompt blocks into new sequences (refcounted, "
                    "copy-on-write on divergence, LRU trie eviction "
                    "under pool pressure); tokens equal off's",
    "prefix_gen": "off|on: also cache a finished request's generated "
                  "full blocks (multi-turn reuse) and share partial tail "
                  "blocks through a one-compile row copy; needs "
                  "prefix_cache on",
    "prefix_route": "off|on: the replica router places a sessionless "
                    "request on the replica whose trie caches its "
                    "leading block when load permits; never overrides "
                    "health gating, never changes tokens; needs "
                    "prefix_cache on",
    "speculative": "off|ngram|draft-model: draft draft_k tokens a "
                   "sequence (from its own earlier tokens, or a tiny "
                   "model over its own paged pool) and verify them in "
                   "one forward; only the argmax-matching prefix is "
                   "emitted, so tokens equal off's",
    "draft_k": "speculative draft window: tokens proposed per verify "
               "forward (the dispatch is draft_k + 1 wide)",
    "draft_auto": "off|on: adapt the effective draft window to an EWMA "
                  "of the accepted length, within [1, draft_k]; the "
                  "dispatch width stays, so nothing recompiles; needs a "
                  "drafter",
    "mixed_batch": "off|on: fuse budget-capped prefill chunks of several "
                   "mid-prefill sequences into the decode dispatch, one "
                   "forward a step; tokens equal off's; replaces the "
                   "decode dispatch as speculative verify does, so the "
                   "two do not compose",
    "prefill_budget": "mixed batching: most prefill tokens fused into "
                      "one step across all mid-prefill sequences (read "
                      "only with mixed_batch on)",
    "kv_dtype": "pool storage format, fp32|int8|int4: fp32 keeps blocks "
                "in the model's compute dtype; int8 stores "
                "symmetric-absmax codes with a float32 scale per (block, "
                "slot, head); int4 packs two codes a byte with a scale "
                "per kv_group channels and a full-precision lane for "
                "each step's own tokens; quantised outputs track fp32 at "
                "a token-match rate, not token for token",
    "kv_group": "int4 scale-group size along head_dim: one float32 scale "
                "per min(kv_group, head_dim) channels, which must divide "
                "head_dim (read only with kv_dtype int4)",
    "kv_tier": "off|host: demote cold prefix-cache blocks to host memory "
               "on eviction and promote them back when a later prompt "
               "walks the same trie path; needs prefix_cache on",
    "tp": "tensor-parallel shards: >1 splits the pool by head and the "
          "QKV/O and MLP projections over a tp mesh axis (serving/tp); "
          "must divide the model's heads and MLP width and fit the "
          "visible devices (checked at engine construction)",
    "deadline_ms": "default per-request time to live from arrival; late "
                   "work fails with deadline_exceeded instead of holding "
                   "a slot (a request's own deadline wins; unset: none)",
    "queue_depth": "bound on the waiting queue; a submit that finds it "
                   "full is shed with queue_full (unset: unbounded)",
    "max_evictions": "a request preempted more often than this fails "
                     "with evicted_too_often instead of requeueing "
                     "forever (unset: unbounded)",
    "drain_ms": "graceful-drain budget after SIGTERM: in-flight work "
                "past it ends with status drained (unset: finish all "
                "in-flight work)",
    "failover_backoff_ms": "replica circuit breaker (serving/router): "
                           "base probe back-off after a transient "
                           "replica fault, doubled per consecutive fault "
                           "and capped at 64x",
    "trace": "off|on: request-lifecycle spans and a bounded step-phase "
             "ring on host clocks (serving/tracing), reported in the "
             "result's trace block; off builds no tracer",
    "trace_out": "write the run's Chrome trace-event JSON here (open in "
                 "Perfetto or chrome://tracing); needs trace on",
}


def pow2_ceil(n: int) -> int:
    """Smallest power of two >= ``n`` — THE bucketing rule the engine's
    dispatch-shape / zero-recompile contract rests on; the serving entry
    point sizes an unset pool with it so the two can never drift."""
    b = 1
    while b < n:
        b *= 2
    return b


def _weakly(method):
    """``method`` as a plain function that does not keep its engine
    alive.  The engine hands its own bound methods to objects it owns
    (the jitted steps, the scheduler's terminal hook, the trie's tier
    hooks); held strongly, each is a reference cycle, and an engine that
    is dropped then keeps its pool and its weights on the device until
    the interpreter's next FULL collection — which a caller that drops an
    engine to make room (a ten-gigabyte model's benchmark, before its
    reference runs) cannot wait for."""
    import weakref

    owner, fn = weakref.ref(method.__self__), method.__func__

    def call(*args, **kw):
        return fn(owner(), *args, **kw)
    # the method's own name: jit names the compiled program, its cache
    # entry and an unnamed Pallas kernel inside it after the function
    call.__name__, call.__qualname__ = fn.__name__, fn.__qualname__
    return call


class _SlackFilled:
    """A jitted step of a model that keeps state by slot
    (``model.slot_state``), callable with or without its trailing row
    arguments: left out (a prewarm on null tables, serve_driver's and
    ``prewarm_decode``'s), ``fill`` supplies the slack slot, so that a
    prewarm builds the very program the loop runs."""

    def __init__(self, fn, arity: int, fill):
        self.fn, self.arity, self.fill = fn, arity, fill

    def __call__(self, *args):
        if len(args) == self.arity:
            args += self.fill(args)
        return self.fn(*args)

    def _cache_size(self):
        return self.fn._cache_size()


def _bucket(n: int, cap: int) -> int:
    """Round ``n`` up to a power of two, capped at ``cap``."""
    return min(pow2_ceil(n), cap)


def _take_slot_tokens(last, slots):
    """The tokens a dispatch feeds: ``last`` (a token a slot) at its
    rows' ``slots``."""
    return last[slots]


def _keep_slot_tokens(last, slots, nxt):
    """``last`` with a dispatch's next tokens put at its rows' slots."""
    return last.at[slots].set(nxt)


def _sum_counters(leaves):
    """The model's device counters summed over its layers."""
    return sum(leaves)


def check_model(cfg, serve: ServeConfig) -> None:
    """The refusals that need the MODEL's widths beside the options: the
    position table against the sequence cap, and the tensor-parallel
    geometry (serving/tp, where the head/mlp rule is stated).  The engine
    calls it first thing; the entry point calls it before building
    anything, so a flag is refused in these words and a fault further
    down keeps its traceback."""
    refusal = getattr(cfg, "serve_refusal", lambda serve: None)(serve)
    if refusal:
        # what a family cannot do yet, in its own words
        raise ValueError(refusal)
    cap = serve.max_blocks_per_seq * serve.block_size
    if cfg.pos_kind == "learned" and cap > cfg.max_positions:
        raise ValueError(
            f"max_seq_len {serve.max_seq_len} (table capacity {cap}) "
            f"exceeds max_positions {cfg.max_positions}")
    from mpi_tensorflow_tpu.serving import tp as tp_lib

    tp_lib.check_geometry(cfg, serve.tp)


class PagedDecodeEngine:
    """Greedy continuous-batching decode over a paged KV cache.

    ``run(requests)`` returns ``{request id: generated token list}`` plus
    latency/throughput stats.  Greedy only: the serving path's parity
    anchor is ``CausalLm.generate(temperature=0)``; sampling belongs on
    top once the deterministic path is pinned.
    """

    def __init__(self, model, params, serve: ServeConfig, *,
                 draft_model=None, draft_params=None):
        import jax

        from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
        from mpi_tensorflow_tpu.serving import speculative as spec_lib

        self.model = model
        self.serve = serve
        check_model(model.cfg, serve)
        # tensor parallelism (serving/tp): the mesh, the sharded
        # parameter placement, and the shard_map forward are all
        # resolved once so TP is static under the jitted steps below
        from mpi_tensorflow_tpu.serving import tp as tp_lib

        self.tp_mesh = (tp_lib.make_tp_mesh(serve.tp)
                        if serve.tp > 1 else None)
        # resolve the knob -> xla|pallas|pallas-interpret ONCE,
        # host-side (compiled vs interpreted is decided here too, never
        # under jit): the literal bakes into the jitted steps below, so
        # kernel choice cannot add dispatch shapes or recompiles (the
        # zero-recompile contract covers the kernel path by
        # construction).  Under TP each shard runs the kernel over its
        # LOCAL heads, so the compile probe must see the per-shard head
        # count
        kcfg = (model.cfg if serve.tp == 1 else dataclasses.replace(
            model.cfg, heads=model.cfg.heads // serve.tp))
        self.kernel = paged_ops.resolve_for(
            model, serve.kernel, serve.block_size, serve.prefill_chunk,
            serve.kv_dtype, serve.kv_group, cfg=kcfg,
            max_slots=serve.max_slots,
            max_blocks=serve.max_blocks_per_seq)
        if self.tp_mesh is not None:
            self.params = tp_lib.shard_params(model, params, self.tp_mesh)
            self._paged_forward = tp_lib.make_paged_forward(
                model, self.tp_mesh, self.kernel,
                kv_dtype=serve.kv_dtype)
        else:
            self.params = params
            kernel = self.kernel        # no ``self`` in the closure
            # ``rows``: each row's slot and the lane whose logits are
            # taken, for a model that keeps state by slot; empty else
            self._paged_forward = (
                lambda params, tokens, pools, tables, lengths, valid,
                **rows:
                model.forward_paged(params, tokens, pools, tables,
                                    lengths, valid=valid, kernel=kernel,
                                    **rows))
        # donate the pools so the cache updates in place — on every
        # platform (XLA:CPU honours donation too), so tier-1 sees a
        # use-after-donate before the chip does
        donate = (1,)
        self._decode_fn = jax.jit(_weakly(self._decode_impl),
                                  donate_argnums=donate)
        self._prefill_fn = jax.jit(_weakly(self._prefill_impl),
                                   donate_argnums=donate)
        # a model with per-slot state (models/phi4_flash) is told each
        # row's slot, and which lane of a prefill chunk it answers for
        self._slot_state = bool(getattr(model, "slot_state", False))
        # ... and may ask for its tables at full width always: where one
        # layer in many reads the table and the kernel's grid follows the
        # live blocks anyway, a narrower table buys only programs
        self._full_tables = bool(getattr(model, "full_tables", False))
        # ... and may name the least prefill bucket it is dispatched at
        # (a prompt's short last chunk then rides a wider program, one of
        # a few)
        self._prefill_floor = min(getattr(model, "prefill_floor", 1),
                                  serve.prefill_chunk)
        if self._slot_state:
            slack = serve.max_slots
            self._decode_fn = _SlackFilled(
                self._decode_fn, 5,
                lambda a: (np.full(np.shape(a[2]), slack, np.int32),))
            self._prefill_fn = _SlackFilled(
                self._prefill_fn, 6,
                lambda a: (np.int32(slack), np.int32(1)))
        # copy-on-write block copy: pools in, pools out, fixed shapes —
        # exactly ONE compile ever (block ids ride as traced scalars)
        self._cow_fn = jax.jit(
            _weakly(self._cow_impl), donate_argnums=(0,))
        # partial tail-block copy (prefix v2): same discipline as
        # _cow_fn — block ids AND the row count ride as traced scalars,
        # so every (src, dst, n) reuses the one compiled program
        self._partial_fn = jax.jit(
            _weakly(self._partial_impl), donate_argnums=(0,))
        # host-tier promotion (--kv-tier host): write a demoted
        # block's host bytes into a freshly allocated device block —
        # same discipline as _cow_fn/_partial_fn: the destination id
        # rides as a traced scalar and the host leaves have one fixed
        # shape (a single block row per pool leaf), so every promotion
        # reuses the one compiled program
        self._promote_fn = jax.jit(
            _weakly(self._promote_impl), donate_argnums=(0,))
        # speculative decoding: the verify step runs pending + k draft
        # tokens through one forward (chunked-prefill math, decode-style
        # batching); the drafter is a host-side policy object built ONCE
        # so its jit cache (draft-model mode) survives reset()
        self._verify_fn = jax.jit(_weakly(self._verify_impl),
                                  donate_argnums=donate)
        # mixed batching: ONE fused prefill+decode forward per step
        # (--mixed-batch on); shares the verify dispatch's
        # masking math — decode rows are the chunk=1 degenerate case
        self._mixed_fn = jax.jit(_weakly(self._mixed_impl),
                                 donate_argnums=donate)
        # the lookahead's two small programs over ``_slot_tokens`` (each
        # slot's last token, kept on the device): a dispatch's next
        # tokens are scattered in by slot, the following dispatch's
        # input gathered out, so a token's value is fed back without
        # ever visiting the host.  One shape a slot bucket (and the
        # prefill's scalar), all built below, before any window opens
        self._take_fn = jax.jit(_take_slot_tokens)
        self._keep_fn = jax.jit(_keep_slot_tokens)
        # a traced run's snapshot of the model's device counters
        self._sum_fn = jax.jit(_sum_counters)
        self.drafter = spec_lib.make_drafter(
            serve.speculative, serve, model,
            draft_model=draft_model, draft_params=draft_params)
        # draft-window auto-tuning (--draft-auto on): EWMA of the
        # accepted length per verify forward drives the EFFECTIVE k.
        # Initialized optimistic (full window) and NOT cleared by
        # reset(): like the jit caches, the learned window is warmed
        # state a trace replay should keep — and it can never change
        # emitted tokens, only how much draft work is attempted
        self._accept_ewma = float(serve.draft_k)
        self._draft_k_eff = serve.draft_k
        self.reset()
        self._prewarm_slot_tokens()
        if self.prefix_cache is not None:
            # pre-pay the CoW copy's single compile with a null-block
            # self-copy (a no-op write), so the first real CoW inside a
            # timed steady-state window can never register as a
            # recompile against the zero-recompile contract
            import jax.numpy as jnp

            z = np.int32(0)
            self.pools = self._cow_fn(self.pools, z, z)
            if self.serve.prefix_gen == "on":
                # same contract for the partial-copy dispatch: a zero-
                # row null-block self-copy is a no-op write that pays
                # its one compile before any timed window opens
                self.pools = self._partial_fn(self.pools, z, z, z)
            if self.serve.kv_tier == "host":
                # same contract for the promote dispatch: a zero-leaf
                # write into the null block pays its one compile, so a
                # first promotion inside a timed steady-state window
                # can never register as a recompile
                host0 = paged_cache.block_rows(
                    self.pools,
                    lambda leaf: jnp.zeros(leaf.shape[1:], leaf.dtype))
                self.pools = self._promote_fn(self.pools, host0, z)
        if self.drafter is not None:
            # pre-warm the verify dispatch at EVERY (slot bucket, table
            # bucket) x width-(k+1) shape, plus the drafter's own chunk
            # buckets: how many tokens a verify step emits — and hence
            # which buckets later steps hit — depends on ACCEPTANCE,
            # i.e. on token content, so a warmup trace replay cannot be
            # trusted to visit every bucket the timed trace will.  The
            # zero-recompile contract must not hinge on content luck.
            self._prewarm_verify()
            if hasattr(self.drafter, "warmup"):
                self.drafter.warmup()
        if serve.mixed_batch == "on":
            # same contract for the fused mixed dispatch: which (slot,
            # chunk, table) buckets a step hits depends on ARRIVAL
            # TIMING — how many sequences are mid-prefill at once and
            # how they split the budget — which a warmup trace replay
            # cannot be trusted to reproduce.  Pay every bucket triple
            # at build, before any timed window opens.
            self._prewarm_mixed()

    def reset(self) -> None:
        """Fresh pools/scheduler; jit caches (and their warmed bucket
        shapes) survive — the serving entry point serves its trace
        against exactly the compiles the warm-up replay paid for."""
        import jax
        import jax.numpy as jnp

        from mpi_tensorflow_tpu.ops import paged_attention as paged_ops
        from mpi_tensorflow_tpu.serving import prefix_cache as prefix_lib

        self.pools = paged_cache.init_pools(
            self.model.cfg, self.serve.num_blocks, self.serve.block_size,
            self.serve.kv_dtype, self.serve.kv_group, model=self.model,
            max_slots=self.serve.max_slots)
        self._cache_bytes = {"paged": 0, "window": 0, "state": 0}
        for p in self.pools:
            for key, leaf in p.items():
                kind = paged_cache.cache_kind(key)
                if kind != "counter":
                    self._cache_bytes[kind] += leaf.size \
                        * leaf.dtype.itemsize
        # device counters the model declared beside its pool leaves
        # (routed experts' load): totals as last read, None = none held
        self._counters = ({} if any(
            paged_cache.is_counter(k) for p in self.pools for k in p)
            else None)
        # traced runs: (dispatch-log record, the counters' sum as a
        # device array taken right after that dispatch), oldest first
        self._snapshots: list = []
        # dispatches whose tokens the host has not read, oldest first:
        # (next tokens on the device, the (slot, Sequence) of each row,
        # a traced run's dispatch-log record).  The sequence identity is
        # what a late delivery checks: a row whose slot no longer holds
        # it is dropped
        self._unread: list = []
        # each slot's last token, on the device (index max_slots takes
        # the bucket slack's rows)
        self._slot_tokens = jnp.zeros((self.serve.max_slots + 1,),
                                      jnp.int32)
        if self.tp_mesh is not None:
            # head-axis sharding (serving/tp): one block id addresses
            # the same slot of every shard's local-heads pool, so the
            # host allocator/scheduler/trie below stay tp-unaware
            from mpi_tensorflow_tpu.serving import tp as tp_lib

            self.pools = tp_lib.shard_pools(self.pools, self.tp_mesh)
        # table entries a decode grid step of the K/V kernel attends,
        # by the kernel's own rule over the leaf a shard sees (a model
        # that brings its kernel walks one block a step): what the
        # ``paged_*`` counters reckon with
        self._decode_group = 1
        k = self.pools[0].get("k")
        if k is not None and not hasattr(self.model, "resolve_kernel"):
            self._decode_group = paged_ops.step_blocks(
                1, jax.ShapeDtypeStruct(k.sharding.shard_shape(k.shape),
                                        k.dtype),
                self.pools[0].get("k_scale"))
        self.allocator = paged_cache.BlockAllocator(self.serve.num_blocks)
        # fresh trie with fresh pools: cached content lives in the pool,
        # so the two reset together (a stale trie would map new
        # sequences onto zeroed blocks)
        self.prefix_cache = (
            prefix_lib.PrefixCache(self.allocator, self.serve.block_size)
            if self.serve.prefix_cache == "on" else None)
        # host-RAM block tier (--kv-tier host): resets WITH the
        # pools/trie — stored bytes index device content that just went
        # away, and crash recovery rebuilds both from the journal
        self.tier = (paged_cache.HostBlockStore()
                     if self.serve.kv_tier == "host" else None)
        if self.tier is not None and self.prefix_cache is not None:
            self.prefix_cache.tier = self.tier
            self.prefix_cache.demote_fetch = _weakly(self._demote_fetch)
            self.prefix_cache.promote_put = _weakly(self._promote_put)
        if self.drafter is not None:
            # the draft pool indexes device state that resets with the
            # engine's own pools (crash recovery rebuilds both)
            self.drafter.reset()
        self.sched = sched_lib.Scheduler(
            self.allocator, self.serve.max_slots, self.serve.block_size,
            self.serve.max_blocks_per_seq,
            queue_depth=self.serve.queue_depth,
            max_evictions=self.serve.max_evictions,
            prefix_cache=self.prefix_cache,
            prefix_gen=self.serve.prefix_gen == "on",
            on_terminal=_weakly(self._on_terminal))
        # pool-occupancy high-water marks: raw = every referenced block
        # (includes trie-retained blocks, which are reclaimable cache);
        # live = distinct blocks mapped by live sequences — the
        # occupancy that actually gates admission, and the number
        # sharing shrinks (two sequences on one physical block count it
        # once)
        self.peak_blocks_in_use = 0
        self.peak_live_blocks = 0
        # tracing resets WITH the engine state (like the pools/trie): a
        # rebuilt engine is a fresh incarnation whose spans the caller
        # merges across harvests.  trace off = no tracer object at all,
        # so every instrumentation site is a `tracer is None` skip
        self.tracer = (tracing.EngineTracer()
                       if self.serve.trace == "on" else None)
        self._progressed = False        # did the last step() do any work
        self._journal = None            # set by run(); step() journals a
                                        # token BEFORE record_token so the
                                        # durable order is always tok-then-
                                        # end (an end-ok preceding its own
                                        # finishing token would replay a
                                        # truncated stream as complete)
        self._last_token: dict = {}     # slot -> next token to feed, as
                                        # delivered: what the verify and
                                        # mixed steps assemble from (the
                                        # plain step feeds the device's
                                        # own copy, ``_slot_tokens``)
        # admitted (slot, Sequence) pairs awaiting prefill: the sequence
        # identity guards against a slot being evicted and re-admitted
        # while queued — a stale entry must not prefill the NEW occupant
        self._prefill_queue: List[tuple] = []
        self.dispatch_shapes: set = set()
        # model-forward dispatches this run (prefill + decode + verify
        # + mixed; CoW/partial copies excluded — they move cache rows,
        # not tokens): dispatches-per-emitted-token is THE CPU-visible
        # win metric of mixed batching
        self.forward_dispatches = 0
        # of those, the ones issued while an earlier dispatch's tokens
        # were still unread — over ``forward_dispatches`` ~1.0 on the
        # plain path, 0 where every dispatch is read at once — and the
        # rows that were computed for a sequence which had left its slot
        # by the time they were read (EOS, eviction, deadline, failure)
        self.lookahead_dispatches = 0
        self.lookahead_discarded_rows = 0
        # arrays the host handed the device through JAX's Python array
        # path (``_put``: the verify and mixed dispatches' inputs, a
        # promoted block's leaves) — none on the plain decode and prefill
        # path, whose numpy arguments ride the compiled call's own — and
        # the table rows the dispatches were cut from, beside
        # ``sched.table_rows_written``: the rows the scheduler rewrote
        # because a block list changed
        self.host_array_puts = 0
        self.table_rows_dispatched = 0
        # what the attention kernel's grid walks over the decode
        # dispatches of this run — each row's live blocks, a slack row's
        # one, in groups of ``_decode_group`` (ops/paged_attention.
        # work_list, step_blocks) — beside the bound of that list (rows x
        # groups of the table bucket): their ratio is the share of the
        # bucketed table that is work, and steps over the kernel's
        # device time is what a step costs.  ``paged_live_blocks`` is
        # what the contexts oblige a step to read and
        # ``paged_blocks_fetched`` what the steps copy, a last group's
        # dead tail included: fetched / live is the waste, live / steps
        # the blocks a step
        self.paged_grid_steps = 0
        self.paged_grid_bound = 0
        self.paged_live_blocks = 0
        self.paged_blocks_fetched = 0
        # prefill chunks that began at position 0: each starts a slot's
        # per-slot state from zero (a model without any counts them too)
        self.state_resets = 0

    def _on_terminal(self, req, status: str) -> None:
        """THE per-request exit hook (installed on every scheduler this
        engine builds): release the drafter's per-request state, then
        forward to the replay journal when one is attached — chaining
        here (instead of run() overwriting ``sched.on_terminal``) keeps
        the tok-then-end durable ordering AND the draft-pool lifecycle
        in one place."""
        if self.drafter is not None:
            self.drafter.release(req.id)
        if self._journal is not None:
            self._journal.record_end(req, status)
        if self.tracer is not None:
            # clock-free on purpose: this fires inside step() where no
            # loop clock is in scope; the tracer queues the transition
            # and EngineLoop.iterate lands it with the post-step stamp
            self.tracer.on_terminal(req, status)

    # ---------------- jitted device steps ----------------

    def _decode_impl(self, params, pools, tokens, lengths, tables,
                     slots=None):
        """(B,) tokens at per-row positions ``lengths`` -> (B,) greedy
        next tokens + updated pools.  Padding rows (bucket slack) carry
        all-null tables; their writes land in the null block and their
        output is discarded on host.  ``slots`` (each row's slot, the
        slack one for padding rows) goes to a model that keeps state by
        slot and to no other."""
        import jax.numpy as jnp

        from mpi_tensorflow_tpu.ops.paged_attention import NULL_BLOCK

        live = (tables[:, 0] != NULL_BLOCK)[:, None]
        rows = {} if slots is None else {"slots": slots}
        logits, pools = self._paged_forward(
            params, tokens[:, None], pools, tables, lengths, live, **rows)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt, pools

    def _prefill_impl(self, params, pools, tokens, length, n_real, tables,
                      slot=None, final=None):
        """One (1, chunk) prefill dispatch: writes the chunk's KV into
        the row's blocks, returns the greedy token following the LAST
        REAL lane (meaningful only on the final chunk) + updated pools.
        A model that keeps state by slot is given the row's ``slot`` and
        the one lane it answers for: the last real one where ``final``
        says this chunk ends the prompt, none otherwise, and computes
        logits for that lane alone."""
        import jax.numpy as jnp

        S = tokens.shape[1]
        valid = jnp.arange(S)[None] < n_real
        if slot is not None:
            take = jnp.where(final > 0, jnp.maximum(n_real - 1, 0), -1)
            logits, pools = self._paged_forward(
                params, tokens, pools, tables, length[None], valid,
                slots=slot[None], take=take[None])
            return jnp.argmax(logits[0, 0], axis=-1).astype(jnp.int32), pools
        logits, pools = self._paged_forward(
            params, tokens, pools, tables, length[None], valid)
        nxt = jnp.argmax(logits[0, jnp.maximum(n_real - 1, 0)], axis=-1)
        return nxt.astype(jnp.int32), pools

    def _cow_impl(self, pools, src, dst):
        """Copy one pool block (all layers, every block leaf the model
        declared — K and V with their scale siblings, or latent rows;
        counters pass through): the device half of copy-on-write.
        ``src``/``dst`` are traced scalars, so every copy reuses the one
        compiled program."""
        return paged_cache.copy_block(pools, src, dst)

    def _partial_impl(self, pools, src, dst, n):
        """Copy the first ``n`` token-slot rows of block ``src`` into
        ``dst`` (serving/paged_cache.partial_copy_block): the device
        half of partial tail-block sharing.  All three operands are
        traced scalars — one compile, like ``_cow_impl``."""
        return paged_cache.partial_copy_block(pools, src, dst, n)

    def _promote_impl(self, pools, host, dst):
        """Write one block row of host leaves into pool block ``dst``
        (all layers, every leaf — codes and, under quantized pools,
        their scale siblings): the device half of tier promotion.
        ``dst`` is a traced scalar; ``host`` is a per-layer list of
        single-block leaves with one fixed shape — one compile."""
        return paged_cache.write_block(pools, host, dst)

    def _demote_fetch(self, block: int) -> list:
        """Copy pool block ``block`` to host (per-layer dicts of
        np.ndarray rows) — the prefix cache calls this just before
        eviction releases the device block (--kv-tier host)."""
        return paged_cache.block_rows(
            self.pools,
            lambda leaf: np.asarray(leaf[block]))  # graft-lint: sync-ok(cold-block demotion off the dispatch path)

    def _promote_put(self, leaves: list, block: int) -> None:
        """Land demoted host bytes in freshly allocated device block
        ``block`` via the pre-warmed one-compile promote dispatch —
        called during the admission match walk, BEFORE the sequence's
        first dispatch, so the promoted content is in place when the
        block table first references it."""
        t0 = time.perf_counter()
        host = [{k: self._put(v) for k, v in p.items()} for p in leaves]
        tr = self.tracer
        if tr is not None:
            tr.enter("serve.launch")
        self.pools = self._promote_fn(self.pools, host, np.int32(block))
        if tr is not None:
            tr.exit()
        self.tier.promote_ms_total += (time.perf_counter() - t0) * 1e3

    def _verify_impl(self, params, pools, tokens, lengths, n_valid,
                     tables):
        """The speculative VERIFY dispatch: row ``b`` feeds its pending
        token plus its draft (``n_valid[b]`` real lanes of the fixed
        ``draft_k + 1`` width) at positions ``lengths[b] + lane``
        through ONE forward — the chunked-prefill math at decode-style
        batching.  Returns the greedy argmax at EVERY lane (``(B, W)``):
        lane ``i``'s token is what vanilla decode would emit after
        consuming the first ``i`` draft tokens, which is exactly the
        chain the host-side acceptance walk compares the draft against.
        Padding lanes (row slack or bucket slack) scatter into the null
        block and their argmax is discarded on host."""
        import jax.numpy as jnp

        from mpi_tensorflow_tpu.ops.paged_attention import NULL_BLOCK

        W = tokens.shape[1]
        live = tables[:, 0] != NULL_BLOCK
        valid = (jnp.arange(W)[None] < n_valid[:, None]) & live[:, None]
        logits, pools = self._paged_forward(
            params, tokens, pools, tables, lengths, valid)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools

    def _mixed_impl(self, params, pools, tokens, lengths, n_valid,
                    tables):
        """The fused mixed prefill+decode dispatch (--mixed-batch
        on): row ``b`` feeds ``n_valid[b]`` real lanes at positions
        ``lengths[b] + lane`` through ONE forward.  A decode row is the
        chunk=1 degenerate case (its pending token at position
        length-1); a prefill row is a budget-capped chunk of its prompt
        at its prefilled offset.  The chunked-prefill math already
        masks per-row lengths (ops/paged_attention.attend), so the
        fused batch is EXACT — each row sees precisely the context the
        unfused dispatch would give it, and greedy outputs are
        token-identical to mixed-off by construction.  Returns the
        greedy argmax at EVERY lane ``(B, S)``; the host consumes lane
        ``n_valid[b] - 1`` for decode rows and prompt-completing
        prefill rows only.  Padding lanes (row slack or bucket slack)
        scatter into the null block and their argmax is discarded on
        host."""
        import jax.numpy as jnp

        from mpi_tensorflow_tpu.ops.paged_attention import NULL_BLOCK

        S = tokens.shape[1]
        live = tables[:, 0] != NULL_BLOCK
        valid = (jnp.arange(S)[None] < n_valid[:, None]) & live[:, None]
        logits, pools = self._paged_forward(
            params, tokens, pools, tables, lengths, valid)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools

    def _prewarm_mixed(self) -> None:
        """Compile the fused mixed dispatch at every (slot bucket,
        chunk bucket, table bucket) triple it can ever run at —
        all-null tables, zero valid lanes, so nothing real is touched.
        The chunk-bucket axis is capped by the smaller of the chunk
        size and the prefill budget (a single row can never carry more
        lanes than either allows).  Same argument as _prewarm_verify:
        bucket visits depend on arrival timing, not just the trace
        envelope, so the zero-recompile contract is paid up front."""
        import jax.numpy as jnp
        import numpy as np

        serve = self.serve
        s_cap = _bucket(min(serve.prefill_chunk, serve.prefill_budget),
                        serve.prefill_chunk)
        Bb = 1
        while True:
            Sb = 1
            while True:
                NBb = 1
                while True:
                    _, self.pools = self._mixed_fn(
                        self.params, self.pools,
                        jnp.asarray(np.zeros((Bb, Sb), np.int32)),
                        jnp.asarray(np.zeros((Bb,), np.int32)),
                        jnp.asarray(np.zeros((Bb,), np.int32)),
                        jnp.asarray(np.zeros((Bb, NBb), np.int32)))
                    if NBb >= serve.max_blocks_per_seq:
                        break
                    NBb = min(NBb * 2, serve.max_blocks_per_seq)
                if Sb >= s_cap:
                    break
                Sb = min(Sb * 2, s_cap)
            if Bb >= serve.max_slots:
                break
            Bb = min(Bb * 2, serve.max_slots)

    def _prewarm_slot_tokens(self) -> None:
        """Compile the two slot-token programs at every slot bucket, and
        the scatter at the prefill's scalar: a few dozen bytes each, so
        they are built with the engine and never first met in a window.
        Everything lands in the slack entry."""
        import jax.numpy as jnp

        slack = self.serve.max_slots
        last = self._keep_fn(self._slot_tokens, np.int32(slack),
                             jnp.zeros((), jnp.int32))
        Bb = 1
        while True:
            slots = np.full((Bb,), slack, np.int32)
            last = self._keep_fn(last, slots, self._take_fn(last, slots))
            if Bb >= slack:
                break
            Bb = min(Bb * 2, slack)
        self._slot_tokens = last
        if self.tracer is not None and self._counters is not None:
            self._sum_fn(self._counter_leaves())

    def prewarm_decode(self) -> None:
        """Compile the decode dispatch at every (slot bucket, table
        bucket) pair it can ever run at — all-null tables, so nothing
        real is touched.  NOT called at build: an engine built directly
        pays decode compiles in its first run.  The serving entry point
        calls this before its warm-up replay, because its zero-recompile
        probe must hold on a wall-clock arrival trace:
        which (occupancy, table-width) pair a decode step runs
        at tracks arrival TIMING, and a compile stall in the warmup
        replay slows it enough to visit different buckets than the
        stall-free served pass — the same argument that makes
        _prewarm_mixed a build-time obligation."""
        Bb = 1
        while True:
            NBb = self._table_bucket(1)
            # the loop's own argument forms: tokens gathered on the
            # device, the rest numpy
            tokens = self._take_fn(self._slot_tokens,
                                   np.full((Bb,), self.serve.max_slots,
                                           np.int32))
            while True:
                _, self.pools = self._decode_fn(
                    self.params, self.pools, tokens,
                    np.zeros((Bb,), np.int32),
                    np.zeros((Bb, NBb), np.int32))
                if NBb >= self.serve.max_blocks_per_seq:
                    break
                NBb = min(NBb * 2, self.serve.max_blocks_per_seq)
            if Bb >= self.serve.max_slots:
                break
            Bb = min(Bb * 2, self.serve.max_slots)

    def _prewarm_verify(self) -> None:
        """Compile the verify dispatch at every (slot bucket, table
        bucket) it can ever run at — all-null tables, zero valid lanes,
        so nothing real is touched.  Verify-step bucket visits depend
        on acceptance — token content — so the contract is paid up
        front.  (Decode bucket visits also drift with arrival timing
        on wall-clock traces; ``prewarm_decode`` covers that for the
        callers that need it.)"""
        import jax.numpy as jnp
        import numpy as np

        W = self.serve.draft_k + 1
        Bb = 1
        while True:
            NBb = 1
            while True:
                toks, self.pools = self._verify_fn(
                    self.params, self.pools,
                    jnp.asarray(np.zeros((Bb, W), np.int32)),
                    jnp.asarray(np.zeros((Bb,), np.int32)),
                    jnp.asarray(np.zeros((Bb,), np.int32)),
                    jnp.asarray(np.zeros((Bb, NBb), np.int32)))
                if NBb >= self.serve.max_blocks_per_seq:
                    break
                NBb = min(NBb * 2, self.serve.max_blocks_per_seq)
            if Bb >= self.serve.max_slots:
                break
            Bb = min(Bb * 2, self.serve.max_slots)

    # ---------------- host-side step assembly ----------------

    def _ensure_private(self, slot: int, start: int, end: int) -> bool:
        """Copy-on-write guard for the write_kv path: before a dispatch
        writes cache positions ``[start, end)`` for ``slot``, any
        backing block that is SHARED (allocator refcount > 1 — other
        sequences and/or the prefix trie read it) is replaced by a
        private copy: allocate a fresh block (evicting under pressure),
        copy the shared block's contents on device, release the shared
        reference, and point the block table at the copy.  The one
        structural trigger is the shared-final-block recompute (a fully
        cached prompt whose length is an exact block multiple); the
        decode step runs the same guard as defense in depth — a write
        may NEVER land in a block another reader maps.

        Returns False when the pool cannot supply a copy target — the
        caller fails this one request, like any allocation dead end."""
        if self.prefix_cache is None or start >= end:
            return True
        seq = self.sched.slots[slot]
        bs = self.serve.block_size
        for j in range(start // bs, (end - 1) // bs + 1):
            if j >= len(seq.block_ids):
                continue            # growth handled by ensure_block
            src = seq.block_ids[j]
            if self.allocator.refcount(src) <= 1:
                continue            # exclusive: in-place write is safe
            dst = self.sched.alloc_for(slot)
            if dst is None:
                return False
            tr = self.tracer
            if tr is not None:
                tr.enter("serve.launch")
            self.pools = self._cow_fn(self.pools, np.int32(src),
                                      np.int32(dst))
            if tr is not None:
                tr.exit()
            self.allocator.release([src])
            self.sched.replace_block(slot, j, dst)
            self.sched.counters["prefix_cow_copies"] += 1
        return True

    def _apply_partial_copies(self) -> None:
        """Land every pending partial tail-block copy (prefix v2):
        admission matched ``partial_rows`` leading tokens of a slot's
        tail block against cached block ``partial_src`` and charged the
        sequence as if they were prefilled — the rows must be on device
        before the first prefill chunk reads past them.  Runs right
        after admit() in step(), so eviction cannot intervene; a slot
        whose sequence left anyway (pin already dropped by the
        scheduler) is skipped."""
        tr = self.tracer
        for seq in self.sched.slots:
            if seq is None or seq.partial_src is None:
                continue
            if tr is not None:
                tr.enter("serve.launch")
            self.pools = self._partial_fn(
                self.pools, np.int32(seq.partial_src),
                np.int32(seq.partial_dst), np.int32(seq.partial_rows))
            if tr is not None:
                tr.exit()
            self.sched._release_partial(seq)

    def _track_occupancy(self) -> None:
        """Advance the pool-occupancy high-water marks (see reset): the
        live blocks are the distinct non-null entries of the scheduler's
        table mirror (an empty slot's row is null, and so is the slack
        row), and with no prefix cache no block is mapped twice."""
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.allocator.num_used)
        tables = self.sched.tables
        live = (np.count_nonzero(tables) if self.prefix_cache is None
                else np.unique(tables).size - 1)
        self.peak_live_blocks = max(self.peak_live_blocks, int(live))

    def _table_bucket(self, blocks: int) -> int:
        """The table width a decode dispatch whose longest row holds
        ``blocks`` blocks runs at."""
        cap = self.serve.max_blocks_per_seq
        return cap if self._full_tables else _bucket(blocks, cap)

    def _row_slots(self, slots: List[int]) -> np.ndarray:
        """``slots`` padded with the slack slot to their bucket: the rows
        of the scheduler's mirrors a dispatch is cut from."""
        out = np.full((_bucket(len(slots), self.serve.max_slots),),
                      self.serve.max_slots, np.int32)
        out[:len(slots)] = slots
        return out

    def _advance_prefill(self) -> None:
        """Advance the oldest mid-prefill sequence by ONE chunk (chunked
        prefill: new prompts trickle into the pool between decode steps
        instead of stalling them for a whole long prompt).  The final
        chunk's token stays on the device, unread (``_hold``).  Every
        argument is numpy, scalars 0-d ``np.int32``: the compiled call
        takes them on its own argument path."""
        while self._prefill_queue:
            slot, seq = self._prefill_queue[0]
            if self.sched.slots[slot] is not seq:
                # evicted while queued (and possibly re-admitted: the
                # new occupant has its own queue entry) — drop the stale
                # entry, never prefill on its behalf
                self._prefill_queue.pop(0)
                continue
            break
        else:
            return
        prompt = seq.request.prompt
        self._progressed = True          # a chunk enters the pool
        chunk = prompt[seq.prefilled:seq.prefilled + self.serve.prefill_chunk]
        if not self._ensure_private(slot, seq.prefilled,
                                    seq.prefilled + len(chunk)):
            # no pool room for a private copy of a shared block this
            # chunk writes into: fail this one request, keep serving
            self._prefill_queue.pop(0)
            self.sched.fail_live(slot, "rejected")
            return
        sb = max(_bucket(len(chunk), self.serve.prefill_chunk),
                 self._prefill_floor)
        toks = np.zeros((1, sb), np.int32)
        toks[0, :len(chunk)] = chunk
        self.dispatch_shapes.add(("prefill", sb))
        self._count_dispatch()
        self.table_rows_dispatched += 1
        self.state_resets += seq.prefilled == 0
        final = seq.prefilled + len(chunk) >= len(prompt)
        rows = ((np.int32(slot), np.int32(final)) if self._slot_state
                else ())
        tr = self.tracer
        if tr is not None:
            tr.enter("serve.launch")
        # ``tables[[slot]]`` is a copy: the mirror moves on, the
        # argument does not
        nxt, self.pools = self._prefill_fn(
            self.params, self.pools, toks, np.int32(seq.prefilled),
            np.int32(len(chunk)), self.sched.tables[[slot]], *rows)
        rec = None
        if tr is not None:
            tr.exit()
            n, at = len(chunk), seq.prefilled
            rec = self._log_dispatch("prefill", n,
                                     n * at + n * (n + 1) // 2,
                                     [at], [n], int(final))
        self.sched.count_prefill(slot, len(chunk))
        if seq.prefilled < len(prompt):
            return
        self._prefill_queue.pop(0)
        if self.prefix_cache is not None:
            # register the fully prefilled prompt's full blocks BEFORE
            # a delivery can finish the request and release them: the
            # trie's own reference is what keeps a cached block alive
            # past its donor sequence
            self.prefix_cache.insert(prompt, seq.block_ids)
        # the prompt's last position already yields the first output
        # token (exactly generate()'s prefill-argmax), so the slot
        # enters the decode pool one token ahead
        self._hold(nxt, np.int32(slot), [(slot, seq)], rec)

    def _put(self, x):
        """``x`` on the device through JAX's Python array path, counted
        (``host_array_puts``)."""
        import jax.numpy as jnp

        self.host_array_puts += 1
        return jnp.asarray(x)

    def _count_dispatch(self) -> None:
        """One model-forward dispatch is about to be issued; it looks
        ahead when an earlier dispatch's tokens are still unread."""
        self.forward_dispatches += 1
        if self._unread:
            self.lookahead_dispatches += 1

    def _hold(self, nxt, slots, rows, rec) -> None:
        """A dispatch that computes a token for each of ``rows`` (its
        ``(slot, Sequence)`` pairs; ``slots`` the same on the device,
        bucket slack pointing at the slack entry) has been issued: put
        its ``nxt`` where the next dispatch gathers from, count a token
        for every row (``Scheduler.advance``) and keep the values unread
        until ``_deliver`` — nothing here waits for the device."""
        tr = self.tracer
        if tr is not None:
            tr.enter("serve.launch")
        self._slot_tokens = self._keep_fn(self._slot_tokens, slots, nxt)
        # start the few bytes' way to the host as soon as they exist, so
        # the read a dispatch later finds them there
        nxt.copy_to_host_async()
        if tr is not None:
            tr.exit()
        for slot, _seq in rows:
            self.sched.advance(slot)
        self._unread.append((nxt, rows, rec))

    def _deliver(self, n: int) -> List[Tuple[int, int]]:
        """Read the tokens of the ``n`` oldest unread dispatches and
        deliver them, in dispatch order and in the order the
        synchronous step had: journal the token, THEN account it (the
        terminal hook may fire: tok-then-end).  A row whose sequence has
        left its slot since the dispatch — it ended on EOS a dispatch
        ago, was evicted, failed, expired or cut — is dropped: its token
        was computed for nobody, and its one K/V write landed in a block
        the sequence still owned when the dispatch was issued, ahead (in
        the device's one stream) of any later owner's writes."""
        emitted: List[Tuple[int, int]] = []
        tr = self.tracer
        if tr is not None:
            tr.enter("serve.deliver")
        for nxt, rows, rec in self._unread[:n]:
            if tr is not None:
                # the time the host stands waiting for the device: next
                # to the step's length it says which side sets the pace
                tr.enter("serve.wait")
            toks = np.asarray(nxt).reshape(-1).tolist()  # graft-lint: sync-ok(the one bulk read a dispatch, taken after the next dispatch is issued)
            if tr is not None:
                tr.exit()
                self._read_counters(rec)
            for (slot, seq), tok in zip(rows, toks):
                if self.sched.slots[slot] is not seq:
                    self.lookahead_discarded_rows += 1
                    continue
                self._last_token[slot] = tok
                rid = seq.request.id
                emitted.append((rid, tok))
                if self._journal is not None:
                    self._journal.record_token(rid, tok)
                self.sched.deliver(slot, tok, self.serve.eos_id)
        if n:
            del self._unread[:n]
            self._progressed = True      # tokens reached the host
        if tr is not None:
            tr.exit()
        return emitted

    def all_done(self) -> bool:
        """Nothing waiting, nothing live and no dispatch unread: what a
        serve loop ends on (the scheduler's own ``all_done`` can read
        True with rows of ended sequences still in flight)."""
        return self.sched.all_done() and not self._unread

    def step(self) -> List[Tuple[int, int]]:
        """One engine iteration: admit, advance one prefill chunk,
        decode every live slot once, THEN read and deliver the tokens of
        the previous iteration's dispatches.  Returns the ``(request
        id, token)`` pairs delivered."""
        tr = self.tracer
        self._progressed = False
        if tr is not None:
            tr.enter("serve.admit")
        admitted = self.sched.admit()
        self._track_occupancy()
        if admitted:
            self._progressed = True
        self._prefill_queue.extend(
            (slot, self.sched.slots[slot]) for slot in admitted)
        self._apply_partial_copies()
        if tr is not None:
            tr.exit()
        if self.serve.mixed_batch == "on":
            # the fused path replaces BOTH the prefill and the decode
            # phases below, and reads its output at once
            return self._step_mixed()
        held = len(self._unread)         # the previous iteration's
        if tr is not None:
            tr.enter("serve.prefill")
        self._advance_prefill()
        if tr is not None:
            tr.exit()
        if self.drafter is not None:
            # the accepted count decides every row's next length, and
            # the drafter reads the delivered stream: the next
            # dispatch's shape hangs on the values, so read at once
            return self._step_verify(self._deliver(len(self._unread)))
        if tr is not None:
            tr.enter("serve.decode")
        self._dispatch_decode()
        if tr is not None:
            tr.exit()
        return self._deliver(held)

    def _dispatch_decode(self) -> None:
        """Assemble and issue one decode dispatch over every live,
        fully prefilled slot whose budget is not already spent by the
        tokens in flight.  Counts only: no token's value is read.  The
        row loop keeps what needs the scheduler (the budget, growth,
        copy-on-write); lengths and tables are the scheduler's mirrors
        indexed once, handed to the compiled call as numpy."""
        sched = self.sched
        live = []
        for slot in sched.live_slots():
            seq = sched.slots[slot]
            if seq is None or seq.prefilled < len(seq.request.prompt) \
                    or seq.spent:
                # mid-prefill: not in the decode pool; spent: its last
                # token is computed, it leaves when that is delivered
                continue
            if not sched.ensure_block(slot) or (
                    self.prefix_cache is not None and not
                    self._ensure_private(slot, seq.length - 1, seq.length)):
                # pool exhausted with nothing left to evict (for growth
                # or a private copy): THIS request cannot go on — fail it
                # alone (blocks freed, terminal status recorded); every
                # other in-flight stream keeps serving.  Unreachable when
                # submit()'s feasibility check gates admission, kept as
                # defense in depth: one request must never take the
                # engine down.
                sched.fail_live(slot, "rejected")
                continue
            live.append(slot)
        # eviction inside ensure_block/CoW may have retired a later slot
        live = [s for s in live if sched.slots[s] is not None]
        self._track_occupancy()
        if not live:
            return
        self._progressed = True

        n = len(live)
        slots = self._row_slots(live)
        Bb = len(slots)
        # the pending token writes at position length-1: the cache holds
        # length-1 entries until this step lands it; slack rows read 0
        lengths = sched.lengths[slots] - 1
        lengths[n:] = 0
        # a row holds the blocks its length needs (admission's, then
        # ensure_block's), so the longest length sizes the table bucket
        NBb = self._table_bucket(paged_cache.blocks_for(
            int(lengths[:n].max()) + 1, self.serve.block_size))
        tables = sched.tables[slots, :NBb]
        self.dispatch_shapes.add(("decode", Bb, NBb))
        self._count_dispatch()
        self.table_rows_dispatched += n
        G = self._decode_group
        blocks = np.minimum(lengths // self.serve.block_size + 1, NBb)
        steps = int((-(-blocks // G)).sum())
        self.paged_grid_steps += steps
        self.paged_grid_bound += Bb * -(-NBb // G)
        self.paged_live_blocks += int(blocks.sum())
        self.paged_blocks_fetched += steps * G
        tr = self.tracer
        if tr is not None:
            tr.enter("serve.launch")
        last = self._take_fn(self._slot_tokens, slots)
        if tr is not None:
            tr.exit()
            tr.enter("serve.launch")
        nxt, self.pools = self._decode_fn(
            self.params, self.pools, last, lengths, tables,
            *((slots,) if self._slot_state else ()))
        rec = None
        if tr is not None:
            tr.exit()
            rec = self._log_dispatch("decode", n, int(lengths.sum()) + n,
                                     lengths[:n], [1] * n, n)
        self._hold(nxt, slots, [(s, sched.slots[s]) for s in live], rec)

    def _step_mixed(self) -> List[Tuple[int, int]]:
        """The fused replacement for the prefill-then-decode phases
        (--mixed-batch on): pack the decode row of every live
        fully-prefilled slot PLUS budget-capped prefill chunks from
        every mid-prefill sequence the per-step token budget reaches
        into ONE forward, so decode ITL never stalls behind a long
        prompt and prefill is no longer serialized to one sequence per
        step.

        Packing rule: decode rows first (one pending token each), then
        the prefill queue in FIFO order — each mid-prefill sequence
        contributes ``min(prefill_chunk, remaining prompt, remaining
        budget)`` tokens until the budget runs out.  A slot is either
        decoding or mid-prefill, never both, so the row count is
        bounded by ``max_slots`` and the dispatch shape set stays
        (slot bucket) x (chunk bucket) x (table bucket), every triple
        pre-warmed at build (_prewarm_mixed).

        Every per-row invariant of the unfused loop holds per row:
        the stale-slot guard (an evicted entry must never prefill the
        slot's new occupant), ensure_block + CoW over exactly the
        row's write range, trie insertion at full prefill BEFORE
        record_token, and the journal's tok-then-end order."""
        serve = self.serve
        emitted: List[Tuple[int, int]] = []
        tr = self.tracer
        if tr is not None:
            tr.enter("serve.decode")
        # decode rows: the same admission/CoW discipline as the
        # unfused decode loop, row by row
        rows = []           # (slot, seq, lane tokens, start, is_prefill)
        for slot in self.sched.live_slots():
            seq = self.sched.slots[slot]
            if seq is None or seq.prefilled < len(seq.request.prompt):
                continue        # mid-prefill: packed below, not here
            if not self.sched.ensure_block(slot):
                self.sched.fail_live(slot, "rejected")
                continue
            if not self._ensure_private(slot, seq.length - 1, seq.length):
                self.sched.fail_live(slot, "rejected")
                continue
            rows.append((slot, seq, [self._last_token[slot]],
                         seq.length - 1, False))
        # prefill rows: FIFO over the queue under the per-step token
        # budget — MULTIPLE sequences advance per step, each by at most
        # one chunk; stale entries (evicted while queued, possibly
        # re-admitted: the new occupant has its own entry) are dropped,
        # never prefilled on behalf of
        budget = serve.prefill_budget
        for slot, seq in list(self._prefill_queue):
            if budget <= 0:
                break
            if self.sched.slots[slot] is not seq:
                self._prefill_queue = [
                    e for e in self._prefill_queue if e[1] is not seq]
                continue
            prompt = seq.request.prompt
            take = min(serve.prefill_chunk,
                       len(prompt) - seq.prefilled, budget)
            chunk = prompt[seq.prefilled:seq.prefilled + take]
            if not self._ensure_private(slot, seq.prefilled,
                                        seq.prefilled + len(chunk)):
                # no pool room for a private copy of a shared block
                # this chunk writes into: fail this one request alone
                self._prefill_queue = [
                    e for e in self._prefill_queue if e[1] is not seq]
                self.sched.fail_live(slot, "rejected")
                continue
            budget -= len(chunk)
            rows.append((slot, seq, list(chunk), seq.prefilled, True))
        # eviction inside ensure_block/CoW may have retired ANY earlier
        # row's slot (decode or mid-prefill): keep only rows whose slot
        # still holds the same sequence — a retired prefill row's queue
        # entry goes stale and drops on a later step
        rows = [r for r in rows if self.sched.slots[r[0]] is r[1]]
        self._track_occupancy()
        if not rows:
            if tr is not None:
                tr.exit()
            return emitted
        self._progressed = True

        Bb = _bucket(len(rows), serve.max_slots)
        slots = self._row_slots([r[0] for r in rows])
        Sb = _bucket(max(len(r[2]) for r in rows), serve.prefill_chunk)
        nb = max(len(r[1].block_ids) for r in rows)
        NBb = _bucket(nb, serve.max_blocks_per_seq)
        tokens = np.zeros((Bb, Sb), np.int32)
        lengths = np.zeros((Bb,), np.int32)
        n_valid = np.zeros((Bb,), np.int32)
        tables = self.sched.tables[slots, :NBb]
        for j, (slot, seq, lanes, start, _) in enumerate(rows):
            tokens[j, :len(lanes)] = lanes
            n_valid[j] = len(lanes)
            lengths[j] = start
        self.dispatch_shapes.add(("mixed", Bb, Sb, NBb))
        self.forward_dispatches += 1
        self.table_rows_dispatched += len(rows)
        if tr is not None:
            tr.enter("serve.launch")
        out, self.pools = self._mixed_fn(
            self.params, self.pools, self._put(tokens),
            self._put(lengths), self._put(n_valid), self._put(tables))
        if tr is not None:
            tr.exit()
            tr.exit()
            tr.enter("serve.deliver")
            tr.enter("serve.wait")
        out = np.asarray(out)  # graft-lint: sync-ok(the one budgeted bulk sync per mixed dispatch)
        if tr is not None:
            tr.exit()

        for j, (slot, seq, lanes, start, is_prefill) in enumerate(rows):
            if is_prefill:
                self.sched.count_prefill(slot, len(lanes))
                if seq.prefilled < len(seq.request.prompt):
                    continue        # still mid-prefill: no token emitted
                self._prefill_queue = [
                    e for e in self._prefill_queue if e[1] is not seq]
                if self.prefix_cache is not None:
                    # register the fully prefilled prompt's full blocks
                    # BEFORE record_token can finish the request and
                    # release them (same order as the unfused path)
                    self.prefix_cache.insert(seq.request.prompt,
                                             seq.block_ids)
            # lane n_valid-1 is exactly what the unfused dispatch
            # consumes: decode's argmax at its one lane, or prefill's
            # argmax after the prompt's last position
            tok = int(out[j, len(lanes) - 1])
            self._last_token[slot] = tok
            rid = seq.request.id
            emitted.append((rid, tok))
            if self._journal is not None:
                self._journal.record_token(rid, tok)
            self.sched.record_token(slot, tok, serve.eos_id)
        if tr is not None:
            tr.exit()
        return emitted

    def _step_verify(self, emitted: List[Tuple[int, int]]) \
            -> List[Tuple[int, int]]:
        """The speculative replacement for the decode phase: draft up
        to ``draft_k`` tokens per live slot, verify every slot's window
        in ONE batched forward, accept the longest argmax-matching
        draft prefix plus the model's own token at the first mismatch,
        then roll back the blocks the rejected tail was parked in.

        Token identity with ``--speculative off`` holds by
        construction: lane ``i`` of the verify output is the argmax
        over exactly the context vanilla decode would have at that
        position, and only argmax-chain-consistent tokens are emitted.
        A slot whose drafter proposes nothing rides the same dispatch
        with one valid lane — an exact one-token decode step."""
        serve = self.serve
        bs = serve.block_size
        cap = serve.max_blocks_per_seq * bs
        # the step's draft-window cap: the configured k, or — under
        # --draft-auto on — the EWMA-tuned effective k (floor 1
        # keeps a cheap probe alive so a recovering accept rate can
        # re-grow the window; the verify dispatch width stays draft_k+1
        # either way, so auto-tuning can never add a compile)
        k_cap = (self._draft_k_eff if serve.draft_auto == "on"
                 else serve.draft_k)
        tr = self.tracer
        if tr is not None:
            tr.enter("serve.decode")
        live: List[int] = []
        drafts: dict = {}
        full_window: dict = {}
        for slot in self.sched.live_slots():
            seq = self.sched.slots[slot]
            if seq is None or seq.prefilled < len(seq.request.prompt):
                continue            # mid-prefill: not in the decode pool
            if not self.sched.ensure_block(slot):
                self.sched.fail_live(slot, "rejected")
                continue
            # draft window, bounded so a full accept can neither bust
            # the request's budget (k <= remaining - 1: at most
            # ``remaining`` tokens emitted) nor the table capacity
            remaining = seq.request.max_new_tokens - len(seq.generated)
            k = min(k_cap, remaining - 1, cap - seq.length)
            # whether this row was OFFERED the policy's full window: a
            # row truncated by its budget, table capacity, or pool
            # pressure necessarily accepts few tokens, which says
            # nothing about the drafter — the auto-tune EWMA must not
            # read truncation as inaccuracy
            window_full = k >= k_cap
            draft: List[int] = []
            if k > 0:
                ctx = list(seq.request.prompt) + seq.generated
                draft = list(self.drafter.draft(
                    seq.request.id, ctx, k))[:k]
            if draft:
                # cover the whole window's writes [length-1, length+|d|)
                # with free blocks only — speculation never preempts
                covered = self.sched.extend_for(slot,
                                                seq.length + len(draft))
                if covered - seq.length < len(draft):
                    window_full = False
                draft = draft[:max(0, covered - seq.length)]
            full_window[slot] = window_full
            if not self._ensure_private(slot, seq.length - 1,
                                        seq.length + len(draft)):
                self.sched.fail_live(slot, "rejected")
                continue
            live.append(slot)
            drafts[slot] = draft
        # eviction inside ensure_block/CoW may have retired a later slot
        live = [s for s in live if self.sched.slots[s] is not None]
        self._track_occupancy()
        if not live:
            if tr is not None:
                tr.exit()
            return emitted
        self._progressed = True

        W = serve.draft_k + 1
        Bb = _bucket(len(live), serve.max_slots)
        slots = self._row_slots(live)
        nb = max(len(self.sched.slots[s].block_ids) for s in live)
        NBb = _bucket(nb, serve.max_blocks_per_seq)
        tokens = np.zeros((Bb, W), np.int32)
        lengths = np.zeros((Bb,), np.int32)
        n_valid = np.zeros((Bb,), np.int32)
        tables = self.sched.tables[slots, :NBb]
        for j, slot in enumerate(live):
            seq = self.sched.slots[slot]
            row = [self._last_token[slot]] + drafts[slot]
            tokens[j, :len(row)] = row
            n_valid[j] = len(row)
            lengths[j] = seq.length - 1
        self.dispatch_shapes.add(("verify", Bb, NBb))
        self.forward_dispatches += 1
        self.table_rows_dispatched += len(live)
        if tr is not None:
            tr.enter("serve.launch")
        out, self.pools = self._verify_fn(
            self.params, self.pools, self._put(tokens),
            self._put(lengths), self._put(n_valid), self._put(tables))
        if tr is not None:
            tr.exit()
            tr.exit()
            tr.enter("serve.deliver")
            tr.enter("serve.wait")
        out = np.asarray(out)  # graft-lint: sync-ok(the one budgeted bulk sync per verify dispatch)
        if tr is not None:
            tr.exit()

        counters = self.sched.counters
        for j, slot in enumerate(live):
            seq = self.sched.slots[slot]
            draft = drafts[slot]
            # longest exact-match prefix of the draft, then the model's
            # own token at the first mismatch (or after a full accept)
            n_acc = 0
            while n_acc < len(draft) and int(out[j, n_acc]) == draft[n_acc]:
                n_acc += 1
            emit = draft[:n_acc] + [int(out[j, n_acc])]
            if serve.eos_id is not None and serve.eos_id in emit:
                # nothing streams past EOS — and nothing past it may be
                # journaled either (the journal holds accepted tokens
                # only, and EOS terminates acceptance)
                emit = emit[:emit.index(serve.eos_id) + 1]
            counters["spec_drafted"] += len(draft)
            counters["spec_accepted"] += min(n_acc, len(emit))
            counters["spec_verify_forwards"] += 1
            counters["spec_emitted"] += len(emit)
            # effective-k accounting + EWMA update: the window the
            # policy would offer (k_cap) is what "effective k" means to
            # the speculation block; the EWMA tracks ACCEPTED
            # length only over rows that drafted into a FULL window —
            # a row with no draft, or one truncated by budget/capacity/
            # pool pressure, says nothing about the drafter's accuracy
            counters["spec_k_sum"] += k_cap
            counters["spec_k_steps"] += 1
            if serve.draft_auto == "on" and draft \
                    and full_window.get(slot, False):
                a = 0.2
                self._accept_ewma = ((1 - a) * self._accept_ewma
                                     + a * n_acc)
            self._last_token[slot] = emit[-1]
            rid = seq.request.id
            for tok in emit:
                emitted.append((rid, tok))
                if self._journal is not None:
                    self._journal.record_token(rid, tok)
            self.sched.record_tokens(slot, emit, serve.eos_id)
            if self.sched.slots[slot] is seq:
                # rollback: the rejected tail's phantom KV writes sit in
                # blocks past the accepted length — release them so the
                # pool never retains entries no accepted token owns
                self.sched.rollback_blocks(slot, seq.length)
        if serve.draft_auto == "on":
            # next step's window: one past the recent mean accepted
            # length (draft what history says will land, plus one probe
            # token of headroom), clamped to [1, configured k] — round,
            # not ceil: a near-zero EWMA must reach the floor instead
            # of parking one above it forever
            self._draft_k_eff = max(1, min(
                serve.draft_k, int(round(self._accept_ewma)) + 1))
        if tr is not None:
            tr.exit()
        return emitted

    # ---------------- request loop ----------------

    def run(self, requests: List[sched_lib.Request],
            time_fn=time.perf_counter, *, guard=None, journal=None,
            advisor=None) -> dict:
        """Serve ``requests`` (replayed against their ``arrival`` stamps)
        to completion or graceful drain.  The per-token latency of a
        token is the wall time since the previous token of the SAME
        sequence (first token: since arrival, queueing included) — the
        stream cadence a client sees.  An evicted request's pre-eviction
        tokens are discarded from the latency sample (they are
        regenerated; only the final delivered stream counts), with its
        clock restarted at eviction.

        ``guard`` (train/preemption.PreemptionGuard or anything with a
        ``should_stop`` flag) wires SIGTERM into a graceful drain:
        admission stops (un-admitted work is ``shed``), in-flight
        sequences finish within ``serve.drain_ms`` (None = no budget),
        and whatever the budget cuts off terminates as ``drained``.
        ``journal`` (serving/recovery.ReplayJournal) records each
        request's prompt + generated prefix so a replacement process can
        replay live sequences token-identically.
        ``advisor`` (serving/autoscale.ScaleAdvisor) observes the
        scheduler's queue-depth / occupancy / shed-rate signals once
        per iteration; its advisory decision log rides the result as
        the ``autoscale`` block (None when no advisor is attached).

        The result dict carries per-request terminal ``statuses``, the
        ``faults`` health-counter block, and the ``drain`` outcome next
        to the existing throughput/latency numbers.
        """
        from mpi_tensorflow_tpu.serving.iteration import (DrainTracker,
                                                          EngineLoop)

        serve = self.serve
        # the shared per-iteration body (serving/iteration): submit
        # stamping, journal wiring (terminal routing runs through the
        # engine's chained _on_terminal hook, already installed on the
        # scheduler at reset()), latency cadence, eviction discard —
        # ONE implementation, also driven per-replica by the router
        loop = EngineLoop(self, journal)
        drain = DrainTracker(serve.drain_ms)
        pending = sorted(requests, key=lambda r: r.arrival)
        t0 = time_fn()
        while pending or not self.all_done():
            now = time_fn() - t0
            if guard is not None and guard.should_stop \
                    and not drain.draining:
                # graceful drain: stop admission, shed everything not in
                # flight, let live sequences finish inside the budget
                drain.start(now, len(self.sched.finished))
                drain.shed = len(pending)
                for req in pending:
                    self.sched.fail_request(req, "shed")
                pending = []
                drain.shed += self.sched.shed_waiting()
            if drain.expired(now):
                # budget's hard edge: cut whatever is still in flight
                self.sched.abort_live("drained")
                break
            while pending and pending[0].arrival <= now:
                loop.submit(pending.pop(0))
            # deadline sweep + step + emit/evict accounting; step()
            # journals each token at emission, BEFORE the terminal hook
            # can fire — the durable order is tok-then-end, so an
            # end-ok can never precede its own finishing token
            emitted = loop.iterate(now, time_fn, t0)
            now = time_fn() - t0
            if advisor is not None:
                if self.tracer is not None \
                        and self.tracer.last_step is not None:
                    # with tracing on the advisor consumes the SAME
                    # step record the TraceBuffer holds, so its advice
                    # is explainable from the trace (ROADMAP item 2)
                    advisor.observe_step(self.tracer.last_step)
                else:
                    advisor.observe(now, **self.load_signals())
            if not emitted and not self._progressed:
                # no work moved this iteration (idle gap before the next
                # arrival, or live-but-stalled slots): sleep instead of
                # busy-spinning a host core at 100%
                delay = 1e-3
                if pending:
                    delay = min(delay, max(0.0, pending[0].arrival - now))
                if delay > 0:
                    time.sleep(delay)
        elapsed = time_fn() - t0
        # a drain cut leaves its last dispatch unread: every row of it
        # belongs to a sequence that was just cut, so reading it only
        # counts them
        self._deliver(len(self._unread))
        # pool-leak invariant: every terminal request released its
        # blocks; only the prefix trie's own references may remain —
        # and the draft pool (every request terminal => every draft
        # state released by the terminal hook) must have drained too
        self.sched.check_quiescent()
        if self.drafter is not None:
            self.drafter.check_quiescent()
        outputs = {s.request.id: list(s.generated)
                   for s in self.sched.finished}
        total = sum(len(v) for v in outputs.values())
        flat = loop.latencies()
        lat = np.asarray(flat) if flat else np.zeros(1)
        from mpi_tensorflow_tpu.utils.metrics_writer import faults_block

        res = {
            "outputs": outputs,
            "statuses": dict(self.sched.statuses),
            "faults": faults_block(self.sched.counters),
            "drain": drain.result(len(self.sched.finished),
                                  self.sched.counters["drained"]),
            "kernel": self.kernel,
            "prefix": self.prefix_block(),
            "speculation": self.speculation_block(),
            "tier": self.tier_block(),
            "moe": self.moe_block(),
            "peak_blocks_in_use": self.peak_blocks_in_use,
            "peak_live_blocks": self.peak_live_blocks,
            "tokens": total,
            "elapsed_s": elapsed,
            "tokens_per_sec": total / elapsed if elapsed > 0 else 0.0,
            "p50_token_latency_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_token_latency_ms": float(np.percentile(lat, 99)) * 1e3,
            "evictions": self.sched.evictions,
            "dispatch_shapes": sorted(self.dispatch_shapes),
            # model-forward dispatch economy: mixed batching's win is
            # fewer dispatches per emitted token (one fused forward per
            # step vs prefill + decode), measurable on any backend
            "forward_dispatches": self.forward_dispatches,
            "dispatches_per_token": (self.forward_dispatches
                                     / max(1, total)),
            # decode dispatches: grid steps of live (row, group of
            # blocks) pairs beside the bound they were cut from, and
            # the blocks those steps copied beside the live ones
            "paged_grid_steps": self.paged_grid_steps,
            "paged_grid_bound": self.paged_grid_bound,
            "paged_live_blocks": self.paged_live_blocks,
            "paged_blocks_fetched": self.paged_blocks_fetched,
            # the one-step lookahead: forward dispatches issued while an
            # earlier one's tokens were unread, and rows computed for a
            # sequence that had left its slot by the time they were read
            "lookahead_dispatches": self.lookahead_dispatches,
            "lookahead_discarded_rows": self.lookahead_discarded_rows,
            # dispatch assembly: Python-path puts (0 on the plain path),
            # table rows rewritten against rows dispatched
            **self._assembly_counts(),
            "caches": self.cache_block(),
            # final-token emit time per request on the run clock (the
            # same clock as Request.arrival): attained whole-request
            # latency = finish - arrival (serving/loadgen goodput join)
            "request_finish_s": dict(loop.last_emit),
            # FIRST-token emit time per request on the same clock:
            # TTFT = first - arrival (the headline latency mixed
            # batching moves; serving/loadgen joins it as ttft_ms)
            "request_first_token_s": dict(loop.first_emit),
            "autoscale": (advisor.report() if advisor is not None
                          else None),
        }
        if self.tracer is not None:
            # the `trace` key exists ONLY with tracing on: the off-path
            # result dict is byte-for-byte the untraced one
            h = self.tracer.harvest(elapsed)
            res["trace"] = {
                "enabled": True,
                "replicas": [{"pid": 0, "label": "engine", **h}],
                "spans": h["spans"],
                "steps": len(h["steps"]),
                "steps_dropped": h["steps_dropped"],
            }
        return res

    def _log_dispatch(self, kind: str, rows: int, attended: int,
                      starts, counts, taken: int) -> list:
        """Traced runs only: one record per model dispatch in the
        process-wide registry (utils/dispatch_log) — when, what, how many
        rows (decode) or chunk tokens (prefill), and how many cached
        tokens its queries attended, from the scheduler's host state;
        and, where the model says what else a dispatch obliges of its
        caches (``dispatch_extra``: each row's position before the
        dispatch, its real tokens, the lanes whose logits were taken),
        that too.
        The routed experts' share of the record is what the model's
        device counters read right after THIS dispatch: a snapshot of
        their few dozen bytes is taken on the device here (the next
        dispatch donates the leaves themselves) and read by
        ``_read_counters`` once the dispatch's tokens are."""
        from mpi_tensorflow_tpu.utils import dispatch_log

        self.tracer.enter("serve.trace")
        extra = getattr(self.model, "dispatch_extra", None)
        rec = dispatch_log.record(
            time.perf_counter(), kind, rows, attended,
            extra(kind, starts, counts, taken) if extra else None)
        if self._counters is not None:
            self._snapshots.append(
                (rec, self._sum_fn(self._counter_leaves())))
        self.tracer.exit()
        return rec

    def _counter_leaves(self) -> list:
        """The model's device counters as they stand in ``self.pools``
        (the last dispatch's output), one leaf a layer that has one."""
        return [leaf for p in self.pools for key, leaf in p.items()
                if paged_cache.is_counter(key)]

    def _read_counters(self, upto: list) -> None:
        """Traced runs only, and only once the tokens of the dispatch
        that ``upto`` records are on the host (every snapshot taken up
        to it is then a ready buffer of a few dozen bytes): give each
        dispatch logged so far its own share — what its snapshot reads
        over the one before.  The counter keeps decode calls and the
        rest apart."""
        if self._counters is None:
            return
        from mpi_tensorflow_tpu.utils import dispatch_log

        self.tracer.enter("serve.trace")
        tot = None
        while self._snapshots:
            rec, snap = self._snapshots.pop(0)
            tot = np.asarray(snap)  # graft-lint: sync-ok(a ready buffer: taken before the dispatch whose tokens were just read)
            row = (tot - self._counters.get("totals", 0))[
                0 if rec[1] == "decode" else 1]
            rec[4], rec[5] = int(row[:-1].sum()), int(row[-1])
            self._counters["totals"] = tot
            if rec is upto:
                break
        if tot is not None:
            dispatch_log.set_totals(tot[:, :-1].sum(axis=0).tolist())
            self.tracer.moe = self.moe_block(tot)
        self.tracer.exit()

    def _counter_totals(self):
        """The counter leaves summed over layers, on the host (a sync
        that waits for whatever dispatch is in flight)."""
        return np.sum([leaf for layer in paged_cache.read_counters(
            self.pools) for leaf in layer.values()], axis=0)

    def moe_block(self, totals=None) -> dict:
        """Routed-expert load accounting, like ``prefix_block``: the
        assignments each held expert received, experts touched (summed
        over calls and layers) and the load's max over mean.  Reads the
        device counters (a sync) unless given ``totals``; zero-safe for
        a model that declares none."""
        from mpi_tensorflow_tpu.utils.metrics_writer import moe_block

        if self._counters is None:
            return moe_block()
        if totals is None:
            totals = self._counter_totals()
        return moe_block(enabled=True,
                         per_expert=totals[:, :-1].sum(axis=0).tolist(),
                         experts_touched=int(totals[:, -1].sum()))

    def load_signals(self) -> dict:
        """Instantaneous load signals for autoscale advice
        (serving/autoscale.ScaleAdvisor.observe) — the same ingredients
        as the router's least-load placement score: waiting-queue
        depth, live-slot fraction, pool occupancy (block 0 is the
        reserved null block), and the shed fraction of requests seen."""
        live = len(self.sched.live_slots())
        waiting = len(self.sched.waiting)
        seen = max(1, waiting + live + len(self.sched.statuses))
        return {
            "queue_depth": waiting,
            "live_fraction": live / self.serve.max_slots,
            "occupancy": (self.allocator.num_used
                          / max(1, self.serve.num_blocks - 1)),
            "shed_rate": self.sched.counters["shed"] / seen,
            # admitted-but-unprefilled prompt tokens, in prefill-chunk
            # units (~ pending prefill dispatches): queue depth alone
            # misses head-of-line work already holding slots but not
            # yet serving (Scheduler.prefill_backlog_tokens)
            "prefill_backlog": (self.sched.prefill_backlog_tokens
                                / max(1, self.serve.prefill_chunk)),
            # running counts, not load (the advisor passes them by):
            # with ``forward_dispatches`` a step record says how much of
            # the host's work the lookahead hid, and at what waste
            "forward_dispatches": self.forward_dispatches,
            "lookahead_dispatches": self.lookahead_dispatches,
            "lookahead_discarded_rows": self.lookahead_discarded_rows,
            **self._assembly_counts(),
            # the decode kernel's grid steps beside their bound, the live
            # blocks they were obliged to read and the blocks they copied
            "paged_grid_steps": self.paged_grid_steps,
            "paged_grid_bound": self.paged_grid_bound,
            "paged_live_blocks": self.paged_live_blocks,
            "paged_blocks_fetched": self.paged_blocks_fetched,
            # the caches by kind (static sizes; ``occupancy`` above is
            # the paged pool's) and the prefill chunks that started a
            # slot's state from zero
            "cache_bytes": dict(self._cache_bytes),
            "state_resets": int(self.state_resets),
        }

    def _assembly_counts(self) -> dict:
        """How the dispatches were assembled (see reset)."""
        return {"host_array_puts": self.host_array_puts,
                "table_rows_written": self.sched.table_rows_written,
                "table_rows_dispatched": self.table_rows_dispatched}

    def cache_block(self) -> dict:
        """Bytes the device holds of each kind of cache (``paged`` block
        leaves, per-slot ``window`` rings, other per-slot ``state``:
        serving/paged_cache.cache_kind), how full the paged pool is, and
        how often a slot's state was started from zero."""
        return {"cache_bytes": dict(self._cache_bytes),
                "pool_occupancy": (self.allocator.num_used
                                   / max(1, self.serve.num_blocks - 1)),
                "state_resets": int(self.state_resets)}

    def prefix_block(self) -> dict:
        """Canonical prefix-cache accounting block for this engine's
        run (utils/metrics_writer.prefix_block — the ONE constructor
        engine results, the recovery supervisor, and the entry point's JSON
        share)."""
        from mpi_tensorflow_tpu.utils.metrics_writer import prefix_block

        return prefix_block(
            self.sched.counters,
            enabled=self.prefix_cache is not None,
            trie_blocks=(self.prefix_cache.num_blocks
                         if self.prefix_cache is not None else 0))

    def speculation_block(self) -> dict:
        """Canonical speculative-decoding accounting block
        (utils/metrics_writer.speculation_block — shared with the
        recovery supervisor's cross-attempt merge and the entry point)."""
        from mpi_tensorflow_tpu.utils.metrics_writer import \
            speculation_block

        return speculation_block(
            self.sched.counters, enabled=self.drafter is not None,
            mode=self.serve.speculative, draft_k=self.serve.draft_k,
            draft_auto=self.serve.draft_auto)

    def tier_block(self) -> dict:
        """Canonical host-tier accounting block
        (utils/metrics_writer.tier_block — the ONE constructor engine
        results and the entry point share); zero-safe with tiering off."""
        from mpi_tensorflow_tpu.utils.metrics_writer import tier_block

        if self.tier is None:
            return tier_block()
        s = self.tier.stats()
        return tier_block(
            enabled=True, mode=self.serve.kv_tier,
            demotions=s["demotions"], promotions=s["promotions"],
            host_blocks=s["host_blocks"],
            host_blocks_peak=s["host_blocks_peak"],
            promote_ms_total=s["promote_ms_total"],
            block_size=self.serve.block_size)

    def compile_counts(self) -> dict:
        """Live jit-cache entry counts — THE zero-recompile probe: a
        steady-state serving window must not grow either number.  A
        count of ``None`` means the probe API is unavailable on this
        jax; consumers must treat that as UNKNOWN, never as "no
        recompiles" (two Nones comparing equal would make the verdict
        vacuously true)."""
        def size(fn):
            try:
                return int(fn._cache_size())
            except Exception:
                return None
        out = {"decode": size(self._decode_fn),
               "prefill": size(self._prefill_fn),
               "cow": size(self._cow_fn),
               "partial": size(self._partial_fn),
               "verify": size(self._verify_fn),
               "mixed": size(self._mixed_fn),
               "promote": size(self._promote_fn),
               # the lookahead's slot-token programs, all built with the
               # engine: a window must not grow them either
               "slot_take": size(self._take_fn),
               "slot_keep": size(self._keep_fn)}
        if self.drafter is not None:
            # a drafter's own jitted dispatches are inside the steady-
            # state loop too — the contract covers them like the
            # engine's (Drafter.compile_counts; {} for host-only ones)
            out.update(self.drafter.compile_counts())
        return out
