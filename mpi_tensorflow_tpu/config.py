"""Run configuration.

The reference has no config system — four module-level constants at
mpipy.py:18-21 (``iteration = 2``, ``image_size = 28``, ``batch_size = 64``,
``num_channel = 10`` — the last is the class count, misnamed) and zero CLI
flags.  Zero-flag invocation of our CLI must reproduce those defaults
(BASELINE.json: "Keep the script's original CLI"), so every default below
matches the reference.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Config:
    # --- reference knobs (mpipy.py:18-21) ---
    epochs: int = 2               # ``iteration`` at mpipy.py:18
    image_size: int = 28          # mpipy.py:19
    batch_size: int = 64          # global batch; per-shard = batch_size in the
                                  # reference (each rank steps its own batch of
                                  # 64, mpipy.py:80-82). ``scale_batch`` below
                                  # controls which semantics we reproduce.
    num_classes: int = 10         # ``num_channel`` at mpipy.py:21 (misnamed)

    # --- optimizer / schedule (mpipy.py:55-66) ---
    base_lr: float = 0.01
    lr_decay: float = 0.95
    momentum: float = 0.9
    weight_decay: float = 5e-4    # L2 on fc params only (mpipy.py:57-58)

    # --- loop / reporting (mpipy.py:87-90) ---
    log_every: int = 50           # 50-step console cadence
    eval_every: int = 50          # reference evaluates EVERY step
                                  # (mpipy.py:86) — an accidental cost; we
                                  # evaluate on the log cadence and keep it off
                                  # the timed path
    early_stop_patience: int = 0  # >0: stop when validation error hasn't
                                  # improved for N trace points.  The
                                  # reference scatters validation shards and
                                  # never reads them (mpipy.py:236-241, dead
                                  # data); 0 keeps that faithful default,
                                  # >0 puts the split to work

    # --- parallelism ---
    sync: str = "psum"            # "psum": per-step gradient summation (the
                                  # north-star semantics) | "avg50": periodic
                                  # parameter averaging, the reference's
                                  # strategy (mpipy.py:95-153) with the rank-0-
                                  # only bug fixed (all ranks receive the mean)
    fused_steps: int = 1          # steps executed per device dispatch in the
                                  # psum loop (lax.scan over staged batches,
                                  # train/step.py make_multi_train_step).
                                  # 1 = one dispatch per step (the
                                  # reference's execution shape); the CLI
                                  # defaults to the 50-step trace cadence on
                                  # TPU, where dispatch latency dominates
                                  # tiny steps
    remat: bool = False           # transformer-layer rematerialization
                                  # (jax.checkpoint): recompute activations
                                  # in the backward pass to cut peak HBM
    text_file: Optional[str] = None  # real-text corpus for the LM families
                                  # (data/corpus.py); None = synthetic
    vocab_file: Optional[str] = None  # WordPiece vocab (one token/line,
                                  # BERT vocab.txt layout) for --text-file
                                  # runs: real-vocab training exercises the
                                  # packed/chunked MLM head at flagship
                                  # vocab size; None = byte-level (261)
    param_sharding: str = "replicated"  # transformer-family state layout:
                                  # "replicated" (pure DP/TP/PP rules),
                                  # "fsdp" (params+moments data-sharded,
                                  # ZeRO-3-style via GSPMD), or "zero1"
                                  # (params keep their layout, moments
                                  # data-sharded — composes with PP)
    prefetch: str = "auto"        # window-assembly prefetch for the fused
                                  # loop: "auto" (native C++ worker when
                                  # built, else Python thread), "native",
                                  # "thread", "off" (inline assembly)
    pp_schedule: str = "gpipe"    # pipeline-parallel training schedule:
                                  # "gpipe" (scanned fwd pipeline, autodiff
                                  # backward), "1f1b" (one-forward-one-
                                  # backward — same bubble, O(P) stash), or
                                  # "1f1b_interleaved" (v virtual chunks
                                  # per device: bubble / v, 2P-deep rings)
    virtual_stages: int = 2       # chunks/device for "1f1b_interleaved"
    grad_accum: int = 1           # microbatches per step: grads accumulate
                                  # on-device (lax.scan) before the single
                                  # allreduce+update — same semantics, 1/A
                                  # the activation memory
    scale_batch: bool = True      # True: per-device batch = batch_size, i.e.
                                  # global batch grows with the mesh — the
                                  # reference's behavior (each rank independently
                                  # slices 64 rows, mpipy.py:80-82)
    mesh_shape: Optional[dict] = None  # e.g. {"data": 8}; None = all devices
                                       # on one "data" axis

    # --- serving (continuous-batching decode engine, serving/) ---
    serve_pool_blocks: int = 128  # paged-KV pool size in blocks (block 0
                                  # reserved as the null/scratch block);
                                  # HBM cost = blocks * block_size * 2KV
                                  # * heads * head_dim * layers * dtype
    serve_block_size: int = 16    # cache entries per pool block
    serve_max_slots: int = 8      # concurrent sequences (decode batch cap)
    serve_max_seq_len: int = 512  # per-request prompt+output cap; also
                                  # sizes the per-sequence block table
    serve_kernel: str = "auto"    # paged-attention lowering: auto (fused
                                  # Pallas kernel on TPU when the compile
                                  # probe passes, else XLA gather), xla
                                  # (force the exact gather fallback),
                                  # pallas (force the kernel; interpret
                                  # mode off TPU — the test path)
    serve_kv_dtype: str = "fp32"  # paged-pool storage format: "fp32"
                                  # (blocks in the model compute dtype —
                                  # byte-for-byte the pre-quantization
                                  # behavior) | "int8" (symmetric-absmax
                                  # codes + per-(block, head, slot) fp32
                                  # row scales: ~4x effective KV
                                  # capacity, dequantized inside the
                                  # attention consume paths; greedy
                                  # outputs track fp32 at a token-match-
                                  # rate gate, not token identity) |
                                  # "int4" (two nibble-packed codes per
                                  # byte + per-group fp32 scales along
                                  # head_dim + a KIVI fp-residual self
                                  # lane: the next capacity rung, same
                                  # token-match-rate gate)
    serve_kv_group: int = 32      # int4 scale-group size along head_dim
                                  # (one fp32 scale per group; clamped
                                  # to head_dim on tiny heads, must
                                  # divide it).  Consumed only under
                                  # serve_kv_dtype=int4
    serve_kv_tier: str = "off"    # host-RAM block tier: "host" demotes
                                  # cold prefix-cache blocks to host
                                  # memory on eviction and promotes
                                  # them back into fresh device blocks
                                  # when a later prompt matches their
                                  # trie path (multi-turn sessions stop
                                  # re-paying prefill); requires
                                  # serve_prefix_cache=on; "off" is
                                  # byte-for-byte untiered
    serve_prefix_cache: str = "off"  # radix prefix cache: "on" shares
                                  # already-cached full prompt blocks
                                  # across requests (refcounted, copy-
                                  # on-write, LRU trie eviction under
                                  # pool pressure); "off" preserves the
                                  # unshared behavior byte-for-byte
    serve_prefix_gen: str = "off"  # prefix cache v2 extensions: "on"
                                  # additionally caches a finished
                                  # request's GENERATED full blocks in
                                  # the trie (follow-up turns that embed
                                  # the prior answer hit them) and
                                  # shares partial tail blocks via a
                                  # one-compile row-prefix copy; "off"
                                  # keeps prefix_cache=on behavior
                                  # byte-for-byte; requires
                                  # serve_prefix_cache=on
    serve_prefix_route: str = "off"  # prefix-aware fleet routing: "on"
                                  # biases sessionless placement toward
                                  # the replica whose trie caches the
                                  # prompt's leading full block (load-
                                  # bounded, never overrides the health
                                  # gate, never changes tokens); "off"
                                  # keeps affinity+least-load routing;
                                  # requires serve_prefix_cache=on
    serve_speculative: str = "off"  # speculative decoding: "ngram"
                                  # (n-gram self-draft, zero extra
                                  # model), "draft-model" (tiny-model
                                  # drafter over its own paged pool);
                                  # drafts verify in ONE batched
                                  # forward and only the argmax-
                                  # matching prefix is emitted, so
                                  # greedy outputs are token-identical
                                  # to "off" (which preserves the one-
                                  # token decode loop byte-for-byte)
    serve_draft_k: int = 4        # draft window: tokens proposed per
                                  # verify forward (dispatch width is
                                  # draft_k + 1)
    serve_draft_auto: str = "off"  # auto-tune the draft window: "on"
                                  # adapts the effective k to an EWMA
                                  # of the observed accepted length,
                                  # clamped to [1, serve_draft_k] (the
                                  # dispatch width never changes, so
                                  # no recompiles); "off" drafts the
                                  # configured k every step
    serve_mixed_batch: str = "off"  # stall-free mixed batching: "on"
                                  # fuses budget-capped prefill chunks
                                  # from MULTIPLE mid-prefill sequences
                                  # into the decode dispatch, so every
                                  # step is ONE forward (chunked-prefill
                                  # math; decode is the chunk=1 case)
                                  # — lower dispatches per emitted
                                  # token and lower TTFT under bursty
                                  # admission; "off" preserves the
                                  # two-dispatch prefill-then-decode
                                  # loop byte-for-byte
    serve_prefill_budget: int = 64  # mixed batching: max prefill
                                  # tokens fused into one step across
                                  # all mid-prefill sequences; bounds
                                  # the decode-latency tax a step can
                                  # pay for prompt ingestion (consumed
                                  # only with serve_mixed_batch=on)
    serve_tp: int = 1             # tensor-parallel shards for the
                                  # decode engine: >1 partitions the
                                  # paged pool's head axis, the QKV/O
                                  # projections, and the MLP over a
                                  # ``tp`` mesh axis (serving/tp) with
                                  # one psum per row-parallel output;
                                  # must divide the model's heads and
                                  # mlp dims and fit the device count
    serve_replicas: int = 1       # data-parallel engine replicas
                                  # fronted by serving/router: each has
                                  # its own pool/scheduler; requests
                                  # place by session affinity then
                                  # least-load (queue depth, occupancy,
                                  # shed rate).  1 = no router layer
    # fault-tolerance policy (serving/engine.ServeConfig; None = off)
    serve_deadline_ms: Optional[float] = None  # default per-request TTL
                                  # from arrival; expired work fails
                                  # with deadline_exceeded instead of
                                  # occupying a slot
    serve_queue_depth: Optional[int] = None    # bound on the waiting
                                  # queue; a full queue load-sheds the
                                  # newest submit (backpressure)
    serve_max_evictions: Optional[int] = None  # preemption-livelock
                                  # guard: a request evicted more than
                                  # this many times fails with
                                  # evicted_too_often
    serve_drain_ms: Optional[float] = None     # graceful-drain budget
                                  # after SIGTERM; in-flight work past
                                  # it is cut with status `drained`
                                  # (None = finish all in-flight)
    serve_failover_backoff_ms: float = 50.0    # replica circuit
                                  # breaker (serving/router): base
                                  # probe backoff after a transient
                                  # replica fault, doubled per
                                  # consecutive fault and capped at
                                  # 64x before the replica is rebuilt
                                  # and probed back into rotation
    serve_workload: str = "poisson"  # synthetic trace shape for bench
                                  # --mode serving (serving/loadgen):
                                  # poisson | bursty | multi-tenant |
                                  # diurnal; poisson replays the
                                  # historical trace byte-for-byte
    serve_slo_ms: Optional[float] = None       # per-request latency
                                  # budget stamped as Request.deadline;
                                  # the goodput metric (tokens/sec
                                  # within budget) keys on it (None =
                                  # no SLO)
    serve_trace: str = "off"      # request-lifecycle + step-phase
                                  # tracing (serving/tracing): off | on.
                                  # off = byte-for-byte untraced
                                  # behavior; on adds host-side span
                                  # stamps (zero device syncs) and the
                                  # `breakdown` block in bench detail
    serve_trace_out: Optional[str] = None      # Chrome trace-event JSON
                                  # path (open in Perfetto or
                                  # chrome://tracing); requires
                                  # serve_trace=on

    # --- checkpointing (absent from the reference; SURVEY.md §5) ---
    checkpoint_dir: Optional[str] = None   # None = checkpointing off
    resume: bool = False                   # resume from latest in the dir

    # --- metrics sink (SURVEY.md §5 metrics row; the reference has only
    #     the stdout trace, mpipy.py:88) ---
    metrics_dir: Optional[str] = None      # TensorBoard events + JSONL here

    # --- precision (TPU-first: bf16 on the MXU, fp32 master params) ---
    precision: str = "fp32"       # "fp32" | "bf16": compute dtype for the
                                  # forward/backward matmuls+convs; parameters,
                                  # optimizer state and loss stay float32.
                                  # fp32 default keeps bit-level comparability
                                  # with the reference (mpipy.py is float32
                                  # throughout)

    optimizer: str = "adamw"      # transformer-family optimizer: "adamw"
                                  # | "lamb" (layer-wise trust ratios, the
                                  # large-batch BERT recipe — You et al.
                                  # 2019).  The image families keep the
                                  # reference's momentum SGD (mpipy.py:65)

    # --- misc ---
    prng_impl: str = "threefry"   # PRNG for the training rng stream
                                  # (dropout masks): "threefry" (JAX default,
                                  # splittable, bit-reproducible across
                                  # backends) | "rbg" | "unsafe_rbg" (XLA
                                  # RngBitGenerator — far cheaper mask
                                  # generation on TPU; rbg keys also shard
                                  # cleanly under GSPMD).  A BERT train step
                                  # runs 25 (B,S,E) mask generations, so the
                                  # generator choice is a first-order
                                  # throughput knob (scripts/bert_diagnose.py
                                  # measures the delta); parameter INIT always
                                  # uses threefry so init is bit-identical
                                  # across prng arms
    seed: int = 1                 # the reference seeds everything with 1
                                  # (mpipy.py:40, 43, 48, 52, 166)
    dropout_rate: float = 0.5     # mpipy.py:166
    data_dir: str = "./data"      # mpipy.py:187
    model: str = "mnist_cnn"      # flagship families: mnist_cnn, resnet20,
                                  # resnet50, bert_base, moe_bert
    dataset: str = "mnist"

    @property
    def num_channels(self) -> int:
        """Input channels (1 for MNIST)."""
        return 1

    def make_train_key(self, seed: int):
        """Training rng stream keyed per ``prng_impl``.  The impl travels
        with the key through every ``fold_in`` inside the jitted step, so
        this one call site decides the dropout-mask generator."""
        import jax

        impl = {"threefry": "threefry2x32"}.get(self.prng_impl,
                                                self.prng_impl)
        return jax.random.key(seed, impl=impl)

    @property
    def compute_dtype(self):
        """The jnp dtype the forward/backward matmuls run in."""
        import jax.numpy as jnp

        if self.precision == "bf16":
            return jnp.bfloat16
        if self.precision == "fp32":
            return jnp.float32
        raise ValueError(f"unknown precision {self.precision!r}")
