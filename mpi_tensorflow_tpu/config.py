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
                                  # throughput knob; parameter INIT always
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
