"""Command-line entry point.

Zero-flag invocation reproduces the reference's hard-wired defaults
(``iteration = 2``, ``batch_size = 64``, ``image_size = 28``, 10 classes —
mpipy.py:18-21) scaled transparently from one chip to a pod slice; every
constant is also exposed as a flag, which the reference lacks entirely
(SURVEY.md §5 config row).

    python -m mpi_tensorflow_tpu                 # the `mpiexec -n N python
                                                 # mpipy.py` equivalent
    python -m mpi_tensorflow_tpu --sync avg50    # reference-fidelity sync
    python -m mpi_tensorflow_tpu --model resnet20 --dataset cifar10
"""

from __future__ import annotations

import argparse

from mpi_tensorflow_tpu.config import Config


TRANSFORMER_MODELS = ("bert_base", "moe_bert", "gpt_base", "encdec_t5")

def build_parser() -> argparse.ArgumentParser:
    d = Config()
    p = argparse.ArgumentParser(
        prog="mpi_tensorflow_tpu",
        description="TPU-native data-parallel trainer "
                    "(capabilities of youzhenfei1995/mpi-Tensorflow)")
    p.add_argument("--epochs", type=int, default=d.epochs,
                   help="the reference's `iteration` (mpipy.py:18)")
    p.add_argument("--batch-size", type=int, default=d.batch_size,
                   help="per-shard batch size (mpipy.py:20)")
    p.add_argument("--image-size", type=int, default=d.image_size)
    p.add_argument("--num-classes", type=int, default=d.num_classes,
                   help="the reference's misnamed `num_channel` (mpipy.py:21)")
    p.add_argument("--base-lr", type=float, default=d.base_lr)
    p.add_argument("--lr-decay", type=float, default=d.lr_decay)
    p.add_argument("--momentum", type=float, default=d.momentum)
    p.add_argument("--weight-decay", type=float, default=d.weight_decay)
    p.add_argument("--log-every", type=int, default=d.log_every)
    p.add_argument("--early-stop-patience", type=int,
                   default=d.early_stop_patience,
                   help="stop when validation error hasn't improved for N "
                        "trace points (0 = off, the reference's behavior — "
                        "it scatters validation shards and never reads "
                        "them, mpipy.py:236-241)")
    p.add_argument("--sync", choices=["psum", "avg50"], default=d.sync,
                   help="psum: per-step gradient allreduce (sync SGD); "
                        "avg50: the reference's periodic parameter averaging "
                        "with its rank-0-only bug fixed")
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--data-dir", default=d.data_dir)
    p.add_argument("--model", default=d.model,
                   choices=["mnist_cnn", "resnet20", "resnet50", "vit",
                            "bert_base", "moe_bert", "gpt_base",
                            "encdec_t5"])
    p.add_argument("--dataset", default=d.dataset,
                   choices=["mnist", "cifar10", "imagenet_synthetic",
                            "mlm_synthetic"])
    p.add_argument("--checkpoint-dir", default=None,
                   help="save train state here at the log cadence")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --checkpoint-dir")
    p.add_argument("--mesh", default=None,
                   help="mesh spec, e.g. 'data=8' or 'data=4,model=2'; "
                        "default: all devices on one data axis")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace here")
    p.add_argument("--metrics-dir", default=None,
                   help="stream scalar metrics here: TensorBoard event "
                        "files (when tensorboardX is available) plus a "
                        "metrics.jsonl that needs no reader dependency")
    p.add_argument("--fused-steps", type=int, default=None,
                   help="train steps per device dispatch (lax.scan). "
                        "Default: the --log-every cadence for psum mode "
                        "(one dispatch per trace window), 1 for avg50. "
                        "Pass 1 for the reference's one-dispatch-per-step "
                        "shape")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize transformer layers (jax.checkpoint): "
                        "trade recompute FLOPs for peak activation HBM")
    p.add_argument("--text-file", default=None,
                   help="train the LM families on a local text file "
                        "(data/corpus.py) instead of the synthetic stream")
    p.add_argument("--vocab-file", default=None,
                   help="WordPiece vocabulary for --text-file (one token "
                        "per line, BERT vocab.txt layout); default: "
                        "self-contained byte-level tokenizer (vocab 261)")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="elastic recovery: restart from the latest "
                        "checkpoint after transient infrastructure "
                        "failures (train/elastic.py; pair with "
                        "--checkpoint-dir)")
    p.add_argument("--param-sharding",
                   choices=["replicated", "fsdp", "zero1"],
                   default=d.param_sharding,
                   help="transformer-family state layout: replicated "
                        "(default), fsdp (params+moments sharded over "
                        "'data', ZeRO-3-style), or zero1 (optimizer "
                        "moments sharded, params keep their layout — "
                        "composes with pipe meshes)")
    p.add_argument("--prefetch", choices=["auto", "native", "thread", "off"],
                   default=d.prefetch,
                   help="background window assembly for the fused loop "
                        "(native = C++ worker, data/prefetch.py)")
    p.add_argument("--pp-schedule",
                   choices=["gpipe", "1f1b", "1f1b_interleaved"],
                   default=d.pp_schedule,
                   help="pipeline schedule for --mesh pipe=N runs: gpipe "
                        "(autodiff backward), 1f1b (one-forward-one-"
                        "backward; same bubble, O(P) activation stash), "
                        "or 1f1b_interleaved (--virtual-stages chunks per "
                        "device; bubble shrinks ~v-fold)")
    p.add_argument("--virtual-stages", type=int, default=d.virtual_stages,
                   help="virtual chunks per device for "
                        "--pp-schedule 1f1b_interleaved")
    p.add_argument("--grad-accum", type=int, default=d.grad_accum,
                   help="microbatches accumulated per optimizer step "
                        "(activation-memory / batch-size trade)")
    p.add_argument("--precision", choices=["fp32", "bf16"], default=d.precision,
                   help="compute dtype for matmuls/convs (bf16 doubles MXU "
                        "throughput; params and loss stay fp32)")
    p.add_argument("--optimizer", choices=["adamw", "lamb"],
                   default=d.optimizer,
                   help="transformer-family optimizer (lamb = layer-wise "
                        "trust ratios, the large-batch BERT recipe); the "
                        "image families keep the reference's momentum SGD")
    p.add_argument("--serve-pool-blocks", type=int,
                   default=d.serve_pool_blocks,
                   help="serving: paged KV pool size in blocks (block 0 "
                        "reserved as the null block; serving/paged_cache)")
    p.add_argument("--serve-block-size", type=int,
                   default=d.serve_block_size,
                   help="serving: cache entries per pool block")
    p.add_argument("--serve-max-slots", type=int,
                   default=d.serve_max_slots,
                   help="serving: concurrent sequences (continuous-"
                        "batching decode batch cap)")
    p.add_argument("--serve-max-seq-len", type=int,
                   default=d.serve_max_seq_len,
                   help="serving: per-request prompt+output cap (sizes "
                        "the per-sequence block table)")
    p.add_argument("--serve-kernel", choices=["auto", "xla", "pallas"],
                   default=d.serve_kernel,
                   help="serving: paged-attention lowering — auto picks "
                        "the fused Pallas decode kernel on TPU when its "
                        "compile probe passes and the XLA gather path "
                        "otherwise; xla/pallas force one side "
                        "(ops/paged_attention.resolve_kernel)")
    p.add_argument("--serve-kv-dtype", choices=["fp32", "int8", "int4"],
                   default=d.serve_kv_dtype,
                   help="serving: paged-pool storage format — fp32 "
                        "keeps the blocks in the model compute dtype "
                        "(byte-for-byte the pre-quantization pool); "
                        "int8 stores symmetric-absmax codes with "
                        "per-(block, head, slot) fp32 row scales "
                        "(~4x effective KV capacity), dequantized "
                        "inside the attention consume paths "
                        "(serving/paged_cache, ops/paged_attention); "
                        "int4 nibble-packs two codes per byte with "
                        "per-group fp32 scales (--serve-kv-group) plus "
                        "a full-precision self lane for each step's "
                        "own tokens — the next capacity rung")
    p.add_argument("--serve-kv-group", type=int, default=d.serve_kv_group,
                   help="serving: int4 scale-group size along head_dim "
                        "— one fp32 scale per group (clamped to "
                        "head_dim on small heads, must divide it); "
                        "smaller groups quantize tighter at more scale "
                        "bytes; consumed only with --serve-kv-dtype "
                        "int4")
    p.add_argument("--serve-kv-tier", choices=["off", "host"],
                   default=d.serve_kv_tier,
                   help="serving: host-RAM KV block tier — host "
                        "demotes cold prefix-cache blocks to host "
                        "memory on eviction and promotes them back "
                        "into fresh device blocks when a later prompt "
                        "matches their trie path, so multi-turn "
                        "sessions stop re-paying prefill; requires "
                        "--serve-prefix-cache on; off is byte-for-byte "
                        "untiered (serving/paged_cache.HostBlockStore)")
    p.add_argument("--serve-prefix-cache", choices=["off", "on"],
                   default=d.serve_prefix_cache,
                   help="serving: radix prefix cache — on shares "
                        "already-cached full prompt blocks across "
                        "requests (refcounted block reuse, copy-on-"
                        "write on divergence, LRU trie eviction under "
                        "pool pressure; serving/prefix_cache); off "
                        "preserves the unshared behavior byte-for-byte")
    p.add_argument("--serve-prefix-gen", choices=["off", "on"],
                   default=d.serve_prefix_gen,
                   help="serving: prefix cache v2 — on additionally "
                        "caches a finished request's generated full "
                        "blocks in the trie (multi-turn reuse) and "
                        "shares partial tail blocks via a one-compile "
                        "row-prefix copy; off keeps "
                        "--serve-prefix-cache on behavior byte-for-"
                        "byte; requires --serve-prefix-cache on")
    p.add_argument("--serve-prefix-route", choices=["off", "on"],
                   default=d.serve_prefix_route,
                   help="serving: prefix-aware fleet routing — on "
                        "biases sessionless placement toward the "
                        "replica whose trie caches the prompt's "
                        "leading full block (load-bounded; never "
                        "overrides the health gate, never changes "
                        "tokens; serving/router); requires "
                        "--serve-prefix-cache on")
    p.add_argument("--serve-speculative",
                   choices=["off", "ngram", "draft-model"],
                   default=d.serve_speculative,
                   help="serving: speculative decoding — ngram drafts "
                        "from the sequence's own earlier tokens, "
                        "draft-model runs a tiny CausalLm over its own "
                        "paged pool; k drafted tokens verify in ONE "
                        "batched forward and only the argmax-matching "
                        "prefix is emitted, so greedy outputs stay "
                        "token-identical to off (the byte-for-byte "
                        "one-token loop; serving/speculative)")
    p.add_argument("--serve-draft-k", type=int, default=d.serve_draft_k,
                   help="serving: speculative draft window — tokens "
                        "proposed per verify forward (dispatch width "
                        "draft_k + 1); >= 1")
    p.add_argument("--serve-draft-auto", choices=["off", "on"],
                   default=d.serve_draft_auto,
                   help="serving: auto-tune the speculative draft "
                        "window — on adapts the effective k to an EWMA "
                        "of the observed accept length, clamped to "
                        "[1, --serve-draft-k] (the verify dispatch "
                        "width never changes, so the zero-recompile "
                        "contract is untouched); needs a drafter "
                        "(--serve-speculative ngram|draft-model)")
    p.add_argument("--serve-mixed-batch", choices=["off", "on"],
                   default=d.serve_mixed_batch,
                   help="serving: stall-free mixed batching — on fuses "
                        "budget-capped prefill chunks from multiple "
                        "mid-prefill sequences into the decode dispatch "
                        "so every step is ONE forward (chunked-prefill "
                        "math, token-identical to off by construction); "
                        "off preserves the two-dispatch prefill-then-"
                        "decode loop byte-for-byte")
    p.add_argument("--serve-prefill-budget", type=int,
                   default=d.serve_prefill_budget,
                   help="serving: mixed-batching budget — max prefill "
                        "tokens fused into one step across all "
                        "mid-prefill sequences (>= 1; consumed only "
                        "with --serve-mixed-batch on)")
    p.add_argument("--serve-tp", type=int, default=d.serve_tp,
                   help="serving: tensor-parallel shards for the decode "
                        "engine — >1 partitions the paged pool's head "
                        "axis, the QKV/O projections, and the MLP over "
                        "a tp mesh axis (serving/tp; one psum per "
                        "row-parallel output, block tables replicated)."
                        " Must divide the model's heads/mlp dims and "
                        "fit the visible device count")
    p.add_argument("--serve-replicas", type=int, default=d.serve_replicas,
                   help="serving: data-parallel engine replicas fronted "
                        "by the serving router (session-affinity "
                        "placement + least-load admission over queue "
                        "depth / pool occupancy / shed rate); each "
                        "replica owns its own pool and scheduler")
    p.add_argument("--serve-deadline-ms", type=float,
                   default=d.serve_deadline_ms,
                   help="serving: default per-request TTL from arrival; "
                        "work not complete by then fails with "
                        "deadline_exceeded instead of occupying a slot "
                        "(default: no deadline)")
    p.add_argument("--serve-queue-depth", type=int,
                   default=d.serve_queue_depth,
                   help="serving: bound on the waiting queue; a full "
                        "queue load-sheds the newest submit with a "
                        "queue_full reason (default: unbounded)")
    p.add_argument("--serve-max-evictions", type=int,
                   default=d.serve_max_evictions,
                   help="serving: a request preempted more than this "
                        "many times fails with evicted_too_often "
                        "instead of requeueing forever (default: "
                        "unbounded)")
    p.add_argument("--serve-failover-backoff-ms", type=float,
                   default=d.serve_failover_backoff_ms,
                   help="serving replica circuit breaker: base probe "
                        "backoff after a transient replica fault "
                        "(doubled per consecutive fault, capped at "
                        "64x) before the router rebuilds and probes "
                        "the replica back in (serving/router)")
    p.add_argument("--serve-drain-ms", type=float,
                   default=d.serve_drain_ms,
                   help="serving: graceful-drain budget after SIGTERM — "
                        "in-flight sequences finish inside it, the rest "
                        "terminate with status `drained` (default: "
                        "finish all in-flight work)")
    p.add_argument("--serve-workload",
                   choices=["poisson", "bursty", "multi-tenant",
                            "diurnal"], default=d.serve_workload,
                   help="serving: synthetic trace shape for bench "
                        "--mode serving (serving/loadgen) — poisson is "
                        "the historical byte-identical default; bursty "
                        "= 2-state MMPP arrivals; multi-tenant adds an "
                        "interactive-vs-batch tenant mix with "
                        "per-tenant SLOs and sticky sessions; diurnal "
                        "= raised-cosine rate envelope")
    p.add_argument("--serve-slo-ms", type=float, default=d.serve_slo_ms,
                   help="serving: per-request latency budget, stamped "
                        "as each request's deadline; the goodput block "
                        "scores tokens/sec from requests that finished "
                        "within it (default: no SLO)")
    p.add_argument("--serve-trace", choices=["off", "on"],
                   default=d.serve_trace,
                   help="serving: request-lifecycle + step-phase "
                        "tracing (serving/tracing) — host-side span "
                        "stamps (zero device syncs) plus the "
                        "`breakdown` latency-attribution block in "
                        "bench detail; off is byte-for-byte the "
                        "untraced behavior")
    p.add_argument("--serve-trace-out", type=str,
                   default=d.serve_trace_out,
                   help="serving: write the run's Chrome trace-event "
                        "JSON here (open in Perfetto or "
                        "chrome://tracing); requires --serve-trace on")
    p.add_argument("--prng", choices=["threefry", "rbg", "unsafe_rbg"],
                   default=d.prng_impl,
                   help="dropout-mask PRNG: threefry (JAX default, "
                        "bit-reproducible) or rbg/unsafe_rbg (XLA "
                        "RngBitGenerator — much cheaper mask generation on "
                        "TPU; a BERT step generates 25 (B,S,E) masks). "
                        "Parameter init always uses threefry")
    return p


def parse_mesh(spec: str | None):
    if spec is None:
        return None
    out = {}
    for part in spec.split(","):
        k, v = part.split("=")
        out[k.strip()] = int(v)
    return out


def config_from_args(args) -> Config:
    return Config(
        epochs=args.epochs, image_size=args.image_size,
        batch_size=args.batch_size, num_classes=args.num_classes,
        base_lr=args.base_lr, lr_decay=args.lr_decay, momentum=args.momentum,
        weight_decay=args.weight_decay, log_every=args.log_every,
        early_stop_patience=args.early_stop_patience,
        sync=args.sync, seed=args.seed, data_dir=args.data_dir,
        model=args.model, dataset=args.dataset,
        mesh_shape=parse_mesh(args.mesh), text_file=args.text_file,
        vocab_file=args.vocab_file,
        checkpoint_dir=args.checkpoint_dir, resume=args.resume,
        metrics_dir=args.metrics_dir,
        precision=args.precision, prng_impl=args.prng,
        optimizer=args.optimizer, grad_accum=args.grad_accum,
        pp_schedule=args.pp_schedule,
        virtual_stages=args.virtual_stages,
        param_sharding=args.param_sharding,
        serve_pool_blocks=args.serve_pool_blocks,
        serve_block_size=args.serve_block_size,
        serve_max_slots=args.serve_max_slots,
        serve_max_seq_len=args.serve_max_seq_len,
        serve_kernel=args.serve_kernel,
        serve_kv_dtype=args.serve_kv_dtype,
        serve_kv_group=args.serve_kv_group,
        serve_kv_tier=args.serve_kv_tier,
        serve_prefix_cache=args.serve_prefix_cache,
        serve_prefix_gen=args.serve_prefix_gen,
        serve_prefix_route=args.serve_prefix_route,
        serve_speculative=args.serve_speculative,
        serve_draft_k=args.serve_draft_k,
        serve_draft_auto=args.serve_draft_auto,
        serve_mixed_batch=args.serve_mixed_batch,
        serve_prefill_budget=args.serve_prefill_budget,
        serve_tp=args.serve_tp,
        serve_replicas=args.serve_replicas,
        serve_deadline_ms=args.serve_deadline_ms,
        serve_queue_depth=args.serve_queue_depth,
        serve_max_evictions=args.serve_max_evictions,
        serve_drain_ms=args.serve_drain_ms,
        serve_failover_backoff_ms=args.serve_failover_backoff_ms,
        serve_workload=args.serve_workload,
        serve_slo_ms=args.serve_slo_ms,
        serve_trace=args.serve_trace,
        serve_trace_out=args.serve_trace_out,
        prefetch=args.prefetch, remat=args.remat,
        fused_steps=(args.fused_steps if args.fused_steps is not None
                     else (args.log_every if args.sync == "psum" else 1)),
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    config = config_from_args(args)

    # flag-combination guards run BEFORE any jax/backend touch (fail fast,
    # no device init on a doomed invocation)
    if args.max_restarts > 0 and not config.checkpoint_dir:
        raise SystemExit(
            "--max-restarts needs --checkpoint-dir: without checkpoints a "
            "restart would silently re-train from step 0")
    if config.text_file and config.model not in ("bert_base", "moe_bert",
                                                 "gpt_base"):
        raise SystemExit(
            f"--text-file applies to the language-model families "
            f"(bert_base, moe_bert, gpt_base); --model {config.model} "
            f"would silently ignore it")
    if config.vocab_file and not config.text_file:
        raise SystemExit("--vocab-file only applies with --text-file")
    if config.optimizer != "adamw" and config.model not in TRANSFORMER_MODELS:
        raise SystemExit(
            f"--optimizer {config.optimizer} applies to the transformer "
            f"families; the image families train with the reference's "
            f"momentum SGD (mpipy.py:65) and would silently ignore it")
    if config.param_sharding != "replicated" and config.model not in TRANSFORMER_MODELS:
        raise SystemExit(
            f"--param-sharding {config.param_sharding} applies to the "
            f"transformer families (GSPMD step); the image loop keeps "
            f"the reference's replicated layout and would silently "
            f"ignore it")
    if args.virtual_stages != Config.virtual_stages \
            and config.pp_schedule != "1f1b_interleaved":
        raise SystemExit(
            f"--virtual-stages {args.virtual_stages} applies only with "
            f"--pp-schedule 1f1b_interleaved; schedule "
            f"{config.pp_schedule!r} would silently ignore it")
    if config.serve_block_size < 1 or config.serve_pool_blocks < 2 \
            or config.serve_max_slots < 1 or config.serve_max_seq_len < 1:
        raise SystemExit(
            f"bad --serve-* geometry: pool-blocks "
            f"{config.serve_pool_blocks} (>= 2; block 0 is reserved), "
            f"block-size {config.serve_block_size} (>= 1), max-slots "
            f"{config.serve_max_slots} (>= 1), max-seq-len "
            f"{config.serve_max_seq_len} (>= 1)")
    if config.serve_kv_dtype not in ("fp32", "int8", "int4"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-kv-dtype {config.serve_kv_dtype!r}: "
            f"must be fp32|int8|int4")
    if config.serve_kv_group < 1:
        raise SystemExit(
            f"bad --serve-kv-group {config.serve_kv_group}: must be "
            f">= 1 (one fp32 scale per group of head_dim channels)")
    if config.serve_kv_tier not in ("off", "host"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-kv-tier {config.serve_kv_tier!r}: "
            f"must be off|host")
    if config.serve_kv_tier == "host" \
            and config.serve_prefix_cache == "off":
        raise SystemExit(
            "--serve-kv-tier host demotes/promotes radix-trie blocks; "
            "with --serve-prefix-cache off there are no trie paths to "
            "key the host store by — turn the cache on or drop the tier")
    if config.serve_prefix_cache not in ("off", "on"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-prefix-cache {config.serve_prefix_cache!r}: "
            f"must be off|on")
    if config.serve_prefix_gen not in ("off", "on"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-prefix-gen {config.serve_prefix_gen!r}: "
            f"must be off|on")
    if config.serve_prefix_route not in ("off", "on"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-prefix-route {config.serve_prefix_route!r}: "
            f"must be off|on")
    if config.serve_prefix_gen == "on" \
            and config.serve_prefix_cache == "off":
        raise SystemExit(
            "--serve-prefix-gen on extends the radix prefix cache; with "
            "--serve-prefix-cache off it would be silently ignored — "
            "turn the cache on or drop it")
    if config.serve_prefix_route == "on" \
            and config.serve_prefix_cache == "off":
        raise SystemExit(
            "--serve-prefix-route on routes by cached prefixes; with "
            "--serve-prefix-cache off there is nothing to route by — "
            "turn the cache on or drop it")
    if config.serve_kernel not in ("auto", "xla", "pallas"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-kernel {config.serve_kernel!r}: "
            f"must be auto|xla|pallas")
    if config.serve_speculative not in ("off", "ngram", "draft-model") \
            or config.serve_draft_k < 1:
        raise SystemExit(
            f"bad --serve-speculative config: mode "
            f"{config.serve_speculative!r} (off|ngram|draft-model), "
            f"draft-k {config.serve_draft_k} (>= 1)")
    if config.serve_draft_auto not in ("off", "on"):
        raise SystemExit(
            f"bad --serve-draft-auto {config.serve_draft_auto!r}: "
            f"must be off|on")
    if config.serve_draft_auto == "on" \
            and config.serve_speculative == "off":
        raise SystemExit(
            "--serve-draft-auto on tunes the speculative draft window; "
            "with --serve-speculative off it would be silently ignored "
            "— pick a drafter or drop it")
    if config.serve_mixed_batch not in ("off", "on"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-mixed-batch {config.serve_mixed_batch!r}: "
            f"must be off|on")
    if config.serve_prefill_budget < 1:
        raise SystemExit(
            f"bad --serve-prefill-budget {config.serve_prefill_budget}: "
            f"the per-step fused prefill token budget must be >= 1")
    if config.serve_mixed_batch == "on" \
            and config.serve_speculative != "off":
        raise SystemExit(
            "--serve-mixed-batch on and --serve-speculative each replace "
            "the decode dispatch with their own fused forward; they do "
            "not compose — pick one")
    if config.serve_tp < 1 or config.serve_replicas < 1:
        # range guards only: head/mlp divisibility and the device-count
        # bound need the model geometry and an initialized backend, so
        # they live where both are known (serving/tp.check_geometry at
        # engine construction)
        raise SystemExit(
            f"bad distributed-serving knobs: --serve-tp "
            f"{config.serve_tp} (>= 1), --serve-replicas "
            f"{config.serve_replicas} (>= 1)")
    if (config.serve_deadline_ms is not None
            and config.serve_deadline_ms <= 0) \
            or (config.serve_queue_depth is not None
                and config.serve_queue_depth < 1) \
            or (config.serve_max_evictions is not None
                and config.serve_max_evictions < 1) \
            or (config.serve_drain_ms is not None
                and config.serve_drain_ms < 0) \
            or config.serve_failover_backoff_ms <= 0:
        raise SystemExit(
            f"bad --serve-* fault policy: deadline-ms "
            f"{config.serve_deadline_ms} (> 0), queue-depth "
            f"{config.serve_queue_depth} (>= 1), max-evictions "
            f"{config.serve_max_evictions} (>= 1), drain-ms "
            f"{config.serve_drain_ms} (>= 0), failover-backoff-ms "
            f"{config.serve_failover_backoff_ms} (> 0)")
    if config.serve_workload not in ("poisson", "bursty", "multi-tenant",
                                     "diurnal"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-workload {config.serve_workload!r}: must be "
            f"poisson|bursty|multi-tenant|diurnal")
    if config.serve_slo_ms is not None and not config.serve_slo_ms > 0:
        raise SystemExit(
            f"bad --serve-slo-ms {config.serve_slo_ms}: the latency "
            f"budget must be > 0 ms")
    if config.serve_trace not in ("off", "on"):
        # argparse choices guard the CLI path; this covers programmatic
        # Config construction routed through main
        raise SystemExit(
            f"bad --serve-trace {config.serve_trace!r}: must be off|on")
    if config.serve_trace_out is not None and config.serve_trace != "on":
        raise SystemExit(
            f"--serve-trace-out {config.serve_trace_out!r} requires "
            f"--serve-trace on (there is no trace to write otherwise)")

    from mpi_tensorflow_tpu.parallel import mesh as meshlib

    meshlib.initialize_distributed()

    from mpi_tensorflow_tpu.utils import cache as cache_lib
    from mpi_tensorflow_tpu.utils import logging as logs
    from mpi_tensorflow_tpu.utils import profiling

    cache_lib.enable_compile_cache()
    logs.device_banner(profiling.device_identity())

    def run_once():
        if config.model in ("bert_base", "moe_bert", "gpt_base",
                            "encdec_t5"):
            from mpi_tensorflow_tpu.train import mlm_loop

            return mlm_loop.train_mlm(config)
        from mpi_tensorflow_tpu.train import loop

        return loop.train(config)

    with profiling.trace(args.profile_dir):
        if args.max_restarts > 0:
            from mpi_tensorflow_tpu.train import elastic

            def on_restart(i, e):
                # retries resume from the latest committed checkpoint
                config.resume = True

            elastic.run_with_recovery(run_once,
                                      max_restarts=args.max_restarts,
                                      on_restart=on_restart)
        else:
            run_once()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
