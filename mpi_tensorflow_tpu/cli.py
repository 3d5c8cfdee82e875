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
