"""Serve one synthetic trace through the paged-KV engine.

    python -m mpi_tensorflow_tpu.serving                      # gpt_base
    python -m mpi_tensorflow_tpu.serving --tiny --precision fp32 \\
        --num-requests 4 --prompt-max 8 --output-max 8        # seconds, CPU
    python -m mpi_tensorflow_tpu.serving --journal J --replicas 2

Every serving flag is DERIVED: one per ``ServeConfig`` field and one per
``WorkloadSpec`` field named in ``loadgen.WORKLOAD_HELP``, its name,
type and default read from the dataclass and its help from the table
beside it.  Nothing is validated here: a bad value or pairing is refused
by the dataclass's ``__post_init__`` in its own words, which ``main``
hands to ``parser.error`` (exit code 2).  Beside them the deployment
settings ``--precision``, ``--tiny``, ``--journal``, ``--replicas``.

The trace (``loadgen.build_trace``) is served ONCE, after one untimed
warm-up replay that pays the compiles: by ``PagedDecodeEngine.run``; with
``--replicas N`` by ``ReplicaRouter.run``; with ``--journal PATH`` through
the crash-recovery path with no warm-up (it would journal the trace
twice), so a run killed and relaunched with the same arguments resumes
from the journal (``<PATH>.r<i>`` per replica) and delivers the same
tokens.  SIGTERM drains.  An unset ``--max-seq-len`` / ``--num-blocks`` is
sized from the trace: the longest request rounded up to a power of two,
and a pool in which every slot fits one.

Prints ONE line of JSON: what was asked, what came out (``statuses``,
``outputs``, ``tokens`` beside ``tokens_requested``), the resolved
``kernel``, the jit-cache sizes after the warm-up and after the served
pass, the device, and the engine's own result blocks.  It measures
nothing: speeds come from ``python benchmarks/run.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import typing
from collections import Counter

from mpi_tensorflow_tpu.config import Config
from mpi_tensorflow_tpu.serving import loadgen
from mpi_tensorflow_tpu.serving.engine import (SERVE_HELP, ServeConfig,
                                               check_model, pow2_ceil)

#: result blocks passed through as the engine (or the router) made them
BLOCKS = ("faults", "fleet_faults", "drain", "health", "replicas", "prefix",
          "speculation", "tier", "replays", "evictions",
          "peak_blocks_in_use", "peak_live_blocks", "dispatch_shapes")


def _add_flags(parser, cls, help_table) -> None:
    """One ``--flag`` per field of ``cls`` that ``help_table`` names.
    Unset is None on the namespace, so the dataclass's own default
    applies (and the sizing rule can tell unset from set)."""
    hints = typing.get_type_hints(cls)
    group = parser.add_argument_group(cls.__name__)
    for f in dataclasses.fields(cls):
        if f.name not in help_table:
            continue
        kind = hints[f.name]
        if typing.get_origin(kind) is typing.Union:     # Optional[x]
            kind = typing.get_args(kind)[0]
        group.add_argument(
            "--" + f.name.replace("_", "-"), type=kind, default=None,
            metavar=kind.__name__.upper(),
            help=f"{help_table[f.name]} (default: {f.default})")


def _given(args, help_table) -> dict:
    return {k: getattr(args, k) for k in help_table
            if getattr(args, k) is not None}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m mpi_tensorflow_tpu.serving",
        description="Serve one synthetic trace through the paged-KV "
                    "continuous-batching engine; one JSON line out.")
    p.add_argument("--precision", default=Config.precision,
                   help="compute dtype of the served model: fp32 | bf16")
    p.add_argument("--model", default="gpt",
                   help="decoder family: gpt (CausalLm at gpt_base's "
                        "widths) | phi4_flash (Phi-4-mini-flash-"
                        "reasoning's: state-space, window, full and cross "
                        "layers) | cohere2_moe (one chip's share of "
                        "Command A+: parallel attention and experts, "
                        "grouped-query window and full layers)")
    p.add_argument("--tiny", action="store_true",
                   help="the family's CPU size, not its published widths")
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="replay journal: serve through the crash-recovery "
                        "path (no warm-up); the same arguments resume it")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="engine replicas behind the session-affinity + "
                        "least-load router; 1 = one engine, no router")
    _add_flags(p, ServeConfig, SERVE_HELP)
    _add_flags(p, loadgen.WorkloadSpec, loadgen.WORKLOAD_HELP)
    return p


#: family -> its served name at published widths
FAMILIES = {"gpt": "gpt_base", "phi4_flash": "phi4_mini_flash_reasoning",
            "cohere2_moe": "command_a_plus_05_2026"}


def _widths(args):
    """The model's config at the family's published or tiny widths."""
    if args.model not in FAMILIES:
        raise ValueError(f"--model must be one of {sorted(FAMILIES)}, "
                         f"got {args.model!r}")
    if args.model == "phi4_flash":
        from mpi_tensorflow_tpu.models import phi4_flash

        return phi4_flash.TINY if args.tiny else phi4_flash.Phi4FlashConfig()
    if args.model == "cohere2_moe":
        from mpi_tensorflow_tpu.models import cohere2_moe

        return cohere2_moe.TINY if args.tiny else cohere2_moe.CHIP_SHARE
    from mpi_tensorflow_tpu.models import bert

    return bert.BERT_TINY if args.tiny else bert.BERT_BASE


def plan(args) -> tuple:
    """(trace, ServeConfig) from the flags given.  An unset sequence cap
    and pool are sized from the trace: every slot fits the longest
    request (no eviction churn: shrink ``--num-blocks`` to study
    pressure)."""
    trace = loadgen.build_trace(loadgen.WorkloadSpec(
        vocab_size=_widths(args).vocab_size,
        **_given(args, loadgen.WORKLOAD_HELP)))
    given = _given(args, SERVE_HELP)
    longest = max(len(p) + o for p, o in zip(trace.prompts, trace.outputs))
    given.setdefault("max_seq_len", pow2_ceil(longest))
    if "num_blocks" not in given:
        # every other rule first, under a pool no table outgrows
        probe = ServeConfig(num_blocks=2 ** 31, **given)
        given["num_blocks"] = probe.max_slots * probe.max_blocks_per_seq + 1
    return trace, ServeConfig(**given)


def _build(args, cfg: ServeConfig, seed: int):
    """The model from the seed, and one engine or a router to serve it."""
    import jax

    from mpi_tensorflow_tpu.models import cohere2_moe, gpt, phi4_flash
    from mpi_tensorflow_tpu.serving import PagedDecodeEngine, ReplicaRouter

    family = {"phi4_flash": phi4_flash.Phi4FlashLm,
              "cohere2_moe": cohere2_moe.Cohere2MoeLm}.get(args.model,
                                                          gpt.CausalLm)
    model = family(dataclasses.replace(
        _widths(args),
        dtype=Config(precision=args.precision).compute_dtype))
    params = model.init(jax.random.key(seed))

    def make():
        return PagedDecodeEngine(model, params, cfg)
    if args.replicas == 1:
        return make(), make
    return ReplicaRouter([make() for _ in range(args.replicas)]), make


def _engines(front) -> list:
    return getattr(front, "engines", [front])


def _serve(front, make, trace, journal) -> tuple:
    """(result, compiles after warm-up, compiles after the served pass)."""
    from mpi_tensorflow_tpu.serving import recovery
    from mpi_tensorflow_tpu.train.preemption import PreemptionGuard

    engines = _engines(front)
    warm = None
    if journal is None:
        # a step's decode bucket follows arrival TIMING, which compile
        # stalls shift: sweep that grid, then replay for the prefill
        # shapes, which follow the trace's content
        for eng in engines:
            eng.prewarm_decode()
        front.run(trace.requests())
        warm = front.compile_counts()
        front.reset()
    with PreemptionGuard.installed() as guard:
        if journal is None:
            res = front.run(trace.requests(), guard=guard)
        elif front is not engines[0]:           # a router
            journals = [recovery.ReplayJournal(f"{journal}.r{i}")
                        for i in range(len(engines))]
            todo, pre = recovery.fleet_replay_requests(
                journals, trace.requests(), eos_id=engines[0].serve.eos_id)
            res = front.run(todo, guard=guard, journals=journals,
                            replay_pre=pre)
        else:
            # the supervisor rebuilds after a transient fault: the engine
            # already built is its first attempt, ``make`` every later one
            first = [front]
            res = recovery.run_with_replay(
                lambda: first.pop() if first else make(),
                trace.requests(), journal_path=journal, guard=guard)
    return res, warm, front.compile_counts()


def _report(args, cfg, trace, front, res, warm, served) -> dict:
    from mpi_tensorflow_tpu.serving import tracing
    from mpi_tensorflow_tpu.utils import engagement, metrics_writer
    from mpi_tensorflow_tpu.utils.profiling import device_identity

    out = {
        "model": (args.model + "_tiny" if args.tiny
                  else FAMILIES[args.model]),
        "precision": args.precision, "journal": args.journal,
        "serve": dataclasses.asdict(cfg),
        "workload": {k: getattr(trace.spec, k)
                     for k in loadgen.WORKLOAD_HELP},
        "kernel": _engines(front)[0].kernel,
        "paths": engagement.snapshot(),
        "statuses": res["statuses"],
        "status_counts": dict(Counter(res["statuses"].values())),
        "outputs": res["outputs"],
        "tokens": sum(len(v) for v in res["outputs"].values()),
        "tokens_requested": sum(trace.outputs),
        "compiles_after_warmup": warm,
        "compiles_after_served": served,
        # None = unknown, never "zero": journaled (no warm-up), no probe
        # on this jax, or a fleet (placement follows load: a replica can
        # meet a prompt bucket its share of the warm-up did not)
        "zero_recompile_steady_state": (
            warm == served if warm is not None and args.replicas == 1
            and None not in (*warm.values(), *served.values()) else None),
        # a journaled run replays earlier attempts' work into this
        # run's clock, so attained latencies would be skewed
        "goodput": (None if args.journal else metrics_writer.goodput_block(
            loadgen.per_request_rows(trace, res),
            elapsed_s=res["elapsed_s"])),
        **{k: res[k] for k in BLOCKS if k in res},
        **device_identity(),
    }
    if "trace" in res:
        tb = res["trace"]
        out["breakdown"] = metrics_writer.breakdown_block(
            tb, stamped_first_s=res.get("request_first_token_s"))
        out["trace"] = {
            "spans": len(tb["spans"]), "steps": tb["steps"],
            "steps_dropped": tb["steps_dropped"],
            "chrome_trace": (tracing.write_chrome_trace(
                cfg.trace_out, tb["replicas"]) if cfg.trace_out else None)}
    return out


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    try:
        # all a flag can make wrong, before anything is built: the
        # dataclasses' rules, the router's on an empty fleet, the engine's
        # on the model's widths.  Past this a ValueError is the program's
        # fault, not a flag's, and keeps its traceback
        trace, cfg = plan(args)
        if args.replicas < 1:
            from mpi_tensorflow_tpu.serving import ReplicaRouter
            ReplicaRouter([])
        check_model(_widths(args), cfg)
    except ValueError as e:
        parser.error(str(e))
    from mpi_tensorflow_tpu.utils import (cache, engagement, jsonsafe,
                                          logging as logs)
    from mpi_tensorflow_tpu.utils.profiling import device_identity

    cache.enable_compile_cache()
    # stdout carries the ONE JSON line; the banner goes to stderr
    logs.device_banner(device_identity(), file=sys.stderr)
    engagement.reset()
    front, make = _build(args, cfg, trace.spec.seed)
    res, warm, served = _serve(front, make, trace, args.journal)
    print(json.dumps(jsonsafe.json_safe(
        _report(args, cfg, trace, front, res, warm, served))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
