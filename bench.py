#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line with the north-star metric.

Metric (BASELINE.json): images/sec/chip on the MNIST CNN train step, with
evaluation OFF the timed path (the reference's loop hides a full
test-shard eval in every step, mpipy.py:86).

Every row is measured by the run that prints it, on the device the row
names (``platform`` / ``device_kind`` / ``device_count``); there is no
recorded number to fall back on.

``vs_baseline`` compares against the single-process reference-semantics
baseline recorded in BASELINE_MEASURED.json (the reference publishes no
numbers).  Regenerate the baseline with ``python bench.py
--record-baseline`` on the baseline host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BASELINE_MEASURED.json")

def _print_json(obj) -> None:
    """One line of STRICT json: NaN/Inf -> null (utils/jsonsafe rule)."""
    from mpi_tensorflow_tpu.utils.jsonsafe import json_safe

    print(json.dumps(json_safe(obj)))



# per-model measurement shapes: batch/chip, input geometry, scan window
# (sized so the staged (K, B, ...) input bank fits HBM), total timed steps
MODEL_SPECS = {
    "mnist_cnn": dict(batch=64, shape=(28, 28, 1), classes=10,
                      scan=400, steps=4000, unit="images"),
    "resnet20": dict(batch=128, shape=(32, 32, 3), classes=10,
                     scan=50, steps=500, unit="images"),
    "resnet50": dict(batch=32, shape=(224, 224, 3), classes=1000,
                     scan=8, steps=48, unit="images"),
    "vit": dict(batch=128, shape=(32, 32, 3), classes=10,
                scan=20, steps=200, unit="images", dataset="cifar10"),
    "bert_base": dict(batch=64, seq=128, scan=4, steps=32, unit="tokens"),
    "moe_bert": dict(batch=64, seq=128, scan=4, steps=32, unit="tokens"),
    "gpt_base": dict(batch=64, seq=128, scan=4, steps=32, unit="tokens"),
    "encdec_t5": dict(batch=64, seq=128, scan=4, steps=32, unit="tokens"),
}

# display names for the image-family metric line; tests pin that every
# image entry in MODEL_SPECS has one (a missing name KeyErrors after the
# measurement has already run)
IMAGE_MODEL_NAMES = {
    "mnist_cnn": "MNIST CNN", "resnet20": "CIFAR ResNet-20",
    "resnet50": "ImageNet ResNet-50", "vit": "CIFAR ViT-Tiny",
}


def _measure_scanned(multi_step, state, batches, labels, key, scan_steps,
                     iters, warmup_calls):
    """Median seconds/step over ``iters`` scanned dispatches.  The value
    fetch of the last step's loss is the sync point: it cannot return
    before the whole scanned dispatch has run."""
    import time

    for _ in range(warmup_calls):
        state, m = multi_step(state, batches, labels, key)
        float(m["loss"][-1])
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state, m = multi_step(state, batches, labels, key)
        float(m["loss"][-1])
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] / scan_steps


def measure_bert(batch_size: int, steps: int, precision: str,
                 scan_steps: int, seq_len: int = 128,
                 ce_impl: str = "auto", ce_chunk: int = 2048,
                 model_name: str = "bert_base", remat: bool = False,
                 params_bf16: bool = False, prng_impl: str = "threefry",
                 fused_qkv: bool = False,
                 flash_min_seq: int | None = None,
                 remat_policy: str = "full") -> dict:
    """BERT-base MLM train-step throughput (BASELINE config 5) via the
    GSPMD path — adamw, tied-decoder MLM loss, scanned dispatches.
    ``model_name="moe_bert"`` swaps in the capacity-routed MoE variant
    (BERT-base geometry, experts on odd layers)."""
    import dataclasses as dc

    import jax
    import numpy as np
    import optax

    from mpi_tensorflow_tpu.config import Config
    from mpi_tensorflow_tpu.data import synthetic
    from mpi_tensorflow_tpu.models import bert
    from mpi_tensorflow_tpu.parallel import mesh as meshlib
    from mpi_tensorflow_tpu.train import gspmd

    cfg = Config(precision=precision, prng_impl=prng_impl)
    mesh = meshlib.make_mesh()
    ndev = meshlib.data_axis_size(mesh)
    global_b = batch_size * ndev
    bcfg = dc.replace(bert.BERT_BASE, dtype=cfg.compute_dtype,
                      ce_impl=ce_impl, ce_chunk=ce_chunk, remat=remat,
                      remat_policy=remat_policy, fused_qkv=fused_qkv,
                      max_positions=max(bert.BERT_BASE.max_positions,
                                        seq_len),
                      **({} if flash_min_seq is None
                         else {"flash_min_seq": flash_min_seq}))
    if model_name == "moe_bert":
        from mpi_tensorflow_tpu.models import moe

        model = moe.MoeBertMlm(bcfg, mesh=mesh)
    elif model_name == "gpt_base":
        from mpi_tensorflow_tpu.models import gpt

        # causal LM: every position carries loss (ce_positions is unused)
        model = gpt.CausalLm(bcfg, mesh=mesh)
    elif model_name == "encdec_t5":
        from mpi_tensorflow_tpu.models import encdec

        model = encdec.EncDecLm(bcfg)
    else:
        model = bert.BertMlm(bcfg, mesh=mesh)
    tx = optax.adamw(1e-4)
    import jax.numpy as jnp

    state = gspmd.init_gspmd_state(
        model, tx, jax.random.key(0), mesh,
        param_dtype=jnp.bfloat16 if params_bf16 else None)
    multi = gspmd.make_gspmd_multi_step(model, mesh, tx)

    K = max(1, min(scan_steps, steps))
    shape = (K, global_b, seq_len)
    # leading axis is the scan (step) axis — batch dim 1 shards over 'data'
    # (gspmd.shard_batch would wrongly map dim 0 to 'data' here)
    import jax.sharding as shd

    sh = shd.NamedSharding(mesh, shd.PartitionSpec(None, "data"))
    if model_name == "encdec_t5":
        src, tgt = synthetic.seq2seq_batches(
            K * global_b, src_len=seq_len, tgt_len=seq_len,
            vocab_size=bcfg.vocab_size, seed=0)
        batches = {"src": jax.device_put(src.reshape(shape), sh),
                   "tgt": jax.device_put(tgt.reshape(shape), sh)}
        labels = batches["tgt"]
    else:
        toks, tgts, mask = synthetic.mlm_batches(
            K * global_b, seq_len=seq_len, vocab_size=bcfg.vocab_size,
            seed=0)
        batches = {"tokens": jax.device_put(toks.reshape(shape), sh),
                   "mask": jax.device_put(mask.reshape(shape), sh)}
        labels = jax.device_put(tgts.reshape(shape), sh)

    from mpi_tensorflow_tpu.utils import engagement

    engagement.reset()   # snapshot below reflects THIS trace only
    sec = _measure_scanned(multi, state, batches, labels,
                           cfg.make_train_key(1), K, max(1, steps // K),
                           warmup_calls=2)
    causal = model_name == "gpt_base"
    from mpi_tensorflow_tpu.utils import flops as flops_lib

    # MoE routes each token through ONE expert of the same width, so the
    # dense formula holds per token; causal counts every position at the
    # head; the enc-dec family adds decoder + cross-attention terms
    if model_name == "encdec_t5":
        step_flops = flops_lib.encdec_train_flops(
            bcfg, model.n_dec, batch_size, seq_len, seq_len)
    else:
        step_flops = flops_lib.transformer_train_flops(
            bcfg, batch_size, seq_len,
            head_positions=seq_len if causal else None)
    return {
        "model_flops_per_step": step_flops,
        "mfu_pct": flops_lib.mfu_pct(step_flops, sec, precision,
                                     jax.devices()[0]),
        "model": model_name,
        # which implementations the compiled step actually engaged
        "paths": engagement.snapshot(),
        "tokens_per_sec_per_chip": batch_size * seq_len / sec,
        "examples_per_sec_per_chip": batch_size / sec,
        "step_time_ms": sec * 1e3,
        "num_devices": ndev,
        "batch_size_per_chip": batch_size,
        "seq_len": seq_len,
        "precision": precision,
        "scan_steps": K,
        "ce_impl": ce_impl,
        "ce_chunk": ce_chunk,
        "params_bf16": params_bf16,
        "prng_impl": prng_impl,
        "fused_qkv": fused_qkv,
        "flash_min_seq": bcfg.flash_min_seq,
        "remat": remat,
        "remat_policy": remat_policy,
        "platform": jax.devices()[0].platform,
    }


def measure(batch_size: int = 64, steps: int = 100, warmup: int = 5,
            precision: str = "fp32", scan_steps: int = 50,
            model_name: str = "mnist_cnn", remat: bool = False,
            prng_impl: str = "threefry") -> dict:
    """Train-step throughput for the image families.

    ``scan_steps > 0`` stages K batches on device and runs K steps per
    dispatch via ``lax.scan`` (train.step.make_multi_train_step) — measuring
    device throughput rather than per-dispatch host latency, which
    dominates a batch-64 MNIST step.
    ``scan_steps = 0`` times the one-dispatch-per-step path, the reference's
    execution shape (one ``sess.run`` per step, mpipy.py:85).
    """
    import jax
    import numpy as np

    from mpi_tensorflow_tpu.config import Config
    from mpi_tensorflow_tpu.parallel import mesh as meshlib
    from mpi_tensorflow_tpu.train import loop, step as step_lib
    from mpi_tensorflow_tpu.utils.profiling import time_step_fn

    spec = MODEL_SPECS[model_name]
    in_shape = spec["shape"]
    cfg = Config(batch_size=batch_size, precision=precision,
                 model=model_name, num_classes=spec["classes"],
                 image_size=in_shape[0], remat=remat, prng_impl=prng_impl,
                 dataset=spec.get("dataset", "mnist"))
    mesh = meshlib.make_mesh()
    ndev = meshlib.data_axis_size(mesh)
    global_b = batch_size * ndev

    model = loop.build_model(cfg)
    state = step_lib.init_state(model, jax.random.key(cfg.seed))

    rng = np.random.default_rng(0)
    from jax.sharding import NamedSharding, PartitionSpec as P

    key = cfg.make_train_key(0)
    if scan_steps > 0:
        scan_steps = min(scan_steps, steps)   # never exceed the requested work
        train_step = step_lib.make_multi_train_step(model, cfg, mesh,
                                                    decay_steps=50000)
        sh = NamedSharding(mesh, P(None, "data"))
        batches = jax.device_put(
            rng.normal(size=(scan_steps, global_b) + in_shape)
            .astype(np.float32) * 0.3, sh)
        labels = jax.device_put(
            rng.integers(0, spec["classes"], size=(scan_steps, global_b))
            .astype(np.int64), sh)
        iters = max(1, steps // scan_steps)
        # ``warmup`` counts single steps, like the non-scan path
        sec_per_step = _measure_scanned(
            train_step, state, batches, labels, key, scan_steps, iters,
            warmup_calls=max(1, warmup // scan_steps) + 1)
    else:
        train_step = step_lib.make_train_step(model, cfg, mesh,
                                              decay_steps=50000)
        sh = NamedSharding(mesh, P("data"))
        n_banks = 4  # rotate buffers so steps don't alias one input
        batches = [jax.device_put(
            rng.normal(size=(global_b,) + in_shape).astype(np.float32) * 0.3,
            sh) for _ in range(n_banks)]
        labels = [jax.device_put(
            rng.integers(0, spec["classes"],
                         size=(global_b,)).astype(np.int64), sh)
            for _ in range(n_banks)]
        sec_per_step, _ = time_step_fn(
            train_step, state,
            lambda i: (batches[i % n_banks], labels[i % n_banks], key),
            iters=steps, warmup=warmup)

    from mpi_tensorflow_tpu.utils import flops as flops_lib

    if model_name == "vit":
        step_flops = flops_lib.vit_train_flops(model.cfg, batch_size)
    else:
        step_flops = flops_lib.image_train_flops(model_name, batch_size)
    return {
        "model": model_name,
        "images_per_sec": global_b / sec_per_step,
        "images_per_sec_per_chip": batch_size / sec_per_step,
        "model_flops_per_step": step_flops,
        "mfu_pct": flops_lib.mfu_pct(step_flops, sec_per_step, precision,
                                     jax.devices()[0]),
        "step_time_ms": sec_per_step * 1e3,
        "num_devices": ndev,
        "batch_size_per_chip": batch_size,
        "precision": precision,
        "scan_steps": scan_steps,
        "remat": remat,
        "platform": jax.devices()[0].platform,
    }


def measure_decode(batch_size: int = 8, prompt_len: int = 32,
                   new_tokens: int = 128, precision: str = "bf16",
                   iters: int = 5, num_beams: int = 0) -> dict:
    """Autoregressive decode throughput: tokens/sec through CausalLm's
    KV-cache ``generate`` (greedy).  The per-token loop is a lax.scan over
    a static cache, so the whole decode is one compiled dispatch.
    ``num_beams > 0`` times ``beam_search`` instead (throughput counted in
    KEPT tokens/sec, i.e. batch tokens — the K-fold beam work is the price
    of the search, not output)."""
    import dataclasses as dc
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_tensorflow_tpu.config import Config
    from mpi_tensorflow_tpu.models import bert, gpt

    cfg = Config(precision=precision)
    bcfg = dc.replace(bert.BERT_BASE, dtype=cfg.compute_dtype)
    model = gpt.CausalLm(bcfg)
    params = model.init(jax.random.key(0))
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(
            0, bcfg.vocab_size, (batch_size, prompt_len)), jnp.int32)
    def median_time(fn):
        np.asarray(jax.tree.leaves(fn())[0])   # warmup + value-fetch sync
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            np.asarray(jax.tree.leaves(fn())[0])
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    # decode time comes from the SLOPE between two generate lengths: both
    # arms pay the identical prefill + dispatch cost, so both cancel in
    # the difference
    n_short = max(8, new_tokens // 8)
    n_long = n_short + new_tokens
    # BOTH arms pin the same cache capacity: each decode step attends over
    # the full (masked) cache buffer, so per-step cost scales with the
    # capacity — different capacities would bias the slope
    L = prompt_len + n_long
    cache0 = model.init_cache(batch_size, L)
    prefill = jax.jit(
        lambda p, t: model.forward_with_cache(p, t, cache0, 0)[0])
    if num_beams > 0:
        gen_short = jax.jit(lambda p, t: model.beam_search(
            p, t, n_short, num_beams=num_beams, cache_len=L)[0])
        gen_long = jax.jit(lambda p, t: model.beam_search(
            p, t, n_long, num_beams=num_beams, cache_len=L)[0])
    else:
        gen_short = jax.jit(
            lambda p, t: model.generate(p, t, n_short, cache_len=L))
        gen_long = jax.jit(
            lambda p, t: model.generate(p, t, n_long, cache_len=L))
    prefill_sec = median_time(lambda: prefill(params, prompt))
    short_sec = median_time(lambda: gen_short(params, prompt))
    long_sec = median_time(lambda: gen_long(params, prompt))
    per_tok = (long_sec - short_sec) / new_tokens
    # roofline sanity: each decode step streams every live parameter
    # from HBM at least once, so per-token time cannot beat
    # param_bytes / HBM_bw on the chip.  A slope below that bound is a
    # measurement artifact and must be flagged degenerate — never
    # recorded as a throughput.
    from mpi_tensorflow_tpu.utils import flops as flops_lib

    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    dev = jax.devices()[0]
    min_per_tok = (
        param_bytes / (flops_lib.device_peaks(dev.device_kind)["hbm_gbps"]
                       * 1e9) if dev.platform == "tpu" else 0.0)
    degenerate = per_tok <= min_per_tok
    return {
        "model": "gpt_base",
        "decode_tokens_per_sec": (batch_size / per_tok if not degenerate
                                  else float("nan")),
        "per_token_ms": per_tok * 1e3,
        "roofline_min_per_token_ms": min_per_tok * 1e3,
        "param_bytes": param_bytes,
        "timing_degenerate": degenerate,
        "decode_lengths": [n_short, n_long],
        "gen_short_ms": short_sec * 1e3,
        "gen_long_ms": long_sec * 1e3,
        "prefill_ms": prefill_sec * 1e3,
        "batch_size": batch_size,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "num_beams": num_beams,
        "precision": precision,
        "platform": jax.devices()[0].platform,
    }


def make_serving_spec(*, workload: str = "poisson",
                      num_requests: int = 24, rate_rps: float = 4.0,
                      prompt_max: int = 32, output_max: int = 128,
                      vocab_size: int = 32000, prefix_tokens: int = 0,
                      slo_ms: float | None = None, seed: int = 0):
    """The bench's trace description: measure_serving's knobs mapped
    onto a ``serving.loadgen.WorkloadSpec`` (which validates them —
    three-layer discipline: argparse choices, cli.py guard, spec).
    Module-level on purpose: the byte-identity test builds the spec
    through THIS seam and pins ``build_trace`` against the historical
    inline generator."""
    from mpi_tensorflow_tpu.serving import loadgen

    return loadgen.WorkloadSpec(
        workload=workload, num_requests=num_requests, rate_rps=rate_rps,
        prompt_max=prompt_max, output_max=output_max,
        vocab_size=vocab_size, prefix_tokens=prefix_tokens,
        slo_ms=slo_ms, seed=seed)


def measure_serving(num_requests: int = 24, rate_rps: float = 4.0,
                    max_slots: int | None = None,
                    pool_blocks: int | None = None,
                    block_size: int | None = None, prompt_max: int = 32,
                    output_max: int = 128, precision: str = "bf16",
                    seed: int = 0, deadline_ms: float | None = None,
                    queue_depth: int | None = None,
                    max_evictions: int | None = None,
                    drain_ms: float | None = None,
                    journal: str | None = None, tiny: bool = False,
                    kernel: str | None = None,
                    kernel_ab: bool = False,
                    kv_dtype: str | None = None,
                    kv_group: int | None = None,
                    kv_tier: str | None = None,
                    kv_ab: bool = False,
                    prefix_cache: str | None = None,
                    prefix_tokens: int = 0,
                    prefix_gen: str | None = None,
                    prefix_route: str | None = None,
                    speculative: str | None = None,
                    draft_k: int | None = None,
                    spec_ab: bool = False,
                    draft_auto: str | None = None,
                    mixed: str | None = None,
                    prefill_budget: int | None = None,
                    mixed_ab: bool = False,
                    tp: int | None = None,
                    replicas: int | None = None,
                    fault_replica: int | None = None,
                    fault_step: int | None = None,
                    fault_kind: str = "transient",
                    workload: str | None = None,
                    slo_ms: float | None = None,
                    trace_mode: str | None = None,
                    trace_out: str | None = None) -> dict:
    """Continuous-batching serving throughput vs the static-batch
    ``generate`` baseline, on ONE synthetic request trace built by
    ``serving.loadgen`` from a seeded ``WorkloadSpec``.

    Trace (default ``workload="poisson"``): ``num_requests`` requests,
    exponential inter-arrivals at ``rate_rps``, prompt lengths uniform
    in [8, prompt_max], output budgets uniform in [8, output_max] — the
    mixed-length regime where static batching burns MXU cycles on
    finished rows (every batch decodes to its LONGEST member) and
    continuous batching recycles the slot the step a sequence finishes.
    The default trace is BYTE-IDENTICAL to the historical inline
    generator (pinned by tests); ``workload`` picks bursty (2-state
    MMPP), diurnal (raised-cosine envelope), or multi-tenant (MMPP
    arrivals + interactive-vs-batch tenant mix with sticky sessions)
    variants — see the loadgen module docstring's workload matrix.

    SLO goodput: ``slo_ms`` stamps a per-request latency budget as
    ``Request.deadline`` (riding the scheduler's existing TTL
    machinery — late work sheds as ``deadline_exceeded``), and the
    detail's ``goodput`` block reports tokens/sec and req/sec from
    requests that FINISHED WITHIN BUDGET, with per-tenant attainment
    and attained-latency percentiles — the serving number raw
    tokens/sec over-reports under load (DistServe, arXiv:2401.09670).
    The timed run also feeds a ``ScaleAdvisor`` (serving/autoscale) one
    observation per engine iteration; its advisory scale-up/down
    decision log lands in the detail's ``autoscale`` block.

    Both arms pay their compiles in an untimed warmup replay (the engine
    keeps its bucketed jit cache across ``reset``; the baseline warms
    each padded batch shape), so the timed numbers compare steady-state
    serving, not compile time.  The baseline ignores arrival stamps
    (batches start as if all members were already present) — a bias IN
    THE BASELINE'S FAVOR; continuous batching must beat it anyway.
    Tokens counted are the REQUESTED output tokens for both arms.

    Fault tolerance: ``deadline_ms/queue_depth/max_evictions/drain_ms``
    are the admission-control and drain knobs (serving ServeConfig; the
    emitted detail carries the ``faults`` health-counter block either
    way).  A ``journal`` path switches to the FAULT-TOLERANT SERVE mode:
    no warmup replay and no static arm (both would double-journal the
    trace) — one journaled run through the crash-recovery supervisor
    (serving/recovery.run_with_replay) with SIGTERM wired to graceful
    drain, emitting per-request outputs + terminal statuses so a
    relaunch after SIGKILL provably resumes token-identically.  ``tiny``
    swaps BERT_TINY geometry in for the model — the smoke/CI
    configuration the fault-injection subprocess tests run.

    ``kernel`` picks the paged-attention lowering (--serve-kernel:
    auto|xla|pallas; None = the run Config's default).  The detail
    reports the RESOLVED kernel plus a bytes-per-decode-token roofline
    estimate for both lowerings.  ``kernel_ab`` additionally replays the
    same trace through the OTHER kernel (own warmup, own zero-recompile
    probe) and emits the speedup line — the control arm for validating
    the fused kernel on real hardware.

    KV quantization: ``kv_dtype`` picks the paged-pool storage format
    (--serve-kv-dtype: fp32|int8|int4; None = the run Config's
    default) — int8 stores symmetric-absmax codes with per-(block,
    head, slot) fp32 row scales, int4 packs two codes per byte with
    per-``kv_group``-wide fp32 group scales (--serve-kv-group), both
    dequantized inside the attention consume paths.  ``kv_tier``
    (--serve-kv-tier: off|host) demotes cold prefix-cache blocks to
    host RAM on eviction and promotes them back on a prefix match —
    it rides the prefix-cache-on multi-turn path and reports in the
    ``tier`` block.  ``kv_ab`` replays the SAME trace under the
    quantized rung and its fp32 reference (each arm with
    its own untimed warmup and zero-recompile probe, mirroring
    ``kernel_ab`` and mutually exclusive with it and every other A/B
    or control-arm mode — one comparison, one variable) and emits the
    canonical ``kv_quant`` block: positionwise greedy token-match rate
    vs the fp32 arm (THE quality gate — int8 outputs track fp32, they
    are not bit-identical to it), the effective-capacity multiplier
    (blocks the same HBM budget holds at quantized bytes-per-block),
    the peak-live-blocks delta (same trace => same block walk => 0),
    and the bytes-per-decode-token roofline at 1 byte/elem + scale
    traffic.

    Prefix sharing: ``prefix_tokens > 0`` prepends a common N-token
    system prompt to every request (the shared-prefix production
    regime); ``prefix_cache`` (--serve-prefix-cache: off|on; None = the
    run Config's default) turns the radix prefix cache on for the timed
    arm.  With the cache on (and no journal), the SAME trace is also
    replayed through a cache-OFF engine so the detail's ``prefix``
    block carries the measurable win — ``hit_rate``, blocks saved, and
    the pool-occupancy delta — plus a token-identity cross-check
    against the unshared arm.

    Prefix sharing v2: ``prefix_gen`` (--serve-prefix-gen: off|on)
    turns on generated-block caching + partial tail-block sharing and
    adds a seeded MULTI-TURN arm — an untimed discovery pass learns
    each request's answer, a follow-up turn replays every request as
    prior prompt + answer + a pre-drawn unique suffix
    (loadgen follow-up mode), and the two-turn trace runs through the
    gen-on engine AND a gen-off control (cache still on); the
    ``prefix_gen`` detail carries ``gen_inserted_blocks``, the
    hit-rate / prefill-tokens-saved gains, and the token-identity
    cross-check.  ``prefix_route`` (--serve-prefix-route: off|on) adds
    a 2-replica ROUTING arm: the same trace (sessionless, so affinity
    never preempts the hint) through a hint-on fleet and a
    least-load-only control; the ``prefix_route`` detail carries
    ``router_prefix_hits``, the aggregate hit-rate comparison, and
    token identity vs both the control fleet and the single engine.

    Speculative decoding: ``speculative`` (--serve-speculative:
    off|ngram|draft-model; None = the run Config's default) drafts
    ``draft_k`` tokens per live sequence and verifies them in one
    forward; the detail's ``speculation`` block carries the bandwidth
    proxy (``accept_rate`` / ``mean_accepted_len`` / ``steps_saved`` =
    emitted tokens minus verify forwards — full KV-streaming passes
    avoided), and a speculative run (no journal) also replays the trace
    through a speculation-OFF engine for a token-identity cross-check.
    ``spec_ab`` additionally TIMES that off arm (own warmup, own
    zero-recompile probe) and emits the wall-clock ``spec_speedup``
    line — mirroring ``kernel_ab``, and mutually exclusive with it
    (one comparison, one variable).  ``draft_auto`` turns on EWMA
    draft-window auto-tuning (--serve-draft-auto; the ``speculation``
    block reports the resulting ``effective_k``).

    Mixed batching: ``mixed`` (--serve-mixed-batch: off|on; None = the
    run Config's default) fuses budget-capped prefill chunks
    (``prefill_budget`` tokens per step, --serve-prefill-budget) into
    the decode dispatch so mid-prefill requests stop stalling decode
    steps — greedy outputs are token-identical to off by construction.
    ``mixed_ab`` additionally TIMES a mixed-off control arm (own
    warmup, own zero-recompile probe) and emits the ``mixed_ab``
    block: per-arm ``dispatches_per_token`` (THE CPU-visible win — the
    fused path must run strictly fewer forwards per emitted token),
    per-arm ``ttft_p99_ms`` from the goodput TTFT stamps (mixed must
    not regress it), ``token_identical_vs_off``, and the off arm's
    zero-recompile probe.  Mutually exclusive with every other A/B or
    control-arm mode (one comparison, one variable); speculative
    decoding is excluded at the ServeConfig layer already (both
    replace the decode dispatch).

    Tracing: ``trace_mode`` (--serve-trace: off|on; None = the run
    Config's default) turns on the serving/tracing layer for every
    engine this bench builds — request lifecycle spans + the bounded
    step-phase ring, host clocks only.  The detail gains a
    ``breakdown`` block (queue/prefill/decode/ttft percentiles
    recomputed FROM SPANS, cross-checked against the loop's stamps)
    and a ``trace`` summary; ``trace_out`` (--serve-trace-out) writes
    the timed run's Chrome trace-event JSON there (open in Perfetto or
    chrome://tracing).  Off is byte-for-byte the untraced bench:
    outputs AND detail keys are unchanged (the traced keys simply do
    not exist).

    Distributed serving: ``tp`` shards the timed engine tensor-parallel
    over the first ``tp`` visible devices (serving/tp — the dispatch
    discipline, zero-recompile probes, and every control arm work
    unchanged on the sharded engine).  ``replicas > 1`` ADDS a
    data-parallel arm after the timed single-engine run: the same trace
    through ``replicas`` engine replicas behind the serving router
    (session-affinity + least-load placement; one thread per replica on
    multi-core hosts so device work overlaps, sequential round-robin on
    one core — ``router.default_parallelism``), emitting per-replica
    metrics (queue depth, pool occupancy, shed rate, tokens/sec) and
    the aggregate-vs-single speedup — the scale-out acceptance signal,
    whose >1 reading needs the threaded mode and real parallel cores
    (the detail's ``replicas.parallel`` flag says which mode ran).
    """
    import dataclasses as dc
    import time
    from collections import Counter

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_tensorflow_tpu.config import Config
    from mpi_tensorflow_tpu.models import bert, gpt
    from mpi_tensorflow_tpu.serving import (PagedDecodeEngine,
                                            ServeConfig, autoscale,
                                            loadgen)
    from mpi_tensorflow_tpu.serving.engine import pow2_ceil
    from mpi_tensorflow_tpu.serving.paged_cache import blocks_for
    from mpi_tensorflow_tpu.utils import engagement, metrics_writer

    cfg = Config(precision=precision)
    # unset knobs resolve through the run Config's --serve-* defaults
    # (the one meaning of those knobs — serving.ServeConfig.from_config)
    max_slots = max_slots if max_slots is not None else cfg.serve_max_slots
    block_size = (block_size if block_size is not None
                  else cfg.serve_block_size)
    spec_mode = (speculative if speculative is not None
                 else cfg.serve_speculative)
    workload = workload if workload is not None else cfg.serve_workload
    slo_ms = slo_ms if slo_ms is not None else cfg.serve_slo_ms
    trace_mode = trace_mode if trace_mode is not None else cfg.serve_trace
    bcfg = dc.replace(bert.BERT_TINY if tiny else bert.BERT_BASE,
                      dtype=cfg.compute_dtype)
    if spec_mode != "off":
        # the speculative workload runs on ROPE positions: an untrained
        # model with per-position learned embeddings emits an aperiodic
        # stream (~every token unique — measured), which is the
        # degenerate worst case for any drafter and says nothing about
        # the machinery; rope dynamics are position-relative, so the
        # same untrained model falls into the recurrent/templated
        # regime speculation targets.  BOTH arms (speculative and the
        # off control) share this model, so the token-identity contract
        # is internal to the run, and speculative-off runs keep the
        # historical learned-position trace byte-for-byte.
        bcfg = dc.replace(bcfg, pos_kind="rope")
    # the trace: spec + seed -> loadgen.build_trace, ONE seeded
    # generator, no wall clock — (spec, seed) reproduces the identical
    # request list across warmup, timed, A/B, routed, and journal arms,
    # and the default poisson/uniform spec replays the pre-loadgen
    # inline generator byte-for-byte (pinned by tests/test_bench.py)
    trace_spec = make_serving_spec(
        workload=workload, num_requests=num_requests, rate_rps=rate_rps,
        prompt_max=prompt_max, output_max=output_max,
        vocab_size=bcfg.vocab_size, prefix_tokens=prefix_tokens,
        slo_ms=slo_ms, seed=seed)
    trace_b = loadgen.build_trace(trace_spec)
    prompts, outputs, arrivals = (trace_b.prompts, trace_b.outputs,
                                  trace_b.arrivals)
    model = gpt.CausalLm(bcfg)
    params = model.init(jax.random.key(0))
    max_len = max(len(p) + o for p, o in zip(prompts, outputs))
    gen_mode = prefix_gen if prefix_gen is not None else cfg.serve_prefix_gen
    if gen_mode == "on":
        # the multi-turn gen arm's follow-up requests are prior prompt
        # + answer (<= the output budget) + a short unique suffix, plus
        # their own output budget — size the sequence cap for the
        # longest possible turn-2 member up front (max_seq_len fixes
        # the bucket ladder and max_blocks_per_seq at engine build)
        max_len = max(max_len,
                      max(len(p) + 2 * o for p, o in zip(prompts, outputs))
                      + min(8, prompt_max))
    max_seq_len = pow2_ceil(max_len)
    bps = blocks_for(max_seq_len, block_size)
    if pool_blocks is None:
        # fits every slot at full length: measures pure continuous
        # batching, no eviction churn (shrink to study pressure)
        pool_blocks = max_slots * bps + 1
    serve = ServeConfig.from_config(
        cfg, num_blocks=pool_blocks, block_size=block_size,
        max_slots=max_slots, max_seq_len=max_seq_len, kernel=kernel,
        kv_dtype=kv_dtype, kv_group=kv_group, kv_tier=kv_tier,
        prefix_cache=prefix_cache,
        prefix_gen=prefix_gen, prefix_route=prefix_route,
        speculative=speculative,
        draft_k=draft_k, draft_auto=draft_auto,
        mixed_batch=mixed, prefill_budget=prefill_budget, tp=tp,
        deadline_ms=deadline_ms, queue_depth=queue_depth,
        max_evictions=max_evictions, drain_ms=drain_ms,
        trace=trace_mode, trace_out=trace_out)
    # resolve the unset knob through cfg like every other serve knob,
    # instead of a hardcoded 1 that shadows cfg.serve_replicas
    replicas = replicas if replicas is not None else cfg.serve_replicas
    if replicas < 1:
        raise ValueError(f"--serve-replicas must be >= 1, got {replicas}")
    if (fault_replica is None) != (fault_step is None):
        raise ValueError("--serve-fault-replica and --serve-fault-step "
                         "name one injected fault together — set both "
                         "or neither")
    if fault_kind not in ("transient", "permanent"):
        raise ValueError(f"--serve-fault-kind must be "
                         f"transient|permanent, got {fault_kind!r}")
    if fault_replica is not None:
        if replicas < 2:
            raise ValueError("--serve-fault-* injects a replica fault "
                             "into the routed fleet; it needs "
                             "--serve-replicas >= 2 so a survivor can "
                             "take the migrated work")
        if not 0 <= fault_replica < replicas:
            raise ValueError(f"--serve-fault-replica {fault_replica} "
                             f"outside the fleet [0, {replicas})")
        if fault_step < 1:
            raise ValueError(f"--serve-fault-step must be >= 1, got "
                             f"{fault_step}")
    if replicas > 1 and (kernel_ab or spec_ab):
        raise ValueError("--serve-replicas adds its own comparison arm "
                         "(aggregate vs single engine); combining it "
                         "with --serve-kernel-ab/--serve-spec-ab would "
                         "change two variables in one comparison — "
                         "pick one")
    if kernel_ab and journal is not None:
        raise ValueError("--serve-kernel-ab is a measurement (two timed "
                         "arms); the journaled serve mode is not — pick "
                         "one")
    if kernel_ab and serve.prefix_cache == "on":
        raise ValueError("--serve-prefix-cache on adds its own cache-off "
                         "control arm; combining it with "
                         "--serve-kernel-ab would change two variables "
                         "in one comparison — pick one")
    if spec_ab and serve.speculative == "off":
        raise ValueError("--serve-spec-ab compares speculative decoding "
                         "against its off arm; pick a drafter with "
                         "--serve-speculative ngram|draft-model")
    if spec_ab and journal is not None:
        raise ValueError("--serve-spec-ab is a measurement (two timed "
                         "arms); the journaled serve mode is not — pick "
                         "one")
    if spec_ab and kernel_ab:
        raise ValueError("--serve-spec-ab and --serve-kernel-ab each "
                         "replay the trace through their own control "
                         "arm; one comparison, one variable — pick one")
    if kernel_ab and serve.speculative != "off":
        raise ValueError("--serve-speculative adds its own off control "
                         "arm; combining it with --serve-kernel-ab "
                         "would change two variables in one comparison "
                         "— pick one")
    if kv_ab and journal is not None:
        raise ValueError("--serve-kv-ab is a measurement (two timed "
                         "arms); the journaled serve mode is not — pick "
                         "one")
    if kv_ab and (kernel_ab or spec_ab):
        raise ValueError("--serve-kv-ab, --serve-kernel-ab and "
                         "--serve-spec-ab each replay the trace through "
                         "their own control arm; one comparison, one "
                         "variable — pick one")
    if kv_ab and replicas > 1:
        raise ValueError("--serve-replicas adds its own comparison arm "
                         "(aggregate vs single engine); combining it "
                         "with --serve-kv-ab would change two variables "
                         "in one comparison — pick one")
    if kv_ab and serve.prefix_cache == "on":
        raise ValueError("--serve-prefix-cache on adds its own "
                         "cache-off control arm; combining it with "
                         "--serve-kv-ab would change two variables in "
                         "one comparison — pick one")
    if kv_ab and serve.speculative != "off":
        raise ValueError("--serve-speculative adds its own off control "
                         "arm; combining it with --serve-kv-ab would "
                         "change two variables in one comparison — "
                         "pick one")
    if serve.prefix_route == "on" and replicas > 1:
        raise ValueError("--serve-prefix-route on adds its own "
                         "2-replica hint-on-vs-off routing arm; "
                         "combining it with --serve-replicas would run "
                         "two fleets in one bench — pick one")
    if mixed_ab and serve.mixed_batch == "off":
        raise ValueError("--serve-mixed-ab compares mixed batching "
                         "against its off arm; turn the fused path on "
                         "with --serve-mixed-batch on")
    if mixed_ab and journal is not None:
        raise ValueError("--serve-mixed-ab is a measurement (two timed "
                         "arms); the journaled serve mode is not — pick "
                         "one")
    if mixed_ab and (kernel_ab or spec_ab or kv_ab):
        raise ValueError("--serve-mixed-ab, --serve-kernel-ab, "
                         "--serve-spec-ab and --serve-kv-ab each replay "
                         "the trace through their own control arm; one "
                         "comparison, one variable — pick one")
    if mixed_ab and replicas > 1:
        raise ValueError("--serve-replicas adds its own comparison arm "
                         "(aggregate vs single engine); combining it "
                         "with --serve-mixed-ab would change two "
                         "variables in one comparison — pick one")
    if mixed_ab and serve.prefix_cache == "on":
        raise ValueError("--serve-prefix-cache on adds its own "
                         "cache-off control arm; combining it with "
                         "--serve-mixed-ab would change two variables "
                         "in one comparison — pick one")

    def _roofline(resolved_kernel: str) -> dict:
        """Bytes-per-decode-token ESTIMATE for both lowerings, from the
        trace's own statistics: the XLA gather path touches the full
        bucketed table width per token (pool read + view write + dense
        attention read, K and V), the Pallas kernel streams one read of
        the LIVE lanes.  A roofline, not a measurement — the label the
        throughput number should be read against."""
        dtype_bytes = jnp.dtype(cfg.compute_dtype).itemsize
        row_bytes = bcfg.heads * bcfg.head_dim * dtype_bytes
        # mean live context per decode token over the trace (position of
        # token t of request i is len(prompt_i) + t)
        ctx = [len(p) + t + 1 for p, o in zip(prompts, outputs)
               for t in range(o)]
        mean_ctx = float(np.mean(ctx))
        cap = serve.max_blocks_per_seq * serve.block_size
        per_layer = 2 * row_bytes                 # K and V
        return {
            "kernel": resolved_kernel,
            "dtype_bytes": int(dtype_bytes),
            "mean_live_context_tokens": round(mean_ctx, 1),
            "padded_table_tokens": int(cap),
            "bytes_per_decode_token_xla":
                int(bcfg.layers * per_layer * cap * 3),
            "bytes_per_decode_token_pallas":
                int(bcfg.layers * per_layer * mean_ctx),
            "xla_over_pallas_bytes": round(cap * 3 / mean_ctx, 1),
        }

    def trace():
        # fresh Request objects per arm (engines mutate scheduling
        # state on them); deadlines/sessions ride along from the spec
        return trace_b.requests()

    def _trace_detail(run_res: dict) -> dict | None:
        """The detail's tracing keys for the mode's MAIN run (timed /
        journaled / fleet): the span-derived ``breakdown`` block
        cross-checked against the run's own first-token stamps, a
        small trace summary, and the Chrome trace-event export when
        ``--serve-trace-out`` names a path.  None (no keys added)
        when tracing is off — the off detail is byte-for-byte the
        untraced one."""
        if serve.trace != "on" or "trace" not in run_res:
            return None
        from mpi_tensorflow_tpu.serving import tracing as tracing_lib

        tb = run_res["trace"]
        chrome = None
        if serve.trace_out is not None:
            chrome = tracing_lib.write_chrome_trace(serve.trace_out,
                                                    tb["replicas"])
        return {
            "breakdown": metrics_writer.breakdown_block(
                tb, stamped_first_s=run_res.get("request_first_token_s")),
            "trace": {
                "enabled": True,
                "spans": len(tb["spans"]),
                "steps": tb["steps"],
                "steps_dropped": tb["steps_dropped"],
                "chrome_trace": chrome,
            },
        }

    from mpi_tensorflow_tpu.train.preemption import PreemptionGuard

    fault_plan = None
    if fault_replica is not None:
        from mpi_tensorflow_tpu.serving.router import (FaultPlan,
                                                       ReplicaFault)

        fault_plan = FaultPlan([ReplicaFault(fault_replica, fault_step,
                                             kind=fault_kind)])

    if journal is not None and replicas > 1:
        # fault-tolerant FLEET serve mode: journaling is per-replica
        # (``<journal>.r<i>``), failover/drain run inside the router,
        # and a SIGKILLed run relaunched with the same --serve-journal
        # resumes by replaying every journal's live entries through the
        # fleet — merged outputs token-identical to an unfaulted run
        from mpi_tensorflow_tpu.serving import recovery
        from mpi_tensorflow_tpu.serving.router import ReplicaRouter

        engagement.reset()
        journals = [recovery.ReplayJournal(f"{journal}.r{i}")
                    for i in range(replicas)]
        todo, pre = recovery.fleet_replay_requests(
            journals, trace(), eos_id=serve.eos_id)
        router = ReplicaRouter(
            [PagedDecodeEngine(model, params, serve)
             for _ in range(replicas)])
        with PreemptionGuard.installed() as guard:
            rr = router.run(todo, guard=guard, journals=journals,
                            replay_pre=pre, fault_plan=fault_plan)
        det = {
            "model": "gpt_tiny" if tiny else "gpt_base",
            "kernel": router.engines[0].kernel,
            "kernel_requested": kernel or cfg.serve_kernel,
            "roofline": _roofline(router.engines[0].kernel),
            "serve_kv_dtype": serve.kv_dtype,
            "serve_kv_group": serve.kv_group,
            "serve_kv_tier": serve.kv_tier,
            "serve_prefix_cache": serve.prefix_cache,
            "serve_prefix_tokens": prefix_tokens,
            "serve_prefix_gen": serve.prefix_gen,
            "serve_prefix_route": serve.prefix_route,
            "serve_speculative": serve.speculative,
            "serve_draft_k": serve.draft_k,
            "serve_draft_auto": serve.draft_auto,
            "serve_tp": serve.tp,
            "serve_replicas": replicas,
            "serve_workload": workload,
            "serve_slo_ms": slo_ms,
            "serve_trace": serve.trace,
            # journaled modes replay prior attempts' work into this
            # run's clock — attained latencies would be skewed, so the
            # goodput/autoscale blocks are timed-path-only
            "goodput": None,
            "autoscale": None,
            "serving_tokens_per_sec": rr["tokens_per_sec"],
            "p50_token_latency_ms": rr["p50_token_latency_ms"],
            "p99_token_latency_ms": rr["p99_token_latency_ms"],
            "static_batch_tokens_per_sec": None,
            "speedup_vs_static": None,
            "tokens": rr["tokens"],
            "elapsed_s": rr["elapsed_s"],
            "outputs": rr["outputs"],
            "statuses": rr["statuses"],
            "status_counts": dict(Counter(rr["statuses"].values())),
            "faults": rr["faults"],
            "fleet_faults": rr["fleet_faults"],
            "drain": rr["drain"],
            "health": rr["health"],
            "replicas": {
                "n": replicas,
                "parallel": rr["parallel"],
                "per_replica": rr["replicas"],
                "aggregate_tokens_per_sec": rr["tokens_per_sec"],
                "sticky_sessions": rr["sticky_sessions"],
                "fleet_faults": rr["fleet_faults"],
            },
            "serve_fault": (None if fault_replica is None else {
                "replica": fault_replica, "step": fault_step,
                "kind": fault_kind}),
            "journal": journal,
            "paths": engagement.snapshot(),
            "num_requests": num_requests, "rate_rps": rate_rps,
            "max_slots": max_slots, "pool_blocks": pool_blocks,
            "block_size": block_size, "prompt_max": prompt_max,
            "output_max": output_max, "max_seq_len": max_seq_len,
            "deadline_ms": deadline_ms, "queue_depth": queue_depth,
            "max_evictions": max_evictions, "drain_ms": drain_ms,
            "tiny": tiny, "precision": precision,
            "platform": jax.devices()[0].platform,
        }
        det.update(_trace_detail(rr) or {})
        return det

    if journal is not None:
        # fault-tolerant serve mode: one journaled pass through the
        # crash-recovery supervisor; a SIGKILLed run relaunched with the
        # same --serve-journal resumes from the journal and the merged
        # outputs are token-identical to an unfaulted run
        from mpi_tensorflow_tpu.serving import recovery

        engagement.reset()
        with PreemptionGuard.installed() as guard:
            res = recovery.run_with_replay(
                lambda: PagedDecodeEngine(model, params, serve),
                trace(), journal_path=journal, guard=guard)
        det = {
            "model": "gpt_tiny" if tiny else "gpt_base",
            "kernel": res.get("kernel"),
            "kernel_requested": kernel or cfg.serve_kernel,
            "roofline": _roofline(res.get("kernel")),
            "serve_kv_dtype": serve.kv_dtype,
            "serve_kv_group": serve.kv_group,
            "serve_kv_tier": serve.kv_tier,
            "prefix": res.get("prefix"),
            "serve_prefix_cache": serve.prefix_cache,
            "serve_prefix_tokens": prefix_tokens,
            "serve_prefix_gen": serve.prefix_gen,
            "serve_prefix_route": serve.prefix_route,
            "speculation": res.get("speculation"),
            "serve_speculative": serve.speculative,
            "serve_draft_k": serve.draft_k,
            "serve_draft_auto": serve.draft_auto,
            "serve_tp": serve.tp,
            "serve_replicas": 1,
            "serve_workload": workload,
            "serve_slo_ms": slo_ms,
            "serve_trace": serve.trace,
            # replayed attempts skew attained latency: timed-path-only
            "goodput": None,
            "autoscale": None,
            "peak_blocks_in_use": res.get("peak_blocks_in_use"),
            "peak_live_blocks": res.get("peak_live_blocks"),
            "serving_tokens_per_sec": res["tokens_per_sec"],
            "p50_token_latency_ms": res["p50_token_latency_ms"],
            "p99_token_latency_ms": res["p99_token_latency_ms"],
            "static_batch_tokens_per_sec": None,
            "speedup_vs_static": None,
            "tokens": res["tokens"],              # the final attempt's own
            "delivered_tokens": res["delivered_tokens"],  # journal-merged
            "elapsed_s": res["elapsed_s"],
            "evictions": res["evictions"],
            "outputs": res["outputs"],
            "statuses": res["statuses"],
            "status_counts": dict(Counter(res["statuses"].values())),
            "faults": res["faults"],
            "drain": res["drain"],
            "replays": res["replays"],
            "journal": journal,
            "paths": engagement.snapshot(),
            "num_requests": num_requests, "rate_rps": rate_rps,
            "max_slots": max_slots, "pool_blocks": pool_blocks,
            "block_size": block_size, "prompt_max": prompt_max,
            "output_max": output_max, "max_seq_len": max_seq_len,
            "deadline_ms": deadline_ms, "queue_depth": queue_depth,
            "max_evictions": max_evictions, "drain_ms": drain_ms,
            "tiny": tiny, "precision": precision,
            "platform": jax.devices()[0].platform,
        }
        det.update(_trace_detail(res) or {})
        return det

    engine = PagedDecodeEngine(model, params, serve)
    engagement.reset()
    # which (occupancy, table-width) pair a decode step runs at tracks
    # wall-clock arrival TIMING, and the compile stalls of the warmup
    # replay slow it enough to visit different buckets than the timed
    # replay (first chip run, PR 21: decode compiles 10 -> 12 inside the
    # timed window).  Sweep the decode bucket grid up front, then replay
    # for the prefill shapes, which depend on the trace's content only
    engine.prewarm_decode()
    engine.run(trace())                       # warmup: pays the compiles
    warm_compiles = engine.compile_counts()
    engine.reset()
    with PreemptionGuard.installed() as guard:
        # the advisor rides the TIMED run only: warmup's compile stalls
        # would read as phantom load spikes in the decision log
        cb = engine.run(trace(), guard=guard,
                        advisor=autoscale.ScaleAdvisor())
    steady_compiles = engine.compile_counts()

    ab = None
    if kernel_ab:
        # the SAME trace through the other lowering: own engine, own
        # untimed warmup (so both arms compare steady state), own
        # zero-recompile probe — the kernel path must honor the bucket
        # contract too, not just the gather path.  Off TPU the pallas
        # arm is the interpreter (named pallas-interpret in the arms);
        # a pallas arm that does not compile raises — no skipped verdict
        other = "pallas" if engine.kernel == "xla" else "xla"
        eng2 = PagedDecodeEngine(
            model, params, dc.replace(serve, kernel=other))
        eng2.prewarm_decode()                 # as for the timed arm
        eng2.run(trace())
        w2 = eng2.compile_counts()
        eng2.reset()
        cb2 = eng2.run(trace())
        s2 = eng2.compile_counts()
        arms = {engine.kernel: cb["tokens_per_sec"],
                eng2.kernel: cb2["tokens_per_sec"]}
        ab = {
            "kernels": sorted(arms),
            "tokens_per_sec": arms,
            # Mosaic-compiled arm only: an interpreted arm has no speed
            "pallas_speedup_vs_xla": (
                round(arms["pallas"] / arms["xla"], 3)
                if "pallas" in arms and arms["xla"] > 0 else None),
            "ab_zero_recompile": (w2 == s2
                                  if all(v is not None for v in
                                         {**w2, **s2}.values()) else None),
        }

    kv_detail = None
    if kv_ab:
        # the SAME trace through the OTHER pool storage format: own
        # engine, own untimed warmup (both arms compare steady state),
        # own zero-recompile probe — quantized pools must honor the
        # bucket contract too (codes and scale siblings are fixed-shape
        # engine state, so nothing about the dispatch shapes changes).
        # Arms are oriented fp32=reference / quantized regardless of
        # which one the timed engine ran; the quantized rung is the
        # run's --serve-kv-dtype when it is already below fp32, else
        # int8 (the ladder's first rung).
        quant_dt = serve.kv_dtype if serve.kv_dtype != "fp32" else "int8"
        other_dt = "fp32" if serve.kv_dtype != "fp32" else quant_dt
        eng2 = PagedDecodeEngine(
            model, params, dc.replace(serve, kv_dtype=other_dt))
        eng2.run(trace())
        w2 = eng2.compile_counts()
        eng2.reset()
        cb2 = eng2.run(trace())
        s2 = eng2.compile_counts()
        cb_fp32, cb_q = ((cb, cb2) if serve.kv_dtype == "fp32"
                         else (cb2, cb))
        # positionwise greedy agreement over the whole trace; a length
        # mismatch counts every unpaired position as a mismatch (the
        # honest denominator — early divergence must not shrink it)
        matched = compared = 0
        for rid, ref_out in cb_fp32["outputs"].items():
            q_out = cb_q["outputs"].get(rid, [])
            compared += max(len(ref_out), len(q_out))
            matched += sum(a == b for a, b in zip(ref_out, q_out))
        # bytes per pool block across all layers: fp32 stores K and V
        # rows at the compute dtype's width; int8 stores 1-byte codes
        # plus one fp32 scale per (head, slot) row — the +4/D
        # overhead; int4 packs two codes per byte (D/2) plus one fp32
        # scale per g_eff-wide group along the head dim — +4/g_eff
        itemsize = int(jnp.dtype(cfg.compute_dtype).itemsize)
        rows = bcfg.heads * serve.block_size          # rows per block
        fp32_block = 2 * rows * bcfg.head_dim * itemsize * bcfg.layers
        if quant_dt == "int4":
            g_eff = min(serve.kv_group, bcfg.head_dim)
            q_row = bcfg.head_dim // 2 + 4 * (bcfg.head_dim // g_eff)
        else:
            q_row = bcfg.head_dim + 4
        q_block = 2 * rows * q_row * bcfg.layers
        # decode-bandwidth roofline at the streaming (pallas) cost
        # model: one read of the live context's K and V rows per token
        mean_ctx = float(np.mean([len(p) + t + 1
                                  for p, o in zip(prompts, outputs)
                                  for t in range(o)]))
        fp32_bpt = bcfg.layers * 2 * bcfg.heads * bcfg.head_dim \
            * itemsize * mean_ctx
        q_bpt = bcfg.layers * 2 * bcfg.heads * q_row * mean_ctx
        kv_detail = {
            **metrics_writer.kv_quant_block(
                kv_dtype=quant_dt,
                matched_tokens=matched, compared_tokens=compared,
                block_bytes_ref=fp32_block, block_bytes=q_block,
                num_blocks=serve.num_blocks,
                peak_live_blocks_ref=cb_fp32["peak_live_blocks"],
                peak_live_blocks=cb_q["peak_live_blocks"],
                bytes_per_decode_token_ref=fp32_bpt,
                bytes_per_decode_token=q_bpt),
            "tokens_per_sec": {"fp32": cb_fp32["tokens_per_sec"],
                               quant_dt: cb_q["tokens_per_sec"]},
            "ab_zero_recompile": (w2 == s2
                                  if all(v is not None for v in
                                         {**w2, **s2}.values()) else None),
        }

    prefix_detail = cb["prefix"]
    if serve.prefix_cache == "on":
        # the cache-off control arm: SAME trace, sharing disabled — the
        # measurable win is its occupancy delta (blocks the trie saved)
        # and it doubles as a token-identity cross-check (greedy decode
        # must not notice the cache).  Not on the throughput line, but
        # it still pays its compiles in an untimed warmup first (like
        # the kernel A/B arm): a cold engine's compile stalls shift the
        # trace's wall clock, which would skew deadline/shed outcomes
        # and the occupancy comparison against the warmed cache-on arm
        eng_off = PagedDecodeEngine(
            model, params, dc.replace(serve, prefix_cache="off",
                                      prefix_gen="off",
                                      prefix_route="off",
                                      kv_tier="off"))
        eng_off.run(trace())
        eng_off.reset()
        off = eng_off.run(trace())
        prefix_detail = {
            **cb["prefix"],
            # live = distinct blocks pinned by in-flight sequences (the
            # occupancy that gates admission; trie-retained blocks are
            # reclaimable cache and excluded).  THE acceptance number:
            # sharing must put the cache-on run strictly below off
            "peak_live_blocks": cb["peak_live_blocks"],
            "peak_live_blocks_off": off["peak_live_blocks"],
            "blocks_saved_peak": (off["peak_live_blocks"]
                                  - cb["peak_live_blocks"]),
            "peak_blocks_in_use": cb["peak_blocks_in_use"],
            "peak_blocks_in_use_off": off["peak_blocks_in_use"],
            "token_identical_vs_off": off["outputs"] == cb["outputs"],
        }

    gen_detail = None
    if serve.prefix_gen == "on":
        # the multi-turn generated-block arm: rebuild the trace spec
        # with one seeded follow-up turn (the followup draws come LAST
        # in the rng order, so turn 1 is byte-identical to the main
        # trace), learn each request's answer in an untimed discovery
        # pass, then replay the combined two-turn trace through the
        # gen-on engine and a gen-off control (cache still on — the
        # PR-13 baseline).  The win is the follow-up prompts' generated
        # region mapping out of the trie instead of re-prefilling; the
        # contract is token identity between the arms.
        spec2 = dc.replace(trace_spec, followup_turns=1)
        trace2_b = loadgen.build_trace(spec2)
        engine.reset()
        disc = engine.run(trace())        # discovery: learn the answers
        t1_end = float(trace2_b.arrivals[-1])

        def mt_trace():
            return trace2_b.requests() + trace2_b.followup_requests(
                1, trace2_b.requests(), disc["outputs"],
                id_base=num_requests, arrival_base=t1_end)

        engine.reset()
        engine.run(mt_trace())            # warm the turn-2 buckets
        w_g = engine.compile_counts()
        engine.reset()
        on_r = engine.run(mt_trace())
        s_g = engine.compile_counts()
        eng_goff = PagedDecodeEngine(
            model, params, dc.replace(serve, prefix_gen="off",
                                      prefix_route="off"))
        eng_goff.run(mt_trace())
        eng_goff.reset()
        off_r = eng_goff.run(mt_trace())
        gen_detail = {
            "turns": 2,
            "requests_per_turn": num_requests,
            "prefix_on": on_r["prefix"],
            "prefix_off": off_r["prefix"],
            # with --serve-kv-tier host the multi-turn trace is where
            # promotion fires: turn-1 leaves demoted under pool
            # pressure are re-admitted when the follow-up turn matches
            # them, so this run's tier counters — not the single-turn
            # main trace's — carry the prefill_tokens_saved_tier win
            "tier": on_r.get("tier"),
            # THE gen-arm acceptance numbers: generated blocks actually
            # entered the trie, and the follow-up turn's reuse beats the
            # prompt-only (v1) baseline strictly
            "gen_inserted_blocks":
                on_r["prefix"]["gen_inserted_blocks"],
            "partial_copy_tokens":
                on_r["prefix"]["partial_copy_tokens"],
            "hit_rate_gain": round(on_r["prefix"]["hit_rate"]
                                   - off_r["prefix"]["hit_rate"], 4),
            "prefill_tokens_saved_gain": (
                on_r["prefix"]["prefill_tokens_saved"]
                - off_r["prefix"]["prefill_tokens_saved"]),
            "tokens_per_sec": {"gen_on": on_r["tokens_per_sec"],
                               "gen_off": off_r["tokens_per_sec"]},
            "token_identical_vs_off":
                on_r["outputs"] == off_r["outputs"],
            "ab_zero_recompile": (w_g == s_g
                                  if all(v is not None for v in
                                         {**w_g, **s_g}.values())
                                  else None),
        }

    route_detail = None
    if serve.prefix_route == "on":
        # the prefix-aware routing arm: the SAME (sessionless) trace
        # through a 2-replica fleet with the hint on, and through the
        # same engines least-load-only — the only variable is the
        # placement stage, so a higher aggregate hit rate is pure
        # locality (requests sharing a leading block land on the
        # replica that already cached it instead of splitting across
        # both tries).  Token identity must hold against both the
        # control fleet and the single timed engine.
        from mpi_tensorflow_tpu.serving.router import ReplicaRouter

        fleet_engines = [PagedDecodeEngine(model, params, serve)
                         for _ in range(2)]
        r_on = ReplicaRouter(fleet_engines, prefix_route=True)
        r_on.run(trace())                 # warm each replica's buckets
        r_on.reset()
        ron = r_on.run(trace())
        hits = ron["prefix"]["router_prefix_hits"]
        r_off = ReplicaRouter(fleet_engines, prefix_route=False)
        r_off.reset()                     # fresh tries; jit caches stay
        roff = r_off.run(trace())
        route_detail = {
            "n": 2,
            "router_prefix_hits": hits,
            "prefix_on": ron["prefix"],
            "prefix_off": roff["prefix"],
            # aggregate full-block reuse with vs without the hint — THE
            # routing acceptance number (the hint concentrates shared
            # prefixes instead of duplicating them per replica)
            "hit_rate": {"route_on": ron["prefix"]["hit_rate"],
                         "route_off": roff["prefix"]["hit_rate"]},
            "hit_rate_gain": round(ron["prefix"]["hit_rate"]
                                   - roff["prefix"]["hit_rate"], 4),
            "tokens_per_sec": {"route_on": ron["tokens_per_sec"],
                               "route_off": roff["tokens_per_sec"]},
            "token_identical_vs_off":
                ron["outputs"] == roff["outputs"],
            "token_identical_vs_single":
                ron["outputs"] == cb["outputs"],
        }

    spec_detail = cb["speculation"]
    spec_ab_detail = None
    if serve.speculative != "off":
        # the speculation-off control arm: SAME trace, same (rope)
        # model, drafting disabled — its outputs pin the token-identity
        # contract (greedy decode must not notice the drafter), and
        # under --serve-spec-ab its timed rate is the denominator of
        # the wall-clock speedup line.  Warmed untimed first, exactly
        # like the kernel A/B and prefix control arms.
        eng_off = PagedDecodeEngine(
            model, params, dc.replace(serve, speculative="off"))
        eng_off.run(trace())
        w_off = eng_off.compile_counts()
        eng_off.reset()
        off = eng_off.run(trace())
        s_off = eng_off.compile_counts()
        spec_detail = {
            **cb["speculation"],
            "token_identical_vs_off": off["outputs"] == cb["outputs"],
        }
        if spec_ab:
            arms = {"speculative": cb["tokens_per_sec"],
                    "off": off["tokens_per_sec"]}
            spec_ab_detail = {
                "arms": arms,
                # >1 = speculation beats vanilla decode on wall clock
                "spec_speedup_vs_off": (
                    round(arms["speculative"] / arms["off"], 3)
                    if arms["off"] > 0 else None),
                "ab_zero_recompile": (
                    w_off == s_off
                    if all(v is not None for v in
                           {**w_off, **s_off}.values()) else None),
            }

    mixed_ab_detail = None
    if mixed_ab:
        # the mixed-off control arm: SAME trace through the byte-for-
        # byte two-dispatch loop (one prefill forward + one decode
        # forward per step), own untimed warmup, own zero-recompile
        # probe — exactly the kernel/spec A/B discipline.  The headline
        # is NOT wall clock (on CPU both arms are host-bound): it is
        # dispatches-per-emitted-token, the hardware-independent count
        # of model forwards the fused path saved, plus the TTFT
        # percentiles the stall-free packing exists to improve.
        eng_off = PagedDecodeEngine(
            model, params, dc.replace(serve, mixed_batch="off"))
        # the two-dispatch loop's decode buckets track LIVE occupancy,
        # which tracks wall-clock arrival timing — on a bursty trace
        # the timed replay reaches (batch, table-width) pairs the
        # (compile-stalled, hence slower) warmup replay never did, and
        # one recompile stall then cascades into queueing that skews
        # TTFT and the dispatch counts this comparison exists for.
        # Sweep the full decode bucket grid up front — the off-arm
        # analogue of the fused path's build-time pre-warm (which is
        # immune by construction) — then replay for the prefill shapes.
        eng_off.prewarm_decode()
        eng_off.run(trace())
        w_m = eng_off.compile_counts()
        eng_off.reset()
        off = eng_off.run(trace())
        s_m = eng_off.compile_counts()
        gp_on = metrics_writer.goodput_block(
            loadgen.per_request_rows(trace_b, cb),
            elapsed_s=cb["elapsed_s"])
        gp_off = metrics_writer.goodput_block(
            loadgen.per_request_rows(trace_b, off),
            elapsed_s=off["elapsed_s"])
        mixed_ab_detail = {
            "prefill_budget": serve.prefill_budget,
            "tokens_per_sec": {"mixed": cb["tokens_per_sec"],
                               "off": off["tokens_per_sec"]},
            # THE win metric: model forwards per emitted token — mixed
            # must be STRICTLY lower (it folds the prefill forwards the
            # off arm pays separately into the decode dispatch)
            "dispatches_per_token": {
                "mixed": cb["dispatches_per_token"],
                "off": off["dispatches_per_token"]},
            "dispatch_reduction": (
                round(1.0 - cb["dispatches_per_token"]
                      / off["dispatches_per_token"], 4)
                if off["dispatches_per_token"] > 0 else None),
            # stall-free packing must not trade first-token latency
            # away: p99 TTFT no worse than the off arm's
            "ttft_p50_ms": {"mixed": gp_on["ttft_p50_ms"],
                            "off": gp_off["ttft_p50_ms"]},
            "ttft_p99_ms": {"mixed": gp_on["ttft_p99_ms"],
                            "off": gp_off["ttft_p99_ms"]},
            "token_identical_vs_off": off["outputs"] == cb["outputs"],
            "ab_zero_recompile": (w_m == s_m
                                  if all(v is not None for v in
                                         {**w_m, **s_m}.values())
                                  else None),
        }

    replicas_detail = None
    if replicas > 1:
        # the data-parallel scale-out arm: the SAME trace through N
        # engine replicas behind the serving router, each replica
        # stepped from its own thread (jax dispatch/blocking release
        # the GIL, so replica device work overlaps — the in-process
        # stand-in for one-process-per-chip).  Warmed untimed first
        # (each replica pays its own bucket compiles), then timed —
        # exactly the single-engine arm's discipline, so the
        # aggregate-vs-single comparison is steady state on both sides.
        from mpi_tensorflow_tpu.serving.router import ReplicaRouter

        router = ReplicaRouter([PagedDecodeEngine(model, params, serve)
                                for _ in range(replicas)])
        router.run(trace())
        router.reset()
        # the fault plan (if any) injects into the TIMED replay only:
        # the warmup replay exists to pay bucket compiles, and a fault
        # there would consume the one-shot plan before the arm it is
        # meant to exercise.  Token identity to the single engine must
        # hold across the failover — replay-by-prefix is exact.
        rr = router.run(trace(), fault_plan=fault_plan,
                        advisor=autoscale.ScaleAdvisor(replicas=replicas))
        replicas_detail = {
            "n": replicas,
            "autoscale": rr["autoscale"],
            "fleet_faults": rr["fleet_faults"],
            "health": rr["health"],
            "serve_fault": (None if fault_replica is None else {
                "replica": fault_replica, "step": fault_step,
                "kind": fault_kind}),
            # threads on multi-core hosts (replica device work
            # overlaps); sequential round-robin on a single core,
            # where the threaded ping-pong is pure GIL overhead and
            # the >1 aggregate speedup physically needs parallel
            # hardware (router.default_parallelism)
            "parallel": rr["parallel"],
            "per_replica": rr["replicas"],
            "aggregate_tokens_per_sec": rr["tokens_per_sec"],
            # >1 = the routed fleet beats one engine on the same trace
            # (THE scale-out acceptance number)
            "speedup_vs_single_replica": (
                round(rr["tokens_per_sec"] / cb["tokens_per_sec"], 3)
                if cb["tokens_per_sec"] > 0 else None),
            "token_identical_vs_single": rr["outputs"] == cb["outputs"],
            "sticky_sessions": rr["sticky_sessions"],
            "p50_token_latency_ms": rr["p50_token_latency_ms"],
            "p99_token_latency_ms": rr["p99_token_latency_ms"],
            "status_counts": dict(Counter(rr["statuses"].values())),
        }

    # -- static-batch baseline: generate() on arrival-order groups of
    # max_slots, each padded to its longest prompt and decoded to its
    # longest output budget, one shared cache capacity per batch --
    # cache capacity per batch: the group's padded prompt + longest
    # output (pmax and nmax can come from DIFFERENT requests, so this
    # may exceed max_seq_len — static batching pays for its padding)
    gen = jax.jit(
        lambda p, t, n, L: model.generate(p, t, n, cache_len=L),
        static_argnums=(2, 3))
    batches = []
    for i in range(0, num_requests, max_slots):
        grp = list(range(i, min(i + max_slots, num_requests)))
        pmax = pow2_ceil(max(len(prompts[j]) for j in grp))
        nmax = max(outputs[j] for j in grp)
        toks = np.zeros((len(grp), pmax), np.int32)
        for r, j in enumerate(grp):
            # LEFT-pad by repeating the first token so every row's real
            # prompt ends at the prefill boundary.  The padded rows'
            # exact tokens differ from the true continuations (pads are
            # attended); the baseline measures static batching's COMPUTE
            # shape — batch-max prompt, batch-max output — not content
            toks[r] = [prompts[j][0]] * (pmax - len(prompts[j])) \
                + prompts[j]
        batches.append((jnp.asarray(toks), nmax, pmax + nmax))
    for t, n, L in batches:
        jax.block_until_ready(gen(params, t, n, L))   # warm each shape
    t0 = time.perf_counter()
    for t, n, L in batches:
        jax.block_until_ready(gen(params, t, n, L))
    static_sec = time.perf_counter() - t0
    useful = sum(outputs)
    static_tps = useful / static_sec if static_sec > 0 else 0.0

    # SLO goodput over the timed run: join trace metadata (tenant,
    # arrival, per-request budget) with the run's finish stamps into
    # the canonical goodput block — THE serving metric when slo_ms is
    # set (raw tokens/sec over-reports under load)
    goodput = metrics_writer.goodput_block(
        loadgen.per_request_rows(trace_b, cb),
        elapsed_s=cb["elapsed_s"])

    det = {
        "model": "gpt_tiny" if tiny else "gpt_base",
        "kernel": engine.kernel,
        "kernel_requested": kernel or cfg.serve_kernel,
        "roofline": _roofline(engine.kernel),
        "kernel_ab": ab,
        "kv_quant": kv_detail,
        "serve_kv_dtype": serve.kv_dtype,
        "serve_kv_group": serve.kv_group,
        "serve_kv_tier": serve.kv_tier,
        "tier": cb.get("tier"),
        "prefix": prefix_detail,
        "prefix_gen": gen_detail,
        "prefix_route": route_detail,
        "serve_prefix_cache": serve.prefix_cache,
        "serve_prefix_tokens": prefix_tokens,
        "serve_prefix_gen": serve.prefix_gen,
        "serve_prefix_route": serve.prefix_route,
        "speculation": spec_detail,
        "spec_ab": spec_ab_detail,
        "serve_speculative": serve.speculative,
        "serve_draft_k": serve.draft_k,
        "serve_draft_auto": serve.draft_auto,
        "mixed_ab": mixed_ab_detail,
        "serve_mixed_batch": serve.mixed_batch,
        "serve_prefill_budget": serve.prefill_budget,
        "serve_tp": serve.tp,
        "serve_replicas": replicas,
        "serve_workload": workload,
        "serve_slo_ms": slo_ms,
        "serve_trace": serve.trace,
        "goodput": goodput,
        "autoscale": cb["autoscale"],
        "replicas": replicas_detail,
        "peak_blocks_in_use": cb["peak_blocks_in_use"],
        "peak_live_blocks": cb["peak_live_blocks"],
        "serving_tokens_per_sec": cb["tokens_per_sec"],
        "p50_token_latency_ms": cb["p50_token_latency_ms"],
        "p99_token_latency_ms": cb["p99_token_latency_ms"],
        # model forwards the timed arm ran and its per-emitted-token
        # rate — the dispatch-economy number mixed batching improves
        "forward_dispatches": cb["forward_dispatches"],
        "dispatches_per_token": cb["dispatches_per_token"],
        "static_batch_tokens_per_sec": static_tps,
        "speedup_vs_static": (cb["tokens_per_sec"] / static_tps
                              if static_tps > 0 else None),
        "tokens": cb["tokens"],
        # the trace's own output budgets: a clean run emits exactly this
        "tokens_requested": useful,
        "elapsed_s": cb["elapsed_s"],
        "evictions": cb["evictions"],
        # serving health counters (admission control / drain outcomes):
        # the canonical faults block, zero-valued on a clean run
        "faults": cb["faults"],
        "status_counts": dict(Counter(cb["statuses"].values())),
        "drain": cb["drain"],
        "deadline_ms": deadline_ms, "queue_depth": queue_depth,
        "max_evictions": max_evictions, "drain_ms": drain_ms,
        "tiny": tiny,
        "dispatch_shapes": [list(s) for s in cb["dispatch_shapes"]],
        "compiles_after_warmup": warm_compiles,
        "compiles_after_steady": steady_compiles,
        # None = probe unavailable on this jax (unknown), never "zero"
        "zero_recompile_steady_state": (
            warm_compiles == steady_compiles
            if all(v is not None for v in
                   {**warm_compiles, **steady_compiles}.values())
            else None),
        "paths": engagement.snapshot(),
        "num_requests": num_requests,
        "rate_rps": rate_rps,
        "max_slots": max_slots,
        "pool_blocks": pool_blocks,
        "block_size": block_size,
        "prompt_max": prompt_max,
        "output_max": output_max,
        "max_seq_len": max_seq_len,
        "precision": precision,
        "platform": jax.devices()[0].platform,
    }
    det.update(_trace_detail(cb) or {})
    return det


def measure_allreduce(payload_mb: float = 25.4, iters: int = 50,
                      chain: int = 32, dispatches: int = 7) -> dict:
    """Gradient-allreduce step time — the second half of the north-star
    metric ('allreduce step-time vs MPI baseline', BASELINE.json).

    Times an in-graph ``psum`` over the data axis on a payload shaped like
    the model gradient pytree.  The default payload is the MNIST CNN's
    1.66M-param gradient (6.65 MB) scaled to the BERT-comparable 25.4 MB
    unless overridden.  The MPI analogue is the reference's per-sync
    ``Gather`` of the four weight tensors (mpipy.py:121-127) — which is not
    even an allreduce; we time the honest collective.

    Method: ``chain`` data-dependent psums run inside ONE compiled
    ``lax.scan`` dispatch, so per-dispatch host overhead amortizes to
    chain⁻¹ of itself; the median is taken over ``dispatches``
    dispatches.  The data dependency (each iteration rescales the
    previous psum's output) keeps XLA from eliding repeats.
    ``iters`` is accepted for CLI compatibility and folded into
    ``dispatches`` when larger.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from mpi_tensorflow_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh()
    n = meshlib.data_axis_size(mesh)
    nfloats = int(payload_mb * 1e6 / 4)
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.device_put(np.random.default_rng(0)
                       .normal(size=(n, nfloats)).astype(np.float32) * 1e-3,
                       NamedSharding(mesh, P("data")))

    from mpi_tensorflow_tpu.parallel import collectives

    scale = jnp.float32(1.0 / n)

    @jax.jit
    def chained(v):
        def shard_body(s):
            def body(c, _):
                # psum then rescale: keeps magnitudes stable across the
                # chain and makes every iteration depend on the last
                return collectives.allreduce_sum(c, axis="data") * scale, None

            out, _ = lax.scan(body, s, None, length=chain)
            return out

        return jax.shard_map(shard_body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False)(v)

    dispatches = max(dispatches, iters // chain)
    float(jnp.sum(chained(x)[0, :8]))      # compile + warmup, value-fetch sync
    times = []
    for _ in range(dispatches):
        t0 = _time.perf_counter()
        float(jnp.sum(chained(x)[0, :8]))  # value fetch = reliable sync
        times.append(_time.perf_counter() - t0)
    sec = sorted(times)[len(times) // 2] / chain
    return {
        "allreduce_ms": sec * 1e3,
        "payload_mb": payload_mb,
        "algbw_gbps": (payload_mb / 1e3) / sec if sec > 0 else float("inf"),
        "chain": chain,
        "dispatches": dispatches,
        "num_devices": n,
        "platform": jax.devices()[0].platform,
    }


def measure_hostio(batch_size: int = 32, window_k: int = 4,
                   windows: int = 12, image_size: int = 224,
                   train_n: int = 512) -> dict:
    """Host input-pipeline throughput (host only; no device involved).

    The reference feeds the device through feed_dict from an inline numpy
    slice per step (mpipy.py:80-85) and never accounts the host cost.
    This mode measures the framework's feed side in isolation, for
    ResNet-50-shaped batches (N,224,224,3 fp32): a disk-backed mmap
    ``.npy`` training array (the data/imagenet.py storage format) driven
    through the three window-assembly paths — inline (the golden gather),
    the Python-thread prefetcher, and the native C++ prefetcher
    (native/prefetcher.cpp) — reporting sustained images/sec each.

    The number to beat is the DEVICE's consumption rate for the same
    batches — which this mode does not measure and does not quote:
    ``device_demand_img_s`` is "not measured" until a chip run of the
    resnet50 cell supplies it.  Reads are page-cache-warm after the
    first pass — an upper bound for cold storage, the right bound for
    the steady-state epochs>1 regime the reference times (mpipy.py:79).
    """
    import tempfile
    import time as _time

    import numpy as np

    from mpi_tensorflow_tpu.data import prefetch as pf

    if batch_size >= train_n:
        # assemble_window's wraparound is offset % (local_n - batch)
        raise ValueError(f"--batch-size {batch_size} must be < the "
                         f"hostio dataset size {train_n}")
    d = tempfile.mkdtemp(prefix="hostio-", dir=".")
    try:
        path = os.path.join(d, "train_images.npy")
        arr = np.lib.format.open_memmap(
            path, mode="w+", dtype=np.float32,
            shape=(1, train_n, image_size, image_size, 3))
        # cheap deterministic fill (bytes are bytes for gather throughput)
        row = np.linspace(0, 1, image_size * image_size * 3,
                          dtype=np.float32).reshape(image_size,
                                                    image_size, 3)
        for i in range(train_n):
            arr[0, i] = row * ((i % 13) + 1)
        arr.flush()
        del arr
        tr_d = np.load(path, mmap_mode="r")
        tr_l = (np.arange(train_n, dtype=np.int64) % 1000)[None, :]

        starts = np.arange(windows) * window_k
        widths = np.full(windows, window_k)
        n_imgs = windows * window_k * batch_size

        def run(force):
            if force == "inline":
                t0 = _time.perf_counter()
                for s, w in zip(starts, widths):
                    pf.assemble_window(tr_d, tr_l, int(s), int(w),
                                       window_k, batch_size)
                return n_imgs / (_time.perf_counter() - t0)
            # timer covers construction too: both prefetchers start
            # assembling in __init__, so starting the clock after would
            # credit them up to `depth` windows of free work
            t0 = _time.perf_counter()
            p = pf.make_prefetcher(tr_d, tr_l, starts, widths, window_k,
                                   batch_size, force=force)
            try:
                while p.next() is not None:
                    pass
                return n_imgs / (_time.perf_counter() - t0)
            finally:
                p.close()

        run("inline")                      # warm the page cache
        out = {"host_images_per_sec_inline": run("inline"),
               "host_images_per_sec_thread": run("thread")}
        try:
            out["host_images_per_sec_native"] = run("native")
        except (RuntimeError, ValueError) as e:
            out["host_images_per_sec_native"] = None
            out["native_error"] = str(e)[:200]
        best = max(v for k, v in out.items()
                   if k.startswith("host_images") and v)
        out.update(
            host_images_per_sec=best,
            device_demand_img_s="not measured",
            batch_size=batch_size, window_k=window_k, windows=windows,
            image_size=image_size,
            note="page-cache-warm mmap reads; steady-state epoch>1 bound")
        return out
    finally:
        import shutil

        shutil.rmtree(d, ignore_errors=True)


def _load_baseline() -> dict:
    if os.path.exists(BASELINE_FILE):
        with open(BASELINE_FILE) as f:
            return json.load(f)
    return {}


def _record_baseline(section: str, result: dict) -> None:
    base = _load_baseline()
    if section == "train":
        # historical schema: train metrics live flat at the top level
        base.update(result)
    else:
        base[section] = result
    with open(BASELINE_FILE, "w") as f:
        json.dump(base, f, indent=2)
    _print_json({"recorded_baseline": result})


_TRANSFORMER_MODELS = ("bert_base", "moe_bert", "gpt_base", "encdec_t5")
_BERT_LABELS = {"moe_bert": "MoE-BERT MLM (capacity-routed EP)",
                "gpt_base": "GPT-base causal LM",
                "encdec_t5": "Encoder-decoder LM (cross-attention)"}

def _report(args, d: dict) -> int:
    """THE metric-line emitter for every mode.  ``d`` is the result dict
    the measure_*() call of THIS run just returned; every row is stamped
    with the device it ran on (platform, device_kind, device_count)."""
    from mpi_tensorflow_tpu.utils.profiling import device_identity

    d.update(device_identity())
    if args.mode == "serving":
        sp = d.get("speedup_vs_static")
        # the workload names the trace in the metric label (absent on
        # old records = the historical Poisson trace)
        wl = d.get("serve_workload", "poisson")
        wl_label = "Poisson" if wl == "poisson" else wl
        out = {
            "metric": f"GPT-base continuous-batching serving throughput "
                      f"(paged KV cache, {wl_label} trace)",
            "value": round(d["serving_tokens_per_sec"], 1),
            "unit": "tokens/sec",
            # >1 = continuous batching beats static-batch generate() on
            # the same trace (the in-run baseline arm)
            "vs_baseline": round(sp, 3) if sp else None,
            # which paged-attention lowering served the timed arm
            "kernel": d.get("kernel"),
            "detail": d,
        }
        ab = d.get("kernel_ab")
        if ab is not None:
            # THE speedup line the A/B flag exists for
            out["kernel_speedup"] = ab.get("pallas_speedup_vs_xla")
        pref = d.get("prefix")
        if pref and pref.get("enabled"):
            # the two numbers the prefix cache exists for: reuse rate
            # and the pool occupancy it saved vs the cache-off arm
            out["prefix_hit_rate"] = pref.get("hit_rate")
            out["prefix_blocks_saved"] = pref.get("blocks_saved_peak")
        spec = d.get("speculation")
        if spec and spec.get("enabled"):
            # the bandwidth proxy the drafter exists for: accepted
            # fraction and full KV-streaming passes avoided
            out["spec_accept_rate"] = spec.get("accept_rate")
            out["spec_steps_saved"] = spec.get("steps_saved")
        sab = d.get("spec_ab")
        if sab is not None:
            # THE wall-clock line the spec A/B flag exists for
            out["spec_speedup"] = sab.get("spec_speedup_vs_off")
        mab = d.get("mixed_ab")
        if mab is not None:
            # THE numbers the mixed A/B flag exists for: the fraction
            # of model forwards the fused path saved per emitted token,
            # and the p99 first-token latency of both arms
            out["mixed_dispatch_reduction"] = mab.get(
                "dispatch_reduction")
            out["mixed_ttft_p99_ms"] = mab.get("ttft_p99_ms")
        reps = d.get("replicas")
        if reps is not None:
            # THE scale-out line the replica flag exists for: the routed
            # fleet's aggregate rate over the single engine's
            out["replica_speedup"] = reps.get("speedup_vs_single_replica")
        gp = d.get("goodput")
        if gp and gp.get("enabled"):
            # THE SLO numbers the workload/SLO knobs exist for: useful
            # (within-budget) tokens/sec and the fraction of requests
            # that met their deadline
            out["goodput_tokens_per_sec"] = gp.get(
                "goodput_tokens_per_sec")
            out["slo_attainment"] = gp.get("slo_attainment")
        if gp:
            # first-token latency rides the goodput block whether or
            # not an SLO was set — queueing + prefill delay is the
            # half of serving latency tokens/sec cannot see
            out["ttft_p50_ms"] = gp.get("ttft_p50_ms")
            out["ttft_p99_ms"] = gp.get("ttft_p99_ms")
        bd = d.get("breakdown")
        if bd and bd.get("enabled"):
            # THE phase numbers tracing exists for: where the tail of
            # attained latency actually goes (queued vs prefilling vs
            # decoding)
            out["queue_ms_p99"] = bd.get("queue_ms_p99")
            out["prefill_ms_p99"] = bd.get("prefill_ms_p99")
            out["decode_ms_p99"] = bd.get("decode_ms_p99")
        _print_json(out)
        return 0
    if args.mode == "decode":
        kind = (f"beam-{args.num_beams}" if args.num_beams > 0 else "greedy")
        v = d["decode_tokens_per_sec"]
        _print_json({
            "metric": f"GPT-base {kind} decode throughput "
                      "(KV cache)",
            "value": round(v, 1) if v == v else None,   # NaN -> null
            "unit": "tokens/sec",
            "vs_baseline": None,
            "detail": d,
        })
        return 0
    if args.mode == "allreduce":
        base = _load_baseline()
        vs = None
        if base.get("allreduce", {}).get("allreduce_ms"):
            # >1 means faster than the recorded baseline (time ratio)
            vs = round(base["allreduce"]["allreduce_ms"] / d["allreduce_ms"],
                       3)
        _print_json({
            "metric": "gradient allreduce step time",
            "value": round(d["allreduce_ms"], 3),
            "unit": "ms",
            "vs_baseline": vs,
            "detail": d,
        })
        return 0
    if args.model in _TRANSFORMER_MODELS:
        label = _BERT_LABELS.get(args.model, "BERT-base MLM")
        _print_json({
            "metric": f"{label} train-step throughput "
                      "(GSPMD, eval off timed path)",
            "value": round(d["tokens_per_sec_per_chip"], 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": None,   # no recorded reference-semantics baseline
            "detail": d,
        })
        return 0
    base = _load_baseline()
    vs = float("nan")
    if args.model == "mnist_cnn" and base.get("images_per_sec_per_chip"):
        # cross-platform (TPU build vs the CPU reference baseline) is the
        # north-star comparison and always valid.  Within one platform,
        # though, a scan-mode device-throughput number is not comparable
        # to a per-dispatch (host-latency-bound) one.
        same_platform = base.get("platform") == d.get("platform")
        same_mode = (base.get("scan_steps", 0) > 0) == \
            (d.get("scan_steps", 0) > 0)
        if not same_platform or same_mode:
            vs = (d["images_per_sec_per_chip"]
                  / base["images_per_sec_per_chip"])
    _print_json({
        "metric": f"{IMAGE_MODEL_NAMES[args.model]} train-step throughput "
                  "(eval off timed path)",
        "value": round(d["images_per_sec_per_chip"], 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs, 3) if vs == vs else None,
        "detail": d,
    })
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--record-baseline", action="store_true",
                    help="store this run as the comparison baseline "
                         "(reference-semantics single-process measurement)")
    ap.add_argument("--steps", type=int, default=None,
                    help="total timed iterations. Default: per-model "
                         "(MODEL_SPECS; 4000 MNIST train steps) or 50 "
                         "allreduce rounds")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="per-chip batch; default per-model (MODEL_SPECS)")
    ap.add_argument("--mode",
                    choices=["train", "allreduce", "decode", "hostio",
                             "serving"],
                    default="train")
    ap.add_argument("--requests", type=int, default=24,
                    help="serving mode: requests in the Poisson trace")
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="serving mode: Poisson arrival rate (req/s)")
    ap.add_argument("--serve-workload",
                    choices=["poisson", "bursty", "multi-tenant",
                             "diurnal"], default=None,
                    help="serving mode: synthetic trace shape "
                         "(serving/loadgen) — poisson (the historical "
                         "byte-identical default), bursty (2-state MMPP "
                         "on/off arrivals), multi-tenant (bursty "
                         "arrivals + interactive-vs-batch tenant mix "
                         "with per-tenant SLOs and sticky sessions), or "
                         "diurnal (raised-cosine rate envelope). "
                         "Default: the run Config's serve_workload")
    ap.add_argument("--serve-slo-ms", type=float, default=None,
                    help="serving mode: per-request latency budget — "
                         "stamped as each request's deadline (late work "
                         "sheds as deadline_exceeded) and the goodput "
                         "block scores tokens/sec from requests that "
                         "FINISHED within it, per tenant class "
                         "(default: no SLO — goodput reads as raw "
                         "delivered throughput)")
    ap.add_argument("--serve-trace", choices=["off", "on"],
                    default=None,
                    help="serving mode: request-lifecycle + step-phase "
                         "tracing (serving/tracing) — the detail gains "
                         "the span-derived `breakdown` block "
                         "(queue/prefill/decode/ttft percentiles) and a "
                         "trace summary; host clocks only, zero device "
                         "syncs, off is byte-for-byte untraced "
                         "(default: the run Config's serve_trace)")
    ap.add_argument("--serve-trace-out", type=str, default=None,
                    help="serving mode: write the timed run's Chrome "
                         "trace-event JSON here (open in Perfetto or "
                         "chrome://tracing); requires --serve-trace on")
    ap.add_argument("--serve-pool-blocks", type=int, default=None,
                    help="serving mode: paged-KV pool blocks (default: "
                         "every slot can reach max length — no "
                         "eviction churn; shrink to study pressure)")
    ap.add_argument("--serve-block-size", type=int, default=None,
                    help="serving mode: cache entries per pool block "
                         "(default: the run Config's serve_block_size)")
    ap.add_argument("--serve-deadline-ms", type=float, default=None,
                    help="serving mode: per-request TTL from arrival; "
                         "expired work fails with deadline_exceeded "
                         "(default: no deadline)")
    ap.add_argument("--serve-queue-depth", type=int, default=None,
                    help="serving mode: waiting-queue bound; a full "
                         "queue load-sheds the newest submit (default: "
                         "unbounded)")
    ap.add_argument("--serve-max-evictions", type=int, default=None,
                    help="serving mode: evictions allowed per request "
                         "before it fails with evicted_too_often "
                         "(default: unbounded)")
    ap.add_argument("--serve-drain-ms", type=float, default=None,
                    help="serving mode: graceful-drain budget after "
                         "SIGTERM (default: finish all in-flight work)")
    ap.add_argument("--serve-kernel", choices=["auto", "xla", "pallas"],
                    default=None,
                    help="serving mode: paged-attention lowering — auto "
                         "(fused Pallas decode kernel on TPU when its "
                         "compile probe passes, else the XLA gather "
                         "path), or force one side (default: the run "
                         "Config's serve_kernel)")
    ap.add_argument("--serve-kernel-ab", action="store_true",
                    help="serving mode: replay the same trace under "
                         "BOTH kernels (each with its own warmup and "
                         "zero-recompile probe) and emit the "
                         "pallas-vs-xla speedup line")
    ap.add_argument("--serve-kv-dtype", choices=["fp32", "int8", "int4"],
                    default=None,
                    help="serving mode: paged-pool storage format — "
                         "int8 stores symmetric-absmax codes plus "
                         "per-(block, head, slot) fp32 row scales "
                         "(~4x effective KV capacity at bf16 compute); "
                         "int4 packs two codes per byte plus per-group "
                         "fp32 scales (--serve-kv-group) with an fp "
                         "self-residual lane for the in-step token "
                         "(~6x); both dequantized inside the attention "
                         "consume paths, greedy outputs gated on "
                         "token-match rate vs fp32 (default: the run "
                         "Config's serve_kv_dtype)")
    ap.add_argument("--serve-kv-group", type=int, default=None,
                    help="serving mode: int4 quantization group width "
                         "along the head dim — one fp32 scale per "
                         "group (clamped to head_dim; smaller = finer "
                         "scales = more accurate and more scale "
                         "traffic) (default: the run Config's "
                         "serve_kv_group)")
    ap.add_argument("--serve-kv-tier", choices=["off", "host"],
                    default=None,
                    help="serving mode: KV block tiering — host "
                         "demotes cold prefix-cache blocks to host RAM "
                         "on eviction and promotes them back on a "
                         "prefix match before first dispatch (requires "
                         "--serve-prefix-cache on; reported in the "
                         "tier block) (default: the run Config's "
                         "serve_kv_tier)")
    ap.add_argument("--serve-kv-ab", action="store_true",
                    help="serving mode: replay the same trace under "
                         "BOTH pool formats (the quantized rung from "
                         "--serve-kv-dtype — int8 when unset/fp32 — "
                         "and its fp32 reference, each with its own "
                         "warmup and zero-recompile probe) and emit "
                         "the kv_quant block — token-match rate vs "
                         "fp32, effective-capacity multiplier, "
                         "peak-live-blocks delta, and the bytes-per-"
                         "decode-token roofline at quantized bytes")
    ap.add_argument("--serve-journal", default=None,
                    help="serving mode: fault-tolerant serve — journal "
                         "each request's prompt + generated prefix here "
                         "and, when the file already exists (a prior "
                         "run crashed), resume by replaying live "
                         "sequences token-identically.  Skips the "
                         "warmup replay and the static-batch arm")
    ap.add_argument("--serve-prefix-cache", choices=["off", "on"],
                    default=None,
                    help="serving mode: radix prefix cache — on shares "
                         "cached full prompt blocks across requests "
                         "(refcounted, copy-on-write) and ALSO replays "
                         "the trace through a cache-off control arm for "
                         "the occupancy delta (default: the run "
                         "Config's serve_prefix_cache)")
    ap.add_argument("--serve-prefix-tokens", type=int, default=0,
                    help="serving mode: prepend one common N-token "
                         "system prompt to every request — the shared-"
                         "prefix workload the prefix cache exists for "
                         "(0 = all-unique prompts, the historical "
                         "trace)")
    ap.add_argument("--serve-prefix-gen", choices=["off", "on"],
                    default=None,
                    help="serving mode: prefix cache v2 — on caches a "
                         "finished request's GENERATED blocks and "
                         "shares partial tail blocks, and adds a "
                         "seeded multi-turn arm (follow-up prompts "
                         "embed the prior answer) with a gen-off "
                         "control for the hit-rate gain and token "
                         "identity; requires --serve-prefix-cache on "
                         "(default: the run Config's serve_prefix_gen)")
    ap.add_argument("--serve-prefix-route", choices=["off", "on"],
                    default=None,
                    help="serving mode: prefix-aware fleet routing — "
                         "on adds a 2-replica arm placing requests by "
                         "cached leading block (load-bounded hint) vs "
                         "a least-load-only control, reporting router "
                         "prefix hits, the aggregate hit-rate gain, "
                         "and token identity; requires "
                         "--serve-prefix-cache on (default: the run "
                         "Config's serve_prefix_route)")
    ap.add_argument("--serve-speculative",
                    choices=["off", "ngram", "draft-model"], default=None,
                    help="serving mode: speculative decoding — draft k "
                         "tokens (ngram self-draft or a tiny draft "
                         "model over its own paged pool) and verify "
                         "them in ONE forward, emitting only the "
                         "argmax-matching prefix (token-identical to "
                         "off by construction).  Runs the workload on "
                         "rope positions so the untrained model's "
                         "greedy stream is recurrent — the templated-"
                         "traffic stand-in (default: the run Config's "
                         "serve_speculative)")
    ap.add_argument("--serve-draft-k", type=int, default=None,
                    help="serving mode: speculative draft window — "
                         "tokens proposed per verify forward; >= 1 "
                         "(default: the run Config's serve_draft_k)")
    ap.add_argument("--serve-draft-auto", choices=["off", "on"],
                    default=None,
                    help="serving: auto-tune the speculative draft "
                         "window from the observed accept rate (EWMA, "
                         "clamped to [1, --serve-draft-k]; the "
                         "speculation block reports effective_k). "
                         "Default: the run Config's serve_draft_auto")
    ap.add_argument("--serve-tp", type=int, default=None,
                    help="serving: tensor-parallel shards for the "
                         "decode engine — shard the paged pool's head "
                         "axis, QKV/O, and MLP over a tp mesh axis "
                         "(serving/tp); must divide the model's heads/"
                         "mlp and fit the visible device count "
                         "(default: the run Config's serve_tp)")
    ap.add_argument("--serve-replicas", type=int, default=None,
                    help="serving: run an additional data-parallel arm "
                         "— the same trace through N engine replicas "
                         "behind the serving router (session affinity "
                         "+ least-load placement, one thread per "
                         "replica), reporting per-replica queue depth/"
                         "occupancy/shed rate/tokens-per-sec and the "
                         "aggregate-vs-single speedup")
    ap.add_argument("--serve-fault-replica", type=int, default=None,
                    help="serving: inject one replica fault into the "
                         "routed arm — kill this replica (index into "
                         "--serve-replicas) and fail its work over to "
                         "the survivors; outputs must stay token-"
                         "identical (the fleet determinism pin)")
    ap.add_argument("--serve-fault-step", type=int, default=None,
                    help="serving: the replica tick the injected fault "
                         "fires at (pair with --serve-fault-replica)")
    ap.add_argument("--serve-fault-kind",
                    choices=["transient", "permanent"],
                    default="transient",
                    help="serving: injected fault class — transient "
                         "(replica ejected, probed back in after "
                         "backoff) or permanent (stays dead)")
    ap.add_argument("--serve-spec-ab", action="store_true",
                    help="serving mode: TIME the speculation-off "
                         "control arm too (own warmup, own zero-"
                         "recompile probe) and emit the spec_speedup "
                         "line — mirrors --serve-kernel-ab and is "
                         "mutually exclusive with it")
    ap.add_argument("--serve-mixed-batch", choices=["off", "on"],
                    default=None,
                    help="serving mode: stall-free mixed batching — on "
                         "fuses budget-capped prefill chunks from "
                         "multiple mid-prefill requests into the decode "
                         "dispatch (ONE forward per step instead of a "
                         "prefill forward plus a decode forward), "
                         "token-identical to off by construction; "
                         "mutually exclusive with --serve-speculative "
                         "(both replace the decode dispatch) (default: "
                         "the run Config's serve_mixed_batch)")
    ap.add_argument("--serve-prefill-budget", type=int, default=None,
                    help="serving mode: max prefill tokens fused into "
                         "one mixed step — bounds each decode token's "
                         "latency cost; consumed only with "
                         "--serve-mixed-batch on (default: the run "
                         "Config's serve_prefill_budget)")
    ap.add_argument("--serve-mixed-ab", action="store_true",
                    help="serving mode: TIME a mixed-off control arm "
                         "too (own warmup, own zero-recompile probe) "
                         "and emit the mixed_ab block — per-arm "
                         "dispatches-per-emitted-token (the fused path "
                         "must be strictly lower), per-arm TTFT "
                         "percentiles, and token identity; mirrors "
                         "--serve-kernel-ab and is mutually exclusive "
                         "with every other A/B or control-arm mode")
    ap.add_argument("--serve-tiny", action="store_true",
                    help="serving mode: BERT_TINY model geometry — the "
                         "smoke/fault-injection configuration, not a "
                         "benchmark number")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="decode mode: prompt length")
    ap.add_argument("--new-tokens", type=int, default=128,
                    help="decode mode: generated tokens per call")
    ap.add_argument("--num-beams", type=int, default=0,
                    help="decode mode: time beam_search at this width "
                         "instead of greedy generate (0 = greedy)")
    ap.add_argument("--model", choices=list(MODEL_SPECS), default="mnist_cnn",
                    help="which BASELINE config to measure (train mode)")
    ap.add_argument("--scan-steps", type=int, default=None,
                    help="steps fused per dispatch via lax.scan (0 = one "
                         "dispatch per step, the reference's shape, which "
                         "times host dispatch as much as device compute)")
    ap.add_argument("--payload-mb", type=float, default=25.4)
    ap.add_argument("--ce", choices=["auto", "dense", "chunked"],
                    default="auto",
                    help="BERT MLM loss implementation (models/bert.py "
                         "ce_impl): chunked = online-logsumexp vocab tiles, "
                         "never materializing (B,S,V) fp32 logits")
    ap.add_argument("--ce-chunk", type=int, default=2048,
                    help="vocab tile width for --ce chunked")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="sequence length for the transformer families "
                         "(default per-model, 128).  Long sequences are "
                         "where the flash attention kernels earn their "
                         "keep — pair with a smaller --batch-size")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize residual blocks / encoder layers "
                         "(frees HBM for larger batches)")
    ap.add_argument("--remat-policy", choices=["full", "dots"],
                    default="full",
                    help="what a rematted transformer layer saves: full "
                         "= nothing (max recompute), dots = keep matmul "
                         "outputs, recompute only elementwise (MXU work "
                         "not repeated)")
    ap.add_argument("--flash-min-seq", type=int, default=None,
                    help="engage the Pallas flash-attention kernel only at "
                         "seq_len >= this (default: the model's measured "
                         "crossover, models/bert.py flash_min_seq; 0 = "
                         "always engage — the kernel A/B arm)")
    ap.add_argument("--prng", choices=["threefry", "rbg", "unsafe_rbg"],
                    default="threefry",
                    help="dropout-mask PRNG for the timed step: threefry "
                         "(JAX default) or rbg/unsafe_rbg (XLA "
                         "RngBitGenerator — cheaper mask generation; a BERT "
                         "step generates 25 (B,S,E) masks)")
    ap.add_argument("--fused-qkv", action="store_true",
                    help="compute q,k,v via one stacked (E,3HD) matmul per "
                         "layer instead of three (transformer families)")
    ap.add_argument("--params-bf16", action="store_true",
                    help="store live parameters in bfloat16 with fp32 "
                         "master weights in the optimizer (halves weight "
                         "HBM traffic; BERT/MoE path)")
    ap.add_argument("--precision", choices=["fp32", "bf16"], default="fp32",
                    help="compute dtype for the timed train step. fp32 is "
                         "the like-for-like reference comparison AND the "
                         "faster choice for this HBM-bound CNN (measured: "
                         "bf16 adds cast overhead at batch 64); bf16 pays "
                         "off on the MXU-bound families (BERT/ResNet-50), "
                         "convergence pinned by tests/test_precision.py.")
    args = ap.parse_args(argv)

    if args.seq_len is not None:
        if args.mode != "train" or args.model not in (
                "bert_base", "moe_bert", "gpt_base", "encdec_t5"):
            ap.error("--seq-len applies to the transformer families in "
                     "train mode only (decode uses --prompt-len/"
                     "--new-tokens)")
        if args.seq_len < 1:
            ap.error(f"--seq-len must be >= 1, got {args.seq_len}")

    if args.fused_qkv and (args.mode != "train" or args.model not in
                           ("bert_base", "moe_bert", "gpt_base", "encdec_t5")):
        ap.error("--fused-qkv applies to the transformer families in train "
                 "mode only — other paths would silently ignore it")
    if args.serve_prefix_tokens < 0:
        ap.error(f"--serve-prefix-tokens must be >= 0, got "
                 f"{args.serve_prefix_tokens}")
    if (args.serve_prefix_tokens or args.serve_prefix_cache is not None) \
            and args.mode != "serving":
        ap.error("--serve-prefix-cache/--serve-prefix-tokens shape the "
                 "serving trace; other modes would silently ignore them")
    if args.serve_prefix_cache == "on" and args.serve_kernel_ab:
        ap.error("--serve-prefix-cache on already adds its own cache-off "
                 "control arm; combine with --serve-kernel-ab one at a "
                 "time so each comparison has a single variable")
    if (args.serve_prefix_gen is not None
            or args.serve_prefix_route is not None) \
            and args.mode != "serving":
        ap.error("--serve-prefix-gen/--serve-prefix-route shape the "
                 "serving arms; other modes would silently ignore them")
    if args.serve_prefix_gen == "on" and args.serve_prefix_cache != "on":
        ap.error("--serve-prefix-gen on extends the radix prefix cache; "
                 "it needs --serve-prefix-cache on")
    if args.serve_prefix_route == "on" \
            and args.serve_prefix_cache != "on":
        ap.error("--serve-prefix-route on routes by cached prefixes; it "
                 "needs --serve-prefix-cache on")
    if args.serve_prefix_route == "on" \
            and (args.serve_replicas or 1) > 1:
        ap.error("--serve-prefix-route on adds its own 2-replica "
                 "hint-on-vs-off routing arm; combining it with "
                 "--serve-replicas would run two fleets in one bench — "
                 "pick one")
    if args.serve_draft_k is not None and args.serve_draft_k < 1:
        ap.error(f"--serve-draft-k must be >= 1, got "
                 f"{args.serve_draft_k}")
    if (args.serve_speculative is not None
            or args.serve_draft_k is not None or args.serve_spec_ab) \
            and args.mode != "serving":
        ap.error("--serve-speculative/--serve-draft-k/--serve-spec-ab "
                 "shape the serving trace; other modes would silently "
                 "ignore them")
    if args.serve_spec_ab and args.serve_kernel_ab:
        ap.error("--serve-spec-ab and --serve-kernel-ab each replay the "
                 "trace through their own control arm; one comparison, "
                 "one variable — pick one")
    if (args.serve_tp is not None or args.serve_replicas is not None
            or args.serve_draft_auto is not None) \
            and args.mode != "serving":
        ap.error("--serve-tp/--serve-replicas/--serve-draft-auto shape "
                 "the serving trace; other modes would silently ignore "
                 "them")
    if args.serve_tp is not None and args.serve_tp < 1:
        ap.error(f"--serve-tp must be >= 1, got {args.serve_tp}")
    if args.serve_replicas is not None and args.serve_replicas < 1:
        ap.error(f"--serve-replicas must be >= 1, got "
                 f"{args.serve_replicas}")
    if args.serve_replicas is not None and args.serve_replicas > 1 \
            and (args.serve_kernel_ab or args.serve_spec_ab
                 or args.serve_kv_ab):
        # NOTE: --serve-replicas + --serve-journal is now a SUPPORTED
        # combination (the fault-tolerant fleet serve mode with one
        # journal per replica); only the two-timed-arms A/B modes stay
        # mutually exclusive with the routed arm
        ap.error("--serve-replicas adds its own routed arm (aggregate "
                 "vs single engine); combine with --serve-kernel-ab/"
                 "--serve-spec-ab/--serve-kv-ab one at a time")
    if (args.serve_kv_dtype is not None or args.serve_kv_ab
            or args.serve_kv_group is not None
            or args.serve_kv_tier is not None) \
            and args.mode != "serving":
        ap.error("--serve-kv-dtype/--serve-kv-group/--serve-kv-tier/"
                 "--serve-kv-ab shape the serving pool; other modes "
                 "would silently ignore them")
    if args.serve_kv_group is not None and args.serve_kv_group < 1:
        ap.error(f"--serve-kv-group must be >= 1, got "
                 f"{args.serve_kv_group}")
    if args.serve_kv_tier == "host" and args.serve_prefix_cache != "on":
        ap.error("--serve-kv-tier host demotes and re-admits blocks "
                 "through the radix prefix cache's eviction/match "
                 "hooks; turn it on with --serve-prefix-cache on")
    if args.serve_kv_ab and (args.serve_kernel_ab or args.serve_spec_ab):
        ap.error("--serve-kv-ab, --serve-kernel-ab and --serve-spec-ab "
                 "each replay the trace through their own control arm; "
                 "one comparison, one variable — pick one")
    if args.serve_kv_ab and args.serve_journal:
        ap.error("--serve-kv-ab is a measurement (two timed arms); the "
                 "journaled serve mode is not — pick one")
    if args.serve_kv_ab and args.serve_prefix_cache == "on":
        ap.error("--serve-prefix-cache on already adds its own "
                 "cache-off control arm; combine with --serve-kv-ab "
                 "one at a time so each comparison has a single "
                 "variable")
    if args.serve_kv_ab and args.serve_speculative not in (None, "off"):
        ap.error("--serve-speculative already adds its own off control "
                 "arm; combine with --serve-kv-ab one at a time so "
                 "each comparison has a single variable")
    if (args.serve_workload is not None or args.serve_slo_ms is not None) \
            and args.mode != "serving":
        ap.error("--serve-workload/--serve-slo-ms shape the serving "
                 "trace; other modes would silently ignore them")
    if (args.serve_trace is not None or args.serve_trace_out is not None) \
            and args.mode != "serving":
        ap.error("--serve-trace/--serve-trace-out instrument the "
                 "serving loop; other modes would silently ignore them")
    if args.serve_trace_out is not None and args.serve_trace != "on":
        ap.error("--serve-trace-out writes the Chrome trace the tracer "
                 "collects; it needs --serve-trace on")
    if args.serve_slo_ms is not None and not args.serve_slo_ms > 0:
        ap.error(f"--serve-slo-ms must be > 0, got {args.serve_slo_ms}")
    if (args.serve_fault_replica is not None
            or args.serve_fault_step is not None
            or args.serve_fault_kind != "transient") \
            and args.mode != "serving":
        ap.error("--serve-fault-* inject a replica fault into the "
                 "serving fleet; other modes would silently ignore "
                 "them")
    if (args.serve_fault_replica is None) != (args.serve_fault_step
                                              is None):
        ap.error("--serve-fault-replica and --serve-fault-step name "
                 "one injected fault together — set both or neither")
    if args.serve_fault_replica is not None \
            and (args.serve_replicas is None or args.serve_replicas < 2):
        ap.error("--serve-fault-* need --serve-replicas >= 2 so a "
                 "survivor can take the migrated work")
    if args.serve_draft_auto == "on" \
            and args.serve_speculative in (None, "off"):
        ap.error("--serve-draft-auto on tunes the speculative draft "
                 "window; pick a drafter with --serve-speculative "
                 "ngram|draft-model")
    if args.serve_spec_ab and args.serve_speculative in (None, "off"):
        ap.error("--serve-spec-ab compares speculative decoding against "
                 "its off arm; pick a drafter with --serve-speculative "
                 "ngram|draft-model")
    if args.serve_speculative not in (None, "off") and args.serve_kernel_ab:
        ap.error("--serve-speculative already adds its own off control "
                 "arm; combine with --serve-kernel-ab one at a time so "
                 "each comparison has a single variable")
    if (args.serve_mixed_batch is not None
            or args.serve_prefill_budget is not None
            or args.serve_mixed_ab) and args.mode != "serving":
        ap.error("--serve-mixed-batch/--serve-prefill-budget/"
                 "--serve-mixed-ab shape the serving step structure; "
                 "other modes would silently ignore them")
    if args.serve_prefill_budget is not None \
            and args.serve_prefill_budget < 1:
        ap.error(f"--serve-prefill-budget must be >= 1, got "
                 f"{args.serve_prefill_budget}")
    if args.serve_mixed_batch == "on" \
            and args.serve_speculative not in (None, "off"):
        ap.error("--serve-mixed-batch on and --serve-speculative each "
                 "replace the decode dispatch with their own fused "
                 "forward; they do not compose — pick one")
    if args.serve_mixed_ab and args.serve_mixed_batch in (None, "off"):
        ap.error("--serve-mixed-ab compares mixed batching against its "
                 "off arm; turn the fused path on with "
                 "--serve-mixed-batch on")
    if args.serve_mixed_ab and (args.serve_kernel_ab or args.serve_spec_ab
                                or args.serve_kv_ab):
        ap.error("--serve-mixed-ab, --serve-kernel-ab, --serve-spec-ab "
                 "and --serve-kv-ab each replay the trace through their "
                 "own control arm; one comparison, one variable — pick "
                 "one")
    if args.serve_mixed_ab and args.serve_journal:
        ap.error("--serve-mixed-ab is a measurement (two timed arms); "
                 "the journaled serve mode is not — pick one")
    if args.serve_mixed_ab and (args.serve_replicas or 1) > 1:
        ap.error("--serve-replicas adds its own routed arm (aggregate "
                 "vs single engine); combining it with --serve-mixed-ab "
                 "would change two variables in one comparison — pick "
                 "one")
    if args.serve_mixed_ab and args.serve_prefix_cache == "on":
        ap.error("--serve-prefix-cache on already adds its own "
                 "cache-off control arm; combine with --serve-mixed-ab "
                 "one at a time so each comparison has a single "
                 "variable")
    if args.prng != "threefry" and args.mode != "train":
        ap.error("--prng shapes the training dropout stream; decode/"
                 "allreduce modes have no dropout and would silently "
                 "ignore it")
    if args.prng != "threefry" and args.record_baseline:
        ap.error("--record-baseline stores the canonical reference-"
                 "semantics run; keep the default threefry stream")
    if args.remat_policy != "full" and not args.remat:
        ap.error("--remat-policy only applies with --remat")
    if args.remat_policy != "full" and (
            args.mode != "train" or args.model not in
            ("bert_base", "moe_bert", "gpt_base", "encdec_t5")):
        ap.error("--remat-policy applies to the transformer families in "
                 "train mode only — other paths would silently ignore it")
    if args.flash_min_seq is not None and (
            args.mode != "train" or args.model not in
            ("bert_base", "moe_bert", "gpt_base", "encdec_t5")):
        ap.error("--flash-min-seq applies to the transformer families in "
                 "train mode only — other paths would silently ignore it")

    if args.mode == "hostio":
        # host-only: no device involved, so no backend is touched
        r = measure_hostio(batch_size=args.batch_size or 32)
        _print_json({
            "metric": "host input pipeline (resnet50-shaped feed)",
            "value": round(r["host_images_per_sec"], 1),
            "unit": "images/sec (host)",
            "vs_baseline": None,
            "detail": r,
        })
        return 0

    from mpi_tensorflow_tpu.utils import cache as cache_lib
    from mpi_tensorflow_tpu.utils import logging as logs
    from mpi_tensorflow_tpu.utils.profiling import device_identity

    cache_lib.enable_compile_cache()
    # stdout carries the ONE JSON line; the banner goes to stderr
    logs.device_banner(device_identity(), file=sys.stderr)

    if args.mode == "serving":
        r = measure_serving(num_requests=args.requests,
                            rate_rps=args.arrival_rate,
                            max_slots=args.batch_size,
                            pool_blocks=args.serve_pool_blocks,
                            block_size=args.serve_block_size,
                            prompt_max=args.prompt_len,
                            output_max=args.new_tokens,
                            precision=args.precision,
                            deadline_ms=args.serve_deadline_ms,
                            queue_depth=args.serve_queue_depth,
                            max_evictions=args.serve_max_evictions,
                            drain_ms=args.serve_drain_ms,
                            journal=args.serve_journal,
                            tiny=args.serve_tiny,
                            kernel=args.serve_kernel,
                            kernel_ab=args.serve_kernel_ab,
                            kv_dtype=args.serve_kv_dtype,
                            kv_group=args.serve_kv_group,
                            kv_tier=args.serve_kv_tier,
                            kv_ab=args.serve_kv_ab,
                            prefix_cache=args.serve_prefix_cache,
                            prefix_tokens=args.serve_prefix_tokens,
                            prefix_gen=args.serve_prefix_gen,
                            prefix_route=args.serve_prefix_route,
                            speculative=args.serve_speculative,
                            draft_k=args.serve_draft_k,
                            spec_ab=args.serve_spec_ab,
                            draft_auto=args.serve_draft_auto,
                            mixed=args.serve_mixed_batch,
                            prefill_budget=args.serve_prefill_budget,
                            mixed_ab=args.serve_mixed_ab,
                            tp=args.serve_tp,
                            replicas=args.serve_replicas,
                            fault_replica=args.serve_fault_replica,
                            fault_step=args.serve_fault_step,
                            fault_kind=args.serve_fault_kind,
                            workload=args.serve_workload,
                            slo_ms=args.serve_slo_ms,
                            trace_mode=args.serve_trace,
                            trace_out=args.serve_trace_out)
        return _report(args, r)

    if args.mode == "decode":
        r = measure_decode(batch_size=args.batch_size or 8,
                           prompt_len=args.prompt_len,
                           new_tokens=args.new_tokens,
                           precision=args.precision,
                           iters=max(1, (args.steps or 5)),
                           num_beams=args.num_beams)
        return _report(args, r)

    if args.mode == "allreduce":
        r = measure_allreduce(payload_mb=args.payload_mb,
                              iters=args.steps or 50)
        if args.record_baseline:
            _record_baseline("allreduce", r)
            return 0
        return _report(args, r)

    if args.record_baseline and args.precision != "fp32":
        # the recorded baseline is by definition the fp32 reference-semantics
        # measurement; recording bf16 numbers would silently invert every
        # later vs_baseline comparison
        ap.error("--record-baseline requires fp32 (it records the "
                 "reference-semantics baseline)")
    if args.record_baseline and args.model != "mnist_cnn":
        # same hazard for the model: the recorded baseline is the MNIST
        # reference semantics; writing another model's flat keys over it
        # would silently corrupt every later vs_baseline comparison
        ap.error("--record-baseline records the MNIST reference baseline; "
                 "drop --model or use mnist_cnn")

    if args.params_bf16 and args.precision != "bf16":
        # bf16 live params under fp32 compute would silently benchmark
        # bf16-rounded weights while reporting precision=fp32
        ap.error("--params-bf16 requires --precision bf16 (fp32 compute "
                 "with bf16-truncated weights is not the fp32 baseline)")
    if args.params_bf16 and args.model not in (
            "bert_base", "moe_bert", "gpt_base", "encdec_t5"):
        ap.error("--params-bf16 is implemented for the transformer families "
                 "(bert_base, moe_bert, gpt_base, encdec_t5) only — the "
                 "image paths would silently ignore it")

    spec = MODEL_SPECS[args.model]
    batch = args.batch_size if args.batch_size is not None else spec["batch"]
    steps = args.steps or spec["steps"]
    scan = args.scan_steps if args.scan_steps is not None else spec["scan"]

    if args.model in ("bert_base", "moe_bert", "gpt_base", "encdec_t5"):
        result = measure_bert(batch_size=batch, steps=steps,
                              precision=args.precision, scan_steps=scan,
                              seq_len=(args.seq_len if args.seq_len is not None
                                       else spec["seq"]),
                              ce_impl=args.ce,
                              ce_chunk=args.ce_chunk, model_name=args.model,
                              remat=args.remat, params_bf16=args.params_bf16,
                              prng_impl=args.prng, fused_qkv=args.fused_qkv,
                              flash_min_seq=args.flash_min_seq,
                              remat_policy=args.remat_policy)
        return _report(args, result)

    result = measure(batch_size=batch, steps=steps,
                     precision=args.precision, scan_steps=scan,
                     model_name=args.model, remat=args.remat,
                     prng_impl=args.prng)

    if args.record_baseline:
        _record_baseline("train", result)
        return 0
    return _report(args, result)


if __name__ == "__main__":
    sys.exit(main())
