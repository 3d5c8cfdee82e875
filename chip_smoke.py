#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                  # on a machine with a TPU
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearsal    # tier-1, CPU

Drives both halves of the system once, end to end, through the entry
points a user would call, at the full width of models the repo has
(weights random, from a seed):

- ``mnist``    ``cli.main(["--epochs", "1"])`` — the reference path:
               MNIST CNN, shard_map + explicit psum, every visible chip
               on ``data``;
- ``bert``     ``cli.main(["--model", "bert_base", "--precision",
               "bf16", "--epochs", "1"])`` — BERT-base 12x768, b64/chip,
               s128, the GSPMD step, every visible chip on ``data``;
- ``server``   ``python -m mpi_tensorflow_tpu.serving``'s ``main(
               ["--precision", "bf16", "--kernel", K])`` for K in auto,
               pallas — gpt_base
               behind the paged-KV engine on the default 24-request
               Poisson trace; both must serve through the Mosaic-compiled
               Pallas kernel;
- ``kernels``  every paged-attention variant (bf16/fp32/int8/int4 pools,
               decode + every prefill bucket) against
               ``attend(kernel="xla")``; decode at the served geometry
               (128 rows, bf16 pool) under each pre-warmed table width
               (8, 16, 32, 64), where the decode body's hand-issued,
               double-buffered group copies run for hundreds of steps —
               a semaphore left unwaited or a slot reused too soon shows
               only under Mosaic; and the flash kernel fwd+bwd at
               S=4096 against ``ring.dense_attention``.

Each phase checks what came out (finite loss, expected step count, all
requests ``ok`` with the requested token count, zero steady-state
recompiles, the engaged paths, kernel-vs-reference agreement within a
stated tolerance).  Nothing is caught: a phase that fails raises, the
run stops, the exit code is non-zero and no result line is printed.

ONE process does everything, strictly one phase after another — a chip
belongs to one process at a time, so no child that needs the chip is
ever started (the only child is ``make`` building the native loaders).
The first JAX call is ``jax.devices()``; the script never sets
``JAX_PLATFORMS``.  With no argument it REQUIRES a TPU whose
``device_kind`` is in ``utils/flops.DEVICE_PEAKS`` and exits non-zero
before any phase otherwise.  ``--rehearsal`` is the CPU control-flow
check at tiny sizes (the same phases, Pallas in interpret mode); every
line it prints starts with ``[rehearsal platform=cpu]`` and it never
prints the bare result line, so it cannot pass for a chip run.

The compile cache is ``utils/cache.enable_compile_cache``'s
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``);
data goes to a temp dir that is removed, metrics and ``summary.json`` to
``--out`` (default ``chiprun_out/chip_smoke``).  Seconds printed here are
set-up facts of this run, not metrics.

Last stdout line of a passing chip run:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import functools
import importlib.metadata
import io
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
KILL_SWITCHES = ("MPI_TF_TPU_DISABLE_FLASH", "MPI_TF_TPU_DISABLE_PAGED_KERNEL")
TIME_LIMIT_S = 1150          # the contract's 1200 s, minus start-up slack
REHEARSAL_TAG = "[rehearsal platform=cpu] "

# served geometry of gpt_base (BERT-base heads) under the default
# ServeConfig: the shapes the kernel phase compiles and checks
HEADS, HEAD_DIM, BLOCK = 12, 64, 16
PAGED_VARIANTS = (("bfloat16", "bf16"), ("float32", "fp32"),
                  ("bfloat16", "int8"), ("bfloat16", "int4"),
                  ("bfloat16", "int4+residual"))
# |kernel - xla| elementwise on O(1) outputs.  bf16 compute: two bf16
# ulps at magnitude 2-4.  fp32 on TPU: both lowerings run fp32 matmuls
# at the default (bf16-pass) precision with different summation orders;
# only fp32 on CPU is exact enough for the tight bound.
PAGED_ATOL, PAGED_ATOL_CPU_FP32 = 3e-2, 1e-4
# max|dg - dg_ref| / max|dg_ref| for bf16 at S=4096 (observed on the v5e:
# 0.7% non-causal, 2.6% causal — bf16 probabilities summed over 4096 keys)
FLASH_GRAD_RTOL = 5e-2


class _Stream(io.TextIOBase):
    """stdout/stderr wrapper: prefixes every line (rehearsal) and can
    record what passes through (to read the serving entry point's JSON
    line)."""

    def __init__(self, stream, prefix: str = ""):
        self._stream, self._prefix = stream, prefix
        self._bol = True
        self.record = None

    def write(self, text: str) -> int:
        if self.record is not None:
            self.record.append(text)
        for piece in text.splitlines(keepends=True):
            if self._bol and self._prefix:
                self._stream.write(self._prefix)
            self._stream.write(piece)
            self._bol = piece.endswith("\n")
        return len(text)

    def flush(self) -> None:
        self._stream.flush()


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMeter:
    """Sums jax.monitoring's compile events between ``mark()`` calls."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        # XLA/Mosaic compile, or the cache retrieval that replaced it
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return (self.seconds, self.hits, self.misses)


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter, summary: dict):
    """Time one phase; on success record its facts.  An exception passes
    straight through — a failed phase ends the run."""
    say(f"phase {name}: start")
    facts: dict = {}
    t0, (c0, h0, m0) = time.perf_counter(), meter.mark()
    yield facts
    wall, (c1, h1, m1) = time.perf_counter() - t0, meter.mark()
    facts.update(ok=True, wall_s=round(wall, 2),
                 compile_s=round(c1 - c0, 2),
                 run_s=round(wall - (c1 - c0), 2),
                 cache_hits=h1 - h0, cache_misses=m1 - m0)
    summary["phases"][name] = facts
    say(f"phase {name}: ok {json.dumps(facts, sort_keys=True)}")


@contextlib.contextmanager
def capture_call(module, name: str, **forced_kwargs):
    """Let the real entry point run ``module.name`` and keep what it
    returned (cli.main drops the trainer's result).  ``forced_kwargs``
    is how the rehearsal shrinks a model no CLI flag can shrink."""
    real = getattr(module, name)
    box: dict = {}

    @functools.wraps(real)
    def wrapper(*a, **k):
        box["result"] = real(*a, **{**k, **forced_kwargs})
        return box["result"]

    setattr(module, name, wrapper)
    try:
        yield box
    finally:
        setattr(module, name, real)


def cache_entry_count(cache) -> int:
    """Files under the compile-cache directory (0 for None or missing)."""
    if not cache or not os.path.isdir(cache):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache))


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def read_metrics(metrics_dir: str) -> dict:
    """``{tag: [(step, value), ...]}`` from the trainer's metrics.jsonl."""
    out: dict = {}
    with open(os.path.join(metrics_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            out.setdefault(rec["tag"], []).append((rec["step"], rec["value"]))
    return out


def check_all_devices_hold(state, devices, facts: dict) -> None:
    """Every trainer-state leaf lives on every visible device, and (where
    the backend reports memory) every device has held bytes."""
    import jax

    from mpi_tensorflow_tpu.utils import profiling

    want = set(devices)
    leaves = [x for x in jax.tree.leaves(state) if hasattr(x, "sharding")]
    require(bool(leaves), "trainer state has array leaves")
    for x in leaves:
        require(set(x.sharding.device_set) == want,
                f"state leaf {x.shape} on {len(x.sharding.device_set)} of "
                f"{len(want)} devices")
    facts["state_leaves"] = len(leaves)
    facts["state_devices"] = len(want)
    peaks = [m["peak_bytes"] for m in profiling.device_memory_stats()]
    facts["device_peak_bytes"] = peaks
    if devices[0].platform == "tpu":
        require(all(p and p > 0 for p in peaks),
                f"every device held memory (peak bytes {peaks})")


# ------------------------------------------------------------- phases

def run_mnist(out: str, data: str, rehearsal: bool, devices, facts) -> None:
    from mpi_tensorflow_tpu import cli
    from mpi_tensorflow_tpu.data import mnist, native
    from mpi_tensorflow_tpu.train import loop

    mdir = os.path.join(out, "mnist_metrics")
    argv = ["--epochs", "1", "--data-dir", data, "--metrics-dir", mdir]
    forced = {}
    if rehearsal:
        # a small synthetic set in place of the 60k-row one (no CLI flag
        # shrinks an epoch); the chip run lets the entry point fetch or
        # synthesize the real shapes
        mnist._write_synthetic(data, train_n=600, test_n=128)
        forced = dict(splits=mnist.load_splits(
            data, len(devices), train_n=600, test_n=128))
        argv += ["--batch-size", "16", "--log-every", "10"]
    with capture_call(loop, "train", **forced) as box:
        require(cli.main(argv) == 0, "cli.main (mnist) returned 0")
    res = box["result"]
    errs = read_metrics(mdir)["eval/test_error_pct"]
    require(errs[-1][0] == res.num_steps - 1,
            f"last trace at the final step {res.num_steps - 1}: {errs[-1]}")
    require(res.num_devices == len(devices),
            f"trainer used {res.num_devices} of {len(devices)} devices")
    # the synthetic classes are separable and real MNIST reaches ~1-2% in
    # an epoch: anything near chance (90%) means the step does not learn
    require(errs[-1][1] is not None and errs[-1][1] < 10.0,
            f"final test error {errs[-1][1]}% < 10%")
    check_all_devices_hold(res.state, devices, facts)
    facts.update(steps=res.num_steps, final_test_error_pct=errs[-1][1],
                 idx_loader="native" if native.available() else "numpy")
    say(f"idx loader: {facts['idx_loader']} "
        f"(native/*.so are built from source by make when absent)")


def run_bert(out: str, data: str, rehearsal: bool, devices, facts) -> None:
    import math

    from mpi_tensorflow_tpu import cli
    from mpi_tensorflow_tpu.models import bert
    from mpi_tensorflow_tpu.train import mlm_loop
    from mpi_tensorflow_tpu.utils import engagement

    mdir = os.path.join(out, "bert_metrics")
    argv = ["--model", "bert_base", "--precision", "bf16", "--epochs", "1",
            "--data-dir", data, "--metrics-dir", mdir]
    forced, batch, train_n = {}, 64, 4096
    if rehearsal:
        import dataclasses

        import jax.numpy as jnp

        batch, train_n = 4, 128
        argv += ["--batch-size", str(batch)]
        forced = dict(
            bert_cfg=dataclasses.replace(bert.BERT_TINY, dtype=jnp.bfloat16),
            seq_len=32, train_n=train_n, test_n=32)
    engagement.reset()
    with capture_call(mlm_loop, "train_mlm", **forced) as box:
        require(cli.main(argv) == 0, "cli.main (bert_base) returned 0")
    res = box["result"]
    want_steps = train_n // (batch * len(devices))
    require(res.num_steps == want_steps and res.num_devices == len(devices),
            f"{res.num_steps} steps on {res.num_devices} devices, expected "
            f"{want_steps} on {len(devices)}")
    loss = read_metrics(mdir)["train/loss"]
    require(loss[-1][0] == want_steps - 1 and loss[-1][1] is not None
            and math.isfinite(loss[-1][1]),
            f"finite loss at the final step: {loss[-1]}")
    paths = engagement.snapshot()
    # s128 is below flash_min_seq: the default step is XLA dense attention
    require(paths.get("attention") == "xla_dense",
            f"attention path at s128 is xla_dense: {paths}")
    check_all_devices_hold(res.state, devices, facts)
    facts.update(steps=res.num_steps, final_loss=round(loss[-1][1], 4),
                 engagement=paths)


def run_server(rehearsal: bool, stdout: _Stream, facts) -> None:
    from mpi_tensorflow_tpu.serving import __main__ as serving_main

    base = ["--precision", "bf16"]
    want_kernel, n_req = "pallas", 24
    if rehearsal:
        base = ["--precision", "fp32", "--tiny", "--num-requests", "4",
                "--prompt-max", "8", "--output-max", "8",
                "--rate-rps", "1000"]
        n_req = 4
    for choice in ("auto", "pallas"):
        if rehearsal:
            # off TPU auto is the XLA path and a forced kernel is the
            # interpreter — and must say so
            want_kernel = {"auto": "xla", "pallas": "pallas-interpret"}[choice]
        stdout.record = []
        require(serving_main.main(base + ["--kernel", choice]) == 0,
                f"serving main (--kernel {choice}) returned 0")
        lines = "".join(stdout.record).strip().splitlines()
        stdout.record = None
        d = json.loads(lines[-1])
        require(d["status_counts"] == {"ok": n_req},
                f"every request ok: {d['status_counts']}")
        require(d["tokens"] == d["tokens_requested"] > 0,
                f"tokens {d['tokens']} == requested {d['tokens_requested']}")
        require(d["kernel"] == want_kernel
                and d["paths"].get("paged_attention") == want_kernel,
                f"--kernel {choice} served through {want_kernel}: "
                f"kernel={d['kernel']} paths={d['paths']}")
        require(d["zero_recompile_steady_state"] is True,
                f"zero steady-state recompiles: "
                f"{d['compiles_after_warmup']} -> {d['compiles_after_served']}")
        require(d["platform"] == ("cpu" if rehearsal else "tpu"),
                f"row platform {d['platform']}")
        facts[choice] = {"kernel": d["kernel"], "tokens": d["tokens"],
                         "status_counts": d["status_counts"],
                         "device_kind": d["device_kind"]}


def _paged_case(S: int, q_dtype: str, variant: str):
    """Random pools written through the repo's own write paths, ragged
    lengths (empty row, mid-block, block boundary, full table)."""
    import jax.numpy as jnp
    import numpy as np

    from mpi_tensorflow_tpu.ops import paged_attention as pa

    B, NB, H, D, bs = 8, 4, HEADS, HEAD_DIM, BLOCK
    rng = np.random.default_rng(S)
    L, nblk, dt = NB * bs, 1 + B * NB, jnp.dtype(q_dtype)
    kf = jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), dt)
    bt = jnp.arange(1, nblk, dtype=jnp.int32).reshape(B, NB)
    lens = jnp.asarray([min(L - S, v) for v in
                        (0, 3, 15, 16, 17, 31, 40, L - S)], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))
    valid = jnp.ones((B, L), bool)
    kw = {}
    if variant in ("bf16", "fp32"):
        z = jnp.zeros((nblk, bs, H * D), dt)
        kp = pa.write_kv(z, kf, bt, pos, valid)
        vp = pa.write_kv(z, vf, bt, pos, valid)
    elif variant == "int8":
        z = jnp.zeros((nblk, bs, H * D), jnp.int8)
        zs = jnp.zeros((nblk, bs, H), jnp.float32)
        kp, ks = pa.write_kv_quant(z, zs, kf, bt, pos, valid)
        vp, vs = pa.write_kv_quant(z, zs, vf, bt, pos, valid)
        kw = dict(k_scale=ks, v_scale=vs)
    else:
        z = jnp.zeros((nblk, bs, H * D // 2), jnp.uint8)
        zs = jnp.zeros((nblk, bs, H * D // 32), jnp.float32)
        kp, ks = pa.write_kv_quant_int4(z, zs, kf, bt, pos, valid)
        vp, vs = pa.write_kv_quant_int4(z, zs, vf, bt, pos, valid)
        kw = dict(k_scale=ks, v_scale=vs)
        if variant == "int4+residual":
            idx = (lens[:, None] + jnp.arange(S)[None])[:, None, :, None]
            kw.update(k_new=jnp.take_along_axis(kf, idx, 2).astype(dt),
                      v_new=jnp.take_along_axis(vf, idx, 2).astype(dt))
    return (q, kp, vp, bt, lens), kw


def _served_decode_case(rows: int, NB: int):
    """Decode at the served geometry under an ``NB``-wide table: ragged
    rows (one full table, null-block tails), the last quarter bucket
    slack (length 0, all-null table), blocks scattered over a pool of
    ``rows * NB`` random blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    H, D, bs = HEADS, HEAD_DIM, BLOCK
    rng = np.random.default_rng(NB)
    nblk = 1 + rows * NB
    lens = rng.integers(0, NB * bs, rows).astype(np.int32)
    lens[0] = NB * bs - 1
    lens[rows - rows // 4:] = 0
    bt = np.zeros((rows, NB), np.int32)
    ids, nxt = rng.permutation(np.arange(1, nblk)), 0
    for b in range(rows - rows // 4):
        n = lens[b] // bs + 1
        bt[b, :n] = ids[nxt:nxt + n]
        nxt += n
    kq, kk, kv = jax.random.split(jax.random.key(NB), 3)
    q = jax.random.normal(kq, (rows, H, 1, D), jnp.bfloat16)
    kp = jax.random.normal(kk, (nblk, bs, H * D), jnp.bfloat16)
    vp = jax.random.normal(kv, (nblk, bs, H * D), jnp.bfloat16)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


def run_kernels(rehearsal: bool, platform: str, facts) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_tensorflow_tpu.ops import flash_attention as fa
    from mpi_tensorflow_tpu.ops import paged_attention as pa
    from mpi_tensorflow_tpu.parallel import ring

    kernel = pa.PALLAS_INTERPRET if rehearsal else pa.PALLAS
    worst = {}
    for q_dtype, variant in PAGED_VARIANTS:
        atol = (PAGED_ATOL_CPU_FP32
                if platform == "cpu" and q_dtype == "float32" else PAGED_ATOL)
        # decode + every pow2 prefill bucket up to the default chunk (64);
        # the rehearsal samples them (interpreted kernels are slow)
        buckets = (1, 2, 4, 8, 16, 32, 64)
        if rehearsal:
            buckets = (1, 8) if variant == "int4+residual" else (1,)
        for S in buckets:
            args, kw = _paged_case(S, q_dtype, variant)
            dt = jnp.dtype(q_dtype)
            want = jax.jit(functools.partial(
                pa.attend, dt=dt, kernel="xla", **kw))(*args)
            got = jax.jit(functools.partial(
                pa.attend, dt=dt, kernel=kernel, **kw))(*args)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            require(np.isfinite(err) and err <= atol,
                    f"paged {q_dtype}/{variant} S={S}: |kernel - xla| "
                    f"{err:.4g} <= {atol}")
            worst[f"{q_dtype}/{variant}"] = max(
                worst.get(f"{q_dtype}/{variant}", 0.0), err)
    facts["paged_max_abs_err"] = {k: round(v, 5) for k, v in worst.items()}
    facts["paged_kernel"] = kernel

    # decode at the served geometry, one call a pre-warmed table width
    served = {}
    for rows, NB in ((8, 3), (8, 8)) if rehearsal else (
            (128, 8), (128, 16), (128, 32), (128, 64)):
        args = _served_decode_case(rows, NB)
        want, got = (jax.jit(functools.partial(
            pa.attend, dt=jnp.bfloat16, kernel=k))(*args)
            for k in ("xla", kernel))
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        require(np.isfinite(err) and err <= PAGED_ATOL,
                f"paged decode, {rows} rows x {NB} table blocks: "
                f"|kernel - xla| {err:.4g} <= {PAGED_ATOL}")
        served[f"{rows}x{NB}"] = round(err, 5)
    facts["paged_served_decode_max_abs_err"] = served

    # flash fwd+bwd at BERT-base head geometry where flash_min_seq engages
    shape = (1, 2, 256, HEAD_DIM) if rehearsal else (1, HEADS, 4096, HEAD_DIM)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    facts["flash"] = {"shape": list(shape), "interpret": rehearsal}

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    for causal in (False, True):
        flash = functools.partial(fa.flash_attention, causal=causal,
                                  interpret=rehearsal)
        dense = functools.partial(ring.dense_attention, causal=causal)
        lk, gk = jax.jit(jax.value_and_grad(
            functools.partial(loss, flash), argnums=(0, 1, 2)))(q, k, v)
        lr, gr = jax.jit(jax.value_and_grad(
            functools.partial(loss, dense), argnums=(0, 1, 2)))(q, k, v)
        rel_l = abs(float(lk) - float(lr)) / abs(float(lr))
        rel_g = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))
                  / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(gk, gr))
        require(rel_l <= 1e-2 and rel_g <= FLASH_GRAD_RTOL,
                f"flash causal={causal}: loss rel err {rel_l:.3g} <= 1e-2, "
                f"grad rel err {rel_g:.3g} <= {FLASH_GRAD_RTOL}")
        facts["flash"][f"causal={causal}"] = {
            "loss_rel_err": round(rel_l, 6), "grad_rel_err": round(rel_g, 5)}


# --------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU control-flow check at tiny sizes (needs "
                         "JAX_PLATFORMS=cpu); never a chip result")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for metrics and summary.json")
    args = ap.parse_args(argv)
    rehearsal = args.rehearsal

    stdout = _Stream(sys.stdout, REHEARSAL_TAG if rehearsal else "")
    stderr = _Stream(sys.stderr, REHEARSAL_TAG if rehearsal else "")
    with contextlib.redirect_stdout(stdout), \
            contextlib.redirect_stderr(stderr):
        # past the time limit: dump every thread's stack and exit non-zero
        faulthandler.dump_traceback_later(TIME_LIMIT_S, exit=True,
                                          file=sys.__stderr__)
        try:
            result = _run(args.out, rehearsal, stdout)
        finally:
            faulthandler.cancel_dump_traceback_later()
    if result is None:
        return 1
    if not rehearsal:
        # the bare result line: the last line of a passing CHIP run only
        print(json.dumps(result), flush=True)
    return 0


def _run(out: str, rehearsal: bool, stdout: _Stream):
    for var in KILL_SWITCHES:
        if os.environ.get(var, "") not in ("", "0"):
            say(f"refusing to run with {var} set: the smoke must take the "
                f"kernels a default run takes")
            return None

    import jax

    devices = jax.devices()          # the first JAX call
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "not installed"

    versions = {p: version(p) for p in ("jax", "jaxlib", "libtpu")}
    say(f"platform={dev['platform']} device_kind={dev['kind']!r} "
        f"count={dev['count']} versions={json.dumps(versions)}")

    if rehearsal:
        if dev["platform"] != "cpu":
            say("--rehearsal is the CPU check (run it under "
                "JAX_PLATFORMS=cpu); on the chip run with no argument")
            return None
    elif dev["platform"] != "tpu":
        say(f"no TPU: JAX found platform {dev['platform']!r}.  The smoke "
            f"requires the chip (CPU control-flow check: --rehearsal)")
        return None
    else:
        from mpi_tensorflow_tpu.utils import flops

        peaks = flops.device_peaks(dev["kind"])      # unknown kind raises
        say(f"published peaks for {dev['kind']!r}: {json.dumps(peaks)}")

    from mpi_tensorflow_tpu.utils import cache as cache_lib

    cache = cache_lib.enable_compile_cache()
    entries0 = cache_entry_count(cache)
    say(f"compile cache: dir={cache} "
        f"({'from ' + cache_lib.CACHE_ENV if os.environ.get(cache_lib.CACHE_ENV) else 'default'}) "
        f"entries={entries0}")

    os.makedirs(out, exist_ok=True)
    for stale in ("mnist_metrics", "bert_metrics"):
        shutil.rmtree(os.path.join(out, stale), ignore_errors=True)
    summary = {"ok": False, "rehearsal": rehearsal, "device": dev,
               "versions": versions, "phases": {},
               "cache": {"dir": cache, "entries_before": entries0}}
    meter = CompileMeter()
    t_start = time.perf_counter()
    data = tempfile.mkdtemp(prefix="chip_smoke_data_")
    try:
        with phase("mnist", meter, summary) as facts:
            run_mnist(out, data, rehearsal, devices, facts)
        with phase("bert", meter, summary) as facts:
            run_bert(out, data, rehearsal, devices, facts)
        with phase("server", meter, summary) as facts:
            run_server(rehearsal, stdout, facts)
        with phase("kernels", meter, summary) as facts:
            run_kernels(rehearsal, dev["platform"], facts)
    finally:
        shutil.rmtree(data, ignore_errors=True)

    summary["cache"]["entries_after"] = cache_entry_count(cache)
    summary["wall_s"] = round(time.perf_counter() - t_start, 2)
    summary["ok"] = True
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
    say(f"summary {json.dumps(summary, sort_keys=True)}")
    return {"ok": True, "device": dev}


if __name__ == "__main__":
    sys.exit(main())
