"""The host training loop — ``Cnn.run_process`` rebuilt (mpipy.py:76-93).

Semantics preserved from the reference:
- per-shard steps: ``epochs * local_train_size // batch_size`` (mpipy.py:79);
- sequential wraparound batching per shard, no shuffling (mpipy.py:80-82) —
  the global batch each step is the concatenation of every shard's 64-row
  window, exactly the rows the N MPI ranks would each slice;
- LR decay_steps = local train size (mpipy.py:62);
- the 50-step console trace, one line per shard (mpipy.py:87-90);
- parameter sync on the trace cadence in ``avg50`` mode (mpipy.py:91).

Deliberate divergences (documented in SURVEY.md §7):
- evaluation runs on the trace cadence, OFF the timed path — the reference
  evaluates the full test shard EVERY step (mpipy.py:86), an accidental cost
  kept out of what is timed;
- ``psum`` mode replaces the reference's rank-0-only periodic averaging with
  per-step gradient allreduce (true synchronous SGD).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_tensorflow_tpu.config import Config
from mpi_tensorflow_tpu.data import mnist
from mpi_tensorflow_tpu.data.idx import error_rate
from mpi_tensorflow_tpu.models import cnn as cnn_lib
from mpi_tensorflow_tpu.parallel import mesh as meshlib
from mpi_tensorflow_tpu.train import evaluation, step as step_lib
from mpi_tensorflow_tpu.utils import logging as logs
from mpi_tensorflow_tpu.utils.profiling import StepTimer


@dataclasses.dataclass
class TrainResult:
    state: Any
    history: list          # [(step, global_test_error), ...]
    final_test_error: float
    images_per_sec: float
    step_time_seconds: float
    num_devices: int
    num_steps: int


def build_model(config: Config):
    dt = config.compute_dtype
    if config.model == "mnist_cnn":
        return cnn_lib.MnistCnn(
            image_size=config.image_size,
            num_channels=config.num_channels,
            num_classes=config.num_classes,
            dropout_rate=config.dropout_rate,
            compute_dtype=dt,
        )
    if config.model in ("resnet20", "resnet50"):
        from mpi_tensorflow_tpu.models import resnet

        return resnet.build(config.model, num_classes=config.num_classes,
                            compute_dtype=dt, remat=config.remat)
    if config.model == "vit":
        import dataclasses as dc

        from mpi_tensorflow_tpu.models import vit

        # channels follow the dataset (MNIST is single-channel); patch
        # size follows the input geometry: 28 -> 7px patches (4x4 grid),
        # 32 -> 4px (8x8 grid), else 16px (224 -> 14x14 grid)
        ch = 1 if config.dataset == "mnist" else 3
        patch = {28: 7, 32: 4}.get(config.image_size, 16)
        vcfg = dc.replace(vit.VIT_TINY_CIFAR,
                          image_size=config.image_size, patch=patch,
                          channels=ch, num_classes=config.num_classes,
                          dtype=dt, remat=config.remat)
        return vit.VisionTransformer(vcfg)
    if config.model == "bert_base":
        import dataclasses as dc

        from mpi_tensorflow_tpu.models import bert

        return bert.BertMlm(dc.replace(bert.BERT_BASE, dtype=dt))
    raise ValueError(f"unknown model {config.model!r}")


def load_dataset(config: Config, num_shards: int) -> mnist.Splits:
    """Dataset dispatch (the reference supports exactly one dataset,
    downloaded at mpipy.py:203-206; scale-out sets come from BASELINE.json)."""
    if config.dataset == "mnist":
        mnist.ensure_downloaded(config.data_dir)
        return mnist.load_splits(config.data_dir, num_shards=num_shards)
    if config.dataset == "cifar10":
        from mpi_tensorflow_tpu.data import cifar

        return cifar.load_splits(config.data_dir)
    if config.dataset == "imagenet_synthetic":
        from mpi_tensorflow_tpu.data import imagenet

        return imagenet.load_splits(config.data_dir)
    raise ValueError(f"unknown dataset {config.dataset!r} for the image loop")


def train(config: Config, model=None, splits: Optional[mnist.Splits] = None,
          mesh=None, verbose: bool = True) -> TrainResult:
    """End-to-end data-parallel training (the ``main()`` + ``Cnn`` path of
    the reference, mpipy.py:201-244, minus MPI)."""
    mesh = mesh if mesh is not None else meshlib.make_mesh(config.mesh_shape)
    ndev = meshlib.data_axis_size(mesh)
    model = model if model is not None else build_model(config)
    if splits is None:
        splits = load_dataset(config, ndev)
    b = config.batch_size

    # per-shard contiguous layout: shard i <- rows [i*localN, (i+1)*localN)
    local_n = splits.train_labels.shape[0] // ndev
    if local_n <= b:
        raise ValueError(f"local train size {local_n} must exceed batch {b}")
    tr_d = splits.train_data[:local_n * ndev].reshape(
        ndev, local_n, *splits.train_data.shape[1:])
    tr_l = splits.train_labels[:local_n * ndev].reshape(ndev, local_n)
    num_steps = config.epochs * local_n // b          # mpipy.py:79
    global_b = b * ndev

    state = step_lib.init_state(model, jax.random.key(config.seed))
    if config.sync == "psum":
        train_step = step_lib.make_train_step(model, config, mesh,
                                              decay_steps=local_n)
        eval_step = step_lib.make_eval_step(model, config, mesh)
    elif config.sync == "avg50":
        if config.grad_accum > 1:
            raise ValueError(
                "grad_accum applies to the psum (sync-SGD) and transformer "
                "paths; the avg50 fidelity mode reproduces the reference's "
                "per-rank batch-64 stepping, where microbatching has no "
                "counterpart")
        train_step = step_lib.make_local_train_step(model, config, mesh,
                                                    decay_steps=local_n)
        avg_step = step_lib.make_average_step(mesh)
        eval_step = step_lib.make_stacked_eval_step(model, config, mesh)
        state = step_lib.stack_state(state, ndev)
    else:
        raise ValueError(f"unknown sync mode {config.sync!r}")

    from mpi_tensorflow_tpu.train.ckpt_hooks import CheckpointHooks

    hooks = CheckpointHooks(config.checkpoint_dir, verbose=verbose)
    from mpi_tensorflow_tpu.utils import metrics_writer

    mw = metrics_writer.for_process(config.metrics_dir,
                                    meshlib.process_index())
    start_step = 0
    if config.resume:
        state, start_step = hooks.resume(state)

    batch_sharding = NamedSharding(mesh, P("data"))
    rng = config.make_train_key(config.seed + 1)
    timer = StepTimer(warmup_steps=1)
    history = []
    if verbose:
        logs.session_start(meshlib.process_index())

    fused = max(1, int(config.fused_steps or 1)) if config.sync == "psum" else 1
    eval_multi = None
    if fused > 1:
        eval_multi = step_lib.make_multi_eval_step(model, config, mesh)

    def run_eval(s, data=None):
        data = splits.test_data if data is None else data
        if eval_multi is not None:
            return evaluation.eval_in_batches_fused(
                lambda w: eval_multi(s.params, s.model_state, w),
                data, global_b)
        predict = lambda b: eval_step(s.params, s.model_state, b)
        return evaluation.eval_in_batches(predict, data, global_b)

    # validation-based early stopping: the reference scatters val shards and
    # never reads them (mpipy.py:236-241); patience > 0 puts them to work
    es_patience = int(getattr(config, "early_stop_patience", 0) or 0)
    es_usable = es_patience > 0 and splits.val_labels.shape[0] >= global_b
    if es_patience > 0 and not es_usable and verbose:
        print(f"[early-stop] DISABLED: validation split "
              f"({splits.val_labels.shape[0]} rows) is smaller than the "
              f"global batch ({global_b}) — --early-stop-patience ignored")
    es_best, es_bad, stop_early = [float("inf")], [0], [False]

    def check_early_stop(s) -> bool:
        if not es_usable:
            return False
        preds = run_eval(s, splits.val_data)
        val_err = error_rate(preds, splits.val_labels)
        if verbose:
            logs.val_trace(meshlib.process_index(), val_err)
        if val_err < es_best[0] - 1e-12:
            es_best[0], es_bad[0] = val_err, 0
            return False
        es_bad[0] += 1
        if es_bad[0] >= es_patience:
            if verbose:
                print(f"[early-stop] validation error has not improved for "
                      f"{es_patience} trace points (best {es_best[0]:.2f}%)")
            return True
        return False

    pending = 0
    if fused > 1:
        # +1: trace points land on completed step t with t % log_every == 0,
        # so the first window is log_every+1 steps; the fixed K plus the
        # n_valid mask keeps every window on ONE compiled shape
        fused_k = fused + 1
        multi_step = step_lib.make_multi_train_step(
            model, config, mesh, decay_steps=local_n, masked=True)
        fused_sharding = NamedSharding(mesh, P(None, "data"))

    def slice_step(t):
        # single window of width 1 — the wraparound-offset semantics live
        # in data/prefetch.assemble_window only (one place per language)
        from mpi_tensorflow_tpu.data import prefetch

        bs, ls = prefetch.assemble_window(tr_d, tr_l, t, 1, 1, b)
        return bs[0], ls[0]

    def window_schedule():
        """(starts, widths): fixed-K windows ending exactly on the 50-step
        trace cadence, so the eval/avg/checkpoint schedule matches the
        per-step loop."""
        L = config.log_every
        starts, widths = [], []
        t = start_step
        while t < num_steps:
            # next step index at which the per-step loop would trace
            T = min(((max(t, 1) + L - 1) // L) * L, num_steps - 1)
            w = min(T - t + 1, fused_k)
            starts.append(t)
            widths.append(w)
            t += w
        return starts, widths

    def run_steps_fused():
        """One device dispatch per window of steps (lax.scan inside,
        train/step.py make_multi_train_step): same step semantics, none of
        the per-step dispatch latency.  Window assembly (a strided gather)
        runs ahead on a background worker — native C++ when available
        (data/prefetch.py) — overlapping the device's previous window."""
        nonlocal state, pending
        from mpi_tensorflow_tpu.data import prefetch

        L = config.log_every
        starts, widths = window_schedule()
        pf = None
        if config.prefetch != "off":
            force = None if config.prefetch == "auto" else config.prefetch
            pf = prefetch.make_prefetcher(tr_d, tr_l, starts, widths,
                                          fused_k, b, force=force)
        try:
            for t0, w in zip(starts, widths):
                if pf is not None:
                    bs, ls, _ = pf.next()
                else:
                    bs, ls = prefetch.assemble_window(tr_d, tr_l, t0, w,
                                                      fused_k, b)
                bdev = jax.device_put(bs, fused_sharding)
                ldev = jax.device_put(ls, fused_sharding)
                state, _ = multi_step(state, bdev, ldev, rng, w)
                pending += w
                t_done = t0 + w - 1

                if hooks.stop_now(t_done):
                    hooks.preempt_save(state, t_done)
                    break

                if (t_done % L == 0 and t_done > 0) \
                        or t_done == num_steps - 1:
                    trace_point(t_done)
                    if stop_early[0]:
                        break
                    if t_done != num_steps - 1 and hooks.stop_agreed(t_done):
                        hooks.preempt_save(state, t_done,
                                           already_queued=True)
                        break
        finally:
            if pf is not None:
                pf.close()

    def trace_point(t):
        nonlocal state, pending
        jax.block_until_ready(state)                   # close the timed span
        timer.stop(pending)
        pending = 0
        preds = run_eval(state)
        global_err = error_rate(preds, splits.test_labels)
        history.append((t, global_err))
        mw.scalar("eval/test_error_pct", global_err, t)
        if verbose:
            # one line per shard, the reference's per-rank trace
            for r, e in enumerate(evaluation.shard_error_rates(
                    preds, splits.test_labels, ndev)):
                logs.step_trace(r, t, e)
        if config.sync == "avg50" and t != num_steps - 1:  # mpipy.py:91
            state = avg_step(state)
        if t != num_steps - 1:   # a verdict at the final step is dead work
            stop_early[0] = check_early_stop(state)
        # async: snapshot now (cheap), write on the worker thread — the
        # train loop does not block on disk at trace points
        hooks.save_async(state, t)
        timer.start()

    def run_steps():
        nonlocal state, pending
        for t in range(start_step, num_steps):
            batch, labels = slice_step(t)
            batch = jax.device_put(batch, batch_sharding)
            labels = jax.device_put(labels, batch_sharding)
            state, metrics = train_step(state, batch, labels, rng)
            pending += 1

            if hooks.stop_now(t):
                hooks.preempt_save(state, t)
                break

            if (t > 0 and t % config.log_every == 0) or t == num_steps - 1:
                trace_point(t)
                if stop_early[0]:
                    break
                if t != num_steps - 1 and hooks.stop_agreed(t):
                    hooks.preempt_save(state, t, already_queued=True)
                    break

    timer.start()
    try:
        if fused > 1:
            run_steps_fused()
        else:
            run_steps()
        ips_t = timer.images_per_sec(global_b)
        if ips_t == ips_t:   # skip the NaN of a run with no timed span
            mw.scalar("perf/images_per_sec", ips_t, num_steps)
    finally:
        hooks.close()   # every queued checkpoint is on disk before return
        mw.close()      # flush TB events even on an exceptional exit
    final_err = history[-1][1] if history else float("nan")
    ips = timer.images_per_sec(global_b)
    if verbose:
        logs.timing_summary(ips, timer.mean_step_seconds * 1e3, ndev)
    return TrainResult(
        state=state, history=history, final_test_error=final_err,
        images_per_sec=ips, step_time_seconds=timer.mean_step_seconds,
        num_devices=ndev, num_steps=num_steps,
    )
