"""Batched evaluation — the ``eval_in_batches`` equivalent (mpipy.py:169-183).

Semantics preserved:
- raises if the dataset is smaller than one batch (mpipy.py:171-172);
- full batches evaluated in sequence; the tail is handled by re-running the
  final full window and slicing the overlap (mpipy.py:179-182) — on TPU this
  also keeps every compiled shape static (no recompilation for the tail);
- predictions are softmax probabilities (mpipy.py:68).

Aggregation: the reference scatters test data, so each rank reports error on
a *different* shard (SURVEY.md §3.5).  ``shard_error_rates`` reproduces that
per-shard trace; ``error_rate`` gives the correct global number.
"""

from __future__ import annotations

import numpy as np

from mpi_tensorflow_tpu.data.idx import error_rate  # re-export  # noqa: F401


def eval_in_batches(predict_fn, data, batch_size: int) -> np.ndarray:
    """Run ``predict_fn(batch) -> probs`` over ``data`` in fixed-size
    batches, tail via overlapped final window.  Bind params/model-state into
    ``predict_fn`` before calling."""
    size = data.shape[0]
    if size < batch_size:
        raise ValueError(
            "batch size for evals larger than dataset: %d" % size)
    out = None
    for begin in range(0, size, batch_size):
        end = begin + batch_size
        if end <= size:
            preds = np.asarray(predict_fn(data[begin:end]))
        else:
            preds = np.asarray(predict_fn(data[-batch_size:]))[begin - size:]
        if out is None:
            out = np.empty((size, preds.shape[-1]), dtype=np.float32)
        out[begin:begin + preds.shape[0]] = preds
    return out


def stack_eval_windows(data, batch_size: int):
    """Assemble the eval windows ``eval_in_batches`` would run — full
    batches plus the overlapped final window for the tail (mpipy.py:179-182)
    — into one ``(K, batch_size, ...)`` array for a single scanned dispatch.

    Returns ``(windows, starts)`` where ``starts[k]`` is the dataset row the
    k-th window's predictions belong at (the tail window's overlap rows are
    simply overwritten by design, exactly like the reference's slicing)."""
    size = data.shape[0]
    if size < batch_size:
        raise ValueError(
            "batch size for evals larger than dataset: %d" % size)
    starts = list(range(0, size - batch_size + 1, batch_size))
    if starts[-1] + batch_size < size:
        starts.append(size - batch_size)   # overlapped tail window
    windows = np.stack([np.asarray(data[s:s + batch_size]) for s in starts])
    return windows, starts


def eval_in_batches_fused(predict_multi_fn, data, batch_size: int
                          ) -> np.ndarray:
    """``eval_in_batches`` semantics in ONE device dispatch:
    ``predict_multi_fn(windows) -> (K, batch_size, C)`` scans the forward
    pass over staged windows (train/step.py make_multi_eval_step).  Per-
    dispatch latency dominates batchwise eval on small models, so the host
    loop of the unfused path becomes a single call."""
    windows, starts = stack_eval_windows(data, batch_size)
    preds = np.asarray(predict_multi_fn(windows))
    out = np.empty((data.shape[0], preds.shape[-1]), dtype=np.float32)
    for k, s in enumerate(starts):
        out[s:s + batch_size] = preds[k]
    return out


def shard_error_rates(predictions: np.ndarray, labels: np.ndarray,
                      num_shards: int) -> list[float]:
    """Per-shard error %, matching the reference's per-rank printed trace
    (each rank holds a contiguous test shard, mpipy.py:88)."""
    n = predictions.shape[0] // num_shards * num_shards
    per = n // num_shards
    return [error_rate(predictions[i * per:(i + 1) * per],
                       labels[i * per:(i + 1) * per])
            for i in range(num_shards)]
