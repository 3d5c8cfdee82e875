"""MNIST dataset: download, load, and reference-faithful partitioning.

Reproduces the reference's data path end to end:
- download of the 4 idx-gz files from the GCS mirror into ``./data`` if
  absent (``data_exist_here``, mpipy.py:185-199) — with the broken error
  path fixed (the reference references an undefined ``DownloadError`` name,
  mpipy.py:197) and a deterministic synthetic fallback for air-gapped
  environments;
- rank-0-style split (mpipy.py:211-222): sizes truncated to multiples of the
  shard count, validation = first ``5000//k*k`` training rows, train = rows
  ``[5000//k*k, 55000//k*k)``, test = first ``10000//k*k`` test rows.

Unlike the reference there is no root-0 Scatter: each host slices its own
shard (``data.sharding``).
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import urllib.error
import urllib.request

import numpy as np

from mpi_tensorflow_tpu.data import idx, sharding

DATA_URL = "https://storage.googleapis.com/cvdf-datasets/mnist/"  # mpipy.py:17
FILES = {
    "train_images": "train-images-idx3-ubyte.gz",
    "train_labels": "train-labels-idx1-ubyte.gz",
    "test_images": "t10k-images-idx3-ubyte.gz",
    "test_labels": "t10k-labels-idx1-ubyte.gz",
}
_TRAIN_N, _TEST_N, _VAL_N = 60000, 10000, 5000
DOWNLOAD_TIMEOUT_S = 10.0      # per socket operation, per file


@dataclasses.dataclass
class Splits:
    """The six arrays the reference Scatters (mpipy.py:236-241), pre-shard."""
    train_data: np.ndarray
    train_labels: np.ndarray
    test_data: np.ndarray
    test_labels: np.ndarray
    val_data: np.ndarray
    val_labels: np.ndarray

    def shard(self, num_shards: int, index: int) -> "Splits":
        """Contiguous equal shard ``index`` of every split — what one MPI rank
        would have received from the reference's six Scatters."""
        return Splits(*sharding.shard_arrays(dataclasses.astuple(self),
                                             num_shards, index))


def ensure_downloaded(data_dir: str = "./data", synthetic_fallback: bool = True,
                      verbose: bool = True) -> dict:
    """Fetch the 4 MNIST files into ``data_dir`` if absent.

    Unlike the reference (every rank races on ``./data``, mpipy.py:203-206),
    call this once per host.  If the network is unreachable and
    ``synthetic_fallback`` is set, writes deterministic synthetic IDX files of
    the real shapes so the rest of the pipeline is exercised identically.
    """
    os.makedirs(data_dir, exist_ok=True)
    paths = {}
    for key, fname in FILES.items():
        path = os.path.join(data_dir, fname)
        if not os.path.exists(path):
            try:
                # bounded: a sealed machine must fail over to the
                # synthetic set in seconds, not wait on a dead resolver
                with urllib.request.urlopen(
                        DATA_URL + fname,
                        timeout=DOWNLOAD_TIMEOUT_S) as r, \
                        open(path, "wb") as f:
                    shutil.copyfileobj(r, f)
            except (urllib.error.URLError, OSError) as e:
                if os.path.exists(path):
                    os.remove(path)
                if not synthetic_fallback:
                    raise RuntimeError(f"download of {fname} failed: {e}") from e
                if verbose:
                    print(f"[data] download of {fname} failed ({e}); "
                          f"writing synthetic fallback")
                _write_synthetic(data_dir)
        paths[key] = path
    return paths


def _write_synthetic(data_dir: str, train_n: int = _TRAIN_N,
                     test_n: int = _TEST_N) -> None:
    """Deterministic fake MNIST: class-dependent blob images so a model can
    actually fit them (error decreases), same dtypes/shapes as the real set."""
    rng = np.random.default_rng(0)
    for n, img_name, lbl_name in (
        (train_n, FILES["train_images"], FILES["train_labels"]),
        (test_n, FILES["test_images"], FILES["test_labels"]),
    ):
        labels = rng.integers(0, 10, size=n).astype(np.uint8)
        images = np.zeros((n, 28, 28), dtype=np.uint8)
        # one bright 8x8 patch whose position encodes the class -> separable
        ys, xs = np.unravel_index(np.arange(10) * 7 % 20, (5, 4))
        for c in range(10):
            mask = labels == c
            patch = rng.integers(128, 255, size=(int(mask.sum()), 8, 8))
            y, x = int(ys[c]) * 4, int(xs[c]) * 5
            images[mask, y:y + 8, x:x + 8] = patch
        idx.write_idx(os.path.join(data_dir, img_name), images)
        idx.write_idx(os.path.join(data_dir, lbl_name), labels)


def load_splits(data_dir: str = "./data", num_shards: int = 1,
                train_n: int | None = None, test_n: int | None = None) -> Splits:
    """Load and split exactly as the reference's rank 0 does (mpipy.py:211-222).

    ``num_shards`` plays the role of the MPI world size in the size
    truncations. ``train_n``/``test_n`` allow small subsets for tests.
    """
    paths = {k: os.path.join(data_dir, f) for k, f in FILES.items()}
    k = num_shards
    avail_train = train_n if train_n is not None else _TRAIN_N
    avail_test = test_n if test_n is not None else _TEST_N
    # reference constants scale: val is first 1/12 of train, per mpipy.py:211-213
    val_total = sharding.truncate_to_multiple(avail_train * _VAL_N // _TRAIN_N, k)
    tr_total = sharding.truncate_to_multiple(avail_train * 55000 // _TRAIN_N, k)
    ts_total = sharding.truncate_to_multiple(avail_test, k)

    # native C++ loader when built (bit-identical; data/native.py falls back
    # to the Python parser itself when the library is unavailable)
    from mpi_tensorflow_tpu.data import native

    tr_data = native.extract_images(paths["train_images"], avail_train)
    tr_labels = native.extract_labels(paths["train_labels"], avail_train)
    ts_data = native.extract_images(paths["test_images"], ts_total)
    ts_labels = native.extract_labels(paths["test_labels"], ts_total)

    return Splits(
        train_data=tr_data[val_total:tr_total],
        train_labels=tr_labels[val_total:tr_total],
        test_data=ts_data,
        test_labels=ts_labels,
        val_data=tr_data[:val_total],
        val_labels=tr_labels[:val_total],
    )


def load_for_host(config=None, data_dir: str = "./data", num_shards: int = 1,
                  shard_index: int = 0, **kwargs) -> Splits:
    """One call: ensure data exists, load, and take this shard's slice."""
    ensure_downloaded(data_dir)
    return load_splits(data_dir, num_shards, **kwargs).shard(num_shards, shard_index)
