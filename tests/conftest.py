"""Test harness: run every test on an 8-device virtual CPU mesh.

The reference's only way to exercise its distributed path is a real
``mpiexec -n N`` launch (SURVEY.md §4).  Here the same multi-device code runs
in-process: the env vars below must be set before ``jax`` is imported anywhere,
which conftest import-time guarantees under pytest.
"""

import os
import sys

# Force a virtual 8-device CPU platform so mesh/psum code runs 8-way with
# no TPU.  The canonical incantation lives in
# __graft_entry__._force_virtual_cpu_env (shared with the driver dryrun);
# it runs before jax is imported, so the env alone decides the platform.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from __graft_entry__ import _force_virtual_cpu_env  # noqa: E402

_force_virtual_cpu_env(os.environ, 8)

import jax  # noqa: E402

# Persistent compilation cache: the transformer-path compiles dominate the
# suite's wall clock; cached compiles make repeat runs and the `-m quick`
# smoke tier usable as a gate.  utils/cache.enable_compile_cache places it
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache) and, for
# this forced-CPU run, scopes it per host and leaves it OFF on boxes that
# cannot reload their OWN XLA:CPU AOT entries: slow beats fatal.
from mpi_tensorflow_tpu.utils.cache import enable_compile_cache  # noqa: E402

if enable_compile_cache() is None:
    print("[conftest] XLA:CPU AOT cache round-trip UNSAFE on this host "
          "(loader cannot verify its own entries) — persistent cache off",
          flush=True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    import jax

    assert len(jax.devices()) == 8, "virtual 8-device CPU platform not active"
    return jax.make_mesh((8,), ("data",))


@pytest.fixture(scope="session")
def mnist_dir(tmp_path_factory):
    """A small synthetic MNIST in IDX format (1200 train / 256 test)."""
    from mpi_tensorflow_tpu.data import mnist

    d = tmp_path_factory.mktemp("mnist")
    mnist._write_synthetic(str(d), train_n=1200, test_n=256)
    return str(d)
