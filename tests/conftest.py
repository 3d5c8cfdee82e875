"""Test harness: run every test on an 8-device virtual CPU mesh.

The reference's only way to exercise its distributed path is a real
``mpiexec -n N`` launch (SURVEY.md §4).  Here the same multi-device code runs
in-process: the env vars below must be set before ``jax`` is imported anywhere,
which conftest import-time guarantees under pytest.
"""

import contextlib
import faulthandler
import os
import shutil
import signal
import sys
import tempfile

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

# Every phase of a test (fixture set-up, the call, teardown) has its own
# limit.  The driver's command has one clock for the whole suite and no
# pytest-timeout: without this a hung test takes the run and every test
# queued behind it on its worker.  No `timeout=` / `deadline_s=` inside
# tests/ may exceed it.
TEST_LIMIT_S = 180
# SIGALRM's handler only runs between bytecodes of the main thread, so a
# phase stuck in native code (an XLA:CPU compile, a collective rendezvous)
# never sees it.  This long after the limit faulthandler's watchdog THREAD
# prints every stack and exits the process: under xdist that is one worker,
# whose item the master reports as crashed, by name, before it starts a
# fresh worker on the files that were queued behind it.  The grace lets a
# test that was failed at the limit finish its `finally:` clean-up (the
# longest such wait in tests/ is `proc.wait(timeout=30)`).
KILL_GRACE_S = 40

_stderr_fd = 2
# xdist's loadfile scheduler hands a crashed worker's whole file, the item
# that killed it included, to the next worker.  Each worker therefore keeps
# the id of the phase it is in here, in a file that outlives it only if it
# was killed there, and no worker starts an item that killed another.
_in_flight_dir = os.path.join(
    tempfile.gettempdir(),
    "pytest-limit-%d" % (os.getppid() if "PYTEST_XDIST_WORKER" in os.environ
                         else os.getpid()))


def pytest_configure(config):
    """Keep the process's real stderr: while a test runs, fd 2 is pytest's
    capture file, and a phase that never returns never gets it printed."""
    global _stderr_fd
    with config.pluginmanager.getplugin(
            "capturemanager").global_and_fixture_disabled():
        _stderr_fd = os.dup(2)
    os.makedirs(_in_flight_dir, exist_ok=True)


def pytest_unconfigure(config):
    if "PYTEST_XDIST_WORKER" not in os.environ:
        shutil.rmtree(_in_flight_dir, ignore_errors=True)


@contextlib.contextmanager
def _limited(item, phase):
    """Fail ``item`` by name once ``phase`` has run its limit: TEST_LIMIT_S,
    or the seconds of a ``@pytest.mark.limit(seconds)`` that CHANGES.md
    justifies (pytest.ini)."""
    marker = item.get_closest_marker("limit")
    limit = marker.args[0] if marker else TEST_LIMIT_S
    said = (f"{item.nodeid} {phase} ran past its {limit} s limit "
            "(tests/conftest.py)")
    mine = os.path.join(_in_flight_dir, str(os.getpid()))

    def expired(signum, frame):
        os.write(_stderr_fd, f"\n{said}; every thread:\n".encode())
        faulthandler.dump_traceback(file=_stderr_fd)
        pytest.fail(said)

    with open(mine, "w") as f:
        f.write(item.nodeid)
    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + KILL_GRACE_S, exit=True,
                                      file=_stderr_fd)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        os.unlink(mine)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    for pid in os.listdir(_in_flight_dir):
        with contextlib.suppress(FileNotFoundError), \
                open(os.path.join(_in_flight_dir, pid)) as f:
            if f.read() == item.nodeid:
                pytest.fail(f"{item.nodeid} hung worker {pid} past its limit "
                            "in native code and was killed with it; the "
                            "stacks are in the run's output")
    with _limited(item, "set-up"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _limited(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _limited(item, "teardown"):
        return (yield)


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
