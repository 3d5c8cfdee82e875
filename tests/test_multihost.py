"""Multi-host (multi-process) path simulation.

The reference's multi-process story is a real ``mpiexec -n N`` launch
(mpipy.py:246-247); there is no way to unit-test it without a cluster.
Here the per-host sharding paths take explicit ``process_index`` /
``process_count`` (or read the jax globals, monkeypatched below), so the
N-host data layout is pinned in CI with one process — and a misconfigured
pod launch fails loudly instead of degrading to single-process training.
"""

import numpy as np
import pytest

from mpi_tensorflow_tpu.data import sharding
from mpi_tensorflow_tpu.parallel import mesh as meshlib

pytestmark = pytest.mark.quick


class TestHostSharding:
    def test_hosts_partition_dataset(self):
        """N host shards tile the (truncated) dataset exactly once."""
        x = np.arange(103 * 3).reshape(103, 3)
        k = 4
        parts = [sharding.host_shard(x, process_index=i, process_count=k)
                 for i in range(k)]
        assert all(p.shape[0] == 103 // k for p in parts)
        np.testing.assert_array_equal(
            np.concatenate(parts), x[:103 // k * k])

    def test_host_shard_reads_jax_process_globals(self, monkeypatch):
        """Zero-arg host_shard follows jax.process_index()/process_count()
        — the values a real pod launch sets."""
        import jax

        x = np.arange(80).reshape(40, 2)
        monkeypatch.setattr(jax, "process_count", lambda: 4)
        for i in range(4):
            monkeypatch.setattr(jax, "process_index", lambda i=i: i)
            got = sharding.host_shard(x)
            np.testing.assert_array_equal(got, x[i * 10:(i + 1) * 10])

    def test_mlm_loop_data_split_matches_scatter_semantics(self):
        """Each of N simulated hosts sees a distinct contiguous slice whose
        sizes follow the reference truncation (mpipy.py:211-213)."""
        n = 1000
        k = 3
        t = sharding.truncate_to_multiple(n, k)
        seen = set()
        for i in range(k):
            lo, hi = sharding.shard_bounds(n, k, i)
            assert hi - lo == t // k
            assert not (set(range(lo, hi)) & seen)
            seen |= set(range(lo, hi))
        assert max(seen) == t - 1


class TestAgreedStop:
    def test_stop_agreed_any_host_wins(self, monkeypatch, tmp_path):
        """A SIGTERM observed on ANY host stops every host at the same
        trace point (simulated via patched process_count/allgather)."""
        import jax
        import numpy as np

        from jax.experimental import multihost_utils
        from mpi_tensorflow_tpu.train.ckpt_hooks import CheckpointHooks

        hooks = CheckpointHooks(str(tmp_path), verbose=False)
        assert hooks.guard is not None
        monkeypatch.setattr(jax, "process_count", lambda: 4)
        # some OTHER host observed the signal; ours did not
        monkeypatch.setattr(
            multihost_utils, "process_allgather",
            lambda x: np.asarray([[False], [True], [False], [False]]))
        assert not hooks.guard.should_stop
        assert hooks.stop_agreed(10) is True
        # the agreement also marks the local guard so the exit path prints
        # a reason and later checks short-circuit
        assert hooks.guard.should_stop
        hooks.close()

    def test_stop_now_is_single_host_only(self, monkeypatch, tmp_path):
        """Per-step local stop must NOT fire multi-host (a lone host
        leaving the loop would deadlock the pod's collectives)."""
        import jax

        from mpi_tensorflow_tpu.train.ckpt_hooks import CheckpointHooks

        hooks = CheckpointHooks(str(tmp_path), verbose=False)
        hooks.guard.request_stop("test")
        monkeypatch.setattr(jax, "process_count", lambda: 1)
        assert hooks.stop_now(5) is True
        monkeypatch.setattr(jax, "process_count", lambda: 4)
        assert hooks.stop_now(5) is False
        hooks.close()


class TestLoudInitFailure:
    def test_explicit_coordinator_failure_raises(self, monkeypatch):
        """A configured-but-broken multi-host launch must raise, not
        silently fall back to single-process (round-1 gap: mesh.py
        swallowed RuntimeError/ValueError)."""
        import jax

        def boom(*a, **k):
            raise RuntimeError("coordinator unreachable")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        monkeypatch.setattr(jax, "process_count", lambda: 1)
        with pytest.raises(RuntimeError, match="multi-host launch"):
            meshlib.initialize_distributed(
                coordinator_address="10.0.0.1:1234")

    def test_auto_env_failure_raises(self, monkeypatch):
        import jax

        def boom(*a, **k):
            raise ValueError("bad topology")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        monkeypatch.setattr(jax, "process_count", lambda: 1)
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h1,h2")
        with pytest.raises(RuntimeError, match="multi-host launch"):
            meshlib.initialize_distributed()

    def test_single_process_is_noop(self, monkeypatch):
        import jax

        monkeypatch.setattr(jax, "process_count", lambda: 1)
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
        monkeypatch.delenv("CLOUD_TPU_TASK_ID", raising=False)
        meshlib.initialize_distributed()   # must not raise

    @pytest.mark.parametrize("hosts", ["localhost", "t1v-n-abc-w-0",
                                       "10.0.0.7"])
    def test_single_host_never_joins_a_coordinator(self, monkeypatch,
                                                   hosts):
        """A one-host machine (the sealed chip machines set
        TPU_WORKER_HOSTNAMES=localhost, TPU_WORKER_ID=0) must never call
        jax.distributed.initialize — it could wait forever on a
        coordinator that does not exist."""
        import jax

        def boom(*a, **k):
            raise AssertionError("single host tried to join a coordinator")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", hosts)
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        monkeypatch.setenv("CLOUD_TPU_TASK_ID", "0")
        meshlib.initialize_distributed()
