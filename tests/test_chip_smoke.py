"""chip_smoke.py off the chip: the CPU rehearsal passes and cannot pass
for a chip run, the no-argument run refuses to start without a TPU, and
the compile cache lands where it was placed.

The kernel-failure-raises pins live with the code they test:
tests/test_paged_kernel.py::TestDispatch (resolve_kernel) and
tests/test_engagement.py (BertMlm._attention); the unknown-device pin for
the peaks table is in tests/test_flops.py.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
TAG = "[rehearsal platform=cpu] "

sys.path.insert(0, REPO)

# The module's `rehearsal` fixture is the whole of chip_smoke.py --rehearsal
# in a child: 62-110 s beside five other workers on an 8-core box, so the
# common 180 s would leave a slower box no room (CHANGES.md PR 24).
pytestmark = pytest.mark.limit(360)


def _run_smoke(args, tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"               # never inherits a chip
    for k in ("JAX_NUM_CPU_DEVICES", "XLA_FLAGS",
              "MPI_TF_TPU_DISABLE_FLASH", "MPI_TF_TPU_DISABLE_PAGED_KERNEL"):
        env.pop(k, None)
    return subprocess.run([sys.executable, SMOKE] + args, env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=340)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("smoke")
    r = _run_smoke(["--rehearsal", "--out", str(tmp / "out")], tmp)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    with open(tmp / "out" / "summary.json") as f:
        return r, json.load(f), tmp


class TestRehearsal:
    def test_every_phase_passes(self, rehearsal):
        _, summary, _ = rehearsal
        assert summary["ok"] and summary["rehearsal"]
        assert summary["device"]["platform"] == "cpu"
        assert sorted(summary["phases"]) == ["bert", "kernels", "mnist",
                                             "server"]
        assert all(p["ok"] for p in summary["phases"].values())
        assert summary["phases"]["bert"]["engagement"]["attention"] \
            == "xla_dense"

    def test_interpreted_kernel_is_named_so(self, rehearsal):
        """Off the chip a forced kernel is the interpreter and every
        report says ``pallas-interpret`` — never ``pallas``."""
        _, summary, _ = rehearsal
        server = summary["phases"]["server"]
        assert server["pallas"]["kernel"] == "pallas-interpret"
        assert server["auto"]["kernel"] == "xla"
        assert summary["phases"]["kernels"]["paged_kernel"] \
            == "pallas-interpret"

    def test_cannot_pass_for_a_chip_run(self, rehearsal):
        r, _, _ = rehearsal
        lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
        assert lines and all(ln.startswith(TAG) for ln in lines), \
            [ln for ln in lines if not ln.startswith(TAG)][:5]
        # the bare {"ok": ...} result line belongs to chip runs only
        with pytest.raises(ValueError):
            json.loads(lines[-1])

    def test_leaves_no_data_in_the_checkout_or_cwd(self, rehearsal):
        _, _, tmp = rehearsal
        assert sorted(os.listdir(tmp)) == ["out"]
        assert not os.path.exists(os.path.join(tmp, "out", "data"))


def test_no_argument_run_requires_the_chip(tmp_path):
    r = _run_smoke([], tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stdout
    assert "phase" not in r.stdout          # stopped before any phase
    assert '"ok"' not in r.stdout           # and printed no result


def test_refuses_kill_switches(tmp_path, monkeypatch):
    import chip_smoke

    monkeypatch.setenv("MPI_TF_TPU_DISABLE_PAGED_KERNEL", "1")
    assert chip_smoke._run(str(tmp_path), True, None) is None


def test_unknown_tpu_kind_stops_before_any_phase(tmp_path, monkeypatch):
    import jax

    import chip_smoke
    from mpi_tensorflow_tpu.utils import flops

    for var in chip_smoke.KILL_SWITCHES:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(platform="tpu", device_kind="TPU v9")])
    with pytest.raises(flops.UnknownDeviceError, match="TPU v9"):
        chip_smoke._run(str(tmp_path / "out"), False, None)
    assert not (tmp_path / "out").exists()


class TestCompileCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them."""
        import jax

        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_var_places_the_cache(self, monkeypatch, updates, tmp_path):
        from mpi_tensorflow_tpu.utils import cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache.enable_compile_cache() == str(tmp_path)
        # the program sets no other directory in code
        assert "jax_compilation_cache_dir" not in updates
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_default_is_the_checkout(self, monkeypatch, updates):
        from mpi_tensorflow_tpu.utils import cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        want = os.path.join(REPO, ".jax_cache")
        assert cache.enable_compile_cache() == want
        assert updates["jax_compilation_cache_dir"] == want

    def test_forced_cpu_only_picks_a_subdirectory(self, monkeypatch,
                                                  updates):
        from mpi_tensorflow_tpu.utils import cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        got = cache.enable_compile_cache()
        base = os.path.join(REPO, ".jax_cache")
        # None = this box cannot reload its own XLA:CPU entries: cache off
        assert got is None or os.path.dirname(got) == base
        assert updates.get("jax_compilation_cache_dir") == got
