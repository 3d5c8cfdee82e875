"""Real multi-process ``jax.distributed`` bring-up (no monkeypatching).

The reference's distributed story is N OS processes under ``mpiexec``
joining one MPI world (mpipy.py:208-210, 236-241).  Everything else in
this suite exercises the multi-host code paths with patched
``jax.process_index``/``process_count``; this test actually launches two
processes, each with 4 virtual CPU devices, and runs
``jax.distributed.initialize`` through ``initialize_distributed`` —
coordinator on 127.0.0.1 — then an 8-device cross-process mesh, per-host
data sharding, one psum train step on the reference CNN, the agreed-stop
allgather, and a sharded save committed by process 0 plus a restore onto
a different mesh layout.  See tests/_bringup_worker.py for the body.

Deep tier: two fresh interpreters + two backend bring-ups + a conv-model
compile each — tens of seconds on a loaded box.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_bringup_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("devices_per_proc", [4])
def test_two_process_bringup(tmp_path, devices_per_proc):
    nprocs = 2
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # workers never inherit a chip
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_proc}")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # each process must see only its own virtual devices
    env.pop("JAX_NUM_CPU_DEVICES", None)

    # worker output goes to FILES, not pipes: a worker blocked on a full
    # stdout pipe can no longer reach the collective its peer is waiting
    # in — a cross-process deadlock the parent's sequential communicate()
    # would sit out until timeout
    logs = [open(tmp_path / f"worker_{i}.log", "w+") for i in range(nprocs)]
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), str(nprocs), coord,
             str(tmp_path)],
            env=env, cwd=REPO, stdout=logs[i], stderr=subprocess.STDOUT,
            text=True)
        for i in range(nprocs)
    ]
    timed_out = False
    try:
        for p in procs:
            try:
                p.wait(timeout=170)
            except subprocess.TimeoutExpired:
                timed_out = True       # read the logs before failing —
                break                  # they localize the hang
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        outs = []
        for f in logs:
            f.seek(0)
            outs.append(f.read())
            f.close()
    assert not timed_out, (
        "workers timed out (cross-process hang?); logs:\n"
        + "\n---\n".join(o[-2000:] for o in outs))
    for i, p in enumerate(procs):
        assert p.returncode == 0, (
            f"worker {i} rc={p.returncode}:\n{outs[i][-3000:]}")

    results = {}
    for i in range(nprocs):
        with open(tmp_path / f"result_{i}.json") as f:
            results[i] = json.load(f)

    for i, r in results.items():
        assert r["process_index"] == i
        assert r["process_count"] == nprocs
        assert r["device_count"] == nprocs * devices_per_proc
        assert r["local_device_count"] == devices_per_proc
        # host_shard gave each process exactly half the 32-row stream
        assert r["local_rows"] == 32 // nprocs
        # the psum train step produced one finite, agreed loss
        assert r["loss"] > 0
        assert r["opt_step"] == 1.0
        # multi-host: local stop suppressed, agreed stop fired on BOTH
        assert r["stop_now_suppressed"] is True
        assert r["stop_agreed"] is True
        assert r["meta_committed"] is True
        assert r["restore_ok"] is True
        assert r["restored_step"] == 1
    # the loss is a global psum — bitwise identical across processes
    assert results[0]["loss"] == results[1]["loss"]
