"""Fault injection through a REAL process (VERDICT r2 #9).

The round-2 recovery tests simulated failures by raising exceptions inside
the process; this launches the actual CLI in a subprocess, SIGKILLs it
mid-run (no grace, no signal handler — the crash-durability path, not the
preemption path), and relaunches with --resume, asserting the run
continues from the last COMMITTED checkpoint step.

Also pins the status-code-first transient classification
(train/elastic.py): the canonical gRPC/absl code a PJRT error carries
decides retry-vs-fail before any message substring can.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from mpi_tensorflow_tpu.train import checkpoint, elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestTransientClassification:
    pytestmark = pytest.mark.quick

    def test_status_code_beats_substring(self):
        # body mentions "invalid_argument", but the structured code says
        # UNAVAILABLE -> retry
        e = RuntimeError("UNAVAILABLE: peer rejected invalid_argument blob")
        assert elastic.is_transient(e)
        # and the reverse: a permanent code with chatty transient words
        e = RuntimeError("RESOURCE_EXHAUSTED: connection pool preempted")
        assert not elastic.is_transient(e)

    def test_reworded_message_with_code_still_retries(self):
        # the round-2 hazard: a reworded device-loss message; the code
        # prefix is the stable contract
        assert elastic.is_transient(RuntimeError(
            "ABORTED: some brand new wording nobody grepped for"))

    def test_type_first(self):
        assert elastic.is_transient(ConnectionResetError("whatever"))
        assert elastic.is_transient(OSError("anything at all"))

    def test_plain_runtime_error_falls_back_to_substrings(self):
        assert elastic.is_transient(RuntimeError("device lost mid-step"))
        assert not elastic.is_transient(RuntimeError("shape mismatch (4,)"))

    def test_unknown_code_falls_through_to_substrings(self):
        # UNKNOWN is gRPC's catch-all for peer-side bugs: it must NOT
        # force a retry; the substring heuristics decide
        assert not elastic.is_transient(
            RuntimeError("UNKNOWN: invalid_argument in peer handler"))
        assert elastic.is_transient(
            RuntimeError("UNKNOWN: socket connection dropped"))


def _cli_env(devices=8):
    # the canonical forced-CPU incantation (cache gating + collective
    # rendezvous timeouts + platform forcing) lives in ONE place
    from __graft_entry__ import _force_virtual_cpu_env

    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    _force_virtual_cpu_env(env, devices)
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _launch(args, env):
    return subprocess.Popen(
        [sys.executable, "-m", "mpi_tensorflow_tpu"] + args,
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


def _read_until(proc, pred, deadline_s):
    """Collect stdout lines until ``pred(lines)`` or deadline/exit."""
    lines = []
    t0 = time.time()
    while time.time() - t0 < deadline_s:
        line = proc.stdout.readline()
        if line:
            lines.append(line.rstrip("\n"))
            if pred(lines):
                return lines, True
        elif proc.poll() is not None:
            break
    return lines, False


class TestServingSigkillReplay:
    """The serving analogue of TestSigkillResume: SIGKILL a real
    ``python -m mpi_tensorflow_tpu.serving`` process mid-decode (no grace,
    no signal handler), relaunch with the same replay journal, and require the
    recovered outputs to be TOKEN-IDENTICAL to an unfaulted run —
    greedy decode is deterministic, so the journal's prompt+prefix
    replay is exact."""

    def _serve(self, env, journal, extra=()):
        args = ["-m", "mpi_tensorflow_tpu.serving", "--tiny",
                "--precision", "fp32", "--num-requests", "6",
                "--prompt-max", "12", "--output-max", "80",
                "--rate-rps", "1000",
                "--journal", journal] + list(extra)
        return subprocess.Popen([sys.executable] + args, cwd=REPO, env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    @staticmethod
    def _outputs(proc_stdout: str) -> dict:
        import json

        rec = json.loads(proc_stdout.strip().splitlines()[-1])
        return rec["outputs"], rec["statuses"]

    def test_sigkill_mid_decode_then_replay_token_identical(self, tmp_path):
        env = _cli_env()
        journal = str(tmp_path / "serve_journal.jsonl")

        # run 1: SIGKILL once the journal shows live mid-decode work
        # (tokens recorded, nothing near the ~460-token completion)
        proc = self._serve(env, journal)
        try:
            t0 = time.time()
            killed = False
            while time.time() - t0 < 150:
                if proc.poll() is not None:
                    break
                try:
                    with open(journal) as f:
                        toks = sum('"tok"' in ln for ln in f)
                except OSError:
                    toks = 0
                if toks >= 8:
                    proc.send_signal(signal.SIGKILL)   # no grace
                    proc.wait(timeout=30)
                    killed = True
                    break
                time.sleep(0.005)
            assert killed, "serving run never reached mid-decode state"
        finally:
            if proc.poll() is None:
                proc.kill()

        # the journal must hold live (unterminated) work — a real crash
        from mpi_tensorflow_tpu.serving import ReplayJournal

        state = ReplayJournal(journal)
        live = [rid for rid, e in state.entries.items() if e.status is None]
        state.close()
        assert live, "SIGKILL landed after completion; nothing to replay"

        # run 2: same journal — resumes and completes
        proc2 = self._serve(env, journal)
        out2, _ = proc2.communicate(timeout=150)
        assert proc2.returncode == 0, out2
        got, statuses = self._outputs(out2)
        assert set(statuses.values()) == {"ok"}, statuses

        # run 3: unfaulted reference with a fresh journal
        proc3 = self._serve(env, str(tmp_path / "clean.jsonl"))
        out3, _ = proc3.communicate(timeout=150)
        assert proc3.returncode == 0, out3
        want, _ = self._outputs(out3)
        assert got == want, "recovered outputs diverged from unfaulted run"


class TestFleetSigkillReplay:
    """The FLEET analogue of TestServingSigkillReplay (ISSUE 9): SIGKILL
    a real ``python -m mpi_tensorflow_tpu.serving --replicas 2 --journal``
    process mid-decode — journaling is per-replica (``<path>.r0`` /
    ``<path>.r1``) — relaunch with the same arguments, and require the
    merged recovered outputs to be TOKEN-IDENTICAL to an unfaulted
    fleet run.  This is the combination PR 6 forbade (replicas x
    journal were mutually exclusive); it now IS the fault-tolerant
    fleet serve mode."""

    N_REPLICAS = 2

    def _serve(self, env, journal):
        args = ["-m", "mpi_tensorflow_tpu.serving", "--tiny",
                "--precision", "fp32", "--num-requests", "6",
                "--prompt-max", "12", "--output-max", "80",
                "--rate-rps", "1000",
                "--replicas", str(self.N_REPLICAS),
                "--journal", journal]
        return subprocess.Popen([sys.executable] + args, cwd=REPO, env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    def _journal_toks(self, journal):
        total = 0
        for i in range(self.N_REPLICAS):
            try:
                with open(f"{journal}.r{i}") as f:
                    total += sum('"tok"' in ln for ln in f)
            except OSError:
                pass
        return total

    @staticmethod
    def _outputs(proc_stdout: str) -> tuple:
        import json

        rec = json.loads(proc_stdout.strip().splitlines()[-1])
        return rec["outputs"], rec["statuses"]

    def test_sigkill_fleet_then_replay_token_identical(self, tmp_path):
        env = _cli_env()
        journal = str(tmp_path / "fleet_journal.jsonl")

        # run 1: SIGKILL once the per-replica journals show live
        # mid-decode work (tokens recorded, far from the ~460-token
        # completion)
        proc = self._serve(env, journal)
        try:
            t0 = time.time()
            killed = False
            while time.time() - t0 < 150:
                if proc.poll() is not None:
                    break
                if self._journal_toks(journal) >= 8:
                    proc.send_signal(signal.SIGKILL)   # no grace
                    proc.wait(timeout=30)
                    killed = True
                    break
                time.sleep(0.005)
            assert killed, "fleet never reached mid-decode state"
        finally:
            if proc.poll() is None:
                proc.kill()

        # the merged journals must hold live (unterminated) work — a
        # real crash, with both replicas' files present
        from mpi_tensorflow_tpu.serving import ReplayJournal
        from mpi_tensorflow_tpu.serving.recovery import \
            merge_fleet_entries

        journals = [ReplayJournal(f"{journal}.r{i}")
                    for i in range(self.N_REPLICAS)]
        live = [rid for rid, (ent, _j) in
                merge_fleet_entries(journals).items()
                if ent.status is None]
        for j in journals:
            j.close()
        assert live, "SIGKILL landed after completion; nothing to replay"

        # run 2: same journals — the fleet resumes and completes
        proc2 = self._serve(env, journal)
        out2, _ = proc2.communicate(timeout=150)
        assert proc2.returncode == 0, out2
        got, statuses = self._outputs(out2)
        assert set(statuses.values()) == {"ok"}, statuses
        assert len(statuses) == 6, statuses

        # run 3: unfaulted fleet reference with fresh journals
        proc3 = self._serve(env, str(tmp_path / "clean.jsonl"))
        out3, _ = proc3.communicate(timeout=150)
        assert proc3.returncode == 0, out3
        want, _ = self._outputs(out3)
        assert got == want, \
            "recovered fleet outputs diverged from unfaulted run"


class TestSigkillResume:
    def test_sigkill_mid_run_then_resume(self, tmp_path):
        """Kill -9 the training process after checkpoints commit; the
        relaunch must resume from the committed step and run to
        completion with the step counter continuing past it.

        What that proves needs no particular mesh or window, so the
        child runs the smallest of each that still commits checkpoints
        before the kill: two devices, fused windows of two steps."""
        from mpi_tensorflow_tpu.data import mnist

        data = tmp_path / "mnist"
        data.mkdir()
        # the CLI splits at the reference's constants (train rows start at
        # 5000): 300 rows / 2 devices / batch 64 = 2 steps per epoch
        mnist._write_synthetic(str(data), train_n=5300, test_n=256)
        ckpt = str(tmp_path / "ckpt")
        env = _cli_env(devices=2)
        # --fused-steps aligned to --log-every: ONE window shape -> one
        # multi-step compile
        common = ["--data-dir", str(data), "--checkpoint-dir", ckpt,
                  "--log-every", "2", "--fused-steps", "2"]

        proc = _launch(common + ["--epochs", "40"], env)
        try:
            def traced(lines):
                # 3 DISTINCT trace points (each prints one line per shard);
                # by the 3rd, the 1st's async save has been drained durable
                # by the 2nd's and committed
                steps = {ln.split("at")[1].split("with")[0].strip()
                         for ln in lines if "with test error" in ln}
                return len(steps) >= 3

            lines, ok = _read_until(proc, traced, deadline_s=120)
            assert ok, "never reached 3 trace points:\n" + "\n".join(lines)
            # no grace: the crash-durability path, not preemption handling
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()

        committed = checkpoint.latest_step(ckpt)
        assert committed is not None and committed >= 2, committed

        # relaunch with just enough epochs to pass the committed step and
        # finish quickly (2 steps/epoch)
        epochs2 = (committed + 1) // 2 + 3
        proc2 = _launch(common + ["--epochs", str(epochs2),
                                  "--resume", "--max-restarts", "1"], env)
        try:
            out, _ = proc2.communicate(timeout=120)
        finally:
            if proc2.poll() is None:
                proc2.kill()
        assert proc2.returncode == 0, out
        assert f"[checkpoint] resumed from step {committed}" in out, out
        # loss continuity: the resumed trace continues past the committed
        # step instead of restarting at step 0
        steps = [int(ln.split("at")[1].split("with")[0])
                 for ln in out.splitlines() if "with test error" in ln]
        assert steps and min(steps) > committed, (committed, steps)
