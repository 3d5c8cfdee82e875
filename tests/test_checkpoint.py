"""Checkpoint/resume: round-trip fidelity, sharding restoration, loop resume."""

import jax
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from mpi_tensorflow_tpu.config import Config
from mpi_tensorflow_tpu.data import mnist
from mpi_tensorflow_tpu.models import bert, cnn
from mpi_tensorflow_tpu.parallel import mesh as meshlib
from mpi_tensorflow_tpu.train import checkpoint, gspmd, loop, step


class TestRoundTrip:
    def test_train_state(self, tmp_path):
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        p = str(tmp_path / "ck")
        checkpoint.save(p, st, step=7, extra={"note": "x"})
        st2, meta = checkpoint.restore(p, step.init_state(model,
                                                          jax.random.key(2)))
        assert meta["step"] == 7
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_restores_sharding(self, tmp_path):
        mesh = meshlib.make_mesh({"data": 2, "model": 2, "seq": 2})
        model = bert.BertMlm(bert.BERT_TINY, mesh=mesh)
        tx = optax.adamw(1e-3)
        st = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh)
        p = str(tmp_path / "ck")
        checkpoint.save(p, st, step=1)
        template = gspmd.init_gspmd_state(model, tx, jax.random.key(9), mesh)
        st2, _ = checkpoint.restore(p, template)
        # values restored AND placement preserved (vocab-parallel embedding)
        assert st2.params["tok_emb"].sharding.spec == P("model",)
        np.testing.assert_array_equal(np.asarray(st.params["tok_emb"]),
                                      np.asarray(st2.params["tok_emb"]))

    def test_sharded_roundtrip_fsdp(self, tmp_path):
        """Pod-scale format: an FSDP 8-way state round-trips with each
        shard written/read separately — no full-leaf host materialization —
        and restores with placement intact."""
        mesh = meshlib.make_mesh({"data": 8})
        model = bert.BertMlm(bert.BERT_TINY, mesh=mesh)
        tx = optax.adamw(1e-3)
        st = gspmd.init_fsdp_state(model, tx, jax.random.key(0), mesh,
                                   min_size=512)
        p = str(tmp_path / "ck")
        checkpoint.save_sharded(p, st, step=3)
        # sharded leaves produce multiple shard files (not one big blob)
        import json as _json
        import os

        with open(p + ".sharded/meta.json") as f:
            meta = _json.load(f)
        multi = [lm for lm in meta["leaves"] if len(lm["shards"]) > 1]
        assert multi, "no leaf was actually written in shards"
        for lm in multi:
            for s in lm["shards"]:
                assert os.path.exists(p + ".sharded/" + s["file"])

        template = gspmd.init_fsdp_state(model, tx, jax.random.key(9), mesh,
                                         min_size=512)
        st2, meta2 = checkpoint.restore_sharded(p, template)
        assert meta2["step"] == 3
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            if hasattr(a, "sharding"):
                assert a.sharding == b.sharding

    def test_sharded_restore_across_mesh_change(self, tmp_path):
        """Saved on an 8-way FSDP mesh, restored onto a 4-device mesh with
        different placement — each device reads its slice from the files."""
        mesh8 = meshlib.make_mesh({"data": 8})
        model8 = bert.BertMlm(bert.BERT_TINY, mesh=mesh8)
        tx = optax.adamw(1e-3)
        st = gspmd.init_fsdp_state(model8, tx, jax.random.key(0), mesh8,
                                   min_size=512)
        p = str(tmp_path / "ck")
        checkpoint.save_sharded(p, st)

        mesh4 = meshlib.make_mesh({"data": 4},
                                  devices=jax.devices()[:4])
        model4 = bert.BertMlm(bert.BERT_TINY, mesh=mesh4)
        template = gspmd.init_fsdp_state(model4, tx, jax.random.key(9),
                                         mesh4, min_size=512)
        st2, _ = checkpoint.restore_sharded(p, template)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_async_saver_writes_and_survives(self, tmp_path):
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        saver = checkpoint.AsyncSaver()
        p = str(tmp_path / "ckpt_5")
        saver.save(p, st, step=5, sharded=True)
        saver.wait()
        st2, meta = checkpoint.restore_sharded(
            p, step.init_state(model, jax.random.key(2)))
        assert meta["step"] == 5
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert checkpoint.latest_step(str(tmp_path)) == 5
        saver.close()

    def test_restore_latest_prefers_sharded_format(self, tmp_path):
        """restore_latest dispatches per format: npz-only steps restore via
        restore(), sharded steps via restore_sharded()."""
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        checkpoint.save(str(tmp_path / "ckpt_1"), st, step=1)
        checkpoint.save_sharded(str(tmp_path / "ckpt_2"), st, step=2)
        assert checkpoint.latest_step(str(tmp_path)) == 2
        template = step.init_state(model, jax.random.key(9))
        st2, meta2 = checkpoint.restore_latest(str(tmp_path), template, 2)
        assert meta2["step"] == 2
        st1, meta1 = checkpoint.restore_latest(str(tmp_path), template, 1)
        assert meta1["step"] == 1
        for a, b in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mismatch_raises(self, tmp_path):
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        p = str(tmp_path / "ck")
        checkpoint.save(p, st)
        other = step.init_state(cnn.MnistCnn(hidden=256), jax.random.key(1))
        with pytest.raises(ValueError, match="mismatch"):
            checkpoint.restore(p, other)

    def test_latest_step(self, tmp_path):
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        for s in (3, 10, 7):
            checkpoint.save(checkpoint.step_path(str(tmp_path), s), st, step=s)
        assert checkpoint.latest_step(str(tmp_path)) == 10
        assert checkpoint.latest_step(str(tmp_path / "nope")) is None


class TestLoopResume:
    def test_resume_continues(self, mesh8, mnist_dir, tmp_path):
        splits = mnist.load_splits(mnist_dir, num_shards=8,
                                   train_n=1200, test_n=256)
        ckdir = str(tmp_path / "ckpts")
        # "interrupted" run: 1 epoch writes checkpoints partway
        cfg = Config(epochs=1, batch_size=8, log_every=10, seed=1,
                     checkpoint_dir=ckdir)
        r1 = loop.train(cfg, splits=splits, mesh=mesh8, verbose=False)
        last = checkpoint.latest_step(ckdir)
        assert last is not None
        # resume with the full 2-epoch budget: picks up after `last`
        cfg2 = Config(epochs=2, batch_size=8, log_every=10, seed=1,
                      checkpoint_dir=ckdir, resume=True)
        r2 = loop.train(cfg2, splits=splits, mesh=mesh8, verbose=False)
        assert r2.num_steps > r1.num_steps  # 2-epoch budget
        assert r2.history[0][0] > last  # did not restart from step 0
        # restored momentum/step counter: opt step equals total steps run
        assert float(r2.state.opt.step) == pytest.approx(
            r2.num_steps - (last + 1) + float(r1.state.opt.step))


class TestCommitSemantics:
    """Commit markers and the async-commit threading contract."""

    def test_bare_npz_is_not_committed(self, tmp_path):
        """A kill between the .npz replace and the .json sidecar write must
        fall back to the previous committed step, not crash restore."""
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        checkpoint.save(checkpoint.step_path(str(tmp_path), 3), st, step=3)
        # simulate the interrupted write: npz present, sidecar missing
        import shutil
        p5 = checkpoint.step_path(str(tmp_path), 5)
        shutil.copy(checkpoint.step_path(str(tmp_path), 3) + ".npz",
                    p5 + ".npz")
        assert checkpoint.latest_step(str(tmp_path)) == 3

    def test_multihost_commit_runs_on_main_thread(self, tmp_path,
                                                  monkeypatch):
        """The sharded commit barrier is a device collective: with >1
        process it must never run on the saver's worker thread (collective
        enqueue order would race the train step's — pod deadlock).  The
        worker writes shard files only; the barrier+meta commit happens in
        the next main-thread save()/wait()."""
        import threading

        calls = []
        real = checkpoint._barrier_and_commit

        def spy(d, meta):
            calls.append(threading.current_thread())
            # skip the real barrier (single actual process) but do commit
            import json as j, os as o
            with open(o.path.join(d, "meta.json"), "w") as f:
                j.dump(meta, f)

        monkeypatch.setattr(checkpoint, "_barrier_and_commit", spy)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(checkpoint, "_all_hosts_ok", lambda ok: ok)
        try:
            model = cnn.MnistCnn()
            st = step.init_state(model, jax.random.key(1))
            saver = checkpoint.AsyncSaver()
            p = str(tmp_path / "ckpt_7")
            saver.save(p, st, step=7, sharded=True)
            # commit is deferred: no marker until a main-thread drain
            assert not (tmp_path / "ckpt_7.sharded" / "meta.json").exists()
            saver.wait()
            assert (tmp_path / "ckpt_7.sharded" / "meta.json").exists()
            assert calls == [threading.main_thread()]
            saver.close()
        finally:
            monkeypatch.setattr(checkpoint, "_barrier_and_commit", real)

    def test_async_saver_bounds_live_snapshots(self, tmp_path):
        """A second save() joins the first write before snapshotting: at
        most one host snapshot is live (the documented memory bound)."""
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        saver = checkpoint.AsyncSaver()
        for s in (1, 2, 3):
            saver.save(checkpoint.step_path(str(tmp_path), s), st, step=s)
            # the previous write is fully on disk before this line returns
            if s > 1:
                assert checkpoint.latest_step(str(tmp_path)) >= s - 1
        saver.close()
        assert checkpoint.latest_step(str(tmp_path)) == 3

    def test_peer_write_failure_skips_commit_and_raises(self, tmp_path,
                                                        monkeypatch):
        """If any host's shard write failed, NO host may enter the commit
        barrier (the healthy ones raise instead of hanging in a collective
        their failed peer never joins)."""
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(checkpoint, "_all_hosts_ok", lambda ok: False)
        saver = checkpoint.AsyncSaver()
        p = str(tmp_path / "ckpt_9")
        saver.save(p, st, step=9, sharded=True)   # local write succeeds
        with pytest.raises(RuntimeError, match="peer host"):
            saver.wait()
        assert not (tmp_path / "ckpt_9.sharded" / "meta.json").exists()

    def test_local_write_failure_never_commits(self, tmp_path, monkeypatch):
        model = cnn.MnistCnn()
        st = step.init_state(model, jax.random.key(1))
        monkeypatch.setattr(jax, "process_count", lambda: 2)

        def boom(d, jobs):
            raise OSError("disk full")

        monkeypatch.setattr(checkpoint, "_write_shard_files", boom)
        monkeypatch.setattr(checkpoint, "_all_hosts_ok", lambda ok: ok)
        saver = checkpoint.AsyncSaver()
        p = str(tmp_path / "ckpt_11")
        saver.save(p, st, step=11, sharded=True)
        with pytest.raises(RuntimeError, match="checkpoint write failed"):
            saver.wait()
        assert not (tmp_path / "ckpt_11.sharded" / "meta.json").exists()
