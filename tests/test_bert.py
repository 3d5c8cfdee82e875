"""BERT-MLM tests: forward, loss, and the flagship multi-axis (DP x TP x SP)
GSPMD train step on a 2x2x2 mesh of the 8 virtual devices."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_tensorflow_tpu.data import synthetic
from mpi_tensorflow_tpu.models import bert
from mpi_tensorflow_tpu.parallel import mesh as meshlib, sharding_rules
from mpi_tensorflow_tpu.train import gspmd


@pytest.fixture(scope="module")
def mesh222():
    return meshlib.make_mesh({"data": 2, "model": 2, "seq": 2})


def mlm_batch(n=4, s=32, vocab=1024, seed=0):
    tokens, targets, mask = synthetic.mlm_batches(
        n, seq_len=s, vocab_size=vocab, seed=seed)
    return {"tokens": tokens, "mask": mask}, targets


class TestBertForward:
    def test_tiny_forward_shape(self):
        model = bert.BertMlm(bert.BERT_TINY)
        params = model.init(jax.random.key(0))
        tokens = np.zeros((2, 16), np.int32)
        logits = model.apply(params, tokens, train=False)
        assert logits.shape == (2, 16, bert.BERT_TINY.vocab_size)

    def test_base_param_count(self):
        model = bert.BertMlm(bert.BERT_BASE)
        params = model.init(jax.random.key(0))
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        # BERT-base encoder + tied MLM head ~ 110M
        assert 100e6 < n < 120e6, n

    def test_logical_axes_tree_matches_params(self):
        model = bert.BertMlm(bert.BERT_TINY)
        params = model.init(jax.random.key(0))
        axes = model.logical_axes()
        # same structure; every leaf's rank equals its axis-tuple length
        jax.tree.map(
            lambda p, a: (_ for _ in ()).throw(AssertionError((p.shape, a)))
            if p.ndim != len(a) else None,
            params, axes, is_leaf=lambda x: isinstance(x, tuple))

    def test_mlm_loss_masks_positions(self):
        model = bert.BertMlm(bert.BERT_TINY)
        params = model.init(jax.random.key(0))
        batch, targets = mlm_batch(n=2, s=16)
        loss, _ = model.loss(params, {}, batch, targets, train=False)
        assert np.isfinite(float(loss))
        # loss ~ log(vocab) at init for a uniform predictor
        assert 0.5 * np.log(1024) < float(loss) < 2.0 * np.log(1024)


class TestGspmdStep:
    def test_sharded_placement(self, mesh222):
        model = bert.BertMlm(bert.BERT_TINY, mesh=mesh222)
        tx = optax.adamw(1e-3)
        state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh222)
        spec = state.params["tok_emb"].sharding.spec
        assert spec == P("model",)          # vocab-parallel embedding
        spec = state.params["layers"][0]["wq"].sharding.spec
        assert spec == P(None, "model")     # heads tensor-parallel
        spec = state.params["layers"][0]["w1"].sharding.spec
        assert spec == P(None, "model")     # MLP column-parallel

    def test_full_step_dp_tp_sp(self, mesh222):
        """The flagship check: one full train step with batch over data,
        heads over model, sequence over seq (ring attention inside)."""
        model = bert.BertMlm(bert.BERT_TINY, mesh=mesh222)
        tx = optax.adamw(2e-3)
        state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh222)
        train_step = gspmd.make_gspmd_train_step(model, mesh222, tx)
        batch, targets = mlm_batch(n=4, s=32)
        batch = gspmd.shard_batch(batch, mesh222)
        targets = gspmd.shard_batch(targets, mesh222)
        losses = []
        for i in range(8):
            state, metrics = train_step(state, batch, targets,
                                        jax.random.key(1))
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(l) for l in losses)
        # memorizing one tiny batch must reduce the loss clearly
        assert losses[-1] < losses[0] - 0.5, losses
        # params remained sharded across the step
        assert state.params["tok_emb"].sharding.spec == P("model",)

    def test_seq_sharding_matches_unsharded(self, mesh222):
        """DPxTPxSP forward == single-device forward (numerics parity of the
        whole sharded stack, ring attention included)."""
        cfg = bert.BERT_TINY
        model_sharded = bert.BertMlm(cfg, mesh=mesh222)
        model_plain = bert.BertMlm(cfg)
        params = model_plain.init(jax.random.key(0))
        tokens = np.asarray(
            np.random.default_rng(0).integers(5, cfg.vocab_size, (4, 32)),
            np.int32)
        want = model_plain.apply(params, tokens, train=False)
        sharded_params = sharding_rules.shard_tree(
            params, model_plain.logical_axes(), mesh222)
        got = jax.jit(lambda p, t: model_sharded.apply(p, t, train=False))(
            sharded_params, gspmd.shard_batch(jnp.array(tokens), mesh222))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-5)


class TestGspmdGradAccum:
    def test_accum_matches_full_batch(self, mesh222):
        """grad_accum=2 microbatching == one full-batch step (dropout is 0
        in BERT_TINY -> same loss/params up to float reassociation)."""
        import dataclasses as dc

        cfg = dc.replace(bert.BERT_TINY, dropout=0.0)
        model = bert.BertMlm(cfg, mesh=mesh222)
        tx = optax.sgd(1e-2)   # stateless optimizer -> exact comparison
        batch, targets = mlm_batch(n=4, s=32)
        batch_s = gspmd.shard_batch(batch, mesh222)
        targets_s = gspmd.shard_batch(targets, mesh222)

        s1 = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh222)
        full = gspmd.make_gspmd_train_step(model, mesh222, tx)
        s1, m1 = full(s1, batch_s, targets_s, jax.random.key(1))

        s2 = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh222)
        acc = gspmd.make_gspmd_train_step(model, mesh222, tx, grad_accum=2)
        s2, m2 = acc(s2, batch_s, targets_s, jax.random.key(1))

        assert float(m2["loss"]) == pytest.approx(float(m1["loss"]),
                                                  rel=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6),
            s2.params, s1.params)


class TestRemat:
    def test_remat_forward_and_grads_match(self):
        """jax.checkpoint changes memory, not math: logits and grads must
        match the plain model exactly (same dropout keys by construction)."""
        import dataclasses as dc

        cfg_p = dc.replace(bert.BERT_TINY, dropout=0.1)
        cfg_r = dc.replace(cfg_p, remat=True)
        m_p, m_r = bert.BertMlm(cfg_p), bert.BertMlm(cfg_r)
        params = m_p.init(jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg_p.vocab_size, (2, 16)),
            jnp.int32)
        key = jax.random.key(7)

        lp = m_p.apply(params, tokens, train=True, rng=key)
        lr = m_r.apply(params, tokens, train=True, rng=key)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lp),
                                   rtol=1e-6, atol=1e-6)

        def loss(m):
            def f(p):
                out = m.apply(p, tokens, train=True, rng=key)
                return jnp.sum(out ** 2) / out.size
            return f

        gp = jax.jit(jax.grad(loss(m_p)))(params)
        gr = jax.jit(jax.grad(loss(m_r)))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            gr, gp)

    def test_remat_dots_policy_matches_plain(self):
        """The 'dots' policy (save matmul outputs, recompute elementwise)
        changes what is SAVED, never the math: logits and grads must
        match the plain model, dropout masks included."""
        import dataclasses as dc

        cfg_p = dc.replace(bert.BERT_TINY, dropout=0.1)
        cfg_d = dc.replace(cfg_p, remat=True, remat_policy="dots")
        m_p, m_d = bert.BertMlm(cfg_p), bert.BertMlm(cfg_d)
        params = m_p.init(jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg_p.vocab_size, (2, 16)),
            jnp.int32)
        key = jax.random.key(9)
        np.testing.assert_allclose(
            np.asarray(m_d.apply(params, tokens, train=True, rng=key)),
            np.asarray(m_p.apply(params, tokens, train=True, rng=key)),
            rtol=1e-6, atol=1e-6)

        def loss(m):
            def f(p):
                out = m.apply(p, tokens, train=True, rng=key)
                return jnp.sum(out ** 2) / out.size
            return f

        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            jax.grad(loss(m_d))(params), jax.grad(loss(m_p))(params))

        with pytest.raises(ValueError, match="remat_policy"):
            bert.BertMlm(dc.replace(cfg_p, remat=True,
                                    remat_policy="nope")) \
                .apply(params, tokens)

    def test_fused_qkv_forward_and_grads_match(self):
        """fused_qkv changes dispatch shape, not math: one stacked
        (E, 3HD) matmul must reproduce the three separate projections
        bit-for-bit in fp32 (same params, same dropout keys)."""
        import dataclasses as dc

        cfg_p = dc.replace(bert.BERT_TINY, dropout=0.1)
        cfg_f = dc.replace(cfg_p, fused_qkv=True)
        m_p, m_f = bert.BertMlm(cfg_p), bert.BertMlm(cfg_f)
        params = m_p.init(jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg_p.vocab_size, (2, 16)),
            jnp.int32)
        key = jax.random.key(7)

        lp = m_p.apply(params, tokens, train=True, rng=key)
        lf = m_f.apply(params, tokens, train=True, rng=key)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lp),
                                   rtol=1e-6, atol=1e-6)

        def loss(m):
            def f(p):
                out = m.apply(p, tokens, train=True, rng=key)
                return jnp.sum(out ** 2) / out.size
            return f

        gp = jax.grad(loss(m_p))(params)
        gf = jax.grad(loss(m_f))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            gf, gp)

    def test_remat_gspmd_step_runs(self, mesh222):
        import dataclasses as dc

        model = bert.BertMlm(dc.replace(bert.BERT_TINY, remat=True),
                             mesh=mesh222)
        tx = optax.adamw(1e-3)
        state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh222)
        step = gspmd.make_gspmd_train_step(model, mesh222, tx)
        batch, targets = mlm_batch(n=4, s=32)
        state, metrics = step(state, gspmd.shard_batch(batch, mesh222),
                              gspmd.shard_batch(targets, mesh222),
                              jax.random.key(1))
        assert np.isfinite(float(metrics["loss"]))
