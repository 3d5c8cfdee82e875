"""PP tests, 1F1B: the manual fwd/bwd interleave against autodiff and
GPipe, its bubble accounting, and 1F1B under SP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _jitted import loss, loss_and_grads
from mpi_tensorflow_tpu.data import synthetic
from mpi_tensorflow_tpu.models import bert
from mpi_tensorflow_tpu.parallel import mesh as meshlib, pipeline, \
    sharding_rules


class TestOneFOneB:
    """Interleaved 1F1B (VERDICT r2 #5): loss+grad parity with GPipe,
    bubble accounting at (P-1)/(M+P-1), and the O(P) stash bound."""

    @pytest.fixture(scope="class")
    def mesh_pd(self):
        return meshlib.make_mesh({"pipe": 4, "data": 2})

    def test_generic_1f1b_matches_autodiff(self):
        """Toy 4-stage tanh pipeline: the schedule's manual grads must
        equal autodiff of the sequential composition."""
        mesh4 = jax.make_mesh((4,), ("pipe",), devices=jax.devices()[:4])
        rng = np.random.default_rng(0)
        Pst, M, mb, d = 4, 6, 2, 8
        W = jnp.asarray(rng.normal(size=(Pst, d, d)).astype(np.float32) * .4)
        Wl = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(M, mb, d)).astype(np.float32))
        tgt = jnp.asarray(rng.normal(size=(M, mb, d)).astype(np.float32))

        def stage_fn(w, h, mi):
            return jnp.tanh(h @ w)

        def last_fn(wl, y, aux):
            return jnp.sum((y * wl - aux) ** 2) / (M * mb)

        def run(W, Wl, x, tgt):
            def inner(Wloc, Wl, x, tgt):
                loss, gs, gl, dx = pipeline.pipeline_1f1b(
                    stage_fn, last_fn, Wloc[0], Wl, x, tgt, "pipe")
                return loss, gs[None], gl, dx
            return jax.shard_map(
                inner, mesh=mesh4, in_specs=(P("pipe"), P(), P(), P()),
                out_specs=(P(), P("pipe"), P(), P()),
                check_vma=False)(W, Wl, x, tgt)

        loss1, gs1, gl1, dx1 = jax.jit(run)(W, Wl, x, tgt)

        def ref_loss(W, Wl, x, tgt):
            def one(xm, tm):
                h = xm
                for s in range(Pst):
                    h = jnp.tanh(h @ W[s])
                return jnp.sum((h * Wl - tm) ** 2) / (M * mb)
            return sum(one(x[i], tgt[i]) for i in range(M))

        loss2, (gW, gWl, gx) = jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2))(W, Wl, x, tgt)
        np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gs1), np.asarray(gW),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gl1), np.asarray(gWl),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dx1), np.asarray(gx),
                                   rtol=1e-4, atol=1e-5)

    def _models(self, mesh, dropout=0.0):
        from mpi_tensorflow_tpu.models import bert_pipeline

        cfg = bert.BertConfig(vocab_size=256, hidden=32, layers=4, heads=4,
                              mlp=64, max_positions=32, dropout=dropout)
        gp = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh,
                                            num_microbatches=2)
        ob = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh,
                                            num_microbatches=2,
                                            schedule="1f1b")
        return gp, ob

    def test_model_loss_and_grads_match_gpipe(self, mesh_pd):
        gp, ob = self._models(mesh_pd)
        params = gp.init(jax.random.key(0))
        params = sharding_rules.shard_tree(params, gp.logical_axes(),
                                           mesh_pd)
        tokens, targets, mask = synthetic.mlm_batches(
            8, seq_len=16, vocab_size=gp.cfg.vocab_size, seed=0)
        batch = {"tokens": tokens, "mask": mask}
        l_gp, g_gp = loss_and_grads(gp, params, batch, targets, train=True)
        l_ob, g_ob = loss_and_grads(ob, params, batch, targets, train=True)
        np.testing.assert_allclose(float(l_ob), float(l_gp), rtol=2e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5), g_gp, g_ob)

    def test_dropout_masks_identical_across_schedules(self, mesh_pd):
        """Both schedules fold dropout keys the same way, so the SAME rng
        must give the SAME loss — a schedule flag cannot change the
        regularization draw."""
        gp, ob = self._models(mesh_pd, dropout=0.1)
        params = gp.init(jax.random.key(0))
        params = sharding_rules.shard_tree(params, gp.logical_axes(),
                                           mesh_pd)
        tokens, targets, mask = synthetic.mlm_batches(
            8, seq_len=16, vocab_size=gp.cfg.vocab_size, seed=0)
        batch = {"tokens": tokens, "mask": mask}
        key = jax.random.key(5)
        l_gp = loss(gp, params, batch, targets, rng=key, train=True)
        l_ob = loss(ob, params, batch, targets, rng=key, train=True)
        np.testing.assert_allclose(float(l_ob), float(l_gp), rtol=2e-5)

    def test_bubble_accounting(self):
        """The schedule table realizes 1F1B's idle fraction
        (P-1)/(M+P-1) exactly, with every mb F'd and B'd once per stage,
        messages consumed one tick after production, and at most P
        activations stashed per stage (the O(P) memory claim)."""
        for Pn, M in ((2, 4), (4, 8), (4, 3), (8, 16)):
            tab = pipeline.schedule_table(Pn, M)
            ticks = len(tab)
            assert ticks == 2 * (M + Pn - 1)
            for s in range(Pn):
                ops = [tab[t][s] for t in range(ticks)]
                idle = sum(1 for o in ops if o is None)
                # per-stage idle = 2(P-1) -> fraction (P-1)/(M+P-1)
                assert idle == 2 * (Pn - 1)
                assert idle / ticks == pytest.approx(
                    (Pn - 1) / (M + Pn - 1))
                assert sorted(i for o, i in
                              [x for x in ops if x and x[0] == "F"]) \
                    == list(range(M))
                assert sorted(i for o, i in
                              [x for x in ops if x and x[0] == "B"]) \
                    == list(range(M))
                # stash occupancy never exceeds P
                live, peak = set(), 0
                for o in ops:
                    if o and o[0] == "F":
                        live.add(o[1])
                    if o and o[0] == "B":
                        live.discard(o[1])
                    peak = max(peak, len(live))
                assert peak <= Pn
            # message timing: F(s,i)@t -> F(s+1,i)@t+1; B(s,i)@t -> B(s-1,i)@t+1
            when = {}
            for t in range(ticks):
                for s in range(Pn):
                    if tab[t][s]:
                        when[(tab[t][s][0], s, tab[t][s][1])] = t
            for i in range(M):
                for s in range(Pn - 1):
                    assert when[("F", s + 1, i)] == when[("F", s, i)] + 1
                    assert when[("B", s, i)] == when[("B", s + 1, i)] + 1
                # loss turnaround at the last stage
                assert when[("B", Pn - 1, i)] == when[("F", Pn - 1, i)] + 1

    def test_schedule_cost_matches_table(self):
        """``schedule_cost``'s accounting must agree with the schedule
        table: the gated path executes exactly the scheduled ops; the
        uniform path executes every tick (VERDICT r4 #4)."""
        for Pn, M in ((2, 4), (4, 8), (8, 16)):
            tab = pipeline.schedule_table(Pn, M)
            ticks = len(tab)
            scheduled_f = sum(1 for row in tab for o in row
                              if o and o[0] == "F") // Pn
            gated = pipeline.schedule_cost(Pn, M, uniform_stages=False)
            uni = pipeline.schedule_cost(Pn, M, uniform_stages=True)
            assert gated["ticks"] == uni["ticks"] == ticks
            assert gated["fwd_body_runs"] == scheduled_f == M
            assert gated["overhead_ratio"] == 1.0
            assert uni["fwd_body_runs"] == ticks
            assert uni["overhead_ratio"] == pytest.approx(
                2 * (M + Pn - 1) / M)
            assert uni["bubble_fraction"] == pytest.approx(
                (Pn - 1) / (M + Pn - 1))
        # the flagship-ish shape: P=4 M=8 pays 2.75x body-equivalents
        assert pipeline.schedule_cost(4, 8, True)["overhead_ratio"] \
            == pytest.approx(2.75)

    def test_1f1b_grad_under_bf16_compute(self, mesh_pd):
        """bf16 compute dtype: the custom_vjp cotangent for the embedding
        stream must come back in the primal's dtype (regression: f32
        cotangent for a bf16 h failed the bwd aval check)."""
        import dataclasses as dc

        from mpi_tensorflow_tpu.models import bert_pipeline

        cfg = dc.replace(bert.BERT_TINY, layers=4, dtype=jnp.bfloat16)
        ob = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh_pd,
                                            num_microbatches=2,
                                            schedule="1f1b")
        params = ob.init(jax.random.key(0))
        params = sharding_rules.shard_tree(params, ob.logical_axes(),
                                           mesh_pd)
        tokens, targets, mask = synthetic.mlm_batches(
            8, seq_len=16, vocab_size=cfg.vocab_size, seed=0)
        batch = {"tokens": tokens, "mask": mask}
        g = loss_and_grads(ob, params, batch, targets, train=True)[1]
        assert all(np.isfinite(np.asarray(x, np.float32)).all()
                   for x in jax.tree.leaves(g))


class TestOneFOneBSP:
    """1F1B + SP (ce_positions='all' — the position-local CE): the
    in-schedule head math runs on seq-sharded activations with local
    sums + a seq psum; parity with GPipe+SP is the correctness pin."""

    CFG = bert.BertConfig(vocab_size=256, hidden=32, layers=4, heads=4,
                          mlp=64, max_positions=32, dropout=0.0,
                          ce_positions="all")

    @pytest.fixture(scope="class")
    def mesh_ps(self):
        return meshlib.make_mesh({"pipe": 2, "seq": 2, "data": 2})

    def _batch(self, cfg, n=8, seq=16, seed=0):
        tokens, targets, mask = synthetic.mlm_batches(
            n, seq_len=seq, vocab_size=cfg.vocab_size, seed=seed)
        return {"tokens": tokens, "mask": mask}, targets

    def _models(self, mesh, cfg=None):
        from mpi_tensorflow_tpu.models import bert_pipeline

        cfg = cfg or self.CFG
        gp = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh,
                                            num_microbatches=2)
        ob = bert_pipeline.PipelinedBertMlm(cfg, mesh=mesh,
                                            num_microbatches=2,
                                            schedule="1f1b")
        params = gp.init(jax.random.key(0))
        params = sharding_rules.shard_tree(params, gp.logical_axes(), mesh)
        return gp, ob, params

    def test_loss_and_grads_match_gpipe_under_sp(self, mesh_ps):
        gp, ob, params = self._models(mesh_ps)
        batch, targets = self._batch(self.CFG)
        l_gp, g_gp = loss_and_grads(gp, params, batch, targets, train=True)
        l_ob, g_ob = loss_and_grads(ob, params, batch, targets, train=True)
        np.testing.assert_allclose(float(l_gp), float(l_ob), rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5),
            g_gp, g_ob)

    def test_dropout_masks_identical_across_schedules_under_sp(self,
                                                               mesh_ps):
        """With dropout on and the same key, both schedules must draw
        IDENTICAL per-(data, seq)-shard masks — the shard fold formulas
        are pinned to each other."""
        import dataclasses as dc

        cfg = dc.replace(self.CFG, dropout=0.3)
        gp, ob, params = self._models(mesh_ps, cfg)
        batch, targets = self._batch(cfg)
        key = jax.random.key(5)
        l_gp = loss(gp, params, batch, targets, rng=key, train=True)
        l_ob = loss(ob, params, batch, targets, rng=key, train=True)
        np.testing.assert_allclose(float(l_gp), float(l_ob), rtol=1e-5)

    def test_causal_1f1b_sp_matches_plain(self, mesh_ps):
        from mpi_tensorflow_tpu.models import bert_pipeline, gpt

        plain = gpt.CausalLm(self.CFG)
        params = plain.init(jax.random.key(0))
        piped = gpt.PipelinedCausalLm(self.CFG, mesh=mesh_ps,
                                      num_microbatches=2,
                                      schedule="1f1b")
        pparams = dict(params)
        pparams["layers"] = bert_pipeline.stack_layers(params["layers"], 2)
        pparams = sharding_rules.shard_tree(pparams, piped.logical_axes(),
                                            mesh_ps)
        toks = self._batch(self.CFG)[0]["tokens"]
        l_plain = loss(plain, params, {"tokens": toks}, None)
        l_pipe = loss(piped, pparams, {"tokens": toks}, None, train=True)
        np.testing.assert_allclose(float(l_plain), float(l_pipe),
                                   rtol=2e-5)

    def test_masked_packing_still_rejected(self, mesh_ps):
        import dataclasses as dc

        from mpi_tensorflow_tpu.models import bert_pipeline

        with pytest.raises(ValueError, match="ce_positions"):
            bert_pipeline.PipelinedBertMlm(
                dc.replace(self.CFG, ce_positions="masked"), mesh=mesh_ps,
                num_microbatches=2, schedule="1f1b")

    def test_1f1b_tp_sp_matches_gpipe(self):
        """The FULL claimed composition pipe x model x seq under 1F1B:
        vocab-parallel CE on seq-sharded position slices inside the
        schedule, ring attention on the local head subset — loss and
        grads must match the GPipe schedule's."""
        mesh = meshlib.make_mesh({"pipe": 2, "model": 2, "seq": 2})
        gp, ob, params = self._models(mesh)
        batch, targets = self._batch(self.CFG)
        l_gp, g_gp = loss_and_grads(gp, params, batch, targets, train=True)
        l_ob, g_ob = loss_and_grads(ob, params, batch, targets, train=True)
        np.testing.assert_allclose(float(l_gp), float(l_ob), rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5),
            g_gp, g_ob)
