"""EP tests: MoE routing, capacity dispatch and the DP x EP x SP step (the PP
half is tests/test_pipeline_*.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from mpi_tensorflow_tpu.data import synthetic
from mpi_tensorflow_tpu.models import bert, moe
from mpi_tensorflow_tpu.parallel import mesh as meshlib
from mpi_tensorflow_tpu.train import gspmd


class TestMoe:
    @pytest.fixture(scope="class")
    def mesh_exp(self):
        return meshlib.make_mesh({"data": 2, "expert": 2, "seq": 2})

    def test_expert_params_sharded(self, mesh_exp):
        model = moe.MoeBertMlm(bert.BERT_TINY, mesh=mesh_exp,
                               moe=moe.MoeConfig(num_experts=4))
        tx = optax.adamw(1e-3)
        state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh_exp)
        lp = state.params["layers"][1]          # odd layers are MoE
        assert "ew1" in lp and "w1" not in lp
        assert lp["ew1"].sharding.spec == P("expert",)
        assert "w1" in state.params["layers"][0]  # even layers stay dense

    def test_full_step_dp_ep_sp(self, mesh_exp):
        """Train step with batch over data, experts over expert, seq over
        seq — EP joins the covered strategy set."""
        model = moe.MoeBertMlm(bert.BERT_TINY, mesh=mesh_exp,
                               moe=moe.MoeConfig(num_experts=4))
        tx = optax.adamw(2e-3)
        state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh_exp)
        step = gspmd.make_gspmd_train_step(model, mesh_exp, tx)
        tokens, targets, mask = synthetic.mlm_batches(
            4, seq_len=32, vocab_size=bert.BERT_TINY.vocab_size)
        batch = gspmd.shard_batch({"tokens": tokens, "mask": mask}, mesh_exp)
        tgt = gspmd.shard_batch(targets, mesh_exp)
        losses = []
        for _ in range(8):
            state, m = step(state, batch, tgt, jax.random.key(1))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0] - 0.5, losses

    def test_capacity_dispatch_matches_naive(self):
        """Scatter/gather dispatch == a per-token python loop: top-1 expert,
        first-come capacity, gate-scaled output, dropped tokens -> zero."""
        cfg = bert.BERT_TINY
        model = moe.MoeBertMlm(
            cfg, moe=moe.MoeConfig(num_experts=4, capacity_factor=0.5))
        params = model.init(jax.random.key(0))
        lp = params["layers"][1]
        rng = np.random.default_rng(3)
        B, S, E = 4, 32, cfg.hidden
        h = jnp.asarray(rng.normal(size=(B, S, E)).astype(np.float32))
        out, aux = model._moe_mlp(h, lp)

        N = B * S
        C = model.capacity(N)
        assert C < N // 4, "capacity must actually drop tokens in this test"
        hf = np.asarray(h).reshape(N, E)
        gates = np.asarray(jax.nn.softmax(
            jnp.asarray(hf) @ lp["router"], axis=-1))
        top1 = gates.argmax(-1)
        want = np.zeros((N, E), np.float32)
        counts = np.zeros(4, np.int64)
        dropped = 0
        for n in range(N):
            x = int(top1[n])
            if counts[x] >= C:
                dropped += 1
                continue
            counts[x] += 1
            a = np.asarray(jax.nn.gelu(
                jnp.asarray(hf[n]) @ lp["ew1"][x] + lp["eb1"][x]))
            o = np.asarray(jnp.asarray(a) @ lp["ew2"][x] + lp["eb2"][x])
            want[n] = o * gates[n, x]
        assert dropped > 0, "test must exercise the overflow path"
        np.testing.assert_allclose(np.asarray(out).reshape(N, E), want,
                                   rtol=2e-4, atol=2e-5)
        assert np.isfinite(float(aux))

    def test_top2_dispatch_matches_naive(self):
        """GShard-style top-2: second choice fills remaining capacity,
        outputs combined with normalized gates; python-loop reference."""
        cfg = bert.BERT_TINY
        model = moe.MoeBertMlm(
            cfg, moe=moe.MoeConfig(num_experts=4, top_k=2,
                                   capacity_factor=1.0))
        params = model.init(jax.random.key(0))
        lp = params["layers"][1]
        rng = np.random.default_rng(11)
        B, S, E = 2, 32, cfg.hidden
        h = jnp.asarray(rng.normal(size=(B, S, E)).astype(np.float32))
        out, aux = model._moe_mlp(h, lp)

        N = B * S
        C = model.capacity(N)
        hf = np.asarray(h).reshape(N, E)
        gates = np.asarray(jax.nn.softmax(
            jnp.asarray(hf) @ lp["router"], axis=-1))
        top1 = gates.argmax(-1)
        g2m = gates.copy()
        g2m[np.arange(N), top1] = 0.0
        top2 = g2m.argmax(-1)

        def expert_out(n, x):
            a = np.asarray(jax.nn.gelu(
                jnp.asarray(hf[n]) @ lp["ew1"][x] + lp["eb1"][x]))
            return np.asarray(jnp.asarray(a) @ lp["ew2"][x] + lp["eb2"][x])

        counts = np.zeros(4, np.int64)
        kept1 = np.zeros(N, bool)
        for n in range(N):           # choice-1 pass claims buffers first
            x = int(top1[n])
            if counts[x] < C:
                counts[x] += 1
                kept1[n] = True
        counts2 = counts.copy()
        want = np.zeros((N, E), np.float32)
        for n in range(N):
            g1, g2 = gates[n, top1[n]], g2m[n, top2[n]]
            w1, w2 = g1 / max(g1 + g2, 1e-9), g2 / max(g1 + g2, 1e-9)
            if kept1[n]:
                want[n] += expert_out(n, int(top1[n])) * w1
            x2 = int(top2[n])
            if counts2[x2] < C:
                counts2[x2] += 1
                want[n] += expert_out(n, x2) * w2
        np.testing.assert_allclose(np.asarray(out).reshape(N, E), want,
                                   rtol=3e-4, atol=3e-5)
        assert np.isfinite(float(aux))

    def test_per_expert_flops_independent_of_expert_count(self):
        """The routed MLP's compiled FLOPs must not scale with num_experts
        (capacity shrinks as experts grow) — the point of real EP dispatch."""
        cfg = bert.BERT_TINY
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.normal(size=(4, 64, cfg.hidden))
                        .astype(np.float32))

        def flops(X):
            model = moe.MoeBertMlm(
                cfg, moe=moe.MoeConfig(num_experts=X, capacity_factor=1.0))
            params = model.init(jax.random.key(0))
            lp = params["layers"][1]
            f = jax.jit(lambda hh: model._moe_mlp(hh, lp)[0])
            cost = f.lower(h).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            return (cost or {}).get("flops")

        f2, f8 = flops(2), flops(8)
        if not f2 or not f8:
            pytest.skip("cost_analysis unavailable on this backend")
        # 4x the experts must NOT mean ~4x the FLOPs; allow routing overhead
        assert f8 < 2.0 * f2, (f2, f8)

    def test_moe_layers_apply_dropout(self):
        """The MoE encoder inherits dropout (round-1 gap: it was silently
        dropped)."""
        import dataclasses as dc

        cfg = dc.replace(bert.BERT_TINY, dropout=0.3)
        model = moe.MoeBertMlm(cfg, moe=moe.MoeConfig(num_experts=2))
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(5)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                             jnp.int32)
        batch = {"tokens": tokens,
                 "mask": jnp.asarray(rng.random((2, 16)) < 0.3)}
        labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                             jnp.int32)
        l_eval, _ = model.loss(params, None, batch, labels, train=False)
        l_tr1, _ = model.loss(params, None, batch, labels, train=True,
                              rng=jax.random.key(1))
        l_tr2, _ = model.loss(params, None, batch, labels, train=True,
                              rng=jax.random.key(2))
        assert float(l_tr1) != float(l_eval)
        assert float(l_tr1) != float(l_tr2)

    def test_routing_is_selective(self):
        """Different tokens must reach different experts (not all one)."""
        model = moe.MoeBertMlm(bert.BERT_TINY,
                               moe=moe.MoeConfig(num_experts=4))
        params = model.init(jax.random.key(0))
        h = jnp.array(np.random.default_rng(0).normal(
            size=(2, 16, bert.BERT_TINY.hidden)).astype(np.float32))
        gate_logits = jnp.einsum(
            "bse,ec->bsc", h, params["layers"][1]["router"])
        top1 = np.asarray(jnp.argmax(gate_logits, -1))
        assert len(np.unique(top1)) > 1
