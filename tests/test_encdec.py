"""Encoder-decoder family (models/encdec.py): cross-attention wiring,
decoder causality, incremental-decode parity, and training."""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tensorflow_tpu.models import bert, encdec

pytestmark = pytest.mark.quick

CFG = dc.replace(bert.BERT_TINY, vocab_size=64, hidden=32, layers=2,
                 heads=2, mlp=64, max_positions=64, dropout=0.0)


def _model(**kw):
    cfg = dc.replace(CFG, **{k: v for k, v in kw.items()
                             if k not in ("dec_layers",)})
    return encdec.EncDecLm(cfg, dec_layers=kw.get("dec_layers"))


def _batch(b=2, s=10, t=8, seed=0):
    r = np.random.default_rng(seed)
    return {"src": jnp.asarray(r.integers(0, CFG.vocab_size, (b, s)),
                               jnp.int32),
            "tgt": jnp.asarray(r.integers(0, CFG.vocab_size, (b, t)),
                               jnp.int32)}


class TestForward:
    def test_shapes_and_dtype(self):
        m = _model()
        params = m.init(jax.random.key(0))
        out = m.apply(params, _batch())
        assert out.shape == (2, 8, CFG.vocab_size)
        assert out.dtype == jnp.float32

    def test_decoder_is_causal_over_tgt(self):
        m = _model()
        params = m.init(jax.random.key(0))
        b = _batch()
        la = m.apply(params, b)
        b2 = dict(b, tgt=b["tgt"].at[:, -1].set(
            (b["tgt"][:, -1] + 1) % CFG.vocab_size))
        lb = m.apply(params, b2)
        np.testing.assert_array_equal(np.asarray(la[:, :-1]),
                                      np.asarray(lb[:, :-1]))
        assert not np.allclose(np.asarray(la[:, -1]), np.asarray(lb[:, -1]))

    def test_every_position_sees_the_source(self):
        """Cross-attention: perturbing ANY source token must move every
        decoder position's logits."""
        m = _model()
        params = m.init(jax.random.key(0))
        b = _batch()
        la = m.apply(params, b)
        b2 = dict(b, src=b["src"].at[:, 0].set(
            (b["src"][:, 0] + 1) % CFG.vocab_size))
        lb = m.apply(params, b2)
        delta = np.abs(np.asarray(la) - np.asarray(lb)).max(axis=-1)
        assert (delta > 0).all()

    def test_dropout_contract(self):
        m = _model(dropout=0.1)
        params = m.init(jax.random.key(0))
        b = _batch()
        with pytest.raises(ValueError, match="rng"):
            m.apply(params, b, train=True)
        a1 = m.apply(params, b, train=True, rng=jax.random.key(1))
        a2 = m.apply(params, b, train=True, rng=jax.random.key(2))
        assert not np.allclose(np.asarray(a1), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(m.apply(params, b)),
                                      np.asarray(m.apply(params, b)))

    def test_dropout_fires_at_both_embedding_sites(self, monkeypatch):
        """The encoder-embed mask (stream 1, as BertMlm applies
        it) and a reserved decoder-embed site must both fire in train
        mode.  Counted via the shared dropout_mask: 1 enc embed +
        2/enc-layer + 1 dec embed + 3/dec-layer."""
        calls = []
        real = bert.dropout_mask

        def counting(x, rate, key):
            calls.append(x.shape)
            return real(x, rate, key)

        monkeypatch.setattr(bert, "dropout_mask", counting)
        m = _model(dropout=0.1)
        params = m.init(jax.random.key(0))
        m.apply(params, _batch(), train=True, rng=jax.random.key(1))
        expected = 1 + 2 * CFG.layers + 1 + 3 * m.n_dec
        assert len(calls) == expected
        m2 = _model(dropout=0.1)
        calls.clear()
        m2.apply(params, _batch())           # eval: no dropout anywhere
        assert calls == []

    def test_generate_rejects_beyond_position_table(self):
        """_dec_embed's dynamic_slice clamps, so decoding past
        dec_pos_emb would silently reuse the last row — must raise like
        CausalLm.init_cache."""
        m = _model()
        params = m.init(jax.random.key(0))
        src = _batch()["src"]
        with pytest.raises(ValueError, match="max_positions"):
            m.generate(params, src, CFG.max_positions + 1)

    def test_asymmetric_stacks(self):
        m = _model(dec_layers=1)
        params = m.init(jax.random.key(0))
        assert len(params["dec_layers"]) == 1
        assert len(params["layers"]) == 2
        assert m.apply(params, _batch()).shape == (2, 8, CFG.vocab_size)

    def test_deep_decoder_init(self):
        """Regression: each decoder layer consumes 10 PRNG keys; the old
        budget under-allocated by (n_dec - 5), so any stack deeper than 5
        (every production config: BERT_BASE is 12) died with
        StopIteration before a single step."""
        m = _model(dec_layers=7)
        params = m.init(jax.random.key(0))
        assert len(params["dec_layers"]) == 7

    def test_chunked_ce_matches_dense(self):
        """cfg.ce_impl drives the enc-dec loss like the sibling families:
        the chunked online-logsumexp CE must equal the dense one."""
        m_auto = _model()                       # auto -> chunked
        m_dense = _model(ce_impl="dense")
        params = m_auto.init(jax.random.key(0))
        b = _batch()
        la, _ = m_auto.loss(params, None, b)
        ld, _ = m_dense.loss(params, None, b)
        np.testing.assert_allclose(float(la), float(ld), rtol=1e-5)

    def test_remat_matches_plain(self):
        """cfg.remat(+policy) is honored on the DECODER stack too: loss
        and grads must match the unrematted model exactly."""
        m_p = _model(dropout=0.1)
        m_r = _model(dropout=0.1, remat=True, remat_policy="dots")
        params = m_p.init(jax.random.key(0))
        b = _batch()
        key = jax.random.key(3)
        lp, gp = jax.jit(jax.value_and_grad(lambda p: m_p.loss(
            p, None, b, rng=key, train=True)[0]))(params)
        lr, gr = jax.jit(jax.value_and_grad(lambda p: m_r.loss(
            p, None, b, rng=key, train=True)[0]))(params)
        np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)
        jax.tree.map(lambda a, c: np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-6), gp, gr)

    def test_mesh_with_pipe_axis_rejected(self):
        """data x model (Megatron TP) is supported; other axes still
        raise rather than silently ignore."""
        from mpi_tensorflow_tpu.config import Config
        from mpi_tensorflow_tpu.parallel import mesh as meshlib
        from mpi_tensorflow_tpu.train import mlm_loop

        cfg = Config(model="encdec_t5", batch_size=2)
        mesh = meshlib.make_mesh({"data": 4, "pipe": 2})
        with pytest.raises(ValueError, match="data x model"):
            mlm_loop.train_mlm(cfg, bert_cfg=CFG, mesh=mesh, seq_len=8,
                               train_n=32, test_n=8, verbose=False)

    def test_tp_sharded_loss_matches_single_device(self):
        """Enc-dec under Megatron TP (heads/MLP/vocab over 'model' via
        the logical-axis table): GSPMD placement must not change the
        math — loss equals the unsharded model's."""
        from mpi_tensorflow_tpu.parallel import mesh as meshlib
        from mpi_tensorflow_tpu.parallel import sharding_rules
        from mpi_tensorflow_tpu.train import gspmd

        m = _model()
        params = m.init(jax.random.key(0))
        b = _batch(b=8)
        want, _ = m.loss(params, None, b)
        # model axis must divide the tiny config's 2 heads; data the batch
        mesh = meshlib.make_mesh({"data": 4, "model": 2})
        placed = sharding_rules.shard_tree(params, m.logical_axes(), mesh)
        sh_b = {k: gspmd.shard_batch(v, mesh) for k, v in b.items()}
        got, _ = jax.jit(lambda p, bb: m.loss(p, None, bb))(placed, sh_b)
        np.testing.assert_allclose(float(want), float(got), rtol=2e-5)
        # the placement must actually shard the TP-able leaves
        wq = placed["layers"][0]["wq"]
        assert not wq.sharding.is_fully_replicated


class TestDecode:
    def test_incremental_matches_teacher_forced(self):
        """generate()'s KV-cache loop must reproduce exactly the greedy
        path of the full teacher-forced forward, token by token."""
        m = _model()
        params = m.init(jax.random.key(0))
        src = _batch()["src"]
        T = 6
        gen = np.asarray(jax.jit(
            lambda p, s: m.generate(p, s, T))(params, src))
        assert gen.shape == (2, T)
        # re-walk greedily with the full forward
        cur = np.zeros((2, 1), np.int32)          # BOS = 0
        enc_out = m.encode(params, src)
        for t in range(T):
            logits = np.asarray(
                m.decode_train(params, enc_out, jnp.asarray(cur)))
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            np.testing.assert_array_equal(gen[:, t], nxt, err_msg=f"t={t}")
            cur = np.concatenate([cur, nxt[:, None]], axis=1)

    def test_generate_guard(self):
        m = _model()
        params = m.init(jax.random.key(0))
        with pytest.raises(ValueError, match="max_new_tokens"):
            m.generate(params, _batch()["src"], 0)


class TestLoopIntegration:
    def test_transformer_loop_trains_reversal_task(self):
        """--model encdec_t5 through the real transformer loop: the
        synthetic reversal task's held-out next-token error must fall off
        the random plateau (cross-attention is the only route to it)."""
        from mpi_tensorflow_tpu.config import Config
        from mpi_tensorflow_tpu.train import mlm_loop

        cfg = Config(epochs=30, batch_size=4, model="encdec_t5",
                     log_every=30)
        bcfg = dc.replace(CFG, vocab_size=16, layers=2, max_positions=16)
        res = mlm_loop.train_mlm(cfg, bert_cfg=bcfg, seq_len=10,
                                 train_n=128, test_n=32,
                                 learning_rate=1e-2, verbose=False)
        assert np.isfinite(res.final_error)
        # random chance over the 11-token payload vocab is ~91%; learned
        # reversal must fall well off that plateau
        assert res.final_error < 60.0, res.history

    def test_text_file_rejected(self):
        from mpi_tensorflow_tpu.config import Config
        from mpi_tensorflow_tpu.train import mlm_loop

        cfg = Config(model="encdec_t5", text_file="x.txt")
        with pytest.raises(ValueError, match="src, tgt"):
            mlm_loop.train_mlm(cfg, bert_cfg=CFG, seq_len=8)

    def test_cli_accepts_encdec(self):
        from mpi_tensorflow_tpu import cli

        args = cli.build_parser().parse_args(["--model", "encdec_t5"])
        assert args.model == "encdec_t5"


class TestTraining:
    def test_gspmd_step_trains_copy_task(self):
        """The unmodified gspmd train step drives the enc-dec loss (batch
        is the {"src","tgt"} dict); on a copy task the loss must drop
        well below uniform chance."""
        import optax

        from mpi_tensorflow_tpu.parallel import mesh as meshlib
        from mpi_tensorflow_tpu.train import gspmd

        cfg = dc.replace(CFG, vocab_size=16, layers=1, max_positions=16)
        model = encdec.EncDecLm(cfg, dec_layers=1)
        mesh = meshlib.make_mesh()
        tx = optax.adamw(3e-3)
        state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh)
        step = gspmd.make_gspmd_train_step(model, mesh, tx)

        r = np.random.default_rng(0)
        src = r.integers(1, 16, (32, 8)).astype(np.int32)
        tgt = np.concatenate([np.zeros((32, 1), np.int32), src[:, :7]], 1)
        batch = {"src": gspmd.shard_batch(src, mesh),
                 "tgt": gspmd.shard_batch(tgt, mesh)}
        labels = batch["tgt"]
        key = jax.random.key(1)
        first = None
        for _ in range(60):
            state, mtr = step(state, batch, labels, key)
            first = first if first is not None else float(mtr["loss"])
        last = float(mtr["loss"])
        assert np.isfinite(last) and last < first * 0.5, (first, last)
