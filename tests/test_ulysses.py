"""Ulysses (all-to-all) sequence parallelism: must equal dense attention on
the full sequence — forward and gradients — and slot into BERT as the ring's
drop-in alternative (cfg.sp_impl)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mpi_tensorflow_tpu.parallel import ring, ulysses


@pytest.fixture(scope="module")
def seq_mesh():
    return jax.make_mesh((8,), ("seq",))


def _rand_qkv(b=2, h=8, s=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.normal(size=(b, h, s, d)).astype(np.float32)
    return mk(), mk(), mk()


def _sharded(seq_mesh, causal=False):
    return jax.jit(jax.shard_map(
        lambda q, k, v: ulysses.ulysses_attention(q, k, v, "seq",
                                                  causal=causal),
        mesh=seq_mesh,
        in_specs=(P(None, None, "seq"),) * 3,
        out_specs=P(None, None, "seq")))


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, seq_mesh, causal):
        q, k, v = _rand_qkv()
        want = np.asarray(ring.dense_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), causal=causal))
        got = np.asarray(_sharded(seq_mesh, causal)(q, k, v))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_matches_ring(self, seq_mesh):
        """The two SP strategies are interchangeable semantics-wise."""
        q, k, v = _rand_qkv(seed=3)
        ring_f = jax.jit(jax.shard_map(
            lambda q, k, v: ring.ring_attention(q, k, v, "seq"),
            mesh=seq_mesh, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq")))
        np.testing.assert_allclose(
            np.asarray(_sharded(seq_mesh)(q, k, v)),
            np.asarray(ring_f(q, k, v)), rtol=2e-4, atol=2e-5)

    def test_flash_inner_matches_dense(self, seq_mesh):
        """Ulysses with the Pallas flash kernel (interpret mode) as the
        local attention — the SP path exercising the kernel, forward and
        backward (round-1 gap: SP never hit the kernel)."""
        from mpi_tensorflow_tpu.ops import flash_attention as fa

        q, k, v = _rand_qkv(b=1, h=8, s=64, d=8, seed=7)

        def inner(q, k, v, causal=False, scale=None):
            return fa.flash_attention(q, k, v, causal, scale, 32, 32, True)

        attn = jax.shard_map(
            lambda q, k, v: ulysses.ulysses_attention(q, k, v, "seq",
                                                      inner=inner),
            mesh=seq_mesh, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq"), check_vma=False)
        want = np.asarray(ring.dense_attention(
            jnp.array(q), jnp.array(k), jnp.array(v)))
        got = np.asarray(jax.jit(attn)(q, k, v))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

        gs = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v) ** 2),
            argnums=(0, 1, 2)))(jnp.array(q), jnp.array(k), jnp.array(v))
        gd = jax.grad(
            lambda q, k, v: jnp.sum(ring.dense_attention(q, k, v) ** 2),
            argnums=(0, 1, 2))(jnp.array(q), jnp.array(k), jnp.array(v))
        for a, b in zip(gs, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)

    def test_bert_ulysses_uses_flash_on_tpu(self, seq_mesh, monkeypatch):
        """The BERT attention wiring passes the flash kernel as the Ulysses
        inner exactly when on TPU with use_flash."""
        from mpi_tensorflow_tpu.models import bert
        from mpi_tensorflow_tpu.parallel import ulysses as ulysses_mod

        seen = {}
        orig = ulysses_mod.ulysses_attention

        def spy(q, k, v, axis_name="seq", *, inner=None, **kw):
            seen["inner"] = inner
            return orig(q, k, v, axis_name, inner=None, **kw)

        from mpi_tensorflow_tpu.parallel import mesh as meshlib

        cfg = dataclasses.replace(bert.BERT_TINY, sp_impl="ulysses",
                                  heads=8,   # divisible by the seq axis
                                  flash_min_seq=0)   # engage at any S
        mesh = meshlib.make_mesh({"data": 1, "seq": 8})
        monkeypatch.setattr(ulysses_mod, "ulysses_attention", spy)
        # pretend we're on TPU for the gate (after building the mesh —
        # bert.jax IS the global jax module, so devices() is patched
        # everywhere)
        monkeypatch.delenv("MPI_TF_TPU_DISABLE_FLASH", raising=False)
        monkeypatch.setattr(
            bert.jax, "devices",
            lambda *a: [type("D", (), {"platform": "tpu"})()])
        model = bert.BertMlm(cfg, mesh=mesh)
        params = model.init(jax.random.key(0))
        tokens = jnp.zeros((2, 64), jnp.int32)
        model.apply(params, tokens)
        assert seen.get("inner") is not None, \
            "BERT's Ulysses path did not receive the flash kernel"

    def test_gradients_match_dense(self, seq_mesh):
        """All-to-alls are linear, so grads must match dense attention's."""
        q, k, v = _rand_qkv(b=1, h=8, s=32)

        attn = jax.shard_map(
            lambda q, k, v: ulysses.ulysses_attention(q, k, v, "seq"),
            mesh=seq_mesh, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq"))

        def loss_sharded(q, k, v):
            return jnp.sum(attn(q, k, v) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(ring.dense_attention(q, k, v) ** 2)

        gs = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(
            jnp.array(q), jnp.array(k), jnp.array(v))
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(
            jnp.array(q), jnp.array(k), jnp.array(v))
        for a, b in zip(gs, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_heads_not_divisible_raises(self, seq_mesh):
        q, k, v = _rand_qkv(h=4)   # 4 heads, 8 shards
        with pytest.raises(ValueError, match="divisible"):
            _sharded(seq_mesh)(q, k, v)

    def test_single_shard_is_dense(self):
        mesh1 = jax.make_mesh((1,), ("seq",))
        q, k, v = _rand_qkv(h=2, s=16)
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ulysses.ulysses_attention(q, k, v, "seq"),
            mesh=mesh1, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq")))
        want = ring.dense_attention(jnp.array(q), jnp.array(k), jnp.array(v))
        np.testing.assert_allclose(np.asarray(f(q, k, v)),
                                   np.asarray(want), rtol=1e-5, atol=1e-6)


class TestBertUlysses:
    def test_bert_forward_matches_ring(self):
        from mpi_tensorflow_tpu.models import bert
        from mpi_tensorflow_tpu.parallel import mesh as meshlib

        mesh = meshlib.make_mesh({"data": 2, "seq": 4})
        cfg_r = dataclasses.replace(bert.BERT_TINY, sp_impl="ring")
        cfg_u = dataclasses.replace(bert.BERT_TINY, sp_impl="ulysses")
        m_r = bert.BertMlm(cfg_r, mesh=mesh)
        m_u = bert.BertMlm(cfg_u, mesh=mesh)
        params = m_r.init(jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg_r.vocab_size, (4, 64)),
            jnp.int32)
        lr = m_r.apply(params, tokens, train=False)
        lu = m_u.apply(params, tokens, train=False)
        np.testing.assert_allclose(np.asarray(lu), np.asarray(lr),
                                   rtol=2e-3, atol=2e-3)


class TestLongContext:
    def test_ulysses_flash_long_sequence(self):
        """S=2048 over 8 shards with the Pallas flash kernel (interpret)
        as the local attention — the intended long-context configuration."""
        from mpi_tensorflow_tpu.ops import flash_attention as fa

        seq_mesh = jax.make_mesh((8,), ("seq",))
        rng = np.random.default_rng(2)
        B, H, S, D = 1, 8, 2048, 16
        mk = lambda: rng.normal(size=(B, H, S, D)).astype(np.float32) * 0.3
        q, k, v = mk(), mk(), mk()

        def inner(q, k, v, causal=False, scale=None):
            return fa.flash_attention(q, k, v, causal, scale, 256, 256,
                                      True)

        f = jax.jit(jax.shard_map(
            lambda q, k, v: ulysses.ulysses_attention(q, k, v, "seq",
                                                      inner=inner),
            mesh=seq_mesh, in_specs=(P(None, None, "seq"),) * 3,
            out_specs=P(None, None, "seq"), check_vma=False))
        got = np.asarray(f(q, k, v))
        want = np.asarray(fa.blockwise_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), block_k=256))
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)
