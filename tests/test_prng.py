"""The dropout-PRNG knob (Config.prng_impl / --prng).

A BERT-base train step generates 25 (B, S, E) dropout masks; the generator
choice (threefry vs XLA RngBitGenerator) is a first-order throughput knob
on TPU.  These tests pin the hardware-independent contract: the impl
travels with the key from the one loop-level call site through every
fold_in inside the jitted step, every surface (CLI, loops) threads it, and
parameter init stays threefry (bit-identical across prng arms).
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tensorflow_tpu.config import Config
from mpi_tensorflow_tpu.models import bert

pytestmark = pytest.mark.quick


def _impl_name(key) -> str:
    return str(jax.random.key_impl(key))


def test_make_train_key_impls():
    assert "threefry" in _impl_name(Config().make_train_key(0))
    assert "rbg" in _impl_name(
        Config(prng_impl="rbg").make_train_key(0))
    assert "unsafe_rbg" in _impl_name(
        Config(prng_impl="unsafe_rbg").make_train_key(0))


def test_impl_travels_through_fold_in():
    key = Config(prng_impl="rbg").make_train_key(7)
    assert "rbg" in _impl_name(jax.random.fold_in(key, 3))


def test_bert_step_trains_under_rbg():
    """The gspmd train step accepts an rbg key: dropout masks generate,
    loss is finite, and a step with a different fold produces different
    masks (the stream is live, not constant)."""
    import optax

    from mpi_tensorflow_tpu.parallel import mesh as meshlib
    from mpi_tensorflow_tpu.train import gspmd

    cfg = dc.replace(bert.BERT_TINY, dropout=0.1)
    mesh = meshlib.make_mesh()
    model = bert.BertMlm(cfg, mesh=mesh)
    tx = optax.adamw(1e-3)
    state = gspmd.init_gspmd_state(model, tx, jax.random.key(0), mesh)
    step = gspmd.make_gspmd_train_step(model, mesh, tx)

    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    mask = rng.random((8, 32)) < 0.25
    batch = gspmd.shard_batch({"tokens": toks, "mask": mask}, mesh)
    labels = gspmd.shard_batch(toks, mesh)

    key = Config(prng_impl="rbg").make_train_key(1)
    state, m = step(state, batch, labels, key)
    assert np.isfinite(float(m["loss"]))

    # dropout actually fires under the rbg stream: two forward passes with
    # different keys differ (same params, train=True)
    params = state.params
    l1 = model.loss(params, None, batch, labels,
                    rng=jax.random.fold_in(key, 1), train=True)[0]
    l2 = model.loss(params, None, batch, labels,
                    rng=jax.random.fold_in(key, 2), train=True)[0]
    assert float(l1) != float(l2)


def test_prng_impl_only_touches_the_dropout_stream():
    """With dropout 0 the training rng is never consumed, so a threefry
    run and an rbg run must be bit-identical end to end — this pins that
    NOTHING else (parameter init, data synthesis, eval) derives from
    Config.prng_impl.  If init ever switched to make_train_key, the rbg
    arm would start from different weights and the traces would split."""
    from mpi_tensorflow_tpu.train import mlm_loop

    def run(impl):
        cfg = Config(epochs=1, batch_size=4, model="bert_base",
                     prng_impl=impl, log_every=2)
        return mlm_loop.train_mlm(cfg, bert_cfg=bert.BERT_TINY,  # dropout 0
                                  seq_len=32, train_n=64, test_n=16,
                                  verbose=False)
    a, b = run("threefry"), run("rbg")
    assert a.history == b.history
    for x, y in zip(jax.tree.leaves(a.state.params),
                    jax.tree.leaves(b.state.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_cli_threads_prng():
    from mpi_tensorflow_tpu import cli

    args = cli.build_parser().parse_args(["--prng", "rbg"])
    assert cli.config_from_args(args).prng_impl == "rbg"
    # default stays the JAX default
    args = cli.build_parser().parse_args([])
    assert cli.config_from_args(args).prng_impl == "threefry"


def test_mlm_loop_runs_under_rbg():
    """train_mlm end-to-end with prng_impl=rbg on the tiny config."""
    from mpi_tensorflow_tpu.train import mlm_loop

    cfg = Config(epochs=1, batch_size=4, model="bert_base",
                 prng_impl="rbg", log_every=2)
    bcfg = dc.replace(bert.BERT_TINY, dropout=0.1)
    res = mlm_loop.train_mlm(cfg, bert_cfg=bcfg, seq_len=32, train_n=64,
                             test_n=16, verbose=False)
    assert np.isfinite(res.final_error)
