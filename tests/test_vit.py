"""ViT family (models/vit.py): the shared BERT encoder stack driven by
the image pipeline — patchify correctness, forward contract, training
through the image train step, and dispatch wiring."""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tensorflow_tpu.models import vit

pytestmark = pytest.mark.quick

TINY = dc.replace(vit.VIT_TINY_CIFAR, hidden=32, layers=2, heads=2,
                  mlp=64, dropout=0.0)


def _model(**kw):
    return vit.VisionTransformer(dc.replace(TINY, **kw))


class TestPatchify:
    def test_round_trip_values(self):
        """Each output row must be exactly the pixels of one P x P patch
        in raster order — checked against a hand-indexed slice."""
        m = _model(image_size=8, patch=4)
        img = jnp.arange(8 * 8 * 3, dtype=jnp.float32).reshape(1, 8, 8, 3)
        p = np.asarray(m._patchify(img))
        assert p.shape == (1, 4, 48)
        want = np.asarray(img[0, 0:4, 4:8]).reshape(-1)   # patch row 0, col 1
        np.testing.assert_array_equal(p[0, 1], want)

    def test_patch_count(self):
        assert vit.VitConfig(image_size=32, patch=4).num_patches == 64
        assert vit.VitConfig(image_size=224, patch=16).num_patches == 196
        with pytest.raises(ValueError, match="divisible"):
            vit.VitConfig(image_size=30, patch=4).num_patches


class TestForward:
    def test_logits_shape_and_dtype(self):
        m = _model()
        params = m.init(jax.random.key(0))
        imgs = jnp.zeros((2, 32, 32, 3))
        out = m.apply(params, imgs)
        assert out.shape == (2, 10) and out.dtype == jnp.float32

    def test_dropout_needs_rng_and_varies(self):
        m = _model(dropout=0.1)
        params = m.init(jax.random.key(0))
        imgs = jnp.ones((2, 32, 32, 3))
        with pytest.raises(ValueError, match="rng"):
            m.apply(params, imgs, train=True)
        a = m.apply(params, imgs, train=True, rng=jax.random.key(1))
        b = m.apply(params, imgs, train=True, rng=jax.random.key(2))
        assert not np.allclose(np.asarray(a), np.asarray(b))
        # eval is deterministic (the reference's eval-dropout bug, fixed)
        np.testing.assert_array_equal(np.asarray(m.apply(params, imgs)),
                                      np.asarray(m.apply(params, imgs)))

    def test_mnist_single_channel(self):
        m = _model(image_size=28, patch=7, channels=1)
        params = m.init(jax.random.key(0))
        out = m.apply(params, jnp.zeros((3, 28, 28, 1)))
        assert out.shape == (3, 10)


class TestTraining:
    def test_image_train_step_reduces_loss(self):
        """The model-agnostic image train step (train/step.py) drives ViT
        unchanged — the framework contract the base protocol promises."""
        from mpi_tensorflow_tpu.config import Config
        from mpi_tensorflow_tpu.parallel import mesh as meshlib
        from mpi_tensorflow_tpu.train import step as step_lib

        cfg = Config(batch_size=2, model="vit", dataset="cifar10",
                     image_size=32, base_lr=0.05)
        mesh = meshlib.make_mesh()
        model = _model()
        state = step_lib.init_state(model, jax.random.key(0))
        train_step = step_lib.make_train_step(model, cfg, mesh,
                                              decay_steps=1000)
        r = np.random.default_rng(0)
        imgs = jax.device_put(
            r.normal(size=(16, 32, 32, 3)).astype(np.float32))
        labels = jax.device_put((np.asarray(imgs).sum((1, 2, 3)) > 0)
                                .astype(np.int64))
        key = jax.random.key(1)
        losses = []
        for _ in range(25):
            state, m = train_step(state, imgs, labels, key)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.7, losses[::6]

    def test_build_model_dispatch(self):
        from mpi_tensorflow_tpu.config import Config
        from mpi_tensorflow_tpu.train import loop

        m = loop.build_model(Config(model="vit", dataset="cifar10",
                                    image_size=32))
        assert isinstance(m, vit.VisionTransformer)
        assert m.cfg.channels == 3 and m.cfg.patch == 4
        m = loop.build_model(Config(model="vit", dataset="mnist",
                                    image_size=28))
        assert m.cfg.channels == 1 and m.cfg.patch == 7

    def test_cli_accepts_vit(self):
        from mpi_tensorflow_tpu import cli

        args = cli.build_parser().parse_args(
            ["--model", "vit", "--dataset", "cifar10"])
        assert args.model == "vit"
