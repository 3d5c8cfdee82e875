"""Path-engagement recording (utils/engagement.py).

A reported number must say which attention/CE implementation actually
compiled into the step.  These tests pin that the records follow the
selection (platform, sequence length, operator switch) and that a kernel
that fails to compile raises instead of becoming an XLA row.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tensorflow_tpu.models import bert
from mpi_tensorflow_tpu.ops import flash_attention as fa
from mpi_tensorflow_tpu.parallel import ring
from mpi_tensorflow_tpu.utils import engagement

pytestmark = pytest.mark.quick


def _tiny_loss(**cfg_overrides):
    import dataclasses

    cfg = dataclasses.replace(bert.BERT_TINY, **cfg_overrides)
    model = bert.BertMlm(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
    mask = jnp.asarray(rng.random((2, 32)) < 0.25)
    batch = {"tokens": toks, "mask": mask}
    loss, _ = model.loss(params, None, batch, toks)
    return float(loss)


def test_records_cpu_fallback_paths():
    engagement.reset()
    loss = _tiny_loss()
    assert np.isfinite(loss)
    snap = engagement.snapshot()
    # CPU: the kernel is selected on TPU only -> XLA dense attention
    assert snap["attention"] == "xla_dense"
    assert snap["ce_positions"] == "masked_packed"
    # packed positions -> auto CE picks dense logits (bert._use_chunked_ce)
    assert snap["ce"] == "dense"


def test_attention_record_flips_with_switch(monkeypatch):
    """On (stubbed) TPU at S >= flash_min_seq the record must say
    'flash'; with the operator kill switch set -> 'xla_dense'."""
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [SimpleNamespace(platform="tpu")])
    monkeypatch.delenv("MPI_TF_TPU_DISABLE_FLASH", raising=False)
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal=False, scale=None:
        ring.dense_attention(q, k, v, causal=causal))
    engagement.reset()
    _tiny_loss(flash_min_seq=0)
    assert engagement.snapshot()["attention"] == "flash"

    monkeypatch.setenv("MPI_TF_TPU_DISABLE_FLASH", "1")
    engagement.reset()
    _tiny_loss(flash_min_seq=0)
    assert engagement.snapshot()["attention"] == "xla_dense"


def test_flash_compile_failure_raises(monkeypatch):
    """A selected kernel that does not compile must RAISE out of
    BertMlm._attention with the compiler's message — never select the
    XLA dense path instead."""
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [SimpleNamespace(platform="tpu")])
    monkeypatch.delenv("MPI_TF_TPU_DISABLE_FLASH", raising=False)

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: boom")

    monkeypatch.setattr(fa, "flash_attention", refuse)
    engagement.reset()
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        _tiny_loss(flash_min_seq=0)
    assert engagement.snapshot().get("attention") != "xla_dense"


def test_short_seq_prefers_xla_even_with_kernel_available(monkeypatch):
    """The flash_min_seq policy: below the threshold the step uses XLA
    dense attention EVEN on TPU with the kernel enabled.  The record
    must say so."""
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [SimpleNamespace(platform="tpu")])
    monkeypatch.delenv("MPI_TF_TPU_DISABLE_FLASH", raising=False)
    engagement.reset()
    _tiny_loss()                     # default flash_min_seq (4096) >> S=32
    assert engagement.snapshot()["attention"] == "xla_dense"


def test_ce_records_flip_with_config():
    cfg = bert.BertConfig(vocab_size=512, hidden=32, layers=1, heads=2,
                          mlp=64, max_positions=64, dropout=0.0,
                          ce_impl="chunked", ce_chunk=128,
                          ce_positions="all")
    model = bert.BertMlm(cfg)
    params = model.init(jax.random.key(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": toks, "mask": jnp.ones((2, 16), bool)}
    engagement.reset()
    model.loss(params, None, batch, toks)
    snap = engagement.snapshot()
    assert snap["ce"] == "chunked:128"
    assert snap["ce_positions"] == "all"


def test_env_kill_switch_disables_kernel(monkeypatch):
    monkeypatch.setenv("MPI_TF_TPU_DISABLE_FLASH", "1")
    assert fa.kernel_enabled() is False
    monkeypatch.setenv("MPI_TF_TPU_DISABLE_FLASH", "0")
    assert fa.kernel_enabled() is True
