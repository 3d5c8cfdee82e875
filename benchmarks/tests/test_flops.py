"""The copied flops and peaks equal the program's on both configurations;
the serving arithmetic against a hand count."""

import os

import pytest

from benchmarks.harness import device, flops, manifest
from benchmarks.reference import transformer as ref_tf


@pytest.mark.parametrize("name", ["bert_base", "gpt_base"])
def test_train_flops_equal_utils_flops(name):
    from types import SimpleNamespace

    from mpi_tensorflow_tpu.utils import flops as prog

    sz = ref_tf.sizes(manifest.load_json(
        os.path.join(manifest.BENCH, "configs", name + ".json")))
    cfg = SimpleNamespace(hidden=sz["hidden"], layers=sz["layers"],
                          mlp=sz["mlp"], vocab_size=sz["vocab"])
    for b, s, hp in [(256, 128, 32), (8, 4096, 1024), (64, 128, 128)]:
        assert flops.train_step_flops(sz, b, s, hp) == \
            prog.transformer_train_flops(cfg, b, s, head_positions=hp)


def test_peaks_equal_the_programs_table():
    from mpi_tensorflow_tpu.utils import flops as prog

    for kind, row in device.PEAKS.items():
        assert row["bf16_flops"] == prog.DEVICE_PEAKS[kind]["tflops"]["bf16"] * 1e12
        assert row["hbm_bytes_per_s"] == prog.DEVICE_PEAKS[kind]["hbm_gbps"] * 1e9
    with pytest.raises(device.NoAcceleratorError):
        device.peaks("TPU v9")


def test_serve_request_flops_is_the_sum_of_its_tokens():
    sz = {"vocab": 1000, "hidden": 64, "layers": 2, "heads": 4, "mlp": 256,
          "positions": 512}
    P, n = 10, 5
    # prompt tokens 0..P-2 emit nothing; token at position P-1+j emits
    # output j and attends over P+j positions
    by_hand = sum(flops.serve_token_flops(sz, p + 1, False)
                  for p in range(P - 1))
    by_hand += sum(flops.serve_token_flops(sz, P + j, True)
                   for j in range(n))
    assert flops.serve_request_flops(sz, P, 0, n - 1, True) == by_hand
    tail = sum(flops.serve_token_flops(sz, P + j, True) for j in (3, 4))
    assert flops.serve_request_flops(sz, P, 3, 4, False) == tail


def test_paged_attention_bytes():
    sz = {"hidden": 768, "layers": 12}
    assert flops.paged_attention_bytes(sz, [100, 28], 2) == \
        128 * 2 * 768 * 2 * 12
