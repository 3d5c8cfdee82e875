"""``program.model`` ``causal_lm``: the repo's ``CausalLm(BertMlm)`` at
BERT's or GPT-2's sizes, served through ``forward_paged`` and the
paged-attention kernel; its reference is ``reference/causal_lm.py``."""

from __future__ import annotations

from .. import flops
from ...reference import causal_lm as ref_lm
from ...reference import transformer as ref_tf

sizes = ref_tf.sizes
init_params = ref_tf.init_params
request_flops = flops.serve_request_flops
cache_bytes = flops.paged_attention_bytes


def build(sz: dict, dtype):
    from mpi_tensorflow_tpu.models import bert, gpt

    return gpt.CausalLm(bert.BertConfig(
        vocab_size=sz["vocab"], hidden=sz["hidden"], layers=sz["layers"],
        heads=sz["heads"], mlp=sz["mlp"], max_positions=sz["positions"],
        dropout=0.0, dtype=dtype))


def reference_logits(params, toks, pos, precision=None):
    return ref_lm.next_token_logits(params, toks, pos,
                                    precision=precision or "f32")
