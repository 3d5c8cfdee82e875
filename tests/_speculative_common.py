"""What the three speculative-decoding test files share: geometries, the
serve config, the trace maker, the engine pair and the adversarial drafter
(tests/test_speculative.py, test_speculative_faults.py,
test_speculative_window.py)."""

import dataclasses

from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (Drafter, PagedDecodeEngine, Request,
                                        ServeConfig)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
ROPE = dataclasses.replace(TINY, pos_kind="rope")


def _shared_trace(rng, n=5, prefix=8, tail_hi=5, budget=24, vocab=None,
                  tail_lens=None):
    vocab = vocab or TINY.vocab_size
    shared = list(map(int, rng.integers(0, vocab, prefix)))
    if tail_lens is None:
        tail_lens = rng.integers(1, tail_hi + 1, n)
    prompts = [shared + list(map(int, rng.integers(0, vocab, int(s))))
               for s in tail_lens]
    return [Request(i, p, budget, arrival=0.0)
            for i, p in enumerate(prompts)]


SERVE = ServeConfig(num_blocks=96, block_size=4, max_slots=3,
                    max_seq_len=64, prefill_chunk=8)


def _pair(cfg, *, key=0, **spec_kw):
    """(model, params, off-engine, speculative-engine) on one config."""
    import jax

    model = gpt.CausalLm(cfg)
    params = model.init(jax.random.key(key))
    serve_kw = {k: v for k, v in spec_kw.items()
                if k not in ("draft_model", "draft_params")}
    eng_kw = {k: v for k, v in spec_kw.items()
              if k in ("draft_model", "draft_params")}
    off = PagedDecodeEngine(model, params, SERVE)
    spec = PagedDecodeEngine(
        model, params, dataclasses.replace(SERVE, **serve_kw), **eng_kw)
    return model, params, off, spec


class _WrongDrafter(Drafter):
    """Adversarial drafter: proposes, at every position, the true next
    token PLUS ONE (mod vocab) — guaranteed to mismatch the target's
    argmax chain at lane 0, so every verify step allocates a full draft
    window and must roll all of it back."""

    def __init__(self, truth, prompts, vocab):
        self.truth = truth        # rid -> full true output stream
        self.prompts = prompts    # rid -> prompt (to locate ctx in it)
        self.vocab = vocab
        self.calls = 0

    def draft(self, rid, ctx, k):
        self.calls += 1
        # ctx = prompt + generated; the next emitted tokens would be
        # truth[len(generated):] — corrupt exactly those
        g = len(ctx) - len(self.prompts[rid])
        return [(t + 1) % self.vocab
                for t in self.truth[rid][g:g + k]]
