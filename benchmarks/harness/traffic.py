"""The one general traffic generator: everything a cell sends is drawn
here from ``--seed`` and the parameters in ``benchmarks/traffic/<mix>``.

Two kinds of mix:

- ``mlm_batches``: synthetic masked-LM rows for a training cell (copy of
  ``mpi_tensorflow_tpu/data/synthetic.mlm_batches``);
- ``closed_loop``: a fixed number of clients, each sending its next
  request when the previous one completes.  Client ``c``'s ``k``-th request
  is a function of ``(seed, c, k)`` alone, so the order in which the server
  completes requests never changes what is sent.

Lengths follow ``sample_len`` (copy of ``serving/loadgen._sample_len``).
"""

from __future__ import annotations

import numpy as np


def mlm_batches(num_examples: int, *, seq_len: int, vocab_size: int,
                mask_token: int = 4, mask_rate: float = 0.15,
                seed: int = 0) -> dict:
    """``tokens`` (N, S) int32 with the mask token substituted, ``targets``
    (N, S) int32 original ids, ``mask`` (N, S) bool.  Runs of 8 equal
    tokens with 2% noise, so the loss is reducible."""
    rng = np.random.default_rng(seed)
    run = 8
    n_runs = (seq_len + run - 1) // run
    run_tokens = rng.integers(5, vocab_size, size=(num_examples, n_runs))
    clean = np.repeat(run_tokens, run, axis=1)[:, :seq_len]
    noise = rng.random((num_examples, seq_len)) < 0.02
    clean = np.where(noise, rng.integers(5, vocab_size, size=clean.shape),
                     clean)
    mask = rng.random((num_examples, seq_len)) < mask_rate
    tokens = np.where(mask, mask_token, clean)
    return {"tokens": tokens.astype(np.int32),
            "targets": clean.astype(np.int32), "mask": mask}


def sample_len(rng, dist: str, lo: int, hi: int) -> int:
    """One length in [lo, hi]: ``uniform``, ``lognormal`` (median near
    ``lo``, tail clamped at ``hi``) or bounded ``zipf``."""
    if hi <= lo:
        return hi
    if dist == "uniform":
        return int(rng.integers(lo, hi + 1))
    if dist == "lognormal":
        return max(lo, min(hi, int(round(lo * rng.lognormal(0.0, 1.0)))))
    if dist == "zipf":
        return max(lo, min(hi, lo - 1 + int(rng.zipf(1.5))))
    raise ValueError(f"unknown length distribution {dist!r}")


def closed_loop_request(mix: dict, vocab_size: int, seed: int, client: int,
                        k: int) -> tuple:
    """``(prompt token ids, output length)`` of client ``client``'s
    ``k``-th request.  Token ids come from ``seed``.  The two lengths come
    from the mix's ``length_seed`` where it has one — every seed then
    sends the same sizes in the same order, which a closed loop whose
    requests outlast the window needs to see the same state — and from
    ``seed`` otherwise.  ``shared_prefix_tokens`` > 0 puts one prefix,
    drawn from the seed alone, in front of every prompt."""
    rng = np.random.default_rng([int(seed), int(client), int(k)])
    sizes = rng if "length_seed" not in mix else np.random.default_rng(
        [int(mix["length_seed"]), int(client), int(k)])
    p = mix["prompt"]
    o = mix["output"]
    plen = sample_len(sizes, p["dist"], int(p["lo"]), int(p["hi"]))
    olen = sample_len(sizes, o["dist"], int(o["lo"]), int(o["hi"]))
    prompt = rng.integers(0, vocab_size, plen).tolist()
    n_shared = int(mix.get("shared_prefix_tokens", 0))
    if n_shared:
        shared = np.random.default_rng([int(seed), 0xC0FFEE]).integers(
            0, vocab_size, n_shared).tolist()
        prompt = shared + prompt
    return prompt, olen
