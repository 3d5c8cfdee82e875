"""The closed-loop generator: a request is a function of (seed, client, k)
alone; lengths stay inside the mix's bounds."""

import json
import os

from benchmarks.harness import manifest, traffic

MIX = manifest.load_json(os.path.join(manifest.BENCH, "traffic",
                                      "closed128.json"))


def test_same_request_for_same_seed_client_k():
    a = traffic.closed_loop_request(MIX, 50257, 3_000_000_007, 17, 5)
    b = traffic.closed_loop_request(MIX, 50257, 3_000_000_007, 17, 5)
    assert a == b
    others = [traffic.closed_loop_request(MIX, 50257, s, c, k)
              for s, c, k in [(3_000_000_008, 17, 5), (3_000_000_007, 18, 5),
                              (3_000_000_007, 17, 6)]]
    assert all(o != a for o in others)


def test_every_seed_sends_the_same_sizes_where_the_mix_fixes_them():
    assert "length_seed" in MIX
    for c, k in [(0, 0), (17, 5), (127, 2)]:
        a = traffic.closed_loop_request(MIX, 50257, 11, c, k)
        b = traffic.closed_loop_request(MIX, 50257, 3_000_000_007, c, k)
        assert (len(a[0]), a[1]) == (len(b[0]), b[1]) and a[0] != b[0]
    free = {k: v for k, v in MIX.items() if k != "length_seed"}
    sizes = {(len(p), o) for p, o in (
        traffic.closed_loop_request(free, 50257, s, 3, 1) for s in range(8))}
    assert len(sizes) > 1


def test_lengths_within_bounds_and_heavy_tailed():
    plens, olens = [], []
    for c in range(128):
        for k in range(4):
            p, o = traffic.closed_loop_request(MIX, 50257, 1, c, k)
            assert all(0 <= t < 50257 for t in p)
            plens.append(len(p))
            olens.append(o)
    assert min(plens) >= MIX["prompt"]["lo"] and max(plens) <= MIX["prompt"]["hi"]
    assert min(olens) >= MIX["output"]["lo"] and max(olens) <= MIX["output"]["hi"]
    assert max(plens) + max(olens) <= MIX["engine"]["max_seq_len"]
    assert sorted(plens)[len(plens) // 2] < 2 * MIX["prompt"]["lo"]


def test_copied_sampler_equals_the_programs():
    import numpy as np

    from mpi_tensorflow_tpu.serving import loadgen

    for dist in ("uniform", "lognormal", "zipf"):
        a = [traffic.sample_len(np.random.default_rng(i), dist, 64, 512)
             for i in range(50)]
        b = [loadgen._sample_len(np.random.default_rng(i), dist, 64, 512)
             for i in range(50)]
        assert a == b


def test_copied_mlm_batches_equal_the_programs():
    import numpy as np

    from mpi_tensorflow_tpu.data import synthetic

    mine = traffic.mlm_batches(64, seq_len=128, vocab_size=30522, seed=9)
    tok, tgt, msk = synthetic.mlm_batches(64, seq_len=128, vocab_size=30522,
                                          seed=9)
    assert np.array_equal(mine["tokens"], tok)
    assert np.array_equal(mine["targets"], tgt)
    assert np.array_equal(mine["mask"], msk)


class _Span:
    def __init__(self, arrive, events):
        self.arrive, self.events = arrive, events


def test_prefill_wait_counts_the_chunks_that_land_in_the_window():
    """A request whose first chunk lies past ``hi`` waited through the
    profiler's stop, not the queue: it is not read.  One submitted before
    ``lo`` whose first chunk lands inside is."""
    from types import SimpleNamespace

    from benchmarks.harness import serve_driver

    lo, hi = 10.0, 14.0
    tracer = SimpleNamespace(spans={
        0: _Span(9.0, [(9.0, "queued"), (11.5, "prefill_chunk"),
                       (11.6, "prefill_chunk")]),        # in: 2.5
        1: _Span(12.0, [(12.0, "queued"), (13.0, "prefill_chunk")]),  # 1.0
        2: _Span(13.5, [(13.5, "queued"), (33.0, "prefill_chunk")]),  # past
        3: _Span(13.9, [(13.9, "queued")]),              # still queued
        4: _Span(5.0, [(5.0, "queued"), (9.9, "prefill_chunk")]),  # before
    })
    assert sorted(serve_driver._prefill_wait(tracer, lo, hi)) == [1.0, 2.5]
