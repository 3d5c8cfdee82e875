"""How the serving host assembles a dispatch: from the scheduler's host
mirrors of every slot's block table and length, handed to the compiled
calls as numpy.

- the mirrors equal the row-by-row assembly they replace (each sequence's
  ``block_ids`` and ``length``) after every iteration, through admission,
  growth, copy-on-write under the prefix cache, eviction under pool
  pressure and finish; every decode and prefill dispatch carries exactly
  the rows that assembly would have built, at the table bucket it would
  have chosen; ``peak_live_blocks`` equals the count over a set of every
  slot's blocks;
- the plain path puts nothing through JAX's Python array path
  (``host_array_puts``) and, after a prewarm in the loop's own argument
  forms, builds no program, for a model that keeps state by slot too;
  the verify path still puts its four arrays a dispatch, counted.
"""

import dataclasses

import numpy as np
import pytest

from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (PagedDecodeEngine, Request,
                                        ServeConfig)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
PLAIN = ServeConfig(num_blocks=11, block_size=4, max_slots=3,
                    max_seq_len=32, prefill_chunk=8)


@pytest.fixture(scope="module")
def gpt_model():
    import jax

    model = gpt.CausalLm(TINY)
    return model, model.init(jax.random.key(5))


def _trace(vocab: int) -> list:
    """A block-multiple prompt asked again once it is cached (the
    copy-on-write trigger), others of 5..14 tokens that open with its
    first block (two live sequences map one block), budgets long enough
    that a tight pool must evict."""
    rng = np.random.default_rng(35)
    twice = list(map(int, rng.integers(1, vocab, 8)))
    prompts = [twice] + [twice[:4] + list(map(int, rng.integers(
        1, vocab, int(n)))) for n in rng.integers(1, 11, 5)] + [twice, twice]
    budgets = [10, 14, 6, 12, 9, 13, 4, 7]
    return [Request(i, p, b) for i, (p, b) in
            enumerate(zip(prompts, budgets))]


def _rows(sched, slots, width: int) -> tuple:
    """What the row-by-row assembly built for ``slots``: each sequence's
    ``block_ids`` (null past them) and ``length - 1``, slack rows null."""
    tables = np.zeros((len(slots), width), np.int32)
    lengths = np.zeros((len(slots),), np.int32)
    for j, slot in enumerate(slots):
        if slot == sched.max_slots:
            continue
        seq = sched.slots[slot]
        ids = seq.block_ids[:width]
        tables[j, :len(ids)] = ids
        lengths[j] = seq.length - 1
    return tables, lengths


def _check_mirrors(sched) -> None:
    for slot in range(sched.max_slots + 1):
        seq = sched.slots[slot] if slot < sched.max_slots else None
        want = np.zeros((sched.max_blocks_per_seq,), np.int32)
        if seq is not None:
            want[:len(seq.block_ids)] = seq.block_ids
            assert sched.lengths[slot] == seq.length
        np.testing.assert_array_equal(sched.tables[slot], want)


@pytest.mark.parametrize("prefix", ["off", "on", "gen"])
def test_mirrors_equal_the_row_by_row_assembly(gpt_model, prefix):
    """``gen``: the prefix cache with generated blocks and partial
    tail-block copies."""
    model, params = gpt_model
    eng = PagedDecodeEngine(model, params, dataclasses.replace(
        PLAIN, prefix_cache="off" if prefix == "off" else "on",
        prefix_gen="on" if prefix == "gen" else "off"))
    sched = eng.sched
    seen = {"decode": 0, "prefill": 0, "slots": None}
    take, decode, prefill = eng._take_fn, eng._decode_fn, eng._prefill_fn
    track = eng._track_occupancy
    peak, shared = [0], [False]

    def taken(last, slots):
        seen["slots"] = np.asarray(slots)
        return take(last, slots)

    def decoded(params, pools, tokens, lengths, tables, *rest):
        slots = seen["slots"]
        live = [int(s) for s in slots if s != sched.max_slots]
        assert isinstance(lengths, np.ndarray) \
            and isinstance(tables, np.ndarray)
        # the bucket the old assembly chose: the longest block list
        assert tables.shape[1] == eng._table_bucket(
            max(len(sched.slots[s].block_ids) for s in live))
        want_t, want_l = _rows(sched, slots, tables.shape[1])
        np.testing.assert_array_equal(tables, want_t)
        np.testing.assert_array_equal(lengths, want_l)
        seen["decode"] += 1
        return decode(params, pools, tokens, lengths, tables, *rest)

    def prefilled(params, pools, toks, start, n, tables, *rest):
        slot, seq = eng._prefill_queue[0]
        assert isinstance(start, np.int32) and isinstance(n, np.int32)
        assert start == seq.prefilled
        want_t, _ = _rows(sched, [slot], sched.max_blocks_per_seq)
        np.testing.assert_array_equal(tables, want_t)
        seen["prefill"] += 1
        return prefill(params, pools, toks, start, n, tables, *rest)

    def tracked():
        live = {b for q in sched.slots if q is not None
                for b in q.block_ids}
        shared[0] |= len(live) < sum(len(q.block_ids)
                                     for q in sched.slots if q is not None)
        peak[0] = max(peak[0], len(live))
        # this sample alone, then the high-water mark
        eng.peak_live_blocks = 0
        track()
        assert eng.peak_live_blocks == len(live)
        eng.peak_live_blocks = peak[0]

    eng._take_fn, eng._decode_fn = taken, decoded
    eng._prefill_fn, eng._track_occupancy = prefilled, tracked
    reqs = _trace(TINY.vocab_size)
    pending = list(reqs)
    while pending or not eng.all_done():
        while pending and len(sched.waiting) < 2:
            sched.submit(pending.pop(0))
        eng.step()
        _check_mirrors(sched)
    assert sorted(s.request.id for s in sched.finished) \
        == [r.id for r in reqs]
    assert seen["decode"] > 20 and seen["prefill"] > 8
    assert sched.evictions > 0, "the trace was meant to evict"
    if prefix != "off":
        assert sched.counters["prefix_cow_copies"] > 0, \
            "the trace was meant to copy a shared block on write"
        assert shared[0], "the trace was meant to map a block twice"
    assert eng.peak_live_blocks == peak[0] > 0
    # every slot released: the mirror is null again
    assert not sched.tables.any()


def _phi4():
    import jax

    from benchmarks.reference import phi4_flash as ref
    from test_phi4_flash import SZ, make_model

    model = make_model()
    return model, jax.jit(lambda k: ref.init_params(SZ, k))(
        jax.random.key(3))


@pytest.mark.parametrize("name", ["gpt", "phi4"])
def test_plain_path_puts_nothing_and_builds_nothing_after_prewarm(
        gpt_model, name):
    """A steady decode-plus-prefill loop hands the device numpy only, and
    after ``prewarm_decode`` and one chunk of every prefill bucket in the
    loop's own argument forms neither program grows its cache; a model
    that keeps state by slot carries its ``slots`` and ``final`` the
    same way."""
    model, params = gpt_model if name == "gpt" else _phi4()
    serve = dataclasses.replace(PLAIN, num_blocks=41, kernel="xla")
    eng = PagedDecodeEngine(model, params, serve)
    assert eng._slot_state == (name == "phi4")
    eng.prewarm_decode()
    S = 1
    while S <= serve.prefill_chunk:
        _, eng.pools = eng._prefill_fn(
            eng.params, eng.pools, np.zeros((1, S), np.int32),
            np.int32(0), np.int32(0),
            np.zeros((1, serve.max_blocks_per_seq), np.int32))
        S *= 2
    warm = eng.compile_counts()
    assert None not in warm.values()
    eng.reset()
    res = eng.run(_trace(model.cfg.vocab_size)[:5])
    assert eng.compile_counts() == warm
    assert res["forward_dispatches"] > 20
    assert res["host_array_puts"] == 0
    assert eng.load_signals()["host_array_puts"] == 0
    assert 0 < res["table_rows_written"] < res["table_rows_dispatched"]


def test_the_verify_path_still_puts_and_is_counted(gpt_model):
    """The counter is live: a drafter's verify dispatch hands its four
    arrays over through the Python path (prefill stays numpy)."""
    model, params = gpt_model
    serve = dataclasses.replace(PLAIN, speculative="ngram", draft_k=2)
    eng = PagedDecodeEngine(model, params, serve)
    res = eng.run(_trace(TINY.vocab_size)[:3])
    verify = [s for s in res["dispatch_shapes"] if s[0] == "verify"]
    assert verify and res["host_array_puts"] > 0
    assert res["host_array_puts"] % 4 == 0
    assert res["host_array_puts"] // 4 <= res["forward_dispatches"]
