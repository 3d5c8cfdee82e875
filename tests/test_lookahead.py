"""The engine's one-step lookahead: iteration n+1 is dispatched before
iteration n's tokens are read, and nothing a client sees changes.

- delivered tokens equal ``generate()``'s and the tokens the synchronous
  engine delivered on the same trace (``PARENT``: digests taken from the
  commit before the lookahead), for both served models, with and without
  an EOS id that ends rows while a dispatch still carries them;
- what is learnt a dispatch late — EOS, preemption, a deadline, a cut —
  drops the row in flight and never a delivered token; the pool is whole
  afterwards;
- the prefix trie never adopts the block a dropped row wrote;
- the overlap itself: between two ``iterate`` calls one decode dispatch
  is unread, and it is read only after the next was issued (the static
  half of this pin is tests/test_analysis.py's host-sync case).

One engine a model for the whole module: a case swaps the options that
live on the host (``_configured``) and resets.
"""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

from _jitted import generate_ref
from mpi_tensorflow_tpu.models import bert, gpt
from mpi_tensorflow_tpu.serving import (BlockAllocator, EngineLoop,
                                        PagedDecodeEngine, PrefixCache,
                                        ReplayJournal, Request, Scheduler,
                                        ServeConfig)

TINY = dataclasses.replace(bert.BERT_TINY, ce_positions="all")
SERVE = ServeConfig(num_blocks=40, block_size=4, max_slots=4,
                    max_seq_len=48, prefill_chunk=8)
MODELS = ("gpt", "mla")

#: sha256 of the outputs the engine BEFORE the lookahead delivered on
#: ``_trace`` (plain) and with ``_eos_of``'s id (eos), a model each
PARENT = {
    ("gpt", "plain"): "c1fc7510667e3df4",
    ("gpt", "eos"): "f6715bd81790b452",
    ("mla", "plain"): "8529028844f9b9a4",
    ("mla", "eos"): "ffc24d65c5817f7d",
}


def _digest(outputs: dict) -> str:
    return hashlib.sha256(json.dumps(
        {str(k): list(map(int, v)) for k, v in sorted(outputs.items())}
    ).encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def engines():
    """``{name: engine}``, built on first use and shared."""
    import jax

    built = {}

    def get(name):
        if name not in built:
            if name == "gpt":
                model = gpt.CausalLm(TINY)
                params = model.init(jax.random.key(1))
            else:
                from benchmarks.reference import pangu_ultra_moe as ref
                from test_mla_moe import SZ, make_model

                model = make_model()
                params = jax.jit(lambda k: ref.init_params(SZ, k))(
                    jax.random.key(3))
            built[name] = PagedDecodeEngine(model, params, SERVE)
        return built[name]
    return get


def _configured(engine, **kw):
    """The shared engine under other HOST-side options (an EOS id, a
    deadline, a pool size, the prefix cache): its compiled programs
    stay, its state is new."""
    engine.serve = dataclasses.replace(SERVE, **kw)
    engine.reset()
    return engine


def _vocab(engine) -> int:
    return getattr(engine.model.cfg, "vocab_size")


def _trace(vocab: int, n=12, seed=7, budget_hi=14):
    """Mixed lengths: prompts of 1..20 tokens (under, at and over a
    chunk), budgets from 1 (the prefill's token is the last)."""
    rng = np.random.default_rng(seed)
    reqs = [Request(i, list(map(int, rng.integers(1, vocab, int(s)))),
                    int(b))
            for i, (s, b) in enumerate(zip(rng.integers(1, 21, n),
                                           rng.integers(1, budget_hi, n)))]
    reqs[3] = dataclasses.replace(reqs[3], max_new_tokens=1)
    return reqs


def _want(engine, reqs, eos=None) -> dict:
    """What the model's own greedy reference continues each prompt
    with: ``generate()``, or for the latent-attention family (which has
    none) its plain float32 reference."""
    out = {}
    for r in reqs:
        if hasattr(engine.model, "generate"):
            toks = generate_ref(engine.model, engine.params, r.prompt,
                                r.max_new_tokens)
        else:
            from test_mla_moe import greedy_of

            toks = greedy_of(engine.params, r.prompt, r.max_new_tokens)
        if eos is not None and eos in toks:
            toks = toks[:toks.index(eos) + 1]
        out[r.id] = toks
    return out


def _eos_of(outputs: dict) -> int:
    """The id the plain run emits most often AFTER a stream's first
    token, so that rows end on it mid-decode."""
    flat = [t for v in outputs.values() for t in v[1:]]
    return max(sorted(set(flat)), key=flat.count)


@pytest.mark.parametrize("name", MODELS)
def test_tokens_equal_generate_and_the_synchronous_engine(engines, name):
    eng = _configured(engines(name))
    reqs = _trace(_vocab(eng))
    res = eng.run(reqs)
    assert res["outputs"] == _want(eng, reqs)
    assert _digest(res["outputs"]) == PARENT[name, "plain"]
    assert all(s == "ok" for s in res["statuses"].values())
    # every dispatch but the first few found an earlier one unread, and
    # with no EOS no row was computed in vain
    assert res["lookahead_dispatches"] >= res["forward_dispatches"] - 3
    assert res["lookahead_discarded_rows"] == 0
    assert eng.load_signals()["lookahead_dispatches"] \
        == res["lookahead_dispatches"]


@pytest.mark.parametrize("name", MODELS)
def test_eos_ends_rows_that_are_still_in_flight(engines, name):
    eng = _configured(engines(name))
    reqs = _trace(_vocab(eng))
    eos = _eos_of(eng.run(reqs)["outputs"])
    eng = _configured(eng, eos_id=eos)
    res = eng.run(reqs)             # run() asserts check_quiescent
    want = _want(eng, reqs, eos)
    assert res["outputs"] == want
    assert _digest(res["outputs"]) == PARENT[name, "eos"]
    ended = [v for v in want.values() if v[-1] == eos and len(v) > 1]
    assert ended, "the trace must end some stream on EOS mid-decode"
    for v in res["outputs"].values():
        assert eos not in v[:-1]                # nothing after EOS
    # each such stream had a row in the dispatch after its EOS
    assert res["lookahead_discarded_rows"] > 0
    assert all(s == "ok" for s in res["statuses"].values())
    assert eng.allocator.num_used == 0 and not eng._unread


def test_preemption_voids_the_token_in_flight(engines):
    """Pool pressure: the youngest sequence is evicted while the last
    dispatch still carries it.  Its unread token is dropped (never
    delivered, never journaled), ``evicted_ids`` voids what was
    delivered, and the restarted stream comes out whole."""
    eng = _configured(engines("gpt"), num_blocks=14)
    reqs = _trace(_vocab(eng))
    journal = ReplayJournal(None)
    res = eng.run(reqs, journal=journal)
    assert res["evictions"] > 0
    assert res["lookahead_discarded_rows"] > 0
    assert res["outputs"] == _want(eng, reqs) == journal.outputs()
    assert all(s == "ok" for s in res["statuses"].values())


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_deadline_and_cut_land_while_a_dispatch_is_unread(engines):
    """A deadline sweep and a cut (what a drain's hard edge does to a
    live sequence) both find their sequence in the unread dispatch: the
    row is dropped, the status is the fault's, no token follows it, and
    the others finish with ``generate()``'s tokens."""
    eng = _configured(engines("gpt"))
    vocab = _vocab(eng)
    rng = np.random.default_rng(11)
    reqs = [Request(i, list(map(int, rng.integers(1, vocab, 5))), 12,
                    deadline=(5.0 if i == 0 else None))
            for i in range(4)]
    clock = _Clock()
    loop = EngineLoop(eng)
    got = {r.id: [] for r in reqs}
    for r in reqs:
        assert loop.submit(r) is None

    def iterate():
        for rid, tok in loop.iterate(clock(), clock, 0.0):
            got[rid].append(tok)

    while len(got[0]) < 3 or len(got[1]) < 3:
        iterate()
    assert eng._unread and eng._progressed      # one dispatch unread
    before = {rid: list(v) for rid, v in got.items()}
    discarded = eng.lookahead_discarded_rows
    clock.t = 6.0                   # request 0's deadline has passed
    slot = next(i for i, s in enumerate(eng.sched.slots)
                if s is not None and s.request.id == 1)
    eng.sched.fail_live(slot, "drained")        # the cut
    iterate()
    assert eng.sched.statuses[0] == "deadline_exceeded"
    assert eng.sched.statuses[1] == "drained"
    assert got[0] == before[0] and got[1] == before[1]
    assert eng.lookahead_discarded_rows == discarded + 2
    while not eng.all_done():
        iterate()
    want = _want(eng, reqs[2:])
    assert got[2] == want[2] and got[3] == want[3]
    assert got[0] == before[0] and got[1] == before[1]
    eng.sched.check_quiescent()


def _turns(vocab: int, eng):
    """Two turns a session and the EOS id that ends first turns
    mid-decode: the second prompt is the first's prompt, its served
    answer and a new question, so generated blocks can be matched."""
    rng = np.random.default_rng(5)
    first = [Request(i, list(map(int, rng.integers(1, vocab, 9 + i))), 10)
             for i in range(4)]
    eos = _eos_of(_want(eng, first))
    answers = _want(eng, first, eos)
    second = [Request(10 + r.id, r.prompt + answers[r.id]
                      + list(map(int, rng.integers(1, vocab, 3))), 8)
              for r in first]
    return first, second, eos


@pytest.mark.parametrize("gen", ["off", "on"], ids=["v1", "v2"])
def test_prefix_cache_never_matches_a_dropped_rows_block(engines, gen):
    """With the cache on (v1: prompt blocks; v2: a finishing request's
    generated blocks too) and streams ending on EOS with a row in
    flight, a second turn that walks the trie gets the cache-off
    tokens: what a dropped row wrote is in no block the trie adopted."""
    eng = _configured(engines("gpt"))
    vocab = _vocab(eng)
    first, second, eos = _turns(vocab, eng)
    want = _want(eng, first + second, eos)
    eng = _configured(eng, eos_id=eos, prefix_cache="on", prefix_gen=gen)
    res1 = eng.run(first)
    trie = eng.prefix_cache.num_blocks
    loop_discards = eng.lookahead_discarded_rows
    # the same engine, trie kept: run() only checks quiescence
    res2 = eng.run(second)
    got = {**res1["outputs"], **res2["outputs"]}
    assert got == want
    assert loop_discards > 0
    assert eng.sched.counters["prefix_hit_tokens"] > 0
    if gen == "on":
        assert eng.sched.counters["prefix_gen_inserted_blocks"] > 0
    assert eng.prefix_cache.num_blocks >= trie
    eng.sched.check_quiescent()


def test_the_block_a_dropped_row_writes_is_not_adopted():
    """Scheduler-level, no device: a sequence ends on EOS while a
    lookahead dispatch carries it (``unread`` 1).  That dispatch writes
    cache position ``len(stream) - 1``; generated-block insertion adopts
    only the full blocks of the ``len(stream) - 1`` entries written for
    delivered tokens, so the block holding that position stays out."""
    bs = 4
    alloc = BlockAllocator(16)
    trie = PrefixCache(alloc, bs)
    s = Scheduler(alloc, 2, bs, 8, prefix_cache=trie, prefix_gen=True)
    s.submit(Request(0, [1, 2, 3, 4], 9))
    (slot,) = s.admit()
    seq = s.slots[slot]
    seq.prefilled = 4
    for tok in (5, 6, 7, 8):                # delivered one at a time
        s.advance(slot)
        s.ensure_block(slot)
        s.deliver(slot, tok, eos_id=99)
    s.advance(slot)                         # dispatch n
    s.ensure_block(slot)
    s.advance(slot)                         # dispatch n+1 looks ahead
    s.ensure_block(slot)                    # ...and owns position 9's block
    assert seq.length == 10 and len(seq.block_ids) == 3
    written_late = seq.block_ids[2]         # holds positions 8..11
    s.deliver(slot, 99, eos_id=99)          # n's token is EOS
    assert seq.done and s.statuses[0] == "ok"
    # stream = 4 + 5 tokens; 8 entries written for delivered tokens
    assert trie.num_blocks == 2
    assert alloc.refcount(written_late) == 0
    s.check_quiescent()


def test_one_decode_dispatch_is_unread_between_iterates(engines):
    """The overlap, pinned without a chip: the decode dispatch of call
    k is read in call k+1, AFTER call k+1's own decode dispatch was
    issued; between calls the engine holds it unread, reports progress
    and is not done."""
    eng = _configured(engines("gpt"))
    vocab = _vocab(eng)
    rng = np.random.default_rng(3)
    reqs = [Request(i, list(map(int, rng.integers(1, vocab, 6))), 6)
            for i in range(3)]
    events = []
    decode, deliver = eng._decode_fn, eng._deliver

    def logged_decode(*a):
        events.append("dispatch")
        return decode(*a)

    def logged_deliver(n):
        out = deliver(n)
        events.append(("read", n, len(out)))
        return out
    eng._decode_fn, eng._deliver = logged_decode, logged_deliver
    try:
        clock = _Clock()
        loop = EngineLoop(eng)
        for r in reqs:
            loop.submit(r)
        got = {r.id: [] for r in reqs}
        calls = 0
        while not eng.all_done():
            for rid, tok in loop.iterate(clock(), clock, 0.0):
                got[rid].append(tok)
            calls += 1
            if eng.sched.slots.count(None) < len(eng.sched.slots):
                # live work: its newest dispatch is unread
                assert eng._unread and eng._progressed
            assert calls < 60
    finally:
        eng._decode_fn, eng._deliver = decode, deliver
    assert got == _want(eng, reqs)
    # every call issues its dispatch before it reads
    per_call, cur = [], []
    for e in events:
        cur.append(e)
        if e != "dispatch":
            per_call.append(cur)
            cur = []
    busy = [c for c in per_call if "dispatch" in c]
    assert len(busy) >= 5
    assert all(c[0] == "dispatch" and c[-1][0] == "read" for c in busy)
    # ...and the first read finds nothing yet: the lookahead's one step
    assert per_call[0][-1] == ("read", 0, 0)
    assert not eng._unread


def test_a_drafter_reads_every_dispatch_at_once(engines):
    """Where the next dispatch's shape hangs on the values (speculative
    verify: the accepted count), nothing is left unread and no dispatch
    looks ahead; the tokens are the plain path's."""
    plain = _configured(engines("gpt"))
    reqs = _trace(_vocab(plain), n=6)
    want = plain.run(reqs)["outputs"]
    eng = PagedDecodeEngine(plain.model, plain.params, dataclasses.replace(
        SERVE, speculative="ngram", draft_k=2))
    res = eng.run(reqs)
    assert res["outputs"] == want
    assert res["lookahead_dispatches"] == 0
    assert res["lookahead_discarded_rows"] == 0
    assert not eng._unread
