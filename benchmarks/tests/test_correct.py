"""``correct`` has to be able to come out false.

- The control: the reference put in the program's place, computed in the
  next precision down (fp8 operands for the bfloat16 configurations), at a
  size a test run can hold, held to the cells' own limits.
- The faults: the rest of a run driven end to end (``run.main`` with the
  look for a chip skipped by ``--rehearse-cpu``) with the timed path broken
  underneath — a step that returns its state unchanged, half of the batch
  left out, one chip's share alone (what the exchange left out gives), a
  token altered where it is produced.
"""

import json

import numpy as np
import pytest

from benchmarks import run as run_lib
from benchmarks.harness import check, manifest

MAN = manifest.manifest()
TRAIN = "bert_base.train_b256_s128"
TRAIN4 = "bert_base.train_dp4_b256_s128"
SERVE = "gpt_base.serve_closed128"


def _cell(name):
    cell = manifest.cell(MAN, name)
    run_lib.apply_rehearsal(cell)
    return cell


def _with_four_chip_cell(monkeypatch):
    """Where the four-chip training cell is not in ``BENCHMARK.json``
    (its mix is), add the entry for the test, held to the one-chip cell's
    limits."""
    if any(w["name"] == TRAIN4 for w in MAN["workloads"]):
        return
    man = json.loads(json.dumps(MAN))
    man["workloads"].append({
        "name": TRAIN4, "config": "bert_base",
        "traffic": "train_dp4_b256_s128", "chips": 4, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if TRAIN in m.get("workloads", []):
            m["workloads"].append(TRAIN4)
    monkeypatch.setattr(manifest, "manifest", lambda: man)
    real = manifest.cell

    def cell(m, name):
        out = real(m, name)
        if name == TRAIN4:
            out["limits"] = real(m, TRAIN)["limits"]
        return out
    monkeypatch.setattr(manifest, "cell", cell)


def _run(capsys, name, seed=41):
    rc = run_lib.main(["--workload", name, "--seed", str(seed), "--seconds",
                       "1", "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def train_cell():
    import jax

    from benchmarks.harness import spans, train_driver

    tc = train_driver.TrainCell(_cell(TRAIN), jax.devices()[:1], 23)
    n = int(tc.mix["check_steps"])
    prog = tc.first_steps(spans.Spans(False), n)
    tc.free()
    return tc, n, prog, tc.reference_steps(n)


def test_program_and_reference_agree_within_the_limits(train_cell):
    tc, n, prog, ref = train_cell
    limits = manifest.cell(MAN, TRAIN)["limits"]
    ok, checked = check.judge(check.training_numbers(prog, ref), limits)
    assert ok, checked
    ok, _ = check.judge(check.training_numbers(ref, ref), limits)
    assert ok


@pytest.mark.parametrize("how", ["fp8", "half_batch", "frozen"])
def test_training_control_and_planted_faults_fail(train_cell, how):
    tc, n, _, ref = train_cell
    limits = manifest.cell(MAN, TRAIN)["limits"]
    bad = (tc.reference_steps(n, precision="fp8") if how == "fp8"
           else tc.reference_steps(n, fault=how))
    ok, checked = check.judge(check.training_numbers(bad, ref), limits)
    assert not ok, checked


def test_leaves_with_no_gradient_are_left_out_of_the_change(train_cell):
    _, _, _, ref = train_cell
    keep = check.moving_leaves(ref["grad_norms"])
    assert 0 < (~keep).sum() < len(keep) // 4      # the key biases


def test_serving_control_reads_wider_than_the_reference_itself():
    import jax

    from benchmarks.harness import serve_driver
    from benchmarks.reference import transformer as ref_tf

    cell = _cell(SERVE)
    sz = ref_tf.sizes(cell["config_data"])
    params = jax.jit(lambda k: ref_tf.init_params(sz, k))(jax.random.key(3))
    rng = np.random.default_rng(0)
    from benchmarks.harness.models import causal_lm as kind
    from benchmarks.reference import causal_lm

    prompt = rng.integers(0, sz["vocab"], 24).tolist()
    toks = list(prompt)
    for _ in range(16):                 # greedy, by the reference itself
        pad = np.zeros((256,), np.int32)
        pad[:len(toks)] = toks
        lg = causal_lm.next_token_logits(
            params, pad, np.asarray([len(toks) - 1], np.int32))
        toks.append(int(np.argmax(lg[0])))
    rec = {"client": 0, "k": 0, "prompt": prompt, "tokens": toks[24:]}
    stats = {"control": "fp8"}
    gap = serve_driver.served_gap_of(kind.reference_logits, params, [rec],
                                     1, 0, stats=stats)
    assert gap == 0.0
    assert stats["control_gap"] > 0.0
    altered = dict(rec, tokens=[(t + 1) % sz["vocab"] for t in rec["tokens"]])
    assert serve_driver.served_gap_of(kind.reference_logits, params,
                                      [altered], 1, 0) > stats["control_gap"]


def test_sound_runs_come_out_correct(capsys, monkeypatch):
    _with_four_chip_cell(monkeypatch)
    for name in (TRAIN, SERVE, TRAIN4):
        line = _run(capsys, name)
        assert line["correct"] is True, line["checked"]
        assert line["metrics"] == {} and line["rehearsal"] is True
        assert list(line)[-1] == "checked"


def test_fault_state_returned_unchanged(capsys, monkeypatch):
    import jax

    from mpi_tensorflow_tpu.train import gspmd

    real = gspmd.make_gspmd_train_step

    def broken(model, mesh, tx, **kw):
        step = real(model, mesh, tx, **kw)

        def unchanged(state, batch, labels, rng):
            _, metrics = step(jax.tree.map(lambda x: x.copy(), state),
                              batch, labels, rng)
            return state, metrics
        return unchanged

    monkeypatch.setattr(gspmd, "make_gspmd_train_step", broken)
    line = _run(capsys, TRAIN)
    assert line["correct"] is False
    assert line["checked"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def _loss_over_first(fraction):
    from mpi_tensorflow_tpu.models import bert

    real = bert.BertMlm.loss

    def loss(self, params, model_state, batch, labels, **kw):
        n = max(1, int(labels.shape[0] * fraction))
        return real(self, params, model_state,
                    {k: v[:n] for k, v in batch.items()}, labels[:n], **kw)
    return bert.BertMlm, loss


def test_fault_half_of_the_batch_left_out(capsys, monkeypatch):
    cls, loss = _loss_over_first(0.5)
    monkeypatch.setattr(cls, "loss", loss)
    line = _run(capsys, TRAIN)
    assert line["correct"] is False, line["checked"]


def test_fault_exchange_between_chips_left_out(capsys, monkeypatch):
    # without the all-reduce a chip steps on its own quarter of the rows
    _with_four_chip_cell(monkeypatch)
    cls, loss = _loss_over_first(0.25)
    monkeypatch.setattr(cls, "loss", loss)
    line = _run(capsys, TRAIN4)
    assert line["device"]["count"] == 4
    assert line["correct"] is False, line["checked"]


def test_fault_token_altered_where_it_is_produced(capsys, monkeypatch):
    from mpi_tensorflow_tpu.serving import engine

    real = engine.PagedDecodeEngine._decode_impl

    def altered(self, params, pools, tokens, lengths, tables):
        nxt, pools = real(self, params, pools, tokens, lengths, tables)
        return (nxt + 1) % self.model.cfg.vocab_size, pools

    monkeypatch.setattr(engine.PagedDecodeEngine, "_decode_impl", altered)
    line = _run(capsys, SERVE)
    assert line["correct"] is False, line["checked"]
