"""The serve driver reaches a model only through
``harness/models/<program.model>.py``, and doing so changed nothing.

What the tree served before the seam (PR 27's parent, recorded from it at
rehearsal sizes, seed 1): the ramp alone, 8 requests finished, is the same
sequence of iterations on any machine, where the window's counts follow
the machine's speed.  So the ramp's served tokens are what is compared."""

import contextlib
import hashlib
import json

import pytest

from benchmarks import run as run_lib
from benchmarks.harness import device, manifest, models, serve_driver, spans

SERVE = "gpt_base.serve_closed128"
BEFORE = {"finished": 8, "tokens": 67, "served_logit_gap": 0.0,
          "sha256": "d91acd8f22c47955fe855ab31c24fcf60acb71487d2ba1d72adff"
                    "d8dcc345ef2"}


def _cell():
    cell = manifest.cell(manifest.manifest(), SERVE)
    run_lib.apply_rehearsal(cell)
    return cell


def test_the_ramp_serves_what_it_served_before_the_seam():
    import jax

    sc = serve_driver.ServeCell(_cell(), jax.devices()[:1], 1, False)
    sc.prewarm()
    serve_driver.closed_loop(sc, spans.Spans(False), 0.0,
                             contextlib.nullcontext, device.CompileCounter())
    fin = sorted((r for r in sc.records.values() if r["status"] == "ok"),
                 key=lambda r: (r["client"], r["k"]))
    h = hashlib.sha256()
    for r in fin:
        h.update(json.dumps([r["client"], r["k"], r["prompt"],
                             r["tokens"]]).encode())
    gap = serve_driver.served_gap_of(
        sc.kind.reference_logits, sc.make_params(jax.random.key(1)), fin, 4,
        1)
    assert {"finished": len(fin), "tokens": sum(len(r["tokens"])
                                                for r in fin),
            "served_logit_gap": gap, "sha256": h.hexdigest()} == BEFORE
    # what the window's counts are read from
    assert len(sc.iter_ends) >= len(sc.decode_calls) > 0
    assert 0 < len(sc.prefill_calls)


def test_the_seam_gives_the_six_functions():
    kind = models.lookup("causal_lm")
    for name in ("sizes", "build", "init_params", "request_flops",
                 "cache_bytes", "reference_logits"):
        assert callable(getattr(kind, name)), name
    assert kind.sizes(_cell()["config_data"])["vocab"] == 1024


def test_unknown_model_fails_with_the_list_of_those_there():
    import jax

    cell = _cell()
    cell["config_data"]["program"]["model"] = "no_such_model"
    with pytest.raises(KeyError, match=r"no_such_model.*causal_lm"):
        serve_driver.ServeCell(cell, jax.devices()[:1], 1, False)
