"""Metrics sink (utils/metrics_writer): the machine-readable counterpart of
the reference's stdout trace (mpipy.py:88) — TensorBoard events when
tensorboardX is importable, metrics.jsonl always."""

import json
import os

import pytest

from mpi_tensorflow_tpu.utils import metrics_writer


def read_jsonl(d):
    with open(os.path.join(d, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f]


@pytest.mark.quick
class TestMetricsWriter:
    def test_jsonl_contract(self, tmp_path):
        d = str(tmp_path / "m")
        with metrics_writer.MetricsWriter(d) as mw:
            mw.scalar("eval/err", 12.5, 0)
            mw.scalars({"a": 1.0, "b": 2.0}, 50)
        recs = read_jsonl(d)
        assert [(r["tag"], r["value"], r["step"]) for r in recs] == [
            ("eval/err", 12.5, 0), ("a", 1.0, 50), ("b", 2.0, 50)]
        # event file appears when tensorboardX is available on the box
        try:
            import tensorboardX  # noqa: F401
        except ImportError:
            return
        assert any(n.startswith("events.") for n in os.listdir(d))

    def test_none_dir_noops(self, tmp_path):
        mw = metrics_writer.MetricsWriter(None)
        mw.scalar("x", 1.0, 0)    # must not raise or create files
        mw.close()
        assert not mw.active

    def test_nonzero_process_noops(self, tmp_path):
        d = str(tmp_path / "m")
        mw = metrics_writer.for_process(d, process_index=3)
        mw.scalar("x", 1.0, 0)
        mw.close()
        assert not os.path.exists(os.path.join(d, "metrics.jsonl"))

    def test_faults_block_normalizes_counters(self):
        """The canonical serving faults block: every key present (0 when
        the counter never fired), plain ints — the one shape engine
        results, the recovery supervisor, and the entry point's JSON share."""
        from collections import Counter

        block = metrics_writer.faults_block(Counter(shed=2, evictions=5))
        assert set(block) == set(metrics_writer.SERVING_FAULT_KEYS)
        assert block["shed"] == 2 and block["evictions"] == 5
        assert block["deadline_exceeded"] == 0 and block["replays"] == 0
        assert all(type(v) is int for v in block.values())

    def test_speculation_block_normalizes_counters(self):
        """The canonical speculative-decoding block: rates derived from
        the raw spec_* counters, steps_saved = emitted - forwards (full
        KV-streaming passes avoided), zero-safe when nothing drafted —
        the one shape engine results, the recovery supervisor's
        cross-attempt merge, and the entry point's JSON share."""
        from collections import Counter

        block = metrics_writer.speculation_block(
            Counter(spec_drafted=10, spec_accepted=6,
                    spec_verify_forwards=4, spec_emitted=10),
            enabled=True, mode="ngram", draft_k=4)
        assert block["enabled"] and block["mode"] == "ngram"
        assert block["draft_tokens"] == 10 and block["accepted_tokens"] == 6
        assert block["accept_rate"] == 0.6
        assert block["mean_accepted_len"] == 1.5
        assert block["steps_saved"] == 6
        # empty counters (off mode, or a crash before the first verify)
        z = metrics_writer.speculation_block({}, enabled=False)
        assert z["accept_rate"] == 0.0 and z["mean_accepted_len"] == 0.0
        assert z["steps_saved"] == 0 and not z["enabled"]

    def test_goodput_block_normalizes_rows(self):
        """The canonical SLO-goodput block: attainment and within-budget
        tokens/sec from per-request rows, with a per-tenant breakdown —
        the one shape the serving entry point prints."""
        rows = [
            # met: ok within budget
            {"tenant": "interactive", "status": "ok", "tokens": 10,
             "attained_ms": 50.0, "slo_ms": 100.0},
            # missed: ok but past budget (slipped between sweeps)
            {"tenant": "interactive", "status": "ok", "tokens": 10,
             "attained_ms": 150.0, "slo_ms": 100.0},
            # missed: deadline sweep already failed it
            {"tenant": "batch", "status": "deadline_exceeded",
             "tokens": 4, "attained_ms": None, "slo_ms": 400.0},
            # met: no budget — any ok completion counts
            {"tenant": "batch", "status": "ok", "tokens": 20,
             "attained_ms": 300.0, "slo_ms": None},
        ]
        block = metrics_writer.goodput_block(rows, elapsed_s=2.0)
        assert set(block) == set(metrics_writer.GOODPUT_KEYS)
        assert block["enabled"]          # any row with an SLO enables it
        assert block["requests"] == 4 and block["ok_requests"] == 3
        assert block["slo_met_requests"] == 2
        assert block["slo_attainment"] == 0.5
        assert block["goodput_tokens_per_sec"] == 15.0   # (10+20)/2
        assert block["goodput_requests_per_sec"] == 1.0
        assert block["p50_attained_ms"] == 150.0
        per = block["per_tenant"]
        assert set(per) == {"interactive", "batch"}
        assert per["interactive"]["slo_attainment"] == 0.5
        assert per["batch"]["slo_met_requests"] == 1
        # zero-safe: no rows, no elapsed time
        z = metrics_writer.goodput_block([], elapsed_s=0.0)
        assert set(z) == set(metrics_writer.GOODPUT_KEYS)
        assert not z["enabled"] and z["slo_attainment"] == 0.0
        assert z["goodput_tokens_per_sec"] == 0.0 and z["per_tenant"] == {}

    def test_tier_block_normalizes_counters(self):
        """The canonical host-tiering block: lifecycle counters, the
        derived prefill-tokens-saved line (promotions * block_size),
        and the zero-safe mean promote latency — the one shape the
        engine result and the entry point's JSON carry under ``tier``."""
        block = metrics_writer.tier_block(
            enabled=True, mode="host", demotions=5, promotions=3,
            host_blocks=2, host_blocks_peak=4,
            promote_ms_total=1.2345, block_size=4)
        assert set(block) == set(metrics_writer.TIER_KEYS)
        assert block["enabled"] and block["mode"] == "host"
        assert block["prefill_tokens_saved_tier"] == 12   # 3 * 4
        assert block["promote_latency_ms_total"] == 1.234
        assert block["promote_latency_ms_mean"] == 0.411  # 1.2345 / 3
        # zero-safe: tiering off, no promotions, no division blowups
        z = metrics_writer.tier_block()
        assert set(z) == set(metrics_writer.TIER_KEYS)
        assert not z["enabled"] and z["mode"] == "off"
        assert z["promote_latency_ms_mean"] == 0.0
        assert z["prefill_tokens_saved_tier"] == 0

    def test_write_faults_streams_one_scalar_per_counter(self, tmp_path):
        d = str(tmp_path / "m")
        with metrics_writer.MetricsWriter(d) as mw:
            block = metrics_writer.write_faults(mw, {"rejected": 3}, step=7)
        assert block["rejected"] == 3
        recs = read_jsonl(d)
        tags = {r["tag"]: r["value"] for r in recs}
        assert tags["serving/faults/rejected"] == 3
        assert tags["serving/faults/drained"] == 0
        assert all(r["step"] == 7 for r in recs)

    def test_image_loop_streams_metrics(self, tmp_path, mesh8, mnist_dir):
        from mpi_tensorflow_tpu.config import Config
        from mpi_tensorflow_tpu.data import mnist
        from mpi_tensorflow_tpu.train import loop

        splits = mnist.load_splits(mnist_dir, num_shards=8, train_n=256,
                                   test_n=64)
        cfg = Config(epochs=8, batch_size=8, log_every=10, seed=1,
                     metrics_dir=str(tmp_path / "m"))
        loop.train(cfg, splits=splits, mesh=mesh8, verbose=False)
        tags = {r["tag"] for r in read_jsonl(cfg.metrics_dir)}
        assert "eval/test_error_pct" in tags
        assert "perf/images_per_sec" in tags

    def test_mlm_loop_streams_metrics(self, tmp_path):
        import dataclasses as dc

        from mpi_tensorflow_tpu.config import Config
        from mpi_tensorflow_tpu.models import bert
        from mpi_tensorflow_tpu.train import mlm_loop

        cfg = Config(batch_size=4, epochs=4, model="bert_base",
                     metrics_dir=str(tmp_path / "m"), log_every=4)
        res = mlm_loop.train_mlm(
            cfg, bert_cfg=dc.replace(bert.BERT_TINY, dropout=0.0),
            train_n=64, test_n=16, verbose=False)
        recs = read_jsonl(cfg.metrics_dir)
        tags = {r["tag"] for r in recs}
        assert {"eval/heldout_error_pct", "train/loss",
                "perf/tokens_per_sec"} <= tags
        losses = [r["value"] for r in recs if r["tag"] == "train/loss"]
        assert all(v == v for v in losses) and losses   # finite stream
        assert res.num_steps > 0


@pytest.mark.quick
class TestPrefixBlockV2:
    def test_prefix_block_v2_keys_and_saved_tokens(self):
        """prefill_tokens_saved = full-block hit tokens + partial-copy
        rows; hit_rate stays FULL-BLOCK-only (the v1 pin), and the v2
        counters normalize to plain ints with zero-safe defaults."""
        from collections import Counter

        block = metrics_writer.prefix_block(
            Counter(prefix_hit_tokens=40, prefix_prompt_tokens=100,
                    prefix_partial_copy_tokens=6,
                    prefix_gen_inserted_blocks=3),
            enabled=True, trie_blocks=9, router_prefix_hits=2)
        assert block["hit_rate"] == 0.4          # partial rows excluded
        assert block["gen_inserted_blocks"] == 3
        assert block["partial_copy_tokens"] == 6
        assert block["prefill_tokens_saved"] == 46
        assert block["router_prefix_hits"] == 2
        empty = metrics_writer.prefix_block(Counter(), enabled=False)
        assert empty["prefill_tokens_saved"] == 0
        assert empty["gen_inserted_blocks"] == 0
        assert empty["router_prefix_hits"] == 0


@pytest.mark.quick
class TestBreakdownPace:
    def test_step_ring_keys_say_which_side_sets_the_pace(self):
        """``step_ms_p50`` / ``device_wait_ms_p50`` / ``lookahead_share``
        come from the step records (serving/tracing): the iteration's
        length, the host's wait for the device inside it, and the
        newest record's lookahead count over its dispatch count."""
        def step(t0, t1, wait, ahead, total, discarded=0):
            return {"t0": t0, "t1": t1,
                    "phases": {"serve.step": t1 - t0 - wait - 1e-3,
                               "serve.launch": 1e-3, "serve.wait": wait},
                    "compiles": 0, "emitted": 1,
                    "signals": {"queue_depth": 0, "occupancy": 0.5,
                                "forward_dispatches": total,
                                "lookahead_dispatches": ahead,
                                "lookahead_discarded_rows": discarded}}
        trace = {"enabled": True, "spans": {}, "steps": 3,
                 "steps_dropped": 0,
                 "replicas": [{"steps": [step(0.00, 0.01, 0.008, 1, 2),
                                         step(0.01, 0.02, 0.006, 3, 4),
                                         step(0.02, 0.03, 0.004, 5, 6)]}]}
        bd = metrics_writer.breakdown_block(trace)
        assert tuple(bd) == metrics_writer.BREAKDOWN_KEYS
        assert bd["step_ms_p50"] == pytest.approx(10.0)
        assert bd["device_wait_ms_p50"] == pytest.approx(6.0)
        assert bd["lookahead_share"] == pytest.approx(5 / 6, abs=1e-4)
        # no step ring (a fleet harvest without records): zeros
        empty = metrics_writer.breakdown_block(
            {"enabled": True, "spans": {}, "steps": 0, "steps_dropped": 0})
        assert empty["step_ms_p50"] == empty["lookahead_share"] == 0
