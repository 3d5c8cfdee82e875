"""bench.py: CLI flag validation and the measure_*/report paths."""

import sys

import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import bench

pytestmark = pytest.mark.quick


class TestFlagValidation:
    def test_params_bf16_requires_bf16(self):
        with pytest.raises(SystemExit):
            bench.main(["--model", "bert_base", "--params-bf16"])

    def test_params_bf16_rejects_image_models(self):
        with pytest.raises(SystemExit):
            bench.main(["--model", "resnet20", "--precision", "bf16",
                        "--params-bf16"])

    def test_record_baseline_rejects_bf16(self):
        with pytest.raises(SystemExit):
            bench.main(["--record-baseline", "--precision", "bf16"])

class TestMeasureBertDetail:
    def test_paths_in_detail(self, monkeypatch):
        """measure_bert's result must record which attention/CE paths the
        compiled step engaged."""
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_bert(batch_size=2, steps=2, precision="fp32",
                               scan_steps=1, seq_len=32)
        assert r["paths"]["attention"] == "xla_dense"   # CPU, S < min_seq
        assert r["paths"]["ce_positions"] == "masked_packed"
        assert "ce" in r["paths"]
        assert "flash_probe" not in r      # no probe: selection, or a raise


class TestMeasureAllreduce:
    def test_chained_method_detail(self):
        r = bench.measure_allreduce(payload_mb=0.05, iters=2, chain=2,
                                    dispatches=2)
        assert r["allreduce_ms"] > 0
        assert r["chain"] == 2
        assert r["num_devices"] == 8          # virtual CPU mesh
        assert r["algbw_gbps"] > 0

    def test_main_reports_device_identity(self, capsys):
        """Every row names the device it was measured on — platform,
        device_kind and device count — and nothing is ever replayed."""
        import json

        rc = bench.main(["--mode", "allreduce", "--payload-mb", "0.05",
                         "--steps", "2"])
        cap = capsys.readouterr()
        out = json.loads(cap.out)
        assert rc == 0
        assert out["metric"] == "gradient allreduce step time"
        assert out["value"] > 0
        assert out["detail"]["chain"] == 32
        assert out["detail"]["platform"] == "cpu"
        assert out["detail"]["device_kind"] == "cpu"
        assert out["detail"]["device_count"] == 8      # virtual CPU mesh
        assert "stale" not in out["detail"]
        # the start-up banner goes to stderr: stdout is the one JSON line
        assert "[device] platform=cpu" in cap.err

    def test_stale_and_probe_machinery_is_gone(self):
        for name in ("_emit_stale", "_stale_score", "_backend_reachable",
                     "_iter_measure_records", "MEASURE_LOG", "_PROBE_ERROR"):
            assert not hasattr(bench, name), name


class TestMeasureDecode:
    def test_decode_detail(self, monkeypatch):
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_decode(batch_size=2, prompt_len=8, new_tokens=4,
                                 precision="fp32", iters=3)
        assert r["num_beams"] == 0
        # slope timing: n_long - n_short == new_tokens extra decode steps
        assert r["decode_lengths"][1] - r["decode_lengths"][0] == 4
        # a tenancy stall can order the arms backwards (flagged, NaN value);
        # on a quiet CPU the slope must be positive
        assert r["timing_degenerate"] or r["decode_tokens_per_sec"] > 0
        assert r["new_tokens"] == 4 and r["batch_size"] == 2

    def test_decode_beam_mode(self, monkeypatch):
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_decode(batch_size=2, prompt_len=8, new_tokens=4,
                                 precision="fp32", iters=2, num_beams=3)
        assert r["num_beams"] == 3
        assert r["timing_degenerate"] or r["decode_tokens_per_sec"] > 0


class TestMeasureServing:
    def test_serving_detail_and_zero_recompiles(self, monkeypatch):
        """measure_serving on a tiny trace: emits both arms' numbers,
        and the steady-state replay adds no compiles over warmup."""
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_serving(num_requests=3, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=8,
                                  precision="fp32")
        assert r["serving_tokens_per_sec"] > 0
        assert r["static_batch_tokens_per_sec"] > 0
        assert r["speedup_vs_static"] > 0
        assert r["zero_recompile_steady_state"], r
        assert r["p99_token_latency_ms"] >= r["p50_token_latency_ms"]
        # engagement records the RESOLVED lowering (auto on CPU -> xla)
        assert r["paths"].get("paged_attention") == "xla"
        assert r["kernel"] == "xla" and r["kernel_requested"] == "auto"
        roof = r["roofline"]
        assert roof["bytes_per_decode_token_xla"] > \
            roof["bytes_per_decode_token_pallas"] > 0
        assert r["kernel_ab"] is None        # not requested
        assert r["tokens"] == r["tokens_requested"] == 3 * 8

    def test_serving_kernel_ab_emits_speedup(self, monkeypatch):
        """--serve-kernel-ab: the same trace through both lowerings
        (pallas in interpret mode on CPU), each zero-recompile after
        its own warmup."""
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_serving(num_requests=2, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=4,
                                  precision="fp32", kernel="xla",
                                  kernel_ab=True)
        ab = r["kernel_ab"]
        # off TPU the kernel arm is the interpreter and is NAMED so; an
        # interpreted arm yields no pallas-vs-xla speed
        assert ab["kernels"] == ["pallas-interpret", "xla"]
        assert ab["tokens_per_sec"]["pallas-interpret"] > 0
        assert ab["tokens_per_sec"]["xla"] > 0
        assert ab["pallas_speedup_vs_xla"] is None
        assert ab["ab_zero_recompile"], ab

    def test_serving_kernel_ab_rejects_journal_mode(self, tmp_path):
        with pytest.raises(ValueError, match="kernel-ab"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  journal=str(tmp_path / "j.jsonl"),
                                  kernel_ab=True)

    def test_serving_shared_prefix_workload(self, monkeypatch):
        """THE prefix-cache acceptance numbers: a shared-prefix trace
        with the cache on shows hit_rate > 0, live pool occupancy
        strictly below the cache-off control arm, token identity
        between the arms, and zero steady-state recompiles preserved."""
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_serving(num_requests=6, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=8,
                                  precision="fp32", prefix_cache="on",
                                  prefix_tokens=16)
        p = r["prefix"]
        assert p["enabled"] and r["serve_prefix_cache"] == "on"
        assert r["serve_prefix_tokens"] == 16
        assert p["hit_rate"] > 0 and p["hit_tokens"] > 0
        assert p["peak_live_blocks"] < p["peak_live_blocks_off"], \
            "sharing must shrink live pool occupancy on this trace"
        assert p["blocks_saved_peak"] > 0
        assert p["token_identical_vs_off"], \
            "prefix cache perturbed greedy outputs"
        assert r["zero_recompile_steady_state"], r
        assert r["serving_tokens_per_sec"] > 0

    def test_serving_prefix_off_detail_shape(self, monkeypatch):
        """Cache off (the default): the prefix block reports disabled
        and carries no comparison arm."""
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_serving(num_requests=2, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=4,
                                  precision="fp32", prefix_tokens=8)
        assert r["serve_prefix_cache"] == "off"
        assert not r["prefix"]["enabled"]
        assert "peak_live_blocks_off" not in r["prefix"]

    def test_serving_prefix_rejects_kernel_ab_combo(self):
        """One comparison, one variable: the prefix-cache control arm
        and the kernel A/B arm cannot share a run."""
        with pytest.raises(ValueError, match="prefix-cache"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  prefix_cache="on", kernel_ab=True)

    def test_serving_negative_prefix_tokens_rejected(self):
        with pytest.raises(ValueError, match="prefix-tokens"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  prefix_tokens=-1)

    def test_serving_prefix_flags_guarded_outside_serving_mode(self):
        """--serve-prefix-* shape the serving trace; any other mode
        would silently ignore them — reject the combo up front."""
        with pytest.raises(SystemExit):
            bench.main(["--mode", "train", "--serve-prefix-tokens", "64"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "decode", "--serve-prefix-cache", "on"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "serving", "--serve-prefix-cache", "on",
                        "--serve-kernel-ab"])

    def test_serving_speculative_workload_and_ab(self, monkeypatch):
        """Speculative serving smoke: the speculation block is live and
        self-consistent, outputs are token-identical to the off control
        arm, zero-recompile holds (the content-dependent verify buckets
        are pre-warmed), and --serve-spec-ab emits the speedup line.
        The accept_rate > 0 pin lives in tests/test_speculative.py on a
        controlled recurrent stream — a tiny Poisson trace can't
        guarantee the drafter lands."""
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_serving(num_requests=4, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=12,
                                  precision="fp32", prefix_tokens=8,
                                  speculative="ngram", draft_k=4,
                                  spec_ab=True)
        sp = r["speculation"]
        assert sp["enabled"] and sp["mode"] == "ngram"
        assert r["serve_speculative"] == "ngram" and r["serve_draft_k"] == 4
        assert sp["verify_forwards"] > 0
        assert sp["emitted_tokens"] == sp["verify_forwards"] \
            + sp["steps_saved"]
        assert sp["token_identical_vs_off"], \
            "speculation perturbed greedy outputs"
        assert r["zero_recompile_steady_state"], r
        ab = r["spec_ab"]
        assert ab["arms"]["speculative"] > 0 and ab["arms"]["off"] > 0
        assert ab["spec_speedup_vs_off"] is not None
        assert ab["ab_zero_recompile"], ab

    def test_serving_speculative_rejects_bad_combos(self, tmp_path):
        """One comparison, one variable — and no silent knobs: the
        measure_serving layer mirrors every bench argparse guard as a
        ValueError for programmatic callers."""
        with pytest.raises(ValueError, match="spec-ab"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  spec_ab=True)            # no drafter
        with pytest.raises(ValueError, match="one variable"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  speculative="ngram", spec_ab=True,
                                  kernel_ab=True)
        with pytest.raises(ValueError, match="journal"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  speculative="ngram", spec_ab=True,
                                  journal=str(tmp_path / "j.jsonl"))
        with pytest.raises(ValueError, match="control arm"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  speculative="ngram", kernel_ab=True)
        with pytest.raises(ValueError, match="draft_k"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  speculative="ngram", draft_k=0)

    def test_serving_fleet_journal_mode(self, tmp_path):
        """--serve-replicas + --serve-journal (the combination PR 6
        forbade) is now the fault-tolerant fleet serve mode: one
        journal per replica at <path>.r<i>, outputs/statuses merged
        across them, fleet_faults block present and clean."""
        journal = str(tmp_path / "fleet.jsonl")
        r = bench.measure_serving(num_requests=3, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=6,
                                  precision="fp32", tiny=True,
                                  journal=journal, replicas=2)
        assert r["serve_replicas"] == 2 and r["journal"] == journal
        assert set(r["statuses"].values()) == {"ok"}
        assert len(r["outputs"]) == 3
        import os

        for i in range(2):
            assert os.path.exists(f"{journal}.r{i}"), \
                "per-replica journal file missing"
        ff = r["fleet_faults"]
        assert ff["failovers"] == 0 and ff["migrated_requests"] == 0
        assert r["replicas"]["per_replica"][0]["health"] == "healthy"

    def test_serving_fault_injection_failover_token_identical(
            self, monkeypatch):
        """--serve-fault-*: the routed arm loses a replica mid-trace
        and still emits exactly the single engine's tokens, with the
        fleet_faults block recording the failover."""
        # the fault fires at replica 0's THIRD TICK, and only sequential
        # stepping ties ticks to work (ReplicaFault: "deterministic under
        # parallel=False"): a replica thread on a loaded host can idle
        # through three ticks before its first request is routed, and the
        # fault then has nothing to migrate
        from mpi_tensorflow_tpu.serving import router

        monkeypatch.setattr(router, "default_parallelism", lambda: False)
        r = bench.measure_serving(num_requests=4, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=8,
                                  precision="fp32", tiny=True,
                                  replicas=2, fault_replica=0,
                                  fault_step=3)
        reps = r["replicas"]
        assert reps["fleet_faults"]["failovers"] == 1
        assert reps["fleet_faults"]["migrated_requests"] >= 1
        assert reps["serve_fault"] == {"replica": 0, "step": 3,
                                       "kind": "transient"}
        assert reps["token_identical_vs_single"], \
            "failover perturbed greedy outputs"

    def test_serving_fault_knobs_validated(self, tmp_path):
        with pytest.raises(ValueError, match="together"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  replicas=2, fault_replica=0)
        with pytest.raises(ValueError, match="replicas"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  fault_replica=0, fault_step=3)
        with pytest.raises(ValueError, match="outside the fleet"):
            bench.measure_serving(num_requests=2, tiny=True, replicas=2,
                                  fault_replica=5, fault_step=3)
        with pytest.raises(ValueError, match="fault-kind"):
            bench.measure_serving(num_requests=2, tiny=True, replicas=2,
                                  fault_replica=0, fault_step=3,
                                  fault_kind="flaky")
        with pytest.raises(ValueError, match="fault-step"):
            bench.measure_serving(num_requests=2, tiny=True, replicas=2,
                                  fault_replica=0, fault_step=0)

    def test_serving_fault_flags_guarded_at_argparse(self):
        with pytest.raises(SystemExit):
            bench.main(["--mode", "train", "--serve-fault-replica", "0",
                        "--serve-fault-step", "3"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "serving", "--serve-fault-replica",
                        "0"])               # step missing
        with pytest.raises(SystemExit):
            bench.main(["--mode", "serving", "--serve-fault-replica",
                        "0", "--serve-fault-step", "3"])  # no fleet

    def test_serving_speculative_flags_guarded_at_argparse(self):
        """--serve-speculative/--serve-draft-k/--serve-spec-ab shape
        the serving trace; reject bad values and non-serving modes up
        front, before any device work."""
        with pytest.raises(SystemExit):
            bench.main(["--mode", "serving", "--serve-draft-k", "0"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "train", "--serve-speculative", "ngram"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "decode", "--serve-spec-ab"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "serving", "--serve-speculative",
                        "ngram", "--serve-spec-ab", "--serve-kernel-ab"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "serving", "--serve-spec-ab"])

    def test_serving_default_trace_byte_identical_post_loadgen(self):
        """THE refactor pin at the bench seam: make_serving_spec +
        loadgen.build_trace on bench's default knobs reproduces the
        pre-loadgen inline generator byte-for-byte (prompts, budgets,
        arrival stamps) — host-only, no engine."""
        import numpy as np

        from mpi_tensorflow_tpu.serving import loadgen

        spec = bench.make_serving_spec(vocab_size=32000)
        t = loadgen.build_trace(spec)
        # the historical inline generator, verbatim
        rng = np.random.default_rng(0)
        prompts = [list(map(int, rng.integers(0, 32000, int(n))))
                   for n in rng.integers(8, 33, 24)]
        outputs = [int(n) for n in rng.integers(8, 129, 24)]
        arrivals = np.cumsum(rng.exponential(1.0 / 4.0, 24))
        arrivals[0] = 0.0
        assert t.prompts == prompts
        assert t.outputs == outputs
        assert np.array_equal(t.arrivals, arrivals)

    def test_serving_workload_slo_goodput_and_autoscale(self, monkeypatch):
        """The acceptance run: a bursty multi-tenant trace under an SLO
        emits the goodput block (per-tenant attainment) and the
        ScaleAdvisor decision log in detail — all on CPU."""
        from mpi_tensorflow_tpu.models import bert

        monkeypatch.setattr(bert, "BERT_BASE", bert.BERT_TINY)
        r = bench.measure_serving(num_requests=6, rate_rps=1e6,
                                  max_slots=2, block_size=8,
                                  prompt_max=8, output_max=8,
                                  precision="fp32",
                                  workload="multi-tenant",
                                  slo_ms=60000.0)
        assert r["serve_workload"] == "multi-tenant"
        assert r["serve_slo_ms"] == 60000.0
        gp = r["goodput"]
        assert gp["enabled"]
        assert gp["requests"] == 6
        assert set(gp["per_tenant"]) <= {"interactive", "batch"}
        assert len(gp["per_tenant"]) >= 1
        # generous SLO on a tiny trace: everything lands in budget
        assert gp["slo_attainment"] == 1.0
        assert gp["goodput_tokens_per_sec"] > 0
        assert r["status_counts"] == {"ok": 6}
        a = r["autoscale"]
        assert a["ticks"] > 0 and isinstance(a["decisions"], list)
        assert a["policy"]["hold_ticks"] >= 1
        # sticky sessions from the interactive tenant rode the trace
        assert r["zero_recompile_steady_state"] in (True, None)

    def test_serving_workload_knobs_validated(self):
        with pytest.raises(ValueError, match="serve-workload"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  workload="sinusoidal")
        with pytest.raises(ValueError, match="serve-slo-ms"):
            bench.measure_serving(num_requests=2, tiny=True,
                                  slo_ms=0.0)

    def test_serving_workload_flags_guarded_at_argparse(self):
        with pytest.raises(SystemExit):
            bench.main(["--mode", "train", "--serve-workload", "bursty"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "decode", "--serve-slo-ms", "100"])
        with pytest.raises(SystemExit):
            bench.main(["--mode", "serving", "--serve-slo-ms", "0"])
        with pytest.raises(SystemExit):      # bad enum dies in argparse
            bench.main(["--mode", "serving", "--serve-workload", "x"])


class TestHostIo:
    def test_hostio_smoke_reports_all_paths(self):
        """measure_hostio runs device-free and reports a rate per
        assembly path; the device's demand is not its to quote."""
        import bench

        r = bench.measure_hostio(batch_size=4, window_k=2, windows=3,
                                 image_size=16, train_n=32)
        assert r["host_images_per_sec_inline"] > 0
        assert r["host_images_per_sec_thread"] > 0
        rates = [v for k, v in r.items()
                 if k.startswith("host_images_per_sec_") and v]
        assert r["host_images_per_sec"] == max(rates)
        assert r["device_demand_img_s"] == "not measured"
        assert "feed_headroom_x" not in r

    def test_hostio_mode_exits_zero_without_device(self, capsys,
                                                   monkeypatch):
        import functools

        import bench

        # tiny shapes: the CLI wiring is under test, not the gather rate
        monkeypatch.setattr(
            bench, "measure_hostio",
            functools.partial(bench.measure_hostio, window_k=2, windows=3,
                              image_size=16, train_n=32))
        rc = bench.main(["--mode", "hostio", "--batch-size", "2"])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        import json

        rec = json.loads(out)
        assert rec["unit"] == "images/sec (host)"
        assert rec["value"] > 0
