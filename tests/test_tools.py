"""Tooling tests: op-benchmark gate logic + cost_model facade + PARITY doc."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestOpBenchmark:
    def test_run_and_compare_gate(self, tmp_path):
        tools_dir = os.path.join(REPO, "tools")
        sys.path.insert(0, tools_dir)
        try:
            import op_benchmark
        finally:
            sys.path.remove(tools_dir)
        base = str(tmp_path / "base.json")
        payload = op_benchmark.run(base, repeats=2)
        assert set(payload["ops"]) >= {"matmul_1024", "flash_attention_256",
                                       "layer_norm_4096"}
        assert all(v > 0 for v in payload["ops"].values())
        # identical files pass the gate
        assert op_benchmark.compare(base, base, threshold=0.05) == 0
        # injected regression fails it
        with open(base) as f:
            data = json.load(f)
        data["ops"]["matmul_1024"] *= 2.0
        reg = str(tmp_path / "reg.json")
        with open(reg, "w") as f:
            json.dump(data, f)
        assert op_benchmark.compare(base, reg, threshold=0.05) == 1
        # improvement passes
        assert op_benchmark.compare(reg, base, threshold=0.05) == 0
        # a baseline op missing from the change run fails the gate
        del data["ops"]["matmul_1024"]
        part = str(tmp_path / "part.json")
        with open(part, "w") as f:
            json.dump(data, f)
        assert op_benchmark.compare(base, part, threshold=0.05) == 1


class TestMetricsSmoke:
    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "metrics_smoke", os.path.join(REPO, "tools",
                                          "metrics_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_exposition_parser_accepts_and_rejects(self):
        ms = self._load()
        good = ('# HELP a_total help\n# TYPE a_total counter\n'
                'a_total{k="v"} 3\n'
                'lat_bucket{le="+Inf"} 1\nlat_sum 0.5\nlat_count 1\n')
        samples = ms.parse_exposition(good)
        assert samples["a_total"] == 1 and samples["lat_bucket"] == 1
        with pytest.raises(ValueError):
            ms.parse_exposition("not a metric line at all\n")
        with pytest.raises(ValueError):
            ms.parse_exposition("a_total{k=unquoted} x\n")

    def test_smoke_gate_passes(self):
        # the full loop: server up -> generate -> scrape -> parse
        assert self._load().main() == 0


class TestServeBench:
    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "serve_bench", os.path.join(REPO, "tools", "serve_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_hist_quantile(self):
        sb = self._load()
        # cumulative {le: count}: 4 obs <= 0.1, 9 <= 0.5, 10 total
        b = {"0.1": 4, "0.5": 9, "1.0": 10, "+Inf": 10}
        assert sb.hist_quantile(b, 0.50) == 0.5
        assert sb.hist_quantile(b, 0.25) == 0.1
        assert sb.hist_quantile(b, 0.99) == 1.0
        assert sb.hist_quantile({"+Inf": 0}, 0.5) is None

    def test_smoke_gate_reports_prefix_hits(self, capsys):
        # ISSUE 2 acceptance: the shared-prefix workload must show a
        # nonzero prefix-cache hit rate, every number monitor-sourced
        sb = self._load()
        assert sb.main([]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        out = json.loads(line)
        assert out["prefix_hit_rate"] > 0
        assert out["prefix_hit_tokens"] > 0
        assert out["tokens_per_sec"] > 0
        assert out["ttft_p50_s"] is not None
        assert out["ttft_p99_s"] >= out["ttft_p50_s"]
        assert out["decode_steps"] > 0
        # ISSUE 4 satellite (ROADMAP telemetry finding): warm-up now
        # covers EVERY decode-batch bucket, so the measured window of
        # the warm serving loop is compile-free — and main() gates on it
        assert out["jit_recompiles"] == 0
        assert out["failed_requests"] == 0

    def test_speculative_lane_gate(self, capsys):
        # ISSUE 6 CI satellite: the spec lane (tiny clone draft + the
        # target, CPU backend) must accept ~everything, beat the plain
        # engine's max_batch-tokens-per-step ceiling, and stay
        # compile-free in the measured window — main() gates on all
        # three
        sb = self._load()
        assert sb.main(["--draft", "--spec-k=2",
                        "--sharers=3", "--uniques=2"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        out = json.loads(line)
        assert out["speculative"] is True
        assert out["spec_proposed_tokens"] > 0
        assert out["spec_accept_rate"] >= 0.7      # clone draft
        assert out["spec_accepted_tokens"] <= out["spec_proposed_tokens"]
        assert out["tokens_per_step"] > out["max_batch"]
        assert out["spec_accept_len_mean"] is not None
        assert out["jit_recompiles"] == 0
        assert out["failed_requests"] == 0

    def test_scenario_matrix_lane_gate(self, capsys):
        # ISSUE 7 CI satellite: the heterogeneous-workload lane must
        # emit one JSON line per class plus a summary, with chat-class
        # TTFT under the long-prompt flood within 2x of its no-flood
        # baseline, the FIFO stall demonstrated, zero recompiles in
        # every measured window, the chunked-prefill program audited
        # clean, and batch-class preemption actually exercised
        sb = self._load()
        # flood == max_batch saturates every slot so interactive
        # admission must go through slot preemption (gated below)
        assert sb.main(["--scenario-matrix", "--flood=4", "--chat=4",
                        "--rag=2"]) == 0
        lines = [json.loads(x) for x in
                 capsys.readouterr().out.strip().splitlines()]
        per_class = {x["class"]: x for x in lines
                     if x.get("lane") == "scenario-matrix"}
        assert set(per_class) == {"interactive", "standard", "batch"}
        for c, row in per_class.items():
            assert row["admitted"] >= 1, c
            assert row["ttft_p50_s"] is not None, c
            assert row["ttft_p99_s"] >= row["ttft_p50_s"], c
            assert row["tpot_mean_s"] is not None, c
            assert row["queue_wait_mean_s"] is not None, c
        assert per_class["batch"]["prefill_chunks"] > \
            per_class["batch"]["requests"]     # long prompts chunked
        summary = next(x for x in lines
                       if x.get("lane") == "scenario-matrix-summary")
        assert summary["jit_recompiles"] == 0
        assert summary["audit_error_findings"] == 0
        assert summary["batch_preemptions"] >= 1
        assert summary["chat_ttft_p50_flood_chunked_s"] <= \
            2.0 * summary["chat_ttft_p50_no_flood_s"] or \
            summary["chat_ttft_mean_flood_chunked_s"] <= \
            2.0 * summary["chat_ttft_mean_no_flood_s"]
        # the stall the subsystem removes: same flood, scheduler off
        # -> chat at least 2x worse on p50 or mean
        assert summary["chat_ttft_p50_flood_fifo_s"] > \
            2.0 * summary["chat_ttft_p50_flood_chunked_s"] or \
            summary["chat_ttft_mean_flood_fifo_s"] > \
            2.0 * summary["chat_ttft_mean_flood_chunked_s"]
        # ISSUE 17 CI satellite: the mixed-batch dispatch pair — the
        # unified window is single-program (ragged-mode only, one
        # dispatch per iteration), the legacy baseline is the
        # multi-dispatch composition, and the collapse shows as
        # strictly fewer target-model dispatches on the SAME workload
        mixed = {x["lane"]: x for x in lines
                 if x.get("lane", "").startswith("mixed-batch-")}
        assert set(mixed) == {"mixed-batch-unified", "mixed-batch-legacy"}
        uni, leg = mixed["mixed-batch-unified"], mixed["mixed-batch-legacy"]
        assert uni["dispatches"]["ragged"] > 0
        assert all(uni["dispatches"][m] == 0
                   for m in ("prefill", "chunk", "decode", "verify"))
        assert leg["dispatches"]["ragged"] == 0
        assert leg["dispatches"]["decode"] > 0
        assert 0 < uni["dispatches_target_model"] \
            < leg["dispatches_target_model"]
        assert uni["unified_fallbacks"] == 0
        # same workload, same work: every request runs to budget, so
        # the token totals agree exactly (steps may batch differently
        # under thread timing)
        assert uni["generated_tokens"] == leg["generated_tokens"] > 0
        assert uni["steps"] > 0 and leg["steps"] > 0
        assert uni["tokens_per_s"] > 0 and leg["tokens_per_s"] > 0
        assert uni["jit_recompiles"] == leg["jit_recompiles"] == 0
        assert uni["audit_error_findings"] == 0
        assert summary["dispatches_unified"] == \
            uni["dispatches_target_model"]
        assert summary["unified_fallbacks"] == 0

    def test_fault_plan_lane_recovers(self, capsys):
        # ISSUE 4: --fault-plan injects failures into the measured
        # wave; the gate passes only if the blast radius stays inside
        # the plan and throughput survives
        sb = self._load()
        plan = json.dumps({"rules": [
            {"site": "prefill", "nth": 3},
            {"site": "decode_step", "nth": 5},
        ]})
        assert sb.main(["--sharers=4", "--uniques=2",
                        f"--fault-plan={plan}"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        out = json.loads(line)
        assert out["failed_requests"] == 1       # only the prefill poison
        assert out["quarantined_requests"] == 1
        assert out["decode_retries"] >= 1        # transient absorbed
        assert out["tokens_per_sec"] > 0
        assert out["fault_plan"] is not None

    def test_recovery_lane_emits_mttr(self, capsys):
        # ISSUE 8: a buffer_loss rule makes the chaos lane a RECOVERY
        # lane — the gate additionally requires survivor replay +
        # rebuild counts and an engine_recovery_seconds (MTTR) sample,
        # with zero failed requests (a transient loss costs nobody)
        sb = self._load()
        plan = json.dumps({"rules": [{"site": "buffer_loss",
                                      "nth": 12}]})
        assert sb.main(["--sharers=4", "--uniques=2",
                        f"--fault-plan={plan}"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        out = json.loads(line)
        assert out["survivor_replays"] >= 1
        assert out["engine_rebuilds"] >= 1
        assert out["recovery_events"] >= 1
        assert out["mttr_p50_s"] is not None
        assert out["failed_requests"] == 0
        assert out["tokens_per_sec"] > 0

    def test_recovery_lane_batched_replay_cuts_dispatches(self, capsys):
        # ISSUE 9 satellite (ROADMAP crash-consistency follow-up (c)):
        # batched survivor replay must reconstruct the same survivors
        # in FEWER compiled dispatches than the per-row path — the
        # deterministic half of the MTTR-drop claim (wall-clock p50 is
        # quoted in the JSON but not gated on shared CI hardware)
        sb = self._load()
        plan = json.dumps({"rules": [{"site": "buffer_loss",
                                      "nth": 12}]})
        argv = ["--sharers=4", "--uniques=2", f"--fault-plan={plan}"]
        # explicit opt-in: the engine's unset default resolves to
        # per-row on TPU (batched replay not yet hardware-verified
        # bit-exact there) and this gate tests the batched machinery
        assert sb.main(argv + ["--replay-batch"]) == 0
        batched = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert sb.main(argv + ["--no-replay-batch"]) == 0
        perrow = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert batched["replay_batch"] is True
        assert perrow["replay_batch"] is False
        assert batched["survivor_replays"] == perrow["survivor_replays"] \
            >= 2
        assert 0 < batched["replay_dispatches"] \
            < perrow["replay_dispatches"]

    def test_quant_lane_gate(self, capsys):
        # ISSUE 9 acceptance: the int8-KV + w8 lane must admit >= 1.8x
        # the baseline's concurrent sequences at EQUAL page-pool bytes,
        # match greedy outputs exactly on the logits-parity path, and
        # stay compile-free in both measured windows
        sb = self._load()
        assert sb.main(["--quant"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        out = json.loads(line)
        assert out["lane"] == "quant"
        assert out["capacity_ratio"] >= 1.8
        assert abs(out["pool_bytes_quant"] - out["pool_bytes_base"]) \
            <= out["pool_bytes_base"] * 0.01     # equal-byte pools
        assert out["greedy_exact"] is True
        assert out["parity_matches"] == out["parity_requests"]
        assert out["logits_max_abs_diff"] < 0.05
        assert out["jit_recompiles"] == 0
        # wall-clock throughput is gated by the lane only on TPU
        # (tps_floor 1.0 there, off on CPU where the ratio is noise-
        # dominated emulation); asserting a ratio here would gate a
        # timing number on shared CI hardware
        assert out["tokens_per_sec_quant"] > 0

    def test_journal_lane_overhead_gate(self, capsys):
        # ISSUE 13 acceptance: decode p50 with the write-ahead journal
        # on (interval_ms fsync) within 5% of journaling off — the WAL
        # is enqueue-only on the engine threads — with the measured
        # windows compile-free and journal_bytes/journal_fsync_p50
        # quoted in the JSON line
        sb = self._load()
        assert sb.main(["--journal"]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()
                 if ln.startswith("{")]
        off, on = lines[0], lines[-1]
        assert off["journal"] is False and on["journal"] is True
        assert on["journal_fsync"] == "interval_ms"
        assert on["journal_bytes"] > 0
        assert on["journal_records"] > 0
        assert on["journal_fsync_p50"] is not None
        assert on["decode_step_p50_s"] \
            <= off["decode_step_p50_s"] * 1.05
        assert off["jit_recompiles"] == 0
        assert on["jit_recompiles"] == 0

    def test_tp_lane_gate(self, capsys):
        # ISSUE 20 acceptance: the --tp lane runs the engine TP=2 on
        # the virtual CPU mesh — bit-exact greedy parity vs 1-chip,
        # compile-free measured window, per-chip KV pool bytes =
        # global / tp, every collective named+priced on the tensor
        # axis, and the int8 quantized collectives quoted at >= 3x
        # fewer bytes than f32 (exactly 8/n = 4x at n=2 on the ring
        # model).  tokens/sec/chip is QUOTED, never gated: TP=2 on
        # virtual CPU devices is the documented lose case.
        sb = self._load()
        assert sb.main(["--tp"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        out = json.loads(line)
        assert out["lane"] == "tp"
        assert out["tp"] == 2
        assert out["greedy_exact"] is True
        assert out["parity_matches"] == out["parity_requests"] >= 6
        assert out["jit_recompiles"] == 0
        assert out["kv_pool_bytes_per_chip"] * 2 == out["kv_pool_bytes"]
        assert out["collectives"] > 0
        assert out["collective_bytes"] > 0
        assert out["mesh_axes"] == {"tensor": 2}
        assert out["int8_collective_ratio"] >= 3.0
        assert out["tokens_per_sec_per_chip"] > 0
        assert out["peak_hbm_bytes_per_chip"] \
            < out["peak_hbm_bytes_base"]

    def test_fleet_lane_gate(self, capsys):
        # ISSUE 14 acceptance: the --fleet lane runs a 2-replica
        # supervised fleet behind the router with a replica kill
        # mid-window — jit_recompiles == 0 in ALL measured windows,
        # per-replica decode p50 within 5% of the router-free baseline
        # at the same co-location, router + probes ~free with one
        # replica, a failover observed, zero failed requests, and the
        # failure-window TTFT/failover economics quoted in the line
        sb = self._load()
        assert sb.main(["--fleet=2"]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()
                 if ln.startswith("{")]
        out = lines[-1]
        assert out["fleet"] == 2
        assert out["jit_recompiles"] == 0
        assert out["failovers"] >= 1
        assert out["failed_requests"] == 0
        assert out["fleet_tokens_per_sec"] > 0
        assert out["failure_window"]["ttft_p50_s"] is not None
        assert out["failure_window"]["ttft_p99_s"] is not None
        assert out["decode_step_p50_s"] \
            <= out["baseline_n_decode_step_p50_s"] * 1.05
        assert out["fleet1_decode_step_p50_s"] \
            <= out["baseline_decode_step_p50_s"] * 1.05

    def test_overload_lane_gate(self, capsys):
        # ISSUE 19 acceptance: under a 3x interactive burst on top of a
        # saturating batch flood, the SLO-aware controlled engine keeps
        # interactive TTFT attainment >= 0.95 while shedding batch with
        # truthful Retry-After hints and pausing batch decoders; the
        # budget-free baseline breaches; both windows compile-free
        sb = self._load()
        assert sb.main(["--overload"]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()
                 if ln.startswith("{")]
        out = next(ln for ln in lines
                   if ln.get("lane") == "overload"
                   and ln.get("class") is None)
        assert out["controlled_attainment"] >= 0.95
        assert out["baseline_attainment"] < 0.95
        assert out["baseline_attainment"] < out["controlled_attainment"]
        assert out["decode_preemptions"] >= 1
        assert out["brownout_transitions"] >= 1
        assert out["retry_after_hints"] \
            and all(1 <= h <= 30 for h in out["retry_after_hints"])
        assert out["jit_recompiles"] == 0
        batch = next(ln for ln in lines
                     if ln.get("lane") == "overload"
                     and ln.get("class") == "batch")
        assert batch["sheds"] >= 1
        assert batch["deadline_s"] == 0.05

    def test_overload_fleet_lane_gate(self, capsys):
        # ISSUE 19 acceptance (elastic half): a sustained flood drives
        # the autoscaler to spawn a second replica (scale-up observed,
        # fleet_scale_events_total fires), the measured window on the
        # scaled fleet is compile-free, load subsiding drains the
        # newcomer back down cleanly, and zero requests fail
        sb = self._load()
        assert sb.main(["--overload-fleet"]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()
                 if ln.startswith("{")]
        out = lines[-1]
        assert out["scale_ups"] >= 1
        assert out["scale_downs"] >= 1
        assert out["routable_peak"] == 2
        assert out["routable_end"] == 1
        assert out["failed_requests"] == 0
        assert out["jit_recompiles"] == 0


class TestTrainBench:
    """ISSUE 5 CI satellite: the training hot-path lane must run a tiny
    config, emit one parseable JSON line with every acceptance gate
    green — fused-vs-single-step loss parity, certified fused program
    (audit), compile-free measured windows, TPL005-clean fit loop."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "train_bench", os.path.join(REPO, "tools", "train_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_hist_quantile(self):
        tb = self._load()
        b = {"0.1": 4, "0.5": 9, "1.0": 10, "+Inf": 10}
        assert tb.hist_quantile(b, 0.50) == 0.5
        assert tb.hist_quantile({"+Inf": 0}, 0.5) is None

    def test_smoke_gate_passes(self, capsys):
        tb = self._load()
        assert tb.main([]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        out = json.loads(line)
        # acceptance criteria, quoted from the one JSON line
        assert out["parity_ok"] and out["parity_max_abs_diff"] < 5e-4
        assert out["audit_error_findings"] == 0
        assert out["jit_recompiles"] == 0
        assert out["tpl005_hapi_findings"] == 0
        assert out["fused_steps"] == out["k"] * 4
        assert out["fused_steps_per_sec"] > 0
        assert out["single_step_p50_s"] is not None
        assert out["fused_step_p50_s"] is not None
        assert out["train_tokens"] == out["fused_steps"] * \
            out["batch"] * out["seq"]
        assert out["input_waits"] > 0        # device prefetch measured


class TestChaosSmoke:
    """ISSUE 4 CI satellite: the resilience counters the README
    documents must exist in monitor.snapshot() after a chaos run."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chaos_smoke", os.path.join(REPO, "tools", "chaos_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_gate_passes(self):
        # the subprocess hard-kill lane runs as its own gate below, so
        # each test stays within its own time envelope
        assert self._load().main(["--skip-hard-kill"]) == 0

    def test_hard_kill_gate(self):
        # ISSUE 13 acceptance: SIGKILL a subprocess server mid-decode
        # with 4 in-flight requests (greedy + sampled + prefix-hit +
        # draft-opted); the relaunch over the same journal completes
        # all of them bit-identically to an uninterrupted run and
        # /result/<id> re-attaches for every journaled id
        assert self._load().main(["--hard-kill-only"]) == 0

    def test_fleet_kill_gate(self):
        # ISSUE 14 acceptance: SIGKILL one of TWO subprocess replicas
        # mid-decode behind the supervisor + router — every in-flight
        # stream completes bit-exactly on the survivor via
        # journal-backed migration (zero failed requests),
        # fleet_failovers_total / fleet_migrated_requests_total fire,
        # every fleet_*/router_* series exists, and /result/<id>
        # re-attaches through the router for every journaled id
        assert self._load().main(["--fleet-only"]) == 0

    def test_overload_kill_gate(self):
        # ISSUE 19 acceptance: overload AND a replica kill composed —
        # two in-process replicas with SLO budgets + brownout take a
        # decode-delayed batch flood plus interactive traffic, one is
        # hard-killed mid-flood; every interactive request completes,
        # batch arrivals shed with sched_shed_on_arrival_total
        # ticking, failover fires, and every OVERLOAD_SERIES metric
        # (shed counter, brownout gauge, decode preemptions, fleet
        # scale events) exists in monitor.snapshot()
        assert self._load().main(["--overload-only"]) == 0


class TestTraceCapture:
    """ISSUE 10 tentpole gate: the self-contained trace-capture demo —
    tiny chunked engine server, capture window over the HTTP surface,
    schema-validated chrome-trace JSON with engine-step + request
    tracks + flow events."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_capture", os.path.join(REPO, "tools",
                                          "trace_capture.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_demo_lane(self, tmp_path, capsys):
        tc = self._load()
        out = str(tmp_path / "trace.json")
        assert tc.main(["--demo", f"--out={out}"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        summary = json.loads(line)
        assert summary["schema_problems"] == []
        assert summary["engine_steps"] > 0
        assert summary["request_tracks"] >= 2
        assert summary["flow_events"] > 0
        # the pinned chunked request's raw timeline rides along
        kinds = [e["kind"]
                 for e in summary["request_timeline"]["events"]]
        assert kinds.count("prefill_chunk") >= 2
        assert kinds[-1] == "retire"
        with open(out) as f:
            payload = json.load(f)
        from paddle_tpu.monitor import validate_chrome_trace
        assert validate_chrome_trace(payload) == []


class TestSpmdAuditGate:
    """ISSUE 11 CI satellite: the SPMD-auditor CLI's demo lane —
    hand-checkable collective pricing on the host's mesh (no TPU;
    a CPU mesh of 1 prices ICI to zero, which is the correct verdict)
    — runs green inside a 10 s budget."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "spmd_audit", os.path.join(REPO, "tools", "spmd_audit.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_demo_gate_within_budget(self, capsys):
        import time
        sa = self._load()
        t0 = time.monotonic()
        rc = sa.main([])
        elapsed = time.monotonic() - t0
        out = capsys.readouterr().out
        assert rc == 0, out
        doc = json.loads(out.strip().splitlines()[-1])
        assert doc["ok"]
        # both demo programs priced with the ring formulas
        (c,) = doc["dp_allreduce"]["collectives"]
        n = c["group_size"]
        assert c["kind"] == "all_reduce"
        assert c["ici_bytes"] == pytest.approx(
            2 * (n - 1) / n * c["payload_bytes"])
        assert doc["tp_matmul"]["peak_hbm_bytes"] > 0
        assert elapsed < 10, f"spmd gate took {elapsed:.1f}s (budget 10s)"

    def test_train_lane_names_dp_collectives(self, capsys):
        # dp>1 on the virtual CPU mesh: the GSPMD tier must name the
        # gradient-sync all-reduces with non-zero priced bytes
        sa = self._load()
        rc = sa.main(["--train"])
        out = capsys.readouterr().out
        assert rc == 0, out
        doc = json.loads(out.strip().splitlines()[-1])
        assert doc["ok"]
        assert any(c["kind"] == "all_reduce" and c["ici_bytes"] > 0
                   for c in doc["collectives"])


class TestTpuLintGate:
    """ISSUE 3 CI satellite: the anti-pattern linter runs clean against
    its checked-in baseline, inside the tier-1 CPU lane's time budget."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "tpu_lint", os.path.join(REPO, "tools", "tpu_lint.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_gate_runs_clean_within_budget(self, capsys):
        import time
        tl = self._load()
        t0 = time.monotonic()
        rc = tl.main(["--baseline",
                      os.path.join(REPO, "tools",
                                   "tpu_lint_baseline.json")])
        elapsed = time.monotonic() - t0
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "0 new" in out
        assert elapsed < 10, f"lint gate took {elapsed:.1f}s (budget 10s)"

    def test_gate_fails_on_new_finding(self, tmp_path, monkeypatch):
        # plant a fresh anti-pattern in a copied tree: the ratchet must
        # reject it against the same baseline
        tl = self._load()
        bad = tmp_path / "pkg" / "planted.py"
        bad.parent.mkdir()
        bad.write_text("def f(q):\n    q.pop(0)\n")
        rc = tl.main(["--baseline",
                      os.path.join(REPO, "tools",
                                   "tpu_lint_baseline.json"),
                      f"--root={tmp_path / 'pkg'}"])
        assert rc == 1

    def test_update_baseline_roundtrip(self, tmp_path):
        tl = self._load()
        bad = tmp_path / "pkg" / "planted.py"
        bad.parent.mkdir()
        bad.write_text("def f(q):\n    q.pop(0)\n")
        base = tmp_path / "base.json"
        assert tl.main([f"--root={tmp_path / 'pkg'}",
                        "--update-baseline",
                        f"--baseline={base}"]) == 0
        doc = json.load(open(base))
        assert len(doc["findings"]) == 1
        # a placeholder justification is NOT an accepted finding: the
        # gate refuses it until someone writes the reason down
        assert tl.main([f"--root={tmp_path / 'pkg'}",
                        f"--baseline={base}"]) == 1
        doc["findings"][0]["justification"] = "test fixture queue"
        base.write_text(json.dumps(doc))
        assert tl.main([f"--root={tmp_path / 'pkg'}",
                        f"--baseline={base}"]) == 0
        # --update-baseline again must PRESERVE the justification
        assert tl.main([f"--root={tmp_path / 'pkg'}",
                        "--update-baseline",
                        f"--baseline={base}"]) == 0
        doc2 = json.load(open(base))
        assert doc2["findings"][0]["justification"] == "test fixture queue"

    def test_space_separated_root_is_not_silently_ignored(self, tmp_path):
        # argparse must reject a bad invocation instead of linting the
        # default tree and reporting a misleading "clean"
        tl = self._load()
        with pytest.raises(SystemExit):
            tl.main(["--root", str(tmp_path), "--unknown-flag"])
        # the supported space-separated form works
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "ok.py").write_text("x = 1\n")
        assert tl.main(["--root", str(pkg),
                        "--baseline",
                        os.path.join(REPO, "tools",
                                     "tpu_lint_baseline.json")]) == 0


class TestCostModelFacade:
    def test_alias(self):
        import paddle_tpu as paddle
        spec = paddle.cost_model.ModelSpec(
            hidden_size=512, num_layers=4, num_heads=8, vocab_size=1000,
            seq_len=128)
        cm = paddle.cost_model.CostModel(spec)
        cfg = paddle.cost_model.ParallelConfig(global_batch_size=8)
        assert cm.step_time(cfg) > 0
        assert cm.memory_bytes(cfg) > 0


class TestParityDoc:
    def test_all_inventory_rows_present(self):
        with open(os.path.join(REPO, "PARITY.md")) as f:
            text = f.read()
        # every SURVEY §2 row number 1..90 is accounted for
        import re
        covered = set()
        for m in re.finditer(r"^\| ([0-9]+)(?:–([0-9]+)|-([0-9]+))? \|",
                             text, re.M):
            lo = int(m.group(1))
            hi = int(m.group(2) or m.group(3) or lo)
            covered.update(range(lo, hi + 1))
        missing = set(range(1, 91)) - covered
        assert not missing, f"PARITY.md missing rows: {sorted(missing)}"


class TestLossCurveHarness:
    def test_curve_determinism_and_reference_format(self):
        """tools/loss_curve.py (VERDICT r3 item 10): same seed -> identical
        curve; the committed reference has the expected schema."""
        import json
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "loss_curve", os.path.join(REPO, "tools", "loss_curve.py"))
        lc = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lc)

        a = lc.run_curve(steps=5)
        b = lc.run_curve(steps=5)
        assert a["losses"] == b["losses"]          # fixed seed -> identical

        ref = json.load(open(os.path.join(REPO, "tools",
                                          "loss_curve_ref.json")))
        for key in ("steps", "seed", "dtype", "losses", "jax"):
            assert key in ref, key
        assert len(ref["losses"]) == ref["steps"] == 200
        assert ref["losses"][-1] < ref["losses"][0]   # the curve learns


class TestExternalOracle:
    def test_framework_curve_matches_plain_jax_oracle(self):
        """VERDICT r4 item 6: the loss curve must match an EXTERNAL
        plain-jax reimplementation (tools/llama_oracle.py, zero
        paddle_tpu imports) on identical weights + data — catches the
        framework being consistently wrong, which the committed-curve
        drift gate cannot."""
        import importlib.util
        tools = os.path.join(REPO, "tools")
        sys.path.insert(0, tools)
        try:
            spec = importlib.util.spec_from_file_location(
                "loss_curve", os.path.join(tools, "loss_curve.py"))
            lc = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(lc)
            assert lc.external_check(steps=10) == 0
        finally:
            sys.path.remove(tools)

    def test_oracle_is_paddle_free(self):
        import ast
        src = open(os.path.join(REPO, "tools", "llama_oracle.py")).read()
        mods = set()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.Import):
                mods.update(a.name.split(".")[0] for a in node.names)
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods.add(node.module.split(".")[0])
        assert mods <= {"jax", "numpy"}, (
            f"oracle must stay framework-free, imports: {mods}")
