"""tools/serve_bench.py: every lane's gate, run in-process at its tiny
size.  The lanes' COUNTS are asserted (dispatches by mode, recompiles in
the measured window, sheds, failovers, failed requests, bytes); their
timings are printed by the tool and decide nothing here."""
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
        # emit one JSON line per class plus a summary, with every chat
        # request served ahead of the long-prompt flood, the FIFO
        # stall demonstrated, zero recompiles in
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
        # chunked admits chat before FIFO does — as an ORDER of the
        # engine's own events, not a ratio of CPU times: under the
        # flood every chat request has its first token before the
        # first flood request is done ...
        assert summary["chat_ahead_of_flood_chunked"] \
            == summary["chat_requests"] == 4
        # ... and with the scheduler off (same flood) none has: the
        # stall the subsystem removes
        assert summary["chat_ahead_of_flood_fifo"] == 0
        # the timings themselves are printed, and decide nothing
        assert summary["chat_ttft_p50_flood_chunked_s"] is not None
        assert summary["chat_ttft_p50_flood_fifo_s"] is not None
        # ISSUE 17 CI satellite: the mixed-batch lane — the chunked
        # window is single-program (ragged-mode only, one dispatch per
        # iteration, no step down the failure ladder)
        mixed = {x["lane"]: x for x in lines
                 if x.get("lane", "").startswith("mixed-batch-")}
        assert set(mixed) == {"mixed-batch-unified"}
        uni = mixed["mixed-batch-unified"]
        assert uni["dispatches"]["ragged"] > 0
        assert all(n == 0 for m, n in uni["dispatches"].items()
                   if m != "ragged")
        assert uni["dispatches_target_model"] \
            == uni["dispatches"]["ragged"]
        assert uni["unified_fallbacks"] == 0
        # every request runs to budget: 4 floods and 2 rags of 6 tokens,
        # 4 chats of 8
        assert uni["generated_tokens"] == 4 * 6 + 2 * 6 + 4 * 8
        assert uni["steps"] > 0
        assert uni["tokens_per_s"] > 0
        assert uni["jit_recompiles"] == 0
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
        # ISSUE 13 acceptance: the write-ahead journal on (interval_ms
        # fsync) writes its records from the measured window and
        # changes no token count, with the measured windows
        # compile-free and decode p50 (quoted beside journaling off's,
        # never compared: the CPU is shared) and
        # journal_bytes/journal_fsync_p50 in the JSON line
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
        assert on["generated_tokens"] == off["generated_tokens"] > 0
        assert on["decode_step_p50_s"] is not None
        assert on["baseline_decode_step_p50_s"] \
            == off["decode_step_p50_s"]
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
        # a failover observed, zero failed requests, and per-replica
        # decode p50 beside the router-free baseline's plus the
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
        for key in ("decode_step_p50_s", "baseline_n_decode_step_p50_s",
                    "fleet1_decode_step_p50_s",
                    "baseline_decode_step_p50_s"):
            assert out[key] is not None, key      # quoted, not compared

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
