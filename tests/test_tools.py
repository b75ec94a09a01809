"""Tooling tests: op-benchmark gate logic, the small tools' gates, cost_model
facade + PARITY doc.  (serve_bench: tests/test_bench_serve.py; chaos_smoke:
tests/test_chaos_smoke_gates.py.)"""
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
    — runs green."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "spmd_audit", os.path.join(REPO, "tools", "spmd_audit.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_demo_gate(self, capsys):
        sa = self._load()
        rc = sa.main([])
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
    its checked-in baseline."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "tpu_lint", os.path.join(REPO, "tools", "tpu_lint.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_gate_runs_clean(self, capsys):
        tl = self._load()
        rc = tl.main(["--baseline",
                      os.path.join(REPO, "tools",
                                   "tpu_lint_baseline.json")])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "0 new" in out

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


class TestScopeOpTable:
    """tools/scope_op_table.py: a device plane's operations by (scope,
    operation), own times, a step."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "scope_op_table", os.path.join(REPO, "tools",
                                           "scope_op_table.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_scope_paths_and_the_table(self, tmp_path, capsys):
        tool = self._load()
        bench = os.path.join(REPO, "benchmark")
        sys.path.insert(0, bench)
        try:
            import xplane
            from readers.xplane_scope_share import own_times
        finally:
            sys.path.remove(bench)
        assert tool.full_scope(
            "jit(pure_step)/transpose(jvp(train/model))/kda/gates/mul") \
            == "train/model/kda/gates/mul"
        assert tool.full_scope("jit(pure_step)/dot_general") == ""
        # a recomputed mixer: backward and recomputed forward under the
        # forward's path
        for inner in ("checkpoint/", "checkpoint/rematted_computation/"):
            assert tool.full_scope(
                "jit(pure_step)/transpose(jvp(train/model))/kda/"
                f"jvp(train/model)/kda/{inner}conv/mul") \
                == "train/model/kda/conv/mul"
        events = [
            # two steps; a while over its body, a fusion of each scope
            ("%while.1 = (f32[8]) while(%t)", 0, 100),
            ("%fusion.3 = f32[8,4]{1,0} fusion(%a)", 10, 30),
            ("%copy.7 = f32[8,4]{1,0} copy(%b)", 120, 40),
            ("%fusion.4 = f32[8,4]{1,0} fusion(%a)", 200, 50),
        ]
        # two whole steps and half of a third: 2.5 steps
        modules = [("jit_pure_step(123)", 0, 100), ("jit_other(1)", 0, 1),
                   ("jit_pure_step(123)", 100, 100),
                   ("jit_pure_step(123)", 200, 50)]
        scopes = {"fusion.3": "train/model/kda/gates/mul/more",
                  "fusion.4": "train/model/kda/gates/add",
                  "while.1": "train/model/moe/experts/while"}
        table = tool.scope_table(events, modules, scopes, own_times,
                                 xplane.short_name)
        assert table["steps"] == 2.5
        rows = {(s, n): (ms, c) for s, n, ms, c in table["rows"]}
        # ns -> ms a step; the two fusions fall together by short name,
        # the while keeps its own 70 ns, the copy has no scope
        assert rows[("train/model/kda/gates", "fusion f32[8,4]")] == (
            pytest.approx(80 / 1e6 / 2.5), pytest.approx(0.8))
        assert rows[("train/model/moe/experts", "while f32[8]")][0] == \
            pytest.approx(70 / 1e6 / 2.5)
        assert rows[("", "copy f32[8,4]")] == (pytest.approx(40 / 1e6 / 2.5),
                                               pytest.approx(0.4))
        assert table["rows"][0][1] == "fusion f32[8,4]"      # longest first
        assert tool.by_scope(table, "train/model/kda") == {
            "train/model/kda/gates": pytest.approx(80 / 1e6 / 2.5)}
        path = tmp_path / "t.json"
        path.write_text(json.dumps(table))
        assert tool.main(["--table", str(path), str(path)]) == 0
        assert "train/model/kda/gates" in capsys.readouterr().out


class TestPagedRaggedMicro:
    """``tools/paged_ragged_micro.py``: the step's ragged call on the
    packed tokens at the shapes ISSUE 50 added (its timed runs need the
    chip; ``--rehearse`` drives the kernels through the interpreter at a
    span of 32) and the table that holds one tree to another."""

    @pytest.fixture(scope="class")
    def tool(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import paged_ragged_micro
        finally:
            sys.path.pop(0)
        return paged_ragged_micro

    @pytest.fixture(scope="class")
    def rehearsed(self, tool, tmp_path_factory):
        path = tmp_path_factory.mktemp("micro") / "change.json"
        assert tool.main(["--rehearse", "--out", str(path), "--shapes",
                          "mimo-full", "mimo-sliding", "zaya"]) == 0
        return str(path), json.loads(path.read_text())

    @pytest.mark.parametrize("shape,rows,tokens,padded", [
        ("zaya", 64, 32 + 63, 128), ("mimo-full", 32, 2 * 32 + 30, 96),
        ("mimo-sliding", 32, 2 * 32 + 30, 96)])
    def test_rehearsal_of_the_new_shapes(self, tool, rehearsed, shape, rows,
                                         tokens, padded):
        """Two chunk rows and 30 one-token rows over MiMo's 4 and 8 KV
        heads (K rows of 384, a window of 128 and sinks), a chunk row and
        63 one-token rows over ZAYA's two-head pool: every live query has
        an output, every position no row owns is zero."""
        case, = (c for c in rehearsed[1]["cases"] if c["shape"] == shape)
        assert tool.TRAFFIC[shape][0] == rows
        assert (case["tokens"], case["tokens_padded"]) == (tokens, padded)
        assert case["dead_nonzero"] == 0 and case["live_zero"] == 0
        assert not case["nan"] and len(case["live_sha"]) == 16

    def test_table_holds_one_tree_to_the_other(self, tool, rehearsed,
                                               tmp_path, capsys):
        path, data = rehearsed
        assert tool.table(path, path) is True
        out = capsys.readouterr().out
        assert out.count("bit for bit") == 3 and "DIFFER" not in out
        data["cases"][1]["live_sha"] = "0" * 16
        other = tmp_path / "parent.json"
        other.write_text(json.dumps(data))
        assert tool.table(str(other), path) is False
        assert "DIFFER" in capsys.readouterr().out
        data["cases"][1]["dead_nonzero"] = 3
        other.write_text(json.dumps(data))
        assert tool.table(str(other), str(other)) is False

    def test_table_holds_another_block_to_the_oracle(self, tool, rehearsed,
                                                     tmp_path, capsys):
        """Two trees that cut a case's walk in other blocks cannot share
        a digest: the change has to stand as close to the XLA oracle as
        the parent (a tenth of room), the one-query call bit for bit."""
        path, data = rehearsed
        for case in data["cases"]:
            # a window of 128 reaches 128 + 16 - 1 + 16 columns of a tile
            assert case["block_tokens"] == (
                256 if case["shape"] == "mimo-sliding" else 512)
            assert 0 < case["oracle_rms_gap"] < case["oracle_max_gap"] < 0.02
        parent = json.loads(json.dumps(data))
        parent["cases"][1].update(block_tokens=128, live_sha="0" * 16)
        other = tmp_path / "parent.json"
        other.write_text(json.dumps(parent))
        assert tool.table(str(other), path) is True
        out = capsys.readouterr().out
        assert out.count("bit for bit") == 2 and "DIFFER" not in out
        assert "another block: the oracle's as the parent's" in out
        parent["cases"][1]["oracle_rms_gap"] /= 2
        other.write_text(json.dumps(parent))
        assert tool.table(str(other), path) is False
        assert "FURTHER FROM THE ORACLE" in capsys.readouterr().out

    def test_an_older_tree_is_handed_the_rectangle(self, tool):
        """``--repo`` on a tree whose kernel takes no ``row_off``: the
        tool gathers the rectangle and packs the output back, as that
        tree's step did."""
        import types

        import jax.numpy as jnp
        import numpy as np
        seen = {}

        def old_kernel(q, kp, vp, lens, q_lens, tabs, interpret=False,
                       window=None, sinks=None):
            seen["q"] = q.shape
            return q[..., :3]

        pa = types.SimpleNamespace(
            paged_attention_ragged=old_kernel, paged_attention=None,
            packed_queries=lambda q, kp, vp: q)
        ragged, _ = tool.step_calls(pa, None, 4, True)
        q = np.arange(10 * 2 * 5, dtype=np.float32).reshape(10, 2, 5)
        off = np.asarray([0, 4, 5], np.int32)
        out = ragged(jnp.asarray(q), None, None, jnp.asarray(off), None,
                     None, None, None)
        assert seen["q"] == (3, 4, 2, 5) and out.shape == (10, 2, 3)
        np.testing.assert_array_equal(np.asarray(out[:6]), q[:6, :, :3])


class TestFlashAttnMicro:
    """``tools/flash_attn_micro.py``'s reductions (its runs need the chip;
    ``--rehearse`` drives the kernels through the interpreter)."""

    @pytest.fixture()
    def tool(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import flash_attn_micro
        finally:
            sys.path.pop(0)
        return flash_attn_micro

    @staticmethod
    def _file(tmp_path, name, rel_rms, fwd_ms):
        gap = {"max_abs": 0.01, "rel_rms": rel_rms, "nan": False}
        data = {
            "check": [{"shape": "mistral-4k", "tiles_a_head": 30.0,
                       **{p: gap for p in ("out", "dq", "dk", "dv")}}],
            "time": [{"shape": "mistral-4k", "fwd_ms": fwd_ms,
                      "bwd_dkv_ms": 2.0, "bwd_dq_ms": None}],
            "sweep": [{"shape": "mistral-4k", "block_q": bq, "block_kv": bkv,
                       "fwd_ms": 1.0 + (bq != 1024) + (bkv != 1024),
                       "bwd_dkv_ms": 2.0, "bwd_dq_ms": 1.5}
                      for bq in (512, 1024) for bkv in (512, 1024)]
            + [{"shape": "mistral-4k", "block_q": 256, "block_kv": 2048,
                "refused": "vmem"}]}
        path = tmp_path / name
        path.write_text(json.dumps(data))
        return str(path)

    @pytest.mark.parametrize("rel_rms,sound", [(2.0e-3, True),
                                               (2.5e-3, False)])
    def test_table_holds_the_change_to_the_parents_gaps(
            self, tool, tmp_path, capsys, rel_rms, sound):
        parent = self._file(tmp_path, "p.json", 2.0e-3, 3.0)
        change = self._file(tmp_path, "c.json", rel_rms, 1.5)
        assert tool.table(parent, change) is sound
        out = capsys.readouterr().out
        assert "| mistral-4k | fwd | 3.000 | 1.500 | 0.50 |" in out
        assert "| mistral-4k | bwd_dq | — | — | — |" in out
        assert ("yes" if sound else "NO") in out
        assert "fwd: best 1024 x 1024 at 1.000" in out

    def test_device_ms_means_a_group_of_calls(self, tool, monkeypatch):
        from benchmark import xplane
        events = {tool.KERNELS["fwd"]: [1.0, 3.0, 5.0, 7.0],
                  tool.KERNELS["bwd_dkv"]: [2.0, 2.0, 4.0],   # one is lost
                  tool.KERNELS["bwd_dq"]: []}
        monkeypatch.setattr(xplane, "durations_ms",
                            lambda trace, pattern, line: events[pattern])
        assert tool.device_ms(None, 2, 2) == {
            "fwd": [2.0, 6.0], "bwd_dkv": [None, None],
            "bwd_dq": [None, None]}
