"""Request-level tracing + engine step timeline + request-id
continuity (ISSUE 10).

Covers: chrome-trace export schema (required keys, monotonic ts,
matched B/E pairs), exact per-request event sequences for chunked /
preempted / replayed requests, the engine-step ring, the stable
request-id surface (result cache, snapshot/restore carry), and the
tracing-off fast path.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.testing import faults


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    return LlamaForCausalLM(cfg)


@pytest.fixture()
def capture():
    monitor.start_capture()
    yield monitor.get_tracer()
    monitor.stop_capture()


def _kinds(request_id):
    tl = monitor.request_timeline(request_id)
    assert tl is not None, f"no timeline for {request_id}"
    return [e["kind"] for e in tl["events"]]


class TestChromeTraceExport:
    def test_export_validates_and_has_tracks(self, model, capture):
        with ContinuousBatchingEngine(model, total_pages=32, page_size=8,
                                      max_batch=2,
                                      prefill_chunk_tokens=4) as eng:
            eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=2,
                       request_id="exp-1").result(timeout=300)
        monitor.stop_capture()
        payload = monitor.export_chrome_trace()
        assert monitor.validate_chrome_trace(payload) == []
        ev = payload["traceEvents"]
        # engine-step track: X events on pid 1 (decode + prefill_chunk)
        step_names = {e["name"] for e in ev
                      if e.get("pid") == 1 and e["ph"] == "X"}
        assert {"decode", "prefill_chunk"} <= step_names
        # per-request track: matched B/E plus instant events
        assert any(e["ph"] == "B" and e.get("pid") == 2 for e in ev)
        assert any(e["ph"] == "E" and e.get("pid") == 2 for e in ev)
        # flow events bind request lifecycle to the step track
        assert any(e["ph"] == "s" for e in ev)
        assert any(e["ph"] == "f" for e in ev)
        # monotonic ts is part of the schema check, but lock it visibly
        ts = [e["ts"] for e in ev]
        assert ts == sorted(ts)

    def test_export_writes_loadable_json(self, model, capture, tmp_path):
        import json
        with ContinuousBatchingEngine(model, total_pages=32,
                                      page_size=8) as eng:
            eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                       request_id="exp-2").result(timeout=300)
        monitor.stop_capture()
        path = tmp_path / "trace.json"
        monitor.export_chrome_trace(str(path))
        loaded = json.loads(path.read_text())
        assert loaded["traceEvents"]
        assert monitor.validate_chrome_trace(loaded) == []

    def test_validator_rejects_broken_traces(self):
        assert monitor.validate_chrome_trace({"nope": 1})
        bad_order = {"traceEvents": [
            {"name": "a", "ph": "i", "s": "t", "ts": 2.0, "pid": 1,
             "tid": 1},
            {"name": "b", "ph": "i", "s": "t", "ts": 1.0, "pid": 1,
             "tid": 1}]}
        assert any("non-decreasing" in p
                   for p in monitor.validate_chrome_trace(bad_order))
        unmatched = {"traceEvents": [
            {"name": "a", "ph": "B", "ts": 1.0, "pid": 1, "tid": 1}]}
        assert any("unclosed" in p
                   for p in monitor.validate_chrome_trace(unmatched))
        orphan_end = {"traceEvents": [
            {"name": "a", "ph": "E", "ts": 1.0, "pid": 1, "tid": 1}]}
        assert any("no open B" in p
                   for p in monitor.validate_chrome_trace(orphan_end))
        missing_keys = {"traceEvents": [{"ph": "X", "ts": 1.0}]}
        assert monitor.validate_chrome_trace(missing_keys)


class TestRequestTimelines:
    def test_chunked_request_exact_sequence(self, model, capture):
        # 9-token prompt through 4-token chunks: 3 chunk dispatches,
        # then exactly max_new_tokens decode participations
        with ContinuousBatchingEngine(model, total_pages=32, page_size=8,
                                      max_batch=2,
                                      prefill_chunk_tokens=4) as eng:
            eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=3,
                       request_id="chunked").result(timeout=300)
        assert _kinds("chunked") == [
            "enqueue", "admitted", "prefill_chunk", "prefill_chunk",
            "prefill_chunk", "first_token", "decode_step", "decode_step",
            "decode_step", "retire"]
        tl = monitor.request_timeline("chunked")
        chunks = [e for e in tl["events"] if e["kind"] == "prefill_chunk"]
        assert [(c["pos"], c["tokens"]) for c in chunks] == [
            (0, 4), (4, 4), (8, 1)]
        retire = tl["events"][-1]
        assert retire["ok"] is True and retire["generated"] == 3

    def test_preempted_request_records_pause_and_resume(self, model,
                                                        capture):
        # chaos_smoke's preemption scenario: a chunk-delayed batch-class
        # prefill is paused for an interactive request, then resumes
        plan = faults.FaultPlan([
            {"site": "prefill_chunk", "seq_id": 0, "kind": "delay",
             "delay_s": 0.05}])
        with faults.installed(plan):
            with ContinuousBatchingEngine(model, total_pages=64,
                                          page_size=8, max_batch=1,
                                          prefill_chunk_tokens=4) as eng:
                rb = eng.submit(np.arange(16, dtype=np.int32),
                                max_new_tokens=2, priority="batch",
                                request_id="victim")
                t0 = time.monotonic()
                while rb.prefill_pos == 0 \
                        and time.monotonic() - t0 < 120:
                    time.sleep(0.005)
                ri = eng.submit(np.arange(4, dtype=np.int32),
                                max_new_tokens=2, priority="interactive",
                                request_id="urgent")
                ri.result(timeout=300)
                rb.result(timeout=300)
        kinds = _kinds("victim")
        assert "preempt" in kinds and "resume" in kinds
        assert kinds.index("preempt") < kinds.index("resume")
        # chunking progressed on both sides of the pause
        assert "prefill_chunk" in kinds[:kinds.index("preempt")]
        assert "prefill_chunk" in kinds[kinds.index("resume"):]
        assert kinds[-1] == "retire"
        assert _kinds("urgent")[-1] == "retire"

    def test_replayed_request_records_replay(self, model, capture):
        # a REAL donated-buffer loss mid-decode: survivors' KV is
        # replayed — the event lands on each survivor's timeline
        plan = faults.FaultPlan([{"site": "buffer_loss", "nth": 6}])
        with faults.installed(plan):
            with ContinuousBatchingEngine(model, total_pages=64,
                                          page_size=8,
                                          max_batch=4) as eng:
                reqs = [eng.submit(np.arange(5, dtype=np.int32),
                                   max_new_tokens=6,
                                   request_id=f"loss-{i}")
                        for i in range(2)]
                for r in reqs:
                    r.result(timeout=300)
        assert any(s["fires"] for s in plan.snapshot())
        for i in range(2):
            kinds = _kinds(f"loss-{i}")
            assert "replay" in kinds, kinds
            assert kinds[-1] == "retire"
        steps = monitor.get_tracer().step_records()
        assert any(s["kind"] == "recovery" for s in steps)

    def test_step_ring_records_batch_composition(self, model, capture):
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=4) as eng:
            reqs = [eng.submit(np.arange(4, dtype=np.int32),
                               max_new_tokens=3,
                               priority=("interactive" if i % 2 == 0
                                         else "batch"))
                    for i in range(2)]
            for r in reqs:
                r.result(timeout=300)
        steps = [s for s in monitor.get_tracer().step_records()
                 if s["kind"] == "decode"]
        assert steps
        full = max(steps, key=lambda s: s["batch"])
        assert full["batch"] == 2
        assert full["classes"] == {"interactive": 1, "batch": 1}
        assert full["end_ns"] >= full["start_ns"]
        assert len(full["requests"]) == 2

    def test_tracing_off_records_nothing(self, model):
        tracer = monitor.get_tracer()
        assert not tracer.enabled
        with ContinuousBatchingEngine(model, total_pages=32,
                                      page_size=8) as eng:
            eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                       request_id="dark").result(timeout=300)
        assert monitor.request_timeline("dark") is None

    def test_bounded_per_request_events(self, model):
        monitor.start_capture(max_events_per_request=4)
        try:
            with ContinuousBatchingEngine(model, total_pages=32,
                                          page_size=8) as eng:
                eng.submit(np.arange(4, dtype=np.int32),
                           max_new_tokens=8,
                           request_id="capped").result(timeout=300)
        finally:
            monitor.stop_capture()
        tl = monitor.request_timeline("capped")
        assert len(tl["events"]) == 4
        assert tl["dropped_events"] > 0


class TestRequestIdContinuity:
    def test_result_cache_done_pending_unknown(self, model):
        with ContinuousBatchingEngine(model, total_pages=32,
                                      page_size=8) as eng:
            r = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=3,
                           request_id="rc-1")
            out = r.result(timeout=300)
            res = eng.result_for("rc-1")
            assert res["status"] == "done"
            assert res["output_ids"] == [int(t) for t in out]
            assert res["new_tokens"] == 3
            assert eng.result_for("never-seen") is None

    def test_auto_assigned_ids_are_unique(self, model):
        with ContinuousBatchingEngine(model, total_pages=64,
                                      page_size=8) as eng:
            reqs = [eng.submit(np.arange(4, dtype=np.int32),
                               max_new_tokens=2) for _ in range(3)]
            for r in reqs:
                r.result(timeout=300)
            ids = [r.request_id for r in reqs]
            assert len(set(ids)) == 3
            assert all(i.startswith("req-") for i in ids)
            for r in reqs:
                assert eng.result_for(r.request_id)["status"] == "done"

    def test_generate_with_requests_row_ids(self, model):
        with ContinuousBatchingEngine(model, total_pages=64,
                                      page_size=8) as eng:
            ids = np.arange(8, dtype=np.int32).reshape(2, 4)
            _out, reqs = eng.generate_with_requests(
                ids, max_new_tokens=2, request_id="batch")
            assert [r.request_id for r in reqs] == ["batch/0", "batch/1"]
            _out, reqs = eng.generate_with_requests(
                ids[:1], max_new_tokens=2, request_id="solo")
            assert [r.request_id for r in reqs] == ["solo"]

    def test_error_results_are_cached(self, model):
        # the fault follows the request (seq 0)
        plan = faults.FaultPlan([{"site": "prefill", "seq_id": 0}])
        with faults.installed(plan):
            with ContinuousBatchingEngine(model, total_pages=32,
                                          page_size=8) as eng:
                r = eng.submit(np.arange(4, dtype=np.int32),
                               max_new_tokens=2, request_id="boom")
                with pytest.raises(faults.FaultError):
                    r.result(timeout=300)
                res = eng.result_for("boom")
                assert res["status"] == "error"
                assert res["error_type"] == "FaultError"

    def test_result_cache_is_bounded(self, model):
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      result_cache_size=2) as eng:
            for i in range(3):
                eng.submit(np.arange(4, dtype=np.int32),
                           max_new_tokens=2,
                           request_id=f"b-{i}").result(timeout=300)
            assert eng.result_for("b-0") is None      # evicted (FIFO)
            assert eng.result_for("b-1")["status"] == "done"
            assert eng.result_for("b-2")["status"] == "done"

    def test_snapshot_restore_preserves_request_id(self, model):
        # the continuity contract: a client holding the id re-attaches
        # on the RESTORED engine and reads the exact same stream
        prompts = [np.arange(5, dtype=np.int32),
                   np.arange(3, dtype=np.int32) + 7]
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=4) as ref_eng:
            refs = [ref_eng.submit(p, max_new_tokens=8).result(timeout=300)
                    for p in prompts]
        engA = ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                        max_batch=4)
        try:
            with faults.installed(faults.FaultPlan(
                    [{"site": "decode_step", "kind": "delay",
                      "delay_s": 0.01}])):
                live = [engA.submit(p, max_new_tokens=8,
                                    request_id=f"snap-{i}")
                        for i, p in enumerate(prompts)]
                t0 = time.monotonic()
                while time.monotonic() - t0 < 120 and not all(
                        len(r.generated) >= 2 for r in live):
                    time.sleep(0.005)
                journal = engA.snapshot()
        finally:
            engA.stop()
        assert sorted(e["request_id"] for e in journal["requests"]) == \
            ["snap-0", "snap-1"]
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=4) as engB:
            resumed = engB.restore(journal)
            assert sorted(r.request_id for r in resumed) == \
                ["snap-0", "snap-1"]
            outs = {r.request_id: r.result(timeout=300) for r in resumed}
            # the SAME ids now resolve on the restored engine's cache
            for i, ref in enumerate(refs):
                res = engB.result_for(f"snap-{i}")
                assert res["status"] == "done"
                assert res["output_ids"] == [int(t) for t in ref]
                assert np.array_equal(outs[f"snap-{i}"], ref)


class TestHttpResultSurface:
    def test_result_endpoint_done_pending_and_404(self, model):
        import json
        import urllib.error
        import urllib.request
        from paddle_tpu.inference import GenerationServer

        with GenerationServer(model, total_pages=64, page_size=8) as srv:
            base = f"http://{srv.host}:{srv.port}"
            body = json.dumps({
                "input_ids": np.arange(4, dtype=np.int32)[None].tolist(),
                "max_new_tokens": 2, "request_id": "http-1"}).encode()
            req = urllib.request.Request(
                base + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                out = json.loads(resp.read())
            assert out["request_ids"] == ["http-1"]
            with urllib.request.urlopen(base + "/result/http-1",
                                        timeout=30) as resp:
                assert resp.status == 200
                res = json.loads(resp.read())
            assert res["status"] == "done"
            assert res["output_ids"] == out["output_ids"][0]
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(base + "/result/ghost", timeout=30)
            assert e.value.code == 404
