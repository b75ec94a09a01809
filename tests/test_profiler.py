"""Profiler subsystem tests: recorder, scheduler, export, timer."""
import json
import os

import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import (
    Profiler, ProfilerState, ProfilerTarget, RecordEvent, SortedKeys,
    make_scheduler, export_chrome_tracing, load_profiler_result,
)
from paddle_tpu.profiler.record import get_recorder


class TestRecorder:
    def test_span_capture(self):
        rec = get_recorder()
        rec.enable(True)
        with RecordEvent("my_span"):
            pass
        rec.enable(False)
        events = rec.collect()
        names = [e.name for e in events]
        assert "my_span" in names
        e = events[names.index("my_span")]
        assert e.end_ns >= e.start_ns

    def test_disabled_records_nothing(self):
        rec = get_recorder()
        rec.collect()
        with RecordEvent("ignored"):
            pass
        assert all(e.name != "ignored" for e in rec.collect())

    def test_decorator(self):
        rec = get_recorder()
        rec.enable(True)

        @RecordEvent("decorated_fn")
        def f(x):
            return x + 1

        assert f(1) == 2
        rec.enable(False)
        assert any(e.name == "decorated_fn" for e in rec.collect())


class TestScheduler:
    def test_make_scheduler_cycle(self):
        sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                               skip_first=1)
        states = [sched(i) for i in range(6)]
        assert states == [
            ProfilerState.CLOSED,           # skip_first
            ProfilerState.CLOSED,           # closed
            ProfilerState.READY,
            ProfilerState.RECORD,
            ProfilerState.RECORD_AND_RETURN,
            ProfilerState.CLOSED,           # repeat exhausted
        ]

    def test_bad_args(self):
        with pytest.raises(ValueError):
            make_scheduler(closed=0, ready=0, record=0)

    def test_negative_skip_first_raises(self):
        with pytest.raises(ValueError):
            make_scheduler(closed=1, ready=1, record=1, skip_first=-1)
        with pytest.raises(ValueError):
            make_scheduler(closed=1, ready=1, record=1, repeat=-1)

    def test_repeat_boundary_returns_to_closed(self):
        # after the final cycle the state machine must land in CLOSED
        # and STAY there — not keep recording on later steps
        sched = make_scheduler(closed=1, ready=1, record=2, repeat=2,
                               skip_first=2)
        span = 1 + 1 + 2
        end = 2 + 2 * span
        # last step of the final cycle flushes
        assert sched(end - 1) == ProfilerState.RECORD_AND_RETURN
        for step in range(end, end + 3 * span):
            assert sched(step) == ProfilerState.CLOSED, step


class TestProfiler:
    def test_records_op_events(self):
        with Profiler(targets=[ProfilerTarget.CPU]) as prof:
            x = paddle.ones([4, 4])
            y = paddle.matmul(x, x)
            _ = y.numpy()
            prof.step()
        names = {e.name for e in prof.events}
        assert any(n.startswith("op::") for n in names), names

    def test_scheduled_capture_and_trace_ready(self):
        seen = []
        prof = Profiler(
            scheduler=make_scheduler(closed=1, ready=1, record=1, repeat=1),
            on_trace_ready=lambda p: seen.append(p.step_num))
        prof.start()
        for _ in range(4):
            with RecordEvent("step_work"):
                pass
            prof.step()
        prof.stop()
        assert seen, "on_trace_ready never fired"
        assert any(e.name == "step_work" for e in prof.events)

    def test_chrome_export_roundtrip(self, tmp_path):
        with Profiler() as prof:
            with RecordEvent("exported"):
                pass
            prof.step()
        path = str(tmp_path / "trace.json")
        prof.export(path)
        with open(path) as f:
            payload = json.load(f)
        assert any(e["name"] == "exported" for e in payload["traceEvents"])
        loaded = load_profiler_result(path)
        assert any(e.name == "exported" for e in loaded)

    def test_export_chrome_tracing_handler(self, tmp_path):
        d = str(tmp_path / "out")
        with Profiler(on_trace_ready=export_chrome_tracing(d)) as prof:
            with RecordEvent("handler_span"):
                pass
        files = os.listdir(d)
        assert len(files) == 1 and files[0].endswith(".json")

    def test_summary(self, capsys):
        with Profiler() as prof:
            with RecordEvent("summarized"):
                pass
        table = prof.summary(sorted_by=SortedKeys.CPUTotal)
        assert "summarized" in table
        assert "Calls" in table

    def test_timer_only(self):
        prof = Profiler(timer_only=True)
        prof.start()
        for _ in range(3):
            prof.step(num_samples=32)
        info = prof.step_info()
        assert "ips" in info and "batch_cost" in info
        prof.stop()

    def test_per_cycle_traces_do_not_accumulate(self):
        cycles = []
        prof = Profiler(
            scheduler=make_scheduler(closed=0, ready=0, record=1, repeat=3),
            on_trace_ready=lambda p: cycles.append(p.events))
        prof.start()
        for i in range(3):
            with RecordEvent(f"cycle_{i}"):
                pass
            prof.step()
        prof.stop()
        assert len(cycles) == 3
        for i, evs in enumerate(cycles):
            names = [e.name for e in evs]
            assert f"cycle_{i}" in names
            for j in range(3):
                if j != i:
                    assert f"cycle_{j}" not in names

    def test_stop_in_ready_state_fires_no_handler(self):
        fired = []
        prof = Profiler(
            scheduler=make_scheduler(closed=2, ready=2, record=2),
            on_trace_ready=lambda p: fired.append(1))
        prof.start()
        for _ in range(3):
            prof.step()   # lands in READY at step 3
        prof.stop()
        assert prof.current_state == ProfilerState.CLOSED
        assert not fired

    def test_dispatch_hook_removed_after_stop(self):
        from paddle_tpu.framework import dispatch
        with Profiler():
            pass
        assert dispatch._prof_recorder is None


class TestBenchmarkTimer:
    def test_reader_and_ips(self):
        bm = profiler.benchmark()
        bm.reset()
        bm.begin()
        for _ in range(5):
            bm.before_reader()
            bm.after_reader()
            bm.step(num_samples=8)
        rep = bm.report()
        assert rep["ips"]["avg"] > 0
        assert bm.steps == 5
