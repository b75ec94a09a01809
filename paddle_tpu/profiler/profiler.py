"""Profiler: scheduler state machine + chrome-trace export.

Capability parity with the reference's Profiler
(reference: python/paddle/profiler/profiler.py:358 — ProfilerState scheduler
``make_scheduler:129``, ``export_chrome_tracing:227``, summary statistics).

TPU-native: host spans come from ``monitor.span`` (``record.py``'s
recorder, and jax's TraceMe); device timelines come from XLA via
``jax.profiler`` (XPlane/TensorBoard), started alongside when
``ProfilerTarget.TPU`` is requested.  Chrome-trace JSON is emitted for host
events so the scheduler/export API surface matches the reference.
"""
from __future__ import annotations

import enum
import json
import os
import socket
import time
from typing import Callable, Iterable, List, Optional, Union

from .record import HostEvent, RecordEvent, get_recorder
from .statistics import SortedKeys, summary_table
from .timer import benchmark


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3   # record; trace is returned/flushed at step end


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0
                   ) -> Callable[[int], ProfilerState]:
    """Cyclic state schedule: skip_first CLOSED steps, then cycles of
    [closed CLOSED, ready READY, record RECORD(last=RECORD_AND_RETURN)],
    repeated ``repeat`` times (0 = forever)."""
    if closed < 0 or ready < 0 or record <= 0:
        raise ValueError("closed/ready must be >=0 and record >=1")
    if skip_first < 0:
        raise ValueError("skip_first must be >= 0")
    if repeat < 0:
        raise ValueError("repeat must be >= 0 (0 = repeat forever)")
    span = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat > 0 and step >= repeat * span:
            return ProfilerState.CLOSED
        pos = step % span
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == span - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str,
                          worker_name: Optional[str] = None) -> Callable:
    """on_trace_ready callback writing chrome-trace JSON into ``dir_name``."""

    def handler(prof: "Profiler") -> None:
        os.makedirs(dir_name, exist_ok=True)
        worker = worker_name or f"host_{socket.gethostname()}_pid{os.getpid()}"
        path = os.path.join(
            dir_name, f"{worker}_time_{int(time.time() * 1000)}.json")
        prof.export(path, format="json")

    return handler


class Profiler:
    """``with Profiler(...) as p: ... p.step()`` — scheduler-driven tracing."""

    def __init__(self,
                 *,
                 targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler: Union[Callable, tuple, None] = None,
                 on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False,
                 record_shapes: bool = False,
                 profile_memory: bool = False,
                 with_flops: bool = False):
        self.targets = list(targets) if targets is not None else [
            ProfilerTarget.CPU]
        if isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self.scheduler = make_scheduler(
                closed=max(start - 1, 0), ready=1 if start > 0 else 0,
                record=end - start, repeat=1)
        else:
            self.scheduler = scheduler or _default_state_scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.record_shapes = record_shapes
        self.profile_memory = profile_memory
        self.with_flops = with_flops

        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        # _events accumulates the in-progress cycle; once a cycle completes
        # (RECORD_AND_RETURN flush or stop) it becomes _completed so each
        # exported trace covers exactly one cycle.
        self._events: List[HostEvent] = []
        self._completed: List[HostEvent] = []
        self._device_trace_dir: Optional[str] = None
        self._device_tracing = False

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        benchmark().begin()
        if self.timer_only:
            return
        self.current_state = self.scheduler(self.step_num)
        self._transition(ProfilerState.CLOSED, self.current_state)

    def stop(self) -> None:
        if self.timer_only:
            return
        rec = get_recorder()
        was_recording = self.current_state in (ProfilerState.RECORD,
                                               ProfilerState.RECORD_AND_RETURN)
        if was_recording:
            self._events.extend(rec.collect())
        rec.enable(False)
        from ..framework import dispatch as _dispatch
        _dispatch.set_profiler_recorder(None)
        self._stop_device_trace()
        if was_recording:
            self._flush_cycle()
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None) -> None:
        benchmark().step(num_samples)
        if self.timer_only:
            self.step_num += 1
            return
        prev = self.current_state
        self.step_num += 1
        new = self.scheduler(self.step_num)
        if prev == ProfilerState.RECORD_AND_RETURN:
            self._events.extend(get_recorder().collect())
            self._flush_cycle()
        self._transition(prev, new)
        self.current_state = new

    def step_info(self, unit: str = "samples") -> str:
        return benchmark().step_info(unit)

    def _transition(self, prev: ProfilerState, new: ProfilerState) -> None:
        rec = get_recorder()
        recording = new in (ProfilerState.RECORD,
                            ProfilerState.RECORD_AND_RETURN)
        was = prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        from ..framework import dispatch as _dispatch
        if recording and not was:
            rec.enable(True)
            _dispatch.set_profiler_recorder(rec)
            self._start_device_trace()
        elif was and not recording:
            self._events.extend(rec.collect())
            rec.enable(False)
            _dispatch.set_profiler_recorder(None)
            self._stop_device_trace()

    # -- device (XLA) trace ------------------------------------------------
    def _start_device_trace(self) -> None:
        if ProfilerTarget.TPU not in self.targets or self._device_tracing:
            return
        try:
            import jax
            self._device_trace_dir = os.environ.get(
                "PADDLE_TPU_TRACE_DIR", "./profiler_xplane")
            jax.profiler.start_trace(self._device_trace_dir)
            self._device_tracing = True
        except Exception:
            self._device_trace_dir = None

    def _stop_device_trace(self) -> None:
        if not self._device_tracing:
            return
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._device_tracing = False

    def _flush_cycle(self) -> None:
        """Close the current cycle: hand it to on_trace_ready, reset."""
        self._completed = self._events
        self._events = []
        if self.on_trace_ready:
            self.on_trace_ready(self)

    # -- results -----------------------------------------------------------
    @property
    def events(self) -> List[HostEvent]:
        """Events of the most recent (completed or in-progress) trace."""
        return list(self._events) if self._events else list(self._completed)

    def export(self, path: str, format: str = "json") -> None:
        """Write chrome-trace JSON ({"traceEvents": [...]})."""
        trace = []
        for e in self.events:
            trace.append({
                "name": e.name, "ph": "X", "cat": "host",
                "pid": os.getpid(), "tid": e.tid % (1 << 31),
                "ts": e.start_ns / 1e3,
                "dur": (e.end_ns - e.start_ns) / 1e3,
            })
        payload = {"traceEvents": trace, "displayTimeUnit": "ms"}
        if format == "json":
            with open(path, "w") as f:
                json.dump(payload, f)
        else:
            raise ValueError(f"unsupported export format: {format}")

    def summary(self, sorted_by: SortedKeys = SortedKeys.CPUTotal,
                op_detail: bool = True, thread_sep: bool = False,
                time_unit: str = "ms") -> str:
        table = summary_table(self.events, sorted_by=sorted_by,
                              time_unit=time_unit)
        print(table)
        return table

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def load_profiler_result(filename: str) -> List[HostEvent]:
    with open(filename) as f:
        payload = json.load(f)
    out = []
    for e in payload.get("traceEvents", []):
        start = int(e["ts"] * 1e3)
        out.append(HostEvent(e["name"], int(e.get("tid", 0)), start,
                             start + int(e.get("dur", 0) * 1e3)))
    return out
