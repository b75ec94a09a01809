"""Host event recording: RecordEvent spans + the recorder.

Capability parity with the reference's RecordEvent/HostEventRecorder
(reference: paddle/phi/api/profiler/host_event_recorder.h:231, RAII spans
auto-inserted by codegen eager_gen.py:322).  The recorder keeps the
``paddle.profiler`` API and the chrome export working; the native
thread-local recorder is jax's TraceMe, which every ``RecordEvent`` also
reaches through ``monitor.span``.
"""
from __future__ import annotations

import threading
import time
from typing import List, NamedTuple


class HostEvent(NamedTuple):
    name: str
    tid: int
    start_ns: int
    end_ns: int


class _PyRecorder:
    """The host recorder: a list of ``HostEvent`` under a lock, stamped
    with ``time.perf_counter_ns``."""

    def __init__(self):
        # re-entrant: the collector's span (monitor/gc_hooks.py) may end
        # while its thread is inside ``push``
        self._lock = threading.RLock()
        self._events: List[HostEvent] = []
        self.enabled = False

    def enable(self, on: bool) -> None:
        self.enabled = on

    def now_ns(self) -> int:
        return time.perf_counter_ns()

    def push(self, name: str, start_ns: int, end_ns: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._events.append(
                HostEvent(name, threading.get_ident(), start_ns, end_ns))

    def collect(self) -> List[HostEvent]:
        with self._lock:
            out, self._events = self._events, []
        return out


_recorder = _PyRecorder()


def get_recorder() -> _PyRecorder:
    """The process-wide host recorder."""
    return _recorder


class RecordEvent:
    """User span: ``with RecordEvent("io"): ...`` (reference:
    python/paddle/profiler/utils.py RecordEvent).  A ``monitor.span``
    under the reference's name: the recorder keeps it only while a
    Profiler is in a RECORD state at ``begin()``, a ``jax.profiler``
    trace whenever one is running."""

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        self._span = None

    def begin(self):
        # imported here: monitor.span imports this module's recorder
        from ..monitor.span import span
        self._span = span(self.name)
        self._span.__enter__()

    def end(self):
        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, func):
        import functools

        @functools.wraps(func)
        def wrapper(*args, **kwargs):
            with RecordEvent(self.name, self.event_type):
                return func(*args, **kwargs)
        return wrapper


def record_function(name: str) -> RecordEvent:
    return RecordEvent(name)
