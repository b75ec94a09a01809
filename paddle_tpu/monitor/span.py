"""The one host-span primitive: a block timed once, written to every
sink.

A ``span`` fans its measurement out to these consumers:

  * a Histogram observation (always — metrics are unconditional), on
    ``time.perf_counter``;
  * ``into``, a plain dict of the caller's: the elapsed seconds are
    added under the span's name (always).  A loop that opens the same
    few spans every pass sums them there and moves its counters once a
    pass (the serving loop's phase seconds), where a labelled counter a
    span would cost more than the span;
  * a profiler ``HostEvent`` (only while a Profiler or a capture window
    has the recorder in a RECORD state — the push is a no-op
    otherwise), on ``time.perf_counter_ns``;
  * a ``jax.profiler.TraceAnnotation`` of the same name, once jax is
    imported: under a ``jax.profiler`` trace the span lands on the
    ``/host:CPU`` plane of the ``.xplane.pb``, on the profiler's own
    clock beside the device's operations, so an idle gap on the device
    can be attributed to what the host was doing in it.  With no trace
    running the annotation is a TraceMe that records nothing (a quarter
    of a microsecond).  A reader of the trace sees the bare name only,
    so an identifier it must see goes into the name
    (``engine/step 17``).

``profiler.RecordEvent`` and the eager-op spans of
``framework.dispatch`` go through this class, so there is one path from
a host span to every sink.
"""
from __future__ import annotations

import functools
import sys
import time
from typing import Optional

from ..profiler.record import get_recorder
from .registry import Histogram

__all__ = ["span"]

_TraceAnnotation = None


def _annotation(name: str):
    """jax's TraceMe of this name, or None while jax is not imported
    (``paddle_tpu.monitor`` stays importable before jax)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


class span:
    """``with span("collective/all_reduce", histogram=h, kind="all_reduce"):``

    Times the block; observes elapsed seconds into ``histogram`` (with
    the given labels), adds them to ``into[name]``, records a host event
    named ``name`` for the profiler timeline and annotates jax's trace
    with it.  Usable as a decorator.  ``elapsed`` holds the measured
    seconds after exit.
    """

    __slots__ = ("name", "histogram", "into", "labels", "elapsed",
                 "_t0", "_start_ns", "_ann")

    def __init__(self, name: str, histogram: Optional[Histogram] = None,
                 into: Optional[dict] = None, **labels):
        self.name = name
        self.histogram = histogram
        self.into = into
        self.labels = labels
        self.elapsed: Optional[float] = None
        self._t0 = None
        self._start_ns = None
        self._ann = None

    def __enter__(self):
        rec = get_recorder()
        if rec.enabled:
            self._start_ns = rec.now_ns()
        ann = self._ann = _annotation(self.name)
        if ann is not None:
            ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.histogram is not None:
            self.histogram.observe(self.elapsed, **self.labels)
        if self.into is not None:
            self.into[self.name] = (self.into.get(self.name, 0.0)
                                    + self.elapsed)
        if self._start_ns is not None:
            rec = get_recorder()
            rec.push(self.name, self._start_ns, rec.now_ns())
            self._start_ns = None
        return False

    def __call__(self, fn):
        """Decorator form.  Each call times through a fresh inner span
        (the decorator instance's config — name/histogram/labels —
        is resolved ONCE, here) and the measurement is copied back to
        THIS instance's ``elapsed``, so tests can read the decorator
        they hold instead of losing the inner span (the old form
        silently dropped it).  Per-call inner spans keep re-entrant
        and concurrent calls from clobbering each other's timers."""
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            inner = span(self.name, self.histogram, self.into,
                         **self.labels)
            try:
                with inner:
                    return fn(*args, **kwargs)
            finally:
                self.elapsed = inner.elapsed
        return wrapper
