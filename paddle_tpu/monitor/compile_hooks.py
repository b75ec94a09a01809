"""Compile telemetry: ``jit_recompile_count`` / ``jit_compile_seconds``
and the seconds of each compile phase.

jax fires a monitoring event for every XLA backend compile the process
performs; ``install_compile_hooks()`` subscribes once and feeds two
registry metrics, so the program auditor's static recompile rules
(``paddle_tpu.analysis``) and the runtime agree on what actually
recompiled.  Every event is a program the jit cache could not serve —
the first compile of a signature counts too, which is exactly what a
serving warm-up wants to see go to zero in the measured window
(tools/serve_bench.py surfaces the deltas).

The event also fires when jax's persistent compilation cache serves
the executable from disk (checked on jax 0.9: a disk hit emits
``compile_time_saved_sec`` / ``cache_retrieval_time_sec`` and then the
same ``backend_compile_duration``), so the counter reads the same with
a warm or a cold disk cache — only the seconds shrink.

Set-up by phase: jax 0.9 times three phases of every program it builds
(``jaxpr_trace_duration``, ``jaxpr_to_mlir_module_duration``,
``backend_compile_duration``) and three counters keep their seconds:
``jit_trace_seconds_total``, ``jit_lower_seconds_total``,
``jit_backend_compile_seconds_total``.  The events nest in time — a
jitted ``jnp`` function traced inside the program's own trace, an
eager op compiled while the program is traced — so each event is
credited with its own time only (its duration less the events that ran
inside it on the same thread): the three counters add up to the wall
time the thread spent building programs, and a warm disk cache shows as
a small third and unchanged first two.

This module must stay lazily importable: nothing here touches jax
until ``install_compile_hooks()`` is called, preserving the registry's
importable-before-jax contract.
"""
from __future__ import annotations

import threading
import time

from .registry import counter, histogram

__all__ = ["install_compile_hooks"]

_COMPILE_EVENT_MARKER = "backend_compile"
_RECOMPILE_HELP = ("XLA backend compiles observed (every event is a "
                   "program the jit cache could not serve; first "
                   "compiles of a signature count too)")
_SECONDS_HELP = "wall seconds per XLA backend compile"

#: jax's duration event -> (the counter of its seconds, help)
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": (
        "jit_trace_seconds_total",
        "seconds spent tracing programs to jaxprs (own time: nested "
        "traces, lowerings and compiles are not counted twice)"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "jit_lower_seconds_total",
        "seconds spent lowering jaxprs to MLIR modules"),
    "/jax/core/compile/backend_compile_duration": (
        "jit_backend_compile_seconds_total",
        "seconds spent in the XLA backend compile (or fetching the "
        "executable from the persistent cache)"),
}
_NEST_SLACK_S = 1e-4      # a listener runs this close behind its event
_MAX_OPEN = 256           # finished events kept in case a parent ends

_lock = threading.Lock()
_installed = False
_local = threading.local()


def _own_seconds(duration: float) -> float:
    """``duration`` less the phase events that started inside it on this
    thread (they have ended already: an inner event ends first)."""
    done = getattr(_local, "done", None)
    if done is None:
        done = _local.done = []
    start = time.time() - duration          # jax times with time.time
    inside = 0.0
    while done and done[-1][0] >= start - _NEST_SLACK_S:
        inside += done.pop()[1]
    done.append((start, duration))
    if len(done) > _MAX_OPEN:
        del done[:_MAX_OPEN // 2]
    return max(0.0, duration - inside)


def _on_event_duration(event: str, duration: float, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is not None:
        counter(*phase).inc(_own_seconds(duration))
    if _COMPILE_EVENT_MARKER not in event:
        return
    # re-fetch per event: a registry.reset() (tests) drops the metric
    # objects, and get-or-create is one dict hit under the registry lock
    counter("jit_recompile_count", _RECOMPILE_HELP).inc()
    histogram("jit_compile_seconds", _SECONDS_HELP).observe(duration)


def install_compile_hooks() -> bool:
    """Idempotently subscribe to jax's compile events; returns True
    (the value callers already read)."""
    global _installed
    with _lock:
        if _installed:
            return True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        # materialize the series now so a snapshot taken before the
        # first compile still carries explicit zeros
        counter("jit_recompile_count", _RECOMPILE_HELP)
        histogram("jit_compile_seconds", _SECONDS_HELP)
        for phase in _PHASES.values():
            counter(*phase).inc(0)
        _installed = True
        return True
