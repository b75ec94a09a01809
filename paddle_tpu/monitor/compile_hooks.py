"""Compile telemetry: ``jit_recompile_count`` / ``jit_compile_seconds``.

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

This module must stay lazily importable: nothing here touches jax
until ``install_compile_hooks()`` is called, preserving the registry's
importable-before-jax contract.
"""
from __future__ import annotations

import threading

from .registry import counter, histogram

__all__ = ["install_compile_hooks"]

_COMPILE_EVENT_MARKER = "backend_compile"
_RECOMPILE_HELP = ("XLA backend compiles observed (every event is a "
                   "program the jit cache could not serve; first "
                   "compiles of a signature count too)")
_SECONDS_HELP = "wall seconds per XLA backend compile"

_lock = threading.Lock()
_installed = False


def _on_event_duration(event: str, duration: float, **kw) -> None:
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
        _installed = True
        return True
