"""Collector pauses: ``host_gc_*`` counters and a ``host/gc gen<n>`` span.

A collection of the Python heap stops every thread of the process for as
long as it takes, and a full one (generation 2) walks every tracked
container the process holds: a serving loop whose device step is 15 ms
can lose several steps to one of them, and nothing else in the program
says so.  ``install_gc_hooks()`` puts ONE entry on ``gc.callbacks``; on
``start`` it enters and on ``stop`` it leaves a
``monitor.span("host/gc gen<generation>")`` — under a ``jax.profiler``
trace the pause lands on the profiler's clock beside the phase it
interrupted, whichever thread it ran on — and moves

  * ``host_gc_collections_total{generation}``,
  * ``host_gc_pause_seconds_total{generation}``,
  * ``host_gc_collected_total`` (objects freed).

``pause_ns()`` is the same total as a plain integer, for a loop that
wants the pauses of one of its passes without a lock (the serving
engine's ``gc_ns`` on a step's ``dispatch`` record).

A callback runs wherever the collector was triggered: between any two
bytecodes of any thread, also one that holds a lock.  So it takes no
lock that its own thread may hold already: the registry's metric locks
and the recorder's are re-entrant for this, and the counters are held
here from installation on (no registry lookup under the registry's
lock).  Stdlib only, importable before jax.
"""
from __future__ import annotations

import gc
import threading

from .registry import counter
from .span import span

__all__ = ["install_gc_hooks", "pause_ns"]

_NAMES = tuple(f"host/gc gen{g}" for g in range(3))
_GENERATIONS = tuple(str(g) for g in range(3))

_lock = threading.Lock()
# the collector never nests and runs under the interpreter lock: one
# slot for the collection that is running, plain totals beside it
_open = None
_pause_ns = 0
_collections = _pause_s = _collected = None


def pause_ns() -> int:
    """Nanoseconds all collections since installation have taken."""
    return _pause_ns


def _on_gc(phase: str, info: dict) -> None:
    global _open, _pause_ns
    if phase == "start":
        _open = span(_NAMES[info["generation"]])
        _open.__enter__()
        return
    sp, _open = _open, None
    if sp is None:              # installed while a collection ran
        return
    sp.__exit__(None, None, None)
    gen = _GENERATIONS[info["generation"]]
    _pause_ns += int(sp.elapsed * 1e9)
    _collections.inc(generation=gen)
    _pause_s.inc(sp.elapsed, generation=gen)
    _collected.inc(info.get("collected", 0))


def install_gc_hooks() -> bool:
    """Idempotently put the one callback on ``gc.callbacks``; returns
    True.  The series are materialized at zero, so a snapshot taken
    before the first collection carries them."""
    global _collections, _pause_s, _collected
    with _lock:
        if _collections is not None:
            return True
        _collections = counter(
            "host_gc_collections_total", "collections of the Python heap, "
            "by generation (2 is a full one)", ("generation",))
        _pause_s = counter(
            "host_gc_pause_seconds_total", "seconds every thread of the "
            "process stood still for a collection of the Python heap, by "
            "generation", ("generation",))
        _collected = counter(
            "host_gc_collected_total", "objects the collector freed")
        for gen in _GENERATIONS:
            _collections.inc(0, generation=gen)
            _pause_s.inc(0, generation=gen)
        _collected.inc(0)
        gc.callbacks.append(_on_gc)
        return True
