"""paddle_tpu.profiler — profiling API (SURVEY #72/#34).

Host spans via ``monitor.span`` (the Python recorder for the chrome
export, jax's TraceMe under a ``jax.profiler`` trace); device timelines
via jax.profiler (XPlane); scheduler/RecordEvent/export surface mirrors
the reference (python/paddle/profiler/).
"""
from .profiler import (  # noqa: F401
    Profiler, ProfilerState, ProfilerTarget, make_scheduler,
    export_chrome_tracing, load_profiler_result,
)
from .record import RecordEvent, record_function  # noqa: F401
from .statistics import SortedKeys  # noqa: F401
from .timer import benchmark  # noqa: F401

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "make_scheduler",
    "export_chrome_tracing", "load_profiler_result", "RecordEvent",
    "record_function", "SortedKeys", "benchmark",
]


class SummaryView:
    """reference: profiler.SummaryView — which summary table to print."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name: str, worker_name: str = None):
    """reference: profiler.export_protobuf — a Profiler on_trace_ready
    handler.  The device timeline on this stack is jax.profiler's XPlane
    protobuf; this handler points jax's trace dump at ``dir_name``."""
    def handler(prof):
        import os
        os.makedirs(dir_name, exist_ok=True)
        try:
            import jax
            jax.profiler.save_device_memory_profile(
                os.path.join(dir_name, (worker_name or "worker")
                             + ".memory.pb"))
        except Exception:
            pass
        # host spans still export as chrome trace alongside
        prof.export(os.path.join(dir_name, (worker_name or "worker")
                                 + ".json"))
    return handler


__all__ += ["SummaryView", "export_protobuf"]
