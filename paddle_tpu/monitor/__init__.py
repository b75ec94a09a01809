"""paddle_tpu.monitor — unified runtime telemetry.

A process-wide metrics registry (Counter / Gauge / Histogram with fixed
log-scale buckets; thread-safe, stdlib-only) plus span tracing that
feeds the profiler's host recorder.  Instrumented subsystems:

  * ``distributed.collective`` — per-kind call count, latency and
    payload-bytes histograms on every eager collective;
  * ``inference.server`` — request count/latency per route, a
    ``GET /metrics`` Prometheus endpoint on both servers;
  * ``inference.continuous`` — queue depth, batch-slot occupancy,
    decode-step latency, generated-token and TTFT telemetry;
  * ``hapi.callbacks.MonitorCallback`` — step time, samples/sec, loss;
  * ``distributed.watchdog`` / ``fault_tolerance`` — heartbeat age,
    in-flight/timeout tasks, preemption/restart/checkpoint counters.

Usage::

    from paddle_tpu import monitor
    h = monitor.histogram("my_latency_seconds", "...", ("stage",))
    with monitor.span("stage/io", histogram=h, stage="io"):
        ...
    print(monitor.prometheus_text())     # or monitor.snapshot()
    monitor.dump_on_exit()               # archive at interpreter exit
"""
from .registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricRegistry, get_registry,
    counter, gauge, histogram, snapshot, prometheus_text,
    dump, dump_on_exit, DEFAULT_LATENCY_BUCKETS, BYTES_BUCKETS,
)
from .span import span  # noqa: F401
from .compile_hooks import install_compile_hooks  # noqa: F401
from .gc_hooks import install_gc_hooks  # noqa: F401
from .trace import (  # noqa: F401
    Tracer, get_tracer, start_capture, stop_capture, request_timeline,
    export_chrome_trace, validate_chrome_trace,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry", "get_registry",
    "counter", "gauge", "histogram", "snapshot", "prometheus_text",
    "dump", "dump_on_exit", "span", "install_compile_hooks",
    "install_gc_hooks",
    "DEFAULT_LATENCY_BUCKETS", "BYTES_BUCKETS",
    "Tracer", "get_tracer", "start_capture", "stop_capture",
    "request_timeline", "export_chrome_trace", "validate_chrome_trace",
]
