"""Cross-layer observability: span tracing, metrics, exporters, timing.

Small, dependency-light modules (stdlib + numpy only; ``trace`` and
``timeit`` import jax lazily, for the profiler's annotations and to
block on async results):

* :mod:`repro.obs.trace` — hierarchical span tracer with an explicit
  contextvar-carried trace context, threaded request → planner →
  substrate → tape phase → kernel dispatch.  Each span is also a
  ``jax.profiler.TraceAnnotation``, so a profiler session records it on
  the device trace's clock; with no profiler running it costs one
  TraceMe object.
* :mod:`repro.obs.metrics` — thread-safe registry of counters, gauges
  and streaming histograms with Prometheus-text / JSON exporters;
  backs ``ServeStats`` and the kernel dispatch counters.
* :mod:`repro.obs.export` — Chrome-trace / Perfetto JSON export of
  span trees.
* :mod:`repro.obs.timeit` — the shared warmup + best-of-N bench timer.

See DESIGN.md §13 for the span hierarchy and threading contract.
"""
from .trace import (Span, SpanEvent, Tracer, current, disable, enable,
                    event, get_tracer, set_tracer, span)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      REGISTRY, get_registry, reset_registry)
from .export import chrome_trace, write_chrome_trace
from .timeit import TimeitResult, timeit

__all__ = [
    "Span", "SpanEvent", "Tracer", "current", "disable", "enable",
    "event", "get_tracer", "set_tracer", "span",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "get_registry", "reset_registry",
    "chrome_trace", "write_chrome_trace",
    "TimeitResult", "timeit",
]
