"""repro.obs — structured tracing + exposition for the serving stack.

Usage sketch (quickstart §12 walks the full loop)::

    from repro import obs
    obs.configure()                      # in-memory ring; off by default
    eng = QueryEngine(expose_port=0)     # /metrics + /health
    ... serve ...
    spans = obs.current_spans()
    obs.export.save_chrome_trace("trace.json", spans)   # Perfetto
    obs.disable()

Span sites cost one global read + one branch while tracing is off, and
spans never feed scheduling or deterministic counters — enabling them
cannot change ``deterministic_snapshot()`` (pinned by
``benchmarks/bench_obs.py`` and the CI ``obs-smoke`` job).

Online health intelligence (quickstart §13) rides the same stream::

    monitor = obs.HealthMonitor()        # aggregator + SLOs + drift
    eng = QueryEngine(monitor=monitor, expose_port=0)
    with obs.tracing(monitor):
        ... serve ...
    eng.health()                         # HealthVerdict{ok|degraded|failing}

While tracing is enabled every span is also a
``jax.profiler.TraceAnnotation``: under ``jax.profiler.trace`` the
program's spans sit on the profile's host plane, on the device's clock.
"""
from . import drift, export, health, sinks, slo  # noqa: F401
from .drift import DriftDetector
from .exposition import parse_prometheus, render_prometheus
from .export import chrome_trace, residuals, save_chrome_trace
from .health import HealthMonitor, HealthVerdict, WindowAggregator
from .sinks import InMemorySink, JsonlSpanSink, load_spans
from .slo import DEFAULT_SLOS, Objective, SLOEngine
from .spans import (
    Tracer,
    configure,
    counter,
    current_spans,
    disable,
    enabled,
    event,
    get_tracer,
    new_trace,
    span,
    tracing,
)

__all__ = [
    "DEFAULT_SLOS", "DriftDetector", "HealthMonitor", "HealthVerdict",
    "InMemorySink", "JsonlSpanSink", "Objective", "SLOEngine", "Tracer",
    "WindowAggregator", "chrome_trace", "configure", "counter",
    "current_spans", "disable", "drift", "enabled", "event", "export",
    "get_tracer", "health", "load_spans", "new_trace",
    "parse_prometheus", "render_prometheus", "residuals",
    "save_chrome_trace", "sinks", "slo", "span", "tracing",
]
