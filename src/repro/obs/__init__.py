"""SpiDR serving-stack telemetry: metrics, span tracing, pipeline timelines.

Three pillars (see docs/observability.md):

* :mod:`repro.obs.metrics` — in-process counters/gauges/histograms with
  Prometheus-text and JSON export; process-wide default registry that is
  **disabled by default** and costs one truthiness check per site when off.
* :mod:`repro.obs.trace` — context-manager span tracer emitting
  Chrome-trace/Perfetto JSON (compile -> per-chunk run_chunk ->
  snapshot/restore).
* :mod:`repro.obs.timeline` — renders the simulated per-core async
  pipeline clocks of ``estimate_multicore_cost`` (busy / AER-routing /
  idle intervals) in the same Chrome-trace format.

Plus :mod:`repro.obs.logs`: shared structured-logging setup with a
per-stream request id on every record.

Quick start::

    from repro import obs
    obs.enable_metrics(); obs.enable_tracing()
    ...  # compile / serve as usual
    print(obs.default_registry().to_prometheus())
    obs.default_tracer().export("trace.json")
"""
from . import logs, metrics, timeline, trace  # noqa: F401
from .logs import logging_setup, request_context
from .metrics import (
    MetricsRegistry, default_registry, disable_metrics, enable_metrics,
    metrics_enabled, set_default_registry,
)
from .timeline import busy_cycle_totals, export_timeline, multicore_timeline
from .trace import (
    Tracer, default_tracer, disable_tracing, enable_tracing,
    set_default_tracer, tracing_enabled,
)

__all__ = [
    "logs", "metrics", "timeline", "trace",
    "logging_setup", "request_context",
    "MetricsRegistry", "default_registry", "set_default_registry",
    "enable_metrics", "disable_metrics", "metrics_enabled",
    "Tracer", "default_tracer", "set_default_tracer",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "multicore_timeline", "busy_cycle_totals", "export_timeline",
]
