"""Serving observability: metrics registry, lifecycle tracer, utilization.

See docs/observability.md for the metrics schema, trace event catalogue,
and the utilization methodology.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               Snapshot, StatsView)
from repro.obs.report import (decode_utilization, utilization_report,
                              write_metrics_json)
from repro.obs.trace import (NULL_TRACER, STEP_SPANS, NullTracer, Tracer,
                             validate_chrome_trace)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "STEP_SPANS",
    "Snapshot",
    "StatsView",
    "Tracer",
    "decode_utilization",
    "utilization_report",
    "validate_chrome_trace",
    "write_metrics_json",
]
