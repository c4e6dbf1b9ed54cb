"""Observability subsystem: tracing, metrics, roofline cost models.

One low-overhead layer threaded through the whole query path
(admission → coalesce → flush → launch_batch → fixpoint → finalize_batch →
device_sync → cache-fill):

- :mod:`.trace` — per-flush/per-batch spans, Chrome ``trace_event`` export
- :mod:`.metrics` — thread-safe counter/gauge/histogram registry with
  Prometheus-text and JSON exporters, absorbing the stats dataclasses
- :mod:`.roofline_attr` — analytic flop/byte cost of a fixpoint launch,
  which the kernel autotuner prices candidates with

The fixpoint loops count their own active rows and semi-naive Δ entries
(``DenseResult.active_rows`` / ``delta_facts``); the serving layer reads
them in the device sync it already makes.
"""
from .trace import NULL_TRACER, NullTracer, Span, Tracer
from .metrics import (
    DEFAULT_BUCKETS, NULL_METRICS, Counter, Gauge, Histogram,
    MetricsRegistry, NullMetrics,
)
from .roofline_attr import csr_launch_cost, dense_launch_cost

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span",
    "MetricsRegistry", "NullMetrics", "NULL_METRICS",
    "Counter", "Gauge", "Histogram", "DEFAULT_BUCKETS",
    "dense_launch_cost", "csr_launch_cost",
]
