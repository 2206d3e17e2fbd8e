"""Observability substrate: span tracing, metrics, convergence telemetry.

Three pieces that import only the stdlib and numpy at module load, so
every layer above — engines, kernels, serving — can import them without
cycles:

* `repro.obs.trace` — zero-cost-when-disabled context-manager spans with a
  ring buffer and an optional JSONL sink. An enabled span also opens a
  ``jax.profiler.TraceAnnotation`` (``repro.<name>``), so spans share the
  profiler's clock; ``jax.profiler`` is imported lazily, on the first
  enabled span.
* `repro.obs.metrics` — a counters/gauges/histograms registry with
  ``summary()`` (dict) and ``prometheus_text()`` exporters.
* `repro.obs.telemetry` — the uniform per-round ``ConvergenceTrace``
  (residual / active fraction / work) every engine attaches to its
  :class:`~repro.engine.convergence.RunResult`.
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    bounded_append,
    percentile,
)
from repro.obs.telemetry import (
    ConvergenceTrace,
    active_columns_per_round,
    trace_from_block_activity,
    trace_from_col_rounds,
    trace_from_push_counts,
)
from repro.obs.trace import NULL_SPAN, SPAN_NAMES, Span, Tracer, tspan

__all__ = [
    "NULL_SPAN",
    "SPAN_NAMES",
    "ConvergenceTrace",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "active_columns_per_round",
    "bounded_append",
    "percentile",
    "trace_from_block_activity",
    "trace_from_col_rounds",
    "trace_from_push_counts",
    "tspan",
]
