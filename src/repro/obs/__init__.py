"""Observability layer: metrics registry, profiler spans, compile log,
request traces, slow-query log.

  MetricsRegistry        — thread-safe counters / gauges / fixed-bucket
                           histograms with labels + cardinality caps;
                           Prometheus text exposition and JSON snapshot
  span, NULL_SPAN        — ``jax.profiler.TraceAnnotation`` host spans named
                           ``repro.<seam>``, on the device trace's clock
  COMPILES               — process-wide log of XLA compiles and persistent
                           cache hits (a ``jax.monitoring`` listener,
                           registered on first import)
  TraceContext, TraceRing,
  SlowQueryLog           — per-request pipeline timestamps (submit →
                           deliver), a bounded ring of recent traces, and
                           a structured JSON slow-query log
  summarize_latency, histogram_counts, percentile_from_counts,
  DEFAULT_LATENCY_BUCKETS_MS
                         — the shared bucket ladder + percentile math used
                           by both the online histograms and the offline
                           benchmarks, so p50/p95 mean the same thing in
                           BENCH_*.json and on /metrics
  parse_prometheus       — exposition-format parser for tests and the
                           load benchmark's invariant checks

Everything here but the spans and the compile listener (which use
``jax.profiler`` and ``jax.monitoring``) is stdlib only, and all of it is
safe to update under ``engine.lock``; ``MetricsRegistry(enabled=False)``
degrades every instrument to a shared no-op so the uninstrumented fast
path is restored.
"""

from repro.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_INSTRUMENT,
    histogram_counts,
    parse_prometheus,
    percentile_from_counts,
    summarize_latency,
)
from repro.obs.compiles import COMPILES, CompileLog
from repro.obs.trace import (
    MARK_ORDER,
    NULL_SPAN,
    SlowQueryLog,
    TraceContext,
    TraceRing,
    span,
)

__all__ = [
    "COMPILES", "CompileLog", "DEFAULT_LATENCY_BUCKETS_MS", "Counter",
    "Gauge", "Histogram", "MARK_ORDER", "MetricsRegistry", "NULL_INSTRUMENT",
    "NULL_SPAN", "SlowQueryLog", "TraceContext", "TraceRing",
    "histogram_counts", "parse_prometheus", "percentile_from_counts", "span",
    "summarize_latency",
]
