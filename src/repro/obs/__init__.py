"""Unified observability: span tracing, metrics registry, exporters.

The measurement substrate under the serving fabric (and the signal
source for every adaptive ROADMAP item):

* ``trace``   — bounded ring-buffer ``SpanTracer`` with deterministic
                ids and an injectable clock; ``span`` mirrors a span
                into any active JAX profile; ``NULL_TRACER`` is the
                tracing-off fast path; ``watch_compiles`` counts the
                process's XLA compiles.
* ``metrics`` — typed ``MetricsRegistry`` (counters / gauges /
                log-bucket histograms, optional labels); the engine's
                ``stats`` dict is a registry-backed ``StatsView`` built
                from ``ENGINE_STATS_SCHEMA``/``CLUSTER_STATS_SCHEMA``;
                ``global_registry()`` backs the kernel/runtime
                trace-time counters.
* ``export``  — Chrome trace-event JSON (Perfetto-loadable),
                Prometheus text exposition, JSON snapshots, and the
                span-chain integrity validator behind
                ``serve.py --check``.

Imports nothing from the rest of ``repro`` — any layer (kernels,
runtime, serving, launch) can depend on it without cycles.
"""
from repro.obs.export import (chrome_trace, prometheus_text, snapshot,
                              validate_chrome_trace, validate_trace,
                              write_chrome_trace)
from repro.obs.metrics import (CLUSTER_STATS_SCHEMA, ENGINE_STATS_SCHEMA,
                               EngineMetrics, Histogram, MetricsRegistry,
                               StatsView, engine_stats_view,
                               extend_stats_view, global_registry,
                               log_buckets)
from repro.obs.trace import (NULL_TRACER, NullTracer, Span, SpanTracer,
                             watch_compiles)

__all__ = ["SpanTracer", "NullTracer", "NULL_TRACER", "Span",
           "watch_compiles",
           "MetricsRegistry", "StatsView", "EngineMetrics", "Histogram",
           "engine_stats_view", "extend_stats_view", "global_registry",
           "log_buckets", "ENGINE_STATS_SCHEMA", "CLUSTER_STATS_SCHEMA",
           "chrome_trace", "write_chrome_trace", "prometheus_text",
           "snapshot", "validate_trace", "validate_chrome_trace"]
