"""Unified observability plane — registry, span tracer, exporters.

One process-wide :class:`MetricsRegistry` (counters, gauges, log-bucketed
histograms with exact-count p50/p95/p99), one :class:`SpanTracer`
(context-manager spans with parent nesting in a bounded ring, plus
cross-thread / cross-process trace propagation via
:class:`TraceContext`), the tail-sampling :class:`FlightRecorder` that
keeps full span trees for slow or failed requests, the online
:class:`RecallSentinel` that audits live routed queries against
exhaustive ground truth, and the exporters that read everything back out
(Prometheus text, JSONL event log, stable JSON snapshot).  The serving
planes record into the module-level defaults ``REGISTRY`` / ``TRACER`` /
``FLIGHT``; see docs/OBSERVABILITY.md for the span taxonomy and operator
recipes.
"""
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                REGISTRY)
from repro.obs.tracer import Span, SpanTracer, TraceContext, TRACER
from repro.obs.flight import FlightRecorder, FLIGHT
from repro.obs.sentinel import RecallSentinel
from repro.obs.export import snapshot, spans_jsonl, to_prometheus
from repro.obs.profile import device_trace, install_compile_counter

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
           "Span", "SpanTracer", "TraceContext", "TRACER",
           "FlightRecorder", "FLIGHT", "RecallSentinel",
           "snapshot", "spans_jsonl", "to_prometheus",
           "device_trace", "install_compile_counter"]
