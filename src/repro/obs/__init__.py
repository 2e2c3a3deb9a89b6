"""Unified observability layer: spans, metrics, timeline export.

The execution-time counterpart of ``repro.telemetry`` (which measures
joules): request-lifecycle and engine-step spans (:mod:`~repro.obs.spans`),
a labeled Counter/Gauge/Histogram registry (:mod:`~repro.obs.metrics`), a
typed telemetry-event schema shared with the trace store
(:mod:`~repro.obs.events`), and Chrome-trace/Perfetto export that merges
spans with ``MonitorSession`` energy windows so every span carries
attributed joules (:mod:`~repro.obs.export`).
"""
from repro.obs.events import (TelemetryEvent, coerce_event, events_from_meta,
                              events_to_meta, window_of)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.spans import (NULL_SPAN, GcSpans, Span, SpanRecord, Tracer,
                             span_or_null, span_tree)
from repro.obs.export import (chrome_trace, parse_chrome_trace,
                              session_energies, timeline_from_trace,
                              validate_chrome_trace, write_chrome_trace)

__all__ = [
    "TelemetryEvent", "coerce_event", "events_to_meta", "events_from_meta",
    "window_of",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Tracer", "Span", "SpanRecord", "NULL_SPAN", "span_tree", "GcSpans",
    "span_or_null",
    "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    "parse_chrome_trace", "timeline_from_trace", "session_energies",
]
