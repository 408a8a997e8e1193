"""Observability: off-process metrics export and in-memory spans
(DESIGN.md §15)."""
from repro.obs.metrics import (CallbackSink, Emitter, JsonlSink,
                               MetricsConfig, MetricsSink, NullSink,
                               RingSink, TeeSink, make_sink)
from repro.obs.spans import RECORDER, Span, SpanRecorder, span, totals

__all__ = [
    "CallbackSink", "Emitter", "JsonlSink", "MetricsConfig",
    "MetricsSink", "NullSink", "RECORDER", "RingSink", "Span",
    "SpanRecorder", "TeeSink", "make_sink", "span", "totals",
]
