"""Round-trace telemetry: span tracing + unified metrics registry.

See ``obs/README.md`` for the span taxonomy, JSONL schema, Prometheus
metric names, and the off-by-default / zero-retrace contract.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.telemetry import (NULL_TELEMETRY, NullTelemetry,  # noqa: F401
                                 Telemetry, as_telemetry, host_read)
from repro.obs.trace import (SCHEMA_VERSION, Span, Tracer,  # noqa: F401
                             load_jsonl, validate_events)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_TELEMETRY", "NullTelemetry", "Telemetry", "as_telemetry",
    "host_read", "SCHEMA_VERSION", "Span", "Tracer", "load_jsonl",
    "validate_events",
]
