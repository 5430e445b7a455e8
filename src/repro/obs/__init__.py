"""Serving observability (DESIGN.md §10): allocation-cheap metrics
(counters / fixed-bucket histograms with interpolated percentiles), a
bounded per-tick trace with request lifecycle spans and host phases
(``serve.*`` profiler spans), and JSONL / Chrome ``trace_event``
exporters.

Entry points: the engine owns a :class:`ServingTelemetry`
(``PagedServingEngine(telemetry=...)``, ``engine.dump_trace(path)``);
``tools/tracestats.py`` summarizes and validates dumped traces.
"""
from repro.obs.metrics import (Counter, Histogram, MetricsRegistry,
                               log_bucket_edges)
from repro.obs.trace import (PHASES, SCHEMA_VERSION, SPAN_KINDS, TICK_FIELDS,
                             Ring, ServingTelemetry)

__all__ = [
    "Counter", "Histogram", "MetricsRegistry", "log_bucket_edges",
    "PHASES", "Ring", "ServingTelemetry", "SCHEMA_VERSION", "SPAN_KINDS",
    "TICK_FIELDS",
]
