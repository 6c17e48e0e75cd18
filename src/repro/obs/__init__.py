"""Observability: process-wide tracing, fleet event log, exporters.

See :mod:`repro.obs.trace` for the span recorder and the Chrome-trace /
Prometheus exporters, :mod:`repro.obs.events` for the fleet event
taxonomy, :mod:`repro.obs.calibration` for the modeled-vs-measured
calibration ledger and memory-margin gauges, :mod:`repro.obs.slo` for
deadline-attainment accounting, :mod:`repro.obs.http` for the live
metrics endpoint, and ``docs/observability.md`` for the user guide.
"""

from .calibration import (CAL_EVENT_KINDS, CalibrationKey,
                          CalibrationLedger, CalibrationStat, MemoryMargin,
                          calibration_prometheus, memory_calibration)
from .events import FLEET_EVENT_KINDS, fleet_event, fleet_event_log
from .http import MetricsServer, metrics_text
from .slo import SLOTier, slo_prometheus, slo_report
from .trace import (LAYER, PHASE_CATEGORIES, InstantEvent, Span, SpanHandle,
                    Tracer, begin, chrome_trace, context, enabled, end,
                    event, get_tracer, incr, prometheus_snapshot,
                    set_tracer, span, write_chrome_trace)

__all__ = [
    "CAL_EVENT_KINDS", "CalibrationKey", "CalibrationLedger",
    "CalibrationStat", "MemoryMargin", "calibration_prometheus",
    "memory_calibration", "MetricsServer", "metrics_text",
    "SLOTier", "slo_prometheus", "slo_report",
    "FLEET_EVENT_KINDS", "fleet_event", "fleet_event_log",
    "LAYER", "PHASE_CATEGORIES", "InstantEvent", "Span", "SpanHandle",
    "Tracer", "begin", "chrome_trace", "context", "enabled", "end", "event",
    "get_tracer", "incr", "prometheus_snapshot", "set_tracer", "span",
    "write_chrome_trace",
]
