"""Observability plane: request tracing, decision attribution, export.

Four modules, one per question the aggregate metrics can't answer:

  * :mod:`repro.obs.trace`    — "which stage owns the p99?"
  * :mod:`repro.obs.explain`  — "why did THIS request miss?"
  * :mod:`repro.obs.export`   — "what is the stack doing right now?"
  * :mod:`repro.obs.compiles` — "did anything compile while serving?"

See DESIGN.md §18.
"""
from repro.obs.compiles import CompileLog, CompileSpan, compile_log
from repro.obs.explain import build_why, effective_edges
from repro.obs.export import (EventLog, MetricsExporter, REQUIRED_FAMILIES,
                              prometheus_text)
from repro.obs.trace import (NULL_TRACE, STAGES, RequestTrace, Span,
                             StageClock, TraceConfig, Tracer)

__all__ = [
    "NULL_TRACE", "STAGES", "RequestTrace", "Span", "StageClock",
    "TraceConfig", "Tracer", "build_why", "effective_edges", "EventLog",
    "MetricsExporter", "REQUIRED_FAMILIES", "prometheus_text",
    "CompileLog", "CompileSpan", "compile_log",
]
