"""repro.prof — integrated profiling of dispatch events (paper §4.3),
request-level span traces, and serve metrics."""

from .export import (compile_summary, export_perfetto, export_table,
                     parse_table, perfetto_trace, queue_chart,
                     render_queue_chart, render_request_gantt)
from .metrics import (DEFAULT_TICK_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry, StatsView)
from .profiler import (InstType, Prof, ProfAgg, ProfInfo, ProfInst,
                       ProfOverlap, Sort)
from .trace import RequestTrace, Span, SpanKind, TraceCollector, host_span

__all__ = [
    "Prof", "ProfAgg", "ProfInfo", "ProfInst", "ProfOverlap", "InstType",
    "Sort", "compile_summary", "export_table", "parse_table", "queue_chart",
    "render_queue_chart", "perfetto_trace", "export_perfetto",
    "render_request_gantt", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "StatsView", "DEFAULT_TICK_BUCKETS", "SpanKind",
    "Span", "RequestTrace", "TraceCollector", "host_span",
]
