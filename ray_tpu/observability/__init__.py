"""ray_tpu.observability: the distributed tracing plane.

See docs/OBSERVABILITY.md for the span API, the propagation contract and
the timeline workflow. Quick tour::

    from ray_tpu.observability import get_tracer

    with get_tracer().start_span("my.operation", attrs={"k": "v"}):
        ...  # children (tasks, actor calls, RPCs) join this trace

Exports land in the GCS and are served by the dashboard
(`/api/traces/<trace_id>`, `/api/timeline`) or the CLI
(`python -m ray_tpu.observability timeline`).

The start-up timeline (lifecycle spans, always on) is read from the
session directory: `startup_report()`, `python -m ray_tpu.observability
startup`; `compile_watch()` gives this process's compile counters.
"""

from ray_tpu.observability.tracing import (  # noqa: F401
    NOOP_SPAN,
    FlightRecorder,
    Span,
    Tracer,
    capture,
    current_ctx,
    enabled,
    format_traceparent,
    get_tracer,
    parse_traceparent,
    refresh_from_config,
)
from ray_tpu.observability.export import (  # noqa: F401
    chrome_trace_events,
    span_tree,
)
from ray_tpu.observability.compile import compile_watch  # noqa: F401
from ray_tpu.observability.startup import (  # noqa: F401
    format_waterfall,
    startup_report,
)

__all__ = [
    "FlightRecorder", "NOOP_SPAN", "Span", "Tracer", "capture",
    "chrome_trace_events", "compile_watch", "current_ctx", "enabled",
    "format_traceparent", "format_waterfall", "get_tracer",
    "parse_traceparent", "refresh_from_config", "span_tree",
    "startup_report",
]
