"""Unified tracing & telemetry (docs/observability.md, ISSUE 5).

- :mod:`opensim_tpu.obs.trace` — contextvar-carried request span trees,
  Chrome-trace export, instant events for resilience-layer actions.
- :mod:`opensim_tpu.obs.metrics` — fixed-bucket latency histograms fed from
  the same spans, plus the one recording lock and label-value escaping.
- :mod:`opensim_tpu.obs.recorder` — the flight recorder behind
  ``GET /api/debug/requests``.

Import-light on purpose: stdlib only at import, imported from the engine hot
path (the first TraceContext imports ``jax.profiler`` for the write-through).
"""

from .trace import (  # noqa: F401
    PHASES,
    Span,
    TraceContext,
    current_span,
    current_trace,
    enabled,
    event,
    new_request_id,
    sanitize_request_id,
    span,
    start_trace,
    trace_scope,
    write_chrome,
)
from .metrics import RECORDER, escape_label_value  # noqa: F401
from .recorder import FLIGHT_RECORDER, FlightRecorder  # noqa: F401
