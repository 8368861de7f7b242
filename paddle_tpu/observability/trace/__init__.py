"""paddle_tpu.observability.trace — device-truth tracing.

Layers on top of the PR-4 telemetry hub (see docs/observability.md,
"Device-truth tracing"):

- **XPlane ingestion** (``capture_steps`` / ``xplane``): capture a
  ``jax.profiler`` trace around a step window, read its ``.xplane.pb``,
  correlate device events back to StepTimeline steps/phases — real
  ``device_compute_us`` (every mode), a top-k device op table, and
  host/device overlap efficiency, and device time by part of a model step
  — a served window program's or a train step's, the latter by phase too
  (``by_part``; ``tools/program_parts.py`` on a file);
- **parts** (``part``, ``PARTS``): the one vocabulary of
  ``jax.named_scope`` names (``pt.norm``, ``pt.attn_proj``, ...) the engine,
  the served blocks and ``models/llama.py``'s layers put on their work, so
  that a device trace says which part of the model asked for each op —
  and, of a train step, which pass ran it (``parts.phase_of``);
- **spans** (``span``): the program's one span primitive — a
  ``jax.profiler.TraceAnnotation`` in whatever profiler trace is running,
  and a row in the tracer's worker ring (``pt.serve.*``, ``pt.train.*``);
- **request-scoped tracing** (``tracer()``): a propagated trace ID per
  serving request (admission -> queue -> coalesce -> execute / prefill ->
  decode -> completion) plus the GenerationEngine slot-occupancy track,
  exported as chrome-trace/Perfetto JSON;
- **flight recorder** (``flight_recorder()``): a bounded ring of recent
  step timelines + runtime events with an anomaly detector
  (regression/stall/burst) that auto-dumps a ``pd_dump`` diagnostic
  bundle on trigger, SIGQUIT, or preemption.
"""
from __future__ import annotations

from .capture import (  # noqa: F401
    StepTraceCapture, capture_steps, device_trace_provider, last_correlation,
)
from .flight import FlightRecorder, dump_bundle, flight_recorder  # noqa: F401
from .parts import PARTS, part  # noqa: F401
from .request_trace import RequestTracer, span, tracer  # noqa: F401
from .xplane import (  # noqa: F401
    CorrelatedTrace, correlate, correlate_logdir, find_xplane, read_xplane,
)

__all__ = [
    "StepTraceCapture", "capture_steps", "last_correlation",
    "device_trace_provider", "CorrelatedTrace", "correlate",
    "correlate_logdir", "find_xplane", "read_xplane", "span",
    "RequestTracer", "tracer", "FlightRecorder", "flight_recorder",
    "dump_bundle", "PARTS", "part",
]
