"""Scheduler and cache: device-idle time that falls while the worker's
innermost span is its own host work (``program_trace.SCHED_SPANS``), over
the traced window: the part of ``serve.device_idle_pct`` the scheduler
owns. The rest is dispatch and sync latency, or ``idle_wait``."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.idle_pct(program_trace.SERVE,
                       program_trace.SCHED_SPANS) if pt else None
