"""Scheduler and cache: the share of the traced window in which the worker's
innermost span is its own host work — ``page_table``, ``decode_build``,
``emit``, or the self time of ``admit`` / ``decode_round`` (not dispatch, not
a wait for the device or for a request)."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.owned_pct(program_trace.SERVE,
                        program_trace.SCHED_SPANS) if pt else None
