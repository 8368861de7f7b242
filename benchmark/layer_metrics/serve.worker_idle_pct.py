"""Scheduler and cache: the share of the traced window the engine's worker
spent in ``pt.serve.idle_wait`` — no sequence in a slot, nothing queued."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.owned_pct(program_trace.SERVE,
                        ("pt.serve.idle_wait",)) if pt else None
