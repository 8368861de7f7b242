"""Scheduler: the engine's own ``queue`` spans (submit to admission) of the
requests admitted in the window, 95th percentile."""
from benchmark.lib.stats import percentile

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    xs = spans.get("queue_ms")
    return percentile(xs, 95) if xs else None
