"""Scheduler and cache: the median duration of the engine worker's
``pt.serve.state_install`` spans that lie in the traced window — writing one
admitted request's final prefill state (every layer's SSM state and conv
tail) over its slot's row of the state arenas, inside ``pt.serve.admit``."""
from benchmark.lib import program_trace

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.span_p50_ms("pt.serve.state_install") if pt else None
