"""Model step: the median duration of the engine worker's
``pt.serve.decode_round`` spans that lie in the traced window — one decode
round as the worker sees it, build to emit, with no wait in it."""
from benchmark.lib import program_trace

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.span_p50_ms("pt.serve.decode_round") if pt else None
