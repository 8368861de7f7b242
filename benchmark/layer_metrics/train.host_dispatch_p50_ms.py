"""Model step: the median duration of the stepping thread's
``pt.train.host_dispatch`` spans in the traced window — Python and dispatch
until the compiled step call returns."""
from benchmark.lib import program_trace

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "train")
    return pt.span_p50_ms("pt.train.host_dispatch") if pt else None
