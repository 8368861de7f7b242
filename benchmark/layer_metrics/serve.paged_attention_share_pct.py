"""Kernels: device self time of the ``pt_paged_attention`` Mosaic calls over
device busy time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_paged_attention") if pt else None
