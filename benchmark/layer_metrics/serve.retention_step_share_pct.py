"""Kernels: device self time of the ``pt_retention_step`` Mosaic calls (one
decode step of power retention over the slot-indexed state arenas, once a
layer a decode round) over device busy time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_retention_step") if pt else None
