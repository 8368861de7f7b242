"""Kernels: device self time of the ``pt_rope`` Mosaic calls over device busy
time, the summed over the devices."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "train")
    return pt.kernel_share_pct("pt_rope") if pt else None
