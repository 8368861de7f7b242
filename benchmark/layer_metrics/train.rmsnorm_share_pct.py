"""Kernels: device self time of the ``pt_rmsnorm_*`` Mosaic calls (forward and
backward, with and without the residual) over device busy time, the mean
over the devices."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "train")
    return pt.kernel_share_pct("pt_rmsnorm") if pt else None
