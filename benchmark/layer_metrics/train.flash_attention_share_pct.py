"""Kernels: device self time of the ``pt_flash_*`` Mosaic calls (forward, dK/dV
and dQ backward) over device busy time, the summed over the devices."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "train")
    return pt.kernel_share_pct("pt_flash") if pt else None
