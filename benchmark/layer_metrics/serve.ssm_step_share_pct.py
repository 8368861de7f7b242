"""Kernels: device self time of the ``pt_ssm_step`` Mosaic calls (one step of
the Mamba-2 recurrence over the slot-indexed state arena, once a layer a
decode round) over device busy time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_ssm_step") if pt else None
