"""Kernels: device self time of the ``pt_retention_chunk`` Mosaic calls (a
prefill chunk of power retention from the state the prompt's previous chunk
left, once a layer a prefill call) over device busy time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_retention_chunk") if pt else None
