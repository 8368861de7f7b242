"""Kernels: device self time of the held experts' grouped matmuls (the
megablox ``gmm`` Mosaic calls of ``moe_held_experts_mlp``: gate, up and down,
once an expert layer in a decode round and in a prefill chunk) over device
busy time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("gmm") if pt else None
