"""Kernels: device self time of the ``pt_mla_sparse_attention`` Mosaic calls
(absorbed latent attention over the keys each query selected: once a layer in
a decode round and in a prefill chunk) over device busy time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_mla_sparse_attention") if pt else None
