"""Kernels: device self time of the ``pt_mla_window_attention`` Mosaic calls
(absorbed latent attention over a sliding window, against the window layers'
paged latent rows: once a window layer in a decode round and in a prefill
chunk) over device busy time. A program without the kernel reads as
nothing."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_mla_window_attention") if pt else None
