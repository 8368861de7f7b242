"""Kernels: device self time of the ``pt_ranged_attention_full`` and
``pt_ranged_attention_window`` Mosaic calls (grouped-query attention against
the paged cache of two layer kinds, over the pages in range: once a layer in a
decode round and in a prefill chunk) over device busy time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    if not shapes.get("ranged"):
        return None
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_ranged_attention") if pt else None
