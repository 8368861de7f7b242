"""Kernels: device self time of the ``pt_dsa_index_scores`` Mosaic calls (the
lightning indexer's scores against the paged index keys: once a layer that
owns an indexer, in a decode round and in a prefill chunk) over device busy
time."""
from benchmark.lib import program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.kernel_share_pct("pt_dsa_index_scores") if pt else None
