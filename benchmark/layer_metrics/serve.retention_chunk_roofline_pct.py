"""Kernels: ``pt_retention_chunk``'s share of its roofline over the traced
window — the least time the chip could take for the window's chunk calls
(``benchmark/lib/retention_cost.py``: the larger of their operations over
the published bf16 peak and their bytes over the HBM bandwidth, from the VALID
positions the engine counted for them, ``retention_chunk_tokens_total``, at
the MINIMAL ``phi``: 8256 rows) over the calls' measured time. A padded
position is no work."""
from benchmark.lib import kernel_time, peaks, program_trace, retention_cost

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("retention")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "pt_retention_chunk")
    t = shape["traced"]
    if not took or not t["chunk_tokens"]:
        return None
    import jax

    floor = retention_cost.chunk_floor_seconds(
        shape, t["chunk_tokens"], t["chunk_calls"],
        peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor["seconds"] / took
