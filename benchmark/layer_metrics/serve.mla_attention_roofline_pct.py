"""Kernels: ``pt_mla_paged_attention``'s share of its roofline over the traced
window — the least time the chip could take for the window's calls
(``benchmark/lib/mla_cost.py``, from the cached positions the engine counted
for them: decode rounds by the larger of bytes / 819 GB/s and operations / 197
TFLOP/s, prefill chunks by their operations) over the calls' measured time."""
from benchmark.lib import kernel_time, mla_cost, peaks, program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("mla")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "pt_mla_paged_attention")
    if not took:
        return None
    import jax

    floor = mla_cost.traced_floor_seconds(
        shape, peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor / took
