"""Kernels: ``pt_dsa_index_scores``'s share of its roofline over the traced
window — the least time the chip could take for the window's index scores
(``benchmark/lib/dsa_cost.py``, from the cached positions the engine counted as
scored: decode rows by the larger of their index keys' bytes / 819 GB/s and
operations / 197 TFLOP/s, prefill chunks by operations alone) over the calls'
measured time."""
from benchmark.lib import dsa_cost, kernel_time, peaks, program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("dsa")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "pt_dsa_index_scores")
    if not took:
        return None
    import jax

    floor = dsa_cost.index_floor_seconds(
        shape, peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor / took
