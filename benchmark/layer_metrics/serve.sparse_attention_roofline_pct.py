"""Kernels: ``pt_mla_sparse_attention``'s share of its roofline over the
traced window — the least time the chip could take for the attention THE
SELECTION LEAVES (``benchmark/lib/dsa_cost.py``, from the keys the engine
counted as selected: decode rows by the larger of the selected rows' bytes /
819 GB/s and operations / 197 TFLOP/s, prefill chunks by operations alone) over
the calls' measured time. The kernel walks every visible page and masks, so
this reads how much of its time the selection would leave a gather to do."""
from benchmark.lib import dsa_cost, kernel_time, peaks, program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("dsa")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "pt_mla_sparse_attention")
    if not took:
        return None
    import jax

    floor = dsa_cost.attend_floor_seconds(
        shape, peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor / took
