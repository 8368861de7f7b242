"""Kernels: the ungated (relu^2) held experts' grouped matmuls' share of their
roofline over the traced window — the least time the chip could take
(``benchmark/lib/moe_relu2_cost.py``: the two matrices of the experts that got
a row, their rows and 2 x rows x 2 x hidden x width operations, from the
counts the window programs handed back) over the ``gmm`` calls' measured
time. A cell whose experts have a gate hands no ``moe_relu2`` shape and reads
as nothing."""
from benchmark.lib import kernel_time, moe_relu2_cost, peaks, program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("moe_relu2")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "gmm")
    if not took:
        return None
    import jax

    t = shape["traced"]
    floor = moe_relu2_cost.floor_seconds(
        moe_relu2_cost.gmm_cost(t["rows"], t["experts_hit"], shape),
        peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor["seconds"] / took
