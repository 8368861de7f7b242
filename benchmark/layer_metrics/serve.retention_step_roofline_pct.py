"""Kernels: ``pt_retention_step``'s share of its roofline over the traced
window — the least time the chip could take to advance the (row, layer) pairs
the engine counted for the window's rounds (``retention_steps_total``;
``benchmark/lib/retention_cost.py``: 2 x the float32 state of those rows at
the MINIMAL ``phi``, 8256 x 128 + 8256 a K/V head, + the small operands, over
the published HBM bandwidth; the call is bound by bytes) over the calls'
measured time. A row the round does not advance is no work: a kernel that
reads it anyway reads lower."""
from benchmark.lib import kernel_time, peaks, program_trace, retention_cost

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("retention")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "pt_retention_step")
    if not took or not shape["traced"]["step_rows"]:
        return None
    import jax

    floor = retention_cost.step_floor_seconds(
        shape, shape["traced"]["step_rows"],
        peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor["seconds"] / took
