"""Kernels: the ``pt_ranged_attention_*`` calls' share of their roofline over
the traced window — the least time the chip could take for them
(``benchmark/lib/kv_attention_cost.py``, from the keys in range the engine
counted by layer kind: decode rounds by the larger of bytes / 819 GB/s and
operations / 197 TFLOP/s, prefill chunks by operations) over the calls'
measured time."""
from benchmark.lib import kernel_time, kv_attention_cost, peaks, program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("ranged")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "pt_ranged_attention")
    if not took:
        return None
    import jax

    floor = kv_attention_cost.traced_floor_seconds(
        shape, peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor / took
