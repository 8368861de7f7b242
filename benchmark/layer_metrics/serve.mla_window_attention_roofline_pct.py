"""Kernels: ``pt_mla_window_attention``'s share of its roofline over the
traced window — the least time the chip could take for what the WINDOW
requires of the window's calls (``benchmark/lib/mla_window_cost.py``, from the
keys inside the queries' windows that the engine counted: decode rows by the
larger of bytes / 819 GB/s and operations / 197 TFLOP/s, prefill chunks by
their operations) over the calls' measured time. The kernel walks whole blocks
and masks what lies behind the window, so the walk's surplus
(``serve.mla_window_walk_pct``) reads here as a share below 100."""
from benchmark.lib import kernel_time, mla_window_cost, peaks, program_trace

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("mla_window")
    if not shape or not shape.get("traced"):
        return None
    took = kernel_time.seconds_in_window(
        program_trace.current(shapes, "serve"), "pt_mla_window_attention")
    if not took:
        return None
    import jax

    floor = mla_window_cost.traced_floor_seconds(
        shape, peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor / took
