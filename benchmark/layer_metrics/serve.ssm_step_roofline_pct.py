"""Kernels: ``pt_ssm_step``'s share of its roofline — the least time the
chip could take for one call (``benchmark/lib/ssm_cost.py``: 2 x the state
bytes of the rows the call covers + its ``x, B, C, dt``, over the published
HBM bandwidth; the call is bound by bytes) over the measured time of a call,
summed over the calls that lie wholly in the traced window."""
from benchmark.lib import peaks, program_trace, ssm_cost, xplane

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("ssm_step")
    pt = program_trace.current(shapes, "serve")
    if pt is None or shape is None or pt.window is None:
        return None
    lo, hi = pt.window
    calls = [e - s for name, lines in pt.planes.items()
             if xplane.DEVICE_PLANE.match(name)
             for n, s, e in lines.get(xplane.OPS_LINE, ())
             if s >= lo and e <= hi and xplane.is_mosaic_kernel(n)
             and program_trace.kernel_of(xplane.short_name(n), "pt_ssm_step")]
    if not calls:
        return None
    import jax

    floor = ssm_cost.floor_seconds(
        shape, peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * len(calls) * floor["seconds"] / (sum(calls) / 1e9)
