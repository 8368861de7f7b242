"""Kernels: the residual path's share of its roofline over the traced window
— the least time the chip could take for the (token, sublayer) mixes the
window's programs ran (``benchmark/lib/mhc_cost.py``: the stream read once
and written once, the sublayer's input out and output in, at 819 GB/s; the
mixes from the engine's ``mhc_mix_tokens_total`` between the profiler's
start and stop) over the device self time of the ops under ``pt.mhc`` — the
SCOPE's ops, kernel or not, so the reading survives a change of
implementation and cannot pass 100 while the cost is a floor."""
from benchmark.lib import mhc_cost, peaks

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("mhc")
    if not shape or not shape.get("traced") or not shape["traced"]["mixes"]:
        return None
    got = mhc_cost.traced_scope_ns(shapes, "mhc")
    if got is None:
        return None
    import jax

    floor = mhc_cost.floor_seconds(
        mhc_cost.mix_cost(shape["traced"]["mixes"], shape),
        peaks.peaks_for(jax.devices()[0].device_kind))
    return 100.0 * floor["seconds"] / (got[0] / 1e9)
