"""Device time of the Mosaic calls of one name inside the traced window:
what a roofline reader divides its floor by."""
from . import program_trace, xplane


def seconds_in_window(pt: "program_trace.ProgramTrace", prefix: str):
    """Summed duration (s) of the Mosaic calls whose instruction name carries
    ``prefix``, clipped to the traced window, over all devices; ``None``
    where the trace holds no such call."""
    if pt is None or pt.window is None:
        return None
    lo, hi = pt.window
    ns = sum(min(e, hi) - max(s, lo)
             for name, lines in pt.planes.items()
             if xplane.DEVICE_PLANE.match(name)
             for n, s, e in lines.get(xplane.OPS_LINE, ())
             if e > lo and s < hi and xplane.is_mosaic_kernel(n)
             and program_trace.kernel_of(xplane.short_name(n), prefix))
    return ns / 1e9 if ns else None
