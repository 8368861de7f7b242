"""Device: 1 - (union of device-op intervals) / traced window."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    if shapes.get("kind") != "serve" or not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
