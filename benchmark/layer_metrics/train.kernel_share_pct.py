"""Kernels: device self time inside Mosaic (Pallas) custom calls over device
busy time, from the trace."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    if shapes.get("kind") != "train" or not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]
