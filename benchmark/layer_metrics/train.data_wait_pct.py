"""Input pipeline: the share of the window the host spent inside
``next(batch)`` (benchmark span; it overlaps the device's step, so it
stalls the step only once it nears 100 % of the step's wall)."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    if shapes.get("kind") != "train" or not counters.get("window_s"):
        return None
    return 100.0 * counters["data_wait_s"] / counters["window_s"]
