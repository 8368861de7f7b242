"""Mesh: the share of the traced window in which a device's innermost
running op is a collective (it sits in the collective, or waits for an
asynchronous one, and computes nothing), averaged over the devices."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    if shapes.get("kind") != "train" or not trace or \
            shapes.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
