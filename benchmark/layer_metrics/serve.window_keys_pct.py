"""Scheduler and cache: the keys the window layers' attention calls scored
over the window (``attn_keys_window_total``) as a share of what the same
calls would have scored with no window — the full layers' keys
(``attn_keys_full_total``) scaled by the two kinds' layer counts: about 512
over the mean context in range. 100 means the window is not applied."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    shape = shapes.get("ranged")
    full = counters.get("attn_keys_full_total")
    if not shape or not full:
        return None
    layers = shape["layers"]
    if not layers["full"]["count"] or not layers["window"]["count"]:
        return None
    unwindowed = full * layers["window"]["count"] / layers["full"]["count"]
    return 100.0 * counters.get("attn_keys_window_total", 0) / unwindowed
