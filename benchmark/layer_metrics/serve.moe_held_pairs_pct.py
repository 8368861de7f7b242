"""Model step: the share of routed (token, choice) pairs that met an expert
this chip holds, ``moe_held_pairs_total / moe_pairs_total`` over the window
(16 of 256 experts held: 6.25 % under even routing)."""
UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pairs = counters.get("moe_pairs_total")
    if not pairs:
        return None
    return 100.0 * counters.get("moe_held_pairs_total", 0) / pairs
