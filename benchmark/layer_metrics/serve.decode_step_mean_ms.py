"""Model step: the window less the time inside prefill spans, over the
decode rounds the engine counted. A mean, and an upper estimate: time the
worker waited with no sequence in a slot is in it (the engine has no span
for that yet)."""
UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    steps = counters.get("decode_steps")
    if not steps or shapes.get("kind") != "serve":
        return None
    prefill_s = sum(spans.get("prefill_ms", ())) / 1e3
    return 1e3 * (counters["window_s"] - prefill_s) / steps
