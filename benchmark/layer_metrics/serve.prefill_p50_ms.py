"""Model step: the engine's own ``prefill`` spans (one window-step call over
all slots plus its host work), median over the window's admissions."""
import statistics

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    xs = spans.get("prefill_ms")
    return statistics.median(xs) if xs else None
