"""Client side: gap between consecutive streamed tokens, median of all gaps of
the window: one decode round, as the client sees it."""
from benchmark.lib.stats import percentile

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    xs = spans.get("itl_ms")
    return percentile(xs, 50) if xs else None
