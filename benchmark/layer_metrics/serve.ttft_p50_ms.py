"""Client side: from due to first streamed token, median of the window's
requests (recorded, no bound)."""
from benchmark.lib.stats import percentile

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    xs = spans.get("ttft_ms")
    return percentile(xs, 50) if xs else None
