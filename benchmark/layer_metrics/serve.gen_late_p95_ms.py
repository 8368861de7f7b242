"""Load generator: how late a request was sent after it was due (95th
percentile). A starved generator must not read as a fast server."""
from benchmark.lib.stats import percentile

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    xs = spans.get("gen_late_ms")
    return percentile(xs, 95) if xs else None
