"""Client side: from when a request was due to its first streamed token, 95th
percentile of the window's requests. The tail of a few tens of ten-second
requests: two runs of one schedule differ by up to 8 % (PR 23) and even the
mean by 2-4 %, so time to first token is recorded here and carries no bound."""
from benchmark.lib.stats import percentile

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    xs = spans.get("ttft_ms")
    return percentile(xs, 95) if xs else None
