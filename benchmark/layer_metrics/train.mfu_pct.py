"""Model step: required FLOPs per token (benchmark/lib/flops.py) times the
tokens per second of the window, over chips times the published bf16 peak.
Recomputed operations are not counted. Left out where there is no chip."""
from benchmark.lib.flops import mfu_pct

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    peak = shapes.get("peak_flops_per_s")
    if shapes.get("kind") != "train" or not peak:
        return None
    return mfu_pct(shapes["flops_per_token"], counters["tokens_per_s"],
                   shapes["chips"], peak)
