"""Model step: device self time in the traced window under an expert layer's
routing: scores, top-k, the sort and gather into the grouped matmuls' layout,
the activation between them and the weighted combine back (everything of
``moe_held_experts_mlp`` that is not a grouped matmul), over device busy time.
The program names the part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and ``benchmark/lib/part_time.py``
reads it from the device trace's op metadata; a program that names no part
reads as nothing."""
from benchmark.lib import part_time

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return part_time.share(shapes, "router")
