"""Model step: device self time in the traced window under ``pt.mlp`` (gate,
up, silu x up, down, the residual add behind it and the ``mp`` all-reduce
behind ``down_proj``), all passes, over device busy time. The program names the
part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and JAX the pass;
``benchmark/lib/train_parts.py`` reads both from the device trace's op
metadata. A program that names no part reads as nothing."""
from benchmark.lib import train_parts

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return train_parts.part_share(shapes, "mlp")
