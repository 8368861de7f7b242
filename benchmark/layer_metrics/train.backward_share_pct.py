"""Model step: device self time in the traced window of every op of the
backward pass proper (``transpose(`` in its name stack and no
``rematted_computation``), whatever its part, over device busy time. The
program names the part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and JAX the pass;
``benchmark/lib/train_parts.py`` reads both from the device trace's op
metadata. A program that names no part reads as nothing."""
from benchmark.lib import train_parts

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return train_parts.phase_share(shapes, "backward")
