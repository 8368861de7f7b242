"""Device: device self time in the traced window under NO part — ops whose name
stack holds none, and the ops of a run in which nothing names one; the readers'
own honesty: the eight part shares add up to 100 — over device busy time. The
program names the part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and JAX the pass;
``benchmark/lib/train_parts.py`` reads both from the device trace's op
metadata. A program that names no part reads as nothing."""
from benchmark.lib import train_parts

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return train_parts.part_share(shapes, train_parts.UNSCOPED)
