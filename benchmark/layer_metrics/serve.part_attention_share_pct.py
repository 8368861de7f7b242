"""Kernels: device self time in the traced window under the engine's attention
callables: the paged / latent / ranged kernel AND what surrounds it (the
query's padding, the slices and the join of a carrying program's two calls,
layout copies scheduled for the kernel) — against the kernel's own share
(``serve.*_attention_share_pct``) the difference is glue, over device busy
time. The program names the part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and ``benchmark/lib/part_time.py``
reads it from the device trace's op metadata; a program that names no part
reads as nothing."""
from benchmark.lib import part_time

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return part_time.share(shapes, "attention")
