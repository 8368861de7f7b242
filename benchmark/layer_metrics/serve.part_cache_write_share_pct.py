"""Scheduler and cache: device self time in the traced window under the window's
rows scattered through the page tables into the arenas (the index arithmetic,
the scatter, and the arena layout copies the compiler schedules for it), over
device busy time. The program names the part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and ``benchmark/lib/part_time.py``
reads it from the device trace's op metadata; a program that names no part
reads as nothing."""
from benchmark.lib import part_time

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return part_time.share(shapes, "cache_write")
