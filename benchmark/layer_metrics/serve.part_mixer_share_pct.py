"""Model step: device self time in the traced window under Falcon-H1's Mamba-2
branch: in-projection, conv, ``pt_ssm_step`` or the chunked scan, gated norm,
out-projection and the join with the attention branch, over device busy time.
The program names the part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and ``benchmark/lib/part_time.py``
reads it from the device trace's op metadata; a program that names no part
reads as nothing."""
from benchmark.lib import part_time

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return part_time.share(shapes, "mixer")
