"""Kernels: device self time in the traced window under ``pt.attention``: the
flash calls AND their glue (layout copies, the transposes around the kernel),
all passes, over device busy time; less ``train.flash_attention_share_pct`` it
is the glue. The program names the part (``jax.named_scope("pt.<part>")``:
``paddle_tpu.observability.trace.parts``) and JAX the pass;
``benchmark/lib/train_parts.py`` reads both from the device trace's op
metadata. A program that names no part reads as nothing."""
from benchmark.lib import train_parts

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    return train_parts.part_share(shapes, "attention")
