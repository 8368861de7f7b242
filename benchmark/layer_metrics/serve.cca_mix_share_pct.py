"""Model step: device self time in the traced window of the ops under the
nested ``pt.cca_mix`` scope (``paddle_tpu.observability.trace.parts.SUBPARTS``:
what a compressed convolutional attention does to its latent queries and keys
between the projections and the cache — the two causal convolutions behind
the slot's tail, the q-k mean, the L2 norm and temperature, the partial RoPE,
the value shift; work INSIDE the part ``attn_proj``) over device busy time. An
op counts where its own name stack holds the scope, kernel or not
(``benchmark/lib/mhc_cost.py:traced_scope_ns``, as ``serve.ssm_scan_share_pct``
reads its scope). A program that has no such scope reads as nothing."""
from benchmark.lib import mhc_cost

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    got = mhc_cost.traced_scope_ns(shapes, "cca_mix")
    if got is None:
        return None
    took, busy = got
    return 100.0 * took / busy
