"""Model step: device self time in the traced window of the ops under the
nested ``pt.ssm_scan`` scope (``paddle_tpu.observability.trace.parts.SUBPARTS``:
a Mamba-2 layer's recurrence — the chunked scan of a prefill call, from zero
or from the state the prompt's previous chunk left, and the one-step kernel of
a decode round; work INSIDE the part ``mixer``) over device busy time. An op
counts where its own name stack holds the scope, kernel or not
(``benchmark/lib/mhc_cost.py:traced_scope_ns``, as ``serve.mhc_share_pct``
reads its scope). A program that has no such scope reads as nothing."""
from benchmark.lib import mhc_cost

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    got = mhc_cost.traced_scope_ns(shapes, "ssm_scan")
    if got is None:
        return None
    took, busy = got
    return 100.0 * took / busy
