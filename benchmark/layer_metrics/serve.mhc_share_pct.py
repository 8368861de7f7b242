"""Model step: device self time in the traced window of the ops under the
nested ``pt.mhc`` scope (``paddle_tpu.observability.trace.parts.SUBPARTS``:
the residual path of a model whose stream is several rows — a sublayer's
mixing maps, Sinkhorn and all, and the mix itself; work that sits INSIDE the
parts ``attn_proj`` and ``mlp`` and is no part itself) over device busy
time. An op counts where its own name stack holds the scope, kernel or not
(``benchmark/lib/mhc_cost.py:scope_ns``, as ``serve.indexer_share_pct``
reads its scope). A program that has no such scope reads as nothing."""
from benchmark.lib import mhc_cost

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    got = mhc_cost.traced_scope_ns(shapes, "mhc")
    if got is None:
        return None
    took, busy = got
    return 100.0 * took / busy
