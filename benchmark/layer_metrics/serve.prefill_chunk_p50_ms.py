"""Model step: the median duration of the engine worker's
``pt.serve.prefill_chunk`` spans that lie in the traced window — one window
call of a prompt's chunked prefill (dispatch to done: every chunk but a
prompt's last is waited for inside its span; the last is read in
``pt.serve.prefill_sync``), inside ``pt.serve.admit``."""
from benchmark.lib import program_trace

UNIT = "ms"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    return pt.span_p50_ms("pt.serve.prefill_chunk") if pt else None
