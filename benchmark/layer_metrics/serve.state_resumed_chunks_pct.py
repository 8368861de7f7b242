"""Scheduler and cache: the share of the window's prefill calls that started
from the recurrent state the prompt's previous chunk left
(``state_resumes_total`` over ``prefill_chunks_total``, the engine's own
counters): 0 where every prompt fits a bucket, (n - 1) / n for a prompt of n
chunks. A program without the counter reads as nothing."""

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    chunks = counters.get("prefill_chunks_total")
    resumed = counters.get("state_resumes_total")
    if not chunks or resumed is None:
        return None
    return 100.0 * resumed / chunks
