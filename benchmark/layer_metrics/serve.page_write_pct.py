"""Scheduler / cache: of the tokens the prefill calls of the traced window
wrote into the paged cache, the share that went in as whole pages (a one-row
prefill whose window is whole pages writes them page by page; a call of a
bucket inside a page, like every decode round, scatters rows). A prefill call
is a ``pt.serve.prefill_chunk`` span: ``W`` is the tokens it wrote, and its
``pages`` argument — the pages it wrote whole, 0 for a program that scatters
rows — says how. The engine counts the same over its lifetime
(``kv_pages_written_total`` x ``page_len`` over
``prefill_window_tokens_total``), but the runners hand the readers a fixed set
of counters that holds neither, so the spans are read, from the run's own
``.xplane.pb``, as ``serve.carried_rounds_pct`` reads its argument. A program
whose prefill calls do not say (the parent of the PR that added the argument)
reads as nothing."""
from benchmark.lib import harness, program_trace, xplane

UNIT = "%"
CHUNK = "pt.serve.prefill_chunk"


def share_pct(calls):
    """``calls``: ``(W, pages)`` of every prefill call (``pages`` ``None``
    where a span carries none). ``100 x (tokens of the calls that wrote
    pages) / (tokens of the calls that say)``; ``None`` without a call that
    says."""
    said = [(int(w), int(p)) for w, p in calls if p is not None]
    tokens = sum(w for w, _p in said)
    if not tokens:
        return None
    return 100.0 * sum(w for w, p in said if p > 0) / tokens


def calls(path, lo, hi):
    """``[(W, pages), ...]`` over the prefill calls that lie wholly in
    ``[lo, hi]`` ns."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != CHUNK or e.start_ns < lo \
                        or e.start_ns + e.duration_ns > hi:
                    continue
                stats = dict(e.stats)
                out.append((stats.get("W", 0), stats.get("pages")))
    return out


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    if pt is None or pt.window is None:
        return None
    path = program_trace.find_run_xplane(harness.ROOT,
                                         program_trace.process_start())
    return share_pct(calls(path, *pt.window))
