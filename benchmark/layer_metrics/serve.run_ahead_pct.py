"""Scheduler / cache: the share of the traced window's window programs —
decode rounds and prefill calls, one ``pt.serve.decode_round`` or
``pt.serve.prefill_chunk`` span each — that the engine's worker dispatched
while the program before them was still unread (``ahead=1`` on the span):
behind those the device did not wait for a host round trip. The engine
counts the same over its lifetime (``programs_run_ahead_total`` over
``decode_steps + prefill_chunks_total``), but the runners hand the readers a
fixed set of counters that holds neither, so the spans are read, from the
run's own ``.xplane.pb``. A program that does not say (the parent of the PR
that added the argument) reads as nothing."""
from benchmark.lib import harness, program_trace, xplane

UNIT = "%"
PROGRAMS = ("pt.serve.decode_round", "pt.serve.prefill_chunk")


def share_pct(flags):
    """``100 x (programs with ahead=1) / programs``; ``None`` without a
    program that says."""
    said = [int(f) for f in flags if f is not None]
    return 100.0 * sum(said) / len(said) if said else None


def ahead_flags(path, lo, hi):
    """The ``ahead`` argument of every program span that lies wholly in
    ``[lo, hi]`` ns (``None`` where a span carries none)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAMS and e.start_ns >= lo \
                        and e.start_ns + e.duration_ns <= hi:
                    out.append(dict(e.stats).get("ahead"))
    return out


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    if pt is None or pt.window is None:
        return None
    path = program_trace.find_run_xplane(harness.ROOT,
                                         program_trace.process_start())
    return share_pct(ahead_flags(path, *pt.window))
