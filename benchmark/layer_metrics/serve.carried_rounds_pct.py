"""Scheduler / cache: of the decode steps in the traced window, the share that
rode a prefill call instead of holding the device alone (the carried step: in
an engine whose model qualifies, a prompt's call of the largest bucket runs
the running sequences' decode step beside its own row). A decode step is a
``pt.serve.decode_round`` span, or a ``pt.serve.prefill_chunk`` span whose
``carried`` argument — the live rows it carried — is above 0. The engine
counts the same over its lifetime (``rounds_carried_total`` over
``decode_steps``), but the runners hand the readers a fixed set of counters
that holds neither, so the spans are read, from the run's own ``.xplane.pb``,
as ``serve.run_ahead_pct`` reads its argument. A program whose prefill calls
do not say (the parent of the PR that added the argument) reads as nothing."""
from benchmark.lib import harness, program_trace, xplane

UNIT = "%"
ROUND, CHUNK = "pt.serve.decode_round", "pt.serve.prefill_chunk"


def share_pct(rounds, carried):
    """``rounds``: decode rounds of their own; ``carried``: the ``carried``
    argument of every prefill call (``None`` where a span carries none).
    ``100 x (calls that carried a live row) / (those + rounds)``; ``None``
    without a call that says, or without a decode step."""
    said = [int(c) for c in carried if c is not None]
    rode = sum(1 for c in said if c > 0)
    if not said or not rode + rounds:
        return None
    return 100.0 * rode / (rode + rounds)


def steps(path, lo, hi):
    """``(rounds, [carried, ...])`` over the program spans that lie wholly in
    ``[lo, hi]`` ns."""
    from jax.profiler import ProfileData

    rounds, carried = 0, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.start_ns < lo or e.start_ns + e.duration_ns > hi:
                    continue
                if e.name == ROUND:
                    rounds += 1
                elif e.name == CHUNK:
                    carried.append(dict(e.stats).get("carried"))
    return rounds, carried


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    if pt is None or pt.window is None:
        return None
    path = program_trace.find_run_xplane(harness.ROOT,
                                         program_trace.process_start())
    return share_pct(*steps(path, *pt.window))
