"""Model step: device self time in the traced window of the ops under the
nested ``pt.indexer`` scope (``paddle_tpu.observability.trace.parts.SUBPARTS``:
the index projections, the index scores and the exact top-k of a learned
sparse attention — work that sits INSIDE the parts ``attn_proj`` and
``attention`` and is no part itself) over device busy time.
``benchmark/lib/part_time.py`` reads the scopes from the device trace's op
metadata (``read_devices`` given the subpart's one-name vocabulary); an op
counts where its own name stack holds the scope — ``shares_pct``'s rule that
an unnamed op goes with its neighbour is for a vocabulary that PARTITIONS the
step and would hand this one name every op of a run. A program that has no
such scope reads as nothing."""
from benchmark.lib import harness, part_time, program_trace, xplane

UNIT = "%"


def reduce(trace, counters, spans, shapes):
    pt = program_trace.current(shapes, "serve")
    if pt is None or pt.window is None:
        return None
    try:
        from paddle_tpu.observability.trace.parts import SUBPARTS
    except ImportError:       # a program without the nested vocabulary
        return None
    path = program_trace.find_run_xplane(harness.ROOT,
                                         program_trace.process_start())
    if path is None:
        return None
    try:
        devices = part_time.read_devices(path, SUBPARTS)
    except (ValueError, IndexError):    # not the schema part_time reads
        return None
    return share_pct(devices, "indexer", *pt.window)


def share_pct(devices, name, lo, hi):
    """100 x self time in ``[lo, hi)`` of the ops whose own scope is ``name``
    over busy time, over ``devices`` (each ``(ops, runs)`` as
    ``part_time.read_devices`` gives them); ``None`` where no op has it."""
    took = busy = 0.0
    for ops, _runs in devices:
        ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        clipped = program_trace.clip(
            [(i, s, e) for i, (_p, s, e) in enumerate(ops)], lo, hi)
        busy += xplane.total(xplane.union((s, e) for _i, s, e in clipped))
        took += sum(e - s for i, s, e in xplane.leaf_segments(clipped)
                    if ops[i][0] == name)
    return 100.0 * took / busy if took and busy else None
