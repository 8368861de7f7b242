"""Device time of the traced window by the PART of the model step that asked
for it: what the ten ``serve.part_*_share_pct`` readers divide.

The program names the parts (``paddle_tpu.observability.trace.parts``:
``jax.named_scope("pt.<part>")`` in the engine's ``attend`` and the served
blocks); XLA carries a name stack into every instruction and the profiler
writes it beside every device event — in the ``tf_op`` stat of the event's
METADATA, which ``jax.profiler.ProfileData`` does not hand out (looked at by
hand, jax 0.9.0 / libtpu 0.0.34: ``jit(pt_window1)/pt.attn_proj/
dot_general:``; a fusion XLA made of several stacks lists them ``a;b``). So
this module walks the file's protobuf wire itself (tsl's ``xplane.proto``:
the few fields below), once a process, and answers on plain tuples.

Definitions:

- the window and the busy time are ``program_trace``'s: ``bench.window``,
  and the union of the ops' intervals clipped to it, summed over devices;
- each instant belongs to the innermost op that covers it
  (``xplane.leaf_segments``: self time), so the parts and ``unscoped`` add
  up to the busy time;
- an op's part is the innermost ``pt.<part>`` of its own name stack (of the
  first stack that holds one, where there are several); ``PARTS`` is the
  program's tuple, so a part added there cannot fall silently into another;
- an op with NO stack of its own (a layout copy the compiler put in, the
  wait for an asynchronous slice) goes with the next op of the same program
  run that has a part, else with the one before it: the compiler schedules
  such an op where its consumer needs it (checked against the dataflow of
  the compiled GPT-2 decode program: PERF.md section 3). A run is an event
  of the ``XLA Modules`` line. An op in a run in which no op has a part is
  ``unscoped``;
- a program that names no part at all (the parent of the PR that added
  them), a trace with no device plane, a program without the vocabulary:
  nothing to read, every reader returns ``None``.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from . import harness, program_trace, xplane

UNSCOPED = "unscoped"
OPS_LINE, MODULES_LINE = xplane.OPS_LINE, "XLA Modules"
Op = Tuple[Optional[str], float, float]       # own part, start_ns, end_ns


# -- arithmetic on plain tuples ------------------------------------------------

def part_of(tf_op: str, parts: Sequence[str]) -> Optional[str]:
    """``jit(f)/pt.attn_proj/pt.norm/mul:`` -> ``norm``; of ``a;b`` the
    first stack that names a part; ``None`` where none does."""
    for stack in tf_op.split(";"):
        for seg in reversed(stack.rstrip(":").split("/")):
            if seg.startswith("pt.") and seg[3:] in parts:
                return seg[3:]
    return None


def inherit(ops: Sequence[Op], runs: Sequence[Tuple[float, float]]
            ) -> List[str]:
    """The part of every op: its own, else the next one's in its run that
    has one, else the one before it, else ``unscoped``. ``ops`` sorted by
    start; ``runs`` the ``(start, end)`` of the program runs (an op outside
    every run is a run of its own kind: all such ops together)."""
    starts = sorted(r[0] for r in runs)
    ends = dict(runs)
    groups: Dict[object, List[int]] = {}
    for i, (_p, s, _e) in enumerate(ops):
        k = bisect.bisect_right(starts, s) - 1
        key = starts[k] if k >= 0 and s < ends[starts[k]] else None
        groups.setdefault(key, []).append(i)
    out: List[str] = [UNSCOPED] * len(ops)
    for idx in groups.values():
        got = [ops[i][0] for i in idx]
        nxt = None
        for k in range(len(idx) - 1, -1, -1):
            nxt = got[k] or nxt
            got[k] = nxt
        prev = None
        for k in range(len(idx)):
            prev = got[k] or prev
            out[idx[k]] = prev or UNSCOPED
    return out


def shares_pct(devices: Sequence[Tuple[Sequence[Op],
                                       Sequence[Tuple[float, float]]]],
               lo: float, hi: float) -> Optional[Dict[str, float]]:
    """``{part | "unscoped": 100 x self time in [lo, hi) / busy time}``
    over ``devices``, each ``(ops, runs)``; ``None`` where no op has a part
    of its own or nothing ran in the window."""
    by: Dict[str, float] = {}
    busy = 0.0
    scoped = False
    for ops, runs in devices:
        ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        part = inherit(ops, runs)
        scoped = scoped or any(o[0] for o in ops)
        clipped = program_trace.clip(
            [(i, s, e) for i, (_p, s, e) in enumerate(ops)], lo, hi)
        busy += xplane.total(xplane.union((s, e) for _i, s, e in clipped))
        for i, s, e in xplane.leaf_segments(clipped):
            by[part[i]] = by.get(part[i], 0.0) + (e - s)
    if not scoped or busy <= 0:
        return None
    return {k: 100.0 * v / busy for k, v in by.items()}


# -- the file ------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """``(field, value)`` of one protobuf message: an int for a varint,
    ``(lo, hi)`` for a length-delimited field, ``None`` for a fixed one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            val, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, val


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def read_devices(path: str, parts: Sequence[str]
                 ) -> List[Tuple[List[Op], List[Tuple[float, float]]]]:
    """``[(ops, runs)]`` of the ``/device:TPU:<n>`` planes of an
    ``.xplane.pb``. Schema (tsl ``xplane.proto``): XSpace.planes = 1;
    XPlane.name = 2, .lines = 3, .event_metadata = 4 (map: key 1, value 2),
    .stat_metadata = 5 (map); XLine.name = 2, .timestamp_ns = 3, .events =
    4; XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3;
    XEventMetadata.id = 1, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, mds, stat_names = "", [], [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
                if not xplane.DEVICE_PLANE.match(name):
                    break
            elif g == 3:
                lines.append(v)
            elif g == 4:
                mds.append(v)
            elif g == 5:
                for h, w in _fields(buf, *v):
                    if h == 2:
                        sid, sname = 0, ""
                        for k, x in _fields(buf, *w):
                            if k == 1:
                                sid = x
                            elif k == 2:
                                sname = _text(buf, x)
                        stat_names[sid] = sname
        if not xplane.DEVICE_PLANE.match(name):
            continue
        own: Dict[int, Optional[str]] = {}
        for span in mds:
            for h, w in _fields(buf, *span):
                if h != 2:
                    continue
                mid, tf_op = 0, ""
                for k, x in _fields(buf, *w):
                    if k == 1:
                        mid = x
                    elif k == 5:
                        sname, val = "", ""
                        for m, y in _fields(buf, *x):
                            if m == 1:
                                sname = stat_names.get(y, "")
                            elif m == 5:
                                val = _text(buf, y)
                            elif m == 7:
                                val = stat_names.get(y, "")
                        if sname == "tf_op":
                            tf_op = val
                own[mid] = part_of(tf_op, parts)
        ops: List[Op] = []
        runs: List[Tuple[float, float]] = []
        for span in lines:
            lname, t0, evs = "", 0, []
            for g, v in _fields(buf, *span):
                if g == 2:
                    lname = _text(buf, v)
                elif g == 3:
                    t0 = v
                elif g == 4:
                    evs.append(v)
            if lname not in (OPS_LINE, MODULES_LINE):
                continue
            for lo, hi in evs:
                mid = off = dur = 0
                for g, v in _fields(buf, lo, hi):
                    if g == 1:
                        mid = v
                    elif g == 2:
                        off = v
                    elif g == 3:
                        dur = v
                s = t0 + off / 1e3
                if lname == OPS_LINE:
                    ops.append((own.get(mid), s, s + dur / 1e3))
                else:
                    runs.append((s, s + dur / 1e3))
        if ops:
            out.append((ops, runs))
    return out


# -- this run's trace ----------------------------------------------------------

_CURRENT: Dict[str, Optional[Dict[str, float]]] = {}


def current(shapes: Dict) -> Optional[Dict[str, float]]:
    """``shares_pct`` of this run's trace over its ``bench.window``, read
    once a process; ``None`` in a train cell, in a run that wrote no trace,
    and wherever there is nothing to read (module docstring)."""
    pt = program_trace.current(shapes, "serve")
    if pt is None or pt.window is None:
        return None
    try:
        from paddle_tpu.observability.trace.parts import PARTS
    except ImportError:       # a program without the vocabulary
        return None
    path = program_trace.find_run_xplane(harness.ROOT,
                                         program_trace.process_start())
    if path is None:
        return None
    if path not in _CURRENT:
        _CURRENT.clear()
        try:
            _CURRENT[path] = shares_pct(read_devices(path, PARTS),
                                        *pt.window)
        except (ValueError, IndexError):    # not the schema above: no number
            _CURRENT[path] = None
    return _CURRENT[path]


def share(shapes: Dict, *parts: str) -> Optional[float]:
    """The summed share of ``parts`` in this run's trace; ``None`` where
    there is nothing to read."""
    by = current(shapes)
    if by is None:
        return None
    return sum(by.get(p, 0.0) for p in parts)
