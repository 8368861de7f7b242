"""XPlane ingestion: device truth for the step timeline.

``jax.profiler.start_trace`` writes ``plugins/profile/<ts>/*.xplane.pb``
under its log directory; ``jax.profiler.ProfileData`` reads the host planes
with nothing but JAX (planes, their lines, and events with a start, a
duration and their stats), ``xspace.read_planes`` the device planes with
what the profiler keeps in an event's METADATA (``tf_op``: the name stack;
``program_id``). What this layer needs from it:

- the program's own spans on the host plane: ``StepTimeline`` brackets every
  step and phase with ``trace.span`` — ``pt.train.step`` (its ``step_num``
  stat is the step's index) and ``pt.train.<phase>`` — the correlation
  anchors, in every trace, whoever started the profiler;
- device execution events. TPU: the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, one event per executed HLO instruction, NESTED
  where an instruction contains others (a ``while`` spans its body), named
  by the instruction's whole HLO text (``%fusion.3 = bf16[...] fusion(...)``;
  the op's name is what stands before `` = ``), and the ``XLA Modules``
  line, one event per program run (``jit_pt_window1(<program id>)``). CPU
  backend: events that carry an ``hlo_op`` stat, on the ``tf_XLA*``
  executor threads of the host plane.

``correlate`` assigns device events to step windows by time (host and device
share the trace clock), unions overlapping intervals per line so nested
spans never double-count, gives the op table each op's SELF time (its
interval less what its children cover), and splits each step's device time
into *exposed* (overlapping a ``device_block``/``stream_wait``/``data_wait``
host span — the host was waiting for it) vs *hidden* (overlapped by useful
host work) — the device-truth ``overlap_efficiency``. And it splits the
device's self time BY PART of a model step (``by_part``): the programs say
which part of the model asked for each op (``observability.trace.parts``),
so the table says where a program's milliseconds go in the model's own
words, program by program — and, for a train step, BY PHASE beside it
(forward, recompute, backward: ``parts.phase_of`` of the same name stack).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import xspace
from .parts import part_of, phase_of

__all__ = ["find_xplane", "read_xplane", "correlate", "correlate_logdir",
           "CorrelatedTrace", "TraceEvent"]

STEP_SPAN = "pt.train.step"
PHASE_PREFIX = "pt.train."
# blocking host phases: device time under these was NOT hidden behind
# useful host work (stall, not overlap)
_BLOCKING_PHASES = ("device_block", "stream_wait", "data_wait")
_DEVICE_PLANE = xspace.DEVICE_PLANE
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"


class TraceEvent(NamedTuple):
    plane: str
    line: str
    name: str
    ts: float            # microseconds on the trace clock
    dur: float           # microseconds
    stats: Dict[str, Any]


def find_xplane(logdir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a capture logdir."""
    hits = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def read_xplane(path: str) -> List[TraceEvent]:
    """The events this layer reads, out of one ``.xplane.pb``: the
    ``pt.train.*`` spans, host-plane events with an ``hlo_op`` stat (the CPU
    backend's device events), and the device planes' ops and program runs,
    each with its instruction's stats (shared by the events of one
    instruction: do not write to them)."""
    from jax.profiler import ProfileData

    out: List[TraceEvent] = []
    for plane in ProfileData.from_file(path).planes:
        if _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                stats = dict(e.stats)
                if not (name.startswith(PHASE_PREFIX) or "hlo_op" in stats):
                    continue
                out.append(TraceEvent(plane.name, line.name, name,
                                      e.start_ns / 1e3, e.duration_ns / 1e3,
                                      stats))
    for pname, plane in xspace.read_planes(
            path, lines=(_OPS_LINE, _MODULES_LINE)).items():
        for lname, evs in plane["lines"].items():
            for mid, t0, t1 in evs:
                md = plane["metadata"].get(mid) or {"name": str(mid),
                                                    "stats": {}}
                out.append(TraceEvent(pname, lname, md["name"], t0 / 1e3,
                                      (t1 - t0) / 1e3, md["stats"]))
    return out


_OPCODE = re.compile(r" [a-z][a-z0-9\-]*\(")


def _op_and_shape(name: str) -> Tuple[str, str]:
    """``%fusion.3 = bf16[640,18432]{1,0:T(8,128)} fusion(...)`` ->
    ``("fusion.3", "bf16[640,18432]")``."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(rest)
    shape = re.sub(r"\{[^}]*\}", "", rest[:m.start()]) if m else ""
    return head.lstrip("%"), shape


NO_PHASE = "none"


def _own_part(stats: Dict[str, Any]
              ) -> Tuple[Optional[str], Optional[str]]:
    """The part and the phase an instruction's own name stack gives it. A
    fusion that XLA made of ops of several name stacks lists them all
    (``a;b``): the first that names a part says both. An op with a name but
    no part is ``unscoped`` in the phase its first stack says; one with no
    name at all is ``(None, None)``."""
    stacks = [s.rstrip(":") for s in str(stats.get("tf_op", "")).split(";")]
    for stack in stacks:
        part = part_of(stack)
        if part:
            return part, phase_of(stack)
    return (UNSCOPED, phase_of(stacks[0])) if stacks[0] else (None, None)


def by_part(ops: Sequence[TraceEvent], own_us: Sequence[float],
            modules: Sequence[TraceEvent], top: int = 5) -> Dict[str, Any]:
    """Device self time by part of the model step, over all the ops and per
    program. ``ops`` are the events of ONE device's ops line, ``own_us``
    their self times, ``modules`` its program runs.

    An op belongs to the innermost ``pt.<part>`` of its own name stack
    (``_own_part``). An op with no name of its own (a layout copy the
    compiler put in, the wait for an asynchronous slice) belongs with the
    next op of the same program run that has one — the compiler schedules
    such an op where its consumer needs it — else with the one before it;
    an op of a program that names no part at all is ``unscoped``. A run is
    an ``XLA Modules`` event; without that line (an old trace) all the ops
    of one ``program_id`` count as one run.

    A program in which some op has a phase (a train step) gets one more key
    a row, ``phases``: ``{part: {phase: us}}`` with the phases of
    ``parts.PHASES`` and ``"none"`` (the optimizer, anything outside the
    gradient); an op with no name takes the phase with the part, and an op
    that HAS a name stack with no part in it is ``unscoped`` there, in the
    phase its stack says (a train step has such ops — what the step does
    around the model and the optimizer — and hiding them in a neighbour's
    part would hide a hole in the vocabulary). A served program's rows are
    as they were."""
    runs = sorted((m.ts, m.ts + m.dur, m.name) for m in modules)
    starts = [r[0] for r in runs]

    def run_of(e: TraceEvent):
        i = bisect.bisect_right(starts, e.ts) - 1
        if i >= 0 and e.ts < runs[i][1]:
            return i
        return "program " + str(e.stats.get("program_id", "?"))

    groups: Dict[Any, List[int]] = {}
    for i in sorted(range(len(ops)), key=lambda i: ops[i].ts):
        groups.setdefault(run_of(ops[i]), []).append(i)
    progs: Dict[str, Dict[str, Any]] = {}
    for key, idx in groups.items():
        name = re.sub(r"\(\d+\)$", "", runs[key][2]) \
            if isinstance(key, int) else key
        parts = [_own_part(ops[i].stats) for i in idx]
        phased = any(ph for _p, ph in parts)
        if not phased:  # a served program: a name with no part inherits
            parts = [(None if p == UNSCOPED else p, None)
                     for p, _ph in parts]
        nxt = None
        for k in range(len(idx) - 1, -1, -1):       # the next that has one
            nxt = parts[k] if parts[k][0] else nxt
            parts[k] = nxt
        prev = None
        for k in range(len(idx)):                   # else the one before
            prev = parts[k] or prev
            parts[k] = prev or (UNSCOPED, None)
        row = progs.setdefault(name, {"calls": 0, "device_us": 0.0,
                                      "parts": {}, "ops": {}})
        row["calls"] += 1
        for i, (part, phase) in zip(idx, parts):
            us = own_us[i]
            row["device_us"] += us
            row["parts"][part] = row["parts"].get(part, 0.0) + us
            op_key = _op_and_shape(ops[i].name)
            if phased:
                phase = phase or NO_PHASE
                cell = row.setdefault("phases", {}).setdefault(part, {})
                cell[phase] = cell.get(phase, 0.0) + us
                op_key += (phase,)
            cell = row["ops"].setdefault(part, {}).setdefault(
                op_key, [0, 0.0])
            cell[0] += 1
            cell[1] += us
    total: Dict[str, float] = {}
    for row in progs.values():
        for part, us in row["parts"].items():
            total[part] = total.get(part, 0.0) + us
        row["top_ops"] = {
            part: [dict(zip(("op", "shape", "phase"), key), calls=c,
                        us=round(us, 1)) for key, (c, us) in
                   sorted(table.items(), key=lambda kv: -kv[1][1])[:top]]
            for part, table in row.pop("ops").items()}
        row["device_us"] = round(row["device_us"], 1)
        row["parts"] = {k: round(v, 1) for k, v in sorted(
            row["parts"].items(), key=lambda kv: -kv[1])}
        if "phases" in row:
            row["phases"] = {part: {ph: round(us, 1)
                                    for ph, us in by.items()}
                             for part, by in row["phases"].items()}
    return {"parts": {k: round(v, 1) for k, v in sorted(
                total.items(), key=lambda kv: -kv[1])},
            "device_us": round(sum(total.values()), 1),
            "programs": progs}


def _overlap_us(intervals: List[Tuple[float, float]],
                windows: Sequence[Tuple[float, float]]) -> float:
    """Covered time of ``intervals`` that falls inside any window (both
    lists are clipped unions, so no double counting)."""
    total = 0.0
    for t0, t1 in intervals:
        for w0, w1 in windows:
            lo, hi = max(t0, w0), min(t1, w1)
            if hi > lo:
                total += hi - lo
    return total


def _self_us(events: Sequence[TraceEvent]) -> List[float]:
    """Each event's duration less what its children cover (events of one
    line nest properly or are disjoint); same order as ``events``."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].ts, -events[i].dur))
    own = [e.dur for e in events]
    stack: List[int] = []
    for i in order:
        ev = events[i]
        while stack and events[stack[-1]].ts + events[stack[-1]].dur <= ev.ts:
            stack.pop()
        if stack:
            own[stack[-1]] -= ev.dur
        stack.append(i)
    return [max(v, 0.0) for v in own]


def _add_parts(total: Optional[Dict[str, Any]], one: Dict[str, Any]
               ) -> Dict[str, Any]:
    """``by_part`` of one more device added to the others' (several chips
    run the same programs: times and calls add, a program's widest ops
    too, instruction by instruction)."""
    if total is None:
        return one
    for part, us in one["parts"].items():
        total["parts"][part] = round(total["parts"].get(part, 0.0) + us, 1)
    total["device_us"] = round(total["device_us"] + one["device_us"], 1)
    for name, row in one["programs"].items():
        have = total["programs"].setdefault(name, row)
        if have is not row:
            have["calls"] += row["calls"]
            have["device_us"] = round(have["device_us"] + row["device_us"], 1)
            for part, us in row["parts"].items():
                have["parts"][part] = round(
                    have["parts"].get(part, 0.0) + us, 1)
            for part, by in row.get("phases", {}).items():
                cell = have.setdefault("phases", {}).setdefault(part, {})
                for ph, us in by.items():
                    cell[ph] = round(cell.get(ph, 0.0) + us, 1)
            for part, ops in row["top_ops"].items():
                mine = have["top_ops"].setdefault(part, [])
                seen = {(o["op"], o["shape"], o.get("phase")): o
                        for o in mine}
                for op in ops:
                    o = seen.get((op["op"], op["shape"], op.get("phase")))
                    if o is None:
                        mine.append(dict(op))
                    else:
                        o["calls"] += op["calls"]
                        o["us"] = round(o["us"] + op["us"], 1)
                mine.sort(key=lambda o: -o["us"])
    return total


class CorrelatedTrace:
    """The parsed + correlated view of one capture: per-step device time,
    per-phase attribution, and the device op table."""

    def __init__(self, steps: List[Dict], op_table: List[Dict],
                 unattributed_device_us: float, device_threads: List[str],
                 source: Optional[str] = None,
                 by_part: Optional[Dict[str, Any]] = None):
        self.steps = steps
        self.op_table = op_table
        self.unattributed_device_us = unattributed_device_us
        self.device_threads = device_threads
        self.source = source
        # device self time by part of a model step and by program — by
        # phase too for a train step (``by_part``); None for a trace with no
        # device ops line
        self.by_part = by_part

    @property
    def steps_correlated(self) -> int:
        return sum(1 for s in self.steps if s["device_us"] > 0)

    def device_us_per_step(self) -> List[float]:
        return [s["device_us"] for s in self.steps]

    def overlap_efficiency(self) -> Optional[float]:
        total = sum(s["device_us"] for s in self.steps)
        if total <= 0:
            return None
        hidden = sum(s["hidden_us"] for s in self.steps)
        return round(hidden / total, 4)

    def summary(self, top: int = 20) -> Dict[str, Any]:
        """JSON-able digest — the hub's ``device_trace`` provider payload
        and the bench ``device_op_table`` shape."""
        dev = [s["device_us"] for s in self.steps if s["device_us"] > 0]
        return {
            "source": self.source,
            "steps_seen": len(self.steps),
            "steps_correlated": self.steps_correlated,
            "device_compute_us": {
                "total": round(sum(dev), 1),
                "per_step_avg": round(sum(dev) / len(dev), 1) if dev else 0.0,
                "last": round(dev[-1], 1) if dev else 0.0,
            },
            "overlap_efficiency": self.overlap_efficiency(),
            "unattributed_device_us": round(self.unattributed_device_us, 1),
            "device_threads": self.device_threads[:8],
            "op_table": self.op_table[:top],
            "by_part": None if self.by_part is None else {
                "parts": self.by_part["parts"],
                "device_us": self.by_part["device_us"],
                "programs": {name: {k: row[k] for k in
                                    ("calls", "device_us", "parts", "phases")
                                    if k in row}
                             for name, row in
                             self.by_part["programs"].items()}},
            "steps": [
                {k: (round(v, 1) if isinstance(v, float) else v)
                 for k, v in s.items() if k != "window"}
                for s in self.steps[:64]
            ],
        }


def correlate(events: Sequence[TraceEvent], source: Optional[str] = None,
              top_ops: int = 5) -> CorrelatedTrace:
    """Correlate one trace's events (``read_xplane``): device events ->
    ``pt.train.step`` / ``pt.train.<phase>`` windows by time, and device
    self time by part of a model step (``by_part``, its ``top_ops`` widest
    ops a part)."""
    steps: List[Dict] = []
    phase_spans: List[Tuple[str, float, float]] = []  # (name, t0, t1)
    by_line: Dict[Tuple[str, str], List[TraceEvent]] = {}
    modules: Dict[str, List[TraceEvent]] = {}
    for e in events:
        if e.name == STEP_SPAN:
            steps.append({"step": e.stats.get("step_num"),
                          "window": (e.ts, e.ts + e.dur), "wall_us": e.dur})
        elif e.name.startswith(PHASE_PREFIX):
            phase_spans.append((e.name[len(PHASE_PREFIX):], e.ts,
                                e.ts + e.dur))
        elif e.line == _MODULES_LINE and _DEVICE_PLANE.match(e.plane):
            modules.setdefault(e.plane, []).append(e)
        elif e.dur > 0.01:
            by_line.setdefault((e.plane, e.line), []).append(e)
    steps.sort(key=lambda s: s["window"][0])
    for i, s in enumerate(steps):  # a trace without the stat: by order
        if s["step"] is None:
            s["step"] = i

    # op table: self time by op (a ``while`` owns only its own overhead)
    agg: Dict[Tuple[str, str], List[float]] = {}
    parts: Optional[Dict[str, Any]] = None
    for (plane, line), evs in by_line.items():
        own_us = _self_us(evs)
        if line == _OPS_LINE and _DEVICE_PLANE.match(plane):
            parts = _add_parts(parts, by_part(
                evs, own_us, modules.get(plane, ()), top=top_ops))
        for e, own in zip(evs, own_us):
            op = e.name.split(" = ", 1)[0].lstrip("%")
            row = agg.setdefault((op, str(e.stats.get("hlo_module", ""))),
                                 [0, 0.0])
            row[0] += 1
            row[1] += own
    op_table = [
        {"op": op, "module": mod, "calls": c,
         "total_us": round(us, 1), "avg_us": round(us / c, 1)}
        for (op, mod), (c, us) in
        sorted(agg.items(), key=lambda kv: -kv[1][1])
    ]

    # per-step attribution: device work is dispatched in step order, so an
    # event belongs to the LAST step whose window opened before it started
    # — this also catches the async spill (param/optimizer updates still
    # executing after the host unblocked on the loss and moved on). Only
    # events before the first window stay unattributed. Per-line interval
    # unions prevent nested spans from double-counting.
    per_step_line: Dict[int, Dict[Any, List[Tuple[float, float]]]] = {}
    unattributed = 0.0
    starts = [s["window"][0] for s in steps]
    for key, evs in by_line.items():
        for e in evs:
            hit = bisect.bisect_right(starts, e.ts) - 1
            if hit < 0:
                unattributed += e.dur
                continue
            per_step_line.setdefault(hit, {}).setdefault(key, []).append(
                (e.ts, e.ts + e.dur))

    for i, s in enumerate(steps):
        w0, w1 = s["window"]
        # union per line, then sum across lines (parallel device threads
        # and several chips legitimately add)
        merged: Dict[Any, List[Tuple[float, float]]] = {}
        dev_us = 0.0
        for key, ivs in per_step_line.get(i, {}).items():
            ivs.sort()
            out: List[Tuple[float, float]] = []
            for t0, t1 in ivs:
                if out and t0 <= out[-1][1]:
                    out[-1] = (out[-1][0], max(out[-1][1], t1))
                else:
                    out.append((t0, t1))
            merged[key] = out
            dev_us += sum(t1 - t0 for t0, t1 in out)
        # phase attribution + hidden/exposed split inside this window
        phases: Dict[str, Dict[str, float]] = {}
        blocking: List[Tuple[float, float]] = []
        for name, p0, p1 in phase_spans:
            if not (p0 < w1 and p1 > w0):
                continue
            t0, t1 = max(p0, w0), min(p1, w1)
            row = phases.setdefault(name, {"ms": 0.0, "device_us": 0.0})
            row["ms"] += (t1 - t0) / 1e3
            for ivs in merged.values():
                row["device_us"] += _overlap_us(ivs, [(t0, t1)])
            if name in _BLOCKING_PHASES:
                blocking.append((t0, t1))
        exposed = sum(_overlap_us(ivs, blocking) for ivs in merged.values())
        s["device_us"] = dev_us
        s["exposed_us"] = exposed
        s["hidden_us"] = max(dev_us - exposed, 0.0)
        s["phases"] = {n: {"ms": round(r["ms"], 3),
                           "device_us": round(r["device_us"], 1)}
                       for n, r in phases.items()}

    dev_threads = sorted({f"{plane}/{line}" for plane, line in by_line})
    return CorrelatedTrace(steps, op_table, unattributed, dev_threads,
                           source=source, by_part=parts)


def correlate_logdir(logdir: str) -> CorrelatedTrace:
    """Read + correlate the newest ``.xplane.pb`` under ``logdir``."""
    path = find_xplane(logdir)
    if path is None:
        raise FileNotFoundError(
            f"no *.xplane.pb under {logdir!r} — did the capture run "
            "(jax.profiler trace) and stop cleanly?")
    return correlate(read_xplane(path), source=path)
