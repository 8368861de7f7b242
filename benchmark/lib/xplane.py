"""From a profiler trace (``*.xplane.pb``) to numbers. The benchmark's own
reduction: JAX alone reads the file (``jax.profiler.ProfileData``), and the
arithmetic below works on plain tuples so that it can be checked on a small
recorded trace and on hand-made intervals.

What a v5e trace looks like (jax 0.9.0 / libtpu 0.0.34, looked at by hand in
PR 23): one plane per chip named ``/device:TPU:<n>`` with the lines ``Steps``,
``XLA Modules`` (one event per program run), ``XLA Ops`` and ``Async XLA
Ops`` (DMA started by ``copy-start`` / ``slice-start``, overlapping the ops).
``XLA Ops`` holds one event per executed HLO instruction, NESTED where an
instruction contains others (a ``while`` spans the ops of its body). An
event's NAME is the instruction's whole HLO text, ``%fusion.391 =
bf16[2048,92544]{...} fusion(...), kind=kOutput, calls=...``: the short name
is what stands before `` = ``, the opcode the first lower-case word before a
``(`` after it. A Pallas kernel is a ``custom-call`` whose text carries
``custom_call_target="tpu_custom_call"``; its short name is
``_unknown_.<n>`` today (the kernels give XLA no name), so single kernels
cannot be told apart by name yet. Host threads are lines of the plane
``/host:CPU``; a ``jax.profiler.TraceAnnotation`` is an event on the line
``python3``. All planes share one clock.

Definitions (the on-chip-measurement guide's):

- busy: the union of the intervals in which an op runs on the device,
  clipped to the window; idle share = 1 - busy / window;
- an op's SELF time: its interval minus what its children cover, so the
  innermost op owns every instant and containers own only their overhead;
- exposed collective: instants whose innermost op is a collective — the
  core sits in the collective (or waits for an asynchronous one to be done)
  and computes nothing;
- kernel share: self time of Mosaic (Pallas) custom calls over busy time.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?([.\d]*)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def read_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane name: {line name: [(event name, start_ns, end_ns)]}}``.
    Lines of one name in one plane are merged (host threads can share a
    name)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                s = float(e.start_ns)
                evs.append((e.name, s, s + float(e.duration_ns)))
    return out


def read_event_stats(path: str, plane_name: str, line_name: str,
                     limit: int = 2000) -> Dict[str, Dict]:
    """First-seen stats of each distinct event name on one line (what a
    classifier may key on: ``hlo_category``, ``tf_op``, ...)."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name != line_name:
                continue
            for e in line.events:
                if e.name not in out:
                    out[e.name] = {k: (v if isinstance(v, (int, float, str))
                                       else str(v)) for k, v in e.stats}
                    if len(out) >= limit:
                        return out
    return out


# -- interval arithmetic ---------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """The idle intervals of ``[lo, hi)`` given a sorted disjoint ``busy``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def leaf_segments(events: Sequence[Event]) -> List[Event]:
    """Split nested events into disjoint segments each owned by the
    INNERMOST event covering it (a sweep with a stack; events of one line
    nest properly or are disjoint)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[Event] = []
    stack: List[Event] = []
    at = 0.0

    def emit(upto: float):
        nonlocal at
        if stack and upto > at:
            out.append((stack[-1][0], at, upto))
        at = max(at, upto)

    for ev in evs:
        while stack and stack[-1][2] <= ev[1]:
            top = stack[-1]
            emit(top[2])
            stack.pop()
        emit(ev[1])
        at = max(at, ev[1]) if stack else ev[1]
        stack.append(ev)
    while stack:
        top = stack[-1]
        emit(top[2])
        stack.pop()
    return out


# -- classification --------------------------------------------------------------

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.391 = bf16[...] fusion(...)`` -> ``fusion.391``."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    m = _OPCODE.search(name.split(" = ", 1)[-1] if " = " in name else "")
    return m.group(1) if m else ""


def label(name: str, width: int = 96) -> str:
    """A name short enough to print: short name, opcode, output type."""
    if " = " not in name:
        return name[:width]
    head, rest = name.split(" = ", 1)
    op = opcode(name)
    out_type = rest.split(" " + op + "(", 1)[0] if op else ""
    mosaic = " tpu_custom_call" if is_mosaic_kernel(name) else ""
    return f"{head.lstrip('%')} {op}{mosaic} {out_type}"[:width].rstrip()


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(short_name(name))) or \
        bool(COLLECTIVE.match(opcode(name)))


def is_mosaic_kernel(name: str) -> bool:
    """A Pallas/Mosaic kernel on the ops line: the event's own HLO text
    names the custom call's target."""
    return 'custom_call_target="tpu_custom_call"' in name


# -- the summary -----------------------------------------------------------------

def host_spans(planes, prefix: str = "bench.") -> List[Event]:
    out = []
    for line in planes.get(HOST_PLANE, {}).values():
        out.extend(e for e in line if e[0].startswith(prefix))
    return out


def _most_overlap(spans: Sequence[Event], s: float, e: float):
    best, best_ov = None, 0.0
    for n, hs, he in spans:
        ov = min(e, he) - max(s, hs)
        if ov > best_ov:
            best, best_ov = n, ov
    return best


def summarize(planes: Dict[str, Dict[str, List[Event]]],
              window: Optional[Tuple[float, float]] = None,
              top: int = 10, n_gaps: int = 5) -> Optional[Dict]:
    """Reduce one trace. ``window`` defaults to the ``bench.window`` host
    span, else to the extent of the device ops. Returns ``None`` when the
    trace has no device plane (a CPU rehearsal): nothing to read."""
    dev = {int(m.group(1)): lines for name, lines in planes.items()
           if (m := DEVICE_PLANE.match(name))}
    dev = {k: v for k, v in dev.items() if v.get(OPS_LINE)}
    if not dev:
        return None
    spans = host_spans(planes)
    if window is None:
        w = [e for e in spans if e[0] == WINDOW_SPAN]
        if w:
            window = (min(e[1] for e in w), max(e[2] for e in w))
        else:
            all_ops = [e for v in dev.values() for e in v[OPS_LINE]]
            window = (min(e[1] for e in all_ops), max(e[2] for e in all_ops))
    lo, hi = window
    per_dev = {}
    self_time: Dict[str, float] = defaultdict(float)
    outer_time: Dict[str, float] = defaultdict(float)
    for d, lines in sorted(dev.items()):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in lines[OPS_LINE]
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for _n, s, e in ops)
        segs = leaf_segments(ops)
        coll = sum(e - s for n, s, e in segs if is_collective(n))
        kern = sum(e - s for n, s, e in segs if is_mosaic_kernel(n))
        for n, s, e in segs:
            self_time[n] += e - s
        # outermost ops (whole loops, not their bodies): the coarse view
        depth0, end = [], lo
        for n, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
            if s >= end:
                depth0.append((n, s, e))
                end = e
        for n, s, e in depth0:
            outer_time[n] += e - s
        per_dev[d] = {"busy_ns": total(busy), "collective_exposed_ns": coll,
                      "kernel_ns": kern, "busy": busy}
    n_dev = len(per_dev)
    # idle gaps of the first device, each named by the host span that
    # overlaps it most (what the host was doing while the chip waited)
    first = per_dev[min(per_dev)]
    idle = sorted(gaps(first["busy"], lo, hi), key=lambda g: g[0] - g[1])
    by_host: Dict[str, float] = defaultdict(float)
    named = []
    # the benchmark's own spans first; where none covers a gap (a thread of
    # the program, which carries no span of ours), the profiler's own host
    # events say what the host ran
    ours = [e for e in spans if e[0] != WINDOW_SPAN]
    theirs = [e for e in host_spans(planes, "") if not e[0].startswith(
        "bench.") and hi > e[1] and e[2] > lo]
    for s, e in idle:
        best = _most_overlap(ours, s, e) or _most_overlap(theirs, s, e) \
            or "host (no span)"
        by_host[best] += e - s
        named.append((best, e - s))
    ops_top = sorted(self_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": n_dev,
        "busy_s": sum(v["busy_ns"] for v in per_dev.values()) / n_dev / 1e9,
        "collective_exposed_s": sum(v["collective_exposed_ns"]
                                    for v in per_dev.values()) / n_dev / 1e9,
        "kernel_s": sum(v["kernel_ns"] for v in per_dev.values())
        / n_dev / 1e9,
        "per_device_busy_s": {d: v["busy_ns"] / 1e9
                              for d, v in per_dev.items()},
        # seconds summed over the devices, by the trace's own op names
        "device_ops": [[label(n), t / 1e9] for n, t in ops_top],
        "outermost_ops": [[label(n), t / 1e9] for n, t in sorted(
            outer_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / 1e9] for n, t in named[:n_gaps]],
        "idle_by_host_span_s": {n: t / 1e9 for n, t in
                                sorted(by_host.items(), key=lambda kv: -kv[1])},
    }
