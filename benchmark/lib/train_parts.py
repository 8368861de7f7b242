"""Device time of a traced TRAIN window by the part of the step that asked for
it AND by the pass that ran it: what the ``train.part_*_share_pct`` and the
three phase readers (``train.forward`` / ``recompute`` / ``backward_share_pct``)
divide. ``part_time.py``'s twin for the train cells.

The program names the parts (``paddle_tpu.observability.trace.parts``:
``jax.named_scope("pt.<part>")`` in ``models/llama.py``'s layers, ``pt.stack``
around a stacked run, ``pt.optimizer`` around the update), and JAX writes the
pass into the same name stack, which the profiler keeps in the ``tf_op`` stat
of a device event's metadata (looked at by hand, jax 0.9.0 / libtpu 0.0.34):

- forward ``jit(step)/jvp(pt.stack)/jit(pp_stage_stack)/while/body/
  closed_call/pt.mlp/.../dot_general:`` (a transform wraps the segment it
  meets first: ``jvp(pt.head)``, so a part is looked for INSIDE a wrapper),
- backward ``jit(step)/transpose(jvp(pt.stack))/.../checkpoint/pt.mlp/...``,
- what the recompute replays ``.../checkpoint/rematted_computation/pt.mlp/...``,
- no pass at all ``jit(step)/pt.optimizer/mul:``.

Definitions:

- the window, the busy time and self time are ``part_time``'s (the
  ``bench.window`` span; the union of the ops' intervals clipped to it,
  summed over the devices; each instant to the innermost op covering it),
  so the labels add up to 100;
- an op's LABEL is ``(part, phase)``: the innermost ``pt.<part>`` of
  ``PARTS + STEP_PARTS`` in its own name stack — of ``a;b`` (a fusion XLA
  made of several stacks) the first stack that names a part gives part AND
  phase — and the phase ``recompute`` where that stack holds
  ``rematted_computation``, else ``backward`` where a segment starts
  ``transpose(``, else ``forward`` where one starts ``jvp(``, else ``none``;
- an op with a name stack and NO part in it is ``unscoped`` in its own
  phase and does not inherit: a hole in the vocabulary has to show (no
  serve program had such ops; a train step has);
- an op with no name at all (a layout copy the compiler put in) takes the
  label of the next op of its program run that has one, else of the one
  before (``part_time.inherit``: PERF.md section 3's rule);
- a collective counts where the partitioner attached it: ``o_proj``'s
  ``mp`` all-reduce carries ``pt.attn_proj``;
- a program that names no part at all (the parent of the PR that added
  them), a trace with no device plane (a CPU rehearsal), a serve cell:
  nothing to read, every reader returns ``None``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import harness, part_time, program_trace, xplane
from .part_time import MODULES_LINE, OPS_LINE, UNSCOPED, _fields, _text

PHASES = ("forward", "recompute", "backward")
NO_PHASE = "none"
Label = Tuple[str, str]                      # (part | "unscoped", phase)
# an op as the file has it: its tf_op stat, start_ns, end_ns
RawOp = Tuple[str, float, float]
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()*pt\.(\w+)\)*$")


# -- arithmetic on plain tuples ------------------------------------------------

def part_of(stack: str, parts: Sequence[str]) -> Optional[str]:
    """``jit(step)/jvp(pt.stack)/while/body/pt.mlp/mul`` -> ``mlp``;
    ``jit(step)/transpose(jvp(pt.head))/mul`` -> ``head``."""
    for seg in reversed(stack.split("/")):
        m = _WRAPPED.match(seg)
        if m and m.group(1) in parts:
            return m.group(1)
    return None


def phase_of(stack: str) -> str:
    segs = stack.split("/")
    if "rematted_computation" in segs:
        return "recompute"
    if any(s.startswith("transpose(") for s in segs):
        return "backward"
    if any(s.startswith("jvp(") for s in segs):
        return "forward"
    return NO_PHASE


def label_of(tf_op: str, parts: Sequence[str]) -> Optional[Label]:
    """The label an op's own ``tf_op`` gives it; ``None`` for an op with no
    name at all (it inherits)."""
    stacks = [s.rstrip(":") for s in tf_op.split(";")]
    for stack in stacks:
        part = part_of(stack, parts)
        if part:
            return part, phase_of(stack)
    return (UNSCOPED, phase_of(stacks[0])) if stacks[0] else None


def shares_pct(devices: Sequence[Tuple[Sequence[RawOp],
                                       Sequence[Tuple[float, float]]]],
               parts: Sequence[str], lo: float, hi: float
               ) -> Optional[Dict[Label, float]]:
    """``{(part | "unscoped", phase): 100 x self time in [lo, hi) / busy
    time}`` over ``devices``, each ``(ops, runs)``; ``None`` where no op
    names a part or nothing ran in the window. The interval arithmetic is
    ``part_time.shares_pct``'s, which takes any label for a part."""
    labelled = [([(label_of(tf_op, parts), s, e) for tf_op, s, e in ops],
                 runs) for ops, runs in devices]
    if not any(o[0] and o[0][0] != UNSCOPED
               for ops, _runs in labelled for o in ops):
        return None
    by = part_time.shares_pct(labelled, lo, hi)
    if by is None:
        return None
    # a run in which nothing has a name of its own: ``inherit``'s fallback
    return {(k if isinstance(k, tuple) else (UNSCOPED, NO_PHASE)): v
            for k, v in by.items()}


def by_part(by: Dict[Label, float]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for (part, _phase), v in by.items():
        out[part] = out.get(part, 0.0) + v
    return out


def by_phase(by: Dict[Label, float]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for (_part, phase), v in by.items():
        out[phase] = out.get(phase, 0.0) + v
    return out


# -- the file ------------------------------------------------------------------

def _message(buf, span) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for f, v in _fields(buf, *span):
        out.setdefault(f, []).append(v)
    return out


def _map_values(buf, entries):
    """The values (field 2) of a protobuf map's entries, each a message."""
    for entry in entries:
        for f, v in _fields(buf, *entry):
            if f == 2:
                yield _message(buf, v)


def read_devices(path: str
                 ) -> List[Tuple[List[RawOp], List[Tuple[float, float]]]]:
    """``[(ops, runs)]`` of the ``/device:TPU:<n>`` planes of an
    ``.xplane.pb``, an op with its ``tf_op`` as written (``part_time.
    read_devices`` hands back a part instead; the schema is in its
    docstring)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    for f, plane_span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        plane = _message(buf, plane_span)
        if not (2 in plane and
                xplane.DEVICE_PLANE.match(_text(buf, plane[2][0]))):
            continue
        stat_names = {md.get(1, [0])[0]: _text(buf, md[2][0]) if 2 in md
                      else "" for md in _map_values(buf, plane.get(5, ()))}
        tf_op: Dict[int, str] = {}
        for md in _map_values(buf, plane.get(4, ())):
            for stat in md.get(5, ()):
                st = _message(buf, stat)
                if stat_names.get(st.get(1, [0])[0]) != "tf_op":
                    continue
                tf_op[md.get(1, [0])[0]] = _text(buf, st[5][0]) if 5 in st \
                    else stat_names.get(st.get(7, [0])[0], "")
        ops: List[RawOp] = []
        runs: List[Tuple[float, float]] = []
        for line_span in plane.get(3, ()):
            line = _message(buf, line_span)
            lname = _text(buf, line[2][0]) if 2 in line else ""
            if lname not in (OPS_LINE, MODULES_LINE):
                continue
            t0 = line.get(3, [0])[0]
            for lo, hi in line.get(4, ()):
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
                    ops.append((tf_op.get(mid, ""), s, s + dur / 1e3))
                else:
                    runs.append((s, s + dur / 1e3))
        if ops:
            out.append((ops, runs))
    return out


# -- this run's trace ----------------------------------------------------------

_CURRENT: Dict[str, Optional[Dict[Label, float]]] = {}


def current(shapes: Dict) -> Optional[Dict[Label, float]]:
    """``shares_pct`` of this run's trace over its ``bench.window``, read
    once a process; ``None`` in a serve cell, in a run that wrote no trace,
    and wherever there is nothing to read (module docstring)."""
    pt = program_trace.current(shapes, "train")
    if pt is None or pt.window is None:
        return None
    try:
        from paddle_tpu.observability.trace.parts import PARTS, STEP_PARTS
    except ImportError:       # a program without the vocabulary
        return None
    path = program_trace.find_run_xplane(harness.ROOT,
                                         program_trace.process_start())
    if path is None:
        return None
    if path not in _CURRENT:
        _CURRENT.clear()
        try:
            _CURRENT[path] = shares_pct(read_devices(path),
                                        PARTS + STEP_PARTS, *pt.window)
        except (ValueError, IndexError):    # not the schema: no number
            _CURRENT[path] = None
    return _CURRENT[path]


def part_share(shapes: Dict, *parts: str) -> Optional[float]:
    """The summed share of ``parts`` (all phases) in this run's trace;
    ``None`` where there is nothing to read."""
    by = current(shapes)
    if by is None:
        return None
    got = by_part(by)
    return sum(got.get(p, 0.0) for p in parts)


def phase_share(shapes: Dict, phase: str) -> Optional[float]:
    """The share of every op of ``phase``, whatever its part."""
    by = current(shapes)
    return None if by is None else by_phase(by).get(phase, 0.0)
