"""The device planes of an ``.xplane.pb``, read from the protobuf wire.

``jax.profiler.ProfileData`` hands out an event's own stats and not those of
its METADATA, and on a TPU that is where the profiler keeps what XLA knows
of an instruction: ``tf_op`` (the jaxpr name stack, ``jit(pt_window1)/
pt.attn_proj/dot_general:``), ``program_id`` (the module the instruction
belongs to; an ``XLA Modules`` event is named ``jit_pt_window1(<id>)``),
``hlo_category``, ``flops``, ``bytes_accessed`` (looked at by hand, jax
0.9.0 / libtpu 0.0.34). So this module walks the file itself — the schema
is tsl's ``xplane.proto``, a handful of fields — and parses only the planes
asked for: every other plane is skipped by its length.
"""
from __future__ import annotations

import re
import struct
from typing import Any, Dict, Iterator, List, Tuple

__all__ = ["read_planes", "DEVICE_PLANE"]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one message: an int for a varint, the
    ``(lo, hi)`` of a length-delimited field, the raw 8 or 4 bytes of a
    fixed one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, val


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stats(buf, spans, stat_names) -> Dict[str, Any]:
    out = {}
    for lo, hi in spans:
        name, val = None, None
        for f, v in _fields(buf, lo, hi):
            if f == 1:
                name = stat_names.get(v, str(v))
            elif f == 2:
                val = struct.unpack("<d", v)[0]
            elif f in (3, 4):
                val = v
            elif f == 5:
                val = _text(buf, v)
            elif f == 7:                      # a string kept once a plane
                val = stat_names.get(v, str(v))
        if name is not None and val is not None:
            out[name] = val
    return out


def _plane(buf: bytes, lo: int, hi: int, lines) -> Dict[str, Any]:
    line_spans: List[Tuple[int, int]] = []
    md_spans: List[Tuple[int, int]] = []
    stat_names: Dict[int, str] = {}
    for f, v in _fields(buf, lo, hi):
        if f == 3:
            line_spans.append(v)
        elif f == 4:
            md_spans.append(v)
        elif f == 5:                           # map entry: id -> XStatMetadata
            for g, w in _fields(buf, *v):
                if g == 2:
                    sid, sname = 0, ""
                    for h, x in _fields(buf, *w):
                        if h == 1:
                            sid = x
                        elif h == 2:
                            sname = _text(buf, x)
                    stat_names[sid] = sname
    metadata: Dict[int, Dict[str, Any]] = {}
    for span in md_spans:                      # map entry: id -> XEventMetadata
        for g, w in _fields(buf, *span):
            if g != 2:
                continue
            mid, name, stat_spans = 0, "", []
            for h, x in _fields(buf, *w):
                if h == 1:
                    mid = x
                elif h == 2:
                    name = _text(buf, x)
                elif h == 5:
                    stat_spans.append(x)
            metadata[mid] = {"name": name,
                             "stats": _stats(buf, stat_spans, stat_names)}
    out_lines: Dict[str, List[Tuple[int, float, float]]] = {}
    for span in line_spans:
        name, t0_ns, ev_spans = "", 0, []
        for f, v in _fields(buf, *span):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                ev_spans.append(v)
        if lines is not None and name not in lines:
            continue
        evs = out_lines.setdefault(name, [])
        for elo, ehi in ev_spans:
            mid = off_ps = dur_ps = 0
            for f, v in _fields(buf, elo, ehi):
                if f == 1:
                    mid = v
                elif f == 2:
                    off_ps = v
                elif f == 3:
                    dur_ps = v
            start = t0_ns + off_ps / 1e3
            evs.append((mid, start, start + dur_ps / 1e3))
    return {"metadata": metadata, "lines": out_lines}


def read_planes(path: str, plane=DEVICE_PLANE, lines=("XLA Ops",
                                                      "XLA Modules")
                ) -> Dict[str, Dict[str, Any]]:
    """``{plane name: {"metadata": {id: {"name", "stats"}}, "lines": {line
    name: [(metadata id, start_ns, end_ns)]}}}`` of the planes whose name
    matches ``plane``; ``lines=None`` keeps every line. An event's name and
    everything the profiler knows of its instruction are its metadata's."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for f, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name = ""
        for g, w in _fields(buf, *v):     # the name is the second field
            if g == 2:
                name = _text(buf, w)
                break
        if plane.match(name):
            out[name] = _plane(buf, v[0], v[1], lines)
    return out
