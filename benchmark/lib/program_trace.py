"""What the PROGRAM says of itself in this run's profiler trace: its own host
spans (``pt.<tier>.<what>``, emitted by ``observability.trace.span`` around
the serving worker's rounds, waits and page work and around the train step's
phases) and its Pallas kernels by name (``pt_<kernel>`` in every
``pallas_call``). The runners hand the readers fixed keys, so the readers of
these metrics come here instead: this module finds the ``.xplane.pb`` the
run's ``harness.Tracer`` wrote, reads it once a process, and answers on
plain tuples — the arithmetic is checked on hand-made intervals in
``tests/bench/``.

Definitions:

- the window is the ``bench.window`` host span, as in ``xplane.summarize``;
- a span's events are taken by name prefix: every ``pt.serve.*`` span is the
  engine worker's and every ``pt.train.*`` span the stepping thread's, so
  one prefix is one thread (``xplane.read_xplane`` merges host lines of one
  name, and every Python thread's line is named alike);
- each instant of a thread belongs to the INNERMOST span that covers it
  (``xplane.leaf_segments``), so a parent owns only its self time;
- a kernel is a Mosaic custom call whose short name carries its
  ``pt_<kernel>`` name. JAX wraps the name when it differentiates or
  rematerialises the call (``transpose(jvp(pt_flash_bwd_dq))`` is the
  instruction ``transpose_jvp_pt_flash_bwd_dq__.1``), so the name is looked
  for inside the short name, at a word boundary, not only at its start;
- a device-idle gap is split over the innermost spans of the worker that
  cover it; what no span covers is ``""``.

A program without the spans or the names (the parent of the PR that added
them) gives empty answers and the readers return ``None``.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from . import harness, xplane

Event = xplane.Event
SERVE = "pt.serve."
TRAIN = "pt.train."
# worker-thread time that is the scheduler's own host work (the rest is
# dispatch, waiting for the device, or waiting for a request)
SCHED_SPANS = ("pt.serve.page_table", "pt.serve.decode_build",
               "pt.serve.emit", "pt.serve.admit", "pt.serve.decode_round")


# -- arithmetic on plain tuples ------------------------------------------------

def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def owned_ns(events: Sequence[Event], lo: float, hi: float
             ) -> Dict[str, float]:
    """Time of ``[lo, hi)`` by the innermost event that covers it."""
    out: Dict[str, float] = {}
    for n, s, e in xplane.leaf_segments(clip(events, lo, hi)):
        out[n] = out.get(n, 0.0) + (e - s)
    return out


def durations_ms(events: Sequence[Event], name: str, lo: float, hi: float
                 ) -> List[float]:
    """Durations of the events of one name that lie wholly in the window."""
    return [(e - s) / 1e6 for n, s, e in events
            if n == name and s >= lo and e <= hi]


def split_gaps(idle: Sequence[Tuple[float, float]],
               events: Sequence[Event]) -> Dict[str, float]:
    """Each idle interval over the innermost events that cover it; the part
    no event covers goes to ``""``."""
    segs = sorted(xplane.leaf_segments(list(events)), key=lambda x: x[1])
    out: Dict[str, float] = {}
    for gs, ge in idle:
        covered = 0.0
        for n, s, e in segs:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[n] = out.get(n, 0.0) + ov
                covered += ov
        if ge - gs > covered:
            out[""] = out.get("", 0.0) + (ge - gs - covered)
    return out


def kernel_of(short: str, prefix: str) -> bool:
    """``pt_flash`` is in ``pt_flash_fwd.3`` and in
    ``transpose_jvp_pt_flash_bwd_dq__.1``, not in ``opt_flash.1``."""
    return re.search(r"(?:^|_)" + re.escape(prefix), short) is not None


def find_run_xplane(root: str, not_before: float) -> Optional[str]:
    """The newest ``.xplane.pb`` a ``harness.Tracer`` left under ``root``,
    refused if it was written before ``not_before`` (this process's start):
    a stale trace of another cell or run is never read."""
    hits = glob.glob(os.path.join(root, ".cache", "bench_trace", "*",
                                  "plugins", "profile", "*", "*.xplane.pb"))
    if not hits:
        return None
    newest = max(hits, key=os.path.getmtime)
    return newest if os.path.getmtime(newest) >= not_before else None


# -- one trace -----------------------------------------------------------------

class ProgramTrace:
    def __init__(self, planes: Dict[str, Dict[str, List[Event]]]):
        self.planes = planes
        host = xplane.host_spans(planes, "")
        w = [e for e in host if e[0] == xplane.WINDOW_SPAN]
        self.window = (min(e[1] for e in w), max(e[2] for e in w)) \
            if w else None
        self._host = [e for e in host if e[0].startswith("pt.")]
        self._dev = None

    def spans(self, prefix: str) -> List[Event]:
        return [e for e in self._host if e[0].startswith(prefix)]

    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def span_p50_ms(self, name: str) -> Optional[float]:
        if self.window is None:
            return None
        d = durations_ms(self._host, name, *self.window)
        return statistics.median(d) if d else None

    def owned_pct(self, prefix: str, names: Sequence[str]) -> Optional[float]:
        """Share of the window in which the innermost ``prefix`` span is one
        of ``names``; ``None`` when the trace holds no span of the prefix."""
        evs = self.spans(prefix)
        if self.window is None or not evs:
            return None
        own = owned_ns(evs, *self.window)
        return 100.0 * sum(own.get(n, 0.0) for n in names) / self.window_ns()

    def _devices(self) -> Dict[int, Dict]:
        """Per device: the leaf segments of its ops and its busy union,
        clipped to the window."""
        if self._dev is None:
            self._dev = {}
            for name, lines in self.planes.items():
                m = xplane.DEVICE_PLANE.match(name)
                if not m or not lines.get(xplane.OPS_LINE) or not self.window:
                    continue
                ops = clip(lines[xplane.OPS_LINE], *self.window)
                self._dev[int(m.group(1))] = {
                    "segs": xplane.leaf_segments(ops),
                    "busy": xplane.union((s, e) for _n, s, e in ops)}
        return self._dev

    def kernel_share_pct(self, prefix: str) -> Optional[float]:
        """Self time of the Mosaic calls named ``prefix*`` over busy time,
        both summed over the devices (as ``train.kernel_share_pct`` is);
        ``None`` without a device plane or without such a call (a program
        that does not name its kernels)."""
        dev = self._devices()
        if not dev:
            return None
        kern = sum(e - s for d in dev.values() for n, s, e in d["segs"]
                   if xplane.is_mosaic_kernel(n)
                   and kernel_of(xplane.short_name(n), prefix))
        busy = sum(xplane.total(d["busy"]) for d in dev.values())
        return 100.0 * kern / busy if kern and busy else None

    def idle_by_span(self, prefix: str) -> Optional[Dict[str, float]]:
        """The first device's idle time in the window (ns) by the innermost
        ``prefix`` span of the host that covers it."""
        dev = self._devices()
        if not dev or not self.spans(prefix):
            return None
        idle = xplane.gaps(dev[min(dev)]["busy"], *self.window)
        return split_gaps(idle, clip(self.spans(prefix), *self.window))

    def idle_pct(self, prefix: str, names: Sequence[str]) -> Optional[float]:
        by = self.idle_by_span(prefix)
        if by is None:
            return None
        return 100.0 * sum(by.get(n, 0.0) for n in names) / self.window_ns()


_CURRENT: Dict[str, Optional[ProgramTrace]] = {}


def process_start() -> float:
    """``benchmark/run.py`` notes its start first thing; a process that is
    not the benchmark's command has none, and reads no trace."""
    return getattr(sys.modules.get("__main__"), "T_PROCESS_START",
                   float("inf"))


def current(shapes: Dict, kind: str) -> Optional[ProgramTrace]:
    """This run's trace for a reader of ``kind`` cells, read once a
    process; ``None`` in a cell of another kind or a run that wrote none."""
    if shapes.get("kind") != kind:
        return None
    path = find_run_xplane(harness.ROOT, process_start())
    if path is None:
        return None
    if path not in _CURRENT:
        _CURRENT.clear()
        _CURRENT[path] = ProgramTrace(xplane.read_xplane(path))
    return _CURRENT[path]
