"""Request-scoped tracing for the serving engines.

Every admitted serving request gets a process-unique trace ID that
propagates through its whole life: admission -> queue -> batch coalesce ->
execution (ServingEngine) / prefill -> decode -> completion
(GenerationEngine). Spans are recorded retroactively from the engines'
own timestamps (zero extra clock reads on the hot path beyond what the
metrics already take) into a bounded ring, and exported as chrome-trace /
Perfetto JSON next to the profiler's host spans:

- one Perfetto *thread* row per request (its spans read left to right:
  queue, coalesce, execute / prefill, decode);
- one ``slots:<engine>`` process with a row per KV slot — the
  GenerationEngine occupancy timeline (each residency span carries the
  owning trace ID and token count).

- one ``worker:<engine>`` row per thread that ran ``span()``s: the serving
  worker's rounds, waits and page work, the train loop's steps and phases.

Cost per request: a few dict appends under one lock. The ring bounds
memory (finished traces beyond ``capacity`` drop oldest-first and are
counted), so the tracer is always-on — no sampling knob to forget.

``span(name, **args)`` is the program's one span primitive: it enters a
``jax.profiler.TraceAnnotation`` unconditionally — so the span lands in
whatever profiler trace is running (TensorBoard, xprof, the benchmark's
own), on the device's clock, and costs a few microseconds when none is —
and on exit appends itself, with the span that enclosed it on its thread,
to the tracer's bounded worker ring. Names are stable and dotted
(``pt.<tier>.<what>``); what varies is an argument, never part of the name.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["RequestTracer", "tracer", "span"]


def _us(t_monotonic: float) -> float:
    return t_monotonic * 1e6


class _Trace:
    __slots__ = ("trace_id", "engine", "kind", "t0", "spans", "done",
                 "ok", "meta", "parent")

    def __init__(self, trace_id, engine, kind, t0, meta, parent=None):
        self.trace_id = trace_id
        self.engine = engine
        self.kind = kind
        self.t0 = t0
        self.spans: List[Dict] = []
        self.done = False
        self.ok: Optional[bool] = None
        self.meta = meta
        self.parent = parent


class RequestTracer:
    """Process-wide request-span collector (one instance via ``tracer()``)."""

    def __init__(self, capacity: int = 2048, slot_capacity: int = 1024,
                 worker_capacity: int = 16384):
        self._lock = threading.Lock()
        self._seq = itertools.count(1)
        self._live: Dict[str, _Trace] = {}
        self._done: deque = deque(maxlen=capacity)
        self._slots: deque = deque(maxlen=slot_capacity)
        self._worker: deque = deque(maxlen=worker_capacity)
        self._counts = {"started": 0, "finished": 0, "failed": 0,
                        "spans": 0, "slot_spans": 0, "worker_spans": 0,
                        "worker_dropped": 0}

    # -- recording ------------------------------------------------------------
    def start(self, engine: str, kind: str = "request",
              t0: Optional[float] = None, parent: Optional[str] = None,
              trace_id: Optional[str] = None, **meta) -> str:
        """Open a trace; returns its ID (carried by the request object).

        ``parent`` is an EXTERNAL trace context (e.g. the supervisor-
        minted ``fleet-<id>``): this process's spans nest under it when a
        fleet collector merges traces across processes. ``trace_id``
        overrides the minted pid-local id — the supervisor uses the fleet
        context itself as its own trace id, so its routing spans and the
        replicas' parented spans share one key."""
        if trace_id is None:
            trace_id = f"{os.getpid():x}-{next(self._seq):x}"
        tr = _Trace(trace_id, engine, kind,
                    time.monotonic() if t0 is None else t0, meta,
                    parent=parent)
        with self._lock:
            self._live[trace_id] = tr
            self._counts["started"] += 1
        return trace_id

    def span(self, trace_id: Optional[str], name: str, t0: float, t1: float,
             **args) -> None:
        """Record one span [t0, t1) (``time.monotonic`` seconds — the
        engines' native timestamps). Unknown/None IDs are ignored so call
        sites never need their own guards."""
        if trace_id is None:
            return
        with self._lock:
            tr = self._live.get(trace_id)
            if tr is None:
                return
            tr.spans.append({"name": name, "t0": t0,
                             "dur_us": max(_us(t1 - t0), 0.0), "args": args})
            self._counts["spans"] += 1

    def finish(self, trace_id: Optional[str], ok: bool = True,
               **args) -> None:
        if trace_id is None:
            return
        with self._lock:
            tr = self._live.pop(trace_id, None)
            if tr is None:
                return
            tr.done = True
            tr.ok = ok
            if args:
                tr.meta.update(args)
            self._done.append(tr)
            self._counts["finished"] += 1
            if not ok:
                self._counts["failed"] += 1

    def slot_span(self, engine: str, slot: int, t0: float, t1: float,
                  trace_id: Optional[str], **args) -> None:
        """One KV-slot residency (admit -> release) on the occupancy
        track."""
        with self._lock:
            self._slots.append({"engine": engine, "slot": int(slot),
                                "t0": t0, "dur_us": max(_us(t1 - t0), 0.0),
                                "trace_id": trace_id, "args": args})
            self._counts["slot_spans"] += 1

    def worker_span(self, span_id: int, name: str, t0: float, t1: float,
                    thread: str, parent: Optional[int], args: Dict) -> None:
        """One closed ``span()`` of a worker or stepping thread. ``parent``
        is the id of the span that enclosed it on that thread, so a span's
        self time is its duration less its children's."""
        row = {"id": span_id, "name": name, "t0": t0,
               "dur_us": max(_us(t1 - t0), 0.0), "thread": thread,
               "parent": parent, "args": args}
        with self._lock:
            if len(self._worker) == self._worker.maxlen:
                self._counts["worker_dropped"] += 1
            self._worker.append(row)
            self._counts["worker_spans"] += 1

    # -- reads ----------------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {**self._counts, "live": len(self._live),
                    "ring": len(self._done), "slot_ring": len(self._slots),
                    "worker_ring": len(self._worker)}

    def worker_spans(self, thread: Optional[str] = None) -> List[Dict]:
        """The worker ring (oldest first, children before their parents:
        a span lands when it closes), each row with its ``self_us``."""
        with self._lock:
            rows = [dict(r) for r in self._worker
                    if thread is None or r["thread"] == thread]
        children: Dict[int, float] = {}
        for r in rows:
            if r["parent"] is not None:
                children[r["parent"]] = children.get(r["parent"], 0.0) \
                    + r["dur_us"]
        for r in rows:
            r["self_us"] = max(r["dur_us"] - children.get(r["id"], 0.0), 0.0)
        return rows

    @staticmethod
    def _export(tr: "_Trace", slots: Optional[List[Dict]] = None) -> Dict:
        out = {"trace_id": tr.trace_id, "engine": tr.engine,
               "kind": tr.kind, "ok": tr.ok, "meta": dict(tr.meta),
               "parent": tr.parent, "pid": os.getpid(),
               "spans": [dict(s) for s in tr.spans]}
        if slots is not None:
            out["slots"] = slots
        return out

    def traces(self, engine: Optional[str] = None) -> List[Dict]:
        """Finished traces (oldest first), JSON-able."""
        with self._lock:
            done = list(self._done)
        return [self._export(tr) for tr in done
                if engine is None or tr.engine == engine]

    def drain_finished(self, max_n: int = 64,
                       require_parent: bool = False,
                       prefix: Optional[str] = None) -> List[Dict]:
        """Pop up to ``max_n`` finished traces (oldest first) as JSON-able
        dicts — the fleet-collector pull: a drained trace leaves the
        local ring, so the supervisor's merged store owns it from here.
        ``require_parent`` selects only externally-parented traces (a
        replica ships fleet requests, never its local-only work);
        ``prefix`` selects on the trace id (the supervisor drains its own
        ``fleet-*`` traces). Matching slot-residency spans ride along
        inside each trace dict (they nest under the fleet trace too)."""
        with self._lock:
            keep, out = deque(maxlen=self._done.maxlen), []
            slots_by_trace: Dict[str, List[Dict]] = {}
            for s in self._slots:
                tid = s.get("trace_id")
                if tid is not None:
                    slots_by_trace.setdefault(tid, []).append(dict(s))
            for tr in self._done:
                wanted = len(out) < max_n
                if wanted and require_parent and tr.parent is None:
                    wanted = False
                if wanted and prefix is not None and \
                        not tr.trace_id.startswith(prefix):
                    wanted = False
                if wanted:
                    out.append(self._export(
                        tr, slots=slots_by_trace.get(tr.trace_id, [])))
                else:
                    keep.append(tr)
            self._done = keep
        return out

    def chrome_events(self) -> List[Dict]:
        """Chrome-trace events: a pid per engine, a tid per request (its
        spans form one row), plus a ``slots:<engine>`` pid with a row per
        slot. Every span's args carry the trace ID — Perfetto's query/
        highlight key."""
        with self._lock:
            done = list(self._done)
            slots = list(self._slots)
            worker = list(self._worker)
        events: List[Dict] = []
        pids: Dict[str, int] = {}

        def pid_of(label: str) -> int:
            if label not in pids:
                pids[label] = 1000 + len(pids)
                events.append({"ph": "M", "pid": pids[label],
                               "name": "process_name",
                               "args": {"name": label}})
            return pids[label]

        for i, tr in enumerate(done):
            pid = pid_of(f"requests:{tr.engine}")
            tid = i + 1
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": f"req {tr.trace_id}"}})
            for s in tr.spans:
                events.append({
                    "ph": "X", "pid": pid, "tid": tid, "name": s["name"],
                    "ts": _us(s["t0"]), "dur": s["dur_us"],
                    "cat": tr.kind,
                    "args": {"trace_id": tr.trace_id, "ok": tr.ok,
                             **({"parent": tr.parent} if tr.parent else {}),
                             **s["args"]},
                })
        for s in slots:
            pid = pid_of(f"slots:{s['engine']}")
            events.append({
                "ph": "X", "pid": pid, "tid": s["slot"] + 1,
                "name": f"slot{s['slot']}",
                "ts": _us(s["t0"]), "dur": s["dur_us"], "cat": "slot",
                "args": {"trace_id": s["trace_id"], **s["args"]},
            })
        for s in worker:  # nested spans of one thread stack in one row
            pid = pid_of("worker:" + s["thread"].removeprefix("pt-serving-"))
            events.append({
                "ph": "X", "pid": pid, "tid": 1, "name": s["name"],
                "ts": _us(s["t0"]), "dur": s["dur_us"], "cat": "worker",
                "args": s["args"],
            })
        return events

    def export_chrome(self, path: str) -> str:
        """Write the request + slot tracks as chrome-trace JSON (load in
        Perfetto/chrome://tracing next to the profiler's span export)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"displayTimeUnit": "ms",
                       "traceEvents": self.chrome_events()}, f)
        return path

    def reset(self) -> None:
        with self._lock:
            self._live.clear()
            self._done.clear()
            self._slots.clear()
            self._worker.clear()
            for k in self._counts:
                self._counts[k] = 0


_TRACER = RequestTracer()


def tracer() -> RequestTracer:
    """The process-wide request tracer every serving engine feeds."""
    return _TRACER


_SPAN_SEQ = itertools.count(1)
_TLS = threading.local()
_PROFILER = None  # paddle_tpu.profiler, resolved at the first span


class span:
    """``with span("pt.serve.decode_round", n_active=3): ...`` — see the
    module docstring. While a ``paddle_tpu.profiler.Profiler`` records,
    the span is also in its host-event buffer (the chrome export)."""

    __slots__ = ("name", "args", "_annot", "_id", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self._annot = TraceAnnotation(name, **args)

    def __enter__(self) -> "span":
        try:
            stack = _TLS.stack
        except AttributeError:
            stack = _TLS.stack = []
            _TLS.thread = threading.current_thread().name
        self._id = next(_SPAN_SEQ)
        stack.append(self._id)
        self._annot.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        global _PROFILER
        t1 = time.monotonic()
        self._annot.__exit__(None, None, None)
        stack = _TLS.stack
        stack.pop()
        _TRACER.worker_span(self._id, self.name, self._t0, t1, _TLS.thread,
                            stack[-1] if stack else None, self.args)
        if _PROFILER is None:
            from ... import profiler as _PROFILER
        if _PROFILER._RECORDER.active:  # its clock is perf_counter
            dur_us = (t1 - self._t0) * 1e6
            _PROFILER._RECORDER.record(
                self.name, time.perf_counter() * 1e6 - dur_us, dur_us,
                "Span")
        return False
