"""StepTimeline: where did this training step's milliseconds go?

Reference role: profiler_statistic.py's per-step breakdown tables over
host_tracer.cc spans. TPU-native translation: the compiled step makes the
device timeline XLA's business, so the host-side question becomes a
per-step phase split:

- ``data_wait``      blocked on the loader / prefetcher for the next batch
- ``host_dispatch``  python + dispatch until the compiled step call returns
                     (async under jax: the device keeps computing after)
- ``device_block``   host *blocking* on the step's outputs — recorded only
                     in *detailed* mode (a Profiler is active or
                     ``timeline().detail(True)``), because the block itself
                     would serialize the async pipeline. This is HOST time,
                     not device time: an upper bound that also contains
                     dispatch slack. Real device time comes from XPlane
                     correlation (below).
- ``compile``        cold builds: trace + XLA compile + first execution
- ``stream_wait``    offload-path steps only: blocked on the streaming
                     lane (a group transfer not yet hidden behind compute)

Device truth: every step and phase bracket is an
``observability.trace.span`` — ``pt.train.step`` (``step_num`` as its
argument) and ``pt.train.<phase>`` — so it is in whatever profiler trace is
running (``capture_steps()``, TensorBoard, xprof, the benchmark's own) on
the device's clock, and in the tracer's worker ring. ``capture_steps()``'s
correlation ingests per-step *device* time back here
(``ingest_device_steps``), so ``summary()`` reports ``device_compute_us``
measured by XLA's own tracer — in every mode, not just detailed — with
``device_source`` naming where the number came from (``"xplane"`` vs the
``device_block`` host proxy).

Producers: ``jit.TrainStep`` / ``AccumulateStep`` / ``ShardedTrainStep`` /
``ShardedAccumulateStep`` wrap their calls, ``hapi.Model.fit`` wraps its
epoch loop. Each phase is aggregated (count/total/max/last — a few adds
per step); while a ``profiler.Profiler`` is recording the spans are in its
chrome-trace export too, next to user/op spans. Completed steps additionally
feed any registered observers (the flight recorder's ring) and the
``step_time_ms`` histogram.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from .trace.request_trace import span

__all__ = ["StepTimeline", "timeline"]


class _PhaseAgg:
    __slots__ = ("count", "total_ms", "max_ms", "last_ms")

    def __init__(self):
        self.count = 0
        self.total_ms = 0.0
        self.max_ms = 0.0
        self.last_ms = 0.0

    def add(self, ms: float):
        self.count += 1
        self.total_ms += ms
        self.last_ms = ms
        if ms > self.max_ms:
            self.max_ms = ms


class _PhaseCtx:
    __slots__ = ("_tl", "_name", "_t0", "_span")

    def __init__(self, tl: "StepTimeline", name: str):
        self._tl = tl
        self._name = name
        self._t0 = None
        self._span = None

    def __enter__(self):
        self._span = span("pt.train." + self._name)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._tl.record(self._name,
                        (time.perf_counter() - self._t0) * 1e3,
                        t0=self._t0)
        self._span.__exit__(None, None, None)
        return False


class _StepCtx:
    __slots__ = ("_tl", "_t0", "_cancelled")

    def __init__(self, tl: "StepTimeline"):
        self._tl = tl
        self._t0 = None
        self._cancelled = False

    def cancel(self):
        """Don't count this bracket as a step (an exhausted-loader probe)."""
        self._cancelled = True

    def __enter__(self):
        self._t0 = self._tl._begin_step()
        return self

    def __exit__(self, *exc):
        self._tl._end_step(self._t0, cancelled=self._cancelled)
        return False


class StepTimeline:
    """Per-step phase aggregator (process-global via ``timeline()``).

    Off-path cost per phase: two ``perf_counter`` reads and a locked
    aggregate add — the "few atomic increments" overhead contract.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._phases: Dict[str, _PhaseAgg] = {}
        self._steps = 0
        self._begun = 0  # step brackets opened (pt.train.step's step_num)
        self._step_total = _PhaseAgg()
        self._detail = False
        # XPlane-correlated device time per step (ingest_device_steps);
        # None source until a capture window delivers real device numbers
        self._device = _PhaseAgg()
        self._device_source: Optional[str] = None
        # last completed step's phase spans, (name, rel_ms, dur_ms) in
        # record order — the "ordered" assertion surface for tests/pd_top
        self._last_step: List[Tuple[str, float, float]] = []
        # completed-step observers (the flight recorder): fn(ms, phases)
        self._observers: List[Callable] = []
        # step_time_ms histogram, resolved lazily once (not per step —
        # the hub lookup takes a process-global lock)
        self._step_hist = None
        # step bracketing is PER THREAD (depth, open-step span list, t0):
        # two loops stepping concurrently must not nest into each other;
        # the aggregates above stay shared under the lock
        self._tls = threading.local()

    # -- configuration --------------------------------------------------------
    def detail(self, on: bool = True) -> "StepTimeline":
        """Force detailed mode (the ``device_block`` host-side block)
        regardless of the profiler state."""
        self._detail = bool(on)
        return self

    @property
    def detailed(self) -> bool:
        if self._detail:
            return True
        try:
            from .. import profiler

            return profiler.is_recording()
        except Exception:
            return False

    def add_observer(self, fn: Callable) -> None:
        """``fn(wall_ms, phases)`` after every completed (non-cancelled)
        step; ``phases`` is the ordered [(name, rel_ms, dur_ms)] list.
        Observer failures are swallowed — telemetry never sinks a step."""
        with self._lock:
            if fn not in self._observers:
                self._observers.append(fn)

    def remove_observer(self, fn: Callable) -> None:
        with self._lock:
            if fn in self._observers:
                self._observers.remove(fn)

    # -- recording ------------------------------------------------------------
    def step(self) -> _StepCtx:
        """Context manager bracketing one training step."""
        return _StepCtx(self)

    def phase(self, name: str) -> _PhaseCtx:
        """Context manager timing one phase (inside or outside a step)."""
        return _PhaseCtx(self, name)

    def record(self, name: str, ms: float, t0: Optional[float] = None) -> None:
        cur = getattr(self._tls, "cur", None)
        with self._lock:
            agg = self._phases.get(name)
            if agg is None:
                agg = self._phases[name] = _PhaseAgg()
            agg.add(ms)
            if cur is not None and t0 is not None:
                cur.append((name, (t0 - self._tls.t0) * 1e3, ms))

    def ingest_device_steps(self, per_step_us, source: str = "xplane") -> None:
        """Land XPlane-correlated per-step device-compute times (us). The
        aggregates surface in ``summary()["device_compute_us"]`` with
        ``device_source`` naming the provenance — the replacement for the
        host-block proxy in ALL modes."""
        with self._lock:
            for us in per_step_us:
                self._device.add(float(us))
            if per_step_us:
                self._device_source = source

    def _begin_step(self) -> float:
        t0 = time.perf_counter()
        ts = self._tls
        depth = getattr(ts, "depth", 0)
        ts.depth = depth + 1
        if depth == 0:  # the outermost bracket owns the step
            ts.cur = []
            ts.t0 = t0
            with self._lock:
                n = self._begun
                self._begun += 1
            ts.span = span("pt.train.step", step_num=n)
            ts.span.__enter__()
        return t0

    def _end_step(self, t0: float, cancelled: bool = False) -> None:
        ms = (time.perf_counter() - t0) * 1e3
        ts = self._tls
        ts.depth = max(getattr(ts, "depth", 1) - 1, 0)
        if ts.depth > 0:
            return
        cur, ts.cur = getattr(ts, "cur", None), None
        step_span, ts.span = getattr(ts, "span", None), None
        if step_span is not None:
            step_span.__exit__(None, None, None)
        if cancelled:
            return
        with self._lock:
            self._steps += 1
            self._step_total.add(ms)
            if cur is not None:
                self._last_step = cur
            observers = list(self._observers)
        try:
            h = self._step_hist
            if h is None:
                from .registry import histogram

                h = self._step_hist = histogram("step_time_ms")
            h.observe(ms)
        except Exception:
            pass
        for fn in observers:
            try:
                fn(ms, cur or [])
            except Exception:
                pass

    # -- reads ----------------------------------------------------------------
    def summary(self) -> Dict:
        """JSON-able aggregate: per-phase count/total/avg/max/last, step
        count, the last step's ordered phase list, and — when an XPlane
        capture has correlated — real per-step device time."""
        with self._lock:
            phases = {
                name: {
                    "count": a.count,
                    "total_ms": round(a.total_ms, 3),
                    "avg_ms": round(a.total_ms / a.count, 3) if a.count else 0.0,
                    "max_ms": round(a.max_ms, 3),
                    "last_ms": round(a.last_ms, 3),
                }
                for name, a in self._phases.items()
            }
            out = {
                "steps": self._steps,
                "step_total_ms": {
                    "avg": round(self._step_total.total_ms /
                                 self._step_total.count, 3)
                    if self._step_total.count else 0.0,
                    "max": round(self._step_total.max_ms, 3),
                    "last": round(self._step_total.last_ms, 3),
                },
                "phases": phases,
                "last_step": [
                    {"phase": n, "rel_ms": round(rel, 3),
                     "dur_ms": round(d, 3)}
                    for (n, rel, d) in self._last_step
                ],
                "detailed": self.detailed,
            }
            # device-time provenance: "xplane" = real device events from a
            # trace capture; "host_block" = only the detailed-mode blocking
            # proxy exists (an upper bound, NOT device time); None = neither
            if self._device.count:
                d = self._device
                out["device_compute_us"] = {
                    "count": d.count,
                    "total": round(d.total_ms, 1),
                    "avg": round(d.total_ms / d.count, 1),
                    "max": round(d.max_ms, 1),
                    "last": round(d.last_ms, 1),
                }
                out["device_source"] = self._device_source
            elif "device_block" in phases:
                out["device_source"] = "host_block"
            else:
                out["device_source"] = None
            return out

    def table(self, time_unit: str = "ms") -> str:
        """Human summary table (profiler_statistic.py shape)."""
        s = self.summary()
        div = {"s": 1e3, "ms": 1.0, "us": 1e-3}[time_unit]
        lines = [
            f"StepTimeline — {s['steps']} steps, "
            f"avg {s['step_total_ms']['avg']} ms/step",
            f"{'Phase':<20}{'Count':>8}{'Total(' + time_unit + ')':>14}"
            f"{'Avg(' + time_unit + ')':>12}{'Max(' + time_unit + ')':>12}"
            f"{'Last(' + time_unit + ')':>12}",
            "-" * 78,
        ]
        order = sorted(s["phases"].items(), key=lambda kv: -kv[1]["total_ms"])
        for name, row in order:
            lines.append(
                f"{name[:19]:<20}{row['count']:>8}"
                f"{row['total_ms'] / div:>14.3f}{row['avg_ms'] / div:>12.3f}"
                f"{row['max_ms'] / div:>12.3f}{row['last_ms'] / div:>12.3f}")
        dev = s.get("device_compute_us")
        if dev:
            lines.append(
                f"device_compute (XPlane): avg {dev['avg']}us over "
                f"{dev['count']} correlated steps")
        return "\n".join(lines)

    def reset(self) -> None:
        with self._lock:
            self._phases.clear()
            self._steps = 0
            self._begun = 0
            self._step_total = _PhaseAgg()
            self._device = _PhaseAgg()
            self._device_source = None
            self._last_step = []
        self._tls.cur = None
        self._tls.depth = 0


_TIMELINE = StepTimeline()


def timeline() -> StepTimeline:
    """The process-global StepTimeline every train-step producer feeds."""
    return _TIMELINE
