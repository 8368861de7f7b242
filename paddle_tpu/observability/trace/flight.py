"""Flight recorder: a bounded ring of recent step timelines + runtime
events with an anomaly detector that auto-dumps a diagnostic bundle.

The black-box-recorder role: when a training or serving process goes
sideways (step-time regression, stall spike, NaN/retry burst, preemption,
operator SIGQUIT), the question is always "what were the last N steps
doing?" — and by then the live process is gone or wedged. The recorder
keeps that answer on hand at a cost of one ring append per step, and
writes a ``pd_dump`` bundle the moment an anomaly trips:

- ``snapshot.json``      full ``observability.snapshot()``
- ``flight_ring.json``   the step ring + runtime events + anomaly log
- ``request_trace.json`` request/slot chrome-trace (serving processes)
- ``device_trace.json``  last XPlane correlation digest (if captured)
- ``memory_report.json`` memory truth: monitor snapshot + watermark
  history, top live buffers by shape/dtype/sharding, drift records, and
  the OOM context when one was reported (observability.memory)
- ``config.json``        versions, backend, devices, PT_* env, argv
- ``MANIFEST.json``      written LAST (the parseable-bundle contract)

Every ring step carries a ``mem`` stamp (device bytes in use / watermark /
host RSS) so a bundle's last-N-steps view answers "where was the memory
going" as well as "where was the time going". Serving engines land their
executed batches / decode steps in the events ring (``serving_step``)
with the same stamps.

Detectors (each arms only once enough baseline exists):

- **step regression**: step wall time > ``regress_factor`` x the median
  of the previous ``baseline`` steps AND ``min_regress_ms`` above it
  (a multiplicative threshold alone is noise on sub-ms baselines —
  a 5ms scheduler hiccup over a 1.5ms median is not a regression);
- **stall spike**: a blocking phase (``stream_wait``/``data_wait``)
  exceeds ``stall_frac`` of the step AND ``regress_factor`` x +
  ``min_regress_ms`` above its own rolling-baseline median (a steady
  transfer-bound walk never fires; a jump does);
- **burst**: ``nan_inf_events`` + resilience ``retries``/
  ``skipped_steps`` grow by >= ``burst_n`` within the last
  ``burst_window`` steps (a slow drip over thousands of steps never
  fires; three in a tight window does);
- **memory pressure**: device bytes-in-use grew by >= ``mem_growth_bytes``
  across the baseline window AND rose in >= 80% of its steps (leak
  suspicion — a steady plateau or a one-step spike-and-release never
  fires; sustained growth dumps the bundle BEFORE the eventual OOM).

Triggers are rate-limited (``min_dump_interval_s``, ``max_dumps``);
SIGQUIT and preemption dumps bypass the limit — an operator asking gets
an answer. Bundles land under ``PT_FLIGHT_DIR`` (default: a
``pt_flight_dumps`` dir under the system temp root — never the repo).
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..registry import family

__all__ = ["FlightRecorder", "flight_recorder", "dump_bundle"]

_BLOCKING = ("stream_wait", "data_wait")


def _utcstamp() -> str:
    return time.strftime("%Y%m%d_%H%M%S", time.gmtime())


def dump_bundle(out_dir: Optional[str] = None, reason: str = "manual",
                ring: Optional[Dict] = None) -> str:
    """Write one diagnostic bundle directory; returns its path. Every
    section degrades independently (a failed writer leaves an ``error``
    row in the manifest, never a half-missing bundle with no explanation);
    the manifest is written LAST so a bundle with a manifest is complete.
    """
    import tempfile

    root = out_dir or os.environ.get("PT_FLIGHT_DIR") or \
        os.path.join(tempfile.gettempdir(), "pt_flight_dumps")
    # fleet processes bundle under PT_FLIGHT_DIR/rank<r>/ so concurrent
    # workers never clobber (or interleave into) each other's dumps; the
    # fleet provider links the per-rank paths in its snapshot
    fleet_rank = os.environ.get("PT_FLEET_RANK")
    if out_dir is None and fleet_rank is not None:
        root = os.path.join(root, f"rank{fleet_rank}")
    path = os.path.join(
        root, f"pd_dump_{_utcstamp()}_{os.getpid()}_"
        f"{''.join(c if c.isalnum() else '_' for c in reason)[:32]}")
    os.makedirs(path, exist_ok=True)
    files: Dict[str, Any] = {}

    def _write(name: str, payload) -> None:
        try:
            p = os.path.join(path, name)
            with open(p, "w") as f:
                json.dump(payload, f, indent=1, default=str)
            files[name] = {"bytes": os.path.getsize(p)}
        except Exception as e:
            files[name] = {"error": str(e)[:200]}

    from .. import snapshot

    try:
        _write("snapshot.json", snapshot())
    except Exception as e:
        files["snapshot.json"] = {"error": str(e)[:200]}
    if ring is not None:
        _write("flight_ring.json", ring)
    try:
        from .request_trace import tracer

        if tracer().snapshot()["finished"] or tracer().snapshot()["live"]:
            tracer().export_chrome(os.path.join(path, "request_trace.json"))
            files["request_trace.json"] = {
                "bytes": os.path.getsize(
                    os.path.join(path, "request_trace.json"))}
    except Exception as e:
        files["request_trace.json"] = {"error": str(e)[:200]}
    try:
        from .capture import last_correlation

        cor = last_correlation()
        if cor is not None:
            _write("device_trace.json", cor.summary())
    except Exception as e:
        files["device_trace.json"] = {"error": str(e)[:200]}
    try:
        from ..memory import build_memory_report

        _write("memory_report.json", build_memory_report())
    except Exception as e:
        files["memory_report.json"] = {"error": str(e)[:200]}
    _write("config.json", _config_digest())
    # manifest LAST: its presence certifies the bundle is complete
    manifest = {"reason": reason, "time_utc": _utcstamp(),
                "pid": os.getpid(), "files": files}
    mp = os.path.join(path, "MANIFEST.json")
    tmp = mp + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, mp)
    return path


def _config_digest() -> Dict[str, Any]:
    out: Dict[str, Any] = {
        "pid": os.getpid(), "argv": sys.argv,
        "python": sys.version.split()[0],
        "env": {k: v for k, v in os.environ.items()
                if k.startswith(("PT_", "JAX_", "XLA_"))},
    }
    try:
        import jax
        import jaxlib

        out["jax"] = jax.__version__
        out["jaxlib"] = jaxlib.__version__
        out["backend"] = jax.default_backend()
        out["device_count"] = jax.device_count()
    except Exception as e:
        out["jax_error"] = str(e)[:200]
    try:
        from ...framework import flags as _flags

        out["flags"] = {k: v for k, v in _flags.get_flags().items()}
    except Exception:
        pass
    return out


class FlightRecorder:
    """See module docstring. One instance per process via
    ``flight_recorder()``; tests construct their own against a private
    ``StepTimeline``."""

    def __init__(self, capacity: int = 256, baseline: int = 16,
                 min_steps: int = 8, regress_factor: float = 3.0,
                 min_regress_ms: float = 25.0, stall_frac: float = 0.6,
                 burst_n: int = 3, burst_window: int = 8,
                 mem_growth_bytes: int = 64 << 20,
                 dump_dir: Optional[str] = None, auto_dump: bool = True,
                 min_dump_interval_s: float = 60.0, max_dumps: int = 3,
                 timeline_obj=None, mem_stamp_fn=None):
        self.capacity = int(capacity)
        self.baseline = int(baseline)
        self.min_steps = int(min_steps)
        self.regress_factor = float(regress_factor)
        self.min_regress_ms = float(min_regress_ms)
        self.stall_frac = float(stall_frac)
        self.burst_n = int(burst_n)
        self.burst_window = int(burst_window)
        self.mem_growth_bytes = int(mem_growth_bytes)
        # memory stamper: observability.memory.step_stamp by default;
        # tests inject a deterministic one
        self._mem_stamp_fn = mem_stamp_fn
        self.dump_dir = dump_dir
        self.auto_dump = bool(auto_dump)
        self.min_dump_interval_s = float(min_dump_interval_s)
        self.max_dumps = int(max_dumps)
        if timeline_obj is None:
            from ..timeline import timeline  # it imports this package

            timeline_obj = timeline()
        self._tl = timeline_obj
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._total_steps = 0  # monotone; never wraps with the ring
        self._events: deque = deque(maxlen=self.capacity)
        self._anomalies: deque = deque(maxlen=64)
        self._dumps: List[Dict] = []
        self._last_dump_t = 0.0
        self._fam = family("flight_recorder", ("event",))
        self._attached = False

    # -- wiring ---------------------------------------------------------------
    def attach(self) -> "FlightRecorder":
        """Start observing completed steps (idempotent)."""
        if not self._attached:
            self._tl.add_observer(self._on_step)
            self._attached = True
        return self

    def detach(self) -> None:
        if self._attached:
            self._tl.remove_observer(self._on_step)
            self._attached = False

    def install_signal(self, sig=None) -> bool:
        """SIGQUIT -> dump now (the operator's 'what is this process
        doing' key). Main-thread only; returns False elsewhere."""
        import signal as _signal

        sig = _signal.SIGQUIT if sig is None else sig
        try:
            _signal.signal(sig, lambda *_: self._dump_async("sigquit"))
            return True
        except ValueError:
            return False

    def watch_preemption(self) -> None:
        """Dump when the resilience SIGTERM handler fires — the bundle
        rides out with the final checkpoint."""
        try:
            from ...distributed.resilience import preempt

            preempt.on_preemption(
                lambda: self._trigger_async("preemption"))
        except Exception:
            pass

    def _dump_async(self, reason: str) -> None:
        """Signal-context dump: handlers run on the main thread between
        bytecodes and can interrupt a step that already holds this
        recorder's (or the hub's/timeline's) non-reentrant locks — taking
        them inline would self-deadlock the process at the exact moment it
        must answer. A short-lived thread takes them from a clean stack;
        the bundle's manifest-last contract covers a process that exits
        before the write completes."""
        threading.Thread(target=self.dump, args=(reason,),
                         kwargs={"force": True}, daemon=True,
                         name=f"pt-flight-dump-{reason}").start()

    def _trigger_async(self, reason: str) -> None:
        """Signal-context trigger (see ``_dump_async``): the anomaly
        append also takes ``self._lock``."""
        threading.Thread(target=self.trigger, args=(reason,),
                         kwargs={"force": True}, daemon=True,
                         name=f"pt-flight-dump-{reason}").start()

    # -- recording ------------------------------------------------------------
    def _sample_counters(self) -> Dict[str, float]:
        out = {}
        try:
            from ..registry import family as _family

            out["nan_inf"] = _family("nan_inf_events").total()
            res = _family("resilience")
            out["retries"] = res.get(("retries",))
            out["skipped_steps"] = res.get(("skipped_steps",))
        except Exception:
            pass
        return out

    def _mem_stamp(self) -> Optional[Dict[str, float]]:
        """Per-step memory stamp (device in-use / watermark / host RSS):
        the default stamper is the throttled monitor read; any failure
        degrades to no stamp, never a broken step."""
        try:
            fn = self._mem_stamp_fn
            if fn is None:
                from ..memory import step_stamp

                fn = self._mem_stamp_fn = step_stamp
            return fn()
        except Exception:
            return None

    def _on_step(self, wall_ms: float, phases) -> None:
        rec = {"t": time.time(), "ms": round(wall_ms, 3),
               "phases": {n: round(d, 3) for (n, _rel, d) in phases},
               "counters": self._sample_counters()}
        mem = self._mem_stamp()
        if mem is not None:
            rec["mem"] = mem
        with self._lock:
            prior = list(self._ring)
            self._ring.append(rec)
            self._total_steps += 1
        reasons = self._detect(rec, prior)
        for r in reasons:
            self.trigger(r, step=rec)

    def step_series(self, n: Optional[int] = None
                    ) -> Tuple[int, List[float]]:
        """The last ``n`` (default: all ringed) step wall-times as
        ``(first_seq, [ms, ...])`` where ``first_seq`` is the monotone
        index of the first returned sample — the online tuner's
        incremental read (consume only samples past the last seq seen,
        ring wraparound included)."""
        with self._lock:
            ring = list(self._ring)
            total = self._total_steps
        if n is not None:
            ring = ring[-int(n):]
        return total - len(ring), [r["ms"] for r in ring]

    def record_event(self, kind: str, **data) -> None:
        """Runtime events that belong in the ring next to the steps
        (stream retries/errors, preemptions, checkpoint commits)."""
        with self._lock:
            self._events.append({"t": time.time(), "kind": kind, **data})
        self._fam.inc(("event:" + kind,))

    def record_serving_step(self, engine: str, kind: str, ms: float,
                            n: int) -> None:
        """One executed serving batch / decode step into the events ring
        (the PR-7 carried ROADMAP item: serving lands in the ring
        automatically), memory-stamped like a train step."""
        data = {"engine": engine, "op": kind, "ms": round(ms, 3), "n": n}
        mem = self._mem_stamp()
        if mem is not None:
            data["mem"] = mem
        self.record_event("serving_step", **data)

    # -- detection ------------------------------------------------------------
    def _detect(self, rec: Dict, prior: List[Dict]) -> List[str]:
        reasons = []
        window = [r["ms"] for r in prior[-self.baseline:]]
        # a step containing a compile phase is EXPECTED to be slow (cold
        # build) — never a regression, and rare enough that the median
        # baseline absorbs it
        if len(window) >= self.min_steps and "compile" not in rec["phases"]:
            med = statistics.median(window)
            # multiplicative AND absolute elevation: 3x a sub-ms median
            # is scheduler jitter, not a regression worth a bundle
            if med > 0 and rec["ms"] > self.regress_factor * med \
                    and rec["ms"] - med > self.min_regress_ms:
                reasons.append(
                    f"step_regression:{rec['ms']:.1f}ms_vs_median_{med:.1f}ms")
        stall = sum(rec["phases"].get(p, 0.0) for p in _BLOCKING)
        if len(window) >= self.min_steps and rec["ms"] > 1.0 \
                and stall > self.stall_frac * rec["ms"]:
            # a SPIKE, not a steady state: a transfer-bound walk whose
            # every step is mostly stream_wait is working as configured —
            # fire only when the stall also jumps vs its own baseline
            med_stall = statistics.median(
                sum(r["phases"].get(p, 0.0) for p in _BLOCKING)
                for r in prior[-self.baseline:])
            if stall > self.regress_factor * med_stall \
                    and stall - med_stall > self.min_regress_ms:
                reasons.append(
                    f"stall_spike:{stall:.1f}ms_of_{rec['ms']:.1f}ms")
        # burst = counter growth vs burst_window steps AGO: a slow drip
        # over a long run never fires, a tight cluster does
        if prior:
            base = prior[max(len(prior) - self.burst_window, 0)]["counters"]
            burst = sum(rec["counters"].get(k, 0.0) - base.get(k, 0.0)
                        for k in ("nan_inf", "retries", "skipped_steps"))
            if burst >= self.burst_n:
                reasons.append(f"fault_burst:+{burst:g}")
        # memory pressure = sustained device-bytes growth across the
        # baseline window (leak suspicion): total growth over the
        # threshold AND rising in >= 80% of the window's steps — a
        # plateau, or one spike-and-release, never fires
        mem = rec.get("mem")
        if mem is not None and len(window) >= self.min_steps:
            series = [r["mem"]["in_use"] for r in prior[-self.baseline:]
                      if r.get("mem")] + [mem["in_use"]]
            if len(series) > self.min_steps:
                growth = series[-1] - series[0]
                pairs = list(zip(series, series[1:]))
                rising = sum(1 for a, b in pairs if b >= a)
                strict = sum(1 for a, b in pairs if b > a)
                # >= 3 strict rises: equal pairs are common (the 50 ms
                # stamp throttle repeats stamps across fast steps), so the
                # rising gate alone is near-vacuous — one or two isolated
                # jumps settling into plateaus (a resident working set
                # landing) are not a leak signature; a leak keeps stepping
                if growth >= self.mem_growth_bytes and strict >= 3 and \
                        rising >= 0.8 * len(pairs):
                    reasons.append(
                        f"memory_pressure:+{growth / 1e6:.0f}MB_over_"
                        f"{len(series) - 1}steps")
        return reasons

    # -- triggering -----------------------------------------------------------
    def trigger(self, reason: str, step: Optional[Dict] = None,
                force: bool = False) -> Optional[str]:
        """Record an anomaly; auto-dump if armed and not rate-limited.
        Returns the bundle path when one was written."""
        with self._lock:
            self._anomalies.append({"t": time.time(), "reason": reason,
                                    "step": step})
        self._fam.inc(("anomaly",))
        if not (self.auto_dump or force):
            return None
        return self.dump(reason, force=force)

    def dump(self, reason: str = "manual", force: bool = False
             ) -> Optional[str]:
        now = time.time()
        with self._lock:
            if not force:
                if len(self._dumps) >= self.max_dumps:
                    return None
                if now - self._last_dump_t < self.min_dump_interval_s:
                    return None
            self._last_dump_t = now
        try:
            path = dump_bundle(self.dump_dir, reason, ring=self.snapshot())
        except Exception:  # a failed dump must never sink the step loop
            self._fam.inc(("dump_failed",))
            return None
        with self._lock:
            self._dumps.append({"t": now, "reason": reason, "path": path})
        self._fam.inc(("dump",))
        return path

    # -- reads ----------------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "steps_recorded": len(self._ring),
                "ring": list(self._ring),
                "events": list(self._events),
                "anomalies": list(self._anomalies),
                "dumps": list(self._dumps),
                "config": {
                    "capacity": self.capacity, "baseline": self.baseline,
                    "min_steps": self.min_steps,
                    "regress_factor": self.regress_factor,
                    "min_regress_ms": self.min_regress_ms,
                    "stall_frac": self.stall_frac, "burst_n": self.burst_n,
                    "mem_growth_bytes": self.mem_growth_bytes,
                },
            }


_RECORDER: Optional[FlightRecorder] = None
_RECORDER_LOCK = threading.Lock()


def flight_recorder(**kwargs) -> FlightRecorder:
    """The process-wide recorder, created + attached on first use (env
    overrides: ``PT_FLIGHT_DIR`` for the bundle root). Later calls return
    the existing instance (kwargs apply only to the first)."""
    global _RECORDER
    if _RECORDER is not None:
        return _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is None:
            rec = FlightRecorder(**kwargs)
            rec.attach()
            rec.watch_preemption()
            _RECORDER = rec
    return _RECORDER
