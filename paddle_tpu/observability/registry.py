"""Process-wide metrics registry: counters, gauges, latency windows, families.

Reference role: the reference's observability stack is split across
host_tracer.cc (spans), profiler_statistic.py (summaries) and the serving
stack's brpc metrics; here ONE process hub owns every counter the framework
emits, and each subsystem registers its island into it:

- ``MetricsRegistry`` (promoted from ``paddle_tpu.serving.metrics``, which
  is now a thin alias): per-engine QPS / latency windows / occupancy;
- ``CounterFamily``: labeled monotonic counters (``nan_inf_events`` by
  (op, dtype), ``collectives`` by op, ``trace_cache`` by site/event);
- ``Histogram``: fixed-bucket distributions with native Prometheus
  histogram exposition (``request_latency_ms``, ``queue_wait_ms``,
  ``step_time_ms`` — the external-scrape shapes percentile windows
  cannot aggregate across processes);
- providers: snapshot-time callables for state that already lives
  elsewhere (``jit.persistent_cache.stats()``, ``analysis.retrace``
  summaries, the ``StepTimeline``) — zero steady-state cost;
- gauges: live values sampled at snapshot time (prefetcher queue depth).

Hot-path contract: recording into a family is one lock + one dict add —
a few "atomic increments" per step. Everything heavier (percentiles,
provider snapshots, exposition) happens at read time.
"""
from __future__ import annotations

import bisect
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, \
    Union

import numpy as np

__all__ = ["LatencyWindow", "MetricsRegistry", "CounterFamily", "Histogram",
           "Hub", "hub", "family", "gauge", "histogram", "register_provider",
           "register_registry"]


def _named_lock(name: str):
    """Hub-internal mutex: witnessed under PT_LOCKDEP=1, plain otherwise.
    Env-gated so the default path never imports paddle_tpu.analysis (and
    jax) at registry-import time, and built on the raw ``lockdep.Lock``
    class — the factory's provider registration would re-enter hub
    construction from inside ``Hub.__init__``."""
    import os

    if os.environ.get("PT_LOCKDEP", "") not in ("", "0", "false"):
        try:
            from ..analysis.lockdep import Lock

            return Lock(name)
        except Exception:
            pass
    return threading.Lock()


class LatencyWindow:
    """Ring buffer of the most recent latencies (ms); percentiles on read.

    A fixed-size window keeps snapshot cost bounded and the percentiles
    honest about *recent* traffic rather than the whole process lifetime.
    """

    def __init__(self, capacity: int = 8192):
        self._buf = np.zeros(capacity, dtype=np.float64)
        self._capacity = capacity
        self._n = 0          # total observations ever
        self._count = 0      # filled entries (<= capacity)
        self._idx = 0

    def observe(self, ms: float) -> None:
        self._buf[self._idx] = ms
        self._idx = (self._idx + 1) % self._capacity
        self._count = min(self._count + 1, self._capacity)
        self._n += 1

    def percentiles(self, qs=(50, 95, 99)) -> Dict[str, float]:
        if self._count == 0:
            return {f"p{q}": 0.0 for q in qs}
        vals = np.percentile(self._buf[: self._count], qs)
        return {f"p{q}": round(float(v), 3) for q, v in zip(qs, vals)}

    @property
    def count(self) -> int:
        return self._n


class MetricsRegistry:
    """Thread-safe registry for one subsystem (a serving engine, a loader).

    - ``inc(name)``: monotonic counters (requests, responses, errors, shed,
      rejected, batches, compile-cache hits/misses, ...)
    - ``observe_latency(ms)``: end-to-end request latency (submit -> result)
    - ``observe_occupancy(frac)``: real rows / bucket rows per executed batch
    - ``mark_done()``: completion timestamp feeding the sliding-window QPS
    - ``gauge(name, fn)``: live values sampled at snapshot time (queue depth)
    """

    def __init__(self, qps_window_s: float = 30.0, latency_capacity: int = 8192):
        self._lock = _named_lock("obs.MetricsRegistry._lock")
        self._counters: Dict[str, int] = {}
        self._latency = LatencyWindow(latency_capacity)
        self._queue_wait = LatencyWindow(latency_capacity)
        self._occ_sum = 0.0
        self._occ_n = 0
        self._qps_window_s = qps_window_s
        self._done_ts: deque = deque()
        self._gauges: Dict[str, Callable[[], float]] = {}
        self._t0 = time.monotonic()
        # process-wide histogram twins, resolved lazily ONCE (resolving
        # through the hub per observation would put its global lock on
        # every engine's completion path)
        self._hist_latency: Optional["Histogram"] = None
        self._hist_queue_wait: Optional["Histogram"] = None

    # -- writes ---------------------------------------------------------------
    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def observe_latency(self, ms: float) -> None:
        with self._lock:
            self._latency.observe(ms)
        # the process-wide histogram family rides along: monotonic bucket
        # counts an external Prometheus stack can aggregate across engines
        # and processes (the percentile window above cannot)
        h = self._hist_latency
        if h is None:
            h = self._hist_latency = _HUB.histogram("request_latency_ms")
        h.observe(ms)

    def observe_queue_wait(self, ms: float) -> None:
        with self._lock:
            self._queue_wait.observe(ms)
        h = self._hist_queue_wait
        if h is None:
            h = self._hist_queue_wait = _HUB.histogram("queue_wait_ms")
        h.observe(ms)

    def observe_occupancy(self, frac: float) -> None:
        with self._lock:
            self._occ_sum += frac
            self._occ_n += 1

    def mark_done(self, n: int = 1) -> None:
        now = time.monotonic()
        with self._lock:
            for _ in range(n):
                self._done_ts.append(now)
            self._prune_locked(now)

    def gauge(self, name: str, fn: Callable[[], float]) -> None:
        with self._lock:
            self._gauges[name] = fn

    def _prune_locked(self, now: float) -> None:
        horizon = now - self._qps_window_s
        while self._done_ts and self._done_ts[0] < horizon:
            self._done_ts.popleft()

    # -- reads ----------------------------------------------------------------
    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def latency_percentile(self, q: int = 95) -> float:
        """One recent-window latency percentile (ms) — cheap enough for a
        router's per-dispatch load probe (no gauges, no counters copy)."""
        with self._lock:
            return self._latency.percentiles((q,))[f"p{q}"]

    def uptime_s(self) -> float:
        """Seconds since the registry was made or last reset: what a
        counter divides by to read as a rate."""
        return max(time.monotonic() - self._t0, 1e-6)

    def qps(self) -> float:
        """Completions per second over the sliding window (or since start
        when the process is younger than the window)."""
        now = time.monotonic()
        with self._lock:
            self._prune_locked(now)
            span = min(self._qps_window_s, max(now - self._t0, 1e-6))
            return len(self._done_ts) / span

    def snapshot(self) -> Dict:
        """One coherent stats dict: QPS, latency percentiles (ms), batch
        occupancy, counters, live gauges."""
        now = time.monotonic()
        with self._lock:
            self._prune_locked(now)
            span = min(self._qps_window_s, max(now - self._t0, 1e-6))
            snap = {
                "qps": round(len(self._done_ts) / span, 3),
                "latency_ms": self._latency.percentiles(),
                "queue_wait_ms": self._queue_wait.percentiles(),
                "batch_occupancy": round(self._occ_sum / self._occ_n, 4)
                if self._occ_n else 0.0,
                "counters": dict(self._counters),
            }
            gauges = {name: fn for name, fn in self._gauges.items()}
        # gauges sampled outside the lock: a gauge callback may itself take
        # the engine lock (queue depth), and lock nesting here could deadlock
        for name, fn in gauges.items():
            try:
                snap[name] = fn()
            except Exception:
                snap[name] = None
        return snap

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._latency = LatencyWindow(self._latency._capacity)
            self._queue_wait = LatencyWindow(self._queue_wait._capacity)
            self._occ_sum = 0.0
            self._occ_n = 0
            self._done_ts.clear()
            self._t0 = time.monotonic()


_Labels = Union[Tuple[str, ...], str]


class CounterFamily:
    """Labeled monotonic counters: one family, one value per label tuple.

    ``fam.inc(("divide", "float32"))`` with ``label_names=("op", "dtype")``
    is the nan_inf_events row for that op/dtype pair. Values may be
    fractional (byte totals, milliseconds) — still add-only.
    """

    def __init__(self, name: str, label_names: Sequence[str] = ()):
        self.name = name
        self.label_names = tuple(label_names)
        self._lock = _named_lock(f"obs.family[{name}]._lock")
        self._values: Dict[Tuple[str, ...], float] = {}

    def inc(self, labels: _Labels = (), n: float = 1) -> None:
        key = (labels,) if isinstance(labels, str) else tuple(
            str(l) for l in labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0) + n

    def get(self, labels: _Labels = ()) -> float:
        key = (labels,) if isinstance(labels, str) else tuple(
            str(l) for l in labels)
        with self._lock:
            return self._values.get(key, 0)

    def total(self) -> float:
        with self._lock:
            return sum(self._values.values())

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able view; keys are '|'-joined label tuples for DISPLAY —
        consumers needing exact labels use ``items()`` (true tuples) or
        the lossless ``items`` rows carried here (the cross-process merge
        feed: a '|' inside a label value survives the wire)."""
        with self._lock:
            items = list(self._values.items())
        rows = {"|".join(k) if k else "total": v for k, v in items}
        return {"label_names": list(self.label_names), "values": rows,
                "items": [[list(k), v] for k, v in items]}

    def items(self):
        with self._lock:
            return list(self._values.items())

    def merge(self, other, prefix: Sequence[str] = ()) -> None:
        """Label-aware merge: add every row of ``other`` into this family
        with ``prefix`` labels PREPENDED — the fleet-merge shape (a
        replica's ``(op,)`` rows land here as ``(replica, pool, op)``).

        ``other`` may be another ``CounterFamily``, an ``items()`` list,
        or a ``snapshot()`` dict (its lossless ``items`` rows). Counters
        are add-only, so merging preserves monotonicity as long as each
        source is itself scraped monotonically. When this family declares
        ``label_names``, a merged row of the wrong arity is a wiring bug
        and raises."""
        if isinstance(other, CounterFamily):
            rows = other.items()
        elif isinstance(other, dict):
            rows = [(tuple(k), v) for k, v in other.get("items", [])]
        else:
            rows = [(tuple(k), v) for k, v in other]
        prefix = tuple(str(p) for p in prefix)
        want = len(self.label_names) if self.label_names else None
        with self._lock:
            for key, val in rows:
                full = prefix + tuple(str(k) for k in key)
                if want is not None and len(full) != want:
                    raise ValueError(
                        f"counter family {self.name!r}: merged row "
                        f"{full!r} does not match label schema "
                        f"{self.label_names}")
                self._values[full] = self._values.get(full, 0) + val

    def reset(self) -> None:
        with self._lock:
            self._values.clear()


# default latency-shaped bounds (ms): sub-ms serving hits through
# multi-second cold compiles, 13 buckets + +Inf
DEFAULT_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class Histogram:
    """Fixed-bucket distribution with native Prometheus histogram
    exposition (``<name>_bucket{le=...}`` / ``_sum`` / ``_count``).

    Unlike ``LatencyWindow`` (recent-window percentiles, honest but not
    aggregatable), bucket counts are monotonic and mergeable across
    processes — the shape an external scrape stack needs. ``observe`` is
    one lock + one bisect + two adds.
    """

    def __init__(self, name: str, buckets: Sequence[float] = DEFAULT_BUCKETS_MS):
        self.name = name
        self.bounds: Tuple[float, ...] = tuple(sorted(float(b)
                                                      for b in buckets))
        if not self.bounds:
            raise ValueError(f"histogram {name!r}: need at least one bucket")
        self._lock = _named_lock(f"obs.hist[{name}]._lock")
        self._counts = [0] * (len(self.bounds) + 1)  # last = +Inf overflow
        self._sum = 0.0
        self._n = 0

    def observe(self, v: float) -> None:
        idx = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._n += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    def items(self):
        """Cumulative (le, count) pairs ending with ("+Inf", total) — the
        Prometheus exposition contract."""
        with self._lock:
            counts = list(self._counts)
        out, cum = [], 0
        for le, c in zip(self.bounds, counts):
            cum += c
            out.append((le, cum))
        out.append(("+Inf", cum + counts[-1]))
        return out

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            counts, s, n = list(self._counts), self._sum, self._n
        cum, buckets = 0, {}
        for le, c in zip(self.bounds, counts):
            cum += c
            buckets[str(le)] = cum
        buckets["+Inf"] = cum + counts[-1]
        # ``bounds``/``raw``/``sum_exact`` are the merge feed: per-bucket
        # (non-cumulative) counts plus the unrounded sum, so a fleet-level
        # merge of replica snapshots reproduces sum/count EXACTLY
        return {"type": "histogram", "buckets": buckets,
                "sum": round(s, 3), "count": n,
                "avg": round(s / n, 3) if n else 0.0,
                "bounds": list(self.bounds), "raw": counts,
                "sum_exact": s}

    def merge(self, other) -> None:
        """Add another histogram's observations into this one — the
        "mergeable across processes" claim made real. ``other`` is a
        ``Histogram`` or a ``snapshot()`` dict; both carry per-bucket
        counts over explicit bounds. Bucket-wise addition of per-bucket
        counts keeps the cumulative view monotonic and sum/count exact;
        MISMATCHED bucket edges cannot be merged faithfully and raise."""
        bounds, counts, s, n = _hist_parts(other)
        if tuple(bounds) != self.bounds:
            raise ValueError(
                f"histogram {self.name!r}: cannot merge bucket edges "
                f"{tuple(bounds)} into {self.bounds}")
        with self._lock:
            for i, c in enumerate(counts):
                self._counts[i] += c
            self._sum += s
            self._n += n

    @staticmethod
    def merge_snapshots(snaps: Sequence[Dict[str, Any]]
                        ) -> Dict[str, Any]:
        """Merge histogram ``snapshot()`` dicts (e.g. one per replica)
        into one snapshot-shaped dict without touching any live
        histogram. All inputs must share bucket edges (mismatch raises
        ``ValueError``); the merged sum/count is the exact element-wise
        total of the inputs."""
        snaps = list(snaps)
        if not snaps:
            raise ValueError("merge_snapshots: need at least one snapshot")
        bounds, counts, s, n = _hist_parts(snaps[0])
        counts = list(counts)
        for snap in snaps[1:]:
            b2, c2, s2, n2 = _hist_parts(snap)
            if list(b2) != list(bounds):
                raise ValueError(
                    f"histogram merge: mismatched bucket edges "
                    f"{list(b2)} vs {list(bounds)}")
            for i, c in enumerate(c2):
                counts[i] += c
            s += s2
            n += n2
        cum, buckets = 0, {}
        for le, c in zip(bounds, counts):
            cum += c
            buckets[str(le)] = cum
        buckets["+Inf"] = cum + counts[-1]
        return {"type": "histogram", "buckets": buckets,
                "sum": round(s, 3), "count": n,
                "avg": round(s / n, 3) if n else 0.0,
                "bounds": list(bounds), "raw": counts, "sum_exact": s}

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._sum = 0.0
            self._n = 0


def _hist_parts(h) -> Tuple[List[float], List[int], float, int]:
    """(bounds, per-bucket counts incl. +Inf, exact sum, count) from a
    live ``Histogram`` or a ``snapshot()`` dict. Snapshots without the
    ``raw`` feed (older dumps) de-cumulate their bucket map."""
    if isinstance(h, Histogram):
        with h._lock:
            return list(h.bounds), list(h._counts), h._sum, h._n
    if not isinstance(h, dict):
        raise TypeError(f"expected Histogram or snapshot dict, got "
                        f"{type(h).__name__}")
    n = int(h.get("count", 0))
    s = float(h.get("sum_exact", h.get("sum", 0.0)))
    if "bounds" in h and "raw" in h:
        return [float(b) for b in h["bounds"]], \
            [int(c) for c in h["raw"]], s, n
    buckets = h.get("buckets", {})
    bounds = [float(k) for k in buckets if k != "+Inf"]
    counts, prev = [], 0
    for b in bounds:
        cum = int(buckets[str(b)])
        counts.append(cum - prev)
        prev = cum
    counts.append(int(buckets.get("+Inf", prev)) - prev)
    return bounds, counts, s, n


class Hub:
    """The process-wide telemetry hub: every family lives (or is reachable)
    here, and ``snapshot()`` is the one JSON of all of them."""

    def __init__(self):
        self._lock = _named_lock("obs.Hub._lock")
        self._families: Dict[str, CounterFamily] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._providers: Dict[str, Callable[[], Any]] = {}
        self._gauges: Dict[str, Callable[[], float]] = {}
        # registries belong to their owners (engines); weak values so a
        # closed+collected engine's rows disappear instead of pinning it
        self._registries: "weakref.WeakValueDictionary[str, MetricsRegistry]" \
            = weakref.WeakValueDictionary()

    # -- registration ---------------------------------------------------------
    def family(self, name: str, label_names: Sequence[str] = ()
               ) -> CounterFamily:
        """Get-or-create a labeled counter family (idempotent). Omitting
        ``label_names`` fetches whatever exists; conflicting non-empty
        schemas are a wiring bug and raise at the call site."""
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = CounterFamily(name, label_names)
                self._families[name] = fam
            elif label_names:
                if not fam.label_names:
                    fam.label_names = tuple(label_names)
                elif tuple(label_names) != fam.label_names:
                    raise ValueError(
                        f"observability family {name!r} already registered "
                        f"with labels {fam.label_names}, got "
                        f"{tuple(label_names)}")
            return fam

    def histogram(self, name: str,
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        """Get-or-create a bucketed histogram (idempotent). Omitting
        ``buckets`` fetches whatever exists; a conflicting non-default
        bucket schema is a wiring bug and raises at the call site."""
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = Histogram(name, buckets if buckets is not None
                              else DEFAULT_BUCKETS_MS)
                self._histograms[name] = h
            elif buckets is not None and \
                    tuple(sorted(float(b) for b in buckets)) != h.bounds:
                raise ValueError(
                    f"observability histogram {name!r} already registered "
                    f"with buckets {h.bounds}")
            return h

    def register_provider(self, name: str, fn: Callable[[], Any]) -> None:
        """A snapshot-time callable for state owned elsewhere (cache stats,
        retrace summaries, the step timeline). Zero steady-state cost."""
        with self._lock:
            self._providers[name] = fn

    def gauge(self, name: str, fn: Callable[[], float]) -> None:
        with self._lock:
            self._gauges[name] = fn

    def register_registry(self, name: str, registry: MetricsRegistry) -> None:
        """Attach a subsystem MetricsRegistry (e.g. a serving engine's) so
        its snapshot rides along under ``registries.<name>``."""
        self._registries[name] = registry

    # -- reads ----------------------------------------------------------------
    def families(self) -> Dict[str, CounterFamily]:
        """The live CounterFamily objects (exact label tuples via
        ``items()`` — the Prometheus emitter's source of truth)."""
        with self._lock:
            return dict(self._families)

    def histograms(self) -> Dict[str, Histogram]:
        """The live Histogram objects (the Prometheus emitter's source of
        native ``_bucket``/``_sum``/``_count`` samples)."""
        with self._lock:
            return dict(self._histograms)

    def snapshot(self) -> Dict[str, Any]:
        """One JSON-able dict of every registered family/provider/gauge.
        Provider or gauge failures degrade to an error string — a telemetry
        read must never raise into the caller."""
        with self._lock:
            families = dict(self._families)
            histograms = dict(self._histograms)
            providers = dict(self._providers)
            gauges = dict(self._gauges)
            registries = dict(self._registries)
        out: Dict[str, Any] = {}
        for name, fam in families.items():
            out[name] = fam.snapshot()
        for name, h in histograms.items():
            out[name] = h.snapshot()
        for name, fn in providers.items():
            try:
                out[name] = fn()
            except Exception as e:
                out[name] = {"error": str(e)[:200]}
        if gauges:
            g = {}
            for name, fn in gauges.items():
                try:
                    g[name] = fn()
                except Exception:
                    g[name] = None
            out["gauges"] = g
        if registries:
            regs = {}
            for name, reg in registries.items():
                try:
                    regs[name] = reg.snapshot()
                except Exception as e:
                    regs[name] = {"error": str(e)[:200]}
            out["registries"] = regs
        return out

    def reset(self) -> None:
        """Zero the hub-owned families/histograms (providers/registries are
        owned by their subsystems and reset there). Test hygiene, not a hot
        path."""
        with self._lock:
            families = list(self._families.values()) + \
                list(self._histograms.values())
        for fam in families:
            fam.reset()


_HUB = Hub()


def hub() -> Hub:
    return _HUB


def family(name: str, label_names: Sequence[str] = ()) -> CounterFamily:
    return _HUB.family(name, label_names)


def histogram(name: str, buckets: Optional[Sequence[float]] = None) -> Histogram:
    return _HUB.histogram(name, buckets)


def gauge(name: str, fn: Callable[[], float]) -> None:
    _HUB.gauge(name, fn)


def register_provider(name: str, fn: Callable[[], Any]) -> None:
    _HUB.register_provider(name, fn)


def register_registry(name: str, registry: MetricsRegistry) -> None:
    _HUB.register_registry(name, registry)
