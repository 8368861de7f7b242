"""What every cell shares: finding a cell's files by name, refusing to run
without the chips, the compile cache at its one fixed place, the profiler
window and its reduction, the per-layer readers, and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: Dict, over: Dict) -> Dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class Spec:
    """One cell: its workload file, its configuration file, and the metrics
    ``BENCHMARK.json`` says it reports."""

    def __init__(self, workload: str, rehearsal: bool = False):
        self.name = workload
        self.rehearsal = rehearsal
        w = _load(os.path.join(BENCH_DIR, "workloads", workload + ".json"))
        c = _load(os.path.join(BENCH_DIR, "configs", w["config"] + ".json"))
        if rehearsal:  # the same files and code path at a size a CPU holds
            w = _merge(w, w.get("rehearsal", {}))
            c = _merge(c, c.get("rehearsal", {}))
        self.workload, self.config = w, c
        self.kind, self.chips = w["kind"], int(w["chips"])
        bench_path = os.path.join(ROOT, "BENCHMARK.json")
        bench = _load(bench_path) if os.path.exists(bench_path) else {}
        listed = any(x["name"] == workload
                     for x in bench.get("workloads", []))

        def mine(metrics: List[Dict]) -> Optional[List[Dict]]:
            if not listed:
                return None  # a cell not admitted yet reports all it can
            return [m for m in metrics
                    if "workloads" not in m or workload in m["workloads"]]

        self.end_to_end = mine(bench.get("end_to_end", []))
        self.per_layer = mine(bench.get("per_layer", []))


def require_devices(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """The device as JAX reports it. No accelerator, or fewer chips than the
    cell asks for: no result (rehearsal alone may run on the CPU, and says
    so in its line)."""
    import jax

    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: no TPU — jax.devices()[0].platform is "
            f"{devs[0].platform!r}; a measurement has no CPU mode")
    if len(devs) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chip(s), JAX reports "
            f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the program's one fixed path inside this checkout
    (``<checkout>/.cache/jax``) — never a path that moves."""
    from paddle_tpu.jit import persistent_cache

    return persistent_cache.enable_jax_compilation_cache()


class CompileCounter:
    """Counts XLA compiles that missed the persistent cache (so a compile
    inside the measured window cannot hide)."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Tracer:
    """The profiler around part of the window, and the reduced trace."""

    def __init__(self, name: str, enabled: bool):
        self.enabled = enabled
        self.dir = os.path.join(ROOT, ".cache", "bench_trace", name)
        self.summary: Optional[Dict] = None
        self.xplane: Optional[str] = None
        self._span = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # host spans are the benchmark's own
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self) -> None:
        if not self.enabled or self._span is None:
            return
        import jax

        from . import xplane

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        self.xplane = xplane.find_xplane(self.dir)
        if self.xplane is None:
            return
        self.summary = xplane.summarize(xplane.read_xplane(self.xplane))


def span(name: str):
    """A host span in the profiler's own trace (``bench.<name>``): what the
    host was doing, on the device's clock. Free when no trace is on."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def read_layer_metric(name: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_metrics(spec: Spec, out: Dict) -> Dict[str, Dict]:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    if spec.per_layer is None:
        names = sorted(f[:-3] for f in os.listdir(
            os.path.join(BENCH_DIR, "layer_metrics")) if f.endswith(".py"))
        wanted = [{"name": n} for n in names]
    else:
        wanted = spec.per_layer
    got = {}
    for m in wanted:
        mod = read_layer_metric(m["name"])
        if mod is None:
            continue
        value = mod.reduce(out.get("trace"), out.get("counters", {}),
                           out.get("spans", {}), out.get("shapes", {}))
        if value is None:
            continue
        got[m["name"]] = {"value": float(value),
                          "unit": m.get("unit", getattr(mod, "UNIT", ""))}
    return got


def end_to_end_metrics(spec: Spec, out: Dict) -> Dict[str, Dict]:
    units = out.get("units", {})
    if spec.end_to_end is None:
        return {k: {"value": float(v), "unit": units.get(k, "")}
                for k, v in out["end_to_end"].items()}
    return {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                        "unit": m["unit"]} for m in spec.end_to_end}


def say(tag: str, **fields) -> None:
    print(tag + " " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def emit(line: Dict) -> None:
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
