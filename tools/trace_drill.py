#!/usr/bin/env python
"""trace_drill: the device-truth tracing acceptance drill (ISSUE-7).

Three asserts, run by tools/ci.sh's observability gate:

1. **XPlane correlation** — a CPU-run traced step window reports
   ``device_compute_us`` from XPlane correlation (not the host-block
   fallback), with step phases correlated and >= 1 device-attributed op
   in the op table.
2. **Request-scoped tracing** — a serving run exports a chrome trace in
   which one request's spans (admission -> queue -> batch_coalesce ->
   execute) share a single trace ID.
3. **Flight recorder** — an injected step-time regression
   (``PT_FAULTS="slow_transfer@..."`` slowing a streaming-lane transfer
   in a subprocess) trips the anomaly detector and produces a complete,
   parseable ``pd_dump`` bundle.

    python tools/trace_drill.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _drill_capture() -> dict:
    """Drill 1: XPlane-correlated step/op attribution on a real capture."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu import jit
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import trace

    obs.timeline().reset()
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 1))
    opt = popt.Adam(learning_rate=0.01, parameters=net.parameters())
    step = jit.TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((8, 16), np.float32))
    y = paddle.to_tensor(np.zeros((8, 1), np.float32))
    step(x, y)  # compile outside the capture window
    with trace.capture_steps() as cap:
        for _ in range(4):
            float(step(x, y))  # the loss read syncs each step
    assert cap.error is None, cap.error
    cor = cap.result
    assert cor.steps_correlated >= 3, cor.summary()
    assert cor.op_table, "no device-attributed ops"
    assert any(s["phases"] for s in cor.steps), "no correlated step phases"
    tl = obs.timeline().summary()
    assert tl["device_source"] == "xplane", tl["device_source"]
    assert tl["device_compute_us"]["count"] >= 3, tl["device_compute_us"]
    snap = obs.snapshot()["device_trace"]
    assert snap["op_table"], snap
    return {"steps_correlated": cor.steps_correlated,
            "top_op": cor.op_table[0]["op"],
            "device_us_avg": tl["device_compute_us"]["avg"],
            "overlap_efficiency": cor.overlap_efficiency()}


def _drill_serving() -> dict:
    """Drill 2: one request's spans share a trace ID, end to end."""
    import numpy as np

    from paddle_tpu import serving
    from paddle_tpu.observability.trace import tracer

    eng = serving.ServingEngine(
        lambda x: x * 2.0, buckets=serving.BucketSpec(batch_sizes=(1, 4)),
        input_specs=[((8,), "float32")], name="drill_eng")
    with eng:
        futs = [eng.submit([np.full(8, i, np.float32)]) for i in range(8)]
        for f in futs:
            f.result(timeout=60)
    path = os.path.join(tempfile.mkdtemp(prefix="pt_drill_"),
                        "requests.trace.json")
    tracer().export_chrome(path)
    with open(path) as f:
        # request and slot rows; the worker:<thread> rows hold trace.span's
        # ring, tied to a request only where a span serves one
        events = [e for e in json.load(f)["traceEvents"]
                  if e.get("ph") == "X" and e.get("cat") != "worker"]
    assert events, "empty request trace export"
    by_id: dict = {}
    for e in events:
        tid = e.get("args", {}).get("trace_id")
        assert tid, f"span without trace_id: {e}"
        by_id.setdefault(tid, set()).add(e["name"])
    want = {"admission", "queue", "batch_coalesce", "execute"}
    full = [t for t, names in by_id.items() if want <= names]
    assert full, f"no request carries the full span chain: {by_id}"
    return {"requests_traced": len(by_id), "full_chain": len(full),
            "export": path}


_CHILD_STEPS = 12
_SLOW_SEQ = 8


def _flight_child() -> None:
    """Subprocess body for drill 3 (PT_FAULTS armed by the parent): a
    streaming-lane transfer + ~10ms of deterministic host work per step
    (a sub-ms baseline would let scheduler jitter on a loaded CI box trip
    the detectors before the injected fault); the injected slow_transfer
    turns one step into a regression + stall spike."""
    import numpy as np

    from paddle_tpu.jit.offload_stream import StreamLane
    from paddle_tpu.observability import timeline
    from paddle_tpu.observability.trace import flight_recorder

    rec = flight_recorder(min_steps=4, regress_factor=3.0,
                          min_dump_interval_s=0.0)
    tl = timeline()
    lane = StreamLane(overlap=True)
    arr = np.ones((256, 256), np.float32)
    for _ in range(_CHILD_STEPS):
        with tl.step():
            h = lane.submit("h2d", [arr], [None])
            time.sleep(0.01)  # the step's "compute"
            with tl.phase("stream_wait"):
                h.wait()
    snap = rec.snapshot()
    print(json.dumps({
        "anomalies": [a["reason"] for a in snap["anomalies"]],
        "dumps": [{"path": d["path"], "reason": d["reason"]}
                  for d in snap["dumps"]],
        "ring_ms": [r["ms"] for r in snap["ring"]],
    }))


def _drill_flight() -> dict:
    """Drill 3: PT_FAULTS slow-transfer -> anomaly -> pd_dump bundle."""
    out = tempfile.mkdtemp(prefix="pt_flight_")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PT_FAULTS": f"slow_transfer@seq={_SLOW_SEQ}&ms=400",
        "PT_FLIGHT_DIR": out,
    })
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--flight-child"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert any(r.startswith(("step_regression", "stall_spike"))
               for r in report["anomalies"]), report
    hits = [d for d in report["dumps"]
            if d["reason"].startswith(("step_regression", "stall_spike"))]
    assert hits, f"anomaly fired but no bundle: {report}"
    bundle = hits[0]["path"]
    with open(os.path.join(bundle, "MANIFEST.json")) as f:
        manifest = json.load(f)
    for name in ("snapshot.json", "flight_ring.json", "config.json"):
        assert name in manifest["files"], manifest
        assert "error" not in manifest["files"][name], manifest
        with open(os.path.join(bundle, name)) as fh:
            json.load(fh)  # parseable
    ring = json.load(open(os.path.join(bundle, "flight_ring.json")))
    spike = max(r["ms"] for r in ring["ring"])
    assert spike >= 400, f"ring missed the injected 400ms stall: {spike}"
    return {"anomalies": report["anomalies"][:2], "bundle": bundle,
            "spike_ms": round(spike, 1)}


def main() -> int:
    if "--flight-child" in sys.argv:
        _flight_child()
        return 0
    results = {}
    for name, fn in (("capture", _drill_capture),
                     ("serving", _drill_serving),
                     ("flight", _drill_flight)):
        results[name] = fn()
        print(f"trace_drill [{name}] OK: {results[name]}")
    print("trace_drill: all three acceptance drills passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
