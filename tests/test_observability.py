"""Profiler, flags, check_nan_inf (VERDICT item 7; reference:
python/paddle/profiler/profiler.py:224, platform/flags.cc,
framework/details/nan_inf_utils_detail.*)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler


def test_flags_set_get():
    assert paddle.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"] is False
    paddle.set_flags({"FLAGS_benchmark": True})
    assert paddle.get_flags(["benchmark"])["FLAGS_benchmark"] is True
    paddle.set_flags({"FLAGS_benchmark": False})
    with pytest.raises(ValueError):
        paddle.set_flags({"FLAGS_no_such_flag": 1})
    allf = paddle.get_flags()
    assert "FLAGS_allocator_strategy" in allf


def test_flag_string_parse():
    paddle.set_flags({"FLAGS_check_nan_inf": "true"})
    assert paddle.get_flags("check_nan_inf")["FLAGS_check_nan_inf"] is True
    paddle.set_flags({"FLAGS_check_nan_inf": "0"})
    assert paddle.get_flags("check_nan_inf")["FLAGS_check_nan_inf"] is False


def test_check_nan_inf_trips():
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    try:
        x = paddle.to_tensor(np.array([1.0, 0.0], np.float32))
        with pytest.raises(RuntimeError, match="check_nan_inf.*divide"):
            _ = paddle.to_tensor(np.array([1.0, 1.0], np.float32)) / x
        # finite path unaffected
        y = x + x
        assert np.isfinite(y.numpy()).all()
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False})


def test_record_event_and_summary():
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    with profiler.RecordEvent("my_span"):
        x = paddle.to_tensor(np.ones((8, 8), np.float32))
        y = (x @ x).numpy()
    prof.stop()
    assert y.shape == (8, 8)
    names = [e[0] for e in prof.events]
    assert "my_span" in names
    assert "matmul_v2" in names  # op span recorded by dispatch
    table = prof.summary()
    assert "matmul_v2" in table and "Calls" in table


def test_scheduler_states():
    sched = profiler.make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(5)]
    assert states[0] == profiler.ProfilerState.CLOSED
    assert states[1] == profiler.ProfilerState.READY
    assert states[2] == profiler.ProfilerState.RECORD
    assert states[3] == profiler.ProfilerState.RECORD_AND_RETURN
    assert states[4] == profiler.ProfilerState.CLOSED


def test_chrome_trace_export(tmp_path):
    out = []
    prof = profiler.Profiler(
        on_trace_ready=lambda p: out.append(p._export_chrome(
            str(tmp_path / "trace.json"))))
    prof.start()
    x = paddle.to_tensor(np.ones((4,), np.float32))
    (x * 2).numpy()
    prof.stop()
    assert out and os.path.exists(out[0])
    with open(out[0]) as f:
        trace = json.load(f)
    assert any(ev["name"] == "multiply" for ev in trace["traceEvents"])


def test_profiler_step_scheduling():
    prof = profiler.Profiler(scheduler=profiler.make_scheduler(
        closed=1, ready=0, record=1, repeat=1))
    prof.start()  # step 0: CLOSED
    x = paddle.to_tensor(np.ones((4,), np.float32))
    (x + 1).numpy()
    assert not prof.events and not profiler.is_recording()
    prof.step()  # step 1: RECORD_AND_RETURN
    (x + 2).numpy()
    prof.stop()
    assert any(e[0] == "add" for e in prof.events)


# -- PR 4: the unified telemetry layer (paddle_tpu.observability) -------------

import re

from paddle_tpu import observability as obs


def test_registry_families_and_labeled_counters():
    fam = obs.family("t4_family", ("op", "kind"))
    fam.reset()
    fam.inc(("matmul", "calls"))
    fam.inc(("matmul", "calls"))
    fam.inc(("add", "bytes"), 128)
    snap = obs.snapshot()
    assert snap["t4_family"]["label_names"] == ["op", "kind"]
    assert snap["t4_family"]["values"]["matmul|calls"] == 2
    assert snap["t4_family"]["values"]["add|bytes"] == 128
    # get-or-create is idempotent: same family object
    assert obs.family("t4_family") is fam
    assert fam.get(("matmul", "calls")) == 2
    assert fam.total() == 130
    # every registered island shows up in one snapshot
    for key in ("persistent_cache", "retrace_events", "step_timeline",
                "trace_cache", "nan_inf_events", "collectives", "prefetcher"):
        assert key in snap, key
    fam.reset()
    assert fam.total() == 0
    json.dumps(snap, default=str)  # the one-JSON contract


def test_step_timeline_phases_ordered_for_jitted_fit(tmp_path):
    """One jitted Model.fit epoch: data_wait / host_dispatch /
    device_block per step, ordered, and exported as chrome-trace spans
    next to user spans (the ISSUE-4 acceptance view; ISSUE-7 renamed the
    host-block phase device_block — it is host time, not device time)."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu.io import TensorDataset

    tl = obs.timeline()
    tl.reset()
    xs = paddle.to_tensor(np.random.RandomState(0).randn(8, 4).astype("float32"))
    ys = paddle.to_tensor(np.random.RandomState(1).randn(8, 1).astype("float32"))
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    model = paddle.Model(net)
    model.prepare(popt.Adam(learning_rate=0.01, parameters=net.parameters()),
                  loss=nn.MSELoss())
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    with profiler.RecordEvent("user_span"):
        model.fit(TensorDataset([xs, ys]), batch_size=4, epochs=1, verbose=0)
    prof.stop()
    s = tl.summary()
    assert s["steps"] == 2  # 8 samples / batch 4
    for phase in ("data_wait", "host_dispatch", "device_block"):
        assert s["phases"][phase]["count"] == 2, s["phases"]
    order = [p["phase"] for p in s["last_step"]]
    assert order == ["data_wait", "host_dispatch", "device_block"]
    rel = [p["rel_ms"] for p in s["last_step"]]
    assert rel == sorted(rel)  # recorded in wall-clock order
    # no XPlane capture ran: the block value must be LABELLED as the
    # host-side proxy, never silently reported as device time
    assert s["device_source"] == "host_block"
    assert "device_compute_us" not in s
    # chrome trace carries BOTH user spans and step phases
    out = str(tmp_path / "trace.json")
    prof._export_chrome(out)
    with open(out) as f:
        names = {ev["name"] for ev in json.load(f)["traceEvents"]}
    assert "user_span" in names
    assert {"pt.train.data_wait", "pt.train.host_dispatch",
            "pt.train.device_block", "pt.train.step"} <= names
    assert tl.table()  # human summary renders


def test_step_timeline_trainstep_compile_then_warm():
    """TrainStep cold call lands in the compile phase, warm calls in
    host_dispatch; detailed mode adds the device_block host block."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu import jit

    tl = obs.timeline()
    tl.reset()
    tc = obs.family("trace_cache")
    builds0 = tc.get(("train_step", "build"))
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = popt.Adam(learning_rate=0.01, parameters=net.parameters())
    step = jit.TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    y = paddle.to_tensor(np.zeros((2, 1), np.float32))
    tl.detail(True)
    try:
        step(x, y)
        step(x, y)
    finally:
        tl.detail(False)
    s = tl.summary()
    assert s["steps"] == 2
    assert s["phases"]["compile"]["count"] == 1
    assert s["phases"]["host_dispatch"]["count"] == 1
    assert s["phases"]["device_block"]["count"] == 2
    assert tc.get(("train_step", "build")) == builds0 + 1
    order = [p["phase"] for p in s["last_step"]]
    assert order == ["host_dispatch", "device_block"]


def test_prefetcher_family_and_gauge():
    from paddle_tpu import io

    fam = obs.family("prefetcher")
    b0 = fam.get(("batches",))
    batches = [(np.ones((2, 4), np.float32),) for _ in range(3)]
    for _ in io.DevicePrefetcher(batches):
        pass
    assert fam.get(("batches",)) == b0 + 3
    assert fam.get(("data_wait_ms",)) >= 0.0
    snap = obs.snapshot()
    assert "prefetch_queue_depth" in snap.get("gauges", {})


def test_prometheus_exposition_parses():
    obs.family("t4_family", ("op", "kind")).inc(("matmul", "calls"))
    text = obs.prometheus_text()
    assert 'pt_t4_family_total{op="matmul",kind="calls"}' in text
    line_re = re.compile(
        r"^(# (TYPE|HELP) .*|pt_[A-Za-z0-9_]+(\{[^}]*\})? -?[0-9eE.+-]+)$")
    for line in text.strip().splitlines():
        assert line_re.match(line), f"unparseable exposition line: {line!r}"


def test_serve_endpoint_and_stop():
    import urllib.request

    port = obs.serve(0)  # free port
    try:
        assert obs.serve(0) == port  # idempotent while running
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/snapshot", timeout=5) as r:
            snap = json.load(r)
        assert "persistent_cache" in snap
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            body = r.read().decode()
        assert body.startswith("# TYPE")
    finally:
        obs.stop_serving()


def test_pd_top_snapshot_roundtrip(tmp_path, capsys):
    import importlib.util

    path = obs.dump(str(tmp_path / "snap.json"))
    spec = importlib.util.spec_from_file_location(
        "pd_top", os.path.join(os.path.dirname(__file__), "..", "tools",
                               "pd_top.py"))
    pd_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pd_top)
    assert pd_top.main([path]) == 0
    out = capsys.readouterr().out
    for fam in ("persistent_cache", "retrace_events", "step_timeline"):
        assert fam in out


def test_nan_inf_counter_and_log_action():
    fam = obs.family("nan_inf_events")
    paddle.set_flags({"FLAGS_check_nan_inf": True,
                      "FLAGS_check_nan_inf_action": "log"})
    try:
        n0 = fam.get(("divide", "float32"))
        with pytest.warns(RuntimeWarning, match="check_nan_inf.*divide"):
            y = paddle.to_tensor(np.array([1.0, 1.0], np.float32)) / \
                paddle.to_tensor(np.array([1.0, 0.0], np.float32))
        assert fam.get(("divide", "float32")) == n0 + 1
        assert np.isinf(y.numpy()).any()  # downgraded: result still usable
        # raise mode still counts the trip
        paddle.set_flags({"FLAGS_check_nan_inf_action": "raise"})
        with pytest.raises(RuntimeError, match="check_nan_inf.*divide"):
            _ = paddle.to_tensor(np.array([1.0], np.float32)) / \
                paddle.to_tensor(np.array([0.0], np.float32))
        assert fam.get(("divide", "float32")) == n0 + 2
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False,
                          "FLAGS_check_nan_inf_action": "raise"})
    with pytest.raises(ValueError):
        paddle.set_flags({"FLAGS_check_nan_inf_action": "explode"})


def test_serving_registry_registered_in_hub():
    import paddle_tpu.nn as nn
    from paddle_tpu import serving

    net = nn.Sequential(nn.Linear(8, 4))
    net.eval()
    eng = serving.ServingEngine(
        net, buckets=serving.BucketSpec(batch_sizes=(1,)),
        input_specs=[((8,), "float32")])
    with eng:
        eng.submit([np.ones(8, np.float32)]).result(timeout=30)
    regs = obs.snapshot().get("registries", {})
    rows = [v for k, v in regs.items() if k.startswith("serving:")]
    assert rows and any(r["counters"].get("responses_total") for r in rows)
    # the promoted classes are the same objects serving re-exports
    assert serving.MetricsRegistry is obs.MetricsRegistry
    assert serving.LatencyWindow is obs.LatencyWindow


def test_fit_auto_prefetch_decision_and_mesh_run():
    """PR-3 follow-up: DistributedBatchSampler-driven fit on an active mesh
    prefetches to the mesh data placement by default."""
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu.hapi.model import _auto_device_prefetch
    from paddle_tpu.io import DataLoader, DistributedBatchSampler, TensorDataset

    xs = paddle.to_tensor(np.random.RandomState(0).randn(16, 4).astype("float32"))
    ys = paddle.to_tensor(np.random.RandomState(1).randn(16, 1).astype("float32"))
    ds = TensorDataset([xs, ys])
    plain = DataLoader(ds, batch_size=8)
    # plain loader, no mesh: stays off
    assert _auto_device_prefetch(plain, None) == (False, None)
    dbs_loader = DataLoader(
        ds, batch_sampler=DistributedBatchSampler(ds, batch_size=8))
    # distributed sampler but no mesh: stays off
    assert _auto_device_prefetch(dbs_loader, None) == (False, None)
    dist.reset_mesh()
    dist.init_mesh(dp=8)
    try:
        on, sharding = _auto_device_prefetch(dbs_loader, None)
        assert on and callable(sharding)
        arr = np.ones((8, 4), np.float32)
        assert "dp" in str(sharding(arr).spec)
        # ragged tail batch (not divisible by dp) lands replicated, never
        # fails the device_put mid-prefetch
        assert "dp" not in str(sharding(np.ones((6, 4), np.float32)).spec)
        # end to end: the fit runs with auto prefetch and records data_wait
        tl = obs.timeline()
        tl.reset()
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
        model = paddle.Model(net)
        model.prepare(popt.Adam(learning_rate=0.01,
                                parameters=net.parameters()),
                      loss=nn.MSELoss())
        model.fit(dbs_loader, epochs=1, verbose=0)
        s = tl.summary()
        assert s["steps"] == 2 and s["phases"]["data_wait"]["count"] == 2
        fam = obs.family("prefetcher")
        assert fam.get(("batches",)) > 0
    finally:
        dist.reset_mesh()


def test_timeline_hot_path_overhead_bounded():
    """The off-path contract: an empty step bracket (no Profiler, no
    exposition) costs a few dict adds — generously bounded here."""
    tl = obs.StepTimeline()  # fresh: no global skew
    n = 2000
    import time as _time

    t0 = _time.perf_counter()
    for _ in range(n):
        with tl.step():
            with tl.phase("host_dispatch"):
                pass
    per_step_us = (_time.perf_counter() - t0) / n * 1e6
    assert tl.summary()["steps"] == n
    assert per_step_us < 500, f"timeline step overhead {per_step_us:.1f}us"


def test_collective_call_byte_counters():
    import paddle_tpu.distributed as dist

    fam = obs.family("collectives")
    dist.reset_mesh()
    dist.init_mesh(dp=8)
    try:
        c0 = fam.get(("all_reduce", "calls"))
        b0 = fam.get(("all_reduce", "bytes"))
        x = paddle.to_tensor(np.ones((8, 4), np.float32))
        dist.all_reduce(x)
        assert fam.get(("all_reduce", "calls")) == c0 + 1
        assert fam.get(("all_reduce", "bytes")) == b0 + 8 * 4 * 4
    finally:
        dist.reset_mesh()
