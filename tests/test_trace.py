"""Device-truth tracing (ISSUE-7): XPlane ingestion + correlation,
request-scoped serving traces, flight recorder + pd_dump bundles,
histogram exposition. The heavy real-capture tests are slow-marked for
tier-1 wall clock but run IN FULL by tools/ci.sh's tracing gate (which
also runs tools/trace_drill.py — the three acceptance asserts)."""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import trace as otrace
from paddle_tpu.observability.timeline import StepTimeline


# -- XPlane parse + correlation (synthetic artifact: exact math) ---------------

def _pb(field, value):
    """One protobuf field: an int as a varint, bytes/str length-delimited
    (all the xplane schema needs here; the profiler's own file format)."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(field << 3 | 2) + varint(len(value)) + value


def _xspace(planes):
    """``{plane: {line: [(name, start_us, dur_us, {stat: value}[, {stat of
    the event's METADATA: value}])]}}`` as the bytes of an XSpace (XPlane:
    name=2 lines=3 event_metadata=4 stat_metadata=5; XLine: name=2 events=4;
    XEvent: metadata_id=1 offset_ps=2 duration_ps=3 stats=4; XStat:
    metadata_id=1 int64_value=4 str_value=5; the metadata maps: key=1
    value=2, value: id=1 name=2, an event's metadata also stats=5). Events
    of one name with different metadata stats are different instructions."""
    space = b""
    for pname, lines in planes.items():
        names, stat_names, body = {}, {}, _pb(2, pname)

        def stat(k, v):
            sid = stat_names.setdefault(k, len(stat_names) + 1)
            return _pb(1, sid) + _pb(4 if isinstance(v, int) else 5, v)

        for lname, events in lines.items():
            line = _pb(2, lname)
            for name, ts, dur, stats, *md in events:
                key = (name, json.dumps(md))
                ev = _pb(1, names.setdefault(key, len(names) + 1)) \
                    + _pb(2, int(ts * 1e6)) + _pb(3, int(dur * 1e6))
                for k, v in stats.items():
                    ev += _pb(4, stat(k, v))
                line += _pb(4, ev)
            body += _pb(3, line)
        for (n, md), i in names.items():
            meta = _pb(1, i) + _pb(2, n)
            for k, v in (json.loads(md) or [{}])[0].items():
                meta += _pb(5, stat(k, v))
            body += _pb(4, _pb(1, i) + _pb(2, meta))
        for n, i in stat_names.items():
            body += _pb(5, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, n)))
        space += _pb(1, body)
    return space


def _synthetic_trace():
    """Two steps; step 0: one 100us hlo op fully inside a device_block
    phase (exposed), step 1: one 80us op outside any blocking phase
    (hidden) + a 20us op spilling past the window (attributed to step 1),
    plus one pre-window op (unattributed), host noise, and on a TPU plane
    a ``while`` that CONTAINS its body's op (self time, no double count)."""
    hlo1 = {"hlo_op": "fusion.1", "hlo_module": "jit_step"}
    hlo2 = {"hlo_op": "fusion.2", "hlo_module": "jit_step"}
    T = "bf16[4,8]{1,0:T(8,128)(2,1)}"
    return {
        "/host:CPU": {
            "python3": [
                ("pt.train.step", 1000, 1000, {"step_num": 7}),
                ("pt.train.host_dispatch", 1000, 300, {}),
                ("pt.train.device_block", 1300, 600, {}),
                ("pt.train.step", 2500, 1000, {"step_num": 8}),
                ("pt.train.host_dispatch", 2500, 400, {}),
                ("PjitFunction(step)", 2500, 400, {}),   # not ours: skipped
            ],
            "tf_XLAEigen/2": [
                ("fusion.1", 500, 30, hlo1),             # pre-window
                ("fusion.1", 1400, 100, hlo1),           # exposed (in block)
                ("fusion.2", 2600, 80, hlo2),            # hidden
                ("fusion.2", 3600, 20, hlo2),            # spill -> step 8
                ("ThunkExecutor::Execute", 2600, 900, {}),  # no hlo_op
            ],
        },
        "/device:TPU:0": {
            "XLA Ops": [
                (f"%while.1 = ({T}) while(({T}) %t.1), body=%b.1", 2700,
                 100, {}),
                (f"%pt_rope.3 = {T} custom-call({T} %p.1), "
                 f"custom_call_target=\"tpu_custom_call\"", 2720, 60, {}),
            ],
            "XLA Modules": [("jit_step(1)", 2700, 100, {})],  # not an op
        },
    }


def test_synthetic_trace_parse_and_correlate(tmp_path):
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(_synthetic_trace()))
    cor = otrace.correlate_logdir(str(tmp_path))
    assert cor.source and cor.source.endswith(".xplane.pb")
    assert len(cor.steps) == 2 and cor.steps_correlated == 2
    s0, s1 = cor.steps
    assert s0["step"] == 7 and s0["device_us"] == pytest.approx(100)
    assert s0["exposed_us"] == pytest.approx(100)   # inside device_block
    assert s0["hidden_us"] == pytest.approx(0)
    assert s0["phases"]["device_block"]["device_us"] == pytest.approx(100)
    # 80 in-window + 20 spill on the CPU line, 100 (the while's union with
    # its body, not 160) on the TPU's
    assert s1["step"] == 8 and s1["device_us"] == pytest.approx(200)
    assert s1["hidden_us"] == pytest.approx(200)    # no blocking phase
    assert cor.unattributed_device_us == pytest.approx(30)
    assert cor.overlap_efficiency() == pytest.approx(2 / 3, abs=1e-4)
    ops = {r["op"]: r for r in cor.op_table}
    assert ops["fusion.2"]["calls"] == 2
    assert ops["fusion.2"]["total_us"] == pytest.approx(100)
    assert ops["fusion.2"]["module"] == "jit_step"
    # the op table holds SELF time under the op's short name
    assert ops["pt_rope.3"]["total_us"] == pytest.approx(60)
    assert ops["while.1"]["total_us"] == pytest.approx(40)
    assert not {"jit_step(1)", "PjitFunction(step)",
                "ThunkExecutor::Execute"} & set(ops)
    assert cor.device_threads == ["/device:TPU:0/XLA Ops",
                                  "/host:CPU/tf_XLAEigen/2"]
    # summary is JSON-able and carries the op table + digest
    json.dumps(cor.summary())


def test_device_time_by_part_of_a_served_step(tmp_path):
    """``by_part``: an op belongs to the innermost ``pt.<part>`` of the name
    stack in its METADATA (two programs share the instruction name
    ``fusion.1``), an op with none to the next of its run that has one, else
    the one before; a ``while`` owns only its self time; a run that names
    nothing is unscoped."""
    T = "bf16[4,8]{1,0:T(8,128)(2,1)}"

    def op(short, ts, dur, tf_op=None, prog=5):
        md = {"program_id": prog}
        if tf_op:
            md["tf_op"] = f"jit(pt_window1)/{tf_op}:"
        return (f"%{short} = {T} fusion({T} %p.1)", ts, dur, {}, md)

    tr = {"/host:CPU": {"python3": [("pt.train.step", 0, 10, {})]},
          "/device:TPU:0": {
        "XLA Modules": [("jit_pt_window1(5)", 100, 100, {}),
                        ("jit_pt_window1(5)", 300, 100, {}),
                        ("jit_pt_prefill8_carry(6)", 500, 100, {}),
                        ("jit_step(7)", 700, 50, {})],
        "XLA Ops": [
            op("copy.1", 100, 10),                       # -> attn_proj
            op("fusion.1", 110, 30, "pt.attn_proj/dot_general"),
            op("while.2", 140, 40, "pt.mixer/while"),
            op("fusion.3", 150, 10, "pt.mixer/while/body/pt.norm/mul"),
            op("copy.4", 180, 20),       # the last: the one before, norm
            op("fusion.1", 310, 30, "pt.attn_proj/dot_general"),
            op("fusion.1", 500, 60, "pt.cache_write/pt.attention/"
                                    "jit(paged_attend)/pallas_call", prog=6),
            op("fusion.9", 560, 40, "pt.cache_write/reshape;jit(x)/"
                                    "pt.attn_proj/squeeze", prog=6),
            op("fusion.1", 700, 50, prog=7)]}}           # the draft's step
    p = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    p.parent.mkdir(parents=True)
    p.write_bytes(_xspace(tr))
    cor = otrace.correlate_logdir(str(tmp_path))
    bp = cor.by_part
    assert bp["parts"] == {"attn_proj": 70.0, "attention": 60.0,
                           "unscoped": 50.0, "cache_write": 40.0,
                           "mixer": 30.0, "norm": 30.0}
    assert bp["device_us"] == pytest.approx(280.0)
    w1 = bp["programs"]["jit_pt_window1"]
    assert w1["calls"] == 2 and w1["device_us"] == pytest.approx(130.0)
    assert w1["parts"] == {"attn_proj": 70.0, "mixer": 30.0, "norm": 30.0}
    assert w1["top_ops"]["attn_proj"][0] == {
        "op": "fusion.1", "shape": "bf16[4,8]", "calls": 2, "us": 60.0}
    carry = bp["programs"]["jit_pt_prefill8_carry"]
    assert carry["parts"] == {"attention": 60.0, "cache_write": 40.0}
    assert bp["programs"]["jit_step"]["parts"] == {"unscoped": 50.0}
    digest = cor.summary()["by_part"]
    assert digest["parts"] == bp["parts"]
    assert "top_ops" not in digest["programs"]["jit_pt_window1"]
    json.dumps(cor.summary())
    # the operator's tool prints the same table
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "program_parts", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "program_parts.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    text = tool.render(bp)
    assert "jit_pt_window1: 2 calls, 0.065 ms a call" in text
    assert "jit_pt_prefill8_carry: 1 calls" in text and "unscoped" in text
    assert tool.find(str(tmp_path)) == str(p)


def test_a_trace_without_a_device_ops_line_has_no_by_part(tmp_path):
    tr = _synthetic_trace()
    del tr["/device:TPU:0"]
    p = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    p.parent.mkdir(parents=True)
    p.write_bytes(_xspace(tr))
    cor = otrace.correlate_logdir(str(tmp_path))
    assert cor.by_part is None and cor.summary()["by_part"] is None


def test_find_xplane_empty_and_step_order_without_the_stat(tmp_path):
    assert otrace.find_xplane(str(tmp_path)) is None
    with pytest.raises(FileNotFoundError):
        otrace.correlate_logdir(str(tmp_path))
    # a trace whose step spans carry no ``step_num``: numbered by order
    tr = _synthetic_trace()
    tr["/host:CPU"]["python3"] = [
        (n, ts, dur, {}) for n, ts, dur, _st in tr["/host:CPU"]["python3"]]
    p = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    p.parent.mkdir(parents=True)
    p.write_bytes(_xspace(tr))
    cor = otrace.correlate(otrace.read_xplane(str(p)))
    assert [s["step"] for s in cor.steps] == [0, 1]


# -- real CPU capture (heavy: runs jax.profiler) -------------------------------

def test_capture_real_cpu_trace_correlates():
    """The ISSUE-7 acceptance shape: a CPU-run traced window reports
    device_compute_us from XPlane correlation (not host-block), phases
    attributed, >= 1 device op — and it lands in snapshot()/pd_top."""
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu import jit

    tl = obs.timeline()
    tl.reset()
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
    opt = popt.Adam(learning_rate=0.01, parameters=net.parameters())
    step = jit.TrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.zeros((4, 1), np.float32))
    step(x, y)  # compile outside the window
    with otrace.capture_steps() as cap:
        for _ in range(3):
            float(step(x, y))
    assert cap.error is None, cap.error
    cor = cap.result
    assert cor.steps_correlated >= 2, cor.summary()
    assert cor.op_table, "no device-attributed ops"
    assert any("host_dispatch" in s["phases"] for s in cor.steps)
    s = tl.summary()
    assert s["device_source"] == "xplane"
    assert s["device_compute_us"]["count"] >= 2
    assert s["device_compute_us"]["avg"] > 0
    # hub provider + renderer carry the digest
    snap = obs.snapshot()
    assert snap["device_trace"]["op_table"], snap["device_trace"]
    assert snap["device_trace"]["captures"] >= 1
    out = obs.render_snapshot(snap)
    assert "device_trace" in out and "steps_correlated" in out
    # capture_steps is reentrant-safe: a second window still correlates
    with otrace.capture_steps() as cap2:
        float(step(x, y))
    assert cap2.error is None and cap2.result is not None


# -- request-scoped tracing ----------------------------------------------------

def test_request_tracer_api_and_export(tmp_path):
    tr = otrace.RequestTracer(capacity=8)
    t0 = time.monotonic()
    tid = tr.start("eng", kind="serve", n=1)
    tr.span(tid, "admission", t0, t0 + 0.001)
    tr.span(tid, "queue", t0 + 0.001, t0 + 0.002)
    tr.finish(tid, ok=True)
    tr.slot_span("eng", 0, t0, t0 + 0.01, tid, tokens=3)
    # unknown ids are ignored, never raise
    tr.span("nope", "x", t0, t0)
    tr.finish(None)
    snap = tr.snapshot()
    assert snap["started"] == snap["finished"] == 1
    assert snap["slot_spans"] == 1
    path = tr.export_chrome(str(tmp_path / "req.json"))
    d = json.load(open(path))
    xs = [e for e in d["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"admission", "queue", "slot0"}
    assert all(e["args"]["trace_id"] == tid for e in xs)
    procs = {e["args"]["name"] for e in d["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert procs == {"requests:eng", "slots:eng"}


def test_serving_trace_id_propagation():
    """Multi-request ServingEngine run: every request's admission ->
    queue -> batch_coalesce -> execute spans share ONE trace id."""
    from paddle_tpu import serving
    from paddle_tpu.observability.trace import tracer

    eng = serving.ServingEngine(
        lambda a: a + 1.0, buckets=serving.BucketSpec(batch_sizes=(1, 4)),
        input_specs=[((4,), "float32")], name="trace_prop")
    with eng:
        futs = [eng.submit([np.full(4, i, np.float32)]) for i in range(6)]
        for f in futs:
            f.result(timeout=60)
    traces = tracer().traces(engine="trace_prop")
    assert len(traces) == 6
    for t in traces:
        assert t["ok"] is True
        names = [s["name"] for s in t["spans"]]
        assert {"admission", "queue", "batch_coalesce", "execute"} \
            <= set(names), names
        # spans are in wall order and the queue ends where coalesce begins
        t0s = [s["t0"] for s in t["spans"]]
        assert t0s == sorted(t0s)
    # distinct requests, distinct ids
    assert len({t["trace_id"] for t in traces}) == 6
    assert "latency_ms" in traces[0]["meta"]


def test_serving_trace_failures_finish():
    """Backpressure and shed requests finish their traces as failed —
    no live-trace leak."""
    from paddle_tpu import serving
    from paddle_tpu.observability.trace import tracer

    tr = tracer()
    before = tr.snapshot()
    eng = serving.ServingEngine(
        lambda a: a, buckets=serving.BucketSpec(batch_sizes=(1,)),
        input_specs=[((2,), "float32")],
        config=serving.ServingConfig(max_queue=1, warmup_on_start=False),
        name="trace_fail")
    # closed engine: the enqueue raises and the trace is finished failed
    eng._closed = True
    with pytest.raises(serving.EngineClosed):
        eng.submit([np.ones(2, np.float32)])
    after = tr.snapshot()
    assert after["failed"] >= before["failed"] + 1
    assert after["live"] == before["live"]


def test_span_ring_parents_drops_and_profiler_feed(tmp_path):
    """The span primitive: the enclosing span of its thread is its parent,
    other threads do not nest into it, arguments set before exit reach the
    ring, a full ring counts what it drops, and a recording Profiler gets
    the span under its own name (Paddle's chrome export)."""
    import threading

    from paddle_tpu import profiler
    from paddle_tpu.observability.trace import span, tracer

    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    with span("pt.test.outer", k=1) as outer:
        with span("pt.test.inner"):
            t = threading.Thread(target=lambda: span(
                "pt.test.other").__enter__().__exit__(), name="other-thread")
            t.start()
            t.join()
        outer.args["late"] = 2
    prof.stop()
    rows = {r["name"]: r for r in tracer().worker_spans()
            if r["name"].startswith("pt.test.")}
    assert rows["pt.test.inner"]["parent"] == rows["pt.test.outer"]["id"]
    assert rows["pt.test.outer"]["parent"] is None
    assert rows["pt.test.outer"]["args"] == {"k": 1, "late": 2}
    assert rows["pt.test.other"]["parent"] is None
    assert rows["pt.test.other"]["thread"] == "other-thread"
    assert rows["pt.test.outer"]["self_us"] == pytest.approx(
        rows["pt.test.outer"]["dur_us"] - rows["pt.test.inner"]["dur_us"])
    out = str(tmp_path / "trace.json")
    prof._export_chrome(out)
    with open(out) as f:
        names = {ev["name"] for ev in json.load(f)["traceEvents"]}
    assert {"pt.test.outer", "pt.test.inner", "pt.test.other"} <= names
    small = otrace.RequestTracer(worker_capacity=2)
    for i in range(3):
        small.worker_span(i, "pt.test.x", 0.0, 1.0, "t", None, {})
    snap = small.snapshot()
    assert snap["worker_spans"] == 3 and snap["worker_dropped"] == 1
    assert [r["id"] for r in small.worker_spans()] == [1, 2]


def test_generation_worker_spans_nest_and_account():
    """The engine worker's own spans (``trace.span``): a decode round with
    its build / dispatch / sync / emit, an admission with its page-table
    work and prefill, one ``idle_wait`` per empty wait — each with the span
    that enclosed it, so self times add up to the parents' durations — and
    the request's trace id on its ``admit``."""
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability.trace import tracer

    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=64,
                    dtype="float32")
    paddle.seed(0)
    eng = serving.GenerationEngine(
        GPTForCausalLM(cfg), serving.GenerationConfig(
            max_slots=2, max_seq_len=48, prefill_buckets=(16,)),
        name="span_gen")
    with eng:
        prompt = np.arange(5).astype("int64")
        for _ in range(2):  # the second submit finds the worker waiting
            futs = [eng.submit(prompt, max_new_tokens=4) for _ in range(2)]
            for f in futs:
                assert len(f.result(timeout=300)) == 9
            deadline = time.monotonic() + 30
            while eng.stats()["active_slots"] and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)
        counters = eng.stats()["counters"]
    rows = tracer().worker_spans(thread="pt-serving-span_gen")
    by_id = {r["id"]: r for r in rows}
    kids = {}
    for r in rows:
        assert r["name"].startswith("pt.serve."), r["name"]
        if r["parent"] is not None:
            kids.setdefault(r["parent"], []).append(r)

    def of(name):
        return [r for r in rows if r["name"] == name]

    rounds, admits = of("pt.serve.decode_round"), of("pt.serve.admit")
    assert len(rounds) == counters["decode_steps"] >= 3
    assert len(admits) == 4 == counters["prefills_total"]
    # a program's span holds its own dispatch unless that went out ahead of
    # its turn (``ahead=1``: inside the span of the program before it), then
    # whatever the worker ran ahead of THIS one's read, then the read
    for r in rounds:
        names = [k["name"] for k in kids[r["id"]]]
        assert names[-2:] == ["pt.serve.decode_sync", "pt.serve.emit"]
        if not r["args"]["ahead"]:
            assert names[:2] == ["pt.serve.decode_build",
                                 "pt.serve.decode_dispatch"]
        assert r["args"]["W"] == 1 and 1 <= r["args"]["n_active"] <= 2
    assert len(of("pt.serve.decode_dispatch")) == len(rounds)
    chunks = of("pt.serve.prefill_chunk")
    assert len(chunks) == 4 == counters["prefill_chunks_total"]
    for a in admits:
        names = [k["name"] for k in kids[a["id"]]]
        chunk, = [c for c in chunks
                  if by_id[c["parent"]]["parent"] == a["id"]]
        assert names == ["pt.serve.page_table"] * (1 - chunk["args"]["ahead"]) \
            + ["pt.serve.prefill_dispatch", "pt.serve.prefill_sync"]
        assert a["args"]["prompt_len"] == 5 and a["args"]["bucket"] == 16
        assert a["args"]["prefix_blocks"] == 0 and a["args"]["slot"] in (0, 1)
    # both slots hold a sequence with tokens left: a round follows a round
    # whatever arrives, so rounds two and three of a pair go out ahead
    assert sum(r["args"]["ahead"] for r in rounds) >= 4
    assert sum(r["args"]["ahead"] for r in rounds + chunks) == \
        counters["programs_run_ahead_total"]
    # the worker's row is tied to the requests' rows by the trace id
    traces = {t["trace_id"] for t in tracer().traces(engine="span_gen")}
    assert {a["args"]["trace_id"] for a in admits} == traces
    # every wait with nothing to run is one span and one count
    waits = of("pt.serve.idle_wait")
    assert len(waits) == counters["idle_waits"] >= 2
    assert all(w["parent"] is None and w["self_us"] == w["dur_us"]
               for w in waits)
    assert "admits_requeued" not in counters  # the pool never ran out
    # self time = duration less the children's; children lie inside
    for r in rows:
        inner = kids.get(r["id"], [])
        assert r["self_us"] + sum(k["dur_us"] for k in inner) == \
            pytest.approx(r["dur_us"], abs=1e-3)
        for k in inner:
            assert k["t0"] >= r["t0"] and k["parent"] in by_id
            assert k["t0"] + k["dur_us"] / 1e6 <= \
                r["t0"] + r["dur_us"] / 1e6 + 1e-6
    # the chrome export shows the ring as the worker's row
    evs = tracer().chrome_events()
    pid = next(e["pid"] for e in evs if e.get("ph") == "M"
               and e["args"]["name"] == "worker:span_gen")
    assert sum(1 for e in evs if e.get("cat") == "worker"
               and e["pid"] == pid) == len(rows)


@pytest.mark.slow  # GPT fixture is heavy; ci.sh tracing gate runs it
def test_generation_trace_and_slot_occupancy():
    """GenerationEngine: prefill/decode spans share the request's trace
    id, the slot-occupancy track records residencies, and pd_top renders
    the compact occupancy view (the PR-4 carried item)."""
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability.trace import tracer

    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=64,
                    dtype="float32")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(max_slots=2, max_seq_len=48,
                                        prefill_buckets=(16,)),
        name="trace_gen")
    with eng:
        prompt = np.arange(5).astype("int64")
        futs = [eng.submit(prompt, max_new_tokens=4) for _ in range(3)]
        for f in futs:
            assert len(f.result(timeout=300)) == 9
        occ = eng.slot_occupancy()
    traces = tracer().traces(engine="trace_gen")
    assert len(traces) == 3
    for t in traces:
        names = [s["name"] for s in t["spans"]]
        assert {"admission", "queue", "prefill", "decode"} <= set(names)
        decode = next(s for s in t["spans"] if s["name"] == "decode")
        assert decode["args"]["tokens"] == 4
    assert occ["slots"] == 2 and occ["residencies"] == 3
    assert any(v > 0 for v in occ["busy_frac"].values())
    # slot track in the chrome export carries the owning trace ids
    evs = tracer().chrome_events()
    slot_pids = {e["pid"] for e in evs
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and e["args"]["name"] == "slots:trace_gen"}
    slot_evs = [e for e in evs if e.get("cat") == "slot"
                and e["pid"] in slot_pids]
    assert len(slot_evs) >= 3
    ids = {t["trace_id"] for t in traces}
    assert {e["args"]["trace_id"] for e in slot_evs} <= ids
    # engine stats + hub registry + renderer carry the occupancy view
    assert "slot_occupancy" in eng.metrics.snapshot()
    out = obs.render_snapshot(obs.snapshot())
    assert "slots:" in out and "active" in out


# -- flight recorder -----------------------------------------------------------

def _feed_steps(tl, n, ms=0.002):
    for _ in range(n):
        with tl.step():
            time.sleep(ms)


def test_flight_recorder_regression_trigger_and_bundle(tmp_path):
    """A step-time regression vs the rolling baseline trips the detector
    and auto-dumps a complete, parseable bundle (manifest written last)."""
    tl = StepTimeline()
    rec = otrace.FlightRecorder(min_steps=4, regress_factor=3.0,
                                dump_dir=str(tmp_path),
                                min_dump_interval_s=0.0,
                                timeline_obj=tl).attach()
    _feed_steps(tl, 8)
    with tl.step():
        time.sleep(0.05)
    snap = rec.snapshot()
    reasons = [a["reason"] for a in snap["anomalies"]]
    assert any(r.startswith("step_regression") for r in reasons), reasons
    assert snap["dumps"], "anomaly did not dump"
    bundle = snap["dumps"][0]["path"]
    man = json.load(open(os.path.join(bundle, "MANIFEST.json")))
    for name in ("snapshot.json", "flight_ring.json", "config.json"):
        assert name in man["files"] and "error" not in man["files"][name]
        json.load(open(os.path.join(bundle, name)))
    ring = json.load(open(os.path.join(bundle, "flight_ring.json")))
    assert ring["steps_recorded"] == 9
    assert max(r["ms"] for r in ring["ring"]) >= 40
    cfg = json.load(open(os.path.join(bundle, "config.json")))
    assert cfg.get("jax") and cfg.get("backend")
    rec.detach()


def test_flight_recorder_stall_compile_and_rate_limit(tmp_path):
    tl = StepTimeline()
    rec = otrace.FlightRecorder(min_steps=4, dump_dir=str(tmp_path),
                                auto_dump=False, stall_frac=0.5,
                                timeline_obj=tl).attach()
    _feed_steps(tl, 6)
    # a compile step is EXPECTED to be slow: no regression anomaly
    with tl.step():
        with tl.phase("compile"):
            time.sleep(0.05)
    assert not any(a["reason"].startswith("step_regression")
                   for a in rec.snapshot()["anomalies"])
    # a stream_wait-dominated step is a stall spike (the 50ms jump
    # clears the min_regress_ms=25 absolute floor; baseline stalls ~0)
    with tl.step():
        with tl.phase("stream_wait"):
            time.sleep(0.05)
    reasons = [a["reason"] for a in rec.snapshot()["anomalies"]]
    assert any(r.startswith("stall_spike") for r in reasons), reasons
    assert rec.snapshot()["dumps"] == []  # auto_dump off records only
    # rate limiting: max_dumps bounds explicit dumps too (unless forced)
    rec.max_dumps = 1
    assert rec.dump("one") is not None
    assert rec.dump("two") is None
    assert rec.dump("forced", force=True) is not None
    rec.detach()


def test_flight_recorder_fault_burst_and_events(tmp_path):
    from paddle_tpu.distributed.resilience import metrics as rmetrics

    tl = StepTimeline()
    rec = otrace.FlightRecorder(min_steps=2, burst_n=3, auto_dump=False,
                                dump_dir=str(tmp_path),
                                timeline_obj=tl).attach()
    _feed_steps(tl, 3)  # establish the counter baseline
    rmetrics.inc("retries", 3)  # a retry burst within one ring window
    _feed_steps(tl, 1)
    reasons = [a["reason"] for a in rec.snapshot()["anomalies"]]
    assert any(r.startswith("fault_burst") for r in reasons), reasons
    rec.record_event("stream_retry", direction="h2d", group=0)
    assert rec.snapshot()["events"][-1]["kind"] == "stream_retry"
    rec.detach()


def test_record_serving_step_lands_in_the_events_ring(tmp_path):
    """What the generation worker calls once a decode round: the step is in
    the events ring with its engine, kind, time and rows, memory-stamped
    like a train step, and a stamper that fails costs the stamp, not the
    round."""
    stamps = iter([{"in_use": 10, "watermark": 12}, None])
    rec = otrace.FlightRecorder(auto_dump=False, dump_dir=str(tmp_path),
                                timeline_obj=StepTimeline(),
                                mem_stamp_fn=lambda: next(stamps))
    rec.record_serving_step("gen", "decode", 12.3456, 7)
    rec.record_serving_step("gen", "decode", 1.0, 0)    # no stamp this time
    rec.record_serving_step("gen", "decode", 2.0, 3)    # the stamper raises
    evs = rec.snapshot()["events"]
    assert [e["kind"] for e in evs] == ["serving_step"] * 3
    assert {k: evs[0][k] for k in ("engine", "op", "ms", "n", "mem")} == {
        "engine": "gen", "op": "decode", "ms": 12.346, "n": 7,
        "mem": {"in_use": 10, "watermark": 12}}
    assert "mem" not in evs[1] and "mem" not in evs[2]
    assert evs[2]["n"] == 3 and evs[0]["t"] <= evs[2]["t"]


def test_a_decode_round_records_its_serving_step():
    """``GenerationEngine._read_round`` puts every decode round in the
    process flight recorder's ring (``docs/observability.md``, "The flight
    recorder")."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import GenerationConfig, GenerationEngine

    model = GPTForCausalLM(GPTConfig(
        vocab_size=32, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, max_position_embeddings=64,
        intermediate_size=64))
    eng = GenerationEngine(model, GenerationConfig(
        max_slots=2, max_seq_len=32, prefill_buckets=(8,)), name="flightgen")
    with eng:
        out = eng.submit(np.arange(4), max_new_tokens=4).result(timeout=120)
    assert len(out) == 8
    steps = [e for e in otrace.flight_recorder().snapshot()["events"]
             if e["kind"] == "serving_step" and e.get("engine") == "flightgen"]
    assert len(steps) >= 3, steps
    assert all(e["op"] == "decode" and e["n"] >= 1 and e["ms"] > 0
               for e in steps)


def test_preemption_fires_flight_callbacks():
    from paddle_tpu.distributed.resilience import preempt

    fired = []
    cb = lambda: fired.append(1)  # noqa: E731
    preempt.on_preemption(cb)
    try:
        preempt.request_preemption()
        assert fired == [1]
    finally:
        preempt.off_preemption(cb)
        preempt.clear_preemption()


def test_pd_dump_cli_roundtrip(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "pd_dump", os.path.join(os.path.dirname(__file__), "..", "tools",
                                "pd_dump.py"))
    pd_dump = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pd_dump)
    assert pd_dump.main(["--out", str(tmp_path), "--reason", "test",
                         "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert "snapshot.json" in out["manifest"]["files"]
    snap = json.load(open(os.path.join(out["path"], "snapshot.json")))
    assert "step_timeline" in snap


# -- histograms (the PR-4 carried exposition item) -----------------------------

def test_histogram_native_prometheus_exposition():
    import re

    h = obs.histogram("step_time_ms")
    n0 = h.count
    tl = obs.timeline()
    with tl.step():
        pass
    assert h.count == n0 + 1  # every completed step observes
    obs.histogram("request_latency_ms").observe(12.0)
    obs.histogram("queue_wait_ms").observe(3.0)
    text = obs.prometheus_text()
    assert "# TYPE pt_step_time_ms histogram" in text
    assert 'pt_step_time_ms_bucket{le="+Inf"}' in text
    assert "pt_step_time_ms_sum" in text and "pt_step_time_ms_count" in text
    assert 'pt_request_latency_ms_bucket{le="25.0"}' in text
    # the whole exposition still line-parses
    line_re = re.compile(
        r"^(# (TYPE|HELP) .*|pt_[A-Za-z0-9_]+(\{[^}]*\})? -?[0-9eE.+-]+|"
        r"pt_[A-Za-z0-9_]+\{le=\"[^\"]+\"\} [0-9]+)$")
    for line in text.strip().splitlines():
        assert line_re.match(line), f"unparseable exposition line: {line!r}"
    # snapshot carries the typed family; cumulative buckets are monotonic
    snap = obs.snapshot()["step_time_ms"]
    assert snap["type"] == "histogram"
    vals = list(snap["buckets"].values())
    assert vals == sorted(vals)
    assert snap["buckets"]["+Inf"] == snap["count"]


def test_histogram_bucket_math_and_conflict():
    from paddle_tpu.observability.registry import Histogram

    h = Histogram("t", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {"1.0": 1, "10.0": 2, "+Inf": 3}
    assert snap["sum"] == pytest.approx(55.5)
    assert h.items()[-1] == ("+Inf", 3)
    # boundary lands in its own bucket (le semantics)
    h2 = Histogram("t2", buckets=(1.0,))
    h2.observe(1.0)
    assert h2.snapshot()["buckets"]["1.0"] == 1
    obs.histogram("t_conflict", buckets=(1, 2))
    with pytest.raises(ValueError):
        obs.histogram("t_conflict", buckets=(3, 4))
