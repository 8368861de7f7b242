"""``benchmark/lib/program_trace.py``: its arithmetic on hand-made intervals
(innermost-span ownership across two threads, idle gaps split by span, the
kernel's name inside an instruction's name, the stale-file refusal), each of
the nine readers on two small traces cut from chip runs of the PR that added
them (``benchmark/testdata/v5e_train_step_named.xplane.pb``: one train step
of cell 1 with ``pt_*`` kernels and ``pt.train.*`` spans;
``v5e_serve_rounds.xplane.pb``: a few decode rounds of cell 2 with one
admission), and the ``program_span`` metrics in each cell's traced
rehearsal."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmark.lib import harness, program_trace as ptr, xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(BENCH, "testdata")


# -- arithmetic ----------------------------------------------------------------

WORKER = [  # one round with its parts, one wait, one admission, in ns
    ("pt.serve.decode_round", 100, 200), ("pt.serve.decode_build", 100, 110),
    ("pt.serve.decode_dispatch", 110, 130), ("pt.serve.decode_sync", 130, 190),
    ("pt.serve.emit", 192, 198),
    ("pt.serve.idle_wait", 200, 260),
    ("pt.serve.admit", 260, 400), ("pt.serve.page_table", 262, 270),
    ("pt.serve.prefill_dispatch", 270, 300),
    ("pt.serve.prefill_sync", 300, 390), ("pt.serve.page_table", 390, 394),
]
STEPPER = [  # another thread, overlapping the worker's in time
    ("pt.train.step", 90, 300), ("pt.train.host_dispatch", 95, 150),
]


def test_each_instant_goes_to_the_innermost_span_of_its_thread():
    own = ptr.owned_ns(WORKER, 0, 1000)
    assert own == {
        "pt.serve.decode_build": 10, "pt.serve.decode_dispatch": 20,
        "pt.serve.decode_sync": 60, "pt.serve.emit": 6,
        "pt.serve.decode_round": 4,          # 190-192 and 198-200
        "pt.serve.idle_wait": 60,
        "pt.serve.page_table": 12, "pt.serve.prefill_dispatch": 30,
        "pt.serve.prefill_sync": 90,
        "pt.serve.admit": 8}                 # 260-262 and 394-400
    assert sum(own.values()) == 300          # the thread's covered time
    # clipped to a window: the sync is cut at 150, the wait is outside
    own = ptr.owned_ns(WORKER, 105, 150)
    assert own == {"pt.serve.decode_build": 5, "pt.serve.decode_dispatch": 20,
                   "pt.serve.decode_sync": 20}
    # two threads: one prefix is one thread, the other's spans never nest in
    pt = ptr.ProgramTrace({xplane.HOST_PLANE: {"python3": WORKER + STEPPER + [
        ("bench.window", 100, 400)]}})
    assert pt.window == (100, 400)
    assert pt.owned_pct(ptr.SERVE, ("pt.serve.idle_wait",)) == \
        pytest.approx(100 * 60 / 300)
    assert pt.owned_pct(ptr.SERVE, ptr.SCHED_SPANS) == \
        pytest.approx(100 * (10 + 6 + 4 + 12 + 8) / 300)
    assert pt.owned_pct(ptr.TRAIN, ("pt.train.host_dispatch",)) == \
        pytest.approx(100 * 50 / 300)        # 100-150 of the window
    assert pt.owned_pct("pt.other.", ()) is None
    # a median over the spans wholly inside the window
    assert pt.span_p50_ms("pt.serve.decode_round") == pytest.approx(100e-6)
    assert pt.span_p50_ms("pt.train.step") is None   # starts before it
    assert pt.kernel_share_pct("pt_rope") is None    # no device plane
    assert pt.idle_pct(ptr.SERVE, ptr.SCHED_SPANS) is None


def test_idle_gaps_are_split_by_the_innermost_span():
    idle = [(105, 115), (185, 195), (250, 265), (500, 510)]
    by = ptr.split_gaps(idle, WORKER)
    assert by == {
        "pt.serve.decode_build": 5, "pt.serve.decode_dispatch": 5,
        "pt.serve.decode_sync": 5, "pt.serve.decode_round": 2,
        "pt.serve.emit": 3, "pt.serve.idle_wait": 10, "pt.serve.admit": 2,
        "pt.serve.page_table": 3, "": 10}
    assert sum(by.values()) == sum(e - s for s, e in idle)
    K = ('%pt_paged_attention.4 = bf16[4,8]{1,0} custom-call(bf16[4,8]{1,0} '
         '%p.1), custom_call_target="tpu_custom_call"')
    F = "%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p.2), kind=kLoop"
    pt = ptr.ProgramTrace({
        xplane.HOST_PLANE: {"python3": WORKER + [("bench.window", 100, 400)]},
        "/device:TPU:0": {xplane.OPS_LINE: [
            (F, 100, 105), (K, 115, 185), (F, 195, 250), (F, 265, 400)]}})
    by = pt.idle_by_span(ptr.SERVE)
    assert sum(by.values()) == 35 and "" not in by
    # build 5 + emit 3 + round self 2 + admit self 2 + page table 3
    assert pt.idle_pct(ptr.SERVE, ptr.SCHED_SPANS) == \
        pytest.approx(100 * 15 / 300)
    assert pt.kernel_share_pct("pt_paged_attention") == \
        pytest.approx(100 * 70 / 265)
    assert pt.kernel_share_pct("pt_flash") is None


@pytest.mark.parametrize("short,prefix,is_in", [
    ("pt_flash_fwd.3", "pt_flash", True),
    ("transpose_jvp_pt_flash_bwd_dq__.1", "pt_flash", True),
    ("jvp_pt_rmsnorm_fwd_residual_.12", "pt_rmsnorm", True),
    ("checkpoint_pt_rope_.2", "pt_rope", True),
    ("pt_rope.7", "pt_rmsnorm", False),
    ("opt_flash.1", "pt_flash", False),
    ("_unknown_.124", "pt_flash", False),
    ("step.17", "pt_paged_attention", False),
])
def test_a_kernels_name_is_found_inside_the_instructions_name(
        short, prefix, is_in):
    assert ptr.kernel_of(short, prefix) is is_in


def _put_trace(root, cell, src=None, mtime=None):
    d = os.path.join(root, ".cache", "bench_trace", cell, "plugins",
                     "profile", "2026_09_27")
    os.makedirs(d)
    path = os.path.join(d, "vm.xplane.pb")
    if src:
        shutil.copy(src, path)
    else:
        open(path, "wb").close()
    if mtime:
        os.utime(path, (mtime, mtime))
    return path


def test_a_trace_written_before_this_process_started_is_refused(tmp_path):
    root = str(tmp_path)
    assert ptr.find_run_xplane(root, 0.0) is None    # nothing there
    now = time.time()
    old = _put_trace(root, "other-cell", mtime=now - 3600)
    assert ptr.find_run_xplane(root, now - 60) is None
    assert ptr.find_run_xplane(root, now - 7200) == old
    new = _put_trace(root, "this-cell", mtime=now)
    assert ptr.find_run_xplane(root, now - 60) == new
    # a process that is not the benchmark's command reads no trace
    assert ptr.process_start() == float("inf")
    assert ptr.current({"kind": "serve"}, "train") is None


# -- the readers on traces recorded on the chip --------------------------------

def _reader(name):
    return harness.read_layer_metric(name)


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """Puts a recorded trace where a run's ``harness.Tracer`` would have
    left it, in a checkout of its own."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(ptr, "process_start", lambda: 0.0)

    def put(name):
        shutil.rmtree(os.path.join(str(tmp_path), ".cache"),
                      ignore_errors=True)
        return _put_trace(str(tmp_path), "cell", os.path.join(DATA, name))
    return put


def test_readers_on_one_named_v5e_train_step(recorded):
    path = recorded("v5e_train_step_named.xplane.pb")
    shapes = {"kind": "train"}
    got = {n: _reader(n).reduce(None, {}, {}, shapes) for n in (
        "train.flash_attention_share_pct", "train.rope_share_pct",
        "train.rmsnorm_share_pct", "train.host_dispatch_p50_ms")}
    assert all(v is not None for v in got.values()), got
    # the three shares are the whole of what summarize calls kernel time
    s = xplane.summarize(xplane.read_xplane(path))
    lump = 100.0 * s["kernel_s"] / s["busy_s"]
    assert got["train.flash_attention_share_pct"] \
        + got["train.rope_share_pct"] + got["train.rmsnorm_share_pct"] \
        == pytest.approx(lump, abs=1e-6)
    assert got["train.flash_attention_share_pct"] > \
        got["train.rope_share_pct"] > got["train.rmsnorm_share_pct"] > 0
    assert 0 < got["train.host_dispatch_p50_ms"] < 50
    assert not any("_unknown_" in n for n, _t in s["device_ops"])
    # a serve reader in a train cell, and a train reader in a serve cell
    assert _reader("serve.decode_round_p50_ms").reduce(
        None, {}, {}, shapes) is None
    assert _reader("train.rope_share_pct").reduce(
        None, {}, {}, {"kind": "serve"}) is None


def test_readers_on_a_few_v5e_decode_rounds_with_one_admission(recorded):
    path = recorded("v5e_serve_rounds.xplane.pb")
    shapes = {"kind": "serve"}
    got = {n: _reader(n).reduce(None, {}, {}, shapes) for n in (
        "serve.decode_round_p50_ms", "serve.worker_idle_pct",
        "serve.sched_host_pct", "serve.idle_sched_pct",
        "serve.paged_attention_share_pct")}
    assert all(v is not None for v in got.values()), got
    pt = ptr.current(shapes, "serve")
    names = {n for n, _s, _e in pt.spans(ptr.SERVE)}
    assert {"pt.serve.decode_round", "pt.serve.decode_sync",
            "pt.serve.admit", "pt.serve.page_table",
            "pt.serve.prefill_sync"} <= names
    s = xplane.summarize(xplane.read_xplane(path))
    idle_pct = 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    assert 0 <= got["serve.idle_sched_pct"] <= idle_pct
    by = pt.idle_by_span(ptr.SERVE)
    assert sum(by.values()) / pt.window_ns() * 100 == \
        pytest.approx(idle_pct, abs=1e-6)    # the split loses nothing
    assert 50 < got["serve.decode_round_p50_ms"] < 200
    assert 20 < got["serve.paged_attention_share_pct"] < 100
    assert 0 <= got["serve.worker_idle_pct"] < 100
    assert 0 < got["serve.sched_host_pct"] < 20
    assert ptr.current(shapes, "serve") is pt  # read once a process


def test_a_program_without_spans_or_names_reads_as_nothing(recorded):
    """The parent's trace (PR 23's recorded step): no ``pt.*`` span, every
    kernel ``_unknown_`` — each new reader returns None and does not raise."""
    recorded("v5e_train_step.xplane.pb")
    for f in sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))):
        mod = _reader(f[:-3])
        if "program_trace" in vars(mod):
            kind = f.split(".")[0]
            assert mod.reduce(None, {}, {}, {"kind": kind}) is None, f


# -- the traced rehearsal ------------------------------------------------------

def _program_span_metrics(cell):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", ()) and m["source"] == "program_span"
            and "program_trace" in vars(_reader(m["name"]))}


@pytest.mark.parametrize("cell", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads"))
    if f.endswith(".json")))
def test_traced_rehearsal_reports_the_program_span_metrics(cell):
    want = _program_span_metrics(cell)
    assert want, cell
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483777", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert want <= set(line["metrics"]), sorted(line["metrics"])
    for name in want:
        assert line["metrics"][name]["value"] >= 0
    # no device plane on the CPU: no device_trace number under any name
    assert not any("share_pct" in n or "idle" in n.replace("worker_idle", "")
                   for n in line["metrics"])
