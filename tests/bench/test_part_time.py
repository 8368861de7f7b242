"""``benchmark/lib/part_time.py`` and the ten ``serve.part_*_share_pct``
readers: the arithmetic on hand-made intervals and name stacks, the file
reader against a hand-made ``.xplane.pb`` and a trace recorded on the chip
(cut from a traced run of ``laguna-xs2-d5.mixed-context-peak`` by
``benchmark/testdata/trim_trace_with_stats.py``), and what the readers make
of the parent's traces, which name no part."""
import json
import os
import shutil
import struct

import pytest

from benchmark.lib import harness, part_time, program_trace as ptr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "benchmark", "testdata")
PARTS = ("embed", "norm", "attn_proj", "cache_write", "attention", "mlp",
         "router", "experts", "mixer", "head")
READERS = sorted(f[:-3] for f in os.listdir(
    os.path.join(REPO, "benchmark", "layer_metrics"))
    if f.startswith("serve.part_"))
RECORDED = "v5e_laguna_carry_parts.xplane.pb"


def test_the_vocabulary_is_the_programs():
    from paddle_tpu.observability.trace import parts

    assert parts.PARTS == PARTS
    assert len(READERS) == 10


@pytest.mark.parametrize("tf_op,want", [
    ("jit(pt_window1)/pt.attn_proj/dot_general:", "attn_proj"),
    # the innermost part owns: a norm inside a projection group
    ("jit(pt_window1)/pt.attn_proj/pt.norm/mul:", "norm"),
    ("jit(pt_window1)/pt.cache_write/pt.attention/jit(paged_attend)/"
     "pt_paged_attention/pallas_call:", "attention"),
    ("jit(pt_window1)/pt.mlp/pt.router/pt.experts/gmm:", "experts"),
    # a nested loop keeps its scope
    ("jit(pt_prefill256)/pt.mixer/while/body/closed_call/dot_general:",
     "mixer"),
    # a fusion XLA made of two stacks: the first that names a part
    ("jit(pt_window1)/pt.cache_write/reshape;jit(pt_window1)/pt.attn_proj/"
     "squeeze:", "cache_write"),
    ("jit(pt_window1)/add;jit(pt_window1)/pt.head/reduce_max:", "head"),
    # no part: outside the vocabulary, another prefix, nothing at all
    ("jit(pt_window1)/pt.softmax/exp:", None),
    ("jit(step)/norm/mul:", None),
    ("", None)])
def test_an_op_belongs_to_the_innermost_part_of_its_name_stack(tf_op, want):
    assert part_time.part_of(tf_op, PARTS) == want


def test_an_op_without_a_name_goes_with_the_next_of_its_run():
    runs = [(0.0, 100.0), (100.0, 200.0)]
    ops = [(None, 0, 5),            # a copy before the first named op
           ("norm", 5, 10),
           (None, 10, 20),          # a layout copy scheduled for the scatter
           ("cache_write", 20, 30),
           ("attention", 30, 60),
           (None, 60, 70),          # the last of its run: the one before it
           (None, 100, 110),        # second run: nothing named at all
           (None, 110, 120),
           (None, 250, 260)]        # outside every run
    assert part_time.inherit(ops, runs) == [
        "norm", "norm", "cache_write", "cache_write", "attention",
        "attention", "unscoped", "unscoped", "unscoped"]
    # without a modules line every op is one run
    assert part_time.inherit(ops, []) == [
        "norm", "norm", "cache_write", "cache_write", "attention",
        "attention", "attention", "attention", "attention"]


def test_shares_are_self_time_over_busy_time_and_add_up():
    # one run; a ``while`` of ``mixer`` spans two children, one of which is
    # named by another part; a gap of idle time; an op cut by the window
    ops = [("embed", 0, 10),
           ("mixer", 10, 50),           # the loop: owns 40 - 10 - 15 = 15
           ("mixer", 12, 22),
           ("norm", 25, 40),
           (None, 60, 70),              # -> head (the next named)
           ("head", 70, 100),
           ("head", 110, 130)]          # past the window's end
    got = part_time.shares_pct([(ops, [(0.0, 140.0)])], 5.0, 100.0)
    busy = (50 - 5) + (100 - 60)
    assert got == pytest.approx({
        "embed": 100 * 5 / busy, "mixer": 100 * 25 / busy,
        "norm": 100 * 15 / busy, "head": 100 * 40 / busy})
    assert sum(got.values()) == pytest.approx(100.0)
    # two devices add; a device on which nothing names a part is unscoped
    two = part_time.shares_pct(
        [(ops, [(0.0, 140.0)]), ([(None, 10, 40)], [(0.0, 140.0)])], 5.0,
        100.0)
    assert two["unscoped"] == pytest.approx(100 * 30 / (busy + 30))
    assert sum(two.values()) == pytest.approx(100.0)
    # nothing named anywhere (the parent), nothing in the window: no number
    assert part_time.shares_pct([([(None, 10, 40)], [])], 0, 100) is None
    assert part_time.shares_pct([(ops, [])], 500.0, 600.0) is None
    assert part_time.shares_pct([], 0, 100) is None


# -- the file reader on a hand-made file ---------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(no << 3 | 1) + struct.pack("<d", value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, metadata, lines):
    """``metadata``: {id: (name, {stat id: str | ("ref", stat id)})};
    ``lines``: {name: (timestamp_ns, [(metadata id, offset_ps, dur_ps)])}."""
    out = _field(1, 7) + _field(2, name)
    for lname, (t0, evs) in lines.items():
        body = _field(2, lname) + _field(3, t0)
        for mid, off, dur in evs:
            body += _field(4, _field(1, mid) + _field(2, off)
                           + _field(3, dur) + _field(4, _field(1, 99)
                                                     + _field(2, 1.5)))
        out += _field(3, body)
    for mid, (mname, stats) in metadata.items():
        md = _field(1, mid) + _field(2, mname)
        for sid, val in stats.items():
            md += _field(5, _field(1, sid) + (
                _field(7, val[1]) if isinstance(val, tuple)
                else _field(5, val)))
        out += _field(4, _field(1, mid) + _field(2, md))
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    return _field(1, out)


def test_the_file_reader_takes_the_name_stack_from_the_events_metadata(
        tmp_path):
    """Two programs share an instruction's NAME: the key is the event's
    metadata (program and instruction), never the name alone."""
    stat_names = {1: "tf_op", 2: "program_id", 3: "hlo_category",
                  4: "jit(pt_window1)/pt.norm/mul:"}
    metadata = {
        10: ("%fusion.1 = bf16[8] fusion()", {
            1: "jit(pt_window1)/pt.attn_proj/dot_general:", 3: "x"}),
        11: ("%fusion.1 = bf16[8] fusion()", {
            1: "jit(pt_prefill8)/pt.mlp/dot_general:"}),
        12: ("%copy.2 = bf16[8] copy()", {3: "data formatting"}),
        13: ("%fusion.7 = bf16[8] fusion()", {1: ("ref", 4)}),
        20: ("jit_pt_window1(5)", {}), 21: ("jit_pt_prefill8(6)", {})}
    lines = {
        "XLA Ops": (1000, [(10, 0, 10_000), (12, 10_000, 5_000),
                           (13, 15_000, 5_000), (11, 50_000, 20_000)]),
        "XLA Modules": (1000, [(20, 0, 30_000), (21, 50_000, 20_000)]),
        "Async XLA Ops": (1000, [(12, 0, 99_000)])}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        _plane("/host:CPU", {}, {1: ("bench.window", {})},
               {"python3": (0, [(1, 0, 1)])})
        + _plane("/device:TPU:0", stat_names, metadata, lines))
    (ops, runs), = part_time.read_devices(str(path), PARTS)
    assert ops == [("attn_proj", 1000.0, 1010.0), (None, 1010.0, 1015.0),
                   ("norm", 1015.0, 1020.0), ("mlp", 1050.0, 1070.0)]
    assert runs == [(1000.0, 1030.0), (1050.0, 1070.0)]
    got = part_time.shares_pct([(ops, runs)], 0.0, 2000.0)
    assert got == pytest.approx({"attn_proj": 25.0, "norm": 25.0,
                                 "mlp": 50.0})


# -- the readers on traces recorded on the chip --------------------------------

@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """Puts a recorded trace where a run's ``harness.Tracer`` would have
    left it, in a checkout of its own."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(ptr, "process_start", lambda: 0.0)

    def put(name):
        shutil.rmtree(os.path.join(str(tmp_path), ".cache"),
                      ignore_errors=True)
        d = os.path.join(str(tmp_path), ".cache", "bench_trace", "cell",
                         "plugins", "profile", "run")
        os.makedirs(d)
        return shutil.copy(os.path.join(DATA, name),
                           os.path.join(d, "t.xplane.pb"))
    return put


@pytest.mark.parametrize("trace", ["v5e_train_step.xplane.pb",
                                   "v5e_serve_rounds.xplane.pb",
                                   "v5e_train_step_named.xplane.pb"])
@pytest.mark.parametrize("reader", READERS)
def test_a_program_that_names_no_part_reads_as_nothing(recorded, reader,
                                                       trace):
    """The parents' traces (PR 23's, PR 24's): not one scoped op — each of
    the ten readers returns None and does not raise."""
    recorded(trace)
    mod = harness.read_layer_metric(reader)
    for shapes in ({"kind": "serve"}, {"kind": "train"}, {}):
        assert mod.reduce(None, {}, {}, shapes) is None


@pytest.mark.parametrize("reader", READERS)
def test_no_trace_no_number(reader):
    mod = harness.read_layer_metric(reader)
    assert mod.reduce(None, {}, {}, {"kind": "serve"}) is None


def test_every_reader_is_listed_with_the_cells_that_have_its_part():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serve = [w["name"] for w in bench["workloads"] if w["name"] in
             next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"].startswith("serve.part_")}
    assert sorted(listed) == READERS
    for name, m in listed.items():
        assert (m["unit"], m["source"], m["moves"], m["better"]) == (
            "%", "device_trace", "serve_tokens_per_s", "lower")
        want = {"serve.part_router_share_pct": [serve[3], serve[5]],
                "serve.part_experts_share_pct": [serve[3], serve[5]],
                "serve.part_mixer_share_pct": [serve[1]]}.get(name, serve)
        assert m["workloads"] == want, name


def test_readers_on_a_recorded_carrying_call_of_laguna(recorded):
    """A carrying call and a smaller bucket's call cut from a traced run of
    ``laguna-xs2-d5.mixed-context-peak`` on the v5e, stats kept: every
    reader of the cell reports, the shares add up to 100, next to nothing is
    unscoped, the experts' part is the ``gmm`` calls' share and little
    else, the attention part holds the ranged kernel's — and the program's
    own reader (``observability.trace.xplane``), which shares no code with
    the benchmark's, splits the same trace the same way."""
    path = recorded(RECORDED)
    shapes = {"kind": "serve"}
    got = {n: harness.read_layer_metric(n).reduce(None, {}, {}, shapes)
           for n in READERS}
    assert got.pop("serve.part_mixer_share_pct") == 0.0      # no SSM here
    assert all(v is not None and v > 0 for v in got.values()), got
    assert sum(got.values()) == pytest.approx(100.0, abs=1e-6)
    assert got["serve.part_unscoped_share_pct"] < 3.0
    gmm = harness.read_layer_metric("serve.moe_experts_share_pct").reduce(
        None, {}, {}, shapes)
    ranged = harness.read_layer_metric(
        "serve.ranged_attention_share_pct").reduce(
            None, {}, {}, dict(shapes, ranged=True))
    assert gmm <= got["serve.part_experts_share_pct"] <= gmm + 1.0
    assert got["serve.part_attention_share_pct"] >= ranged > 10
    # read once a process
    assert part_time.current(shapes) is part_time.current(shapes)

    from paddle_tpu.observability.trace import xplane as oxplane

    cor = oxplane.correlate(oxplane.read_xplane(path))
    theirs = cor.by_part
    names = sorted(n for n in theirs["programs"] if n.startswith("jit_pt_"))
    assert len(names) == 2 and sum(n.endswith("_carry") for n in names) == 1
    by = part_time.current(shapes)
    for part, pct in by.items():
        assert 100.0 * theirs["parts"][part] / theirs["device_us"] == \
            pytest.approx(pct, abs=0.05), part
    carry = next(r for n, r in theirs["programs"].items()
                 if n.endswith("_carry"))
    named = sum(us for p, us in carry["parts"].items() if p != "unscoped")
    assert named / carry["device_us"] >= 0.97
