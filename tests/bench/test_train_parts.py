"""``benchmark/lib/train_parts.py`` and the eleven ``train.part_*`` / phase
readers: the classifier and the shares on hand-made intervals and name
stacks, the file reader against a hand-made ``.xplane.pb``, what the readers
make of no trace, of a serve cell and of the parent's trace (which names
kernels and no part), their entries in ``BENCHMARK.json``, and ONE step of
``internlm2-1.8b-d12.pretrain-2k`` recorded on the chip by PR 55 (cut by
``benchmark/testdata/trim_train_trace_with_stats.py``)."""
import json
import os

import pytest
from test_part_time import _plane, recorded  # noqa: F401  (a fixture)

from benchmark.lib import harness, train_parts

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "benchmark", "testdata")
PARTS = ("embed", "norm", "attn_proj", "cache_write", "attention", "mlp",
         "router", "experts", "mixer", "head", "stack", "optimizer")
PART_READERS = {
    "train.part_attn_proj_share_pct": ("attn_proj",),
    "train.part_mlp_share_pct": ("mlp",),
    "train.part_norm_share_pct": ("norm",),
    "train.part_head_share_pct": ("embed", "head"),
    "train.part_optimizer_share_pct": ("optimizer",),
    "train.part_stack_share_pct": ("stack",),
    "train.part_attention_share_pct": ("attention",),
    "train.part_unscoped_share_pct": ("unscoped",)}
PHASE_READERS = {"train.forward_share_pct": "forward",
                 "train.recompute_share_pct": "recompute",
                 "train.backward_share_pct": "backward"}
READERS = sorted(list(PART_READERS) + list(PHASE_READERS))
LAYER = {"train.part_attention_share_pct": "kernels",
         "train.part_unscoped_share_pct": "device"}
CELLS = ["internlm2-1.8b-d12.pretrain-2k", "internlm2-1.8b-x4.pretrain-2k"]
RECORDED = "v5e_train_step_parts.xplane.pb"
F = "jit(step)/jvp(pt.stack)/jit(<unknown>)/while/body/"
B = "jit(step)/transpose(jvp(pt.stack))/jit(<unknown>)/while/body/"


def test_the_vocabulary_is_the_programs():
    from paddle_tpu.observability.trace import parts

    assert parts.PARTS + parts.STEP_PARTS == PARTS
    assert parts.PHASES == train_parts.PHASES
    assert len(READERS) == 11 and READERS == sorted(
        f[:-3] for f in os.listdir(os.path.join(
            REPO, "benchmark", "layer_metrics"))
        if f.startswith(("train.part_", "train.forward_",
                         "train.recompute_", "train.backward_")))


@pytest.mark.parametrize("tf_op,want", [
    (F + "closed_call/pt.mlp/pt.mlp/jit(<unknown>)/dot_general:",
     ("mlp", "forward")),
    # the wrapper is around the part where the gradient meets it first
    ("jit(step)/jvp(pt.head)/jit(<unknown>)/while/body/closed_call/"
     "dot_general:", ("head", "forward")),
    ("jit(step)/transpose(jvp(pt.embed))/jit(<unknown>)/scatter-add:",
     ("embed", "backward")),
    # the scan's own plumbing, and the innermost part inside it
    (F + "dynamic_update_slice:", ("stack", "forward")),
    (B + "closed_call/checkpoint/pt.attn_proj/pt.attention/"
     "custom_vjp_call/pallas_call:", ("attention", "backward")),
    (B + "closed_call/checkpoint/rematted_computation/pt.norm/rsqrt:",
     ("norm", "recompute")),
    ("jit(step)/pt.optimizer/sqrt:", ("optimizer", "none")),
    # of ``a;b`` the first stack that names a part gives part AND phase
    ("jit(step)/jvp(add):;" + B + "closed_call/checkpoint/"
     "rematted_computation/pt.mlp/mul:", ("mlp", "recompute")),
    (B + "closed_call/checkpoint/pt.mlp/mul:;" + F + "closed_call/pt.norm/"
     "mul:", ("mlp", "backward")),
    # a name and no part: unscoped, in the phase its own stack says
    ("jit(step)/jvp(mul):", ("unscoped", "forward")),
    ("jit(step)/transpose(jvp(pt.softmax))/exp:", ("unscoped", "backward")),
    ("jit(step)/convert_element_type:", ("unscoped", "none")),
    # an op called ``transpose`` is no wrapper
    ("jit(step)/pt.optimizer/transpose:", ("optimizer", "none")),
    # no name at all: it inherits
    ("", None)])
def test_an_op_is_labelled_by_part_and_phase(tf_op, want):
    assert train_parts.label_of(tf_op, PARTS) == want


def test_a_serve_vocabulary_skips_the_step_parts():
    assert train_parts.label_of(F + "dynamic_slice:", PARTS[:10]) == (
        "unscoped", "forward")
    assert train_parts.part_of(F + "closed_call/pt.mlp/mul", PARTS[:10]) \
        == "mlp"


def test_shares_keep_the_three_cases_apart_and_add_up():
    runs = [(0.0, 200.0)]
    ops = [("", 0, 10),                                  # -> stack forward
           (F[:-6] + ":", 10, 100),                      # the while: owns 20
           (F + "closed_call/pt.mlp/dot_general:", 10, 50),
           ("jit(step)/jvp(mul):", 50, 60),              # named, no part
           ("", 60, 70),                                 # -> mlp recompute
           (B + "closed_call/checkpoint/rematted_computation/pt.mlp/mul:",
            70, 90),
           ("jit(step)/pt.optimizer/mul:", 100, 140),
           ("", 140, 150),                       # the last: the one before
           ("jit(step)/pt.optimizer/mul:", 190, 230)]    # cut by the window
    got = train_parts.shares_pct([(ops, runs)], PARTS, 0.0, 200.0)
    busy = 150.0 + 10.0
    assert got == pytest.approx({
        ("stack", "forward"): 100 * 20 / busy,
        ("mlp", "forward"): 100 * 40 / busy,
        ("unscoped", "forward"): 100 * 10 / busy,
        ("mlp", "recompute"): 100 * 30 / busy,
        ("optimizer", "none"): 100 * 60 / busy})
    assert sum(got.values()) == pytest.approx(100.0)
    assert train_parts.by_part(got) == pytest.approx({
        "stack": 12.5, "mlp": 43.75, "unscoped": 6.25, "optimizer": 37.5})
    assert train_parts.by_phase(got) == pytest.approx({
        "forward": 43.75, "recompute": 18.75, "none": 37.5})
    # two devices add; a run in which nothing has a name is unscoped
    two = train_parts.shares_pct(
        [(ops, runs), ([("", 10, 50)], [(0.0, 200.0)])], PARTS, 0.0, 200.0)
    assert two[("unscoped", "none")] == pytest.approx(100 * 40 / (busy + 40))
    assert sum(two.values()) == pytest.approx(100.0)
    # names and no part anywhere (the parent), nothing in the window
    parent = [("jit(step)/jvp(jit(<unknown>))/while/body/dot_general:", 0, 9),
              ("", 9, 12)]
    assert train_parts.shares_pct([(parent, runs)], PARTS, 0, 200) is None
    assert train_parts.shares_pct([(ops, runs)], PARTS, 500.0, 600.0) is None
    assert train_parts.shares_pct([], PARTS, 0, 200) is None


def test_the_file_reader_hands_back_the_name_stack_as_written(tmp_path):
    """``tf_op`` sits in the event's METADATA, as a string or as a reference
    to a stat's name; two instructions share a NAME; an op without the stat
    has no name; other lines are not read."""
    stat_names = {1: "tf_op", 2: "program_id", 3: "hlo_category",
                  4: B + "closed_call/checkpoint/pt.mlp/dot_general:"}
    metadata = {
        10: ("%fusion.1 = bf16[8] fusion()", {
            1: F + "closed_call/pt.norm/mul:", 3: "x"}),
        11: ("%fusion.1 = bf16[8] fusion()", {1: ("ref", 4)}),
        12: ("%copy.2 = bf16[8] copy()", {3: "data formatting"}),
        13: ("%fusion.7 = bf16[8] fusion()", {
            1: "jit(step)/pt.optimizer/mul:"}),
        20: ("jit_step(5)", {})}
    lines = {
        "XLA Ops": (1000, [(10, 0, 10_000), (12, 10_000, 5_000),
                           (11, 15_000, 25_000), (13, 40_000, 10_000)]),
        "XLA Modules": (1000, [(20, 0, 50_000)]),
        "Async XLA Ops": (1000, [(12, 0, 99_000)])}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        _plane("/host:CPU", {}, {1: ("bench.window", {})},
               {"python3": (0, [(1, 0, 1)])})
        + _plane("/device:TPU:0", stat_names, metadata, lines))
    (ops, runs), = train_parts.read_devices(str(path))
    assert ops == [(F + "closed_call/pt.norm/mul:", 1000.0, 1010.0),
                   ("", 1010.0, 1015.0), (stat_names[4], 1015.0, 1040.0),
                   ("jit(step)/pt.optimizer/mul:", 1040.0, 1050.0)]
    assert runs == [(1000.0, 1050.0)]
    got = train_parts.shares_pct([(ops, runs)], PARTS, 0.0, 2000.0)
    assert got == pytest.approx({("norm", "forward"): 20.0,
                                 ("mlp", "backward"): 60.0,
                                 ("optimizer", "none"): 20.0})


# -- the readers ---------------------------------------------------------------

@pytest.mark.parametrize("reader", READERS)
def test_no_trace_no_number(reader):
    mod = harness.read_layer_metric(reader)
    assert mod.UNIT == "%"
    for shapes in ({"kind": "train"}, {"kind": "serve"}, {}):
        assert mod.reduce(None, {}, {}, shapes) is None


@pytest.mark.parametrize("trace", ["v5e_train_step_named.xplane.pb",
                                   "v5e_train_step.xplane.pb",
                                   "v5e_laguna_carry_parts.xplane.pb"])
@pytest.mark.parametrize("reader", READERS)
def test_a_program_that_names_no_part_of_a_train_step_reads_as_nothing(
        recorded, reader, trace):  # noqa: F811
    """The parent's traced train step (PR 24's: kernels named, no part; PR
    23's) and a serve cell's trace: every reader returns None in a train
    cell and in a serve cell, and does not raise."""
    recorded(trace)
    mod = harness.read_layer_metric(reader)
    kinds = [{"kind": "serve"}, {}]
    if "laguna" not in trace:     # a served program is no train cell's trace
        kinds.append({"kind": "train"})
    for shapes in kinds:
        assert mod.reduce(None, {}, {}, shapes) is None


def test_every_reader_is_listed_with_both_train_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert CELLS == next(m for m in bench["end_to_end"] if m["name"] ==
                         "train_tokens_per_s")["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in READERS}
    assert sorted(listed) == READERS
    # appended: behind every metric the benchmark already had
    assert [m["name"] for m in bench["per_layer"]][-11:] == [
        m for m in (n["name"] for n in bench["per_layer"]) if m in READERS]
    for name, m in listed.items():
        assert m == {"name": name, "unit": "%", "better": "lower",
                     "source": "device_trace",
                     "layer": LAYER.get(name, "model step"),
                     "moves": "train_tokens_per_s", "workloads": CELLS}


def test_readers_on_a_recorded_step_of_cell_1(recorded):  # noqa: F811
    """One step of ``internlm2-1.8b-d12.pretrain-2k`` cut from a traced run
    on the v5e (PR 55), stats kept: all eleven readers report, the eight
    parts add up to 100 and so do the phases with what has none, all three
    phases are there, next to nothing is unscoped, the attention part holds
    the flash kernels and some glue — and the program's own reader
    (``observability.trace.xplane``), which shares no code with the
    benchmark's, splits the same step the same way."""
    path = recorded(RECORDED)
    assert os.path.getsize(path) < 300_000
    shapes = {"kind": "train"}
    got = {n: harness.read_layer_metric(n).reduce(None, {}, {}, shapes)
           for n in READERS}
    assert all(v is not None for v in got.values()), got
    parts = {n: got[n] for n in PART_READERS}
    phases = {n: got[n] for n in PHASE_READERS}
    assert sum(parts.values()) == pytest.approx(100.0, abs=1e-6)
    assert all(v > 0 for v in phases.values()), phases
    by = train_parts.current(shapes)
    no_phase = train_parts.by_phase(by).get("none", 0.0)
    assert sum(phases.values()) + no_phase == pytest.approx(100.0, abs=1e-6)
    assert no_phase >= got["train.part_optimizer_share_pct"] > 0
    assert got["train.part_unscoped_share_pct"] < 3.0
    assert got["train.part_mlp_share_pct"] > \
        got["train.part_attn_proj_share_pct"] > 0
    assert got["train.backward_share_pct"] > got["train.forward_share_pct"] \
        > got["train.recompute_share_pct"]
    flash = harness.read_layer_metric(
        "train.flash_attention_share_pct").reduce(None, {}, {}, shapes)
    assert flash <= got["train.part_attention_share_pct"] <= flash + 3.0
    # the recompute replays no flash kernel (PR 51) and no optimizer exists
    # inside the gradient
    assert ("optimizer", "none") in by and not any(
        part == "optimizer" and ph != "none" for part, ph in by)
    # read once a process
    assert train_parts.current(shapes) is by
    # a serve cell asks for nothing of it
    assert harness.read_layer_metric(READERS[0]).reduce(
        None, {}, {}, {"kind": "serve"}) is None

    from paddle_tpu.observability.trace import xplane as oxplane

    theirs = oxplane.correlate(oxplane.read_xplane(path)).by_part
    step, = theirs["programs"].values()
    assert step["calls"] == 1
    for part, pct in train_parts.by_part(by).items():
        assert 100.0 * theirs["parts"][part] / theirs["device_us"] == \
            pytest.approx(pct, abs=0.05), part
    for (part, phase), pct in by.items():
        assert 100.0 * step["phases"][part][phase] / theirs["device_us"] == \
            pytest.approx(pct, abs=0.05), (part, phase)
