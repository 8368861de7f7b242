"""The parts and the phases of a train step (``observability.trace.parts``;
the train twin of ``tests/test_step_parts.py``): every heavy op of
``models/llama.py``'s compiled step says which part of the model asked for
it and whether it is forward, recompute or backward, the names change
nothing but metadata, and the program's reader splits a trace by both."""
import contextlib
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.optimizer as opt
from paddle_tpu import jit
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.trace import parts, xplane

STEPS = {"one_device": None, "dp2_mp2": dict(dp=2, mp=2)}
HEAVY = ("dot", "convolution", "custom-call")
COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter")


@pytest.fixture(autouse=True)
def clean_mesh():
    dist.reset_mesh()
    yield
    dist.reset_mesh()


def lowered_step(kind):
    """``jax.stages.Lowered`` of a tiny recomputing Llama's whole step:
    ``jit.TrainStep`` on one device, ``ShardedTrainStep`` on the 2 x 2
    virtual mesh the suite's sharded tests build."""
    dist.reset_mesh()
    mesh = STEPS[kind]
    if mesh:
        dist.init_mesh(**mesh, devices=jax.devices()[:4])
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        hidden_size=64, intermediate_size=192, num_attention_heads=4,
        num_key_value_heads=2, vocab_size=320, max_position_embeddings=64,
        use_recompute=True))
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          weight_decay=0.1)
    step = (dist.ShardedTrainStep if mesh else jit.TrainStep)(
        model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = np.random.default_rng(0).integers(0, 320, (8, 32)).astype(np.int32)
    x = paddle.to_tensor(ids)
    return step.lower(x, x)


@pytest.fixture(scope="module")
def compiled():
    cache = {}

    def get(kind):
        if kind not in cache:
            low = lowered_step(kind)
            cache[kind] = (low.as_text(), low.compile().as_text())
        return cache[kind]

    return get


_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def ops_with_name_stacks(text):
    """``[(opcode, name stack)]`` of every instruction of a compiled module
    (``""`` where the compiler wrote none)."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            name = _OP_NAME.search(line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("kind", list(STEPS))
def test_every_heavy_op_has_a_part_and_inside_the_gradient_a_phase(
        compiled, kind):
    ops = ops_with_name_stacks(compiled(kind)[1])
    heavy = [(op, st) for op, st in ops if op in HEAVY and st]
    assert len(heavy) > 20
    bare = [(op, st) for op, st in heavy if parts.part_of(st) is None]
    assert not bare, bare[:5]
    used = {parts.part_of(st) for _op, st in heavy}
    assert {"attn_proj", "attention", "mlp", "head"} <= used <= set(
        parts.PARTS + parts.STEP_PARTS), used
    # outside the optimizer every heavy op is the gradient's: it has a phase
    for _op, st in heavy:
        assert (parts.phase_of(st) is None) == \
            (parts.part_of(st) == "optimizer"), st
    # ... and a recomputing step has all three, spelled as the installed jax
    # spells them (a jax that renames one fails HERE, not by reading 0)
    assert {parts.phase_of(st) for _op, st in heavy} == set(parts.PHASES)
    stacks = [st for _op, st in ops]
    assert any("/jvp(pt." in st for st in stacks)
    assert any("/transpose(jvp(pt." in st for st in stacks)
    assert any("/checkpoint/rematted_computation/pt." in st for st in stacks)
    # every part of a layer is replayed, run forward and differentiated
    by_phase = {ph: {parts.part_of(st) for _op, st in ops
                     if parts.phase_of(st) == ph} for ph in parts.PHASES}
    layer = {"norm", "attn_proj", "attention", "mlp"}
    assert layer <= by_phase["recompute"] <= layer | {"stack"}
    assert layer | {"embed", "head", "stack"} <= by_phase["forward"]
    assert layer | {"embed", "head", "stack"} <= by_phase["backward"]


@pytest.mark.parametrize("kind", list(STEPS))
def test_the_update_is_optimizer_and_has_no_phase(compiled, kind):
    ops = ops_with_name_stacks(compiled(kind)[1])
    update = [st for _op, st in ops if "pt.optimizer" in st.split("/")]
    assert len(update) > 20
    assert {(parts.part_of(st), parts.phase_of(st)) for st in update} == {
        ("optimizer", None)}
    # the moments' square root is the update's and nobody else's
    assert all(parts.part_of(st) == "optimizer"
               for op, st in ops if op == "sqrt")


@pytest.mark.parametrize("kind", list(STEPS))
def test_the_scans_own_slices_are_stack(compiled, kind):
    """What the layer scan does around its body — a layer's weights sliced
    out of their stacks, a kept value written into its stack and read back,
    the counter — sits directly under the stack's ``while``: ``stack``, in
    the forward and in the backward pass; what a layer asked for sits under
    ``closed_call`` and keeps its part."""
    ops = ops_with_name_stacks(compiled(kind)[1])
    own = re.compile(r"pt\.stack\)*/jit\([^)]*\)/while/body/"
                     r"dynamic_(update_)?slice$")
    plumbing = [(op, st) for op, st in ops if own.search(st)]
    assert {op for op, _st in plumbing} >= {"dynamic-slice",
                                            "dynamic-update-slice"}
    assert {parts.part_of(st) for _op, st in plumbing} == {"stack"}
    assert {parts.phase_of(st) for _op, st in plumbing} == {"forward",
                                                            "backward"}
    inside = [st for _op, st in ops
              if re.search(r"pt\.stack.*/while/body/closed_call/.*pt\.", st)]
    assert inside and all(parts.part_of(st) != "stack" for st in inside)


def test_a_collective_counts_in_the_part_it_was_attached_to(compiled):
    """``o_proj``'s and ``down_proj``'s ``mp`` all-reduces are ``attn_proj``
    and ``mlp``; no collective of the step is without a part."""
    ops = ops_with_name_stacks(compiled("dp2_mp2")[1])
    coll = [(op, st) for op, st in ops
            if op.startswith(COLLECTIVE) and st]
    assert len(coll) >= 6
    assert all(parts.part_of(st) for _op, st in coll), coll
    in_layer = {(parts.part_of(st), parts.phase_of(st)) for _op, st in coll
                if "/while/body/closed_call/" in st and "pt.stack" in st}
    assert {("attn_proj", "forward"), ("mlp", "forward")} <= in_layer


def _canonical(text):
    """A compiled module's text without what a name stack reaches: the
    metadata, the tables of files and frames, and the instruction names
    (XLA derives ``%jvp_pt_head_...`` from the stack)."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n.*?\n\n", "", text)
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


@pytest.mark.parametrize("kind", list(STEPS))
def test_the_scopes_are_names_and_nothing_else(compiled, kind, monkeypatch):
    """With ``jax.named_scope`` a null context the step lowers to the same
    text letter for letter, and compiles to the same instructions."""
    lowered, text = compiled(kind)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()      # a jitted helper keeps the jaxpr it traced
    try:
        plain = lowered_step(kind)
        plain_lowered, plain_text = plain.as_text(), plain.compile().as_text()
    finally:
        jax.clear_caches()
    named = re.compile(r"pt\.(%s)\b" % "|".join(
        parts.PARTS + parts.STEP_PARTS))
    assert named.search(text) and not named.search(plain_text)
    assert lowered == plain_lowered
    assert _canonical(text) == _canonical(plain_text)


@pytest.mark.parametrize("stack,part,phase", [
    # forward: the wrapper is around the first scope the gradient meets
    ("jit(step)/jvp(pt.head)/jit(<unknown>)/while/body/closed_call/"
     "dot_general", "head", "forward"),
    ("jit(step)/jvp(pt.stack)/jit(<unknown>)/while/body/closed_call/pt.mlp/"
     "jit(<unknown>)/dot_general", "mlp", "forward"),
    ("jit(step)/jvp(pt.stack)/jit(<unknown>)/while/body/dynamic_slice",
     "stack", "forward"),
    # backward, and what the recompute replays inside it
    ("jit(step)/transpose(jvp(pt.stack))/jit(<unknown>)/while/body/"
     "closed_call/checkpoint/pt.attn_proj/pt.attention/custom_vjp_call",
     "attention", "backward"),
    ("jit(step)/transpose(jvp(pt.stack))/jit(<unknown>)/while/body/"
     "closed_call/checkpoint/rematted_computation/pt.norm/rsqrt", "norm",
     "recompute"),
    ("jit(step)/transpose(jvp(pt.embed))/jit(<unknown>)/scatter-add",
     "embed", "backward"),
    # a scope the gradient never meets first: ISSUE 55's experiment
    ("jit(f)/jvp()/while/body/closed_call/pt.mlp/dot_general", "mlp",
     "forward"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/pt.mlp/tanh", "mlp", "recompute"),
    # outside the gradient
    ("jit(step)/pt.optimizer/sqrt", "optimizer", None),
    ("jit(step)/mul", None, None),
    # a transpose or a checkpoint that is an OP, not a wrapper
    ("jit(pt_window1)/pt.attention/bhqk,bkhd->bqhd/transpose", "attention",
     None),
    ("jit(step)/jvp(pt.softmax)/exp", None, "forward"),
    ("", None, None)])
def test_part_and_phase_of_a_name_stack(stack, part, phase):
    assert parts.part_of(stack) == part
    assert parts.phase_of(stack) == phase


def test_a_serve_reader_hands_the_ten_parts_and_skips_a_step_part():
    stack = "jit(step)/jvp(pt.stack)/jit(run)/while/body/dynamic_slice"
    assert parts.part_of(stack, parts.PARTS) is None
    assert parts.part_of(stack.replace("dynamic_slice", "pt.norm/mul"),
                         parts.PARTS) == "norm"
    assert parts.STEP_PARTS == ("stack", "optimizer")
    assert not set(parts.STEP_PARTS) & set(parts.PARTS + parts.SUBPARTS)
    assert len(parts.PARTS) == 10
    with pytest.raises(ValueError, match="not a part of a train step"):
        parts.step_part("mlp")
    with pytest.raises(ValueError, match="not a part"):
        parts.part("stack")


def _ev(line, name, ts, dur, tf_op=None, prog=3):
    md = {"program_id": prog}
    if tf_op is not None:
        md["tf_op"] = tf_op
    return xplane.TraceEvent("/device:TPU:0", line, name, ts, dur, md)


def test_by_part_splits_a_train_step_by_phase_and_leaves_a_served_program():
    F = "jit(step)/jvp(pt.stack)/jit(run)/while/body/"
    B = "jit(step)/transpose(jvp(pt.stack))/jit(run)/while/body/"
    T = "bf16[4,8]"
    ops = [
        _ev("XLA Ops", f"%while.1 = ({T}) while()", 0, 100, F[:-6] + ":"),
        _ev("XLA Ops", f"%fusion.1 = {T} fusion()", 0, 30,
            F + "closed_call/pt.mlp/dot_general:"),
        _ev("XLA Ops", f"%copy.2 = {T} copy()", 30, 10),      # -> stack fwd
        _ev("XLA Ops", f"%fusion.3 = {T} fusion()", 40, 20,
            F + "dynamic_update_slice:"),
        # a fusion of two stacks: the first that names a part gives BOTH
        _ev("XLA Ops", f"%fusion.4 = {T} fusion()", 100, 40,
            "jit(step)/jvp(add):;" + B + "closed_call/checkpoint/"
            "rematted_computation/pt.mlp/mul:"),
        _ev("XLA Ops", f"%fusion.5 = {T} fusion()", 140, 60,
            B + "closed_call/checkpoint/pt.mlp/dot_general:"),
        # named, no part: unscoped in its own phase, it does not inherit
        _ev("XLA Ops", f"%fusion.6 = {T} fusion()", 200, 5,
            "jit(step)/jvp(mul):"),
        _ev("XLA Ops", f"%fusion.7 = {T} fusion()", 205, 45,
            "jit(step)/pt.optimizer/mul:"),
        # a served program in the same trace
        _ev("XLA Ops", f"%fusion.1 = {T} fusion()", 300, 20,
            "jit(pt_window1)/pt.attn_proj/dot_general:", prog=4),
        _ev("XLA Ops", f"%fusion.2 = {T} fusion()", 320, 10,
            "jit(pt_window1)/add:", prog=4)]
    modules = [_ev("XLA Modules", "jit_step(3)", 0, 250),
               _ev("XLA Modules", "jit_pt_window1(4)", 300, 30, prog=4)]
    bp = xplane.by_part(ops, xplane._self_us(ops), modules)
    step = bp["programs"]["jit_step"]
    assert step["parts"] == {"mlp": 130.0, "stack": 70.0, "optimizer": 45.0,
                             "unscoped": 5.0}
    assert step["phases"] == {
        "mlp": {"forward": 30.0, "recompute": 40.0, "backward": 60.0},
        "stack": {"forward": 70.0},       # the while's self time 40 + 10 + 20
        "unscoped": {"forward": 5.0}, "optimizer": {"none": 45.0}}
    assert step["top_ops"]["mlp"][0] == {
        "op": "fusion.5", "shape": T, "phase": "backward", "calls": 1,
        "us": 60.0}
    # a served program: no phase key, a name without a part inherits as ever
    window = bp["programs"]["jit_pt_window1"]
    assert window["parts"] == {"attn_proj": 30.0} and "phases" not in window
    assert "phase" not in window["top_ops"]["attn_proj"][0]
    assert bp["device_us"] == pytest.approx(280.0)
    # two chips add, phases included
    both = xplane._add_parts(xplane.by_part(
        ops, xplane._self_us(ops), modules), bp)
    assert both["programs"]["jit_step"]["phases"]["mlp"]["recompute"] == 80.0
    assert both["programs"]["jit_step"]["calls"] == 2
    assert both["programs"]["jit_step"]["top_ops"]["mlp"][0] == {
        "op": "fusion.5", "shape": T, "phase": "backward", "calls": 2,
        "us": 120.0}


def test_the_tool_prints_part_by_phase_for_a_train_step():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "program_parts", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "program_parts.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ops = [_ev("XLA Ops", "%fusion.1 = bf16[8] fusion()", 0, 3000,
               "jit(step)/jvp(pt.head)/dot_general:"),
           _ev("XLA Ops", "%fusion.2 = bf16[8] fusion()", 3000, 1000,
               "jit(step)/pt.optimizer/mul:")]
    text = tool.render(xplane.by_part(ops, [3000.0, 1000.0], [
        _ev("XLA Modules", "jit_step(3)", 0, 4000)]))
    rows = {}       # the table comes first; a part's own row follows it
    for ln in text.splitlines():
        if ln.startswith("  ") and not ln.startswith("   "):
            rows.setdefault(ln.split()[0], ln.split()[1:])
    assert rows["ms"][2:] == ["forward", "recompute", "backward", "none",
                              "all"]
    assert rows["head"][:5] == ["3.000", "0.000", "0.000", "0.000", "3.000"]
    assert rows["all"] == ["3.000", "0.000", "0.000", "1.000", "4.000"]
    assert "[forward]" in text and "[none]" in text
