"""On a mesh the data axes divide the step's work (ISSUE 29).

Read from the compiled text of a tiny Llama's ``ShardedTrainStep`` on the
virtual CPU mesh: no data replica gathers the global batch, the tensor-
parallel sums and every matmul — the fused linear+CE head included —
run on a replica's rows, and the losses are the one-device step's. Off the
mesh the programs' lowered text is the one the parent commit lowered.

Since ISSUE 56 the stream between sublayers lies sequence-sharded over
``mp`` and the scan body walks a replica's rows as two halves: a sum over
``mp`` is a reduce-scatter (the CPU backend writes it as an all-reduce and
a ``dynamic-slice``) on HALF a replica's rows, and each column-parallel
layer gathers its input.
"""
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.optimizer as opt
from paddle_tpu import jit
from paddle_tpu.distributed.mesh import compiled_collectives
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama import _fused_linear_ce

B, S, HIDDEN = 8, 32, 64
ACTIVATION = f"f32[{B // 2},{S},{HIDDEN}]"   # of one of two data replicas
HALF = f"f32[{B // 4},{S},{HIDDEN}]"         # of one of its two row groups
DATA_AXES = {"dp", "sdp"}


@pytest.fixture(autouse=True)
def clean_mesh():
    dist.reset_mesh()
    yield
    dist.reset_mesh()


def _tiny(ce_chunk, **over):
    # widths chosen so that no weight dim equals a row count asserted on
    cfg = LlamaConfig.tiny(
        hidden_size=HIDDEN, intermediate_size=192, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=320,
        max_position_embeddings=64, use_recompute=True, **over)
    cfg.ce_chunk = ce_chunk
    return cfg


def _step_and_batch(step_cls, ce_chunk=10 ** 6):
    paddle.seed(0)
    model = LlamaForCausalLM(_tiny(ce_chunk))
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          weight_decay=0.1)
    step = step_cls(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = np.random.default_rng(0).integers(0, 320, (B, S)).astype(np.int32)
    return step, paddle.to_tensor(ids)


def _dims(shape: str):
    return [int(d) for d in re.search(r"\[([\d,]*)\]", shape).group(1).split(",")
            if d]


def _dot_shapes(text: str):
    """Result and operand shapes of every ``dot`` in a compiled module."""
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])",
                               text, re.M))
    for m in re.finditer(r"= (\w+\[[\d,]*\])\S* dot\(([^)]*)\)", text):
        yield [m.group(1)] + [shape_of[n] for n in
                              re.findall(r"%[\w.\-]+", m.group(2))]


@pytest.fixture(scope="module")
def one_device_losses():
    dist.reset_mesh()
    step, x = _step_and_batch(jit.TrainStep)
    return [float(step(x, x)) for _ in range(2)]


def _mp_operands(rows, op, shape):
    """How many operands of ``shape`` the program's ``op``s over ``mp``
    carry (XLA's combiner decides how many instructions they ride)."""
    return sum(r["count"] * r["shapes"].count(shape) for r in rows
               if r["op"] == op and r["axes"] == ("mp",))


def _mp_crossings(rows):
    """(sums of half a replica's rows, of a whole replica's; gathers of
    half, of whole) over ``mp``."""
    return (_mp_operands(rows, "all-reduce", HALF),
            _mp_operands(rows, "all-reduce", ACTIVATION),
            _mp_operands(rows, "all-gather", HALF),
            _mp_operands(rows, "all-gather", ACTIVATION))


# What crosses ``mp`` in the sequence-sharded, two-halves form, a scan body
# counted once. Sums (reduce-scatters): forward o_proj and down_proj of each
# half (4), backward the input gradients of gate / up (2) and q / k / v (3)
# of each half (10) — the recompute keeps o_proj's sum (ISSUE 47) and never
# needs down_proj's; the embedding's sum lands whole (1). Gathers: in front
# of q / k / v and of gate / up, each half, forward (4) and replayed (4), and
# the cotangents of o_proj's and down_proj's outputs (4); the head's input
# and the embedding's cotangent whole (2).
CROSSINGS = (14, 1, 12, 2)


MESHES = {"dp2-mp2": dict(dp=2, mp=2), "sdp2-mp2": dict(sharding=2, mp=2),
          "dp2-cp2": dict(dp=2, cp=2)}


@pytest.mark.dist
@pytest.mark.parametrize("mesh,norms", [
    ("dp2-mp2", "reference"), ("sdp2-mp2", "reference"),
    ("dp2-cp2", "reference"),
    # the chip's path: the Pallas norms and RoPE (interpreted here), each a
    # manual region forward and another backward (ISSUE 31)
    ("dp2-mp2", "kernels"), ("sdp2-mp2", "kernels")])
def test_data_axes_divide_the_compiled_step(mesh, norms, one_device_losses,
                                            monkeypatch):
    if norms == "kernels":
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    env = dist.init_mesh(**MESHES[mesh], devices=jax.devices()[:4])
    step, x = _step_and_batch(dist.ShardedTrainStep)
    text = step.lower(x, x).compile().as_text()
    rows = compiled_collectives(text, env.mesh)   # what collectives() reads
    replica_rows = B * S // (2 * env.get_dim("cp"))

    gathers = [r for r in rows
               if r["op"] == "all-gather" and DATA_AXES & set(r["axes"])]
    assert not gathers, f"a data replica gathers over the data axes: {gathers}"

    mp_reduced = [_dims(s) for r in rows
                  if r["op"] == "all-reduce" and r["axes"] == ("mp",)
                  for s in r["shapes"]]
    too_many = [d for d in mp_reduced if len(d) > 1
                and math.prod(d[:-1]) > replica_rows]
    assert not too_many, f"mp all-reduces on more than a replica's rows: {too_many}"
    if env.get_dim("mp") > 1:
        # a replica's activation crosses mp where the math sums it and where
        # a column-parallel layer needs its rows whole, and nowhere else —
        # whichever implementation the norms take. A custom_vjp INSIDE a
        # check_vma=False shard_map cost three more sums, named psum: JAX's
        # transpose adding equal copies of dx together
        assert _mp_crossings(rows) == CROSSINGS, rows
        assert not re.findall(rf"%psum[\w.\-]* = (?:{re.escape(ACTIVATION)}"
                              rf"|{re.escape(HALF)})\S* all-reduce", text)
    # both replicas computing identical gradients need no reduction over the
    # data axes; dividing the batch does
    assert any(r["op"] in ("all-reduce", "reduce-scatter")
               and DATA_AXES & set(r["axes"])
               and any(len(_dims(s)) == 2 for s in r["shapes"]) for r in rows)

    dots = list(_dot_shapes(text))
    assert dots
    global_rows = {B * S, B * (S - 1)}
    repeated = [d for d in dots for s in map(_dims, d)
                if global_rows & set(s) or (len(s) > 2 and s[0] == B)]
    assert not repeated, f"a matmul on the global batch's rows: {repeated}"

    losses = [float(step(x, x)) for _ in range(2)]
    np.testing.assert_allclose(losses, one_device_losses, rtol=1e-5)


# -- the recompute keeps what crossed mp (ISSUE 47) ---------------------------

def _plain_checkpoint(monkeypatch):
    """The layer scan's recompute as it was: ``jax.checkpoint``'s own policy."""
    from paddle_tpu.distributed.meta_parallel import stage_stack

    monkeypatch.setattr(stage_stack, "remat_wrap",
                        lambda fn, keep=(): jax.checkpoint(fn))


def _traced(step, x):
    from paddle_tpu.jit import _batch_arrays, step_args

    arrays = _batch_arrays((x, x))
    step._ensure_built(arrays)
    fn = step._jitted
    while not hasattr(fn, "trace"):
        fn = fn.__wrapped__
    return fn.trace(*step_args(step, arrays, jax.random.key(0))).jaxpr


def _traced_text(step, x):
    """The step's jaxpr as text: ``checkpoint_name`` shows as ``name=``."""
    return str(_traced(step, x))


@pytest.mark.dist
@pytest.mark.parametrize("mesh", ["dp2-mp2", "sdp2-mp2"])
def test_recompute_keeps_what_crossed_mp(mesh, monkeypatch):
    """o_proj's sum over ``mp`` is not sent again in the recompute, under any
    policy, and nothing else about the step changes: its losses are
    ``jax.checkpoint``'s plain policy's, bit for bit."""
    def losses_and_rows(policy="", steps=3):
        paddle.set_flags({"FLAGS_remat_policy": policy})
        try:
            dist.reset_mesh()
            dist.init_mesh(**MESHES[mesh], devices=jax.devices()[:4])
            step, x = _step_and_batch(dist.ShardedTrainStep)
            rows = step.collectives(x, x)
            return ([float(step(x, x)) for _ in range(steps)], rows,
                    _traced_text(step, x))
        finally:
            paddle.set_flags({"FLAGS_remat_policy": ""})

    kept, rows, traced = losses_and_rows()
    assert "name=mp_out" in traced
    assert _mp_crossings(rows) == CROSSINGS, rows
    for policy in ("dots", "flash"):
        _, rows, _ = losses_and_rows(policy, steps=0)
        assert _mp_crossings(rows)[:2] == CROSSINGS[:2], (policy, rows)

    _plain_checkpoint(monkeypatch)
    plain, rows, _ = losses_and_rows()
    # o_proj's sum of each half, sent again in the replay
    assert _mp_crossings(rows)[0] == CROSSINGS[0] + 2, rows
    assert kept == plain


@pytest.mark.dist
def test_no_mp_no_name_and_the_same_collectives(monkeypatch):
    """The mesh decides: with no ``mp`` axis nothing carries the name, the
    policy keeps nothing, and the compiled collectives are the plain
    recompute's."""
    dist.init_mesh(**MESHES["dp2-cp2"], devices=jax.devices()[:4])
    step, x = _step_and_batch(dist.ShardedTrainStep)
    assert "mp_out" not in _traced_text(step, x)
    rows = step.collectives(x, x)

    _plain_checkpoint(monkeypatch)
    dist.reset_mesh()
    dist.init_mesh(**MESHES["dp2-cp2"], devices=jax.devices()[:4])
    step, x = _step_and_batch(dist.ShardedTrainStep)
    assert step.collectives(x, x) == rows


@pytest.mark.dist
def test_head_chunks_stay_on_a_replica():
    """With several chunks a replica, the scan walks chunks of a replica's
    rows: its matmul has chunk rows and it runs rows ÷ (data degree x chunk)
    times, not once a chunk of the global batch."""
    dist.init_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    step, x = _step_and_batch(dist.ShardedTrainStep, ce_chunk=62)
    assert not [r for r in step.collectives(x, x) if r["op"] == "all-gather"
                and DATA_AXES & set(r["axes"])]
    text = step.lower(x, x).compile().as_text()
    head = [d for d in _dot_shapes(text) if _dims(d[0]) == [62, 160]]
    assert head, "the head's chunk matmul [62 rows, vocab / mp] is not there"
    # a replica's 4 x 31 rows are two chunks of 62; the parent's scan walked
    # the global batch's four
    assert 'known_trip_count":{"n":"2"}' in text
    assert 'known_trip_count":{"n":"4"}' not in text


@pytest.mark.dist
def test_pp_manual_region_keeps_unconstrained_leading_dims(one_device_losses):
    """Inside the pipeline's manual region ``constrain_spec`` strips ``pp``
    from a spec and must hand ``UNCONSTRAINED`` through untouched."""
    dist.init_mesh(pp=2, dp=2, mp=2)
    step, x = _step_and_batch(dist.ShardedTrainStep)
    losses = [float(step(x, x)) for _ in range(2)]
    np.testing.assert_allclose(losses, one_device_losses, rtol=1e-5)


# -- the sequence-sharded stream and the two row groups (ISSUE 56) -------------

def _replicated_rows(monkeypatch):
    """The form before ISSUE 56: between sublayers the sequence is whole on
    every ``mp`` shard (``"rows"`` read as ``"gathered"``) and a replica's
    rows go through the layer whole."""
    from paddle_tpu.distributed import mesh
    from paddle_tpu.models import llama

    spec = mesh.activation_spec

    def whole_sequence(shape, layout):
        return spec(shape, "gathered" if layout == "rows" else layout)

    monkeypatch.setattr(mesh, "activation_spec", whole_sequence)
    monkeypatch.setattr(llama, "activation_spec", whole_sequence)
    _whole_rows(monkeypatch)


def _whole_rows(monkeypatch):
    """The scan body as it was: a replica's rows go through the layer whole."""
    from paddle_tpu.distributed.meta_parallel import stage_stack

    monkeypatch.setattr(stage_stack, "_row_groups", lambda carry: None)


def _mesh_step(mesh, batch=B, seq=S, optimizer=None):
    dist.reset_mesh()
    dist.init_mesh(**MESHES[mesh], devices=jax.devices()[:4])
    paddle.seed(0)
    model = LlamaForCausalLM(_tiny(10 ** 6))
    optimizer = (optimizer or (lambda ps: opt.AdamW(
        learning_rate=3e-4, parameters=ps, weight_decay=0.1)))(
        model.parameters())
    step = dist.ShardedTrainStep(model, lambda m, x, y: m(x, labels=y),
                                 optimizer)
    ids = np.random.default_rng(0).integers(0, 320, (batch, seq))
    return step, paddle.to_tensor(ids.astype(np.int32)), model


@pytest.mark.dist
@pytest.mark.parametrize("mesh", ["dp2-mp2", "sdp2-mp2"])
def test_losses_are_the_replicated_rows_forms(mesh, monkeypatch):
    """Three optimizer steps: the sequence-sharded layout alone adds the same
    two partial sums an all-reduce added, and the two halves reorder one sum
    (a weight's gradient is dW(half 0) + dW(half 1))."""
    def losses():
        step, x, _ = _mesh_step(mesh)
        return [float(step(x, x)) for _ in range(3)]

    new = losses()
    with monkeypatch.context() as m:
        _whole_rows(m)
        layout_alone = losses()
    with monkeypatch.context() as m:
        _replicated_rows(m)
        parent = losses()
    np.testing.assert_allclose(layout_alone, parent, rtol=2e-7)
    np.testing.assert_allclose(new, parent, rtol=1e-6)


@pytest.mark.dist
@pytest.mark.parametrize("mesh", ["dp2-mp2", "sdp2-mp2"])
def test_halved_body_gradients_are_the_unsplit_ones(mesh, monkeypatch):
    """One SGD step at learning rate 1 moves a weight by its gradient: the
    halved body's are the unsplit body's within float32's rounding of one
    more add (a bf16 model's within bf16's)."""
    def gradients():
        step, x, model = _mesh_step(
            mesh, optimizer=lambda ps: opt.SGD(learning_rate=1.0,
                                               parameters=ps))
        before = {n: np.asarray(p.data) for n, p in model.named_parameters()}
        step(x, x)
        return {n: before[n] - np.asarray(p.data)
                for n, p in model.named_parameters()}

    halved = gradients()
    with monkeypatch.context() as m:
        _whole_rows(m)
        unsplit = gradients()
    assert halved.keys() == unsplit.keys()
    for name, g in unsplit.items():
        scale = np.abs(g).max()
        assert scale > 0, name
        np.testing.assert_allclose(halved[name], g, rtol=0,
                                   atol=4e-6 * scale, err_msg=name)


@pytest.mark.dist
def test_a_layer_with_a_router_sees_its_rows_whole(monkeypatch):
    """An expert layer reports an auxiliary loss: its capacity and its
    balance couple the rows, so the body runs it on the whole carry — the
    losses are the unsplit body's bit for bit."""
    from paddle_tpu.models.llama import LlamaMoEConfig

    def losses():
        dist.reset_mesh()
        dist.init_mesh(dp=2, mp=2, devices=jax.devices()[:4])
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaMoEConfig.tiny(
            hidden_size=HIDDEN, intermediate_size=96, vocab_size=320,
            max_position_embeddings=64, use_recompute=True))
        step = dist.ShardedTrainStep(
            model, lambda m, x, y: m(x, labels=y),
            opt.AdamW(learning_rate=3e-4, parameters=model.parameters()))
        ids = np.random.default_rng(0).integers(0, 320, (B, S))
        x = paddle.to_tensor(ids.astype(np.int32))
        return [float(step(x, x)) for _ in range(2)]

    routed = losses()
    _whole_rows(monkeypatch)
    assert losses() == routed


@pytest.mark.dist
def test_the_ladder_counts_both_halves_kept_values(monkeypatch):
    """Each half names its q / k / v / up / gate: what ``jit/remat_fit.py``
    prices a rung by is their sum, the unsplit body's bytes."""
    from paddle_tpu.jit import remat_fit

    def named():
        step, x, _ = _mesh_step("dp2-mp2")
        return remat_fit.named_bytes(_traced(step, x).jaxpr, devices=4)

    halved = named()
    assert halved and "attn_o" not in halved   # o_proj's sum is mp's to name
    _whole_rows(monkeypatch)
    assert named() == halved


@pytest.mark.dist
@pytest.mark.parametrize("case,mesh,batch,seq,parent_form", [
    # three rows a replica: the layout stays, the rows go through whole
    ("odd-rows", "dp2-mp2", 6, S, "whole_rows"),
    # mp does not divide the sequence: neither part engages
    ("odd-seq", "dp2-mp2", B, S - 1, "replicated"),
    # no mp axis: the cp layout and its collectives are the parent's
    ("no-mp", "dp2-cp2", B, S, "replicated")])
def test_falls_back_to_the_parents_text(case, mesh, batch, seq, parent_form,
                                        monkeypatch):
    def lowered():
        step, x, _ = _mesh_step(mesh, batch, seq)
        return step.lower(x, x).as_text()

    new = lowered()
    with monkeypatch.context() as m:
        (_whole_rows if parent_form == "whole_rows" else _replicated_rows)(m)
        assert lowered() == new
    if case == "odd-rows":   # and the layout did engage
        with monkeypatch.context() as m:
            _replicated_rows(m)
            assert lowered() != new


# What the TPU compiler writes for a reduce-scatter and for an asynchronous
# all-gather (the forms of a step compiled for a described ``v5e:2x2``, cut
# to their headers): neither has an opcode of its own
TPU_FORMS = """HloModule jit_step

%all-reduce-scatter.1.clone (input.1: bf16[4,2048,2048]) -> bf16[4,1024,2048] {
  %input.1 = bf16[4,2048,2048]{2,1,0} parameter(0)
  %all-reduce.61 = bf16[4,2048,2048]{2,1,0} all-reduce(%input.1), channel_id=79, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add.9
  ROOT %dynamic-slice.3 = bf16[4,1024,2048]{2,1,0} dynamic-slice(%all-reduce.61, %c, %m, %c), dynamic_slice_sizes={4,1024,2048}
}

%fused_computation.341 (param_0.1: bf16[2,1024,2048]) -> (bf16[2,1024,2048], bf16[2,2048,2048], s32[2]) {
  %all-gather.57 = bf16[2,2048,2048]{1,2,0} all-gather(%param_0.1), channel_id=43, replica_groups=[2,2]<=[4], dimensions={1}, use_global_device_ids=true
  ROOT %custom-call.26 = (bf16[2,1024,2048]{1,2,0}, bf16[2,2048,2048]{1,2,0}, s32[2]{0}) custom-call(%all-gather.57), custom_call_target="AllGatherStart"
}

%async_collective_fusion.385 (param_0.2: bf16[2,1024,2048], param_1.2: bf16[2048,4096]) -> (bf16[2,2048,4096], bf16[2,2048,2048]) {
  %all-gather.59 = bf16[2,2048,2048]{1,2,0} all-gather(%param_0.2), channel_id=43, replica_groups=[2,2]<=[4], dimensions={1}, use_global_device_ids=true
  ROOT %tuple.308 = (bf16[2,2048,4096]{2,1,0}, bf16[2,2048,2048]{1,2,0}) tuple(%convolution.1, %all-gather.59)
}

%fused_computation.344 (param_0.3: bf16[2,1024,2048]) -> bf16[2,2048,2048] {
  %all-gather.67 = bf16[2,2048,2048]{1,2,0} all-gather(%param_0.3), channel_id=43, replica_groups=[2,2]<=[4], dimensions={1}, use_global_device_ids=true
  ROOT %custom-call.28 = bf16[2,2048,2048]{1,2,0} custom-call(%param_0.3, %all-gather.67), custom_call_target="AllGatherDone"
}

%body.1 (wide.param.1: (s32[], bf16[4,1024,2048])) -> (s32[], bf16[4,1024,2048]) {
  %async-collective-start = (bf16[2,1024,2048]{1,2,0}, bf16[2,2048,2048]{1,2,0}, s32[2]{0}) fusion(%copy.211), kind=kCustom, calls=%fused_computation.341
  %fusion.385 = (bf16[2,2048,4096]{2,1,0}, bf16[2,2048,2048]{1,2,0}) fusion(%gte.1, %gte.2), kind=kOutput, calls=%async_collective_fusion.385
  %async-collective-done = bf16[2,2048,2048]{1,2,0} fusion(%gte.3), kind=kCustom, calls=%fused_computation.344
  %fusion.342 = bf16[4,1024,2048]{2,1,0} fusion(%gte.4), kind=kCustom, calls=%all-reduce-scatter.1.clone
  %all-gather.112 = bf16[2,2048,2048]{2,1,0} all-gather(%bitcast.684), channel_id=2, replica_groups=[2,2]<=[4], dimensions={1}, use_global_device_ids=true
  ROOT %all-reduce.63 = (bf16[1,2048,1024]{2,1,0}, bf16[2048]{0}) all-reduce(%dw.1, %dw.2), channel_id=76, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add.6
}
"""


def test_collectives_reads_the_tpu_compilers_forms():
    """``compiled_collectives`` on the TPU's text: an ``all-reduce-scatter``
    fusion is ONE synchronous reduce-scatter of the fusion's result, an
    ``async-collective-start`` / ``-done`` pair ONE asynchronous all-gather
    (the fusions that carry it on repeat the instruction), and what stands
    in a body under its own opcode is synchronous."""
    env = dist.init_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    assert compiled_collectives(TPU_FORMS, env.mesh) == [
        {"axes": ("dp",), "op": "all-reduce",
         "shapes": ("bf16[1,2048,1024]", "bf16[2048]"), "count": 1,
         "async": 0},
        {"axes": ("mp",), "op": "all-gather",
         "shapes": ("bf16[2,2048,2048]",), "count": 2, "async": 1},
        {"axes": ("mp",), "op": "reduce-scatter",
         "shapes": ("bf16[4,1024,2048]",), "count": 1, "async": 0}]


@pytest.mark.dist
def test_collectives_on_the_cpu_say_the_kinds_alone():
    """The CPU backend writes no pair: every row of ``collectives()`` reads
    ``async`` 0 there, and a reduce-scatter stays an all-reduce."""
    dist.init_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    step, x = _step_and_batch(dist.ShardedTrainStep)
    rows = step.collectives(x, x)
    assert rows and all(r["async"] == 0 for r in rows)
    assert {r["op"] for r in rows} == {"all-reduce", "all-gather"}


# sha256 of the sharded step's lowered text on meshes WITHOUT ``mp``, taken
# by this very function on a checkout of ISSUE 56's parent (b2370ae): where
# no ``mp`` splits the stream, ``"rows"`` is the layout it was and the body
# walks whole rows — not a line of the lowered text moved
PARENT_MESH_STEP_SHA256 = {
    "dp2-cp2": (dict(dp=2, cp=2), "0339f145fe2c759af3612d712cb1694e"
                                  "3e39004e57da92a4cf6585d288c8c32b"),
    "dp2": (dict(dp=2), "b5ba3908c243d594348be0d9795f6e26"
                        "164f1a440e8989ab75a32eba73b92b83"),
    "sdp2-cp2": (dict(sharding=2, cp=2), "65fcacd76c1f174321773f259ff23f7d"
                                         "e4ad18091cfdb346caafa73c882689a8"),
    "pp2-dp2": (dict(pp=2, dp=2), "e9543ffadd5260260cbe214b9bed5aab"
                                  "061b025155050b14bd3c94a8da009dae"),
}


@pytest.mark.dist
@pytest.mark.parametrize("mesh", list(PARENT_MESH_STEP_SHA256))
def test_mesh_without_mp_lowers_to_the_parents_text(mesh):
    degrees, parent = PARENT_MESH_STEP_SHA256[mesh]
    dist.init_mesh(**degrees,
                   devices=jax.devices()[:math.prod(degrees.values())])
    step, x = _step_and_batch(dist.ShardedTrainStep)
    text = step.lower(x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == parent


def _env_of(**degrees):
    n = math.prod(degrees.values())
    dist.reset_mesh()
    return dist.init_mesh(**degrees, devices=jax.devices()[:n]) if n > 1 \
        else None


@pytest.mark.parametrize("degrees,shape,halved", [
    (dict(dp=2, mp=2), (8, 32, 64), True),
    (dict(sharding=2, mp=2), (8, 32, 64), True),
    (dict(dp=2, sharding=2, mp=2), (8, 32, 64), True),
    (dict(mp=2), (2, 32, 64), True),
    # with cp the same form: seq over (cp, mp), two halves
    (dict(cp=2, mp=2), (2, 32, 64), True),
    (dict(dp=2, mp=2), (6, 32, 64), False),    # three rows a replica
    (dict(dp=2, mp=2), (2, 32, 64), False),    # one row a replica
    (dict(dp=2, mp=2), (8, 31, 64), False),    # mp does not divide the seq
    (dict(dp=2, cp=2), (8, 32, 64), False),    # no mp
    # the pipeline keeps today's body: a stage walks microbatches already
    (dict(pp=2, dp=2, mp=2), (8, 32, 64), False),
    (dict(dp=2, mp=2), (8, 64), False),        # not [batch, seq, hidden]
    (dict(), (8, 32, 64), False)])             # no mesh
def test_row_groups_are_chosen_by_the_mesh_and_the_shape(degrees, shape,
                                                         halved):
    from paddle_tpu.distributed.meta_parallel import stage_stack

    _env_of(**degrees)
    carry = np.arange(math.prod(shape), dtype=np.float32).reshape(shape)
    groups = stage_stack._row_groups(jnp.asarray(carry))
    assert (groups is not None) == halved
    if not halved:
        return
    # each half holds half the rows of EVERY data replica, in order: no row
    # changes device; the join puts them back
    d = degrees.get("dp", 1) * degrees.get("sharding", 1)
    per = shape[0] // d
    for i, half in enumerate(groups):
        want = np.concatenate([carry[j * per + i * per // 2:
                                     j * per + (i + 1) * per // 2]
                               for j in range(d)])
        np.testing.assert_array_equal(half, want)
    np.testing.assert_array_equal(stage_stack._join_rows(groups, shape),
                                  carry)


# -- off the mesh nothing moved: the lowered text is the parent commit's ------

def _parent_fused_linear_ce(hidden2d, w, labels1d, *, chunk, ignore_index):
    """``models/llama.py:_fused_linear_ce`` as it stood before ISSUE 29."""
    n = hidden2d.shape[0]
    n_chunks = max(n // chunk, 1)
    c = -(-n // n_chunks)
    pad = n_chunks * c - n
    if pad:
        hidden2d = jnp.pad(hidden2d, ((0, pad), (0, 0)))
        labels1d = jnp.pad(labels1d, (0, pad), constant_values=ignore_index)
    h3 = hidden2d.reshape(n_chunks, c, hidden2d.shape[1])
    l2 = labels1d.reshape(n_chunks, c)

    def body(acc, xs):
        h, lab = xs
        logits = jnp.matmul(h, w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        mask = lab != ignore_index
        safe = jnp.where(mask, lab, 0).astype(jnp.int32)
        picked = jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
        loss_sum = -jnp.sum(jnp.where(mask, picked, 0.0))
        cnt = jnp.sum(mask)
        return (acc[0] + loss_sum, acc[1] + cnt), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (h3, l2))
    return total / jnp.maximum(count, 1)


def _stablehlo(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return re.sub(r"@\w+", "@f", text)   # function names carry Python names


@pytest.mark.parametrize("rows,chunk", [(248, 62), (250, 62), (100, 2048)],
                         ids=["even", "padded", "one-chunk"])
def test_head_off_mesh_lowers_to_the_parents_text(rows, chunk):
    h = jax.ShapeDtypeStruct((rows, HIDDEN), jnp.float32)
    w = jax.ShapeDtypeStruct((HIDDEN, 320), jnp.float32)
    lab = jax.ShapeDtypeStruct((rows,), jnp.int32)
    kw = dict(chunk=chunk, ignore_index=-100)
    new = _stablehlo(lambda *a: _fused_linear_ce.fn(*a, groups=1, **kw),
                     h, w, lab)
    old = _stablehlo(lambda *a: _parent_fused_linear_ce(*a, **kw), h, w, lab)
    assert new == old


def test_head_groups_are_the_same_loss():
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((248, HIDDEN)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((HIDDEN, 320)) * 0.1, jnp.float32)
    lab = jnp.asarray(rng.integers(0, 320, 248), jnp.int32).at[::7].set(-100)
    kw = dict(chunk=50, ignore_index=-100)
    whole = _parent_fused_linear_ce(h, w, lab, **kw)
    for groups in (2, 4, 3):   # 3 does not divide 248: falls back to one run
        np.testing.assert_allclose(
            _fused_linear_ce.fn(h, w, lab, groups=groups, **kw), whole,
            rtol=1e-6)


# sha256 of the one-device train step's lowered text, taken by this very
# function. PR 29 took it on a checkout of its parent (7572d4e): the mesh
# work left the one-device program alone. PR 30 took it again on its own
# tree: the decoder layer's residual add and post-attention norm became one
# op on every backend (another text; the step's losses stayed bit-equal to
# the parent's over three optimizer steps, CHANGES.md PR 30). PR 54 took it a
# third time: the layer's projection outputs carry ``checkpoint_name``s,
# which lower to nothing but take numbers, so the private functions behind
# them are numbered five higher (``@"<unknown>_95"`` for ``_90``) and the
# text with those numbers taken out is the parent's (ee53fad), to the letter
PARENT_TRAIN_STEP_SHA256 = (
    "5277303b5b2a659a615eb7afcca07b0b03b205912f27fa00a908cfde57f0b98d")


def _train_step_digest():
    step, x = _step_and_batch(jit.TrainStep, ce_chunk=62)
    text = step.lower(x, x).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_train_step_off_mesh_lowers_to_the_parents_text():
    assert _train_step_digest() == PARENT_TRAIN_STEP_SHA256
