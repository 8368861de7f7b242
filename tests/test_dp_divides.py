"""On a mesh the data axes divide the step's work (ISSUE 29).

Read from the compiled text of a tiny Llama's ``ShardedTrainStep`` on the
virtual CPU mesh: no data replica gathers the global batch, the tensor-
parallel all-reduces and every matmul — the fused linear+CE head included —
run on a replica's rows, and the losses are the one-device step's. Off the
mesh the programs' lowered text is the one the parent commit lowered.
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


def _mp_activation_reduces(rows):
    """(operands, count) of each ``mp`` all-reduce that carries a replica's
    activation."""
    return {(len(r["shapes"]), r["count"]) for r in rows
            if r["op"] == "all-reduce" and r["axes"] == ("mp",)
            and ACTIVATION in r["shapes"]}


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
        # a replica's activation crosses mp where the math sums it (the
        # embedding, o_proj and down_proj, the column-parallel layers'
        # input gradients) and nowhere else — the recompute keeps o_proj's
        # sum (ISSUE 47) — whichever implementation the norms take: three
        # lone instructions and two XLA combined, eight operands. A
        # custom_vjp INSIDE a check_vma=False shard_map cost three more,
        # named psum: JAX's transpose adding equal copies of dx together
        assert _mp_activation_reduces(rows) == {(1, 3), (2, 1), (3, 1)}, rows
        assert not re.findall(rf"%psum[\w.\-]* = {re.escape(ACTIVATION)}\S* "
                              r"all-reduce", text)
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


def _traced_text(step, x):
    """The step's jaxpr as text: ``checkpoint_name`` shows as ``name=``."""
    from paddle_tpu.jit import _batch_arrays, step_args

    arrays = _batch_arrays((x, x))
    step._ensure_built(arrays)
    fn = step._jitted
    while not hasattr(fn, "trace"):
        fn = fn.__wrapped__
    return str(fn.trace(*step_args(step, arrays, jax.random.key(0))).jaxpr)


@pytest.mark.dist
@pytest.mark.parametrize("mesh", ["dp2-mp2", "sdp2-mp2"])
def test_recompute_keeps_what_crossed_mp(mesh, monkeypatch):
    """o_proj's all-reduce is not sent again in the recompute, under any
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
    assert _mp_activation_reduces(rows) == {(1, 3), (2, 1), (3, 1)}, rows
    for policy in ("dots", "flash"):
        _, rows, _ = losses_and_rows(policy, steps=0)
        assert (1, 3) in _mp_activation_reduces(rows), (policy, rows)

    _plain_checkpoint(monkeypatch)
    plain, rows, _ = losses_and_rows()
    assert (1, 4) in _mp_activation_reduces(rows), rows
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
    assert not [r for r in step.collectives(x, x) if r["op"] == "all-gather"]
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
