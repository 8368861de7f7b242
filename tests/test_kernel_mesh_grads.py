"""A kernel's backward on the mesh (ISSUE 31): forward and backward are each a
manual region of their own, written by the op, so the gradients are the
one-device gradients and the only reduction is the one the math has — a
norm's ``dw`` over the axes that split its rows.

Every mesh the seam serves, the kernels interpreted, against the jnp
reference on one device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu.distributed.mesh import activation_spec, compiled_collectives
from paddle_tpu.kernels import registry
from paddle_tpu.models.llama import _rope
from paddle_tpu.nn.functional.common import _rms_norm, _rms_norm_residual

B, S, H = 4, 16, 64
HEADS, HEAD_DIM = 2, 32     # mp=4 does not divide the heads: left unsplit
MESHES = {"dp2-mp2": dict(dp=2, mp=2), "sdp2-mp2": dict(sharding=2, mp=2),
          "dp2-cp2": dict(dp=2, cp=2), "mp4": dict(mp=4)}


@pytest.fixture(autouse=True)
def clean_mesh():
    dist.reset_mesh()
    yield
    dist.reset_mesh()


def _rand(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _norm_loss(impl):
    def loss(x, w, cy):
        return jnp.sum(_rms_norm.fn(x, w, eps=1e-6, impl=impl) * cy)
    return loss, (_rand(0, B, S, H), 1 + 0.1 * _rand(1, H), _rand(2, B, S, H))


def _norm_residual_loss(impl):
    def loss(x, r, w, cy, cs):
        y, s = _rms_norm_residual.fn(x, r, w, eps=1e-6, impl=impl)
        return jnp.sum(y * cy) + jnp.sum(s * cs)
    return loss, (_rand(0, B, S, H), _rand(3, B, S, H), 1 + 0.1 * _rand(1, H),
                  _rand(2, B, S, H), _rand(4, B, S, H))


def _rope_loss(impl):
    def loss(x, cy):
        return jnp.sum(_rope.fn(x, theta=1e4, pos_offset=0, impl=impl) * cy)
    return loss, (_rand(5, B, S, HEADS, HEAD_DIM),
                  _rand(6, B, S, HEADS, HEAD_DIM))


# op -> (loss and operands, operands differentiated, registry name, layout)
OPS = {"rms_norm": (_norm_loss, (0, 1), "rms_norm", "rows"),
       "rms_norm_residual": (_norm_residual_loss, (0, 1, 2), "rms_norm",
                             "rows"),
       "rope": (_rope_loss, (0,), "rope", "bshd")}


@pytest.mark.dist
@pytest.mark.parametrize("mesh", list(MESHES))
@pytest.mark.parametrize("op", list(OPS))
def test_mesh_gradients_are_the_one_device_gradients(op, mesh, monkeypatch):
    make, argnums, name, layout = OPS[op]
    loss, args = make("reference")
    want = jax.grad(loss, argnums)(*args)       # no mesh, plain jnp

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    env = dist.init_mesh(**MESHES[mesh], devices=jax.devices()[:4])
    impl = registry.resolve(name)
    # RoPE needs global positions: on a sequence-split mesh the seam hands
    # back the reference, which GSPMD partitions
    assert impl == ("reference" if (op, mesh) == ("rope", "dp2-cp2")
                    else "interpret")
    loss, args = make(impl)
    # operands arrive and gradients leave laid out as the layers hold them,
    # so the program's collectives are the op's own and no resharding's
    held = [env.sharding_for(activation_spec(a.shape, layout) if a.ndim > 1
                             else P()) for a in args]
    compiled = jax.jit(jax.grad(loss, argnums), in_shardings=held,
                       out_shardings=tuple(held[i] for i in argnums)
                       ).lower(*args).compile()
    for g, ref in zip(compiled(*args), want):
        np.testing.assert_allclose(g, ref, rtol=2e-5, atol=2e-5)

    if impl == "interpret":
        # nothing activation-shaped is reduced: what a shard computed for its
        # rows is the gradient of its rows
        rows = compiled_collectives(compiled.as_text(), env.mesh)
        reduced = [s for r in rows if r["op"] == "all-reduce"
                   for s in r["shapes"] if s.count(",") >= 2]
        assert not reduced, reduced


@pytest.mark.dist
@pytest.mark.parametrize("mesh,seq,over", [
    ("dp2-mp2", S, ("dp", "mp")), ("sdp2-mp2", S, ("sdp", "mp")),
    ("mp4", S, ("mp",)),
    # mp does not divide the sequence: the rows stay whole over mp and each
    # mp shard computes the same dw
    ("dp2-mp2", S - 1, ("dp",))])
@pytest.mark.parametrize("op", ["rms_norm", "rms_norm_residual"])
def test_norm_dw_sums_over_mp_where_mp_splits_the_rows(op, mesh, seq, over,
                                                       monkeypatch):
    """Between sublayers the rows lie sequence-sharded over ``mp``
    (ISSUE 56): a norm kernel then sees 1/mp of the sequence, and the seam
    sums its ``dw`` — ``[hidden]`` floats — over ``mp`` besides the data
    axes, by its own rule (the axes that split another operand)."""
    make, argnums, name, layout = OPS[op]
    loss, args = make("reference")
    args = tuple(a[:, :seq] if a.ndim == 3 else a for a in args)
    want = jax.grad(loss, argnums)(*args)

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    env = dist.init_mesh(**MESHES[mesh], devices=jax.devices()[:4])
    spec = activation_spec(args[0].shape, layout)
    assert ("mp" in str(spec[1])) == ("mp" in over), spec
    loss, _ = make(registry.resolve(name))
    held = [env.sharding_for(spec if a.ndim > 1 else P()) for a in args]
    compiled = jax.jit(jax.grad(loss, argnums), in_shardings=held,
                       out_shardings=tuple(held[i] for i in argnums)
                       ).lower(*args).compile()
    for g, ref in zip(compiled(*args), want):
        np.testing.assert_allclose(g, ref, rtol=2e-5, atol=2e-5)
    rows = compiled_collectives(compiled.as_text(), env.mesh)
    assert [(r["axes"], r["op"], r["shapes"]) for r in rows] == \
        [(over, "all-reduce", (f"f32[{H}]",))], rows
