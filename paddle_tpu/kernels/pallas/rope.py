"""Fused rotate-half RoPE — Pallas kernel (fwd + VJP).

The reference form materializes cos/sin tables, splits the activation,
and concatenates — several elementwise HLOs over the full [b, s, h, d]
q/k tensors. The fused kernel streams each sequence block once and
computes the angles in-register from the block's global positions (no
cos/sin tables in HBM at all).

The VJP needs no residuals: a rotation is orthogonal, so the backward is
the same kernel with the angle negated (``inverse=True``) applied to the
cotangent — RoPE becomes memory-traffic-free to differentiate.

``pos_offset`` shifts the global positions (decode-cache append and the
context-parallel rank offset ride this, matching ``models/llama.py``'s
``rope_apply`` contract). The reference is the plain forward in jnp,
differentiated by JAX. Parity is pinned by tests/test_pallas_kernels.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve
from ._common import differentiable, pick_rows

__all__ = ["rope_apply", "rope_halves"]


# VMEM the kernel may plan for per grid step (v5e scopes 16 MiB): the
# in/out blocks are double-buffered in x.dtype and the body holds ~4 f32
# temporaries of the block, so the sequence block is sized from
# heads x head_dim, not from the sequence alone (a 512-row block at
# 16 x 128 asked for 24.4 MiB and was refused by the chip's compiler)
_VMEM_BUDGET = 6 << 20


def _pick_seq_block(s: int, h: int, d: int, itemsize: int) -> int:
    per_row = h * d * (4 * itemsize + 4 * 4)
    return pick_rows(s, max(8, min(512, _VMEM_BUDGET // per_row)))


def _angles(bs: int, d: int, theta: float, base_pos):
    """cos and sign-folded sin, [bs, 1, d], for positions base_pos +
    [0..bs) — computed in-register from INTEGER iotas cast to f32 (the
    TPU iota op yields integers only); no table input. Both halves of the
    lane axis carry the same angle; sin is negated on the first half so
    ``x*cos + swap_halves(x)*sin`` is the rotate-half rotation."""
    half = d // 2
    pos = (base_pos + jax.lax.broadcasted_iota(jnp.int32, (bs, 1, d), 0)
           ).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bs, 1, d), 2)
    upper = lane >= half
    idx = jnp.where(upper, lane - half, lane).astype(jnp.float32)
    # inv_freq_i = theta^(-2i/d) == exp(-(2i/d) * ln(theta))
    freqs = pos * jnp.exp(idx * (-2.0 / d) * math.log(theta))
    return jnp.cos(freqs), jnp.where(upper, jnp.sin(freqs), -jnp.sin(freqs))


def _rope_kernel(x_ref, o_ref, *, theta, pos_offset, block_s, d, inverse):
    s_start = pl.program_id(1) * block_s
    cos, sin = _angles(block_s, d, theta, pos_offset + s_start)
    if inverse:
        sin = -sin
    xf = x_ref[0].astype(jnp.float32)          # [block_s, h, d]
    half = d // 2
    if d % 128 == 0:
        swapped = pltpu.roll(xf, half, 2)      # lane rotate: one XLU pass
    else:
        swapped = jnp.concatenate([xf[..., half:], xf[..., :half]], axis=-1)
    o_ref[0] = (xf * cos + swapped * sin).astype(o_ref.dtype)


def _rope_pallas(x, theta, pos_offset, inverse, interpret):
    b, s, h, d = x.shape
    bs = _pick_seq_block(s, h, d, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_rope_kernel, theta=theta, pos_offset=pos_offset,
                          block_s=bs, d=d, inverse=inverse),
        name="pt_rope",
        grid=(b, s // bs),
        in_specs=[pl.BlockSpec((1, bs, h, d), lambda i, j: (i, j, 0, 0))],
        out_specs=pl.BlockSpec((1, bs, h, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


def _reference(x, theta, pos_offset):
    b, s, h, d = x.shape
    pos = jnp.arange(pos_offset, pos_offset + s, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.outer(pos, inv)  # [s, d/2]
    cos = jnp.cos(freqs)[None, :, None, :]
    sin = jnp.sin(freqs)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def rope_halves(theta, pos_offset, impl):
    """``(fwd, bwd)`` of the op (see ``rmsnorm.rms_norm_halves``): no
    residuals, the backward is the inverse rotation of the cotangent."""
    interpret = impl == "interpret"

    def fwd(x):
        return _rope_pallas(x, theta, pos_offset, False, interpret), ()

    def bwd(_res, dy):
        return (_rope_pallas(dy, theta, pos_offset, True, interpret),)

    return fwd, bwd


def rope_apply(x, theta: float = 10000.0, pos_offset: int = 0,
               impl: str = None):
    """Rotate-half RoPE on [b, s, h, d]; d must be even. ``impl``: None
    (``registry.resolve``), 'pallas', 'interpret' or 'reference'."""
    if x.shape[-1] % 2:
        raise ValueError(f"RoPE head_dim must be even, got {x.shape[-1]}")
    if impl is None:
        impl = resolve("rope")
    if impl == "reference":
        return _reference(x, theta, pos_offset)
    return differentiable(
        *rope_halves(float(theta), int(pos_offset), impl))(x)


register_kernel(
    "rope", seq_local=False,
    doc="rotate-half RoPE: in-register angles, residual-free inverse VJP")
