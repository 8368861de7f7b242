"""Fused rotate-half RoPE — Pallas kernel (fwd + VJP).

The reference form materializes cos/sin tables, splits the activation,
and concatenates — several elementwise HLOs over the full [b, s, h, d]
q/k tensors. The fused kernel streams each sequence block once.

Where the angles are computed is the kernel's cost (the chip, PR 52). The
first form took a ``[rows, h, d]`` block and built ``cos``/``sin`` as
``[rows, 1, d]`` in every grid step: one position a vreg, an eighth of it
filled, through ``exp``, ``cos`` and ``sin`` — 0.27-0.28 ms a call at
``[4, 2048, h, 128]`` whatever ``h`` (3.4 x the op's bytes at 16 heads,
13 x at 4), against 0.03-0.04 ms with the angles held constant. Now the
kernel reads the tensor head-major, ``[b, h, s, d]``: a head's
``[rows, d]`` tile has the positions on the SUBLANES, so the angles are one
``[rows, d]`` float32 pair (eight positions a vreg), computed once a
sequence block — at its first batch entry; the batch is the inner grid axis
and the pair stays in VMEM scratch for the other entries — and reused by
every head. Still no cos/sin table in HBM. The head-major view is a
transpose on each side of the call, which XLA folds into its neighbours'
layouts in a compiled step: the q/k projection writes ``[b, h, s, d]``
for the attention kernel anyway, and a ``[b, s, h, d]`` call in between
cost a layout copy in front of each of its six calls a layer.

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
from ._common import differentiable

__all__ = ["rope_apply", "rope_halves"]


# VMEM the kernel may plan for per grid step (v5e scopes 16 MiB): the
# in/out blocks are double-buffered in x.dtype; the float32 values are the
# two [rows, d] angle tables and one head's [rows, d] tile at a time
_VMEM_BUDGET = 8 << 20
_MAX_ROWS = 512


def _pick_seq_block(s: int, h: int, d: int, itemsize: int) -> int:
    """Rows of a sequence block: a power of two from 32 (the sublane
    packing of every dtype down to 8 bits) to ``_MAX_ROWS``, or the whole
    sequence. A power of two divides the usual sequence lengths: 384 rows
    at 16 heads read 46 us for the 39 of 256, a sixth block mostly padding
    (the chip, PR 52). The grid is ``cdiv(s, rows)``: where the last block
    is ragged, rows are independent and what its padding computes is
    dropped."""
    lanes = -(-d // 128) * 128                 # a tile's lanes are padded
    per_row = h * lanes * 4 * itemsize + 8 * lanes * 4
    fit = max(32, min(_MAX_ROWS, _VMEM_BUDGET // per_row))
    rows = 1 << (fit.bit_length() - 1)
    return s if s <= rows else rows


def _angles(bs: int, d: int, theta: float, base_pos, inverse: bool):
    """cos and sign-folded sin, [bs, d] float32, for positions base_pos +
    [0..bs) — computed in-register from INTEGER iotas cast to f32 (the
    TPU iota op yields integers only); no table input. What depends on
    the lane alone (the frequency, sin's sign) is computed on one row.
    Both halves of the lane axis carry the same angle; sin is negated on
    the first half (on the second for the inverse rotation) so
    ``x*cos + swap_halves(x)*sin`` is the rotate-half rotation."""
    half = d // 2
    pos = (base_pos + jax.lax.broadcasted_iota(jnp.int32, (bs, d), 0)
           ).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)   # a row: per lane
    upper = lane >= half
    idx = jnp.where(upper, lane - half, lane).astype(jnp.float32)
    # inv_freq_i = theta^(-2i/d) == exp(-(2i/d) * ln(theta))
    freqs = pos * jnp.exp(idx * (-2.0 / d) * math.log(theta))
    sign = jnp.where(upper != inverse, 1.0, -1.0)
    return jnp.cos(freqs), jnp.sin(freqs) * sign


def _rope_kernel(x_ref, o_ref, cos_ref, sin_ref, *, theta, pos_offset,
                 block_s, inverse):
    h, d = x_ref.shape[1], x_ref.shape[3]
    base = pos_offset + pl.program_id(0) * block_s

    @pl.when(pl.program_id(1) == 0)            # first batch entry of the block
    def _():
        cos_ref[...], sin_ref[...] = _angles(block_s, d, theta, base,
                                             inverse)

    cos, sin = cos_ref[...], sin_ref[...]
    half = d // 2
    for g in range(h):
        xf = x_ref[0, g].astype(jnp.float32)   # [block_s, d]
        if d % 128 == 0:
            swapped = pltpu.roll(xf, half, 1)  # lane rotate: one XLU pass
        else:
            swapped = jnp.concatenate([xf[:, half:], xf[:, :half]], axis=1)
        o_ref[0, g] = (xf * cos + swapped * sin).astype(o_ref.dtype)


def _rope_pallas(x, theta, pos_offset, inverse, interpret):
    b, s, h, d = x.shape
    bs = _pick_seq_block(s, h, d, x.dtype.itemsize)
    block = pl.BlockSpec((1, h, bs, d), lambda j, i: (i, 0, j, 0))
    return pl.pallas_call(
        functools.partial(_rope_kernel, theta=theta, pos_offset=pos_offset,
                          block_s=bs, inverse=inverse),
        name="pt_rope",
        grid=(pl.cdiv(s, bs), b),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs, d), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)


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
    doc="rotate-half RoPE: in-register angles once a sequence block, "
        "residual-free inverse VJP")
