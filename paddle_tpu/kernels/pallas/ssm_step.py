"""One step of the Mamba-2 state-space recurrence over a slot-indexed state
arena — the decode half of a recurrent model behind ``GenerationEngine``.

For every (row, head)::

    S   <- exp(dt A) S + (dt x) (outer) B        # S: [P, N] float32
    y   =  S C + D x

``S`` is read once and written once, aliased onto its input: the call is
bound by the bytes of the state (2 x rows x heads x P x N x 4), which is why
it is a kernel — composed XLA keeps the update and the contraction apart
and walks the arena twice. The chunked scan of a prefill is matmuls and
stays composed ``jnp`` (``models/falcon_h1.py``).

Layouts:

- ``state``: [R, H, P, N] float32 — the arena (R = slots); returned updated
- ``x``:     [R, H, P]; ``dt``: [R, H] (after softplus; 0 leaves a row's
             state as it is: exp(0) S + 0); ``a``, ``d``: [H]
- ``b``/``c``: [R, G, N] — the H // G heads of a group share them

The grid walks (row, group); one block is a group's heads of one row,
``[H/G, P, N]`` (2.1 MB at 16 x 128 x 256), N on the lanes. ``B`` and ``C``
are rows along the lanes; ``x`` rides transposed as ``[R, G, P, H/G]`` so
that a head's ``x`` is a ``[P, 1]`` column that broadcasts along the lanes,
and ``y`` is written the same way. The per-(row, head) scalars ``exp(dt A)``,
``dt`` and ``D`` ride SMEM by scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["ssm_step"]

F32 = jnp.float32


def _ssm_kernel(da_ref, dt_ref, d_ref, s_ref, x_ref, b_ref, c_ref,
                s_out, y_out, *, hb):
    r, g = pl.program_id(0), pl.program_id(1)
    b_row = b_ref[0, 0]                                      # [1, N]
    c_row = c_ref[0, 0]
    for h in range(hb):
        head = g * hb + h
        xcol = x_ref[0, 0, :, h:h + 1]                       # [P, 1]
        s = da_ref[r, head] * s_ref[0, h] + \
            (dt_ref[r, head] * xcol) * b_row                 # [P, N]
        s_out[0, h] = s
        y_out[0, 0, :, h:h + 1] = \
            jnp.sum(s * c_row, axis=-1, keepdims=True) + d_ref[head] * xcol


def _ssm_pallas(state, x, dt, a, b, c, d, interpret):
    R, H, P, N = state.shape
    G = b.shape[1]
    hb = H // G
    da = jnp.exp(dt * a)                                     # [R, H]
    xt = jnp.swapaxes(x.reshape(R, G, hb, P), 2, 3)          # [R, G, P, hb]
    new_state, yt = pl.pallas_call(
        functools.partial(_ssm_kernel, hb=hb),
        name="pt_ssm_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, G),
            in_specs=[
                pl.BlockSpec((1, hb, P, N), lambda r, g, *_: (r, g, 0, 0)),
                pl.BlockSpec((1, 1, P, hb), lambda r, g, *_: (r, g, 0, 0)),
                pl.BlockSpec((1, 1, 1, N), lambda r, g, *_: (r, g, 0, 0)),
                pl.BlockSpec((1, 1, 1, N), lambda r, g, *_: (r, g, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, P, N), lambda r, g, *_: (r, g, 0, 0)),
                pl.BlockSpec((1, 1, P, hb), lambda r, g, *_: (r, g, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((R, G, P, hb), F32)],
        # the state is updated in place: operand 3 (after the three
        # scalar-prefetch operands) is output 0
        input_output_aliases={3: 0},
        interpret=interpret,
    )(da, dt, d, state, xt, b[:, :, None, :], c[:, :, None, :])
    return new_state, jnp.swapaxes(yt, 2, 3).reshape(R, H, P)


def _reference(state, x, dt, a, b, c, d):
    """The same step in plain ``jnp``."""
    H, G = state.shape[1], b.shape[1]
    bh, ch = (jnp.repeat(m, H // G, axis=1) for m in (b, c))  # [R, H, N]
    new = jnp.exp(dt * a)[:, :, None, None] * state + \
        (dt[:, :, None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.einsum("rhpn,rhn->rhp", new, ch) + d[None, :, None] * x
    return new, y


def ssm_step(state, x, dt, a, b, c, d, impl: str = None):
    """Advance every row of ``state`` one step; returns ``(state, y)``.
    ``state`` [R, H, P, N] float32 (donate it: the Pallas call writes it in
    place); ``x`` [R, H, P]; ``dt`` [R, H]; ``a``, ``d`` [H]; ``b``, ``c``
    [R, G, N]. Everything is computed in float32; ``y`` is float32."""
    H, G = state.shape[1], b.shape[1]
    if H % G:
        raise ValueError(f"heads {H} not a multiple of groups {G}")
    if state.dtype != F32:
        raise ValueError(f"state must be float32, got {state.dtype}")
    if impl is None:
        impl = resolve("ssm_step")
    args = [t.astype(F32) for t in (x, dt, a, b, c, d)]
    x, dt, a, b, c, d = args
    if impl == "reference":
        return _reference(state, x, dt, a, b, c, d)
    return _ssm_pallas(state, x, dt, a, b, c, d,
                       interpret=(impl == "interpret"))


register_kernel(
    "ssm_step",
    doc="one step of the Mamba-2 recurrence over the slot-indexed state "
        "arena: state read and written once, in place")
