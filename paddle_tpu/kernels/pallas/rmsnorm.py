"""Fused RMSNorm and RMSNorm+residual — Pallas kernels (fwd + VJP).

The FlashAttention lesson applied to the norm: the composed-XLA form
reads the activation once for the mean-square reduction and again for the
normalize (plus a third pass when a residual add precedes it), so a
[b, s, h] hidden state round-trips HBM up to 3x per norm. The fused
kernel streams each row block once: residual add, f32 mean-square,
rsqrt, scale — one read, one write, with the per-row ``rstd`` saved for
a single-pass backward (no recompute of the reduction).

Two entry points:

- ``rms_norm(x, w, eps)``: plain norm, y = x * rsqrt(mean(x^2)+eps) * w.
- ``rms_norm_residual(x, res, w, eps) -> (y, s)``: the decoder-layer
  pattern ``s = x + res; y = norm(s)`` fused; ``s`` is returned as the
  new residual stream (both outputs carry cotangents in the VJP).

Each op is a forward half and a backward half (``*_halves``); the
backward is also one kernel (dx [+dres] and a cross-row dw accumulated in
VMEM scratch over the sequential grid). The reference is the plain
forward in jnp, differentiated by JAX — what every non-TPU backend runs.
Parity is pinned by tests/test_pallas_kernels.py (fwd and grads, odd
widths), the mesh's gradients by tests/test_kernel_mesh_grads.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve
from ._common import differentiable
from ._common import pick_rows as _pick_rows

__all__ = ["rms_norm", "rms_norm_residual", "rms_norm_halves",
           "rms_norm_residual_halves"]


# -- forward ------------------------------------------------------------------
# The plain and +residual variants have DIFFERENT operand lists (not just
# different math): the plain kernel must not stream a dead residual input
# or write a redundant s output — on a memory-bound op those extra
# [n, h] DMAs would cost what the fusion saves. The saved "s" for the
# plain backward IS the primal input.

def _fwd_kernel(x_ref, r_ref, w_ref, y_ref, s_ref, rstd_ref, *, eps):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    ms = jnp.mean(s * s, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y_ref[...] = (s * rstd * w_ref[...].astype(jnp.float32)).astype(
        y_ref.dtype)
    s_ref[...] = s.astype(s_ref.dtype)
    rstd_ref[...] = rstd


def _fwd_kernel_plain(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32)).astype(
        y_ref.dtype)
    rstd_ref[...] = rstd


def _fwd_pallas(x2, r2, w, eps, residual, interpret):
    n, h = x2.shape
    bn = _pick_rows(n)
    grid = (n // bn,)
    w2 = w.reshape(1, h)
    row = pl.BlockSpec((bn, h), lambda i: (i, 0))
    wspec = pl.BlockSpec((1, h), lambda i: (0, 0))
    rstd_spec = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    if residual:
        y, s, rstd = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps),
            name="pt_rmsnorm_fwd_residual",
            grid=grid,
            in_specs=[row, row, wspec],
            out_specs=[row, row, rstd_spec],
            out_shape=[
                jax.ShapeDtypeStruct((n, h), x2.dtype),
                jax.ShapeDtypeStruct((n, h), x2.dtype),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ],
            interpret=interpret,
        )(x2, r2, w2)
        return y, s, rstd
    y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel_plain, eps=eps),
        name="pt_rmsnorm_fwd",
        grid=grid,
        in_specs=[row, wspec],
        out_specs=[row, rstd_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, w2)
    return y, x2, rstd


# -- backward -----------------------------------------------------------------

def _bwd_body(s, w, rstd, dy, dr):
    g = dy * w
    # y = s * rstd * w with rstd = (mean(s^2)+eps)^-1/2:
    # ds = rstd * (g - s * rstd^2 * mean(g*s))
    ds = rstd * (g - s * (rstd * rstd) *
                 jnp.mean(g * s, axis=-1, keepdims=True))
    if dr is not None:
        # s is ALSO the new-residual output — its cotangent adds straight
        # through (dx == dres: the add fans the same gradient both ways)
        ds = ds + dr
    return ds, jnp.sum(dy * s * rstd, axis=0, keepdims=True)


def _bwd_kernel(s_ref, w_ref, rstd_ref, dy_ref, dr_ref, dx_ref, dw_ref,
                dw_acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    ds, dw_part = _bwd_body(
        s_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        rstd_ref[...], dy_ref[...].astype(jnp.float32),
        dr_ref[...].astype(jnp.float32))
    dx_ref[...] = ds.astype(dx_ref.dtype)
    dw_acc[...] += dw_part

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dw_ref[...] = dw_acc[...]


def _bwd_kernel_plain(s_ref, w_ref, rstd_ref, dy_ref, dx_ref, dw_ref,
                      dw_acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    ds, dw_part = _bwd_body(
        s_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        rstd_ref[...], dy_ref[...].astype(jnp.float32), None)
    dx_ref[...] = ds.astype(dx_ref.dtype)
    dw_acc[...] += dw_part

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dw_ref[...] = dw_acc[...]


def _bwd_pallas(s, w, rstd, dy, dr, residual, interpret):
    n, h = s.shape
    bn = _pick_rows(n)
    grid = (n // bn,)
    w2 = w.reshape(1, h)
    row = pl.BlockSpec((bn, h), lambda i: (i, 0))
    wspec = pl.BlockSpec((1, h), lambda i: (0, 0))
    rstd_spec = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    out_specs = [row, wspec]
    out_shape = [jax.ShapeDtypeStruct((n, h), s.dtype),
                 jax.ShapeDtypeStruct((1, h), jnp.float32)]
    scratch = [pltpu.VMEM((1, h), jnp.float32)]
    if residual:
        dx, dw = pl.pallas_call(
            _bwd_kernel, name="pt_rmsnorm_bwd_residual", grid=grid,
            in_specs=[row, wspec, rstd_spec, row, row],
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=interpret,
        )(s, w2, rstd, dy, dr)
    else:
        dx, dw = pl.pallas_call(
            _bwd_kernel_plain, name="pt_rmsnorm_bwd", grid=grid,
            in_specs=[row, wspec, rstd_spec, row],
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch, interpret=interpret,
        )(s, w2, rstd, dy)
    return dx, dw.reshape(h)


# -- the two halves of each op ([..., h] layout) ------------------------------
# ``fwd(*operands) -> (out, residuals)`` and ``bwd(residuals, cotangent) ->
# one gradient an operand``, for ``_common.differentiable`` (one device) and
# ``distributed.mesh.run_kernel_on_mesh`` (a live mesh: each half a manual
# region of its own, a half seeing ONE shard's rows). The residuals keep the
# operand's rank (``rstd`` is [..., 1]) so that they cross from one region to
# the other under the operand's own spec; ``dw`` is the sum over the rows the
# half saw.

def rms_norm_halves(eps, impl):
    interpret = impl == "interpret"

    def fwd(x, w):
        h = x.shape[-1]
        x2 = x.reshape(-1, h)
        y, _, rstd = _fwd_pallas(x2, x2, w, eps, False, interpret)
        # the saved "s" of the plain backward IS the primal input
        return y.reshape(x.shape), (x, w, rstd.reshape(x.shape[:-1] + (1,)))

    def bwd(res, dy):
        s, w, rstd = res
        h = s.shape[-1]
        dy2 = dy.reshape(-1, h)
        dx, dw = _bwd_pallas(s.reshape(-1, h), w, rstd.reshape(-1, 1), dy2,
                             dy2, False, interpret)
        return dx.reshape(s.shape), dw.astype(w.dtype)

    return fwd, bwd


def rms_norm_residual_halves(eps, impl):
    interpret = impl == "interpret"

    def fwd(x, r, w):
        h = x.shape[-1]
        y, s, rstd = _fwd_pallas(x.reshape(-1, h), r.reshape(-1, h), w, eps,
                                 True, interpret)
        s = s.reshape(x.shape)
        return (y.reshape(x.shape), s), \
            (s, w, rstd.reshape(x.shape[:-1] + (1,)))

    def bwd(res, cts):
        s, w, rstd = res
        dy, dr = cts
        h = s.shape[-1]
        ds, dw = _bwd_pallas(s.reshape(-1, h), w, rstd.reshape(-1, 1),
                             dy.reshape(-1, h), dr.reshape(-1, h), True,
                             interpret)
        ds = ds.reshape(s.shape)
        return ds, ds, dw.astype(w.dtype)

    return fwd, bwd


# -- the jnp reference ---------------------------------------------------------

def _reference(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


# -- public API ([..., h] layout) ---------------------------------------------

def rms_norm(x, w, eps: float = 1e-6, impl: str = None):
    """RMSNorm over the last axis. ``impl``: None (``registry.resolve``),
    'pallas', 'interpret' (the kernel through the Pallas interpreter —
    parity tests) or 'reference' (plain jnp)."""
    if impl is None:
        impl = resolve("rms_norm")
    if impl == "reference":
        return _reference(x, w, eps)
    return differentiable(*rms_norm_halves(float(eps), impl))(x, w)


def rms_norm_residual(x, res, w, eps: float = 1e-6, impl: str = None):
    """``s = x + res; y = rmsnorm(s) * w`` -> ``(y, s)`` — the pre-norm
    decoder pattern with the residual add folded into the same HBM pass.
    Returns the normed branch input and the new residual."""
    if impl is None:
        impl = resolve("rms_norm")
    if impl == "reference":
        s = x + res
        return _reference(s, w, eps).astype(x.dtype), s
    return differentiable(*rms_norm_residual_halves(float(eps), impl))(
        x, res, w)


register_kernel(
    "rms_norm",
    doc="RMSNorm (+residual) fused: one HBM pass fwd, one-kernel VJP")
