"""The residual path of manifold-constrained hyper-connections (mHC; arXiv
2512.24880): a token's stream is ``n`` rows of ``C`` (``[T, n C]`` float32),
and every sublayer ``F`` of a block is wrapped by two ops::

    u, maps = mhc_pre(X, proj, bias)       # the three maps, and F's input
    X'      = mhc_post(X, F(u), maps)      # the streams mixed, F's output added

    xh     = X / sqrt(mean(X^2) + eps) * g                  # over all n C
    H_pre  = sigmoid(a_pre (xh phi_pre) + b_pre)            # [T, n]
    H_post = 2 sigmoid(a_post (xh phi_post) + b_post)       # [T, n]
    M      = exp(clamp(a_res mat(xh phi_res) + b_res, lo, hi))
    H_res  = M after ``iters`` times (columns / (their sums + eps), then rows)
    u      = sum_i H_pre[i] X[i]
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

Per token and sublayer that is 0.86 MFLOP against 143 kB at ``n`` 4, ``C``
3584: bound by memory 40 to 1, which is why it is a kernel pair — composed
XLA reads the stream for the norm, for the projection and for ``u``, then for
the mix. Here ``mhc_pre`` reads ``X`` once (a tile of tokens' whole stream
resident in VMEM: the norm's sum, the skinny projection on the MXU and ``u``
all from that one copy) and writes ``u`` and the maps; ``mhc_post`` reads
``X``, ``y`` and the maps and writes ``X'``: a sublayer reads the stream twice
and writes it once. ``X'`` is a buffer of its own: with ``X'`` aliased onto
``X`` (``input_output_aliases``) the pair read right alone and 5 of a scale of
7 wrong INSIDE a program on the chip, at 128 and at 256 rows, with any tile
and with or without a raised VMEM limit (my chip runs, PR 53; PERF.md section
7) — every row block of ``X'`` needs every row block of ``X``, which is what
tells this kernel from ``pt_ssm_step``'s elementwise in-place update.

Layouts. ``proj`` ``[2, n C, 128]`` bfloat16 and ``bias`` ``[1, 128]``
float32 come from ``pack_params``: ``g`` and the three scalars folded into
``phi`` (``xh phi = rinv (X (g phi))``), its ``2n + n^2`` columns on lanes
``[0, n^2)`` (``H_res``, row-major), ``[n^2, n^2 + n)`` (``H_pre``), ``[n^2 + n,
n^2 + 2n)`` (``H_post``), as a bfloat16 high part and the bfloat16 remainder:
the kernel multiplies in three bfloat16 passes (``hi hi + lo hi + hi lo``:
float32's product to 2^-16), because the MXU rounds a float32 operand to
bfloat16 and a 0.4 % error on the maps is a 0.4 % error on the stream itself.
``maps`` ``[T, 128]`` float32 holds the three maps on those same lanes.

The Sinkhorn iterations run on the tile's ``[rows, 128]`` logits in
registers, in a loop (``iters`` 20 unrolled would be 20 x 2 x 10 sublayers in
every traced program): the ``n^2`` entries are copied with period ``n^2`` over
the 128 lanes, so that a column's sum is ``log2 n`` lane rotations by
multiples of ``n`` (the rotation wraps onto the next copy) and a row's sum a
butterfly of ``log2 n`` exchanges inside each group of ``n`` lanes.

A padded position or an idle decode row costs what a real row costs and no
more: every row of a tile is computed alike, and a row of zeros gives finite
maps (``eps`` in the norm and in every denominator).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["mhc_pre", "mhc_post", "pack_params", "unpack_maps", "LANES"]

F32 = jnp.float32
LANES = 128
_TILE = 128          # tokens a grid step: 7.3 MB of stream at n C = 14336
_HIGHEST = jax.lax.Precision.HIGHEST


def pack_params(g, phi, b, a, n: int):
    """``(proj [2, n C, 128] bfloat16, bias [1, 128] float32)`` of a
    sublayer's ``g [n C]``, ``phi [n C, 2n + n^2]`` (columns ``pre | post |
    res``), ``b [2n + n^2]`` and ``a [3]`` (module docstring)."""
    nn = n * n
    if nn + 2 * n > LANES or LANES % nn or n & (n - 1):
        raise ValueError(f"mhc: n = {n} streams; a power of two with "
                         f"n^2 + 2n <= {LANES}")
    scale = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                             jnp.full((nn,), a[2])]).astype(F32)
    full = g.astype(F32)[:, None] * phi.astype(F32) * scale[None, :]
    order = lambda t: jnp.concatenate(       # noqa: E731  res | pre | post
        [t[..., 2 * n:], t[..., :2 * n]], -1)
    pad = LANES - nn - 2 * n
    full = jnp.pad(order(full), ((0, 0), (0, pad)))
    hi = full.astype(jnp.bfloat16)
    lo = (full - hi.astype(F32)).astype(jnp.bfloat16)
    return jnp.stack([hi, lo]), jnp.pad(order(b.astype(F32)), (0, pad))[None]


def unpack_maps(maps, n: int):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of ``maps``."""
    nn = n * n
    return (maps[:, nn:nn + n], maps[:, nn + n:nn + 2 * n],
            maps[:, :nn].reshape(-1, n, n))


# -- the plain jnp reference ---------------------------------------------------

def _pre_reference(x, proj, bias, *, n, iters, eps, lo, hi):
    t, nc = x.shape
    nn, c = n * n, nc // n
    w = proj[0].astype(F32) + proj[1].astype(F32)
    rinv = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    z = jnp.matmul(x, w, precision=_HIGHEST) * rinv + bias
    m = jnp.exp(jnp.clip(z[:, :nn], lo, hi)).reshape(t, n, n)

    def once(_, m):
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
        return m / (jnp.sum(m, -1, keepdims=True) + eps)

    m = jax.lax.fori_loop(0, iters, once, m)
    h_pre = jax.nn.sigmoid(z[:, nn:nn + n])
    h_post = 2.0 * jax.nn.sigmoid(z[:, nn + n:nn + 2 * n])
    maps = jnp.concatenate([m.reshape(t, nn), h_pre, h_post,
                            jnp.zeros((t, LANES - nn - 2 * n), F32)], -1)
    u = jnp.einsum("ti,tic->tc", h_pre, x.reshape(t, n, c),
                   precision=_HIGHEST)
    return u, maps


def _post_reference(x, y, maps, *, n):
    t, nc = x.shape
    _pre, h_post, h_res = unpack_maps(maps, n)
    out = jnp.einsum("tij,tjc->tic", h_res, x.reshape(t, n, nc // n),
                     precision=_HIGHEST) + h_post[:, :, None] * y[:, None, :]
    return out.reshape(t, nc)


# -- the kernels ---------------------------------------------------------------

def _col(maps, k):
    """Lane ``k`` of ``maps`` as a ``[rows, 1]`` column."""
    return maps[:, k:k + 1]


def _pre_kernel(x_ref, proj_ref, bias_ref, u_ref, maps_ref, *, n, c, iters,
                eps, lo, hi):
    nn = n * n
    x = x_ref[...]                                            # [rows, n C]
    rinv = jax.lax.rsqrt(
        jnp.sum(x * x, -1, keepdims=True) * (1.0 / (n * c)) + eps)
    x_hi = x.astype(jnp.bfloat16)
    x_lo = (x - x_hi.astype(F32)).astype(jnp.bfloat16)
    dot = functools.partial(jnp.dot, preferred_element_type=F32)
    z = dot(x_hi, proj_ref[0]) + dot(x_lo, proj_ref[0]) + \
        dot(x_hi, proj_ref[1])                                # [rows, 128]
    z = z * rinv + bias_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    sig = jax.nn.sigmoid(z)
    sig = jnp.where(lane >= nn + n, 2.0 * sig, sig)
    m = jnp.where(lane < nn, jnp.exp(jnp.clip(z, lo, hi)), 0.0)
    s = nn
    while s < LANES:                 # the n^2 entries, copied over the lanes
        m = m + pltpu.roll(m, s, 1)
        s *= 2

    def once(_, m):
        t, s = m, n
        while s < nn:                # column j: lanes j, j + n, j + 2n, ...
            t = t + pltpu.roll(t, s, 1)
            s *= 2
        m = m / (t + eps)
        t, s = m, 1
        while s < n:                 # row i: the n lanes of group i
            t = t + jnp.where((lane // s) % 2 == 0,
                              pltpu.roll(t, LANES - s, 1),
                              pltpu.roll(t, s, 1))
            s *= 2
        return m / (t + eps)

    m = jax.lax.fori_loop(0, iters, once, m)
    maps = jnp.where(lane < nn, m, jnp.where(lane < nn + 2 * n, sig, 0.0))
    maps_ref[...] = maps
    u = _col(maps, nn) * x[:, :c]
    for i in range(1, n):
        u = u + _col(maps, nn + i) * x[:, i * c:(i + 1) * c]
    u_ref[...] = u


def _post_kernel(x_ref, y_ref, maps_ref, out_ref, *, n, c):
    nn = n * n
    maps, y = maps_ref[...], y_ref[...]
    for i in range(n):
        acc = _col(maps, nn + n + i) * y
        for j in range(n):
            acc = acc + _col(maps, i * n + j) * x_ref[:, j * c:(j + 1) * c]
        out_ref[:, i * c:(i + 1) * c] = acc


def _tile(t: int) -> int:
    """Tokens a grid step, and ``t`` padded to whole tiles of 8 rows."""
    for rows in (_TILE, 64, 32, 16, 8):
        if t % rows == 0:
            return rows
    return 8


def _padded(t: int, *arrays):
    rows = _tile(t)
    pad = -t % rows
    if pad:
        arrays = tuple(jnp.pad(a, ((0, pad), (0, 0))) for a in arrays)
    return (rows, t + pad) + arrays


def _params(rows: int, nc: int, streams: int):
    """``streams`` double-buffered copies of a tile's stream, the projection
    and what the body keeps live beside them."""
    need = rows * nc * 4 * (2 * streams + 2) + 4 * nc * LANES * 2 + (8 << 20)
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=int(need))


def _pre_pallas(x, proj, bias, *, n, iters, eps, lo, hi, interpret):
    t, nc = x.shape
    c = nc // n
    rows, tp, x = _padded(t, x)
    u, maps = pl.pallas_call(
        functools.partial(_pre_kernel, n=n, c=c, iters=iters, eps=eps,
                          lo=lo, hi=hi),
        name="pt_mhc_pre",
        grid=(tp // rows,),
        in_specs=[pl.BlockSpec((rows, nc), lambda i: (i, 0)),
                  pl.BlockSpec((2, nc, LANES), lambda i: (0, 0, 0)),
                  pl.BlockSpec((1, LANES), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, c), lambda i: (i, 0)),
                   pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((tp, c), F32),
                   jax.ShapeDtypeStruct((tp, LANES), F32)],
        compiler_params=_params(rows, nc, 1),
        interpret=interpret,
    )(x, proj, bias)
    return u[:t], maps[:t]


def _post_pallas(x, y, maps, *, n, interpret):
    t, nc = x.shape
    c = nc // n
    rows, tp, x, y, maps = _padded(t, x, y, maps)
    out = pl.pallas_call(
        functools.partial(_post_kernel, n=n, c=c),
        name="pt_mhc_post",
        grid=(tp // rows,),
        in_specs=[pl.BlockSpec((rows, nc), lambda i: (i, 0)),
                  pl.BlockSpec((rows, c), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, nc), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, nc), F32),
        compiler_params=_params(rows, nc, 2),
        interpret=interpret,
    )(x, y, maps)
    return out[:t]


# -- the ops -------------------------------------------------------------------

def mhc_pre(x, proj, bias, *, n: int, iters: int, eps: float, lo: float,
            hi: float, impl: str = None):
    """The maps of a sublayer and its input. ``x`` [T, n C] float32, the
    stream; ``proj``, ``bias`` from ``pack_params``; ``iters`` Sinkhorn
    iterations, ``eps`` in the norm and the denominators, ``[lo, hi]`` the
    clamp before ``exp``. Returns ``(u [T, C], maps [T, 128])`` float32."""
    if x.dtype != F32 or x.shape[1] % n:
        raise ValueError(f"mhc_pre: a float32 stream of {n} rows, got "
                         f"{x.dtype} {x.shape}")
    if impl is None:
        impl = resolve("mhc_pre")
    kw = dict(n=n, iters=int(iters), eps=float(eps), lo=float(lo),
              hi=float(hi))
    if impl == "reference":
        return _pre_reference(x, proj, bias, **kw)
    return _pre_pallas(x, proj, bias, interpret=(impl == "interpret"), **kw)


def mhc_post(x, y, maps, *, n: int, impl: str = None):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``. ``x`` [T, n C]
    float32, ``y`` [T, C] float32,
    ``maps`` from ``mhc_pre``. Returns ``X'`` [T, n C] float32."""
    if impl is None:
        impl = resolve("mhc_post")
    y = y.astype(F32)
    if impl == "reference":
        return _post_reference(x, y, maps, n=n)
    return _post_pallas(x, y, maps, n=n, interpret=(impl == "interpret"))


register_kernel(
    "mhc_pre",
    doc="a hyper-connection sublayer's three maps (norm, skinny projection, "
        "Sinkhorn) and its input from one read of the n-row stream")
register_kernel(
    "mhc_post",
    doc="a hyper-connection sublayer's mix: the n-row stream through H_res "
        "plus H_post times the sublayer's output")
