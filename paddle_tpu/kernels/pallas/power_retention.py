"""Power retention (degree-2 gated linear attention, arXiv:2507.04239): the
two ops of a model whose every layer keeps, per K/V head, a recurrent state
instead of a K/V cache — ``models/brumby.py`` behind
``serving.GenerationEngine``.

With ``phi: R^d -> R^D`` such that ``phi(a) . phi(b) = (a . b)^2``, per K/V
head (its ``G`` query heads share the state)::

    S_t = e^{lambda_t} S_{t-1} + phi(k_t / d^{1/4}) v_t^T      # [D, dv]
    Z_t = e^{lambda_t} Z_{t-1} + (k_t k_t^T) / sqrt(d)         # [d, d]
    y_t = phi(q_t / d^{1/4})^T S_t / (q_t^T Z_t q_t / sqrt(d) + eps)

which is ``sum_s w(t, s) v_s / (sum_s w(t, s) + eps)`` with ``w(t, s) =
exp(sum_{r in (s, t]} lambda_r) (q_t . k_s / sqrt(d))^2``: the attention form
``models/reference/brumby.py`` computes.

The layout of ``phi`` (``phi_layout``) is the symmetric square TILED to the
chip's 8 sublanes: the ``d`` coordinates in ``d / 8`` blocks, and for each
``i`` the row ``x_i x_j`` for every ``j`` from the start of ``i``'s block on —
weight 1 inside the block (both ``(i, j)`` and ``(j, i)`` are there), ``sqrt
2`` past it. ``D = 8 sum_I (d - 8 I)`` = 8704 at ``d`` = 128, 5 % over the
minimal 8256, and every row range a kernel touches starts on a sublane tile.
The normaliser's state is kept DENSE, ``Z = sum decay k k^T`` (``x (outer)
x``: the full square, a 128th of ``S``), so that it is one small matmul a
chunk. ``canonical_state`` maps both onto the minimal layout (``i <= j``,
``sqrt 2`` off the diagonal) a reference holds.

- ``retention_step`` (``pt_retention_step``): one decode round. The grid
  walks (row, K/V head); a step reads the head's ``S`` once and writes it
  once, aliased onto its input, builds ``phi(k)`` and the ``G`` ``phi(q)``
  in VMEM a slab at a time — the update on the VPU in float32, the
  contraction ``phi(q)^T S`` on the MXU (128 small matmuls a head, the new
  rows as its right side): the call is bound by the state's bytes.
  A row that is not ``valid`` is neither read nor written: the grid walks a
  head's rows in order and an idle row's block index is its valid
  neighbour's, so the pipeline revisits that block and moves nothing (with
  no valid row at all, every row is copied through).
- ``retention_chunk`` (``pt_retention_chunk``): a window of ``W`` tokens a
  row from the state ``S0, Z0`` in inner chunks of ``c``: inside a chunk the
  attention form (a masked ``c x c`` product, squared, decayed), against what
  came before ``phi(Q) S``, and ``S <- e^Lambda S + phi(K decayed)^T V`` at
  its end — the grid walks (row, K/V head, chunk) with the state resident in
  VMEM; ``phi(Q)`` and ``phi(K)`` exist there only. ``phi(Q) S`` is ONE
  contraction a query head a chunk: ``phi(q)`` is laid out ``d x d`` wide
  (slab ``i``'s columns are ``q_i`` times the weighted ``q``, 0 where ``j``
  is in front of the slab's block) against a copy of the state
  padded the same way, so the MXU sums over the slabs. Every matmul of both
  kernels is handed float32 operands: Mosaic feeds the MXU bfloat16 whatever
  it is handed (an explicit cast gives the same bits and costs VPU work:
  4.64 for 4.29 ms a layer at W = 2048, PERF.md section 6, PR 46) and
  accumulates in float32; the state is float32. A padded position (``valid``
  false) neither decays nor writes.

Each op is one Pallas kernel and one ``jnp`` reference (``phi`` materialised,
float32 at ``highest``: what runs off the TPU) behind one function;
``kernels.registry.resolve`` alone decides.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["retention_step", "retention_chunk", "phi", "phi_layout",
           "phi_dim", "canonical_state", "EPS"]

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
_R2 = math.sqrt(2.0)
_TILE = 8          # sublanes of a float32 tile: phi's block
# the state of one head is resident in VMEM, in and out, double-buffered
# (4 x 4.45 MB at d = 128) beside the kernels' scratch
_VMEM_LIMIT = 100 * 1024 * 1024


def phi_dim(d: int) -> int:
    """``D`` of the tiled symmetric square over ``d`` coordinates."""
    if d % _TILE:
        raise ValueError(f"head_dim {d} is not a multiple of {_TILE}")
    nb = d // _TILE
    return _TILE * sum(d - _TILE * blk for blk in range(nb))


@functools.lru_cache(maxsize=None)
def phi_layout(d: int):
    """``(i, j, w)``: row ``r`` of ``phi(x)`` is ``w[r] x[i[r]] x[j[r]]``."""
    ii, jj, ww = [], [], []
    for i in range(d):
        lo = (i // _TILE) * _TILE
        for j in range(lo, d):
            ii.append(i)
            jj.append(j)
            ww.append(1.0 if j < lo + _TILE else _R2)
    out = (np.asarray(ii, np.int32), np.asarray(jj, np.int32),
           np.asarray(ww, np.float32))
    assert len(ii) == phi_dim(d)
    return out


def phi(x):
    """``phi(x)`` over the last axis, materialised (the references')."""
    i, j, w = phi_layout(x.shape[-1])
    return x[..., i] * x[..., j] * w


@functools.lru_cache(maxsize=None)
def _canonical_index(d: int):
    """For the minimal layout's pairs ``i <= j`` in order: the tiled row that
    holds ``x_i x_j`` and what to multiply it by (so that the weight is 1 on
    the diagonal and ``sqrt 2`` off it), and the same for the dense ``Z``."""
    i, j, w = phi_layout(d)
    row_of = {(int(a), int(b)): r for r, (a, b) in enumerate(zip(i, j))}
    rows, scale, zi, zj, zscale = [], [], [], [], []
    for a in range(d):
        for b in range(a, d):
            r = row_of[a, b]
            rows.append(r)
            scale.append((1.0 if a == b else _R2) / float(w[r]))
            zi.append(a)
            zj.append(b)
            zscale.append(1.0 if a == b else _R2)
    return (np.asarray(rows, np.int32), np.asarray(scale, np.float32),
            np.asarray(zi, np.int32), np.asarray(zj, np.int32),
            np.asarray(zscale, np.float32))


def canonical_state(S, Z):
    """``S`` [..., D, dv] and the dense ``Z`` [..., d, d] on the minimal
    symmetric square (``d (d + 1) / 2`` rows: ``x_i x_j`` for ``i <= j``,
    ``sqrt 2`` off the diagonal): ``(S [..., Dmin, dv], z [..., Dmin])``,
    what ``sum_s decay phi_min(k_s) v_s^T`` and ``sum_s decay phi_min(k_s)``
    are."""
    rows, scale, zi, zj, zscale = _canonical_index(Z.shape[-1])
    return S[..., rows, :] * scale[:, None], Z[..., zi, zj] * zscale


# -- the references ------------------------------------------------------------

def _step_reference(S, Z, q, k, v, log_g, valid):
    R, H, d = q.shape
    Hk = k.shape[1]
    g = jnp.exp(log_g)[..., None, None]
    live = valid[:, None, None, None]
    S1 = jnp.where(live, g * S + phi(k)[..., None] * v[:, :, None, :], S)
    Z1 = jnp.where(live, g * Z + k[..., :, None] * k[..., None, :], Z)
    qg = q.reshape(R, Hk, H // Hk, d)
    num = jnp.einsum("rkgD,rkDe->rkge", phi(qg), S1, precision=_HI)
    den = jnp.einsum("rkgi,rkij,rkgj->rkg", qg, Z1, qg, precision=_HI)
    y = num / (den[..., None] + EPS)
    return S1, Z1, y.reshape(R, H, v.shape[-1])


def _chunk_reference(S0, Z0, Q, K, V, log_g, c):
    """``W`` a multiple of ``c``; padded positions already carry ``K`` = 0 and
    ``log_g`` = 0."""
    R, W, H, d = Q.shape
    Hk, dv = K.shape[2], V.shape[-1]
    G, n = H // Hk, W // c
    Qc = jnp.moveaxis(Q.reshape(R, n, c, Hk, G, d), 1, 0)
    Kc, Vc = (jnp.moveaxis(t.reshape(R, n, c, Hk, -1), 1, 0) for t in (K, V))
    Lc = jnp.moveaxis(log_g.reshape(R, n, c, Hk), 1, 0)
    tri = jnp.tril(jnp.ones((c, c), bool))[None, :, :, None]

    def one(carry, xs):
        S, Z = carry
        q, k, v, lg = xs
        cum = jnp.cumsum(lg, axis=1)                       # [R, c, Hk]
        seg = cum[:, :, None, :] - cum[:, None, :, :]      # [R, t, s, Hk]
        decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        a = jnp.einsum("rtkgd,rskd->rtskg", q, k, precision=_HI) ** 2 * \
            decay[..., None]
        num = jnp.einsum("rtskg,rske->rtkge", a, v, precision=_HI)
        den = jnp.sum(a, axis=2)                           # [R, t, Hk, G]
        before = jnp.exp(cum)[..., None]                   # [R, c, Hk, 1]
        num = num + before[..., None] * jnp.einsum(
            "rtkgD,rkDe->rtkge", phi(q), S, precision=_HI)
        den = den + before * jnp.einsum(
            "rtkgi,rkij,rtkgj->rtkg", q, Z, q, precision=_HI)
        to_end = jnp.exp(cum[:, -1:, :] - cum)             # [R, c, Hk]
        last = jnp.exp(cum[:, -1, :])[..., None, None]     # [R, Hk, 1, 1]
        kd = k * to_end[..., None]
        S = last * S + jnp.einsum("rskD,rske->rkDe", phi(k) *
                                  to_end[..., None], v, precision=_HI)
        Z = last * Z + jnp.einsum("rski,rskj->rkij", kd, k, precision=_HI)
        return (S, Z), num / (den[..., None] + EPS)

    (S1, Z1), Y = jax.lax.scan(one, (S0, Z0), (Qc, Kc, Vc, Lc))
    return S1, Z1, jnp.moveaxis(Y, 0, 1).reshape(R, W, H, dv)


# -- the step kernel -----------------------------------------------------------

def _slab_offsets(d: int):
    """Row of ``S`` at which the slab of coordinate ``i`` starts."""
    offs, at = [], 0
    for i in range(d):
        offs.append(at)
        at += d - (i // _TILE) * _TILE
    return offs


def _weights(at, lo, masked: bool = False):
    """``phi``'s weights over the coordinates ``at`` (an iota) for a slab
    whose block starts at ``lo``: 1 inside the block, ``sqrt 2`` past it and
    — ``masked`` — 0 in front of it (the rows of the ``d`` that end with the
    slab which are not its own)."""
    w = jnp.where(at < lo + _TILE, 1.0, _R2)
    return jnp.where(at < lo, 0.0, w) if masked else w


def _step_kernel(g_ref, valid_ref, src_ref, s_ref, z_ref, kcol_ref, krow_ref,
                 q_ref, v_ref, s_out, z_out, y_out, kb_ref, o_ref, *, d, G):
    h, r = pl.program_id(0), pl.program_id(1)
    T, Gp = _TILE, q_ref.shape[2]
    offs = _slab_offsets(d)

    # a row that is not valid rides the state blocks of a valid neighbour
    # (``src``: the grid neither reads nor writes its own) and touches
    # nothing; with no valid row at all it is its own and is copied through
    @pl.when(valid_ref[r] == 0)
    def _():
        y_out[...] = jnp.zeros(y_out.shape, F32)

    @pl.when((valid_ref[r] == 0) & (src_ref[r] == r))
    def _():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    @pl.when(valid_ref[r] != 0)
    def _():
        g = g_ref[r, h]
        kcol = kcol_ref[0, 0]                                # [d, 1]
        q = q_ref[0, 0]                                      # [Gp, d]: a head a row
        # k_i on every lane of row i (a [1, 1] cannot broadcast along
        # sublanes and lanes at once), and k (outer) v
        kb_ref[...] = jnp.broadcast_to(kcol, kb_ref.shape)
        o_ref[...] = kb_ref[...] * v_ref[0, 0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
        y = jnp.zeros((Gp, v_ref.shape[-1]), F32)
        for blk in range(d // T):
            lo = blk * T
            # over the rows of a slab (j from lo on), and over the d rows
            # that END with it
            ow = (o_ref[...] * _weights(row, lo))[lo:, :]    # [d - lo, dv]
            qw = q * _weights(lane, lo, masked=True)
            for i in range(lo, lo + T):
                rows = slice(offs[i], offs[i] + d - lo)
                # S <- g S + phi(k) v^T, a slab (k_i times a block of k v^T)
                s_out[0, 0, rows, :] = g * s_ref[0, 0, rows, :] + \
                    kb_ref[i:i + 1, :] * ow
                # y += phi(q)^T S over the slab: q_i on every lane of a
                # head's row times the weighted q, against the new rows
                y = y + jnp.dot(
                    qw * q_ref[0, 0, :, i:i + 1],
                    s_out[0, 0, offs[i] - lo:offs[i] - lo + d, :],
                    preferred_element_type=F32)
        # the normaliser: Z <- g Z + k k^T, den = q^T Z q
        z = g * z_ref[0, 0] + kcol * krow_ref[0, 0]
        z_out[0, 0] = z
        den = jnp.sum(jnp.dot(q, z, preferred_element_type=F32) * q, axis=1,
                      keepdims=True)                         # [Gp, 1]
        y_out[0, 0] = y / (den + EPS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(S, Z, q, k, v, log_g, valid, *, interpret):
    R, Hk, D, dv = S.shape
    d = q.shape[-1]
    G = q.shape[1] // Hk
    Gp = -(-G // _TILE) * _TILE          # a head a sublane, a whole tile
    qg = jnp.pad(q.reshape(R, Hk, G, d), ((0, 0), (0, 0), (0, Gp - G),
                                          (0, 0)))
    at = lambda h, r, *_: (r, h, 0, 0)                       # noqa: E731
    # the state's blocks: a valid row's own; an idle row's are those of the
    # valid row before it (after it, for the leading ones), so that the rows
    # of a head, walked in order, revisit a block instead of moving one —
    # the pipeline then neither fetches nor writes back an idle row
    state = lambda h, r, g, valid, src: (src[r], h, 0, 0)    # noqa: E731
    at_r = jnp.arange(R, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(valid, at_r, -1), axis=0)
    after = jax.lax.cummin(jnp.where(valid, at_r, R), axis=0, reverse=True)
    src = jnp.where(before >= 0, before, jnp.where(after < R, after, at_r))
    S1, Z1, y = pl.pallas_call(
        functools.partial(_step_kernel, d=d, G=G),
        name="pt_retention_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Hk, R),
            in_specs=[pl.BlockSpec((1, 1, D, dv), state),
                      pl.BlockSpec((1, 1, d, d), state),
                      pl.BlockSpec((1, 1, d, 1), at),
                      pl.BlockSpec((1, 1, 1, d), at),
                      pl.BlockSpec((1, 1, Gp, d), at),
                      pl.BlockSpec((1, 1, 1, dv), at)],
            out_specs=[pl.BlockSpec((1, 1, D, dv), state),
                       pl.BlockSpec((1, 1, d, d), state),
                       pl.BlockSpec((1, 1, Gp, dv), at)],
            scratch_shapes=[pltpu.VMEM((d, dv), F32),
                            pltpu.VMEM((d, dv), F32)]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, F32),
                   jax.ShapeDtypeStruct(Z.shape, F32),
                   jax.ShapeDtypeStruct((R, Hk, Gp, dv), F32)],
        # both states are updated in place: operands 3 and 4 (after the
        # three scalar-prefetch operands) are outputs 0 and 1
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.exp(log_g), valid.astype(jnp.int32), src, S, Z, k[..., None],
      k[:, :, None, :], qg, v[:, :, None, :])
    return S1, Z1, y[:, :, :G].reshape(R, Hk * G, dv)


def retention_step(S, Z, q, k, v, log_g, valid, impl: str = None):
    """Advance every ``valid`` row one token; returns ``(S, Z, y)``.

    ``S`` [R, Hk, D, dv] and ``Z`` [R, Hk, d, d] float32 (donate them: the
    Pallas call writes both in place); ``q`` [R, H, d], ``k`` [R, Hk, d],
    ``v`` [R, Hk, dv] (``H`` a multiple of ``Hk``: query head ``h`` reads the
    state of K/V head ``h // (H / Hk)``); ``log_g`` [R, Hk] (<= 0: the log of
    the gate); ``valid`` [R] bool. ``q`` and ``k`` are scaled by ``d^{-1/4}``
    here. Everything is computed in float32; ``y`` [R, H, dv] is float32 (0
    for a row that is not valid)."""
    if S.dtype != F32 or Z.dtype != F32:
        raise ValueError(f"the state must be float32, got {S.dtype}, "
                         f"{Z.dtype}")
    d = q.shape[-1]
    if q.shape[1] % k.shape[1] or S.shape[2] != phi_dim(d):
        raise ValueError(f"q {q.shape}, k {k.shape}, S {S.shape}: heads or "
                         "phi's rows do not fit")
    if impl is None:
        impl = resolve("retention_step")
    scale = d ** -0.25
    q, k = q.astype(F32) * scale, k.astype(F32) * scale
    v, log_g = v.astype(F32), log_g.astype(F32)
    if impl == "reference":
        S1, Z1, y = _step_reference(S, Z, q, k, v, log_g, valid)
        return S1, Z1, jnp.where(valid[:, None, None], y, 0.0)
    return _step_pallas(S, Z, q, k, v, log_g, valid,
                        interpret=(impl == "interpret"))


# -- the chunk kernel ----------------------------------------------------------

def _chunk_kernel(s0_ref, z0_ref, q_ref, k_ref, kt_ref, v_ref, cumc_ref,
                  cumr_ref, last_ref, s_out, z_out, y_out, sb_ref, lhs_ref,
                  ktd_ref, *, d, G, c):
    n = pl.program_id(2)
    T = _TILE
    offs = _slab_offsets(d)

    @pl.when(n == 0)
    def _():
        s_out[...] = s0_ref[...]
        z_out[...] = z0_ref[...]
        # the MXU's copy of the state keeps every slab at d rows: the rows
        # in front of a slab's own (j < lo) are 0 and stay 0
        sb_ref[...] = jnp.zeros(sb_ref.shape, sb_ref.dtype)

    dot = functools.partial(jnp.dot, preferred_element_type=F32)
    cum_c = cumc_ref[0, 0, 0]                                # [c, 1]
    cum_r = cumr_ref[0, 0, 0]                                # [1, c]
    before = jnp.exp(cum_c)                                  # [c, 1]
    # e^Lambda of the whole chunk, on every lane (a [1, 1] cannot broadcast
    # along sublanes and lanes at once)
    last = last_ref[0, 0, 0]                                 # [1, dv]
    to_end = jnp.exp(cumr_ref[0, 0, 0, :, c - 1:c] - cum_r)  # [1, c]
    tri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, cum_c - cum_r, 0.0)), 0.0)
    k = k_ref[0, 0, 0]                                       # [c, d]
    kt = kt_ref[0, 0, 0]                                     # [d, c] f32
    v = v_ref[0, 0, 0]                                       # [c, dv]
    z = z_out[0, 0]
    # the state as the MXU reads it, once a chunk: slab i at rows
    # [d i + lo, d (i + 1))
    for i in range(d):
        lo = (i // T) * T
        sb_ref[d * i + lo:d * (i + 1), :] = \
            s_out[0, 0, offs[i]:offs[i] + d - lo, :]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    for g in range(G):
        q = q_ref[0, 0, 0, g]                                # [c, d]
        a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        a = a * a * decay                                    # [c, c]
        num = dot(a, v)                                      # [c, dv]
        den = jnp.sum(a, axis=1, keepdims=True) + before * jnp.sum(
            dot(q, z) * q, axis=1, keepdims=True)            # [c, 1]
        # what came before the chunk, phi(q) S in ONE contraction of d x d:
        # columns [d i, d (i + 1)) of phi(q) are q_i times the weighted q
        # (0 on the j < lo that are not slab i's own), against the padded
        # copy; the MXU sums over the slabs
        for blk in range(d // T):
            lo = blk * T
            qw = q * _weights(col, lo, masked=True)          # [c, d]
            for i in range(lo, lo + T):
                lhs_ref[:, d * i:d * (i + 1)] = \
                    qw * q_ref[0, 0, 0, g, :, i:i + 1]
        y_out[0, 0, 0, g] = (num + before * dot(
            lhs_ref[...], sb_ref[...])) / (den + EPS)
    # the state at the chunk's end
    ktd_ref[...] = kt * to_end                               # [d, c]
    z_out[0, 0] = last * z + dot(ktd_ref[...], k)
    row = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
    for blk in range(d // T):
        lo = blk * T
        ktw = (kt * _weights(row, lo))[lo:, :]               # [d - lo, c]
        for i in range(lo, lo + T):
            rows = slice(offs[i], offs[i] + d - lo)
            s_out[0, 0, rows, :] = last * s_out[0, 0, rows, :] + dot(
                ktw * ktd_ref[i:i + 1, :], v)


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _chunk_pallas(S0, Z0, Q, K, V, log_g, *, c, interpret):
    R, W, H, d = Q.shape
    Hk, dv = K.shape[2], V.shape[-1]
    G, n = H // Hk, W // c
    D = S0.shape[2]
    # [R, Hk, n, (G,) c, .]: a chunk of one head is a block
    Qb = jnp.transpose(Q.reshape(R, n, c, Hk, G, d), (0, 3, 1, 4, 2, 5))
    Kb = jnp.transpose(K.reshape(R, n, c, Hk, d), (0, 3, 1, 2, 4))
    Vb = jnp.transpose(V.reshape(R, n, c, Hk, dv), (0, 3, 1, 2, 4))
    cum = jnp.cumsum(jnp.transpose(log_g.reshape(R, n, c, Hk),
                                   (0, 3, 1, 2)), axis=-1)   # [R, Hk, n, c]
    head = lambda r, h, i: (r, h, 0, 0)                      # noqa: E731
    blk4 = lambda r, h, i: (r, h, i, 0, 0)                   # noqa: E731
    S1, Z1, Y = pl.pallas_call(
        functools.partial(_chunk_kernel, d=d, G=G, c=c),
        name="pt_retention_chunk",
        grid=(R, Hk, n),
        in_specs=[pl.BlockSpec((1, 1, D, dv), head),
                  pl.BlockSpec((1, 1, d, d), head),
                  pl.BlockSpec((1, 1, 1, G, c, d),
                               lambda r, h, i: (r, h, i, 0, 0, 0)),
                  pl.BlockSpec((1, 1, 1, c, d), blk4),
                  pl.BlockSpec((1, 1, 1, d, c), blk4),
                  pl.BlockSpec((1, 1, 1, c, dv), blk4),
                  pl.BlockSpec((1, 1, 1, c, 1), blk4),
                  pl.BlockSpec((1, 1, 1, 1, c), blk4),
                  pl.BlockSpec((1, 1, 1, 1, dv), blk4)],
        out_specs=[pl.BlockSpec((1, 1, D, dv), head),
                   pl.BlockSpec((1, 1, d, d), head),
                   pl.BlockSpec((1, 1, 1, G, c, dv),
                                lambda r, h, i: (r, h, i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(S0.shape, F32),
                   jax.ShapeDtypeStruct(Z0.shape, F32),
                   jax.ShapeDtypeStruct((R, Hk, n, G, c, dv), F32)],
        scratch_shapes=[pltpu.VMEM((d * d, dv), F32),
                        pltpu.VMEM((c, d * d), F32),
                        pltpu.VMEM((d, c), F32)],
        input_output_aliases={0: 0, 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(S0, Z0, Qb, Kb, jnp.swapaxes(Kb, 3, 4), Vb, cum[..., None],
      cum[..., None, :],
      jnp.broadcast_to(jnp.exp(cum[..., -1])[..., None, None],
                       (R, Hk, n, 1, dv)))
    Y = jnp.transpose(Y, (0, 2, 4, 1, 3, 5)).reshape(R, W, H, dv)
    return S1, Z1, Y


def retention_chunk(S0, Z0, Q, K, V, log_g, valid, chunk: int = 128,
                    impl: str = None):
    """A window of ``W`` tokens a row from the state ``(S0, Z0)``; returns
    ``(S1, Z1, Y)``: the state after the row's last VALID token and each
    position's output.

    ``S0`` [R, Hk, D, dv], ``Z0`` [R, Hk, d, d] float32 (zeros: a fresh
    sequence; donate them); ``Q`` [R, W, H, d], ``K`` [R, W, Hk, d], ``V`` [R,
    W, Hk, dv]; ``log_g`` [R, W, Hk] (<= 0); ``valid`` [R, W] bool: a position
    that is not valid neither decays the state nor writes to it (its own
    output is what a query there would read, and nobody reads it). ``chunk``:
    the inner chunk ``c`` (the window is padded to a multiple of it).
    The Pallas kernel's matmuls take float32 operands, which Mosaic feeds the
    MXU as bfloat16 (module docstring); the reference is float32 at
    ``highest`` throughout. ``Y`` [R, W, H, dv]
    float32."""
    if S0.dtype != F32 or Z0.dtype != F32:
        raise ValueError(f"the state must be float32, got {S0.dtype}, "
                         f"{Z0.dtype}")
    R, W, H, d = Q.shape
    if H % K.shape[2] or S0.shape[2] != phi_dim(d):
        raise ValueError(f"Q {Q.shape}, K {K.shape}, S {S0.shape}: heads or "
                         "phi's rows do not fit")
    if impl is None:
        impl = resolve("retention_chunk")
    scale = d ** -0.25
    Q = Q.astype(F32) * scale
    K = jnp.where(valid[..., None, None], K.astype(F32) * scale, 0.0)
    log_g = jnp.where(valid[..., None], log_g.astype(F32), 0.0)
    V = V.astype(F32)
    c = min(int(chunk), -(-W // _TILE) * _TILE)
    pad = (-W) % c
    if pad:
        Q, K, V, log_g = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] *
                                  (t.ndim - 2)) for t in (Q, K, V, log_g))
    if impl == "reference":
        S1, Z1, Y = _chunk_reference(S0, Z0, Q, K, V, log_g, c)
    else:
        S1, Z1, Y = _chunk_pallas(S0, Z0, Q, K, V, log_g, c=c,
                                  interpret=(impl == "interpret"))
    return S1, Z1, Y[:, :W]


register_kernel(
    "retention_step",
    doc="one decode step of power retention over the slot-indexed state "
        "arenas: each head's state read and written once, in place, phi "
        "built in VMEM")
register_kernel(
    "retention_chunk",
    doc="a prefill window of power retention from a given state in inner "
        "chunks: phi(Q) S, the squared masked product and the state's "
        "update, phi never in HBM")
