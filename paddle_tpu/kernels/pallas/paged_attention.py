"""Paged attention — decode/window attention against a page table.

PR 11's window step gathers every slot's K/V pages into a dense
[S, L, h, d] context (``kc[tables]``) and then attends — the gather
round-trips the whole addressable context through HBM even though the
attention itself touches each page once. This kernel closes that follow-
up: the grid walks (slot, page), the page table rides SMEM via scalar
prefetch, and each step DMAs ONE page of K/V and folds it into a
per-slot online softmax (flash-style f32 accumulators in VMEM scratch) —
the dense gathered context never exists.

Layouts (matching ``serving.paged_kv`` + ``_build_window_step``):

- ``q``:        [S, W, nh, hd] — W window tokens per slot
- ``k/v``:      [P, PL, kvh, hd] — the page-pool arenas (kvh <= nh, GQA)
- ``tables``:   [S, B] int32 page ids (0 = scratch page)
- ``pos``:      [S, W] int32 global positions; key position j is visible
                to window token (s, w) iff j <= pos[s, w]

Serving never differentiates through the decode step, but the kernel
still carries a VJP (backward = ``jax.vjp`` of the reference) so the
parity suite can pin gradients and nothing breaks if a scoring path
ever backprops through it. The reference IS the PR-11 gather-then-attend
math, and what the window step computes on any backend but the TPU; the
two have not been timed against each other on the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["paged_attention"]

_NEG = -1e30


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, kvh, PL, scale):
    """One (slot, page) grid step. Blocks: ``pos`` [1, R, 1] (a column,
    so the visibility mask is a plain broadcast against the key iota),
    ``q``/``o`` [1, kvh, R, hd] (K/V-head-major: the R = rep x W query
    rows that share K/V head ``g`` — its ``rep`` query heads, W window
    tokens each — are one aligned [R, hd] slab, so a grouped-query page
    costs ``kvh`` matmuls, not ``nh``), ``k``/``v`` [1, PL, kvh, hd] (the
    arena's own layout — one page DMA'd per step, head ``g`` read as a
    strided slab)."""
    del tbl_ref  # consumed by the index maps
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = pos_ref[0]                                        # [R, 1] int32
    kpos = b * PL + jax.lax.broadcasted_iota(jnp.int32, (1, PL), 1)
    visible = kpos <= qpos                                   # [R, PL]

    for h in range(kvh):
        q = q_ref[0, h]                                      # [R, hd]
        k = k_ref[0, :, h, :]                                # [PL, hd]
        v = v_ref[0, :, h, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(visible, s, _NEG)
        m_prev = m_ref[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[h, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(b == pl.num_programs(1) - 1)
    def _():
        for h in range(kvh):
            l = jnp.maximum(l_ref[h, :, :1], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def _paged_pallas(q, k_arena, v_arena, tables, pos, scale, interpret):
    S, W, nh, hd = q.shape
    P, PL, kvh, _ = k_arena.shape
    B = tables.shape[1]
    rep = nh // kvh
    R = rep * W  # query rows that share one K/V head
    # the rep query heads of a K/V head ride together, [S, W, kvh, rep, hd]
    # -> [S, kvh, rep * W, hd]; every one of the rep copies of a window
    # token sees the same positions (rep = 1: a plain head-major swap)
    qh = q.reshape(S, W, kvh, rep, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(S, kvh, R, hd)
    qpos = jnp.tile(pos, (1, rep))[:, :, None]
    out = pl.pallas_call(
        functools.partial(_paged_kernel, kvh=kvh, PL=PL, scale=scale),
        name="pt_paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, B),
            in_specs=[
                # pos rides as [S, W, 1]: a (1, W) block of [S, W] is not
                # a tile the TPU lowering accepts; (W, 1) equals the
                # array's last two dims and lands as a column vector
                pl.BlockSpec((1, R, 1), lambda s, b, t: (s, 0, 0)),
                pl.BlockSpec((1, kvh, R, hd), lambda s, b, t: (s, 0, 0, 0)),
                pl.BlockSpec((1, PL, kvh, hd),
                             lambda s, b, t: (t[s, b], 0, 0, 0)),
                pl.BlockSpec((1, PL, kvh, hd),
                             lambda s, b, t: (t[s, b], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, kvh, R, hd),
                                   lambda s, b, t: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kvh, R, hd), jnp.float32),
                pltpu.VMEM((kvh, R, 128), jnp.float32),
                pltpu.VMEM((kvh, R, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, kvh, R, hd), q.dtype),
        interpret=interpret,
    )(tables, qpos, qh, k_arena, v_arena)
    return out.reshape(S, kvh, rep, W, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(S, W, nh, hd)


def _reference(q, k_arena, v_arena, tables, pos, scale):
    """The PR-11 gather-then-attend math, verbatim."""
    S, W, nh, hd = q.shape
    _P, PL, kvh, _ = k_arena.shape
    B = tables.shape[1]
    L = B * PL
    kk = k_arena[tables].reshape(S, L, kvh, hd)
    vv = v_arena[tables].reshape(S, L, kvh, hd)
    if kvh != nh:
        rep = nh // kvh
        kk = jnp.repeat(kk, rep, axis=2)
        vv = jnp.repeat(vv, rep, axis=2)
    j = jnp.arange(L)
    mask = j[None, None, :] <= pos[:, :, None]               # [S, W, L]
    logits = jnp.einsum("swhd,sLhd->swhL", q, kk)
    logits = logits.astype(jnp.float32) * scale
    logits = jnp.where(mask[:, :, None, :], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("swhL,sLhd->swhd", probs, vv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _paged(q, k_arena, v_arena, tables, pos, scale, interpret):
    return _paged_pallas(q, k_arena, v_arena, tables, pos, scale, interpret)


def _paged_fwd(q, k_arena, v_arena, tables, pos, scale, interpret):
    out = _paged_pallas(q, k_arena, v_arena, tables, pos, scale, interpret)
    return out, (q, k_arena, v_arena, tables, pos)


def _paged_bwd(scale, interpret, res, do):
    # serving never backprops through decode; the VJP exists for the
    # parity suite and recomputes through the reference
    q, k_arena, v_arena, tables, pos = res
    _, vjp = jax.vjp(
        lambda qq, kk, vv: _reference(qq, kk, vv, tables, pos, scale),
        q, k_arena, v_arena)
    dq, dk, dv = vjp(do)
    return dq, dk, dv, None, None


_paged.defvjp(_paged_fwd, _paged_bwd)


def paged_attention(q, k_arena, v_arena, tables, pos, scale=None,
                    impl: str = None):
    """Window attention straight against the page table. ``q`` [S, W,
    nh, hd]; arenas [P, PL, kvh, hd]; ``tables`` [S, B]; ``pos`` [S, W]
    (key j visible iff j <= pos). Returns [S, W, nh, hd] in q.dtype.
    ``impl``: None (``registry.resolve``), 'pallas', 'interpret' or
    'reference'."""
    nh, kvh = q.shape[2], k_arena.shape[2]
    if nh % kvh:
        raise ValueError(f"num_heads {nh} not a multiple of kv heads {kvh}")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if impl is None:
        impl = resolve("paged_attention")
    tables, pos = tables.astype(jnp.int32), pos.astype(jnp.int32)
    if impl == "reference":
        return _reference(q, k_arena, v_arena, tables, pos, scale)
    return _paged(q, k_arena, v_arena, tables, pos, float(scale),
                  impl == "interpret")


register_kernel(
    "paged_attention",
    doc="decode window attention against the PagedKVPool page table: "
        "per-page online softmax, no dense gathered context")
