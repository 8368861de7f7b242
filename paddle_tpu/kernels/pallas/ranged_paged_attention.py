"""Grouped-query attention against a page table, over a RANGE of pages.

A layer of a model whose layers are of two kinds (``serving.paged_kv`` with
``cache_spec["kind"] == "kv_by_layer"``) caches a key and a value of ``[G,
d]`` a token (``G`` K/V heads) in arenas laid out ``[P, G, PL, d]`` — a page
holds ``PL`` tokens of every K/V head, one head's tokens contiguous, so a
page is ONE DMA and a head's keys are a ``[PL, d]`` slab of it. Query head
``h`` reads K/V head ``h // (H / G)``; the ``H / G`` query heads of a K/V
head ride as one slab (6 or 8 rows a token).

A query at global position ``i`` sees the keys ``j <= i`` — and, in a layer
with a sliding ``window``, only ``j > i - window`` (``window`` keys, the
token's own among them). The kernel's cost follows that range, not the
table's width and not even the tokens cached: the grid is (row, tile of
``TW`` window tokens), and a step walks the blocks of ``KP`` pages from the
one that holds the first key its tile can see (``lo``: 0 in a full layer)
to the one that holds its last query's own key (``hi``), in a ``fori_loop``
whose bounds are read from the prefetched ``start``, pages DMA'd from HBM
into a double buffer by hand (the design of ``mla_paged_attention.py``). A
window layer's pages behind the window may have gone back to the allocator
(their table entries are 0, the scratch page): they lie before ``lo`` or are
masked by position.

Layouts (``_build_window_step``):

- ``q``:        [S, W, H, d], W window tokens a row
- ``k_arena``,
  ``v_arena``:  [P, G, PL, d]
- ``tables``:   [S, B] int32 page ids by ABSOLUTE block (position // PL)
- ``start``:    [S] int32 — window token ``w`` of row ``s`` sits at global
                position ``start[s] + w``

One kernel serves the decode round (W = 1) and the prefill chunk (one row,
up to 2048 tokens); a trace tells a window layer's calls
(``pt_ranged_attention_window``) from a full layer's
(``pt_ranged_attention_full``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["ranged_paged_attention"]

_NEG = -1e30
# query rows (tile tokens x the query heads of ONE K/V head) a grid step holds
# for each K/V head, and the pages a loop iteration folds in: a full layer
# walks 512 tokens an iteration, a window layer 256 (a window of 512 then
# spans at most three blocks: 6 pages DMA'd for the 5 that hold a visible key)
_ROWS = 256
_PAGES_FULL = 4
_PAGES_WINDOW = 2


def _kernel(tbl_ref, start_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            m_ref, l_ref, acc_ref, *, G, TW, PL, KP, window, scale):
    """One (row, window tile) grid step. ``q``/``o`` blocks ``[1, G, Rp, d]``:
    for each K/V head the tile's query rows, HEAD-major (row ``r`` is tile
    token ``r % TW``; ``TW`` is a power of two; rows past ``Hg x TW`` are
    padding). ``kbuf``/``vbuf`` ``[2, G, KP x PL, d]`` are the double
    buffers a block of ``KP`` pages lands in."""
    s, t = pl.program_id(0), pl.program_id(1)
    KB = KP * PL
    Rp = q_ref.shape[2]
    base = start_ref[s] + t * TW       # position of the tile's first token
    hi = (base + TW - 1) // KB         # block of the last query's own key
    lo = 0 if window is None else \
        jnp.maximum(base - (window - 1), 0) // KB

    def copies(slot, blk):
        out = []
        for j in range(KP):
            page = tbl_ref[s, blk * KP + j]
            dst = pl.ds(j * PL, PL)
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[slot, :, dst], sem.at[0, slot, j]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], vbuf.at[slot, :, dst], sem.at[1, slot, j]))
        return out

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    for c in copies(0, lo):
        c.start()
    qpos = base + jnp.bitwise_and(
        jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0), TW - 1)

    def body(blk, carry):
        slot = (blk - lo) % 2

        @pl.when(blk < hi)
        def _():
            for c in copies(1 - slot, blk + 1):
                c.start()

        for c in copies(slot, blk):
            c.wait()
        kpos = blk * KB + jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
        seen = kpos <= qpos                                    # [Rp, KB]
        if window is not None:
            seen = seen & (kpos > qpos - window)
        for g in range(G):
            k, v = kbuf[slot, g], vbuf[slot, g]                # [KB, d]
            sc = jax.lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(seen, sc, _NEG)
            m_prev = m_ref[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(sc > _NEG * 0.5, jnp.exp(sc - m_new), 0.0)
            l_new = alpha * l_ref[g, :, :1] + \
                jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        return carry

    jax.lax.fori_loop(lo, hi + 1, body, 0)
    for g in range(G):
        o_ref[0, g] = (acc_ref[g] / jnp.maximum(l_ref[g, :, :1], 1e-30)) \
            .astype(o_ref.dtype)


def _tile_tokens(W: int, Hg: int) -> int:
    """Window tokens a grid step holds: the largest power of two that keeps
    ``TW x Hg`` query rows within ``_ROWS`` and divides ``W``."""
    tw = 1
    while tw * 2 * Hg <= _ROWS and W % (tw * 2) == 0:
        tw *= 2
    return tw


def _pallas(q, k_arena, v_arena, tables, start, window, scale, interpret):
    S, W, H, d = q.shape
    _P, G, PL, _ = k_arena.shape
    Hg, B = H // G, tables.shape[1]
    KP = _PAGES_FULL if window is None else _PAGES_WINDOW
    n_blk = -(-B // KP)
    # whole blocks: the pages past a row's table are the scratch page
    tables = jnp.pad(tables, ((0, 0), (0, n_blk * KP - B)))
    TW = _tile_tokens(W, Hg)
    T, R = W // TW, Hg * TW
    Rp = -(-R // 16) * 16    # whole (16, 128) tiles of a 16-bit query
    # [S, W, H, d] -> for each K/V head the tiles' rows, head-major in a tile
    qt = q.reshape(S, T, TW, G, Hg, d).transpose(0, 3, 1, 4, 2, 5) \
        .reshape(S, G, T, R, d)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, Rp - R), (0, 0))) \
        .reshape(S, G, T * Rp, d)
    block = pl.BlockSpec((1, G, Rp, d), lambda s, t, tb, st: (s, 0, t, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, G=G, TW=TW, PL=PL, KP=KP, window=window,
                          scale=scale),
        name="pt_ranged_attention_" + ("full" if window is None
                                       else "window"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, T),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, G, KP * PL, d), k_arena.dtype),
                pltpu.VMEM((2, G, KP * PL, d), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2, KP)),
                pltpu.VMEM((G, Rp, 128), jnp.float32),
                pltpu.VMEM((G, Rp, 128), jnp.float32),
                pltpu.VMEM((G, Rp, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, G, T * Rp, d), q.dtype),
        interpret=interpret,
    )(tables, start, qt, k_arena, v_arena)
    out = out.reshape(S, G, T, Rp, d)[:, :, :, :R]
    return out.reshape(S, G, T, Hg, TW, d).transpose(0, 2, 4, 1, 3, 5) \
        .reshape(S, W, H, d)


def _reference(q, k_arena, v_arena, tables, start, window, scale):
    """Gather the rows' pages, then attend: the same math in plain jnp."""
    S, W, H, d = q.shape
    _P, G, PL, _ = k_arena.shape
    L = tables.shape[1] * PL

    def rows(arena):     # [S, B, G, PL, d] -> [S, G, L, d]
        return arena[tables].transpose(0, 2, 1, 3, 4).reshape(S, G, L, d)

    k, v = rows(k_arena), rows(v_arena)
    pos = (start[:, None] + jnp.arange(W))[:, :, None]         # [S, W, 1]
    kpos = jnp.arange(L)[None, None, :]
    seen = kpos <= pos                                         # [S, W, L]
    if window is not None:
        seen = seen & (kpos > pos - window)
    qg = q.reshape(S, W, G, H // G, d)
    logits = jnp.einsum("swghd,sgLd->swghL", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(seen[:, :, None, None, :], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("swghL,sgLd->swghd", probs, v,
                      preferred_element_type=jnp.float32) \
        .astype(q.dtype).reshape(S, W, H, d)


def ranged_paged_attention(q, k_arena, v_arena, tables, start, *,
                           window: Optional[int] = None, scale: float,
                           impl: str = None):
    """Attention of ``q`` [S, W, H, d] against the K/V page arenas ``[P, G,
    PL, d]`` through ``tables`` [S, B]: window token ``w`` of row ``s``, at
    position ``i = start[s] + w``, sees the cached keys ``j <= i`` and, with
    a ``window``, ``j > i - window``. Returns ``[S, W, H, d]`` in
    ``q.dtype``. ``impl``: None (``registry.resolve``), 'pallas',
    'interpret' or 'reference'. Serving never differentiates through it and
    it carries no VJP."""
    if impl is None:
        impl = resolve("ranged_paged_attention")
    if q.shape[2] % k_arena.shape[1]:
        raise ValueError(f"{q.shape[2]} query heads over "
                         f"{k_arena.shape[1]} K/V heads")
    window = None if window is None else int(window)
    tables, start = tables.astype(jnp.int32), start.astype(jnp.int32)
    if impl == "reference":
        return _reference(q, k_arena, v_arena, tables, start, window, scale)
    return _pallas(q, k_arena, v_arena, tables, start, window, float(scale),
                   impl == "interpret")


register_kernel(
    "ranged_paged_attention",
    doc="grouped-query attention against K/V page arenas over the pages "
        "[lo, hi] of each row: hi from the row's length, lo from a sliding "
        "window (0 in a full layer); decode rounds and prefill chunks")
