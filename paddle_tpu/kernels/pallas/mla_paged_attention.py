"""Absorbed latent attention (MLA) against a page table.

A layer of a latent-attention model (DeepSeek-V2/V3's MLA: openPangu-Ultra)
caches ONE row a token: ``[c_kv | k_r]``, the normed KV latent (``dv`` = 512
values) and the single roped key head every query head shares (64). In the
absorbed form a query head's ``q_nope`` is carried through its slice of the
KV up-projection first, so the score of head ``h`` against a cached token is
one dot product with that token's row and the head's value is the row's
first ``dv`` columns:

    score_h(t, s) = [q_lat_h(t) | q_rope_h(t)] . [c_kv(s) | k_r(s)] * scale
    o_h(t)        = sum_s softmax_s(score_h(t, .)) c_kv(s)          (dv wide)

So all ``H`` heads of a token are ONE ``[H, 576]`` slab against a page of
latents: multi-query attention at 128 heads, 2 x H FLOPs a cached byte.

Layouts (``serving.paged_kv`` with a latent cache + ``_build_window_step``):

- ``q``:      [S, W, H, dl] — ``[q_lat | q_rope]``, W window tokens a row
- ``arena``:  [P, PL, dl]   — the layer's page arena of latent rows
- ``tables``: [S, B] int32 page ids (0 = the scratch page)
- ``start``:  [S] int32 — window token ``w`` of row ``s`` sits at global
              position ``start[s] + w`` and sees keys ``j <= start[s] + w``

The Pallas kernel's cost follows the tokens cached, not ``B``: the grid is
(row, tile of ``TW`` window tokens) and each step walks only the pages its
tile can see — ``ceil((start + last token of the tile + 1) / (KP x PL))``
blocks of ``KP`` pages in a ``fori_loop`` whose bound is read from the
prefetched ``start``, the pages DMA'd from HBM into a double buffer by hand
(the design of JAX's own paged-attention kernel; ``pt_paged_attention``'s
one-grid-step-a-page walk costs 0.85 us a step whatever the lengths). An idle
decode row (``start`` 0, table all scratch) costs one block. One kernel serves
the decode round (W = 1: a ``[H, dl]`` slab a row) and the prefill chunk (one
row, ``TW x H`` query rows a tile).

With a ``window`` (a latent layer of the SLIDING kind: dots3-note's, whose row
is ``[c_kv (1024) | k_r (64)]`` and whose query at position ``i`` sees the
``window`` keys ``i - window < j <= i``) the walk starts at the block that
holds the first key the tile's first query can see — the design of
``ranged_paged_attention.py`` — and keys behind a query's window are masked by
position: pages the engine has given back (table entry 0, the scratch page)
lie before that block or are masked. ``window=None`` is the kernel above, the
same program as before the argument existed; a trace tells the two apart
(``pt_mla_paged_attention`` / ``pt_mla_window_attention``). ``window_walk``
counts what the window kernel's tiles read against what their windows hold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["mla_paged_attention", "window_walk"]

_NEG = -1e30
# query rows (window tokens x heads) a grid step holds, and cached tokens a
# loop iteration folds in: at H = 128 a prefill tile is 4 tokens, a block is
# 4 pages of 128 (my AOT compiles for a described v5e, PR 32: 6.4 MB of VMEM)
_ROWS = 512
_BLOCK_TOKENS = 512


def _mla_kernel(tbl_ref, start_ref, q_ref, arena_ref, o_ref, buf, sem,
                m_ref, l_ref, acc_ref, *, H, TW, PL, KP, dv, scale, window=None):
    """One (row, window tile) grid step. ``q``/``o`` blocks [1, TW x H, .]
    (token-major: query row ``r`` is head ``r % H`` of tile token
    ``r // H``); ``arena_ref`` is the whole arena in HBM; ``buf`` [2, KP x
    PL, dl] is the double buffer a block of ``KP`` pages lands in. With a
    ``window`` the walk starts at block ``lo``, the one that holds the first
    key the tile's first query sees (0 without, a Python int that folds away:
    ``window=None`` lowers to the text it had before the argument existed,
    which is what keeps cells 6 and 10 on the program they were measured
    with). A block lands in the buffer of its own parity wherever the walk
    starts."""
    s, t = pl.program_id(0), pl.program_id(1)
    R, KB = TW * H, KP * PL
    base = start_ref[s] + t * TW       # position of the tile's first token
    n_blocks = (base + TW - 1) // KB + 1
    lo = 0 if window is None else jnp.maximum(base - (window - 1), 0) // KB

    def copies(slot, blk):
        return [pltpu.make_async_copy(
            arena_ref.at[tbl_ref[s, blk * KP + j]],
            buf.at[slot, pl.ds(j * PL, PL)], sem.at[slot, j])
            for j in range(KP)]

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    for c in copies(lo % 2, lo):
        c.start()
    q = q_ref[0]                                               # [R, dl]
    qpos = base + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // H

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            for c in copies(1 - slot, blk + 1):
                c.start()

        for c in copies(slot, blk):
            c.wait()
        kv = buf[slot]                                         # [KB, dl]
        sc = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        kpos = blk * KB + jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (kpos > qpos - window)
        sc = jnp.where(seen, sc, _NEG)                         # [R, KB]
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(sc > _NEG * 0.5, jnp.exp(sc - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(lo, n_blocks, body, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)) \
        .astype(o_ref.dtype)


def _tile_tokens(W: int, H: int) -> int:
    """Window tokens a grid step holds: the most that keep ``TW x H`` query
    rows within ``_ROWS`` and divide ``W``."""
    tw = max(1, min(W, _ROWS // H))
    while W % tw:
        tw -= 1
    return tw


def _tiles(W: int, H: int, PL: int):
    """``(TW, KP)`` of a call: window tokens a grid step, pages a block —
    from the call's shape under the module's two bounds."""
    return _tile_tokens(W, H), max(1, _BLOCK_TOKENS // PL)


# Mosaic's scoped default: what a call gets that asks for nothing
VMEM_DEFAULT = 16 * 2 ** 20


def vmem_limit(R: int, KB: int, dl: int, dv: int, itemsize: int) -> dict:
    """``pallas_call`` arguments for a call whose buffers pass Mosaic's
    scoped default, ``{}`` for one whose do not (its program is then what it
    was before anyone counted): the page double buffer and the block as the
    body holds it, the pipeline's two ``q`` and two ``o`` blocks, ``acc``,
    ``m`` / ``l`` (a lane tile a row) and the float32 score tiles the
    compiler keeps in flight (the scores, their exponentials and the 16-bit
    copy, twice over: what AOT compiles at dots3-note's widths ask for)."""
    need = 3 * KB * dl * itemsize + 2 * R * (dl + dv) * itemsize \
        + R * (dv + 2 * 128) * 4 + 6 * R * KB * 4
    if need <= VMEM_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=need)}


def window_walk(W: int, H: int, PL: int, window: int, keys):
    """What the window kernel's tiles read for one call of ``len(keys)`` rows
    x ``W`` window tokens, ``keys`` the tokens cached in front of each row's
    window: ``(walked, in_window)`` latent rows — the rows of the blocks every
    grid step DMAs, and the rows some query of the step sees (the union of
    its tile's windows). Equal when the walk reads nothing it masks."""
    import numpy as np

    TW, KP = _tiles(W, H, PL)
    KB = KP * PL
    base = np.asarray(keys, np.int64).reshape(-1, 1) + np.arange(W // TW) * TW
    first = np.maximum(base - (window - 1), 0)
    walked = ((base + TW - 1) // KB - first // KB + 1) * KB
    return int(walked.sum()), int((base + TW - first).sum())


def _mla_pallas(q, arena, tables, start, dv, scale, interpret, window=None):
    S, W, H, dl = q.shape
    _P, PL, _ = arena.shape
    B = tables.shape[1]
    TW, KP = _tiles(W, H, PL)
    n_blk = -(-B // KP)
    # whole blocks: the pages past a row's table are the scratch page
    tables = jnp.pad(tables, ((0, 0), (0, n_blk * KP - B)))
    R = TW * H
    out = pl.pallas_call(
        functools.partial(_mla_kernel, H=H, TW=TW, PL=PL, KP=KP, dv=dv,
                          scale=scale, window=window),
        name="pt_mla_paged_attention" if window is None
        else "pt_mla_window_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, W // TW),
            in_specs=[
                pl.BlockSpec((1, R, dl), lambda s, t, tb, st: (s, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, R, dv), lambda s, t, tb, st: (s, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, KP * PL, dl), arena.dtype),
                pltpu.SemaphoreType.DMA((2, KP)),
                pltpu.VMEM((R, 128), jnp.float32),
                pltpu.VMEM((R, 128), jnp.float32),
                pltpu.VMEM((R, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, W * H, dv), q.dtype),
        interpret=interpret,
        # dv 1024 and rows of 1152 lanes (dots3-note's window layers) pass
        # Mosaic's default at a chunk's tile; dv 512 and 640 lanes do not
        **vmem_limit(R, KP * PL, dl, dv, arena.dtype.itemsize),
    )(tables, start, q.reshape(S, W * H, dl), arena)
    return out.reshape(S, W, H, dv)


def _reference(q, arena, tables, start, dv, scale, window=None):
    """Gather the rows' pages, then attend: the same math in plain jnp."""
    S, W, H, dl = q.shape
    _P, PL, _ = arena.shape
    L = tables.shape[1] * PL
    kv = arena[tables].reshape(S, L, dl)
    pos = start[:, None] + jnp.arange(W)                       # [S, W]
    mask = jnp.arange(L)[None, None, :] <= pos[:, :, None]     # [S, W, L]
    if window is not None:
        mask = mask & (jnp.arange(L)[None, None, :] > pos[:, :, None] - window)
    logits = jnp.einsum("swhd,sLd->swhL", q, kv,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, :, None, :], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("swhL,sLd->swhd", probs, kv[..., :dv],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def mla_paged_attention(q, arena, tables, start, *, dv: int, scale: float,
                        window: int = None, impl: str = None):
    """Absorbed latent attention of ``q`` [S, W, H, dl] against the latent
    page ``arena`` [P, PL, dl] through ``tables`` [S, B]; window token ``w``
    of row ``s``, at position ``i = start[s] + w``, sees the cached rows at
    positions ``j <= i`` and, with a ``window``, ``j > i - window`` (tables by
    ABSOLUTE block either way).
    Returns ``[S, W, H, dv]`` in ``q.dtype``: each head's softmax-weighted sum
    of the rows' first ``dv`` columns (the caller carries it through the
    value up-projection). ``impl``: None (``registry.resolve``), 'pallas',
    'interpret' or 'reference'. Serving never differentiates through it and
    it carries no VJP."""
    if impl is None:
        impl = resolve("mla_paged_attention")
    tables, start = tables.astype(jnp.int32), start.astype(jnp.int32)
    window = None if window is None else int(window)
    if impl == "reference":
        return _reference(q, arena, tables, start, dv, scale, window)
    return _mla_pallas(q, arena, tables, start, int(dv), float(scale),
                       impl == "interpret", window)


register_kernel(
    "mla_paged_attention",
    doc="absorbed latent attention (MLA) against a paged latent cache: all "
        "heads of a token one slab against a page of [c_kv | k_r] rows, "
        "walking only the pages a row's length covers — or, with a window, "
        "the pages that hold a key the row's queries can see")
