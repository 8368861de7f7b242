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

The walk's pipeline runs ACROSS grid steps (the grid is sequential on the
chip's one core: ``dimension_semantics`` "arbitrary" on both axes). In its
last iteration a step starts the first block of the step after it — the
row's next tile, or the next row's first — into the buffer half it is not
reading, and that step waits for the block instead of starting it; which
half, and whether a block is in flight, ride in two int32 of SMEM scratch
(the pattern of JAX's own TPU paged-attention kernel). A row with NOTHING
cached — the table entry of its first token's position is 0: page 0 is the
scratch page and never allocated, a live row's own key is written before it
attends, and the pages a window layer gave back lie before ``lo`` — starts
no walk: no DMA, no matmul, zeros in its output block (an idle slot of a
decode round: length 0, a table of zeros; nothing reads its output).
Nothing is started for such a step, the call's last step starts nothing,
and no DMA is left un-waited at the call's end. On a v5e a round's call at
2 K/V heads spent a third of its time in the first block's DMA and another
sixth in idle rows' walks of the scratch page (PERF.md section 6, PR 62).

The tiles are a function of the call's shape (``choose_tiles``; no flag, no
model's name): every tile of a row walks its range again and computes the
block's every key, seen or masked, so ``walk_cost`` counts what a tiling
walks — grid steps, iterations, pages DMA'd, the buffers' VMEM — and the
chooser takes the least reckoned work that fits ``VMEM_BUDGET``, blocks
reckoned in KEYS (``KP x PL``) and, in a window layer, bounded by the
window's own width. The call asks Mosaic for the VMEM its tiles count
(``vmem_limit_bytes``). At Laguna's shapes: a chunk of 256 to 2048 tokens
rides 128 tokens a tile (768 or 1024 query rows a K/V head) over blocks of
1024 keys in a full layer and of 256 in a window layer (a 512-key window
plus the tile's own 128 tokens is 3.5 such blocks); a decode round (one
token a row: nothing shares a block) blocks of 512 keys in a full layer and
of ONE page in a window layer, which then DMAs the five pages that hold a
visible key and no sixth.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["ranged_paged_attention"]

_NEG = -1e30
# Mosaic's scoped default is 16 MB of a v5e's 128; a call asks for what
# `walk_cost` counts for its tiles (``vmem_limit_bytes``), under this budget
VMEM_BUDGET = 48 * 2 ** 20
# float32 [rows, block] tiles the compiler holds at once: the scores, their
# exponentials and the bfloat16 copy, of the two or three K/V heads it keeps
# in flight (bisecting the limit at the published shapes found 2 to 7)
_SCORE_TILES = 8
# what `choose_tiles` reckons a tiling's work with, in (query row x key)
# units; measured on a v5e over 90 (shape, context, tiling) points (PERF.md
# section 6, PR 48). A K/V head's slab rides the MXU in passes of 128 rows, so
# fewer rows cost 128. An iteration costs its block's keys and a fixed part
# (DMA issue and latency, the loop, the waits) that is paid ONCE whatever the
# K/V heads a block holds, while the keys' work is paid a head: `_ITER_KEYS`
# keys of a head's work at the `_ITER_HEADS` heads it was measured at, so
# `_ITER_HEADS / G` times that at `G` — at 2 K/V heads a round's blocks of
# 1024 keys are 3 % (4 query heads a head) to 17 % (16) faster than blocks of
# 512, at 8 heads 6 % slower (PERF.md section 6, PR 62). Where the slab
# fills a pass, the softmax's reductions and the rescale of the running
# state — work a row, whatever the block — hide behind the matmuls only from
# `_ITER_FLOOR_KEYS` keys a block on. A grid step costs a block's keys and
# `_STEP_KEYS` keys of one pass: fitted when a step's first DMA was exposed;
# since the step before starts it (PR 62) the same terms still rank the
# measured tilings right, so they stay. The tokens cached in front of the
# window the work is reckoned at: `_REFERENCE_KEYS` (and 15 more spread over
# the next 2048, so that no block size sits on a lucky boundary).
_MXU_ROWS = 128
_ITER_KEYS = 128
_ITER_HEADS = 8
_ITER_FLOOR_KEYS = 768
_STEP_KEYS = 2048
_REFERENCE_KEYS = 4096 + 131 * np.arange(16)
# a window's worst walk DMAs at most this share of the window's own keys (6
# pages of 128 for a window of 512, of which a row sees 5)
_WINDOW_WALK = 1.5


def _kernel(tbl_ref, start_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            m_ref, l_ref, acc_ref, pipe_ref, *, G, TW, PL, KP, window, scale):
    """One (row, window tile) grid step. ``q``/``o`` blocks ``[1, G, Rp, d]``:
    for each K/V head the tile's query rows, HEAD-major (row ``r`` is tile
    token ``r % TW``; ``TW`` is a power of two; rows past ``Hg x TW`` are
    padding). ``kbuf``/``vbuf`` ``[2, G, KP x PL, d]`` are the double
    buffers a block of ``KP`` pages lands in; ``pipe_ref`` (SMEM, two
    int32) is what a step leaves the next: the buffer half its first block
    lands in, and whether that block is in flight already."""
    s, t = pl.program_id(0), pl.program_id(1)
    S, T = pl.num_programs(0), pl.num_programs(1)
    KB = KP * PL
    Rp = q_ref.shape[2]

    def walk(row, tile):
        """Of the step (row, tile): whether the row is live, its first
        token's position and the blocks ``lo`` .. ``hi`` it walks."""
        first = start_ref[row]
        # page 0 is the scratch page, never allocated: a row whose FIRST
        # token's own key lies there has nothing cached (a live row's key is
        # written before it attends, and a window layer gives back only
        # pages before ``lo``). The first token's, not the last's: a chunk's
        # padding past its last real token may lie in a page never taken
        live = tbl_ref[row, first // PL] != 0
        base = first + tile * TW       # position of the tile's first token
        hi = (base + TW - 1) // KB     # block of the last query's own key
        lo = 0 if window is None else \
            jnp.maximum(base - (window - 1), 0) // KB
        return live, base, lo, hi

    def copies(row, slot, blk, wait=False):
        """Start (or wait for) the DMAs of block ``blk`` of ``row``'s table
        into half ``slot`` of the buffers."""
        for j in range(KP):
            page = tbl_ref[row, blk * KP + j]
            dst = pl.ds(j * PL, PL)
            for n, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                c = pltpu.make_async_copy(
                    hbm.at[page], buf.at[slot, :, dst], sem.at[n, slot, j])
                c.wait() if wait else c.start()

    live, base, lo, hi = walk(s, t)
    # the step after this one: the row's next tile, or the next row's first
    wraps = t == T - 1
    row_n = jnp.minimum(jnp.where(wraps, s + 1, s), S - 1)
    live_n, _, lo_n, _ = walk(row_n, jnp.where(wraps, 0, t + 1))
    live_n = live_n & jnp.logical_not(wraps & (s == S - 1))

    @pl.when((s == 0) & (t == 0))
    def _():
        pipe_ref[0] = 0
        pipe_ref[1] = 0

    @pl.when(jnp.logical_not(live))
    def _idle():     # no DMA, no matmul; the step before started none for it
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _walk():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

        slot0 = pipe_ref[0]

        @pl.when(pipe_ref[1] == 0)
        def _():
            copies(s, slot0, lo)

        qpos = base + jnp.bitwise_and(
            jax.lax.broadcasted_iota(jnp.int32, (Rp, 1), 0), TW - 1)

        def body(blk, carry):
            slot = (slot0 + blk - lo) % 2
            last = blk == hi

            # the block after this one, into the other half: this step's
            # next, or (in its last iteration) the next step's first
            @pl.when(jnp.logical_not(last) | live_n)
            def _():
                copies(jnp.where(last, row_n, s), 1 - slot,
                       jnp.where(last, lo_n, blk + 1))

            copies(s, slot, blk, wait=True)
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
                m_prev = m_ref[g]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(sc > _NEG * 0.5, jnp.exp(sc - m_new), 0.0)
                l_new = alpha * l_ref[g] + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[g] = m_new
                l_ref[g] = l_new
            return carry

        jax.lax.fori_loop(lo, hi + 1, body, 0)
        pipe_ref[0] = (slot0 + hi - lo + 1) % 2
        pipe_ref[1] = live_n.astype(jnp.int32)
        for g in range(G):
            o_ref[0, g] = (acc_ref[g] / jnp.maximum(l_ref[g], 1e-30)) \
                .astype(o_ref.dtype)


def _padded_rows(Hg: int, TW: int) -> int:
    """Query rows a K/V head's slab holds in a grid step: ``Hg x TW`` in
    whole (16, 128) tiles of a 16-bit query."""
    return -(-Hg * TW // 16) * 16


def walk_cost(S, W, Hg, G, PL, d, window, keys, tiling, itemsize=2,
              live=None):
    """What one call of ``S`` rows x ``W`` window tokens does under
    ``tiling = (TW, KP)``, reckoned from shapes and lengths alone. ``keys``:
    the tokens cached in front of a row's window (a number, or one a row);
    ``live``: which rows hold a sequence (one bool a row; ``None``: all) —
    an idle row's steps start no walk. Returns a dict: ``steps`` (grid
    steps), ``skipped`` (those of idle rows), ``prefetched`` (live steps
    whose first block the step before — a live one — had started),
    ``iterations`` (blocks folded in, over all live steps: every tile walks
    ``[lo, hi]`` again), ``pages`` and ``bytes`` (K and V pages
    DMA'd), ``pages_in_range`` (the pages that hold a key some query of a
    live row sees: what one walk a row would DMA), ``work`` (what
    `choose_tiles` compares: the module's constants) and ``vmem`` (bytes of
    the call's buffers: the K / V double buffers, the pipeline's two ``q``
    and two ``o`` blocks, ``acc``, ``m`` / ``l`` — a float32 a row each,
    which Mosaic pads to a lane tile — and the score tiles in flight)."""
    TW, KP = tiling
    KB, T, Rp = KP * PL, W // TW, _padded_rows(Hg, TW)
    keys = np.broadcast_to(np.asarray(keys, np.int64), (S,))
    live = np.ones(S, bool) if live is None else \
        np.broadcast_to(np.asarray(live, bool), (S,))
    base = keys[live, None] + np.arange(T) * TW              # [live rows, T]
    first = np.zeros_like(base) if window is None else \
        np.maximum(base - (window - 1), 0)
    steps = base.size
    iterations = int(((base + TW - 1) // KB - first // KB + 1).sum())
    in_range = int(((keys[live] + W - 1) // PL - first[:, 0] // PL + 1).sum())
    page = 2 * G * PL * d * itemsize                # a page's K and V
    floor = _ITER_FLOOR_KEYS if Rp >= _MXU_ROWS else 0
    work = max(Rp, _MXU_ROWS) * (
        iterations * (max(KB, floor) + _ITER_KEYS * _ITER_HEADS / G)
        + steps * KB) + steps * _MXU_ROWS * _STEP_KEYS
    vmem = 2 * KP * page + 4 * G * Rp * d * itemsize \
        + G * Rp * (d + 2 * 128) * 4 + _SCORE_TILES * Rp * KB * 4
    return {"steps": S * T, "skipped": S * T - steps,
            # a row's later tiles, and its first behind a live row
            "prefetched": int(live.sum()) * (T - 1)
            + int((live[1:] & live[:-1]).sum()),
            "iterations": iterations,
            "pages": iterations * KP, "bytes": iterations * KP * page,
            "pages_in_range": in_range, "work": work, "vmem": vmem}


@functools.lru_cache(maxsize=None)
def choose_tiles(W, Hg, G, PL, d, window, itemsize=2):
    """The ``(TW, KP)`` of a call of ``W`` window tokens a row — ``TW``
    tokens a grid step (``Hg x TW`` query rows a K/V head) and ``KP`` pages a
    block — from the call's shape alone: of the powers of two ``TW`` that
    divide ``W`` and the blocks of ``2^n`` pages up to 2048 keys, whose
    buffers fit `VMEM_BUDGET` and, in a window layer, whose worst walk of
    one token's window stays within `_WINDOW_WALK` of the window, the pair
    of the least `walk_cost` ``work`` at the reference contexts; the fewest
    DMA'd bytes, then the fewest iterations among equals. The block is
    reckoned in KEYS, so pages of 16 tokens get blocks of many pages by the
    same rule. At Laguna's shapes (pages of 128, 6 or 8 query heads a K/V
    head of 128): a chunk of 256 to 2048 tokens ``(128, 8)`` in a full layer
    and ``(128, 2)`` in a window layer, a decode round ``(1, 4)`` and
    ``(1, 1)``; a round over 2 K/V heads (ZAYA1, Nemotron-H) ``(1, 8)``."""
    fits = []
    for TW in (1 << n for n in range(W.bit_length()) if W % (1 << n) == 0):
        for KP in (1 << n for n in range(12) if n == 0 or PL << n <= 2048):
            KB = KP * PL
            c = walk_cost(len(_REFERENCE_KEYS), W, Hg, G, PL, d, window,
                          _REFERENCE_KEYS, (TW, KP), itemsize)
            # a block of one page is the least a walk can DMA
            walk = 0 if window is None or KP == 1 else \
                (-(-(window - 1) // KB) + 1) * KB
            if c["vmem"] <= VMEM_BUDGET and walk <= _WINDOW_WALK * (
                    window or 1):
                fits.append(((c["work"], c["bytes"], c["iterations"]),
                             (TW, KP)))
    # one token a step over one page a block fits whatever the head
    return min(fits)[1]


def _pallas(q, k_arena, v_arena, tables, start, window, scale, interpret):
    S, W, H, d = q.shape
    _P, G, PL, _ = k_arena.shape
    Hg, B = H // G, tables.shape[1]
    tiling = TW, KP = choose_tiles(W, Hg, G, PL, d, window,
                                   k_arena.dtype.itemsize)
    vmem = walk_cost(S, W, Hg, G, PL, d, window, 0, tiling,
                     k_arena.dtype.itemsize)["vmem"]
    n_blk = -(-B // KP)
    # whole blocks: the pages past a row's table are the scratch page
    tables = jnp.pad(tables, ((0, 0), (0, n_blk * KP - B)))
    T, R, Rp = W // TW, Hg * TW, _padded_rows(Hg, TW)
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
                pltpu.VMEM((G, Rp, 1), jnp.float32),
                pltpu.VMEM((G, Rp, 1), jnp.float32),
                pltpu.VMEM((G, Rp, d), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, G, T * Rp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the grid runs in order on the chip's one core: a step starts
            # the DMA the next one waits for
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
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
    out = jnp.einsum("swghL,sgLd->swghd", probs, v,
                     preferred_element_type=jnp.float32) \
        .astype(q.dtype).reshape(S, W, H, d)
    # a row whose first token's own key lies in the scratch page is idle
    live = jnp.take_along_axis(tables, start[:, None] // PL, axis=1) != 0
    return jnp.where(live[:, :, None, None], out, 0)


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
