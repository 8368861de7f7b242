"""The lightning indexer of a learned sparse attention (DeepSeek Sparse
Attention, as GLM-5's ``glm_moe_dsa`` configures it) against a page table:
index scores, and the exact top-k of them.

A ``full`` layer of such a model caches a second row a token, the index key
``kI(s)`` (``Di`` = 128 values), and every query token carries ``Hi`` = 32
index queries and as many weights:

    I(t, s) = sum_j w_j(t) * relu(qI_j(t) . kI(s))          s <= t

``dsa_index_scores`` computes ``I`` for the window's tokens against the
paged index keys; ``exact_topk_bias`` turns a token's scores into the mask
of its ``k`` largest, as an additive bias (0 / ``-1e30``) that
``mla_sparse_attention`` reads tile by tile.

Layouts:

- ``qi``:     [S, W, Hi, Di] — the window's index queries (roped)
- ``wi``:     [S, W, Hi] float32 — their weights, the scale folded in
- ``arena``:  [P, PL, Di]    — the layer's page arena of index keys
- ``tables``: [S, B] int32 page ids (0 = the scratch page)
- ``start``:  [S] int32 — window token ``w`` of row ``s`` sits at position
              ``start[s] + w`` and scores the keys ``j <= start[s] + w``
- scores:     [S, Wp, Lp] float32, ``Wp`` = ``W`` rounded up to 8 and ``Lp``
              = ``B`` rounded up to whole blocks of ``KP`` pages, times
              ``PL``; ``-inf`` wherever a token scores no key (past its own
              position, and the rows past ``W``)

The Pallas kernel is ``mla_paged_attention``'s walk: the grid is (row, tile
of 8 window tokens), each step DMAs the blocks of ``KP`` pages its tile can
see into a double buffer, multiplies the tile's ``8 x Hi`` query rows against
a block on the MXU, and folds the heads on the VPU (ReLU, the weight, a sum
over each token's ``Hi`` rows). The ``[8, Lp]`` scores of the tile stay in
VMEM until the step ends (1.6 MB at 49 664 positions), so the ``[W, Hi, L]``
per-head scores — 12.9 GB for a 2048-token chunk at 49 152 — never exist.

``exact_topk_bias`` is plain ``jnp`` on every backend: the ``k``-th largest
score of a row by bisection over the 32 bits of its order-preserving integer
image (32 counting passes), then, because float32 scores tie (at 49 k scores a
row one row in 300 ties AT its threshold), the tied scores of lowest position
by 16 more passes over the positions — exactly ``k`` keys (``lax.top_k``'s
choice: the larger score, then the earlier position), never an approximation.
A sort of 49 664 scores a query is far dearer on this chip, and the counting
passes read a whole padded row whatever the context, so the caller hands in
the longest context any row has and the passes run over the smallest of a
few power-of-two widths that covers it (``lax.switch``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve

__all__ = ["dsa_index_scores", "exact_topk_bias", "padded_context",
           "BLOCK_TOKENS", "TILE", "NEG"]

NEG = -1e30
# window tokens a grid step holds, and cached tokens a loop iteration folds
# in (the walk ``mla_sparse_attention`` makes over the same pages)
TILE = 8
BLOCK_TOKENS = 512


def padded_context(n_blocks: int, page_len: int) -> int:
    """``Lp``: the positions a row's ``n_blocks`` pages cover, rounded up to
    whole blocks of ``BLOCK_TOKENS`` (whole pages where a page is longer)."""
    kp = max(1, BLOCK_TOKENS // page_len)
    return -(-n_blocks // kp) * kp * page_len


def _scores_kernel(tbl_ref, start_ref, q_ref, w_ref, arena_ref, o_ref, buf,
                   sem, *, Hi, PL, KP, W):
    """One (row, tile) grid step. ``q`` [1, 8 x Hi, Di] token-major (query
    row ``r`` is index head ``r % Hi`` of tile token ``r // Hi``), ``w`` [1,
    8 x Hi, 1]; ``o`` [1, 8, Lp]."""
    s, t = pl.program_id(0), pl.program_id(1)
    KB = KP * PL
    base = start_ref[s] + t * TILE
    # the tile's last REAL token bounds the walk (rows past W score nothing)
    last = base + jnp.minimum(TILE, W - t * TILE) - 1
    n_blocks = last // KB + 1

    def copies(slot, blk):
        return [pltpu.make_async_copy(
            arena_ref.at[tbl_ref[s, blk * KP + j]],
            buf.at[slot, pl.ds(j * PL, PL)], sem.at[slot, j])
            for j in range(KP)]

    o_ref[...] = jnp.full_like(o_ref, -jnp.inf)
    for c in copies(0, 0):
        c.start()
    q, w = q_ref[0], w_ref[0]                          # [R, Di], [R, 1]
    tok = jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)
    qpos = jnp.where(t * TILE + tok < W, base + tok, -1)           # [8, 1]

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            for c in copies(1 - slot, blk + 1):
                c.start()

        for c in copies(slot, blk):
            c.wait()
        sc = jax.lax.dot_general(q, buf[slot], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = jnp.maximum(sc, 0.0) * w                               # [R, KB]
        tile = jnp.concatenate(
            [jnp.sum(sc[i * Hi:(i + 1) * Hi], axis=0, keepdims=True)
             for i in range(TILE)], 0)                             # [8, KB]
        kpos = blk * KB + jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
        o_ref[0, :, pl.ds(pl.multiple_of(blk * KB, KB), KB)] = \
            jnp.where(kpos <= qpos, tile, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)


def _scores_pallas(qi, wi, arena, tables, start, interpret):
    S, W, Hi, Di = qi.shape
    _P, PL, _ = arena.shape
    B = tables.shape[1]
    KP = max(1, BLOCK_TOKENS // PL)
    n_blk = -(-B // KP)
    tables = jnp.pad(tables, ((0, 0), (0, n_blk * KP - B)))
    Wp = -(-W // TILE) * TILE
    R = TILE * Hi
    pad = ((0, 0), (0, Wp - W), (0, 0), (0, 0))
    q = jnp.pad(qi, pad).reshape(S, Wp * Hi, Di)
    w = jnp.pad(wi.astype(jnp.float32)[..., None], pad) \
        .reshape(S, Wp * Hi, 1)
    Lp = n_blk * KP * PL
    return pl.pallas_call(
        functools.partial(_scores_kernel, Hi=Hi, PL=PL, KP=KP, W=W),
        name="pt_dsa_index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, Wp // TILE),
            in_specs=[
                pl.BlockSpec((1, R, Di), lambda s, t, tb, st: (s, t, 0)),
                pl.BlockSpec((1, R, 1), lambda s, t, tb, st: (s, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, TILE, Lp),
                                   lambda s, t, tb, st: (s, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, KP * PL, Di), arena.dtype),
                pltpu.SemaphoreType.DMA((2, KP)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, Wp, Lp), jnp.float32),
        interpret=interpret,
    )(tables, start, q, w, arena)


def _scores_reference(qi, wi, arena, tables, start):
    """Gather the rows' pages, then score: the same math in plain jnp."""
    S, W, _Hi, Di = qi.shape
    _P, PL, _ = arena.shape
    B = tables.shape[1]
    Lp = padded_context(B, PL)
    keys = arena[tables].reshape(S, B * PL, Di)
    per_head = jnp.einsum("swjd,sLd->swjL", qi, keys,
                          preferred_element_type=jnp.float32)
    sc = jnp.sum(jnp.maximum(per_head, 0.0)
                 * wi.astype(jnp.float32)[..., None], axis=2)      # [S, W, L]
    pos = start[:, None] + jnp.arange(W)
    sc = jnp.where(jnp.arange(B * PL)[None, None, :] <= pos[:, :, None], sc,
                   -jnp.inf)
    Wp = -(-W // TILE) * TILE
    return jnp.pad(sc, ((0, 0), (0, Wp - W), (0, Lp - B * PL)),
                   constant_values=-jnp.inf)


def dsa_index_scores(qi, wi, arena, tables, start, *, impl: str = None):
    """Index scores ``I(t, s)`` of the window's tokens (``qi`` [S, W, Hi,
    Di], ``wi`` [S, W, Hi]) against the paged index keys ``arena`` [P, PL,
    Di] through ``tables`` [S, B]; token ``w`` of row ``s`` scores the keys at
    positions ``<= start[s] + w``. Returns ``[S, Wp, Lp]`` float32 (module
    docstring), ``-inf`` where nothing is scored. ``impl``: None
    (``registry.resolve``), 'pallas', 'interpret' or 'reference'."""
    if impl is None:
        impl = resolve("dsa_index_scores")
    tables, start = tables.astype(jnp.int32), start.astype(jnp.int32)
    if impl == "reference":
        return _scores_reference(qi, wi, arena, tables, start)
    return _scores_pallas(qi, wi, arena, tables, start, impl == "interpret")


# -- the exact top-k -----------------------------------------------------------

def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' (``-inf``
    lowest; no NaN comes here)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def _largest_with(count_at_least, k, bits: int):
    """The largest unsigned ``bits``-bit ``T`` a row with ``count_at_least(T)
    >= k`` (a count that falls as ``T`` rises): one pass a bit."""
    def one(i, t):
        cand = t | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(count_at_least(cand) >= k, cand, t)

    return jax.lax.fori_loop(0, bits, one,
                             jnp.zeros(k.shape, jnp.uint32))


def _topk_bias(scores, k: int):
    """``scores`` [..., n] -> (bias [..., n], selected [...] int32)."""
    n = scores.shape[-1]
    u = _ordered(scores)
    want = jnp.full(scores.shape[:-1] + (1,), k, jnp.int32)

    def count(mask):
        return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)

    # the k-th largest score of each row
    thr = _largest_with(lambda c: count(u >= c), want, 32)
    above = u > thr
    # of the scores that tie with it, the first ``k - #above`` positions: on
    # positions counted from the END (``back``), the tied scores at ``back >=
    # c`` are the earliest ones and their count falls as ``c`` rises, so the
    # same bisection finds the last position taken
    tied = u == thr
    need = want - count(above)
    back = jnp.uint32(n - 1) - jnp.arange(n, dtype=jnp.uint32)
    bits = max(1, (n - 1).bit_length())
    last = _largest_with(lambda c: count(tied & (back >= c)), need, bits)
    keep = (above | (tied & (back >= last))) & (scores > -jnp.inf)
    return (jnp.where(keep, 0.0, NEG).astype(jnp.float32),
            count(keep)[..., 0])


def exact_topk_bias(scores, k: int, longest=None):
    """The exact top-``k`` of every row of ``scores`` [..., n] float32
    (``-inf``: no key there) as an additive attention bias — 0 at the ``k``
    largest finite scores of a row (all of them where a row has fewer; among
    equal scores the earlier position first, as ``lax.top_k`` chooses),
    ``-1e30`` elsewhere — and the number selected a row (int32). ``longest``
    (an int32 scalar, traced): no row has a finite score at a position ``>=
    longest``; the counting passes then run over the narrowest of a few
    widths that covers it."""
    n = scores.shape[-1]
    if longest is None:
        return _topk_bias(scores, k)
    widths = [n]
    while widths[0] >= 4 * max(k, BLOCK_TOKENS) and widths[0] % 2 == 0 \
            and len(widths) < 4:
        widths.insert(0, widths[0] // 2)

    def over(width):
        def run(sc):
            bias, cnt = _topk_bias(sc[..., :width], k)
            pad = [(0, 0)] * (sc.ndim - 1) + [(0, n - width)]
            return jnp.pad(bias, pad, constant_values=NEG), cnt

        return run

    which = sum((longest > w).astype(jnp.int32) for w in widths[:-1])
    return jax.lax.switch(which, [over(w) for w in widths], scores)


register_kernel(
    "dsa_index_scores",
    doc="lightning-indexer scores of a learned sparse attention against a "
        "paged cache of index keys: sum over index heads of w * relu(q . k), "
        "walking only the pages a row's length covers")
