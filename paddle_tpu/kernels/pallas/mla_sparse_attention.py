"""Absorbed latent attention (MLA) over the keys a query SELECTED, against a
page table: the attention of a learned sparse attention layer (DeepSeek
Sparse Attention; GLM-5's ``glm_moe_dsa``).

``mla_paged_attention``'s layouts and walk (one slab of all ``H`` heads of a
token against a page of ``[c_kv | k_r]`` rows, the grid (row, tile of window
tokens), the visible pages DMA'd into a double buffer), with one operand
more: ``bias`` [S, Wp, Lp] float32, 0 where window token ``w`` of row ``s``
selected the cached position and ``-1e30`` where it did not
(``dsa_index.exact_topk_bias`` of the layer's — or, in a ``shared`` layer, of
the nearest ``full`` layer's — index scores). A step DMAs the ``[8, KB]``
tile of it beside each block of pages and adds it to every head's scores, so
the softmax runs over the selected keys alone:

    o_h(t) = sum_{s in S_t} softmax_{s in S_t}(q_h(t) . row(s) * scale) c_kv(s)

What this kernel does NOT do is read less than the dense one: the keys a
query selects lie scattered over its whole context (one row of 1280 bytes
here, one there), the chip moves memory by the page, and with 2048 of L keys
chosen every page of the context holds some — so the walk covers every
visible page and the selection is a mask on the scores. A gather of single
rows by index costs this chip more than the pages it would save (PERF.md
section 6, PR 44)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve
from .dsa_index import BLOCK_TOKENS, NEG, TILE, padded_context
from .mla_paged_attention import vmem_limit

__all__ = ["mla_sparse_attention"]

def _sparse_kernel(tbl_ref, start_ref, q_ref, arena_ref, bias_ref, o_ref,
                   buf, bbuf, sem, bsem, m_ref, l_ref, acc_ref, *, H, TW, PL,
                   KP, dv, scale):
    """One (row, window tile) grid step: ``mla_paged_attention``'s, plus the
    tile's ``[8, KB]`` bias block landing beside each block of pages."""
    s, t = pl.program_id(0), pl.program_id(1)
    R, KB = TW * H, KP * PL
    base = start_ref[s] + t * TW
    n_blocks = (base + TW - 1) // KB + 1
    # TW = 8: the tile's own 8 rows of the bias; TW = 1 (a decode row): the
    # 8-row group whose first row is the row's one token
    brow = pl.multiple_of(t * TILE, TILE) if TW == TILE else 0

    def copies(slot, blk):
        pages = [pltpu.make_async_copy(
            arena_ref.at[tbl_ref[s, blk * KP + j]],
            buf.at[slot, pl.ds(j * PL, PL)], sem.at[slot, j])
            for j in range(KP)]
        return pages + [pltpu.make_async_copy(
            bias_ref.at[s, pl.ds(brow, TILE),
                        pl.ds(pl.multiple_of(blk * KB, KB), KB)],
            bbuf.at[slot], bsem.at[slot])]

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    for c in copies(0, 0):
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
        b = bbuf[slot]                                         # [8, KB]
        sc = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        sc = sc + jnp.concatenate(
            [jnp.broadcast_to(b[i:i + 1], (H, KB)) for i in range(TW)], 0)
        kpos = blk * KB + jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
        sc = jnp.where(kpos <= qpos, sc, NEG)                  # [R, KB]
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(sc > NEG * 0.5, jnp.exp(sc - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)) \
        .astype(o_ref.dtype)


def _sparse_pallas(q, arena, tables, start, bias, dv, scale, interpret):
    S, W, H, dl = q.shape
    _P, PL, _ = arena.shape
    B = tables.shape[1]
    KP = max(1, BLOCK_TOKENS // PL)
    n_blk = -(-B // KP)
    tables = jnp.pad(tables, ((0, 0), (0, n_blk * KP - B)))
    # a decode row is a tile of its own; any other window goes in tiles of 8
    # tokens, padded with queries that see nothing (their bias rows are -1e30)
    TW = 1 if W == 1 else TILE
    Wq = -(-W // TW) * TW
    q = jnp.pad(q, ((0, 0), (0, Wq - W), (0, 0), (0, 0)))
    R = TW * H
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, H=H, TW=TW, PL=PL, KP=KP, dv=dv,
                          scale=scale),
        name="pt_mla_sparse_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, Wq // TW),
            in_specs=[
                pl.BlockSpec((1, R, dl), lambda s, t, tb, st: (s, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, R, dv), lambda s, t, tb, st: (s, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, KP * PL, dl), arena.dtype),
                pltpu.VMEM((2, TILE, KP * PL), jnp.float32),
                pltpu.SemaphoreType.DMA((2, KP)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((R, 128), jnp.float32),
                pltpu.VMEM((R, 128), jnp.float32),
                pltpu.VMEM((R, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, Wq * H, dv), q.dtype),
        interpret=interpret,
        # 128 heads a tile (dots3-note) pass Mosaic's default; 64 do not
        **vmem_limit(R, KP * PL, dl, dv, arena.dtype.itemsize),
    )(tables, start, q.reshape(S, Wq * H, dl), arena, bias)
    return out.reshape(S, Wq, H, dv)[:, :W]


def _reference(q, arena, tables, start, bias, dv, scale):
    """Gather the rows' pages, then attend under the bias: plain jnp."""
    S, W, H, dl = q.shape
    _P, PL, _ = arena.shape
    L = tables.shape[1] * PL
    kv = arena[tables].reshape(S, L, dl)
    pos = start[:, None] + jnp.arange(W)                       # [S, W]
    mask = jnp.arange(L)[None, None, :] <= pos[:, :, None]     # [S, W, L]
    logits = jnp.einsum("swhd,sLd->swhL", q, kv,
                        preferred_element_type=jnp.float32) * scale
    logits = logits + bias[:, :W, None, :L]
    logits = jnp.where(mask[:, :, None, :], logits, NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("swhL,sLd->swhd", probs, kv[..., :dv],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def mla_sparse_attention(q, arena, tables, start, bias, *, dv: int,
                         scale: float, impl: str = None):
    """Absorbed latent attention of ``q`` [S, W, H, dl] against the latent
    page ``arena`` [P, PL, dl] through ``tables`` [S, B], over the cached
    positions ``bias`` [S, Wp, Lp] leaves at 0 (``Wp`` = ``W`` rounded up to
    8, ``Lp`` = ``dsa_index.padded_context(B, PL)``) among those ``<=
    start[s] + w``. Returns ``[S, W, H, dv]`` in ``q.dtype``. ``impl``: None
    (``registry.resolve``), 'pallas', 'interpret' or 'reference'. No VJP."""
    if impl is None:
        impl = resolve("mla_sparse_attention")
    tables, start = tables.astype(jnp.int32), start.astype(jnp.int32)
    S, W = q.shape[:2]
    want = (S, -(-W // TILE) * TILE,
            padded_context(tables.shape[1], arena.shape[1]))
    if bias.shape != want:
        raise ValueError(f"bias is {bias.shape}, the window's is {want}")
    if impl == "reference":
        return _reference(q, arena, tables, start, bias, dv, scale)
    return _sparse_pallas(q, arena, tables, start, bias, int(dv),
                          float(scale), impl == "interpret")


register_kernel(
    "mla_sparse_attention",
    doc="absorbed latent attention (MLA) over the keys each query selected "
        "(a 0 / -1e30 bias from the indexer's exact top-k), walking the "
        "pages a row's length covers")
