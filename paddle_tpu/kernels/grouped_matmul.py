"""Grouped (ragged) matmul: per-expert row blocks through the MXU.

The TPU-native analogue of the reference's expert-parallel dispatch ops
(paddle/fluid/operators/collective/global_scatter_op.cc builds per-expert
contiguous row buffers from counts; the expert FFN then matmuls each block).
Here the blocks stay in ONE [m, k] array sorted by expert, and a grouped
kernel walks the per-expert row ranges back-to-back on the systolic array —
no capacity padding, no one-hot dispatch tensors (megablox-style).

Backends:
- TPU: the Pallas megablox `gmm` kernel shipped with JAX (tiled grouped
  matmul with a custom VJP — the backward runs gmm for dx and the transposed
  tgmm for dw).
- CPU (tests / virtual meshes): `jax.lax.ragged_dot`, which XLA:CPU expands
  natively and which carries full JVP/transpose rules.

Tiles. `DEFAULT_TILING` is the training dispatch's (`nn/layer/moe.py`
`FLAGS_moe_dispatch=gmm`, thousands of rows a group; tuned on a v5e at
m=32768, k=1536, n=2048 in round 5, before the served path existed: 81
TFLOP/s there against 128 for a batched einsum over capacity-padded
buffers, so the capacity path stayed that dispatch's default — numbers of
that shape and that round, not of the served shapes below). The served
expert layers (`moe_held_experts_mlp`: tens of rows a group) take their
tiles from `choose_tiling`, which reckons the BYTES a tiling moves: the
kernel's grid is (n tiles, (group, row tile) visits, k tiles) and Pallas
skips a block's copy only when its index is the one of the step before, so
with the contraction in ONE tile a group's weights and a row tile's rows
stay in VMEM over consecutive visits, and with k tiled every visit streams
both again (PERF.md section 6, PR 45).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the training dispatch's tile (see the module docstring)
DEFAULT_TILING = (512, 512, 1024)

# a visit multiplies its WHOLE row tile whatever part of it is the group's,
# so a served group's tens of rows get the MXU's own 128 rows and no more
ROW_TILE = 128
# double-buffered weight, row and result tiles + the float32 accumulator:
# Mosaic's scoped limit is 16 MB and megablox hands out no compiler
# parameter to raise it, so a choice stays well inside
VMEM_BUDGET = 12 * 2 ** 20


def _tile_sizes(x: int):
    """Tiles of a k or n extent: the multiples of 128 that divide it."""
    return [t for t in range(128, x + 1, 128) if x % t == 0] or [x]


def tiling_cost(m, k, n, tiling, *, groups, rows_per_group, lhs_item=2,
                rhs_item=2, out_item=2):
    """What one `gmm` over ``lhs[m, k] @ rhs[groups, k, n]`` moves and holds
    under ``tiling``, reckoned from shapes alone: ``rows_per_group`` is the
    rows a group can expect (a float; a held expert's share of the routed
    pairs). Returns a dict: ``visits`` ((group, row tile) pairs: every row
    tile once and once more for each group edge inside one), ``tiles_k``,
    ``tiles_n``, ``steps`` (grid steps), ``weights`` / ``rows`` /
    ``results`` / ``bytes`` (HBM bytes) and ``vmem`` (bytes of buffers)."""
    tm, tk, tn = tiling
    real = max(1, min(m, round(groups * rows_per_group)))
    hit = min(groups, real)
    row_tiles = -(-real // tm)
    visits = row_tiles + hit - 1
    tiles_k, tiles_n = -(-k // tk), -(-n // tn)
    # one k tile: a block's index repeats over a group's (a row tile's)
    # consecutive visits and its copy is skipped; k tiled: the k index
    # cycles inside every visit, so each streams weights and rows again
    weights = (hit if tiles_k == 1 else visits) * k * n * rhs_item
    rows = (row_tiles if tiles_k == 1 else visits) * tm * k * lhs_item \
        * tiles_n
    results = row_tiles * tm * n * out_item
    vmem = 2 * tk * tn * rhs_item + 2 * tm * tk * lhs_item \
        + 2 * tm * tn * out_item + tm * tn * 4
    return {"visits": visits, "tiles_k": tiles_k, "tiles_n": tiles_n,
            "steps": tiles_n * visits * tiles_k, "weights": weights,
            "rows": rows, "results": results,
            "bytes": weights + rows + results, "vmem": vmem}


def choose_tiling(m, k, n, *, groups, rows_per_group, lhs_item=2, rhs_item=2,
                  out_item=2):
    """The ``(tm, tk, tn)`` of a served grouped matmul: of the k and n tiles
    that divide the operands and whose buffers fit `VMEM_BUDGET`, the pair
    that moves the fewest bytes (`tiling_cost`), the fewest grid steps among
    equals. At the served widths that is the whole contraction in one tile
    every time — beside the whole n where it fits (Laguna's 2048 x 512), a
    narrow n tile where it does not (k of 6144 / 7680 at n tiles of 256): the
    rows re-read once an n tile cost less than the weights' second stream;
    a k no whole tile of which fits stays tiled."""
    tm = min(ROW_TILE, m)
    fits = []
    for tk in _tile_sizes(k):
        for tn in _tile_sizes(n):
            c = tiling_cost(m, k, n, (tm, tk, tn), groups=groups,
                            rows_per_group=rows_per_group, lhs_item=lhs_item,
                            rhs_item=rhs_item, out_item=out_item)
            if c["vmem"] <= VMEM_BUDGET:
                fits.append(((c["bytes"], c["steps"]), (tm, tk, tn)))
    # nothing fits only where no 128 divides an operand, and those take
    # ``ragged_dot`` in `grouped_matmul` whatever the tile
    return min(fits)[1] if fits else (tm, min(128, k), min(128, n))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def grouped_matmul(lhs, rhs, group_sizes, *, tiling=None, out_dtype=None):
    """lhs[m, k] @ rhs[g, k, n] per contiguous row group -> [m, n].

    Rows of `lhs` must be grouped by expert: rows
    [sum(group_sizes[:i]), sum(group_sizes[:i+1])) multiply rhs[i].
    sum(group_sizes) must equal m. Accumulates fp32. With ``out_dtype`` the
    KERNEL hands back that dtype: megablox rounds its float32 accumulator
    once, at the last k step, so no float32 result is written and no pass
    outside reads it again; with none the result is float32 from the kernel
    and cast to ``lhs.dtype`` here (the training dispatch's form).
    Differentiable on both backends.
    """
    group_sizes = group_sizes.astype(jnp.int32)
    m, k = lhs.shape
    n = rhs.shape[-1]
    kernel_dtype = jnp.float32 if out_dtype is None else out_dtype
    # the Pallas kernel tiles in (8, 128) registers: every matmul dim must
    # be tileable (fwd AND the bwd tgmm, which transposes the roles of
    # m/k/n) — small/odd layers take the XLA ragged_dot expansion instead
    aligned = m % 8 == 0 and k % 128 == 0 and n % 128 == 0
    if _on_tpu() and aligned:
        from jax.experimental.pallas.ops.tpu import megablox as mb

        tm, tk, tn = tiling or DEFAULT_TILING
        tm, tk, tn = min(tm, m), min(tk, k), min(tn, n)
        out = mb.gmm(lhs, rhs, group_sizes,
                     preferred_element_type=kernel_dtype,
                     tiling=(tm, tk, tn))
    else:
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype if out_dtype is None else out_dtype)
