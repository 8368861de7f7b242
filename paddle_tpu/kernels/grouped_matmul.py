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
  tgmm for dw). Tiling tuned on v5e at the bench MoE shape
  (m=32768, k=1536, n=2048): (512, 512, 1024) -> 81 TF/s; larger k-tiles
  OOM the 16MB VMEM at these widths.
- CPU (tests / virtual meshes): `jax.lax.ragged_dot`, which XLA:CPU expands
  natively and which carries full JVP/transpose rules.

Measured context (v5e, bf16, equal groups at the bench shape): a plain
batched `jnp.einsum("ech,ehi->eci")` over capacity-padded [e, cap, h]
buffers reaches 128 TF/s vs gmm's 81 TF/s, so the capacity path remains the
default MoE FFN; gmm wins only when padding waste exceeds ~1.6x (dropless
recipes with heavy imbalance). Both are exposed — see
nn/layer/moe.py `FLAGS_moe_dispatch`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# v5e-tuned default (see module docstring); callers may override.
DEFAULT_TILING = (512, 512, 1024)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def grouped_matmul(lhs, rhs, group_sizes, *, tiling=None, out_dtype=None):
    """lhs[m, k] @ rhs[g, k, n] per contiguous row group -> [m, n].

    Rows of `lhs` must be grouped by expert: rows
    [sum(group_sizes[:i]), sum(group_sizes[:i+1])) multiply rhs[i].
    sum(group_sizes) must equal m. Accumulates fp32, returns ``out_dtype``
    (``lhs.dtype`` where none is given). Differentiable on both backends.
    """
    group_sizes = group_sizes.astype(jnp.int32)
    m, k = lhs.shape
    n = rhs.shape[-1]
    # the Pallas kernel tiles in (8, 128) registers: every matmul dim must
    # be tileable (fwd AND the bwd tgmm, which transposes the roles of
    # m/k/n) — small/odd layers take the XLA ragged_dot expansion instead
    aligned = m % 8 == 0 and k % 128 == 0 and n % 128 == 0
    if _on_tpu() and aligned:
        from jax.experimental.pallas.ops.tpu import megablox as mb

        tm, tk, tn = tiling or DEFAULT_TILING
        tm, tk, tn = min(tm, m), min(tk, k), min(tn, n)
        out = mb.gmm(lhs, rhs, group_sizes,
                     preferred_element_type=jnp.float32, tiling=(tm, tk, tn))
    else:
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype if out_dtype is None else out_dtype)
