"""Pallas fused-op library (the operators/fused/ role, TPU-native).

Each module ships one fused op behind one public function holding the
Pallas TPU kernel and a plain ``jnp`` reference (``impl=None`` asks
``kernels.registry.resolve`` which runs), and registers its name there:

- ``rmsnorm``: RMSNorm and RMSNorm+residual, fwd + VJP in single kernels
  (the FlashAttention lesson applied to norms: the f32 normalize never
  round-trips the activation through HBM twice);
- ``rope``: rotate-half rotary embedding, fwd + VJP (the VJP is the
  inverse rotation — no residuals beyond the input positions);
- ``moe_dispatch``: dropless MoE routing/dispatch — top-k select +
  position-in-expert (the "sort by expert") in ONE sequential-grid
  kernel, row movement through scalar-prefetch gather/combine kernels
  with gather-only VJPs, feeding ``kernels.grouped_matmul``;
- ``paged_attention``: decode/window attention straight against the
  ``serving.paged_kv`` page table (per-page online softmax) instead of
  gather-then-attend;
- ``ssm_step``: one step of the Mamba-2 state-space recurrence over a
  slot-indexed state arena, the state read and written once, in place
  (the decode half of a recurrent model behind ``GenerationEngine``);
- ``mla_paged_attention``: absorbed latent attention (MLA) against a paged
  cache of ``[c_kv | k_r]`` rows — all heads of a token one slab against a
  page, walking only the pages a row's length covers (decode round and
  prefill chunk of a latent-attention model);
- ``ranged_paged_attention``: grouped-query attention against K/V page
  arenas over the pages ``[lo, hi]`` of each row — ``hi`` from the row's
  length, ``lo`` from a sliding window (0 in a full layer) — for a model
  whose layers are of two kinds (decode round and prefill chunk);
- ``dsa_index``: the lightning indexer of a learned sparse attention —
  ``dsa_index_scores`` (sum over index heads of ``w * relu(q . k)`` against
  a paged cache of index keys) and ``exact_topk_bias`` (the exact top-k of a
  row of scores as an additive mask; plain ``jnp`` on every backend);
- ``mla_sparse_attention``: ``mla_paged_attention`` over the keys each
  query selected (that mask), for a latent cache with an index row;
- ``power_retention``: the two ops of a power retention layer (degree-2
  gated linear attention; a model with NO K/V cache) — ``retention_step``
  (a decode round over the slot-indexed state arenas, each head's state read
  and written once, in place, ``phi`` built in VMEM) and ``retention_chunk``
  (a prefill window from a given state in inner chunks, the state resident
  in VMEM);
- ``mhc``: the residual path of manifold-constrained hyper-connections (a
  token's stream is ``n`` rows) — ``mhc_pre`` (a sublayer's three mixing
  maps, Sinkhorn-projected, and its input from ONE read of the stream) and
  ``mhc_post`` (the streams mixed and the sublayer's output added).

Import order matters only in that importing this package populates the
registry.
"""
from . import (dsa_index, mhc, mla_paged_attention,  # noqa: F401
               mla_sparse_attention, moe_dispatch, paged_attention,
               power_retention, ranged_paged_attention, rmsnorm, rope,
               ssm_step)

__all__ = ["rmsnorm", "rope", "moe_dispatch", "paged_attention", "ssm_step",
           "mla_paged_attention", "ranged_paged_attention", "dsa_index",
           "mla_sparse_attention", "power_retention", "mhc"]
