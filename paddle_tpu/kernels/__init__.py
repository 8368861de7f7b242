"""Pallas TPU kernels: the fused-op library (operators/fused/ role).

- ``flash_attention``: Pallas flash attention fwd/bwd (online softmax).
- ``grouped_matmul``: megablox-style ragged per-expert matmul.
- ``pallas``: the fused-op layer (RMSNorm/RoPE fusions, fused MoE
  dispatch, paged attention, the SSM step) — each op one Pallas kernel +
  one jnp reference behind the ``registry`` seam, chosen by platform and
  live mesh (see docs/performance.md "Fused kernels").
"""
from . import registry  # noqa: F401
