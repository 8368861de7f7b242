"""Shared helpers for the fused-op kernel modules."""
from __future__ import annotations

import jax

__all__ = ["pick_rows", "differentiable"]


def differentiable(fwd, bwd):
    """The op whose forward half is ``fwd(*args) -> (out, residuals)`` and
    whose backward half is ``bwd(residuals, cotangent) -> one gradient an
    argument``: a ``custom_vjp`` over the two. ``distributed.mesh.
    run_kernel_on_mesh`` builds the same op from the same halves with each
    in a manual region of its own."""
    @jax.custom_vjp
    def op(*args):
        return fwd(*args)[0]

    op.defvjp(fwd, bwd)
    return op


def pick_rows(n: int, pref: int = 256) -> int:
    """Largest row-block <= pref dividing n (kernels that reduce over
    the full row width block whole rows only)."""
    b = min(pref, n)
    while n % b:
        b -= 1
    return max(b, 1)
