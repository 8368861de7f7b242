"""Shared helpers for the fused-op kernel modules."""
from __future__ import annotations

__all__ = ["pick_rows"]


def pick_rows(n: int, pref: int = 256) -> int:
    """Largest row-block <= pref dividing n (kernels that reduce over
    the full row width block whole rows only)."""
    b = min(pref, n)
    while n % b:
        b -= 1
    return max(b, 1)
