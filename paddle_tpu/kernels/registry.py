"""Fused-kernel registry: ONE dispatch seam for the Pallas op library.

Reference role: paddle/fluid/operators/fused/ — the reference ships its
hot-path fusions (fused_attention, fused_ffn, fused_rms_norm) as separate
CUDA kernels picked by a pass. TPU-native mapping: each fused op registers
here with TWO implementations of the SAME fused algorithm:

- ``pallas``: the Pallas TPU kernel (``kernels/pallas/``). On CPU the same
  kernel runs in interpret mode when ``PT_PALLAS_INTERPRET=1`` — that is
  the parity-test surface, not a production path (the interpreter is slow).
- ``composed``: the composed-XLA twin — identical math and custom-VJP
  structure, expressed in jnp. Fast on CPU (tier-1, virtual meshes) and
  the A/B reference on TPU.

Call sites gate on ``fused_enabled(name)`` (live ``FLAGS_fused_kernels``:
``auto`` = fused on TPU, legacy composed-XLA path on CPU; ``on``/``off``
force it; a comma list enables exactly the named ops on any backend) and
then call ``resolve(name)`` for the implementation. The gate decision must
reach the jit cache key — layer code passes it as a primitive ATTR (see
``nn/functional/common.py``, ``models/llama.py``) so a flag flip retraces
and the ``analysis.retrace`` auditor names the flip.

``kernel_table()`` is the introspection surface (per-op choice + trace
counts), registered as the ``fused_kernels`` observability provider; the
PR-9 planner prices the same entries via ``cost_model.fused``.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = ["register_kernel", "fused_enabled", "resolve", "kernel_table",
           "enabled_ops", "KernelEntry"]


class KernelEntry:
    __slots__ = ("name", "pallas", "composed", "doc", "calls")

    def __init__(self, name: str, pallas: Callable, composed: Callable,
                 doc: str = ""):
        self.name = name
        self.pallas = pallas
        self.composed = composed
        self.doc = doc
        # trace-time counters per implementation (a count here is a
        # compile-side event, not a per-step cost — the audit semantics)
        self.calls: Dict[str, int] = {"pallas": 0, "interpret": 0,
                                      "composed": 0}


_KERNELS: Dict[str, KernelEntry] = {}
_PROVIDER_REGISTERED = False


def register_kernel(name: str, *, pallas: Callable, composed: Callable,
                    doc: str = "") -> KernelEntry:
    entry = KernelEntry(name, pallas, composed, doc)
    _KERNELS[name] = entry
    _ensure_provider()
    return entry


def _ensure_provider():
    global _PROVIDER_REGISTERED
    if _PROVIDER_REGISTERED:
        return
    try:
        from ..observability import register_provider

        register_provider("fused_kernels", kernel_table)
        _PROVIDER_REGISTERED = True
    except Exception:  # mid-build partial package
        pass


def _backend() -> str:
    # no fallback: a backend that cannot be asked is an error the caller
    # must see, not a silent "cpu" (which would hide the device)
    import jax

    return jax.default_backend()


def _flag() -> str:
    from ..framework import flags as flags_mod

    return str(flags_mod.get_flags("FLAGS_fused_kernels")
               ["FLAGS_fused_kernels"]).strip()


def fused_enabled(name: str) -> bool:
    """Live per-op gate: should this call site take the fused path?

    ``auto`` (default): fused on TPU, legacy composed-XLA on CPU — tier-1
    keeps running the code it always ran. ``on``: fused everywhere (CPU
    executes the composed twin unless ``PT_PALLAS_INTERPRET=1``).
    ``off``: never. A comma-separated op list enables exactly those ops on
    any backend (e.g. ``rms_norm,rope``).
    """
    if name not in _KERNELS:
        _register_builtin()  # first touch in this process
    if name not in _KERNELS:
        return False
    mode = _flag()
    if mode == "off":
        return False
    if mode == "on":
        return True
    if mode == "auto" or not mode:
        return _backend() == "tpu"
    return name in {m.strip() for m in mode.split(",") if m.strip()}


def enabled_ops() -> Tuple[str, ...]:
    _register_builtin()  # a fresh process has an empty table
    return tuple(sorted(n for n in _KERNELS if fused_enabled(n)))


def _interpret_forced() -> bool:
    return os.environ.get("PT_PALLAS_INTERPRET", "0") == "1"


def resolve(name: str) -> Tuple[str, Callable]:
    """(impl, fn) for one fused op: ``pallas`` on TPU, ``composed`` on CPU,
    ``interpret`` (the Pallas kernel through the interpreter) when
    ``PT_PALLAS_INTERPRET=1`` — the parity-test hook. The choice is
    per-process (backend cannot change mid-process); the live gate is
    ``fused_enabled``, which call sites thread into their jit cache keys.
    """
    if name not in _KERNELS:
        _register_builtin()
    entry = _KERNELS[name]
    if _interpret_forced():
        entry.calls["interpret"] += 1
        return "interpret", functools.partial(entry.pallas, impl="interpret")
    if _backend() == "tpu":
        entry.calls["pallas"] += 1
        return "pallas", entry.pallas
    entry.calls["composed"] += 1
    return "composed", entry.composed


def kernel_table() -> Dict[str, Any]:
    """Per-op dispatch truth: which implementation each registered fused
    op resolves to right now, whether its call-site gate is open, and the
    trace-time call counts (the ``fused_kernels`` hub provider)."""
    _register_builtin()
    backend = _backend()
    mode = _flag()
    impl = "interpret" if _interpret_forced() else (
        "pallas" if backend == "tpu" else "composed")
    return {
        "flag": mode,
        "backend": backend,
        "ops": {
            name: {
                "enabled": fused_enabled(name),
                "impl": impl,
                "calls": dict(e.calls),
                "doc": e.doc,
            }
            for name, e in sorted(_KERNELS.items())
        },
    }


def _register_builtin():
    """Import the Pallas library so its ops land in the registry (safe to
    call repeatedly; imports are idempotent)."""
    from . import pallas as _  # noqa: F401


def registry() -> Dict[str, KernelEntry]:
    _register_builtin()
    return _KERNELS
