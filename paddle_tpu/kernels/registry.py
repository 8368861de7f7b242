"""Kernel registry: the ONE seam between a fused op's call sites and its
implementations.

Reference role: paddle/fluid/operators/fused/ — the reference ships its
hot-path fusions (fused_attention, fused_ffn, fused_rms_norm) as separate
CUDA kernels picked by a pass. TPU-native mapping: each op of
``kernels/pallas/`` is one public function holding exactly two
implementations — the Pallas TPU kernel and a plain ``jnp`` reference —
and ``resolve(name)`` is the one function that says which runs, from what
the process can observe:

- ``"reference"`` where a Mosaic call cannot run under the live mesh
  (``distributed.mesh.kernel_mesh_ok``: ``pp > 1``; ``cp > 1`` for a
  kernel that needs global sequence positions), whatever the platform;
- ``"interpret"`` (the Pallas kernel through the Pallas interpreter) when
  ``PT_PALLAS_INTERPRET=1`` — the parity tests' hook, not a production
  path (the interpreter is slow);
- ``"pallas"`` on the TPU, ``"reference"`` on any other backend.

No flag selects an implementation. Layer code passes the answer down as a
primitive ATTR (``nn/functional/common.py``, ``models/llama.py``), as
``sdpa`` does with its ``impl``: the live mesh can change inside a process,
and the op cache and the ``analysis.retrace`` auditor must see it.

``kernel_table()`` is the introspection surface (per-op answer + decision
counts), registered as the ``fused_kernels`` observability provider; the
PR-9 planner prices the same entries via ``cost_model.fused``.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Tuple

__all__ = ["register_kernel", "resolve", "kernel_table", "enabled_ops",
           "KernelEntry", "IMPLS"]

IMPLS = ("pallas", "interpret", "reference")


class KernelEntry:
    __slots__ = ("name", "seq_local", "doc", "calls")

    def __init__(self, name: str, seq_local: bool = True, doc: str = ""):
        self.name = name
        # False: the kernel computes from GLOBAL sequence positions (RoPE)
        # and cannot run on a sequence-split mesh
        self.seq_local = seq_local
        self.doc = doc
        # decisions taken, by answer: one per call that reaches the seam —
        # every eager op call, and once per trace of a compiled program
        # (a cached program does not decide again)
        self.calls: Dict[str, int] = dict.fromkeys(IMPLS, 0)


_KERNELS: Dict[str, KernelEntry] = {}
_PROVIDER_REGISTERED = False


def register_kernel(name: str, *, seq_local: bool = True,
                    doc: str = "") -> KernelEntry:
    entry = KernelEntry(name, seq_local, doc)
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
    # the one platform probe every decision reads, at call time. No
    # fallback: a backend that cannot be asked is an error the caller
    # must see, not a silent "cpu" (which would hide the device)
    import jax

    return jax.default_backend()


def _decide(entry: KernelEntry) -> str:
    from ..distributed.mesh import kernel_mesh_ok

    if not kernel_mesh_ok(seq_local=entry.seq_local):
        return "reference"  # GSPMD partitions the jnp form itself
    if os.environ.get("PT_PALLAS_INTERPRET", "0") == "1":
        return "interpret"
    return "pallas" if _backend() == "tpu" else "reference"


def resolve(name: str) -> str:
    """Which implementation of op ``name`` runs here and now: one of
    ``IMPLS`` (see the module docstring for the rule). Counted in the
    entry's ``calls``."""
    entry = registry()[name]
    impl = _decide(entry)
    entry.calls[impl] += 1
    return impl


def enabled_ops() -> Tuple[str, ...]:
    """The ops whose Pallas kernel this platform would run (the planner's
    default ``fused_kernels`` set). Of the platform alone: the planner
    prices candidate meshes, not the live one."""
    return tuple(sorted(registry())) if _backend() == "tpu" else ()


def kernel_table() -> Dict[str, Any]:
    """Per-op dispatch truth: the implementation each registered op
    resolves to right now (backend, live mesh, interpreter hook) and how
    many decisions went to each so far (``KernelEntry.calls``) — the
    ``fused_kernels`` hub provider. Reading the table counts nothing."""
    return {
        "backend": _backend(),
        "ops": {
            name: {
                "impl": _decide(e),
                "calls": dict(e.calls),
                "doc": e.doc,
            }
            for name, e in sorted(registry().items())
        },
    }


def registry() -> Dict[str, KernelEntry]:
    """The table, with the Pallas library's ops in it (importing the
    package registers them; a fresh process has an empty table)."""
    from . import pallas as _  # noqa: F401

    return _KERNELS
