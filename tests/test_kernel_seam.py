"""The one kernel seam (ISSUE 30): ``kernels.registry.resolve`` decides which
implementation of a registered op runs, from what the process can observe —
the backend, the live mesh, the parity hook — and nothing else in the program
makes that decision.

The backend is steered to ``"tpu"`` the way ``benchmark/rehearse_aot.py``
does it (``registry._backend`` is the one platform probe every decision
reads): in the test, not through an option of the program.
"""
import ast
import os

import jax
import pytest

from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.kernels import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = ("dsa_index_scores", "mhc_post", "mhc_pre", "mla_paged_attention",
       "mla_sparse_attention", "moe_dispatch", "paged_attention",
       "ranged_paged_attention", "retention_chunk", "retention_step",
       "rms_norm", "rope", "ssm_step")

# situation -> (backend the probe reports, PT_PALLAS_INTERPRET, live mesh)
SITUATIONS = {
    "cpu": ("cpu", None, None),
    "tpu": ("tpu", None, None),
    "interpret": ("cpu", "1", None),
    "tpu_pp2": ("tpu", None, {"pp": 2, "dp": 4}),
    "tpu_cp2": ("tpu", None, {"cp": 2, "dp": 4}),
}

# every op answers by platform and hook alone, and gives way to the reference
# inside the pipeline's manual region; a sequence-split mesh rules out only
# the kernel that needs GLOBAL positions
WANT = {"cpu": "reference", "tpu": "pallas", "interpret": "interpret",
        "tpu_pp2": "reference"}
CASES = [(op, s, want) for op in OPS for s, want in WANT.items()] + \
    [("rope", "tpu_cp2", "reference"), ("rms_norm", "tpu_cp2", "pallas")]


@pytest.mark.parametrize("op,situation,want", CASES)
def test_resolve_decides_from_what_it_observes(op, situation, want,
                                               monkeypatch):
    backend, interpret, mesh = SITUATIONS[situation]
    monkeypatch.setattr(registry, "_backend", lambda: backend)
    if interpret is None:
        monkeypatch.delenv("PT_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    if mesh is not None and len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    try:
        if mesh is not None:
            mesh_mod.init_mesh(**mesh)
        before = dict(registry.registry()[op].calls)
        assert registry.resolve(op) == want
        table = registry.kernel_table()
        assert table["backend"] == backend
        assert table["ops"][op]["impl"] == want
        # one decision, counted under its answer and nowhere else
        after = table["ops"][op]["calls"]
        assert after == {**before, want: before[want] + 1}
        # the planner's default set is of the platform alone
        assert registry.enabled_ops() == (OPS if backend == "tpu" else ())
    finally:
        mesh_mod.reset_mesh()


def _program_files():
    for root, _dirs, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(root, name)


def test_nothing_else_decides():
    """An AST walk over the program: only ``kernels/registry.py`` (and
    flash attention's own ``attention_backend``: ROADMAP D4b) calls
    ``kernel_mesh_ok``; only the registry reads the interpreter hook; no
    kernel flag and no ``fused=`` attribute is left to read."""
    mesh_ok, hook, leftovers = set(), set(), []
    for path in _program_files():
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                fn = node.func
                name = fn.id if isinstance(fn, ast.Name) else \
                    getattr(fn, "attr", None)
                if name == "kernel_mesh_ok":
                    mesh_ok.add(rel)
                if name in ("fused_enabled", "_rms_fused_gate"):
                    leftovers.append((rel, node.lineno, name))
                leftovers += [(rel, node.lineno, "fused=")
                              for kw in node.keywords if kw.arg == "fused"]
            elif isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                if node.value == "PT_PALLAS_INTERPRET":
                    hook.add(rel)
                if node.value in ("FLAGS_fused_kernels", "fused_kernels") \
                        and rel != "paddle_tpu/kernels/registry.py":
                    # the registry names its hub provider "fused_kernels"
                    leftovers.append((rel, node.lineno, node.value))
    assert mesh_ok == {"paddle_tpu/kernels/registry.py",
                       "paddle_tpu/nn/functional/attention.py"}
    assert hook == {"paddle_tpu/kernels/registry.py"}
    assert not leftovers, leftovers
