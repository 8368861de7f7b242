"""Repo self-lint: AST pass forbidding known footguns inside jit'd paths.

Reference role: the reference CI greps its op library for banned patterns
(tools/check_file_diff_approvals.sh, tools/ci_op_benchmark.sh gates);
paddle_tpu's equivalent hazards live where Python meets tracing. This pass
parses every framework source file, finds the functions that will run
UNDER A TRACE — decorated with ``jax.jit``/``partial(jax.jit, ...)``,
registered via ``@primitive(...)`` (every eager op), lexically passed to
``jax.jit(...)``, or used as Pallas kernel bodies — and flags, inside
them (nested defs included):

- SL001 error   host syncs: ``jax.device_get`` / ``.item()`` — break the
  trace or silently fetch device values to the host every step.
- SL002 warning ``print(...)`` — executes once at trace time, not per
  step (use jax.debug.print).
- SL003 error   host nondeterminism: ``time.time``/``perf_counter``,
  ``datetime.now``, ``np.random.*``, stdlib ``random.*`` — baked into the
  compiled executable as constants (the Date-in-kernel bug class).
- SL004 warning in-place subscript mutation of a traced parameter
  (``x[i] = v`` where ``x`` is an argument of the jit'd function) — jax
  arrays are immutable; use ``x.at[i].set(v)``.

Suppression: trailing ``# pd-lint: disable=SL003`` on the offending line
(or on the ``def`` line to suppress for a whole function).
"""
from __future__ import annotations

import ast
import os
from typing import Iterable, List, Optional, Set, Tuple

from .diagnostics import Diagnostic

__all__ = ["lint_file", "lint_tree", "run_selfcheck"]

_HOST_SYNCS = {"jax.device_get"}
_NONDET = (
    "time.time", "time.perf_counter", "time.monotonic", "time.time_ns",
    "datetime.now", "datetime.utcnow", "datetime.datetime.now",
    "np.random.", "numpy.random.", "random.random", "random.randint",
    "random.uniform", "random.choice", "random.shuffle", "random.sample",
)


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a call target ('jax.device_get', ...)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_partial_of_jit(call: ast.Call) -> bool:
    if not isinstance(call.func, (ast.Name, ast.Attribute)):
        return False
    name = _dotted(call.func)
    if name.split(".")[-1] != "partial" or not call.args:
        return False
    return _dotted(call.args[0]).endswith("jit")


def _jit_decorated(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            name = _dotted(dec.func)
            if name in ("jax.jit", "jit") or _is_partial_of_jit(dec):
                return True
            if name == "primitive" or name.endswith(".primitive"):
                return True  # dispatch op: always runs under jax.jit
        else:
            name = _dotted(dec)
            if name in ("jax.jit", "jit"):
                return True
    return False


class _JitSiteCollector(ast.NodeVisitor):
    """Names of functions handed to jax.jit(...) (jit set) and
    pl.pallas_call(...) (pallas set) anywhere in the module (including
    partial(fn, ...) wrappers)."""

    def __init__(self):
        self.names: Set[str] = set()
        self.pallas_names: Set[str] = set()

    def visit_Call(self, node: ast.Call):
        callee = _dotted(node.func)
        if callee.endswith("pallas_call"):
            for arg in node.args[:1]:
                self._collect(arg, self.pallas_names)
        elif callee.endswith("jit") or callee.endswith("checkpoint") or \
                callee.endswith("remat"):
            for arg in node.args[:1]:
                self._collect(arg, self.names)
        self.generic_visit(node)

    def _collect(self, arg: ast.AST, into: Set[str]):
        if isinstance(arg, ast.Name):
            into.add(arg.id)
        elif isinstance(arg, ast.Call):  # partial(fn, ...) / wrapper(fn)
            for a in arg.args[:1]:
                self._collect(a, into)


def _suppressed(src_lines: List[str], lineno: int, code: str) -> bool:
    if 0 < lineno <= len(src_lines):
        line = src_lines[lineno - 1]
        if "pd-lint:" in line and ("disable=" + code in line
                                   or "disable=all" in line):
            return True
    return False


class _BodyChecker(ast.NodeVisitor):
    """Applies the footgun rules inside one jit'd function body."""

    def __init__(self, fn: ast.FunctionDef, path: str,
                 src_lines: List[str], diags: List[Diagnostic],
                 kind: str = "jit"):
        self.fn = fn
        self.path = path
        self.src = src_lines
        self.diags = diags
        self.kind = kind  # "jit" | "pallas" (Ref stores are idiomatic)
        args = fn.args
        self.params = {a.arg for a in
                       args.posonlyargs + args.args + args.kwonlyargs}
        if args.vararg:
            self.params.add(args.vararg.arg)
        # params rebound to a new value (e.g. `sections = list(sections)`)
        # are local copies — mutating them is fine
        self.rebound = {t.id for node in ast.walk(fn)
                        if isinstance(node, ast.Assign)
                        for t in node.targets if isinstance(t, ast.Name)}

    def _emit(self, node, severity, code, message, suggestion=None):
        line = getattr(node, "lineno", self.fn.lineno)
        if _suppressed(self.src, line, code) or \
                _suppressed(self.src, self.fn.lineno, code):
            return
        self.diags.append(Diagnostic(
            severity=severity, code=code, pass_name="selfcheck",
            op=self.fn.name, location=f"{self.path}:{line}",
            message=message, suggestion=suggestion))

    def visit_Call(self, node: ast.Call):
        name = _dotted(node.func)
        if name in _HOST_SYNCS:
            self._emit(node, "error", "SL001",
                       f"jax.device_get inside jit'd `{self.fn.name}` — "
                       f"host sync in a traced path",
                       "move the fetch outside the compiled step")
        elif isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args:
            self._emit(node, "error", "SL001",
                       f".item() inside jit'd `{self.fn.name}` — "
                       f"forces a device->host sync per step",
                       "keep the value as a traced array")
        elif name == "print":
            self._emit(node, "warning", "SL002",
                       f"print() inside jit'd `{self.fn.name}` runs at "
                       f"trace time only",
                       "use jax.debug.print for per-step output")
        elif any(name == n or (n.endswith(".") and name.startswith(n))
                 for n in _NONDET):
            self._emit(node, "error", "SL003",
                       f"host nondeterminism `{name}` inside jit'd "
                       f"`{self.fn.name}` — the value is baked into the "
                       f"compiled executable as a constant",
                       "pass it in as an argument, or use jax.random")
        self.generic_visit(node)

    def _check_subscript_target(self, target):
        if self.kind == "pallas":
            return  # Ref[...] = v is THE Pallas store idiom
        if isinstance(target, ast.Subscript) and \
                isinstance(target.value, ast.Name) and \
                target.value.id in self.params and \
                target.value.id not in self.rebound:
            self._emit(
                target, "warning", "SL004",
                f"in-place subscript assignment to traced argument "
                f"`{target.value.id}` in jit'd `{self.fn.name}` — jax "
                f"arrays are immutable",
                f"use {target.value.id}.at[...].set(...)")

    def visit_Assign(self, node: ast.Assign):
        for t in node.targets:
            self._check_subscript_target(t)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._check_subscript_target(node.target)
        self.generic_visit(node)


def _walk_functions(tree: ast.Module) -> Iterable[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def lint_file(path: str, src: Optional[str] = None) -> List[Diagnostic]:
    if src is None:
        with open(path, encoding="utf-8") as f:
            src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Diagnostic(severity="error", code="SL000",
                           pass_name="selfcheck",
                           location=f"{path}:{e.lineno or 0}",
                           message=f"syntax error: {e.msg}")]
    src_lines = src.splitlines()
    collector = _JitSiteCollector()
    collector.visit(tree)
    diags: List[Diagnostic] = []
    in_kernels_dir = os.sep + "kernels" + os.sep in path
    for fn in _walk_functions(tree):
        if fn.name in collector.pallas_names or \
                (in_kernels_dir and fn.name.endswith("_kernel")):
            kind = "pallas"
        elif _jit_decorated(fn) or fn.name in collector.names:
            kind = "jit"
        else:
            continue
        _BodyChecker(fn, path, src_lines, diags, kind=kind).visit(fn)
    return diags


def lint_tree(root: str, exclude: Tuple[str, ...] = ("tests",)
              ) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in exclude and not d.startswith(".")]
        for fname in sorted(filenames):
            if fname.endswith(".py"):
                diags.extend(lint_file(os.path.join(dirpath, fname)))
    return diags


def run_selfcheck(root: Optional[str] = None) -> List[Diagnostic]:
    """Lint the installed paddle_tpu package itself (CI entry point)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return lint_tree(root)
