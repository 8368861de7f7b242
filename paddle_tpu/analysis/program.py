"""Capture + op-graph: the front half of every analysis pass.

Reference role: the graph-IR half of paddle/fluid/framework/ir — passes
walk an op-graph with per-op shape/dtype annotations. TPU-native mapping:
the IR already exists (the jaxpr jax builds for every compiled step), so
`capture()` obtains a ClosedJaxpr from any callable / jit.TrainStep /
ShardedTrainStep / static Program WITHOUT running it, and `Program` walks
it (recursing into pjit / scan / while / cond / shard_map / remat
sub-jaxprs) into a flat list of `OpNode`s annotated with shapes, dtypes,
flops, bytes and user source locations. Every other module in
`paddle_tpu.analysis` consumes this walk.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import core as jcore

from .diagnostics import Diagnostic

__all__ = ["OpNode", "Program", "capture", "run_passes", "register_pass",
           "PASSES"]

# jaxpr classes moved around across jax versions; resolve defensively
_JAXPR_TYPES: Tuple[type, ...]
try:
    _JAXPR_TYPES = (jcore.Jaxpr, jcore.ClosedJaxpr)
except AttributeError:  # pragma: no cover - future jax
    from jax.extend import core as jext_core

    _JAXPR_TYPES = (jext_core.Jaxpr, jext_core.ClosedJaxpr)


def _user_location(eqn) -> Optional[str]:
    """file:line of the user frame that created this eqn, best-effort."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return None
        return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        return None


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * jnp.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _aval_str(aval) -> str:
    try:
        return f"{jnp.dtype(aval.dtype).name}[{','.join(map(str, aval.shape))}]"
    except Exception:
        return str(aval)


def _dot_general_flops(eqn) -> int:
    (lc, _rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = math.prod(lhs.shape[i] for i in lb) if lb else 1
    contract = math.prod(lhs.shape[i] for i in lc) if lc else 1
    lhs_free = lhs.size // max(batch * contract, 1)
    rhs_free = rhs.size // max(batch * contract, 1)
    return 2 * batch * contract * lhs_free * rhs_free


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    # each output element reduces over (kernel spatial x in-features)
    dn = eqn.params.get("dimension_numbers")
    try:
        reduce_size = rhs.size // rhs.shape[dn.rhs_spec[0]]
    except Exception:
        reduce_size = rhs.size
    return 2 * out.size * reduce_size


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_general_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    out_size = sum(int(v.aval.size) for v in eqn.outvars
                   if hasattr(v.aval, "size"))
    if name in ("exp", "log", "tanh", "logistic", "erf", "sin", "cos",
                "rsqrt", "sqrt", "pow", "integer_pow"):
        return 8 * out_size  # transcendental weight
    return out_size


@dataclass
class OpNode:
    """One jaxpr equation, annotated. `path` is the call chain of enclosing
    call-like eqns ("pjit:train_step", "scan", ...); `mult` is the product
    of known trip counts along that path (scan length etc.) so per-node
    flops/bytes sum to whole-program totals."""

    name: str
    in_avals: List[Any]
    out_avals: List[Any]
    flops: int
    bytes_in: int
    bytes_out: int
    location: Optional[str]
    path: Tuple[str, ...] = ()
    mult: int = 1
    params: Dict[str, Any] = field(default_factory=dict)
    eqn: Any = None  # the live JaxprEqn, for passes needing var identity
    is_leaf: bool = True  # no sub-jaxprs (real computation, not a call)

    @property
    def total_flops(self) -> int:
        return self.flops * self.mult

    @property
    def total_bytes(self) -> int:
        return (self.bytes_in + self.bytes_out) * self.mult

    def describe(self) -> str:
        ins = ", ".join(_aval_str(a) for a in self.in_avals[:4])
        outs = ", ".join(_aval_str(a) for a in self.out_avals[:4])
        where = "/".join(self.path) or "<top>"
        return f"{self.name}({ins}) -> {outs}  @{where}"


# params that hold sub-jaxprs but re-execute them (trip-count semantics)
_CALL_LABELS = {
    "jit": lambda e: f"pjit:{e.params.get('name', '')}",  # jax >= 0.7 name
    "pjit": lambda e: f"pjit:{e.params.get('name', '')}",
    "closed_call": lambda e: "closed_call",
    "core_call": lambda e: "call",
    "xla_call": lambda e: "xla_call",
    "remat2": lambda e: "remat",
    "checkpoint": lambda e: "remat",
    "custom_jvp_call": lambda e: "custom_jvp",
    "custom_vjp_call": lambda e: "custom_vjp",
    "custom_vjp_call_jaxpr": lambda e: "custom_vjp",
    "shard_map": lambda e: "shard_map",
    "scan": lambda e: f"scan[{e.params.get('length', '?')}]",
    "while": lambda e: "while",
    "cond": lambda e: "cond",
}


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(param_name, jaxpr) pairs hiding inside this eqn's params."""
    out: List[Tuple[str, Any]] = []
    for k, v in eqn.params.items():
        if isinstance(v, _JAXPR_TYPES):
            out.append((k, v))
        elif isinstance(v, (tuple, list)):
            for i, item in enumerate(v):
                if isinstance(item, _JAXPR_TYPES):
                    out.append((f"{k}[{i}]", item))
    return out


def _as_open(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


class Program:
    """A captured ClosedJaxpr walked into a flat annotated op list."""

    def __init__(self, closed_jaxpr, label: str = "program",
                 donated_invars: Sequence[bool] = ()):
        self.closed_jaxpr = closed_jaxpr
        self.jaxpr = _as_open(closed_jaxpr)
        self.label = label
        self.donated_invars = tuple(donated_invars)
        self.nodes: List[OpNode] = []
        self._walk(self.jaxpr, path=(), mult=1)

    # -- walking -------------------------------------------------------------
    def _walk(self, jaxpr, path: Tuple[str, ...], mult: int):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            subs = _sub_jaxprs(eqn)
            node = OpNode(
                name=name,
                in_avals=[v.aval for v in eqn.invars],
                out_avals=[v.aval for v in eqn.outvars],
                flops=_eqn_flops(eqn),
                bytes_in=sum(_aval_bytes(v.aval) for v in eqn.invars),
                bytes_out=sum(_aval_bytes(v.aval) for v in eqn.outvars),
                location=_user_location(eqn),
                path=path,
                mult=mult,
                params={k: v for k, v in eqn.params.items()
                        if isinstance(v, (int, float, str, bool, tuple))
                        and k not in ("jaxpr",)},
                eqn=eqn,
                is_leaf=not subs,
            )
            self.nodes.append(node)
            if not subs:
                continue
            label = _CALL_LABELS.get(name, lambda e: name)(eqn)
            sub_mult = mult
            if name == "scan":
                sub_mult = mult * int(eqn.params.get("length", 1) or 1)
            # while-loop trip counts are unknowable statically; keep mult
            # (lower bound) — passes that care read node.name == "while"
            for _, sub in subs:
                self._walk(_as_open(sub), path + (label,), sub_mult)

    # -- aggregate views -----------------------------------------------------
    def leaf_nodes(self) -> List[OpNode]:
        """Nodes that are real computation (no sub-jaxpr call wrappers)."""
        return [n for n in self.nodes if n.is_leaf]

    def total_flops(self) -> int:
        return sum(n.total_flops for n in self.leaf_nodes())

    def total_bytes(self) -> int:
        return sum(n.total_bytes for n in self.leaf_nodes())

    def count_ops(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for n in self.leaf_nodes():
            out[n.name] = out.get(n.name, 0) + n.mult
        return out

    def find(self, name: str) -> List[OpNode]:
        return [n for n in self.nodes if n.name == name]

    def summary(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "num_eqns": len(self.nodes),
            "total_flops": self.total_flops(),
            "total_bytes": self.total_bytes(),
            "top_ops": sorted(self.count_ops().items(),
                              key=lambda kv: -kv[1])[:12],
        }


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

def _tensorify(fn: Callable) -> Callable:
    """Wrap an eager-layer callable so it maps array pytrees to array
    pytrees (make_jaxpr traces arrays; the eager op layer wants Tensors)."""
    from ..core.tensor import Tensor

    def runner(*arrays):
        from ..core import autograd

        wrapped = [Tensor(a) if hasattr(a, "dtype") else a for a in arrays]
        with autograd.no_grad():
            out = fn(*wrapped)
        return jax.tree_util.tree_map(
            lambda t: t.data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor))

    return runner


def _data_of(x):
    from ..core.tensor import Tensor

    return x.data if isinstance(x, Tensor) else jnp.asarray(x)


def _capture_train_step(step, batch) -> Tuple[Any, str, Tuple[bool, ...]]:
    """TrainStep / ShardedTrainStep -> (ClosedJaxpr over one step, label,
    donated_invars mask aligned with the jaxpr invars)."""
    from ..framework import random as random_mod

    arrays = [_data_of(b) for b in batch]
    opt = step.optimizer
    params = [p.data for p in step.train_params]
    states = [opt._accumulators[id(p)] for p in step.train_params]
    frozen = [t.data for t in step.frozen]
    lr = jnp.asarray(opt.get_lr(), jnp.float32)
    step_no = jnp.asarray(int(opt._global_step) + 1, jnp.int32)
    # a pure analysis must not advance the training run's random stream:
    # draw the example key with the generator state restored afterwards
    gen = random_mod.default_generator()
    saved_state = gen.get_state()
    try:
        key = random_mod.next_key()
    finally:
        gen.set_state(saved_state)
    build = step._build
    try:
        fn = build(arrays)      # ShardedTrainStep._build(batch_arrays)
    except TypeError:
        fn = build()            # jit.TrainStep._build()
    args = (params, states, frozen, lr, step_no, key, *arrays)
    closed = jax.make_jaxpr(fn)(*args)
    donate = getattr(step, "donate", False)
    # donated leaves: params + states (donate_argnums=(0, 1) in both builders)
    n_donated = len(jax.tree_util.tree_leaves((params, states)))
    n_in = len(_as_open(closed).invars)
    mask = tuple(i < n_donated for i in range(n_in)) if donate \
        else (False,) * n_in
    return closed, type(step).__name__, mask


def capture(target, *args, label: Optional[str] = None,
            **kwargs) -> Program:
    """Obtain a `Program` (ClosedJaxpr + op-graph) from:

    - a ClosedJaxpr (walked as-is),
    - a `jit.TrainStep` / `distributed.ShardedTrainStep` (pass the example
      batch as *args; captures the whole fwd+bwd+update step),
    - a `static.Program` (replayed through the trace it would execute),
    - any callable over Tensors/arrays (example inputs in *args).

    Nothing is executed on device: the callable is traced abstractly.
    """
    if isinstance(target, _JAXPR_TYPES):
        return Program(target, label or "jaxpr")
    if hasattr(target, "_build") and hasattr(target, "train_params"):
        closed, auto_label, donated = _capture_train_step(target, args)
        return Program(closed, label or auto_label, donated)
    # static.Program (compat record-and-replay): trace its replay over the
    # declared feed placeholders — the exact op list Executor.run executes
    if hasattr(target, "_replay") and hasattr(target, "feeds"):
        aids, feed_arrays = [], []
        for _name, (aid, dtype, shape) in target.feeds.items():
            dummy = tuple(1 if (d is None or (isinstance(d, int) and d < 0))
                          else int(d) for d in shape)
            aids.append(aid)
            feed_arrays.append(jnp.zeros(dummy, dtype))
        if not target.nodes:
            raise ValueError("analysis.capture: static Program records no ops")
        last = target.nodes[-1]

        def replay(*arrays):
            env = dict(zip(aids, arrays))
            env = target._replay(env)
            return [env[oid] for oid in last.out_ids]

        closed = jax.make_jaxpr(replay)(*feed_arrays)
        return Program(closed, label or "static.Program")
    if callable(target):
        arrays = [_data_of(a) if hasattr(a, "shape") or hasattr(a, "dtype")
                  else a for a in args]
        try:
            # plain jax callables (shard_map'd fns, jitted fns) take arrays
            closed = jax.make_jaxpr(target)(*arrays)
        except Exception:
            # eager-layer callables want Tensors
            closed = jax.make_jaxpr(_tensorify(target))(*arrays)
        return Program(closed, label or getattr(target, "__name__", "fn"))
    raise TypeError(f"analysis.capture: cannot capture {type(target)!r}")


# ---------------------------------------------------------------------------
# pass runner
# ---------------------------------------------------------------------------

PASSES: Dict[str, Callable[..., List[Diagnostic]]] = {}


def register_pass(name: str):
    def deco(fn):
        PASSES[name] = fn
        return fn

    return deco


def run_passes(program: Program,
               passes: Optional[Sequence[str]] = None,
               **config) -> List[Diagnostic]:
    """Run the named jaxpr-level passes (default: all registered) over a
    captured Program; returns the concatenated Diagnostic list."""
    diags: List[Diagnostic] = []
    for name in (passes if passes is not None else sorted(PASSES)):
        if name not in PASSES:
            raise KeyError(f"unknown analysis pass {name!r}; "
                           f"registered: {sorted(PASSES)}")
        diags.extend(PASSES[name](program, **config))
    return diags
