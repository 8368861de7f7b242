"""Device mesh environment: the NCCL-comm-registry replacement.

Reference: fleet/base/topology.py (CommunicateTopology:36 cartesian rank mesh,
HybridCommunicateGroup:117 building NCCL groups per axis) + platform
collective_helper.h NCCLCommContext. TPU-native: ONE `jax.sharding.Mesh` whose
named axes are the parallelism dimensions; "creating a comm group" becomes
naming an axis; collectives are XLA ops lowered over ICI/DCN.

Axes (superset of the reference's ['data','pipe','sharding','model'] — we add
the context/expert axes the reference lacked, SURVEY §5 long-context note):
    dp   data parallel
    pp   pipeline stages
    sdp  ZeRO sharding (parameter/optimizer-state sharding)
    mp   tensor (model) parallel
    cp   context/sequence parallel
    ep   expert parallel
"""
from __future__ import annotations

import collections
import math
import re
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXES = ("dp", "pp", "sdp", "mp", "cp", "ep")

_GLOBAL: Dict[str, Optional[object]] = {"env": None}


def kernel_mesh():
    """The live mesh a Pallas kernel call must be wrapped for, or None.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    under a live multi-device mesh every kernel call goes through
    ``run_kernel_on_mesh`` (the op brings its backward) or, forward-only,
    ``run_forward_kernel_on_mesh``; with no mesh, or one device, it is
    called directly."""
    env = _GLOBAL["env"]
    return env if env is not None and env.nranks > 1 else None


def kernel_mesh_ok(seq_local: bool = True) -> bool:
    """Can a Pallas kernel run under the live mesh? Not inside the
    pipeline's own manual region (pp > 1: a nested full-manual shard_map
    cannot be entered from there), and a kernel that needs GLOBAL sequence
    positions (``seq_local=False``: RoPE) not on a sequence-split (cp > 1)
    mesh. ``kernels.registry.resolve`` then answers with the op's jnp
    reference, which GSPMD partitions itself."""
    env = kernel_mesh()
    if env is None:
        return True
    if env.get_dim("pp") > 1:
        return False
    return seq_local or env.get_dim("cp") == 1


def _axes_if_divisible(env, names, n):
    axes = tuple(ax for ax in names if env.get_dim(ax) > 1)
    deg = math.prod(env.get_dim(ax) for ax in axes)
    if not axes or n % deg:
        return None  # stays replicated over those axes: right, only slower
    return axes if len(axes) > 1 else axes[0]


def activation_spec(shape, layout: str) -> PartitionSpec:
    """How an activation lies on the live mesh — what a kernel call hands
    ``run_kernel_on_mesh`` and what a model anchors its residual stream to
    (``models/llama.py:_mark_seq``): ONE source, so the two agree.
    ``"bshd"`` = [batch, seq, heads, head_dim] (batch over dp/sdp, heads
    over mp, the sequence whole: what attention and RoPE see).
    ``"rows"`` = [batch, (seq,) ..., hidden], the stream BETWEEN sublayers:
    batch over dp/sdp, seq over cp AND mp. With the sequence over ``mp`` a
    row-parallel layer's partial sums leave as a reduce-scatter and the next
    column-parallel layer gathers its input (Megatron's sequence-parallel
    form: the same bytes as the all-reduce, but the gather half is a
    collective this chip's compiler runs under matmuls, and the norms and
    residual adds between them touch 1/mp of the rows). A dim the degree
    does not divide is left unsplit (a sequence cp x mp does not divide
    keeps cp alone if that divides). ``"gathered"`` = the same stream where
    the vocabulary-parallel embedding hands it over: seq over cp alone, so
    the embedding's sum lands whole and the slice to ``"rows"`` behind it is
    local. None when there is no multi-device mesh (``run_kernel_on_mesh`` then needs no spec)."""
    env = kernel_mesh()
    if env is None:
        return None
    data = _axes_if_divisible(env, ("dp", "sdp"), shape[0])
    if layout == "bshd":
        return PartitionSpec(data, None,
                             _axes_if_divisible(env, ("mp",), shape[2]), None)
    rest = [None] * (len(shape) - 1)
    if len(shape) >= 3:
        rest[0] = (layout == "rows" and _axes_if_divisible(
            env, ("cp", "mp"), shape[1])) or _axes_if_divisible(
            env, ("cp",), shape[1])
    return PartitionSpec(data, *rest)


def _spec_axes(spec) -> set:
    return {ax for part in spec if part is not None
            for ax in ((part,) if isinstance(part, str) else part)}


def _manual_region(env, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=env.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def run_kernel_on_mesh(fwd, bwd, args, in_specs, out_specs, res_specs):
    """A differentiable kernel op on the live mesh, from the two halves the
    op brings: ``fwd(*args) -> (out, residuals)`` and ``bwd(residuals,
    cotangent) -> one gradient an argument``, each written for ONE shard.
    With no multi-device mesh it is the plain ``custom_vjp`` over them.

    Under a mesh the forward runs as one full-manual ``shard_map`` (operands
    split by ``in_specs``, ``PartitionSpec()`` = replicated; the residuals
    leave under ``res_specs``, which the op states) and the backward as
    another, under the same specs. The differentiation rule sits OUTSIDE
    both, so JAX transposes neither region. It must not: with
    ``check_vma=False`` the transpose of a ``shard_map`` cannot know that an
    operand was replicated over a mesh axis its spec does not name, so it
    divides the cotangent by that axis's size and ``psum``s the operand's
    gradient over it — one all-reduce of a whole activation for every
    operand replicated over an axis, to add equal parts back together
    (PR 31: two a decoder layer over ``mp``). ``check_vma=True`` is not the
    way out on jax 0.9: the Pallas interpreter fails under it.

    What the math does sum is summed here, for every op: a gradient is
    ``psum``med over the axes that split another operand or an output but
    not its own operand — there each shard saw its share of the rows and
    holds a partial sum (a norm's ``dw`` over ``dp``/``sdp``/``cp``). Over
    an axis no spec names every shard computed the same value, and nothing
    is reduced. A half must therefore hold no collective of its own."""
    from ..kernels.pallas._common import differentiable

    env = kernel_mesh()
    if env is None:
        return differentiable(fwd, bwd)(*args)
    in_specs = tuple(in_specs)
    split = set().union(*map(_spec_axes, in_specs + tuple(jax.tree.leaves(
        out_specs, is_leaf=lambda s: isinstance(s, PartitionSpec)))))
    partial_over = [tuple(ax for ax in env.axis_names
                          if ax in split - _spec_axes(spec))
                    for spec in in_specs]

    def bwd_summed(res, ct):
        return tuple(jax.lax.psum(g, axes) if axes else g
                     for g, axes in zip(bwd(res, ct), partial_over))

    return differentiable(
        _manual_region(env, fwd, in_specs, (out_specs, res_specs)),
        _manual_region(env, bwd_summed, (res_specs, out_specs), in_specs),
    )(*args)


def run_forward_kernel_on_mesh(fn, args, in_specs, out_specs):
    """``fn(*args)`` as one full-manual ``shard_map`` over the live mesh, or
    a plain call when there is no multi-device mesh. If ``fn`` is
    differentiated, JAX transposes the region (see ``run_kernel_on_mesh``
    for what that costs wherever a spec leaves a mesh axis out)."""
    env = kernel_mesh()
    if env is None:
        return fn(*args)
    return _manual_region(env, fn, tuple(in_specs), out_specs)(*args)


class MeshEnv:
    """The live mesh + axis degrees (HybridCommunicateGroup role)."""

    def __init__(self, degrees: Dict[str, int], devices: Optional[Sequence] = None):
        devices = list(devices if devices is not None else jax.devices())
        full = {ax: int(degrees.get(ax, 1)) for ax in AXES}
        n = math.prod(full.values())
        if n != len(devices):
            raise ValueError(
                f"product of axis degrees {full} = {n} != device count {len(devices)}")
        self.degrees = full
        # Axis order chooses ICI locality: mp (heaviest traffic) innermost.
        self.axis_names = tuple(ax for ax in ("pp", "dp", "sdp", "ep", "cp", "mp"))
        shape = tuple(full[ax] for ax in self.axis_names)
        dev_array = np.asarray(devices).reshape(shape)
        self.mesh = Mesh(dev_array, self.axis_names)

    # -- queries (CommunicateTopology API shape) ----------------------------
    def get_dim(self, axis: str) -> int:
        return self.degrees[axis]

    @property
    def nranks(self) -> int:
        return math.prod(self.degrees.values())

    def sharding_for(self, spec: PartitionSpec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def __repr__(self):
        used = {k: v for k, v in self.degrees.items() if v > 1}
        return f"MeshEnv({used or 'single-device'}, devices={self.nranks})"


def init_mesh(dp=1, mp=1, pp=1, sharding=1, cp=1, ep=1, devices=None) -> MeshEnv:
    """Create + install the global mesh (fleet._init_hybrid_parallel_env role)."""
    env = MeshEnv({"dp": dp, "mp": mp, "pp": pp, "sdp": sharding, "cp": cp, "ep": ep},
                  devices)
    _GLOBAL["env"] = env
    return env


_COLLECTIVE = re.compile(
    r"= (\(.*?\)|\S+) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")
_SHAPE = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")
_GROUPS = re.compile(r"(?:replica_groups|source_target_pairs)="
                     r"(\{\{.*?\}\}|\{\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")


def _device_groups(written: str, n: int) -> np.ndarray:
    """An HLO ``replica_groups`` / ``source_target_pairs`` attribute as rows
    of partition ids: ``{{0,2},{1,3}}``, ``{}`` (everyone) or the iota form
    ``[groups,size]<=[dims]T(perm)``."""
    if written == "{}":
        return np.arange(n).reshape(1, n)
    if written.startswith("{"):
        return np.asarray([[int(i) for i in g.split(",")]
                           for g in re.findall(r"\{([\d,]+)\}", written)])
    shape, dims, perm = re.fullmatch(
        r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", written).groups()
    ints = lambda t: [int(i) for i in t.split(",")]  # noqa: E731
    ids = np.arange(math.prod(ints(dims))).reshape(ints(dims))
    if perm:
        ids = ids.transpose(ints(perm))
    return ids.reshape(ints(shape))


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> (.*) \{$")
_CALLER = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*\bcalls=%([\w.\-]+)")


def compiled_collectives(text: str, mesh: Mesh) -> List[dict]:
    """The collectives of one compiled SPMD program, read from its text
    (``lowered.compile().as_text()``): rows ``{"axes", "op", "shapes",
    "count", "async"}`` — the mesh axes a group of the instruction spans,
    its opcode, its result shapes as written (``"f32[4,32,64]"``; several
    where XLA combined operands), how many such instructions the program
    holds, and how many of those are asynchronous start / done PAIRS (other
    work can be scheduled between the two; the rest hold the device until
    they end). An instruction inside a loop body counts once, whatever the
    trip count. Partition ``i`` is ``mesh.devices.flat[i]``, jit's device
    assignment.

    The TPU compiler writes two forms no opcode names, and both are read
    for what they are. A reduce-scatter is a ``fusion`` calling a
    computation ``%all-reduce-scatter*`` (an all-reduce and the slice of
    it): one synchronous ``reduce-scatter`` of the computation's result. An
    asynchronous collective is a fusion NAMED ``async-collective-start``
    whose computation holds the collective; the fusions that carry it on
    under other work (``%async_collective_fusion*``) and the one named
    ``async-collective-done`` repeat the instruction and are not counted
    again. The CPU backend writes neither (a reduce-scatter stays an
    all-reduce and a ``dynamic-slice``; nothing is asynchronous), so there
    the rows say the kinds alone."""
    caller, found, comp, result = {}, [], "", ""
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            comp, result = head.groups()
            continue
        called = _CALLER.match(line)
        if called is not None:
            caller[called.group(2)] = called.group(1)
        m = _COLLECTIVE.search(line)
        if m is not None:
            found.append((comp, result, line, m))
    counts, pairs = collections.Counter(), collections.Counter()
    for comp, result, line, m in found:
        by = caller.get(comp, "")
        if by.startswith("async-collective-done") or \
                comp.startswith("async_collective_fusion"):
            continue  # a pair's later steps: its start was counted
        op, shapes = m.group(2), tuple(_SHAPE.findall(m.group(1)))
        if m.group(3) and op in ("all-gather", "collective-permute"):
            shapes = shapes[len(shapes) // 2:]  # a start's type: (operands, results)
        if comp.startswith("all-reduce-scatter"):
            op, shapes = "reduce-scatter", tuple(_SHAPE.findall(result))
        g = _GROUPS.search(line)
        groups = _device_groups(g.group(1) if g else "{}", mesh.devices.size)
        coords = np.stack(np.unravel_index(groups, mesh.devices.shape), -1)
        spans = (coords != coords[:, :1]).any(axis=(0, 1))
        axes = tuple(ax for ax, on in zip(mesh.axis_names, spans) if on)
        counts[axes, op, shapes] += 1
        pairs[axes, op, shapes] += bool(
            m.group(3) or by.startswith("async-collective-start"))
    return [{"axes": a, "op": o, "shapes": s, "count": c,
             "async": pairs[a, o, s]}
            for (a, o, s), c in sorted(counts.items())]


def auto_mesh(devices=None) -> MeshEnv:
    """All devices on dp (pure data parallel) — the default world."""
    devices = list(devices if devices is not None else jax.devices())
    return init_mesh(dp=len(devices), devices=devices)


def get_mesh_env() -> Optional[MeshEnv]:
    return _GLOBAL["env"]


def require_mesh_env() -> MeshEnv:
    env = _GLOBAL["env"]
    if env is None:
        env = auto_mesh()
    return env


def reset_mesh():
    _GLOBAL["env"] = None
