"""Static peak-HBM estimator: a live-range sweep over the op-graph.

Reference role: paddle/fluid/framework/ir/memory_optimize_pass — the
reference plans buffer reuse from variable live ranges at compile time.
TPU-native mapping: XLA owns the real buffer assignment, but it only tells
you it didn't fit AFTER a TPU compile; this pass walks the captured jaxpr
the same way (birth = defining eqn, death = last use) and reports the peak
resident-byte estimate up front, on CPU, so OOMs and fat intermediates are
visible before a chip is involved. Donated inputs (TrainStep params/opt
state) die at last use — modeling XLA's buffer donation; non-donated
inputs and all outputs are resident for the whole program.

The estimate is an upper bound relative to XLA (no fusion, no rematerial-
ization inside the sweep) and a lower bound in one place: `while` bodies
with unknown trip counts contribute one iteration's live set.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .diagnostics import Diagnostic
from .program import (Program, register_pass, _aval_bytes, _sub_jaxprs,
                      _as_open, _user_location)

__all__ = ["PeakEstimate", "estimate_peak", "estimate_train_step_hbm",
           "estimate_offload_stream_hbm", "offload_stream_plan",
           "stream_plan_check", "memory_pass", "device_hbm_bytes"]


def device_hbm_bytes(device=None) -> int:
    """Per-device memory budget as the backend itself reports it
    (``memory_stats()["bytes_limit"]``). There is no default: a backend
    that reports none (the CPU test backend) makes this raise, and the
    caller passes ``hbm_bytes=`` explicitly instead."""
    import jax

    # local: under jax.distributed, devices()[0] can belong to another
    # process and expose no stats to this one
    dev = jax.local_devices()[0] if device is None else device
    stats = dev.memory_stats() or {}
    if not stats.get("bytes_limit"):
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"memory_stats()['bytes_limit']; pass hbm_bytes= explicitly")
    return int(stats["bytes_limit"])


@dataclass
class PeakEstimate:
    peak_bytes: int
    resident_bytes: int          # non-donated inputs + outputs (always live)
    peak_step: int               # eqn index (flattened) where the peak occurs
    peak_op: Optional[str]
    peak_location: Optional[str]
    breakdown: Dict[str, int] = field(default_factory=dict)

    @property
    def peak_gb(self) -> float:
        return self.peak_bytes / 1e9

    def to_dict(self) -> Dict[str, Any]:
        return {"peak_bytes": self.peak_bytes, "peak_gb": round(self.peak_gb, 3),
                "resident_bytes": self.resident_bytes,
                "peak_op": self.peak_op, "peak_location": self.peak_location,
                "breakdown": self.breakdown}


def _var_key(v):
    # jaxpr Var objects are unique per binding; Literals carry values inline
    return id(v)


def _size_of(v) -> int:
    aval = getattr(v, "aval", None)
    return _aval_bytes(aval) if aval is not None else 0


def _inline_eqns(jaxpr, mult: int = 1) -> List[Tuple[Any, int]]:
    """Flatten call-like eqns whose sub-jaxpr vars alias the caller's
    (pjit/closed_call/remat/custom_*): substitute outer vars for inner
    invars so live ranges span the call boundary. Loop-like eqns (scan /
    while / cond / shard_map) stay atomic — their internal peak is computed
    recursively and attached to the eqn entry as (eqn, mult, internal)."""
    out: List[Tuple[Any, int]] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = _sub_jaxprs(eqn)
        if not subs:
            out.append((eqn, mult))
            continue
        # ("jit" is what jax >= 0.7 calls the pjit primitive)
        if name in ("jit", "pjit", "closed_call", "core_call", "xla_call",
                    "remat2", "checkpoint", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr"):
            # splice the (first) sub-jaxpr inline; var identity is preserved
            # via a rename map inner-invar -> outer operand
            sub = _as_open(subs[0][1])
            out.extend(_spliced(eqn, sub, mult))
        else:
            out.append((eqn, mult))
    return out


def _spliced(eqn, sub, mult) -> List[Tuple[Any, int]]:
    """Rewrite sub-jaxpr eqns with outer var identities at the boundary."""
    rename: Dict[int, Any] = {}
    for inner, outer in zip(sub.invars, eqn.invars):
        rename[id(inner)] = outer
    for inner, outer in zip(sub.outvars, eqn.outvars):
        rename[id(inner)] = outer

    class _Bound:
        """eqn view with boundary vars renamed to the caller's."""

        __slots__ = ("invars", "outvars", "primitive", "params",
                     "source_info")

        def __init__(self, e):
            self.invars = [rename.get(id(v), v) for v in e.invars]
            self.outvars = [rename.get(id(v), v) for v in e.outvars]
            self.primitive = e.primitive
            self.params = e.params
            self.source_info = e.source_info

    out: List[Tuple[Any, int]] = []
    for e in _inline_eqns(sub, mult):
        inner_eqn, m = e
        out.append((_Bound(inner_eqn), m))
    return out


def _internal_peak(eqn) -> int:
    """Peak of a loop-like eqn's body BEYOND its boundary operands (those
    already sit in the caller's live set)."""
    subs = _sub_jaxprs(eqn)
    peak = 0
    for _, sub in subs:
        open_sub = _as_open(sub)
        est = estimate_peak_jaxpr(open_sub)
        boundary = sum(_size_of(v) for v in open_sub.invars) + \
            sum(_size_of(v) for v in open_sub.constvars)
        peak = max(peak, est.peak_bytes - boundary)
    return max(peak, 0)


def estimate_peak_jaxpr(jaxpr, donated_invars: Sequence[bool] = (),
                        label: str = "") -> PeakEstimate:
    """Live-range sweep over one (open) jaxpr."""
    eqns = _inline_eqns(jaxpr)
    donated = list(donated_invars) + [False] * (len(jaxpr.invars)
                                               - len(donated_invars))
    # last-use step per var; inputs are born at -1, outputs die at +inf
    last_use: Dict[int, int] = {}
    for i, (eqn, _m) in enumerate(eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and type(v).__name__ != "Literal":
                last_use[_var_key(v)] = i
    outvar_keys = {_var_key(v) for v in jaxpr.outvars
                   if type(v).__name__ != "Literal"}
    n_steps = len(eqns)
    for k in outvar_keys:
        last_use[k] = n_steps  # program outputs live to the end

    # non-donated inputs + constvars are resident for the whole program
    live = 0
    alive: Dict[int, int] = {}  # var key -> bytes

    def birth(v):
        nonlocal live
        k = _var_key(v)
        if k in alive:
            return
        sz = _size_of(v)
        alive[k] = sz
        live += sz

    permanent = set()
    for i, v in enumerate(jaxpr.invars):
        k = _var_key(v)
        birth(v)
        if not (i < len(donated) and donated[i]):
            permanent.add(k)
    for v in jaxpr.constvars:
        birth(v)
        permanent.add(_var_key(v))
    resident = sum(alive[k] for k in permanent)

    def _sig(v):
        aval = getattr(v, "aval", None)
        try:
            return (tuple(aval.shape), str(aval.dtype))
        except Exception:
            return None

    peak = live
    peak_step, peak_op, peak_loc = -1, None, None
    for i, (eqn, _m) in enumerate(eqns):
        # buffer-reuse model (XLA's donation aliasing + fusion in-place
        # update): an output whose shape/dtype matches an operand dying at
        # this eqn takes over that operand's buffer instead of allocating
        dying = {}
        for v in eqn.invars:
            k = _var_key(v)
            if k in alive and k not in permanent and \
                    last_use.get(k, -1) <= i:
                dying[k] = _sig(v)
        for v in eqn.outvars:
            k = _var_key(v)
            if k in alive:
                continue
            sig = _sig(v)
            reused = next((dk for dk, ds in dying.items()
                           if ds == sig and ds is not None), None)
            if reused is not None:
                del dying[reused]
                alive[k] = alive.pop(reused)  # transfer, no live change
            else:
                birth(v)
        transient = _internal_peak(eqn) if _sub_jaxprs(eqn) else 0
        here = live + transient
        if here > peak:
            peak = here
            peak_step = i
            peak_op = eqn.primitive.name
            peak_loc = _user_location(eqn)
        # free remaining dead operands (and anything else past last use)
        for k in [k for k in alive
                  if last_use.get(k, -1) <= i and k not in permanent]:
            live -= alive.pop(k)
    return PeakEstimate(
        peak_bytes=int(peak), resident_bytes=int(resident),
        peak_step=peak_step, peak_op=peak_op, peak_location=peak_loc,
        breakdown={"inputs_and_outputs": int(resident),
                   "transients_at_peak": int(peak - resident)})


def estimate_peak(program: Program) -> PeakEstimate:
    """Peak-HBM estimate for a captured Program (donation-aware when the
    Program was captured from a TrainStep)."""
    return estimate_peak_jaxpr(program.jaxpr, program.donated_invars,
                               program.label)


def estimate_train_step_hbm(step, *batch) -> PeakEstimate:
    """Convenience: capture a jit.TrainStep / ShardedTrainStep with its
    example batch and estimate the whole-step peak (params + grads +
    optimizer state + live activations), modeling buffer donation."""
    from .program import capture

    return estimate_peak(capture(step, *batch))


@register_pass("memory")
def memory_pass(program: Program, hbm_bytes: Optional[int] = None,
                warn_frac: float = 0.8, **_cfg) -> List[Diagnostic]:
    """MM001 peak estimate info; MM002 peak within warn_frac of the HBM
    envelope; MM003 static OOM (peak exceeds the envelope)."""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    est = estimate_peak(program)
    diags = [Diagnostic(
        severity="info", code="MM001", pass_name="memory",
        message=(f"estimated peak HBM {est.peak_gb:.3f} GB "
                 f"(resident {est.resident_bytes / 1e9:.3f} GB, "
                 f"peak at op {est.peak_op or '?'})"),
        op=est.peak_op, location=est.peak_location, data=est.to_dict())]
    if est.peak_bytes > hbm_bytes:
        diags.append(Diagnostic(
            severity="error", code="MM003", pass_name="memory",
            message=(f"static OOM: estimated peak {est.peak_gb:.2f} GB "
                     f"exceeds the {hbm_bytes / 1e9:.1f} GB HBM envelope"),
            op=est.peak_op, location=est.peak_location,
            suggestion=("shard the fat operands (dist_spec / batch_specs), "
                        "enable remat, or move the step to "
                        "SegmentedTrainStep/StreamedTrainStep"),
            data=est.to_dict()))
    elif est.peak_bytes > warn_frac * hbm_bytes:
        diags.append(Diagnostic(
            severity="warning", code="MM002", pass_name="memory",
            message=(f"estimated peak {est.peak_gb:.2f} GB is within "
                     f"{(1 - warn_frac) * 100:.0f}% of the "
                     f"{hbm_bytes / 1e9:.1f} GB envelope"),
            op=est.peak_op, location=est.peak_location,
            suggestion="leave headroom: XLA temps and fragmentation land on top",
            data=est.to_dict()))
    return diags


def offload_stream_plan(step) -> Dict[str, Any]:
    """Static plan of the streaming offload executor's memory story.

    The two-deep lane holds at most TWO groups in flight, so the staging
    working set is ``2 * max_group(f32 grads down + fresh params up)`` —
    NOT the full fp32-master + optimizer-state residency a resident step
    (or a naive whole-set offload round-trip) would pay. ``step`` is an
    offload ``ShardedTrainStep`` (``optimizer._offload`` set)."""
    from ..jit.offload_stream import plan_stream_groups

    params = step.train_params
    seg = int(getattr(step, "_stream_segment", 2 ** 20))
    bufmax = int(getattr(step, "_stream_bufmax", 2 ** 23))
    groups = plan_stream_groups([p.size * 4 for p in params], seg, bufmax)
    # grads stream down in the fwd executable's dtype — the model dtype,
    # unless a global-norm clip upcast them to f32 on the device side
    clipped = getattr(step.optimizer, "_grad_clip", None) is not None
    staging = []
    for idx in groups:
        down = sum(
            params[i].size * (4 if clipped
                              else int(params[i].data.dtype.itemsize))
            for i in idx)                               # grads D2H
        up = sum(int(params[i].data.nbytes) for i in idx)  # fresh params H2D
        staging.append(down + up)
    opt = step.optimizer
    state_bytes = sum(
        int(v.nbytes)
        for p in params for v in opt._accumulators[id(p)].values())
    master_bytes = sum(p.size * 4 for p in params)
    return {
        "groups": len(groups),
        "group_param_counts": [len(g) for g in groups],
        "max_group_staging_bytes": max(staging) if staging else 0,
        "working_set_bytes": 2 * max(staging) if staging else 0,
        "full_residency_bytes": master_bytes + state_bytes,
        "segment_size": seg, "buffer_max_size": bufmax,
    }


def estimate_offload_stream_hbm(step, *batch) -> Dict[str, Any]:
    """HBM model of one streamed-offload step: device side = the fwd+bwd
    program's live-range peak (params + grads + activations; master and
    optimizer state never HBM-resident) PLUS the lane's two-group staging
    working set. The honest counterpart of ``estimate_train_step_hbm`` for
    offload steps — the full-residency estimate would overcharge by the
    whole master/state pool."""
    import jax

    from ..framework import random as random_mod
    from .program import _data_of

    arrays = [_data_of(b) for b in batch]
    params = [p.data for p in step.train_params]
    frozen = [t.data for t in step.frozen]
    gen = random_mod.default_generator()
    saved = gen.get_state()
    try:
        key = random_mod.next_key()
    finally:
        gen.set_state(saved)
    closed = jax.make_jaxpr(step._build_offload(arrays))(
        params, frozen, key, *arrays)
    est = estimate_peak_jaxpr(_as_open(closed), (),
                              label="ShardedTrainStep[offload]")
    plan = offload_stream_plan(step)
    peak = est.peak_bytes + plan["working_set_bytes"]
    return {
        "peak_bytes": int(peak), "peak_gb": round(peak / 1e9, 3),
        "device_program_peak_bytes": est.peak_bytes,
        "stream_working_set_bytes": plan["working_set_bytes"],
        "avoided_full_residency_bytes": plan["full_residency_bytes"],
        "plan": plan, "device_estimate": est.to_dict(),
    }


def stream_plan_check(step, *batch, hbm_bytes: Optional[int] = None
                      ) -> List[Diagnostic]:
    """MM012 info: streamed-offload peak (two-group working set model);
    MM013: that peak still exceeds the envelope."""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    est = estimate_offload_stream_hbm(step, *batch)
    diags = [Diagnostic(
        severity="info", code="MM012", pass_name="memory",
        message=(f"streamed offload: estimated peak {est['peak_gb']:.3f} GB "
                 f"(device program {est['device_program_peak_bytes'] / 1e9:.3f}"
                 f" GB + 2-group staging "
                 f"{est['stream_working_set_bytes'] / 1e9:.3f} GB; avoids "
                 f"{est['avoided_full_residency_bytes'] / 1e9:.3f} GB of "
                 f"master/state residency)"),
        data=est)]
    if est["peak_bytes"] > hbm_bytes:
        diags.append(Diagnostic(
            severity="error", code="MM013", pass_name="memory",
            message=(f"streamed offload still exceeds the envelope "
                     f"({est['peak_gb']:.2f} GB > {hbm_bytes / 1e9:.1f} GB)"),
            suggestion=("shrink buffer_max_size (smaller stream groups), "
                        "enable remat, or shard params (level p_g_os)"),
            data=est))
    return diags


def segment_plan_check(step, *batch, hbm_bytes: Optional[int] = None
                       ) -> List[Diagnostic]:
    """Cross-check SegmentedTrainStep-style planning: estimate the step peak
    and report whether segmentation is needed / sufficient for the envelope.
    Accepts any TrainStep-shaped object."""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    est = estimate_train_step_hbm(step, *batch)
    if est.peak_bytes <= hbm_bytes:
        return [Diagnostic(
            severity="info", code="MM010", pass_name="memory",
            message=(f"step fits resident: est peak {est.peak_gb:.2f} GB "
                     f"<= {hbm_bytes / 1e9:.1f} GB"),
            data=est.to_dict())]
    return [Diagnostic(
        severity="warning", code="MM011", pass_name="memory",
        message=(f"step does NOT fit resident (est peak {est.peak_gb:.2f} "
                 f"GB); per-layer segmentation or host offload required"),
        suggestion="use jit.SegmentedTrainStep / StreamedTrainStep",
        data=est.to_dict())]
