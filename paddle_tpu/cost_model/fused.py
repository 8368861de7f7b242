"""Per-op fused-kernel cost entries for the auto-parallel planner.

The ``kernels/pallas`` layer changes the compute term of a candidate:
fused RMSNorm/RoPE remove whole HBM round-trips of the activation
stream, and the fused MoE dispatch cuts the measured ``dispatch_share``
of the MoE MLP. ``plan()`` must see those deltas or it will keep ranking
configs by the composed-path cost and mis-order candidates whose
bottleneck a fusion removes — these entries are what make the kernel
layer a *system* input rather than a local speedup.

Each entry models one op's saving as bytes-not-moved (normalized to HBM
stream time) or as a fraction of the MoE compute term, with constants
seeded from this repo's measurements (BENCH r04 ``dispatch_share``
0.148; the fused target 0.06) and overridable by a persisted calibration
profile (``cost_model.comm.save_calibration`` stores measured
fused-vs-composed per-op times from the bench A/B next to the link
tables, keyed by (topology, jax version)).

``fused_gain_s(profile, cfg, link, ops)`` returns the predicted seconds
saved per step for the enabled op set — ``score_config`` subtracts it
and records the per-op breakdown, so enabling fused entries visibly
re-ranks (or at minimum re-prices) candidates: the ci.sh kernels gate
asserts exactly that.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Iterable, Optional, Tuple

__all__ = ["FusedOpEntry", "FUSED_OP_ENTRIES", "fused_entries",
           "fused_gain_s", "enabled_fused_ops"]


@dataclass(frozen=True)
class FusedOpEntry:
    """One fused op's cost-delta model.

    ``hbm_passes_saved``: activation-stream round-trips removed per
    application, fwd+bwd combined (an elementwise op reads + writes the
    tensor once per pass; the composed forms add reduction re-reads,
    table reads and concat writes the fusion eliminates).
    ``applications_per_layer``: how many times the op runs per decoder
    layer. ``act_scale``: the op's operand size relative to the [b, s, h]
    hidden block. MoE dispatch is modeled separately via the measured
    ``dispatch_share`` pair; serving-only ops carry zero train-step gain.
    """

    name: str
    hbm_passes_saved: float = 0.0
    applications_per_layer: float = 0.0
    act_scale: float = 1.0
    # MoE dispatch model: fraction of the MoE MLP spent on routing/
    # dispatch, composed vs fused (BENCH r04 measured vs fused target;
    # calibrated from the bench A/B when a profile is persisted)
    dispatch_share_composed: float = 0.0
    dispatch_share_fused: float = 0.0
    train_step: bool = True  # False: serving-side only, no train gain
    note: str = ""

    def override(self, **kw) -> "FusedOpEntry":
        return replace(self, **kw)


FUSED_OP_ENTRIES: Dict[str, FusedOpEntry] = {
    # 2 norms/layer; composed RMSNorm reads the row for the mean-square
    # reduction and again for the normalize (fwd), and the backward
    # re-reads twice more; the residual variant also folds the separate
    # add's round-trip in. ~3 round-trips saved per application fwd+bwd.
    "rms_norm": FusedOpEntry(
        "rms_norm", hbm_passes_saved=3.0, applications_per_layer=2.0,
        note="reduction re-read + normalize pass + residual-add fold"),
    # q and k per layer (~1 + kv/heads of a hidden block); the composed
    # form materializes cos/sin tables and a concat intermediate.
    "rope": FusedOpEntry(
        "rope", hbm_passes_saved=2.0, applications_per_layer=1.5,
        note="cos/sin table + rotate-half concat intermediates"),
    "moe_dispatch": FusedOpEntry(
        "moe_dispatch", dispatch_share_composed=0.148,
        dispatch_share_fused=0.06,
        note="BENCH r04 dispatch_share 0.148 -> fused routing kernel + "
             "scalar-prefetch gathers"),
    "paged_attention": FusedOpEntry(
        "paged_attention", train_step=False,
        note="serving decode only — priced by the serving A/B, not the "
             "train-step planner"),
}

# fraction of a MoE model's compute term spent in the expert-MLP stack
# (the r04 probe shapes: expert FFN ≈ attention+embed+head at top-2 with
# per-expert FFNs smaller than dense) — the dispatch share applies to it
_MOE_MLP_COMPUTE_FRAC = 0.55


def fused_entries(topology: Optional[str] = None) -> Dict[str, FusedOpEntry]:
    """The entry table, with any persisted calibration overrides for this
    (topology, jax version) merged in — under the same
    ``PT_LINK_CALIBRATION=1`` opt-in as the link tables (the bench writes
    profiles unconditionally; consuming them must stay armed explicitly
    so CI ranking assertions remain deterministic)."""
    table = dict(FUSED_OP_ENTRIES)
    import os

    if os.environ.get("PT_LINK_CALIBRATION", "0") != "1":
        return table
    try:
        from .comm import load_calibration

        prof = load_calibration(topology)
        for name, kw in ((prof or {}).get("fused") or {}).items():
            if name in table and isinstance(kw, dict):
                safe = {k: float(v) for k, v in kw.items()
                        if k in ("hbm_passes_saved",
                                 "applications_per_layer", "act_scale",
                                 "dispatch_share_composed",
                                 "dispatch_share_fused")}
                table[name] = table[name].override(**safe)
    except Exception:
        pass
    return table


def enabled_fused_ops() -> Tuple[str, ...]:
    """The ops whose Pallas kernel this platform runs (the planner's
    default when the caller does not pin a set)."""
    from ..kernels.registry import enabled_ops

    return enabled_ops()


def fused_gain_s(profile, cfg: Dict[str, Any], link,
                 ops: Optional[Iterable[str]] = None,
                 entries: Optional[Dict[str, FusedOpEntry]] = None,
                 compute_s: float = 0.0
                 ) -> Tuple[float, Dict[str, float]]:
    """Predicted seconds-per-step saved by the enabled fused ops for ONE
    candidate config. ``profile`` is the planner ``ModelProfile``;
    ``compute_s`` is the candidate's priced compute term (the MoE
    dispatch share applies to it)."""
    if ops is None:
        ops = enabled_fused_ops()
    ops = set(ops)
    if not ops:
        return 0.0, {}
    entries = entries or fused_entries(getattr(link, "name", None))
    mesh = cfg.get("mesh", {})
    data = mesh.get("dp", 1) * mesh.get("sharding", 1)
    shard = max(data * mesh.get("cp", 1) * mesh.get("pp", 1), 1)
    layers = max(profile.num_layers, 1)
    # one [b, s, h] hidden block's bytes on this candidate's shard —
    # sqrt(mp) matches the planner's own activation model (the residual
    # stream is replicated over mp, the fat intermediates sharded)
    act_block = (profile.batch * profile.seq * max(profile.hidden, 1) *
                 profile.dtype_size) / shard / \
        math.sqrt(max(mesh.get("mp", 1), 1))
    bwd_factor = 4.0 / 3.0 if cfg.get("remat") else 1.0  # recompute re-runs
    per_op: Dict[str, float] = {}
    for name in sorted(ops):
        ent = entries.get(name)
        if ent is None or not ent.train_step:
            continue
        if name == "moe_dispatch":
            if profile.num_experts <= 1:
                continue
            s_c, s_f = ent.dispatch_share_composed, ent.dispatch_share_fused
            moe_s = compute_s * _MOE_MLP_COMPUTE_FRAC
            # composed pays dispatch on top of the FFN: t = ffn/(1-share)
            gain = moe_s * (1.0 / max(1.0 - s_c, 1e-3) -
                            1.0 / max(1.0 - s_f, 1e-3))
        else:
            bytes_saved = (ent.hbm_passes_saved *
                           ent.applications_per_layer * ent.act_scale *
                           act_block * layers * bwd_factor)
            gain = bytes_saved / link.hbm_bytes_per_s
        if gain > 0:
            per_op[name] = gain
    return sum(per_op.values()), per_op
