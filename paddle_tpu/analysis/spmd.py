"""SPMD / collective consistency lint over shard_map'd jaxprs.

Reference role: the auto-parallel completion/validation passes
(python/paddle/distributed/auto_parallel/completion.py checks that every
dist-attr names a real mesh axis and that process groups agree across
stages). TPU-native mapping: collectives are jaxpr primitives inside
``shard_map`` regions — statically walkable — so this pass checks, without
touching a chip:

- SP001 a collective's axis name is not a manual axis of its enclosing
  shard_map (or there is no enclosing shard_map at all) — XLA would reject
  it at compile time on the TPU; we say it on CPU.
- SP002 a ppermute's perm is malformed: duplicate sources/destinations or
  indices outside the mesh axis size. Duplicate destinations deadlock the
  reference's p2p handoff; jax silently drops, which diverges.
- SP003 ppermutes over the same axis in one program use perms that are
  neither identical nor mutual inverses — the classic mismatched pipeline
  handoff (stage A sends i->i+1, stage B expects i->i-1): a static
  deadlock in rendezvous-style backends, silent garbage under GSPMD.
- SP004 a fat intermediate (> hbm_frac of the HBM envelope) materializes
  OUTSIDE any shard_map/sharding-constraint region — the unsharded
  fat-intermediate failure mode behind surprise OOMs.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from .diagnostics import Diagnostic
from .memory import device_hbm_bytes
from .program import (Program, register_pass, _aval_bytes, _aval_str,
                      _sub_jaxprs, _as_open, _user_location)

__all__ = ["spmd_pass", "COLLECTIVES"]

COLLECTIVES = {
    "psum", "psum2", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
    "axis_index", "pmax", "pmin",
}


def _axes_of(eqn) -> Tuple[str, ...]:
    """Mesh axis names a collective eqn operates over."""
    p = eqn.params
    for key in ("axes", "axis_name", "axis_index_groups_axis"):
        v = p.get(key)
        if v is None:
            continue
        if isinstance(v, (tuple, list)):
            return tuple(a for a in v if isinstance(a, str))
        if isinstance(v, str):
            return (v,)
    return ()


def _manual_axes(eqn) -> Tuple[Tuple[str, ...], Dict[str, int]]:
    """(manual axis names, axis sizes) of a shard_map eqn."""
    mesh = eqn.params.get("mesh")
    sizes: Dict[str, int] = {}
    if mesh is not None:
        try:
            sizes = dict(mesh.shape)
        except Exception:
            sizes = {}
    auto = eqn.params.get("auto", frozenset()) or frozenset()
    manual = tuple(a for a in sizes if a not in auto)
    if not manual:
        # fall back to the axis names appearing in in_names/out_names
        names = set()
        for part in ("in_names", "out_names"):
            for entry in eqn.params.get(part, ()) or ():
                if isinstance(entry, dict):
                    for v in entry.values():
                        names.update(v if isinstance(v, (tuple, list)) else (v,))
        manual = tuple(n for n in names if isinstance(n, str))
    return manual, sizes


def _check_perm(perm, axis_size: Optional[int]) -> List[str]:
    problems: List[str] = []
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    if len(set(srcs)) != len(srcs):
        problems.append(f"duplicate sources {sorted(srcs)}")
    if len(set(dsts)) != len(dsts):
        problems.append(f"duplicate destinations {sorted(dsts)}")
    if axis_size:
        bad = [i for i in srcs + dsts if i < 0 or i >= axis_size]
        if bad:
            problems.append(
                f"indices {sorted(set(bad))} outside axis size {axis_size}")
    return problems


def _is_inverse(pa: Tuple, pb: Tuple) -> bool:
    return sorted((d, s) for s, d in pa) == sorted(pb)


class _Walker:
    def __init__(self, hbm_bytes: int, hbm_frac: float):
        self.diags: List[Diagnostic] = []
        self.ppermutes: Dict[str, List[Tuple[Tuple, Any]]] = {}
        self.hbm_bytes = hbm_bytes
        self.hbm_frac = hbm_frac
        self._fat_reported = 0

    def walk(self, jaxpr, manual: Tuple[str, ...],
             sizes: Dict[str, int], in_manual_region: bool):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "shard_map":
                m, s = _manual_axes(eqn)
                for _, sub in _sub_jaxprs(eqn):
                    self.walk(_as_open(sub), m, {**sizes, **s}, True)
                continue
            if name in COLLECTIVES:
                self._check_collective(eqn, manual, sizes, in_manual_region)
            elif not in_manual_region and name not in (
                    "jit", "pjit", "closed_call", "remat2", "checkpoint"):
                self._check_fat(eqn)
            for _, sub in _sub_jaxprs(eqn):
                self.walk(_as_open(sub), manual, sizes, in_manual_region)

    # -- checks ---------------------------------------------------------------
    def _check_collective(self, eqn, manual, sizes, in_manual_region):
        name = eqn.primitive.name
        axes = _axes_of(eqn)
        loc = _user_location(eqn)
        for ax in axes:
            if not in_manual_region:
                self.diags.append(Diagnostic(
                    severity="error", code="SP001", pass_name="spmd",
                    op=name, location=loc,
                    message=(f"collective {name} over axis {ax!r} outside "
                             f"any shard_map region — the axis name is "
                             f"unbound at XLA lowering"),
                    suggestion=("wrap the caller in shard_map (or "
                                "collective.* helpers, which do)")))
            elif ax not in manual:
                self.diags.append(Diagnostic(
                    severity="error", code="SP001", pass_name="spmd",
                    op=name, location=loc,
                    message=(f"collective {name} uses axis {ax!r} which is "
                             f"not a manual axis of the enclosing shard_map "
                             f"(manual: {sorted(manual)})"),
                    suggestion=("add the axis to the shard_map manual set "
                                "or fix the axis name")))
        if name == "ppermute":
            perm = tuple(tuple(p) for p in eqn.params.get("perm", ()))
            ax = axes[0] if axes else None
            problems = _check_perm(perm, sizes.get(ax))
            if problems:
                self.diags.append(Diagnostic(
                    severity="error", code="SP002", pass_name="spmd",
                    op="ppermute", location=loc,
                    message=(f"malformed ppermute perm over axis {ax!r}: "
                             + "; ".join(problems)),
                    suggestion="each rank must appear at most once as "
                               "source and destination"))
            if ax is not None:
                self.ppermutes.setdefault(ax, []).append((perm, loc))

    def _check_fat(self, eqn):
        if self._fat_reported >= 8:  # cap the noise on huge programs
            return
        thresh = self.hbm_frac * self.hbm_bytes
        for v in eqn.outvars:
            nbytes = _aval_bytes(getattr(v, "aval", None))
            if nbytes > thresh:
                self._fat_reported += 1
                self.diags.append(Diagnostic(
                    severity="warning" if nbytes <= self.hbm_bytes else "error",
                    code="SP004", pass_name="spmd",
                    op=eqn.primitive.name, location=_user_location(eqn),
                    message=(f"unsharded intermediate "
                             f"{_aval_str(v.aval)} = {nbytes / 1e9:.2f} GB "
                             f"(> {self.hbm_frac:.0%} of the "
                             f"{self.hbm_bytes / 1e9:.1f} GB HBM envelope) "
                             f"materializes outside any manual region"),
                    suggestion=("shard it: with_sharding_constraint / "
                                "dist_spec on the producing layer, or remat")))
                break

    def finish(self):
        for ax, entries in self.ppermutes.items():
            uniq: List[Tuple[Tuple, Any]] = []
            for perm, loc in entries:
                if all(perm != u for u, _ in uniq):
                    uniq.append((perm, loc))
            if len(uniq) <= 1:
                continue
            # identical or mutually inverse perms (fwd + its transpose from
            # autodiff) are consistent; anything else is a stage mismatch
            base, base_loc = uniq[0]
            for perm, loc in uniq[1:]:
                if perm == base or _is_inverse(base, perm):
                    continue
                self.diags.append(Diagnostic(
                    severity="warning", code="SP003", pass_name="spmd",
                    op="ppermute", location=loc,
                    message=(f"mismatched ppermute perms over axis {ax!r}: "
                             f"{base} (at {base_loc}) vs {perm} — pipeline "
                             f"stages disagree on the handoff direction "
                             f"(static deadlock risk on rendezvous "
                             f"backends)"),
                    suggestion=("derive every stage's perm from one "
                                "schedule (see meta_parallel.pipeline."
                                "ppermute_pipeline)")))
        return self.diags


@register_pass("spmd")
def spmd_pass(program: Program, hbm_bytes: Optional[int] = None,
              hbm_frac: float = 0.5, **_cfg) -> List[Diagnostic]:
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes()
    w = _Walker(hbm_bytes, hbm_frac)
    w.walk(program.jaxpr, manual=(), sizes={}, in_manual_region=False)
    return w.finish()
