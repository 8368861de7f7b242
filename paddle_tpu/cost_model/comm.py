"""Per-link communication cost tables for the auto-parallel planner.

Reference role: python/paddle/distributed/auto_parallel/cost_model.py —
the reference prices candidate distributed programs with a measured
per-op/per-link cost table before picking a plan. TPU-native mapping:
collectives are XLA ops over ICI (or host memcpy on the CPU test mesh),
so a topology is priced by four numbers — peak matmul FLOPS, link
bandwidth, per-collective launch latency, and per-executable dispatch
overhead — plus the host-link bandwidth the offload executor streams
through. The seeds below come from this repo's own measurements:

- ``tpu-v5e``: the published peaks (197 TFLOP/s bf16, 819 GB/s HBM),
  ~90 GB/s per-direction ICI ring, and a host link seeded at ~2 GB/s
  from the PR-5 ``stream_capacity`` legs (an old set-up; not re-measured
  on the current chip);
- ``cpu-host``: the 8-device ``--xla_force_host_platform_device_count``
  dryrun mesh (``__graft_entry__``) — "links" are memcpys between thread
  shards, cheap on bytes but expensive per collective (every extra
  partitioned op pays SPMD overhead on an oversubscribed host).

Tables are overridable per call (``LinkModel(**overrides)``) and
re-calibratable from the live PR-4 collective byte/call counters plus
XPlane device timings (``calibrate_from_counters``).
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, replace, asdict
from typing import Any, Dict, Optional

__all__ = ["LinkModel", "LINK_TABLES", "link_model_for",
           "calibrated_link_model", "DEVICE_KINDS", "topology_for_device",
           "ring_factor",
           "reduce_scatter_factor", "all_to_all_factor",
           "all_gather_factor", "calibrate_from_counters",
           "save_calibration", "load_calibration", "calibration_path",
           "kv_ship_seconds", "kv_reprefill_seconds",
           "kv_migration_crossover"]


@dataclass(frozen=True)
class LinkModel:
    """One topology's cost constants (everything the step-time model
    multiplies bytes/flops by)."""

    name: str = "tpu-v5e"
    peak_flops: float = 197e12        # per-device matmul peak (model dtype)
    hbm_bytes_per_s: float = 8.1e11   # device memory stream bandwidth
    ici_bytes_per_s: float = 9e10     # per-direction inter-device link
    coll_latency_s: float = 1e-5      # per-collective launch/sync charge
    dispatch_s: float = 1e-4          # per-executable host dispatch charge
    host_bytes_per_s: float = 2e9     # host<->device offload stream link
    host_hidden_frac: float = 0.6     # offload transfer fraction the
    # double-buffered lane hides behind the group updates (PR-5 measured
    # overlap_efficiency ~0.23-0.54 CPU, higher on real DMA links)

    def to_dict(self) -> Dict[str, float]:
        return asdict(self)

    def override(self, **kw) -> "LinkModel":
        return replace(self, **kw)


LINK_TABLES: Dict[str, LinkModel] = {
    "tpu-v4": LinkModel("tpu-v4", peak_flops=275e12,
                        hbm_bytes_per_s=1.2e12),
    "tpu-v5e": LinkModel("tpu-v5e"),
    "tpu-v5p": LinkModel("tpu-v5p", peak_flops=459e12,
                         hbm_bytes_per_s=2.765e12,
                         ici_bytes_per_s=2e11),
    "tpu-v6e": LinkModel("tpu-v6e", peak_flops=918e12,
                         hbm_bytes_per_s=1.6e12),
    # the 8-device CPU host mesh every dryrun/CI leg runs on: bytes are
    # cheap (shared memory), partitioned-op overhead is what ranks configs
    "cpu-host": LinkModel("cpu-host", peak_flops=2e10,
                          hbm_bytes_per_s=2e10, ici_bytes_per_s=8e9,
                          coll_latency_s=5e-5, dispatch_s=3e-4,
                          host_bytes_per_s=2e9, host_hidden_frac=0.35),
}


# The ONE map from what a device calls itself (``jax.Device.device_kind``,
# verbatim) to its row of peaks above. Peaks are the published per-chip
# numbers (Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM; "TPU v4", "TPU v5p", "TPU v6e" pages likewise); the kind
# strings and their aliases are jax's own (``pallas/mosaic/tpu_info.py``) —
# a v5e chip says "TPU v5 lite" (seen on the chip, PR 21). A kind that is
# not listed is an error, never a default: a wrong peak makes every
# utilization computed from it wrong in silence.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "tpu-v4",
    "TPU v5 lite": "tpu-v5e", "TPU v5e": "tpu-v5e",
    "TPU v5": "tpu-v5p", "TPU v5p": "tpu-v5p",
    "TPU v6 lite": "tpu-v6e", "TPU v6e": "tpu-v6e",
}


def topology_for_device(device=None) -> str:
    """LINK_TABLES key for a live device (default: the first one). CPU
    devices map to the host-mesh row; an unlisted kind raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return "cpu-host"
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(
            f"unknown device_kind {kind!r} (platform {device.platform!r}): "
            f"add it to cost_model.comm.DEVICE_KINDS with its published "
            f"peaks; known kinds: {sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[kind]


def link_model_for(topology: Optional[str] = None, **overrides) -> LinkModel:
    """Resolve a LinkModel: explicit topology name, else the live jax
    backend (CPU test meshes -> "cpu-host", TPUs by their exact
    ``device_kind``; an unknown kind raises)."""
    if topology is None:
        topology = topology_for_device()
    base = LINK_TABLES.get(topology)
    if base is None:
        raise KeyError(f"unknown topology {topology!r}; known: "
                       f"{sorted(LINK_TABLES)} (or pass overrides on one)")
    # persisted calibration (opt-in: PT_LINK_CALIBRATION=1 so CI ranking
    # assertions stay deterministic unless a round armed it): measured
    # per-(topology, jax version) refits land on top of the seed table,
    # explicit caller overrides still win
    if os.environ.get("PT_LINK_CALIBRATION", "0") == "1":
        prof = load_calibration(topology)
        if prof:
            cal = {k: float(v) for k, v in (prof.get("link") or {}).items()
                   if k in base.to_dict() and k != "name"}
            if cal:
                base = base.override(**cal)
    return base.override(**overrides) if overrides else base


def calibrated_link_model(topology: Optional[str] = None,
                          **overrides) -> LinkModel:
    """``link_model_for`` with the persisted calibration ALWAYS merged
    (no ``PT_LINK_CALIBRATION`` gate): the explicit opt-in the online
    tuner's live re-scoring uses — a runtime deciding whether to swap
    the active plan must rank under measured link rates, while CI's
    deterministic ranking assertions keep the env-gated path."""
    lm = link_model_for(topology)
    prof = load_calibration(topology or lm.name)
    if prof:
        cal = {k: float(v) for k, v in (prof.get("link") or {}).items()
               if k in lm.to_dict() and k != "name"}
        if cal:
            lm = lm.override(**cal)
    return lm.override(**overrides) if overrides else lm


# -- bytes-on-wire multipliers ------------------------------------------------

def ring_factor(n: int) -> float:
    """Ring all-reduce: each rank moves 2(n-1)/n of the payload."""
    return 2.0 * (n - 1) / n if n > 1 else 0.0


def reduce_scatter_factor(n: int) -> float:
    """Reduce-scatter (the os_g grad path): half an all-reduce."""
    return (n - 1) / n if n > 1 else 0.0


def all_to_all_factor(n: int) -> float:
    """All-to-all (MoE dispatch/combine): (n-1)/n of the payload leaves
    this rank."""
    return (n - 1) / n if n > 1 else 0.0


def all_gather_factor(n: int) -> float:
    """Ring all-gather (ZeRO param materialization): each rank receives
    (n-1)/n of the payload."""
    return (n - 1) / n if n > 1 else 0.0


# -- KV page migration (disaggregated prefill/decode serving) -----------------
# Prices the ship-pages-vs-re-prefill decision: moving a prompt's paged
# KV across replicas costs bytes on the replica-to-replica link (the
# host link on a CPU fleet, DCN/ICI on a real one) plus a fixed RPC
# round-trip charge; recomputing it costs the prompt's prefill FLOPs.
# The crossover prompt length is where shipping starts winning — the
# bench's measured ratio validates the same quantities end-to-end.

def kv_ship_seconds(lm: LinkModel, wire_bytes: float,
                    rpc_overhead_s: float = 2e-3) -> float:
    """Wall-clock to ship ``wire_bytes`` of packed KV pages between two
    replicas: bytes over the inter-replica link plus a per-transfer
    RPC/staging charge (export head + chunk round trips + install
    commit)."""
    return float(wire_bytes) / lm.host_bytes_per_s + \
        float(rpc_overhead_s)


def kv_reprefill_seconds(lm: LinkModel, prompt_tokens: int,
                         flops_per_token: float) -> float:
    """Wall-clock to RECOMPUTE a prompt's KV on the target replica: the
    prefill FLOPs at the link model's effective peak, plus one
    executable dispatch."""
    return (float(prompt_tokens) * float(flops_per_token)
            ) / lm.peak_flops + lm.dispatch_s


def kv_migration_crossover(lm: LinkModel, page_len: int,
                           bytes_per_page: float,
                           flops_per_token: float,
                           quantized: bool = False,
                           max_pages: int = 4096) -> Dict[str, Any]:
    """The planner's migration policy input: for each prompt size find
    whether shipping the pages beats re-prefilling, and the crossover
    page count (smallest page count where ship wins; None when
    re-prefill always wins inside ``max_pages``). ``quantized`` halves
    the transit bytes (int8 per-page scales are noise next to the
    payload)."""
    scale = 0.5 if quantized else 1.0
    crossover = None
    for n in range(1, int(max_pages) + 1):
        ship = kv_ship_seconds(lm, n * bytes_per_page * scale)
        pre = kv_reprefill_seconds(lm, n * page_len, flops_per_token)
        if ship < pre:
            crossover = n
            break
    sample = crossover or int(max_pages)
    return {
        "crossover_pages": crossover,
        "ship_s": kv_ship_seconds(
            lm, sample * bytes_per_page * scale),
        "reprefill_s": kv_reprefill_seconds(
            lm, sample * page_len, flops_per_token),
        "quantized": bool(quantized),
        "bytes_per_page": float(bytes_per_page) * scale,
    }


_COLLECTIVE_OP_MARKERS = ("all-reduce", "all-gather", "all-to-all",
                          "reduce-scatter", "collective-permute",
                          "allreduce", "allgather", "alltoall")


def _is_collective_op(name: str) -> bool:
    n = name.lower()
    return any(m in n for m in _COLLECTIVE_OP_MARKERS)


def calibrate_from_counters(base: Optional[LinkModel] = None, *,
                            flops_per_step: Optional[float] = None,
                            persist: bool = False) -> LinkModel:
    """Best-effort recalibration from live telemetry — every bench round
    becomes a calibration round (ROADMAP direction 5's planner leg):

    - the PR-5 ``offload_stream`` family refits the host link bandwidth
      and hidden fraction (the original host-link-only calibration);
    - the PR-7 ``device_trace`` op table refits the ICI link: XPlane-
      measured device time of collective-shaped ops against the PR-4
      ``collectives`` byte counters gives measured bytes-on-wire/s;
    - with a ``flops_per_step`` hint (the planner profile knows it), the
      per-step XPlane ``device_compute_us`` refits the effective
      ``peak_flops`` — compute calibration, not just links.

    Families that have not recorded anything leave the seed untouched —
    calibration never degrades the table, and never raises.

    ``persist=True`` writes the refit next to the persistent executable
    cache, keyed by (topology, jax version); ``link_model_for`` merges
    it back when ``PT_LINK_CALIBRATION=1``, which is how the planner's
    per-topology tables learn from measured rounds.
    """
    lm = base or link_model_for()
    kw: Dict[str, float] = {}
    try:
        from .. import observability as obs

        snap = obs.snapshot()
        lane = snap.get("offload_stream") or {}
        t_ms = float(lane.get("transfer_ms") or 0.0)
        moved = float(lane.get("h2d_bytes") or 0) + \
            float(lane.get("d2h_bytes") or 0)
        if t_ms > 1.0 and moved > 1e6:
            kw["host_bytes_per_s"] = moved / (t_ms / 1e3)
        stall = float(lane.get("stall_ms") or 0.0)
        if t_ms > 1.0:
            kw["host_hidden_frac"] = max(
                0.0, min(1.0, 1.0 - stall / t_ms))
        # XPlane-measured per-op device times (PR-7 op table). The byte
        # counters are PROCESS-CUMULATIVE while the op table covers one
        # capture window, so both sides normalize to per-step rates:
        # bytes over every timeline step vs device time over the steps
        # the capture correlated — dividing raw totals would inflate the
        # bandwidth by (total steps / captured steps).
        dt = snap.get("device_trace") or {}
        op_table = dt.get("op_table") or []
        coll_us = sum(float(r.get("total_us") or 0.0) for r in op_table
                      if _is_collective_op(str(r.get("op", ""))))
        cap_steps = float(dt.get("steps_correlated") or 0)
        tl_steps = float((snap.get("step_timeline") or {}).get("steps")
                         or 0)
        colls = (snap.get("collectives") or {}).get("values") or {}
        coll_bytes = sum(float(v or 0.0) for k, v in colls.items()
                         if str(k).endswith("|bytes"))
        if coll_us > 100.0 and coll_bytes > 1e6 and cap_steps > 0 \
                and tl_steps > 0:
            bytes_per_step = coll_bytes / tl_steps
            us_per_step = coll_us / cap_steps
            kw["ici_bytes_per_s"] = bytes_per_step / (us_per_step / 1e6)
        if flops_per_step:
            per_step = float(((dt.get("device_compute_us") or {})
                              .get("per_step_avg")) or 0.0)
            if per_step > 100.0:
                kw["peak_flops"] = float(flops_per_step) / (per_step / 1e6)
    except Exception:
        pass
    lm = lm.override(**kw) if kw else lm
    if persist and kw:
        try:
            save_calibration(lm)
        except Exception:
            pass  # persistence is best-effort, never sinks the caller
    return lm


# -- persisted calibration profiles -------------------------------------------
# One JSON per (topology, jax version), living next to the persistent
# executable cache (same lifecycle: measured artifacts that make a fresh
# process as smart as the last one). Shape:
#   {"link": {<LinkModel field>: value, ...},
#    "fused": {<op>: {<FusedOpEntry field>: value, ...}, ...},
#    "meta": {...}}

def calibration_path(topology: Optional[str] = None) -> str:
    import jax

    topo = topology or link_model_for().name
    ver = getattr(jax, "__version__", "unknown")
    root = os.environ.get("PT_CALIBRATION_DIR")
    if not root:
        try:
            from ..jit import persistent_cache

            root = persistent_cache.cache_dir()
        except Exception:
            root = None
    if not root:
        root = os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu")
    return os.path.join(root, "calibration", f"{topo}-jax{ver}.json")


def save_calibration(lm: LinkModel, fused: Optional[Dict[str, Dict]] = None,
                     topology: Optional[str] = None) -> str:
    """Persist a measured profile (merging over any prior file so a
    round that only refit the link keeps earlier fused-op rows)."""
    path = calibration_path(topology or lm.name)
    prior = load_calibration(topology or lm.name) or {}
    seed = LINK_TABLES.get(lm.name)
    link_delta = {k: v for k, v in lm.to_dict().items()
                  if k != "name" and
                  (seed is None or getattr(seed, k) != v)}
    payload = {
        "link": dict(prior.get("link") or {}, **link_delta),
        "fused": dict(prior.get("fused") or {}, **(fused or {})),
        "meta": {"topology": lm.name},
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_calibration(topology: Optional[str] = None
                     ) -> Optional[Dict[str, Any]]:
    try:
        path = calibration_path(topology)
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)
    except Exception:
        return None
