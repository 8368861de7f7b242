"""Elastic multi-host training runtime: a coordinator-led ``jax.distributed``
fleet with failure detection, auto re-plan, and bounded restart.

Reference role: the elastic/collective launch product (fleet/elastic/
manager.py + launch_utils.py + run/controllers/master.py) — a gang of
training processes supervised by a controller that notices a dead/hung
node and relaunches the survivors at the new world size, with training
scripts resuming from their checkpoint. This module is that product
rebuilt on the pieces earlier PRs landed:

- **control plane**: the native ``TCPStore`` (store/) owned by the
  supervisor; workers heartbeat through the hardened ``ElasticManager``
  (fleet/elastic.py) and rendezvous/fence/allreduce through gen-scoped
  keys (every key carries a ``<key>/published`` add-counter so probes
  never block — ``TCPStore.get`` blocks on absent keys by design);
- **data plane**: each worker initializes ``jax.distributed`` against a
  per-generation coordinator port, so on TPU the gang is one global
  mesh. On the CPU backend multiprocess XLA programs are unimplemented
  (jaxlib refuses them), so the CPU fleet runs data-parallel with a
  host-side gradient allreduce through the store (``FleetGradSync``) —
  same control flow, same recovery protocol, drillable in CI;
- **recovery protocol** (the supervisor's loop, decided by the pure
  ``FleetStateMachine`` so the whole protocol unit-tests without
  processes): a worker crash / stale heartbeat / hung gang **fences**
  the generation (one store counter workers poll at step boundaries and
  inside blocking collective waits), survivors **drain** — commit a
  final checkpoint if they are at a boundary, abandon the torn step if
  their collective can never complete (``FleetFenced``) — and **exit
  fast** (``os._exit``: a surviving ``jax.distributed`` client that
  unwinds normally blocks ~100 s in the XLA shutdown barrier waiting on
  the dead peer, then aborts); the supervisor tears down stragglers,
  applies bounded exponential backoff, and **restarts** the gang at the
  surviving world size with the generation bumped;
- **auto re-plan**: gen>0 workers re-run ``plan(model, chips, hbm)``
  (auto_parallel.planner) for the NEW device count — rank 0 publishes
  the pick, everyone derives the per-rank batch from its dp degree —
  so a human never chooses the post-failure config;
- **resume**: workers restore from the newest committed checkpoint
  across every rank's dir (``pick_resume_dir``: max committed step,
  ties to the lowest rank — all ranks compute the same answer from the
  shared filesystem) re-sharded onto the new mesh by the PR-6 manifest
  reassembly path; losses stitch bit-equal where the config permits
  (replicated math), allclose under a dp re-split (fp summation order);
- **observability**: the supervisor registers a ``fleet`` hub provider
  (membership timeline, per-rank last heartbeat, restart/recovery
  wall-clock breakdown, per-rank flight-bundle paths) and a failed run
  leaves a ``fleet_forensics`` bundle (MANIFEST written last, same
  parseable-bundle contract as pd_dump).

Deterministic drills: ``PT_FAULTS="worker_crash@rank=2&step=6"`` hard-
kills rank 2 at global step 6; ``coordinator_lost`` simulates the
supervisor's store dying; ``heartbeat_stall@rank=1&ms=800`` stalls one
worker's heartbeat daemon under the eviction grace window. See
tools/resilience_drill.py --fleet and tests/test_fleet_runtime.py.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = [
    "FleetPolicy", "FleetPhase", "FleetAction", "FleetStateMachine",
    "ElasticFleet", "FleetWorkerContext", "FleetFenced", "FleetGradSync",
    "BlockShardedDataset", "elastic_fit", "pick_resume_dir",
    "replan_for_world", "EXIT_FENCED", "EXIT_COORD_LOST",
]

# Worker exit codes the supervisor classifies (chosen clear of shell/
# signal ranges): a fenced worker drained and left; a coordinator-lost
# worker exits rather than orphan itself under a dead control plane.
EXIT_FENCED = 75
EXIT_COORD_LOST = 76


class FleetFenced(RuntimeError):
    """The supervisor fenced this generation: the current step can never
    complete (a collective peer is gone). The worker must abandon the
    step — its last committed checkpoint is the resume point."""


# ---------------------------------------------------------------------------
# policy + pure recovery state machine
# ---------------------------------------------------------------------------

@dataclass
class FleetPolicy:
    """Knobs of the recovery protocol (docs/resilience.md lists each)."""

    min_world: int = 1
    max_restarts: int = 3
    backoff_base_s: float = 0.5     # restart n sleeps base * 2**(n-1)
    backoff_max_s: float = 30.0
    heartbeat_interval: float = 0.5
    heartbeat_timeout: float = 6.0  # the eviction grace window: a stall
    # shorter than this never evicts (tests pin it)
    drain_timeout_s: float = 20.0   # fence -> every survivor exited
    start_timeout_s: float = 180.0  # spawn -> all ranks ready
    poll_interval: float = 0.2

    def backoff_s(self, restart_id: int) -> float:
        return min(self.backoff_base_s * (2 ** max(restart_id - 1, 0)),
                   self.backoff_max_s)


class FleetPhase(Enum):
    LAUNCHING = "launching"
    RUNNING = "running"
    FENCED = "fenced"
    RESTARTING = "restarting"
    COMPLETED = "completed"
    FAILED = "failed"


@dataclass
class FleetAction:
    """What the supervisor should do next. ``kind`` is one of ``hold`` /
    ``fence`` / ``restart`` / ``complete`` / ``fail``."""

    kind: str
    dead: List[int] = field(default_factory=list)
    world: Optional[int] = None       # restart: the new world size
    backoff_s: float = 0.0
    reason: str = ""


class FleetStateMachine:
    """The recovery protocol's decision core — pure (caller supplies the
    clock), so membership flaps, budget exhaustion and grace windows are
    unit-testable without spawning a process.

    Per generation the supervisor feeds it ``heartbeat(rank, ts)`` as
    beats arrive and ``observe(now, exits)`` each poll; after a fence it
    calls ``observe`` until every worker exited, then ``restarted()``
    (or gets ``fail``/``complete``). Membership transitions land in
    ``timeline`` (bounded): join / evict (stale heartbeat) / flap (a
    beat from an evicted rank) / leave (exit) / fence / restart /
    complete / fail.
    """

    def __init__(self, world: int, policy: Optional[FleetPolicy] = None,
                 now: float = 0.0, gen: int = 0):
        self.policy = policy or FleetPolicy()
        self.phase = FleetPhase.LAUNCHING
        self.gen = int(gen)
        self.world = int(world)
        self.restarts = 0
        self.timeline: List[Dict[str, Any]] = []
        self._beats: Dict[int, float] = {}
        self._evicted: set = set()
        self._left: Dict[int, int] = {}   # rank -> exit code
        self._fence_reason = ""
        self._start_t = float(now)
        self._rank_restarts: Dict[int, int] = {}  # replica mode: per rank
        # a PLANNED fence (online retune raised by a worker, mirrored by
        # the supervisor probing the published reason) restarts the gang
        # without spending crash budget — the gang-mode analogue of
        # replica_restarted(count=False)
        self.planned_fence = False

    # -- inputs ---------------------------------------------------------------
    def _event(self, event: str, now: float, **data) -> None:
        rec = {"t": round(float(now), 3), "gen": self.gen, "event": event}
        rec.update(data)
        self.timeline.append(rec)
        if len(self.timeline) > 512:
            del self.timeline[:-512]

    def heartbeat(self, rank: int, now: float) -> None:
        first = rank not in self._beats
        if not first and float(now) <= self._beats[rank]:
            return  # a re-read of the same beat, not a fresh one
        self._beats[rank] = float(now)
        if first:
            self._event("join", now, rank=rank)
            if self.phase is FleetPhase.LAUNCHING and \
                    len(self._beats) >= self.world:
                self.phase = FleetPhase.RUNNING
        elif rank in self._evicted:
            # an evicted rank beat again: it was stalled, not dead — the
            # flap is recorded (the fence already happened; the restart
            # path re-admits it only through a fresh generation)
            self._evicted.discard(rank)
            self._event("flap", now, rank=rank)

    def ranks_alive(self, now: float) -> List[int]:
        cut = float(now) - self.policy.heartbeat_timeout
        return sorted(r for r, ts in self._beats.items()
                      if ts >= cut and r not in self._left)

    def stale_ranks(self, now: float) -> List[int]:
        """Registered ranks silent past the grace window and not exited —
        a stall SHORTER than ``heartbeat_timeout`` never lands here (the
        no-false-evict contract)."""
        cut = float(now) - self.policy.heartbeat_timeout
        return sorted(r for r, ts in self._beats.items()
                      if ts < cut and r not in self._left)

    # -- decision -------------------------------------------------------------
    def observe(self, now: float, exits: Dict[int, Optional[int]]
                ) -> FleetAction:
        """One poll: ``exits`` maps rank -> exit code (None = running)."""
        for r, rc in exits.items():
            if rc is not None and r not in self._left:
                self._left[r] = rc
                self._event("leave", now, rank=r, rc=rc)
        crashed = [r for r, rc in self._left.items()
                   if rc not in (0, EXIT_FENCED)]
        if self.phase in (FleetPhase.LAUNCHING, FleetPhase.RUNNING):
            if self.phase is FleetPhase.LAUNCHING and not crashed and \
                    now - self._start_t > self.policy.start_timeout_s:
                # checked before staleness: ranks that NEVER registered
                # have no heartbeat to go stale, and a partially-arrived
                # gang stuck past the window is a launch failure, not a
                # membership change
                self.phase = FleetPhase.FAILED
                missing = sorted(set(range(self.world)) - set(self._beats))
                self._event("fail", now, reason="start_timeout",
                            missing=missing)
                return FleetAction(
                    kind="fail",
                    reason=f"start_timeout: ranks {missing} never "
                           f"registered within "
                           f"{self.policy.start_timeout_s:.0f}s")
            stale = self.stale_ranks(now)
            if crashed or stale:
                for r in stale:
                    if r not in self._evicted:
                        self._evicted.add(r)
                        self._event("evict", now, rank=r, cause="stale",
                                    last_beat=self._beats.get(r))
                for r in crashed:
                    if r not in self._evicted:
                        self._evicted.add(r)
                        self._event("evict", now, rank=r, cause="crash",
                                    rc=self._left.get(r))
                self.phase = FleetPhase.FENCED
                dead = sorted(set(crashed) | set(stale))
                self._fence_reason = \
                    f"dead={crashed} stale={stale}".replace("'", "")
                self._event("fence", now, dead=dead,
                            reason=self._fence_reason)
                return FleetAction(kind="fence", dead=dead,
                                   reason=self._fence_reason)
            if len(self._left) == self.world:
                if all(rc == 0 for rc in self._left.values()):
                    self.phase = FleetPhase.COMPLETED
                    self._event("complete", now, world=self.world)
                    return FleetAction(kind="complete")
                # every process exited, none crashed: only fenced-style
                # exits remain (a gang that aborted a generation on its
                # own) — resolve through the restart budget instead of
                # holding forever
                self.phase = FleetPhase.FENCED
                self._fence_reason = "gang_exited"
                self._event("fence", now, dead=[], reason="gang_exited")
                return FleetAction(kind="fence", dead=[],
                                   reason="gang_exited")
            return FleetAction(kind="hold")
        if self.phase is FleetPhase.FENCED:
            if len(self._left) < self.world:
                return FleetAction(kind="hold")  # drain in progress
            return self._restart_decision(now)
        return FleetAction(kind="hold")

    def worker_fence(self, now: float, reason: str) -> None:
        """Adopt a fence the WORKERS raised themselves (online retune:
        the plan tuner published ``retune:*`` before adding the fence
        counter).  The gang moves to FENCED with NO eviction and the
        restart is flagged planned.  Adopting BEFORE any drain fallout
        lands matters: once rank 0 (which hosts the jax.distributed
        coordination service) fast-exits ``EXIT_FENCED``, a still-
        draining peer may be killed by the coordinator loss — that
        death is drain mechanics, not a membership change, and must
        spend neither eviction nor crash budget."""
        if self.phase not in (FleetPhase.LAUNCHING, FleetPhase.RUNNING):
            return
        self.phase = FleetPhase.FENCED
        self.planned_fence = True
        self._fence_reason = reason
        self._event("fence", now, dead=[], reason=reason)

    def _restart_decision(self, now: float) -> FleetAction:
        # a fence raised during LAUNCHING may leave ranks that never
        # registered at all: they are not survivors either
        dead = sorted(self._evicted |
                      (set(range(self.world)) - set(self._beats)))
        survivors = self.world - len(dead)
        if survivors < self.policy.min_world:
            self.phase = FleetPhase.FAILED
            self._event("fail", now, reason="below_min_world",
                        survivors=survivors)
            return FleetAction(
                kind="fail", dead=dead,
                reason=f"{survivors} survivors < min_world="
                       f"{self.policy.min_world} ({self._fence_reason})")
        if not self.planned_fence and \
                self.restarts >= self.policy.max_restarts:
            self.phase = FleetPhase.FAILED
            self._event("fail", now, reason="restart_budget",
                        restarts=self.restarts)
            return FleetAction(
                kind="fail", dead=dead,
                reason=f"restart budget exhausted "
                       f"({self.restarts}/{self.policy.max_restarts})")
        self.phase = FleetPhase.RESTARTING
        backoff = 0.0 if self.planned_fence \
            else self.policy.backoff_s(self.restarts + 1)
        self._event("restart", now, world=survivors, dead=dead,
                    restart_id=self.restarts + 1, backoff_s=backoff,
                    planned=self.planned_fence)
        return FleetAction(kind="restart", dead=dead, world=survivors,
                           backoff_s=backoff)

    # -- replica mode (the serving fleet's per-replica supervision) -----------
    # A training gang fences and restarts as ONE unit: a lost rank tears
    # the collective, so everyone drains and the gang respawns at the
    # surviving world size. A SERVING fleet is the opposite shape — the
    # replicas are independent, the survivors must keep serving, and the
    # dead one restarts ALONE. These methods drive that per-rank
    # lifecycle against the same beats/eviction/timeline state (one
    # membership record, one grace window, one budget/backoff policy),
    # without touching the gang decision paths above.

    def replica_fence(self, rank: int, now: float, cause: str,
                      rc: Optional[int] = None) -> bool:
        """Fence ONE replica (crash rc / stale heartbeat / operator).
        Records evict+fence in the timeline; the fleet phase is untouched
        because the survivors keep serving. Idempotent per incarnation —
        returns False when the rank is already fenced."""
        if rank in self._evicted:
            return False
        self._evicted.add(rank)
        self._event("evict", now, rank=rank, cause=cause, rc=rc,
                    last_beat=self._beats.get(rank))
        self._event("fence", now, dead=[rank], reason=cause)
        # the beat record dies with the incarnation: a hung-not-dead
        # process that wakes later must not flap a fenced replica back
        self._beats.pop(rank, None)
        return True

    def replica_restart_decision(self, rank: int, now: float) -> FleetAction:
        """Restart-or-fail for ONE fenced replica: per-rank budget, the
        shared exponential-capped backoff formula."""
        n = self._rank_restarts.get(rank, 0)
        if n >= self.policy.max_restarts:
            self._event("fail", now, rank=rank, reason="restart_budget",
                        restarts=n)
            return FleetAction(
                kind="fail", dead=[rank],
                reason=f"replica {rank} restart budget exhausted "
                       f"({n}/{self.policy.max_restarts})")
        backoff = self.policy.backoff_s(n + 1)
        self._event("restart", now, rank=rank, restart_id=n + 1,
                    backoff_s=backoff)
        return FleetAction(kind="restart", dead=[rank], backoff_s=backoff)

    def replica_restarted(self, rank: int, now: float,
                          count: bool = True) -> None:
        """The supervisor respawned one replica: clear its fenced state so
        its first beat re-joins membership. ``count=False`` is the planned
        rolling-restart path — it spends no restart budget."""
        if count:
            self._rank_restarts[rank] = self._rank_restarts.get(rank, 0) + 1
            self.restarts += 1
        self._evicted.discard(rank)
        self._beats.pop(rank, None)
        self._left.pop(rank, None)

    def replica_restart_counts(self) -> Dict[int, int]:
        return dict(self._rank_restarts)

    def note(self, event: str, now: float, **data) -> None:
        """Record a supervisor-annotated event (planned rolling restart,
        brownout transition) in the membership timeline — one ordered
        record of everything that happened to the fleet."""
        self._event(event, now, **data)

    def restarted(self, now: float, world: int) -> None:
        """The supervisor re-spawned the gang: reset per-generation state.
        A planned (retune) fence rolls the generation without touching
        the crash-restart budget."""
        if not self.planned_fence:
            self.restarts += 1
        self.planned_fence = False
        self.gen += 1
        self.world = int(world)
        self.phase = FleetPhase.LAUNCHING
        self._beats = {}
        self._evicted = set()
        self._left = {}
        self._start_t = float(now)

    def snapshot(self) -> Dict[str, Any]:
        snap = {"phase": self.phase.value, "gen": self.gen,
                "world": self.world, "restarts": self.restarts,
                "timeline": list(self.timeline)}
        if self._rank_restarts:
            snap["rank_restarts"] = {str(r): n for r, n
                                     in self._rank_restarts.items()}
        return snap


# ---------------------------------------------------------------------------
# store helpers: publish/probe (get blocks on absent keys by design)
# ---------------------------------------------------------------------------

def _publish(store, key: str, value) -> None:
    data = value if isinstance(value, (bytes, bytearray)) else \
        json.dumps(value).encode()
    store.set(key, data)
    store.add(f"{key}/published", 1)


def _probe(store, key: str):
    """Non-blocking read: None when unpublished (the ElasticManager
    store_get_nowait idiom, shared fleet-wide)."""
    if store.add(f"{key}/published", 0) < 1:
        return None
    return store.get(key)


def _probe_json(store, key: str):
    raw = _probe(store, key)
    return None if raw is None else json.loads(raw)


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

class ElasticFleet:
    """The coordinator: owns the control-plane ``TCPStore``, spawns the
    worker gang, drives ``FleetStateMachine`` decisions, and survives
    worker failures by fencing + bounded gang restarts.

    ``cmd`` is the worker command (each rank gets ``PT_FLEET_*`` env and
    ``PADDLE_TRAINER_ID``); workers normally call :func:`elastic_fit` (or
    build a :class:`FleetWorkerContext` themselves). ``run()`` returns a
    report dict; the ``fleet`` hub provider serves the live view.
    """

    def __init__(self, cmd: Sequence[str], np: int,
                 policy: Optional[FleetPolicy] = None,
                 min_np: Optional[int] = None,
                 max_restarts: Optional[int] = None,
                 log_dir: Optional[str] = None,
                 ckpt_root: Optional[str] = None,
                 flight_root: Optional[str] = None,
                 extra_env: Optional[Dict[str, str]] = None):
        from ..store import TCPStore

        self.cmd = list(cmd)
        self.np = int(np)
        self.policy = policy or FleetPolicy()
        if min_np is not None:
            self.policy.min_world = int(min_np)
        if max_restarts is not None:
            self.policy.max_restarts = int(max_restarts)
        self.log_dir = log_dir
        self.ckpt_root = ckpt_root
        self.flight_root = flight_root
        self.extra_env = dict(extra_env or {})
        self.store = TCPStore(is_master=True, world_size=1)
        self.sm = FleetStateMachine(self.np, self.policy,
                                    now=time.time())
        self.recoveries: List[Dict[str, Any]] = []  # wall-clock breakdowns
        self.plans: Dict[int, Any] = {}             # gen -> published plan
        self._beat_payload: Dict[int, float] = {}   # rank -> last beat ts
        self.forensics_path: Optional[str] = None
        self._ctx = None
        self._gen_t0 = 0.0
        from ...analysis.lockdep import lock as _named_lock  # lazy

        self._lock = _named_lock("fleet.FleetSupervisor._lock")
        self._register_provider()

    # -- provider -------------------------------------------------------------
    def _register_provider(self) -> None:
        try:
            from ...observability import register_provider

            register_provider("fleet", self.provider_snapshot)
        except Exception:
            pass

    def provider_snapshot(self) -> Dict[str, Any]:
        """The fleet-wide anomaly view: membership timeline, per-rank
        heartbeat ages, restart/recovery breakdowns, per-rank flight
        bundle paths, the per-generation plan digests."""
        with self._lock:
            now = time.time()
            snap = self.sm.snapshot()
            snap["policy"] = {
                "min_world": self.policy.min_world,
                "max_restarts": self.policy.max_restarts,
                "heartbeat_timeout": self.policy.heartbeat_timeout,
                "backoff_base_s": self.policy.backoff_base_s,
            }
            snap["ranks"] = {
                str(r): {"last_heartbeat_age_s": round(now - ts, 3)}
                for r, ts in self.sm._beats.items()}
            snap["recoveries"] = list(self.recoveries)
            snap["plans"] = {str(g): p for g, p in self.plans.items()}
            gen, world = self.sm.gen, self.sm.world
            if self.forensics_path:
                snap["forensics"] = self.forensics_path
        # store probes + bundle dir walk are TCP/disk I/O: done with the
        # lock RELEASED so a telemetry scrape can never stall the
        # supervisor loop behind a slow store round-trip (CC001)
        snap["flight_bundles"] = self._rank_bundles()
        snap["worker_exits"] = self._worker_exits(gen, world)
        return snap

    def _worker_exits(self, gen: int, world: int) -> Dict[str, Any]:
        """The structured exit/done records workers publish on their way
        out (code + reason + ts) — richer than the raw process rc the
        state machine classifies on, and what the forensics bundle quotes
        for 'why did rank r leave'."""
        out: Dict[str, Any] = {}
        try:
            for r in range(world):
                rec = _probe_json(self.store, f"fleet/{gen}/exit/{r}")
                if rec is not None:
                    out[str(r)] = rec
                elif _probe(self.store,
                            f"fleet/{gen}/done/{r}") is not None:
                    out[str(r)] = {"code": 0, "reason": "done"}
        except Exception:
            pass  # store already closed: the rc classification stands
        return out

    def _rank_bundles(self) -> Dict[str, List[str]]:
        """Per-rank pd_dump bundle paths under the fleet flight root
        (satellite: concurrent workers never clobber each other — each
        dumps under ``PT_FLIGHT_DIR/rank<r>/``)."""
        root = self.flight_root or os.environ.get("PT_FLIGHT_DIR")
        out: Dict[str, List[str]] = {}
        if not root or not os.path.isdir(root):
            return out
        try:
            for d in sorted(os.listdir(root)):
                if not d.startswith("rank"):
                    continue
                sub = os.path.join(root, d)
                bundles = sorted(
                    os.path.join(sub, b) for b in os.listdir(sub)
                    if b.startswith("pd_dump"))
                if bundles:
                    out[d] = bundles
        except OSError:
            pass
        return out

    # -- spawning -------------------------------------------------------------
    def _spawn(self, world: int, gen: int):
        from ..launch.process import ProcessContext
        from ..run.master import PortReservation

        # heartbeat reset: the previous generation's stale timestamps must
        # not condemn freshly spawned workers before their first beat
        for r in range(self.np):
            self.store.delete_key(f"elastic/worker/{r}")
            self.store.delete_key(f"elastic/worker/{r}/published")
        self._beat_payload = {}
        # one jax.distributed coordinator port per generation, held bound
        # until just before the workers that bind it spawn (TOCTOU)
        res = PortReservation()
        coord_port = res.port
        resume_dir = ""
        if gen > 0 and self.ckpt_root:
            resume_dir = pick_resume_dir(self.ckpt_root) or ""
        env = dict(self.extra_env)
        env.update({
            "PT_FLEET_ENDPOINT": f"127.0.0.1:{self.store.port}",
            "PT_FLEET_COORDINATOR": f"127.0.0.1:{coord_port}",
            "PT_FLEET_GEN": str(gen),
            "PT_FLEET_WORLD": str(world),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_RESTART_ID": str(gen),
        })
        if self.ckpt_root:
            env["PT_FLEET_CKPT_ROOT"] = self.ckpt_root
        if resume_dir:
            env["PT_FLEET_RESUME_DIR"] = resume_dir
        if self.flight_root:
            env["PT_FLIGHT_DIR"] = self.flight_root

        def rank_env(r):
            return {"PT_FLEET_RANK": str(r)}

        log_dir = os.path.join(self.log_dir, f"gen{gen}") \
            if self.log_dir else None
        res.release()
        ctx = ProcessContext.start(self.cmd, world, base_env=env,
                                   log_dir=log_dir, extra_env_fn=rank_env)
        return ctx

    def _poll_beats(self):
        """Read worker beats (and any unpublished plan) off the store —
        TCP round-trips, so called from the supervisor thread with NO
        lock held (CC001: a telemetry scrape must never queue behind a
        store probe). gen/world only mutate on this same thread."""
        beats: Dict[int, float] = {}
        for r in range(self.sm.world):
            beat = _probe_json(self.store, f"elastic/worker/{r}")
            if beat is None:
                continue
            try:
                beats[r] = float(beat["ts"])
            except (KeyError, TypeError, ValueError):
                continue
        plan = None
        if self.sm.gen not in self.plans:
            plan = _probe_json(self.store, f"fleet/{self.sm.gen}/plan")
        wfence = None
        if self.sm.phase in (FleetPhase.LAUNCHING, FleetPhase.RUNNING):
            reason = _probe_json(self.store,
                                 f"fleet/{self.sm.gen}/fence_reason")
            if isinstance(reason, str) and reason.startswith("retune:"):
                wfence = reason
        return beats, plan, wfence

    def _pump_heartbeats(self, now: float, beats: Dict[int, float],
                         plan) -> None:
        """Feed polled beats (and any published plan) into the machine.
        The machine is fed the SUPERVISOR's receipt time, deduped on the
        worker-written payload ts: staleness must never compare clocks
        across hosts — a worker host lagging the supervisor by more than
        the grace window would otherwise be falsely evicted on every
        beat."""
        for r, ts in beats.items():
            if self._beat_payload.get(r) == ts:
                continue  # same beat re-read, not a fresh one
            self._beat_payload[r] = ts
            self.sm.heartbeat(r, now)
        if plan is not None and self.sm.gen not in self.plans:
            self.plans[self.sm.gen] = plan

    def fence(self, reason: str = "operator") -> None:
        """Raise the fence for the current generation: workers drain at
        the next step boundary (or abandon a torn collective) and exit.
        A reason already published for this generation wins — a worker
        that raised the fence itself (online retune) named WHY, and the
        supervisor's later mirror (e.g. ``gang_exited``) must not
        overwrite it."""
        gen = self.sm.gen
        self.store.add(f"fleet/{gen}/fence", 1)
        if _probe_json(self.store, f"fleet/{gen}/fence_reason") is None:
            _publish(self.store, f"fleet/{gen}/fence_reason", reason)

    # -- the supervisor loop --------------------------------------------------
    def run(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        """Launch and supervise until COMPLETED or FAILED; returns the
        report (phase, restarts, timeline, recoveries, forensics path on
        failure)."""
        from ..resilience.faults import injector

        deadline = None if timeout is None else time.time() + timeout
        self._gen_t0 = time.time()
        self._ctx = self._spawn(self.np, 0)
        recovery: Optional[Dict[str, Any]] = None
        while True:
            now = time.time()
            if deadline is not None and now > deadline:
                with self._lock:
                    self.sm.phase = FleetPhase.FAILED
                    self.sm._event("fail", now, reason="timeout")
                self._ctx.terminate()
                return self._finish("timeout")
            if injector().peek("coordinator_lost", gen=self.sm.gen):
                # the control plane dies: workers must notice their store
                # is gone and exit cleanly on their own (no orphans)
                self.store.close()
                self._ctx.wait(timeout=60)
                with self._lock:
                    self.sm.phase = FleetPhase.FAILED
                    self.sm._event("fail", now, reason="coordinator_lost")
                return self._finish("coordinator_lost", forensics=False)
            # store I/O: lock released
            beats, plan, wfence = self._poll_beats()
            with self._lock:
                self._pump_heartbeats(now, beats, plan)
                if wfence is not None and recovery is None:
                    # a WORKER raised this generation's fence (online
                    # retune): adopt it now, before any drain fallout
                    # lands — see FleetStateMachine.worker_fence
                    self.sm.worker_fence(now, wfence)
                    recovery = {"gen": self.sm.gen, "reason": wfence,
                                "dead": [], "fence_t": now,
                                "planned": True,
                                "detect_ms": round(
                                    (now - self._gen_t0) * 1e3, 1)}
                exits = {e.rank: e.proc.poll() for e in self._ctx.entries}
                act = self.sm.observe(now, exits)
            if act.kind == "hold":
                if recovery is not None and \
                        now - recovery["fence_t"] > \
                        self.policy.drain_timeout_s:
                    # drain window expired: kill stragglers so the fenced
                    # state can resolve into a restart/fail decision
                    self._ctx.terminate()
                time.sleep(self.policy.poll_interval)
                continue
            if act.kind == "fence":
                self.fence(act.reason)
                # the canonical reason is whatever is NOW published for
                # this gen — a worker-raised retune fence keeps its name
                # (and flags the restart as planned: no budget spent)
                published = _probe_json(
                    self.store, f"fleet/{self.sm.gen}/fence_reason")
                reason = published if isinstance(published, str) \
                    and published else act.reason
                if reason.startswith("retune:"):
                    with self._lock:
                        self.sm.planned_fence = True
                recovery = {"gen": self.sm.gen, "reason": reason,
                            "dead": act.dead, "fence_t": now,
                            "planned": reason.startswith("retune:"),
                            "detect_ms": round((now - self._gen_t0) * 1e3,
                                               1)}
                continue
            if act.kind == "restart":
                drained_t = time.time()
                self._ctx.terminate()   # reap stragglers + close logs
                teardown_t = time.time()
                if act.backoff_s:
                    time.sleep(act.backoff_s)
                with self._lock:
                    self.sm.restarted(time.time(), act.world)
                self._gen_t0 = time.time()
                self._ctx = self._spawn(act.world, self.sm.gen)
                spawn_t = time.time()
                if recovery is not None:
                    recovery.update({
                        "drain_ms": round(
                            (drained_t - recovery["fence_t"]) * 1e3, 1),
                        "teardown_ms": round(
                            (teardown_t - drained_t) * 1e3, 1),
                        "backoff_ms": round(act.backoff_s * 1e3, 1),
                        "respawn_ms": round((spawn_t - teardown_t) * 1e3,
                                            1),
                        "new_world": act.world,
                        "restart_id": self.sm.restarts,
                    })
                    with self._lock:
                        self.recoveries.append(recovery)
                recovery = None
                continue
            if act.kind == "complete":
                return self._finish("completed", forensics=False)
            if act.kind == "fail":
                self._ctx.terminate()
                return self._finish(act.reason)

    def _note_first_step(self) -> None:
        """Recovery ends when the restarted gang trains again: rank 0
        publishes its first completed step's wall time per generation."""
        for rec in self.recoveries:
            if "resume_ms" in rec:
                continue
            try:
                ts = _probe_json(self.store,
                                 f"fleet/{rec['gen'] + 1}/first_step_ts")
            except Exception:
                ts = None
            if ts is not None:
                rec["resume_ms"] = round(
                    (float(ts) - rec["fence_t"]) * 1e3, 1)

    def _finish(self, reason: str, forensics: Optional[bool] = None
                ) -> Dict[str, Any]:
        try:
            self._note_first_step()
        except Exception:
            pass
        report = self.report()
        report["reason"] = reason
        if forensics is None:
            forensics = self.sm.phase is FleetPhase.FAILED
        if forensics:
            try:
                self.forensics_path = self.dump_forensics(reason)
                report["forensics"] = self.forensics_path
            except Exception:
                pass
        return report

    def report(self) -> Dict[str, Any]:
        return self.provider_snapshot()

    # -- forensics ------------------------------------------------------------
    def dump_forensics(self, reason: str = "manual") -> str:
        """A failed fleet leaves one aggregated bundle: the provider
        snapshot, every worker's log tail, and the per-rank flight-bundle
        paths — MANIFEST.json written LAST (a bundle with a manifest is
        complete, the pd_dump contract)."""
        import tempfile

        root = self.flight_root or os.environ.get("PT_FLIGHT_DIR") or \
            os.path.join(tempfile.gettempdir(), "pt_flight_dumps")
        stamp = time.strftime("%Y%m%d_%H%M%S", time.gmtime())
        safe = "".join(c if c.isalnum() else "_" for c in reason)[:32]
        path = os.path.join(root, f"fleet_forensics_{stamp}_"
                                  f"{os.getpid()}_{safe}")
        os.makedirs(path, exist_ok=True)
        files: Dict[str, Any] = {}

        def _write(name, payload):
            try:
                p = os.path.join(path, name)
                with open(p, "w") as f:
                    json.dump(payload, f, indent=1, default=str)
                files[name] = {"bytes": os.path.getsize(p)}
            except Exception as e:
                files[name] = {"error": str(e)[:200]}

        _write("fleet_report.json", self.provider_snapshot())
        tails: Dict[str, str] = {}
        if self._ctx is not None:
            for e in self._ctx.entries:
                if e.log_path and os.path.exists(e.log_path):
                    try:
                        with open(e.log_path, "rb") as f:
                            f.seek(max(os.path.getsize(e.log_path) - 4096,
                                       0))
                            tails[f"rank{e.rank}"] = \
                                f.read().decode(errors="replace")
                    except OSError:
                        pass
        _write("worker_log_tails.json", tails)
        manifest = {"reason": reason, "time_utc": stamp,
                    "pid": os.getpid(), "files": files}
        tmp = os.path.join(path, "MANIFEST.json.tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, os.path.join(path, "MANIFEST.json"))
        return path

    def close(self) -> None:
        if self._ctx is not None:
            self._ctx.terminate()
        try:
            self.store.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------

def latest_commit_step(root: str) -> Optional[int]:
    """Step of ``root``'s newest committed checkpoint, or None — through
    ``resilience.commit.read_latest``, so a torn/stale ``LATEST`` file
    degrades to the newest complete dir on disk exactly like ``resume()``
    will when it reads the same root."""
    from ..resilience import commit as commit_mod

    tag = commit_mod.read_latest(root)
    if not tag:
        return None
    try:
        meta = commit_mod.load_manifest(os.path.join(root, tag)) \
            .get("meta", {})
        return int(meta["step"])
    except (OSError, ValueError, KeyError, TypeError):
        return None


def pick_resume_dir(ckpt_root: str) -> Optional[str]:
    """The authoritative resume dir after a membership change: every
    rank's per-rank checkpoint dir is scanned for its newest committed
    step; the max step wins, ties to the lowest rank. Deterministic reads
    of the shared filesystem — every worker (and the supervisor) computes
    the same answer, so no coordination is needed."""
    best: Optional[tuple] = None
    if not os.path.isdir(ckpt_root):
        return None
    for d in sorted(os.listdir(ckpt_root)):
        if not d.startswith("rank"):
            continue
        root = os.path.join(ckpt_root, d)
        try:
            rank = int(d[4:])
        except ValueError:
            continue
        step = latest_commit_step(root)
        if step is None:
            continue
        key = (step, -rank)
        if best is None or key > best[0]:
            best = (key, root)
    return None if best is None else best[1]


class FleetWorkerContext:
    """One worker's handle on the fleet: membership heartbeats, the
    fence, the store allreduce, re-planning, and the fast clean exit.
    Standalone mode (no ``PT_FLEET_ENDPOINT``) degrades every fleet
    operation to a no-op so the same training script runs un-supervised.
    """

    def __init__(self, rank: int, world: int, gen: int = 0,
                 store=None, coordinator: Optional[str] = None,
                 ckpt_root: Optional[str] = None,
                 resume_dir: Optional[str] = None,
                 heartbeat_interval: float = 0.5):
        self.rank = int(rank)
        self.world = int(world)
        self.gen = int(gen)
        self.store = store
        self.coordinator = coordinator
        self.ckpt_root = ckpt_root
        self.resume_dir = resume_dir
        self.manager = None
        self._hb_interval = heartbeat_interval
        self._gstep = 0
        self._store_failures = 0
        self._jax_dist = False
        self._fenced = False

    # -- bootstrap ------------------------------------------------------------
    @classmethod
    def from_env(cls) -> "FleetWorkerContext":
        from ..store import TCPStore

        rank = int(os.environ.get("PT_FLEET_RANK",
                                  os.environ.get("PADDLE_TRAINER_ID", 0)))
        world = int(os.environ.get("PT_FLEET_WORLD",
                                   os.environ.get("PADDLE_TRAINERS_NUM",
                                                  1)))
        gen = int(os.environ.get("PT_FLEET_GEN",
                                 os.environ.get("PADDLE_RESTART_ID", 0)))
        endpoint = os.environ.get("PT_FLEET_ENDPOINT")
        store = None
        if endpoint:
            host, port = endpoint.rsplit(":", 1)
            store = TCPStore(host=host, port=int(port), world_size=world,
                             timeout=60)
        return cls(rank, world, gen, store=store,
                   coordinator=os.environ.get("PT_FLEET_COORDINATOR"),
                   ckpt_root=os.environ.get("PT_FLEET_CKPT_ROOT"),
                   resume_dir=os.environ.get("PT_FLEET_RESUME_DIR") or None)

    def register(self) -> "FleetWorkerContext":
        """Start heartbeating (hardened ElasticManager): the first beat
        IS the registration signal the supervisor joins membership on."""
        if self.store is None:
            return self
        from .elastic import ElasticManager

        self.manager = ElasticManager(
            self.store, self.rank, self.world,
            heartbeat_interval=self._hb_interval).register()
        return self

    def init_jax_distributed(self) -> bool:
        """Initialize ``jax.distributed`` against this generation's
        coordinator (rank 0 hosts the service). Gated off by
        ``PT_FLEET_JAX_DIST=0`` and skipped for world-1 fleets.

        jax requires this BEFORE any computation runs — and importing
        ``paddle_tpu`` itself runs some (generator seeding, backend
        probes) — so worker scripts normally run the
        ``jax.distributed.initialize`` handshake from the ``PT_FLEET_*``
        env as their FIRST act, before the paddle_tpu import; this
        method then just adopts the live client."""
        if self.world <= 1 or not self.coordinator or \
                os.environ.get("PT_FLEET_JAX_DIST", "1") in ("0", "false"):
            return False
        import jax
        from jax._src import distributed as _jd

        if getattr(_jd.global_state, "client", None) is not None:
            self._jax_dist = True  # bootstrapped before import
            return True
        jax.distributed.initialize(coordinator_address=self.coordinator,
                                   num_processes=self.world,
                                   process_id=self.rank)
        self._jax_dist = True
        return True

    # -- fence + faults -------------------------------------------------------
    def fenced(self) -> bool:
        """Probe the generation fence (one non-blocking store add)."""
        if self._fenced or self.store is None:
            return self._fenced
        try:
            if self.store.add(f"fleet/{self.gen}/fence", 0) > 0:
                self._fenced = True
            self._store_failures = 0
        except Exception:
            self._coord_failure()
        return self._fenced

    def _coord_failure(self) -> None:
        """A dead control plane means nobody will fence or restart us:
        after a few consecutive failures the worker exits cleanly rather
        than training as an orphan."""
        self._store_failures += 1
        if self._store_failures >= 3:
            self.exit(EXIT_COORD_LOST, reason="coordinator_lost")

    def step_site(self, gstep: Optional[int] = None) -> None:
        """Per-step hook (FleetCallback calls it at every batch end):
        fires the deterministic ``worker_crash`` fault, then polls the
        fence — a fenced worker requests the preemption path so ``fit``
        drains the lane and commits before stopping."""
        from ..resilience.faults import injector
        from ..resilience.preempt import request_preemption

        g = self._gstep if gstep is None else int(gstep)
        # gen is a match id so a drill rule (worker_crash@rank=2&step=6&
        # gen=0) cannot re-fire in the restarted generation, whose resumed
        # ranks walk the same global step numbers again
        if injector().peek("worker_crash", rank=self.rank, step=g,
                           gen=self.gen):
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(43)  # a crash does not unwind
        if self.fenced():
            request_preemption()
        self._gstep = g + 1

    # -- collectives (control-plane allreduce for CPU fleets) -----------------
    def allreduce(self, arrays: List, step: int, timeout: float = 120.0,
                  tag: str = "grad") -> List:
        """Mean-allreduce numpy arrays through the store: publish this
        rank's payload, poll every peer's (fence-aware — a dead peer's
        payload never arrives, the fence does), average in rank order
        (every rank computes the bit-identical result). One step's keys
        are retired two steps later by their owner. World-1/standalone
        returns the input unchanged."""
        import numpy as np

        if self.world <= 1 or self.store is None:
            return list(arrays)
        flat = np.concatenate([np.asarray(a).ravel() for a in arrays])
        prefix = f"fleet/{self.gen}/ar/{tag}"
        _publish(self.store, f"{prefix}/{step}/{self.rank}",
                 flat.astype(np.float32).tobytes())
        acc = np.zeros_like(flat, dtype=np.float64)
        deadline = time.time() + timeout
        for r in range(self.world):
            while True:
                raw = _probe(self.store, f"{prefix}/{step}/{r}")
                if raw is not None:
                    break
                if self.fenced():
                    raise FleetFenced(
                        f"fenced while waiting for rank {r}'s {tag} at "
                        f"step {step}")
                if time.time() > deadline:
                    raise TimeoutError(
                        f"allreduce[{tag}] step {step}: rank {r} never "
                        f"published within {timeout}s (and no fence "
                        f"arrived)")
                time.sleep(0.02)
            acc += np.frombuffer(raw, dtype=np.float32).astype(np.float64)
        old = step - 2
        if old >= 0:
            self.store.delete_key(f"{prefix}/{old}/{self.rank}")
            self.store.delete_key(f"{prefix}/{old}/{self.rank}/published")
        mean = (acc / self.world).astype(np.float32)
        out, off = [], 0
        for a in arrays:
            a = np.asarray(a)
            out.append(mean[off:off + a.size].reshape(a.shape)
                       .astype(a.dtype, copy=False))
            off += a.size
        return out

    # -- re-plan --------------------------------------------------------------
    def replan(self, model, *, batch: int, sample_batch=None, loss_fn=None,
               hbm_bytes: Optional[float] = None, **enum_kw
               ) -> Optional[Dict[str, Any]]:
        """Run the PR-9 planner for THIS generation's world size. Rank 0
        computes and publishes the pick; other ranks read it (one
        deterministic answer fleet-wide). Standalone mode plans locally.

        An online-tuner override (``fleet/plan_override``, published by
        the plan-rerank policy before it raised its retune fence) wins
        over a fresh plan when its mesh still covers this generation's
        world size — the tuner already re-scored the cached candidates
        under live profiles; re-planning from priors here would undo the
        swap the fence was raised FOR."""
        if self.store is None or self.rank == 0:
            desc = None
            if self.store is not None:
                ov = _probe_json(self.store, "fleet/plan_override")
                if isinstance(ov, dict):
                    mesh = ov.get("config", {}).get("mesh", {})
                    total = 1
                    for v in mesh.values():
                        total *= int(v)
                    if total == self.world:
                        desc = ov
            if desc is None:
                cand = replan_for_world(model, self.world, batch=batch,
                                        sample_batch=sample_batch,
                                        loss_fn=loss_fn,
                                        hbm_bytes=hbm_bytes, **enum_kw)
                desc = cand.to_dict() if hasattr(cand, "to_dict") else cand
            if self.store is not None:
                _publish(self.store, f"fleet/{self.gen}/plan", desc)
            return desc
        deadline = time.time() + 120
        while True:
            p = _probe_json(self.store, f"fleet/{self.gen}/plan")
            if p is not None:
                return p
            if self.fenced() or time.time() > deadline:
                return None
            time.sleep(0.05)

    # -- lifecycle ------------------------------------------------------------
    def mark_first_step(self) -> None:
        if self.store is not None and self.rank == 0:
            _publish(self.store, f"fleet/{self.gen}/first_step_ts",
                     time.time())

    def mark_done(self) -> None:
        if self.store is not None:
            _publish(self.store, f"fleet/{self.gen}/done/{self.rank}",
                     {"ts": time.time()})

    def exit(self, code: int, reason: str = "") -> None:
        """Fast clean exit. ``os._exit`` on purpose: a fenced worker that
        unwinds the interpreter destroys its ``jax.distributed`` client,
        whose destructor blocks in the XLA shutdown barrier waiting for
        the dead peer (~100 s) and then aborts the process. Everything
        durable (checkpoints, flight bundles) is already committed under
        manifest-last protocols, so skipping destructors loses nothing.
        """
        try:
            if self.store is not None:
                _publish(self.store,
                         f"fleet/{self.gen}/exit/{self.rank}",
                         {"code": int(code), "reason": reason,
                          "ts": time.time()})
        except Exception:
            pass
        try:
            sys.stdout.flush()
            sys.stderr.flush()
        except Exception:
            pass
        os._exit(int(code))

    def close(self) -> None:
        """Graceful teardown for the COMPLETED path (every peer alive):
        stop heartbeating and leave the jax.distributed barrier quickly
        while the whole gang is still present."""
        if self.manager is not None:
            self.manager.exit()
        if self._jax_dist:
            try:
                import jax

                jax.distributed.shutdown()
            except Exception:
                pass
        self._jax_dist = False


# ---------------------------------------------------------------------------
# training-side glue: grad sync, dataset sharding, the fit driver
# ---------------------------------------------------------------------------

class FleetGradSync:
    """Optimizer wrapper: mean-allreduce every parameter gradient across
    the fleet before the inner optimizer applies it (the CPU fleet's
    data-parallel glue; a TPU global mesh does this inside XLA). The
    wrapper delegates everything else, so checkpointing sees the real
    optimizer state."""

    _OWN = ("_opt", "_ctx", "_step")

    def __init__(self, optimizer, ctx: FleetWorkerContext):
        object.__setattr__(self, "_opt", optimizer)
        object.__setattr__(self, "_ctx", ctx)
        object.__setattr__(self, "_step", 0)

    def step(self):
        import numpy as np

        from ...core.tensor import Tensor

        params = [p for p in self._opt._parameter_list
                  if not p.stop_gradient and p.grad is not None]
        if params and self._ctx.world > 1:
            grads = [np.asarray(p.grad.data) for p in params]
            avg = self._ctx.allreduce(grads, self._step)
            for p, g in zip(params, avg):
                p.grad = Tensor(g)
        object.__setattr__(self, "_step", self._step + 1)
        return self._opt.step()

    def __getattr__(self, name):
        return getattr(self._opt, name)

    def __setattr__(self, name, value):
        # writes pass through too: the checkpoint restore sets
        # ``optimizer._global_step`` / ``_state_version`` on whatever
        # object fit holds — landing them on the wrapper would silently
        # desync the REAL optimizer's bias-correction step count
        if name in FleetGradSync._OWN:
            object.__setattr__(self, name, value)
        else:
            setattr(self._opt, name, value)


class BlockShardedDataset:
    """Rank r's contiguous slice of every global batch: global step k's
    samples ``[G*k + per*r, G*k + per*(r+1))`` where ``per = G/world``.
    Feeding this to a ``batch_size=per`` loader (shuffle off) makes the
    per-step GLOBAL batch identical at every world size — the property
    that lets a resumed fleet's loss curve stitch onto a run at a
    different world size."""

    def __init__(self, dataset, global_batch: int, rank: int, world: int):
        if global_batch % world:
            raise ValueError(
                f"global_batch={global_batch} must divide by world="
                f"{world} (the planner's dp degree guarantees this)")
        self.dataset = dataset
        self.global_batch = int(global_batch)
        self.per = self.global_batch // int(world)
        self.rank = int(rank)
        self._steps = len(dataset) // self.global_batch

    def __len__(self):
        return self._steps * self.per

    def __getitem__(self, i):
        step, off = divmod(i, self.per)
        return self.dataset[step * self.global_batch +
                            self.per * self.rank + off]


class FleetCallback:
    """Wires the fleet protocol into ``Model.fit``: every trained batch
    runs the worker's step site (deterministic ``worker_crash``, fence
    poll -> preemption request) and the first batch of a restarted
    generation publishes the recovery's ``first_step_ts``."""

    def __init__(self, ctx: FleetWorkerContext, start_step: int = 0):
        self._ctx = ctx
        self._gstep = int(start_step)
        self._first = True
        # hapi CallbackList duck-types hooks via getattr but calls
        # set_model/set_params unconditionally
        self.model = None
        self.params = {}

    def set_model(self, model):
        self.model = model

    def set_params(self, params):
        self.params = params

    def on_train_batch_end(self, step, logs=None):
        if self._first:
            self._first = False
            self._ctx.mark_first_step()
        self._ctx.step_site(self._gstep)
        self._gstep += 1


def replan_for_world(model, world: int, *, batch: int, sample_batch=None,
                     loss_fn=None, hbm_bytes: Optional[float] = None,
                     pure_dp: bool = True, **enum_kw):
    """``plan(model, chips, hbm)`` for a changed device count. With
    ``pure_dp`` (the CPU fleet's executable subset — host-side grad
    allreduce shards only the data axis) the pick is the best-ranked
    candidate whose mesh is a pure dp split covering ``world``."""
    from ..auto_parallel.planner import plan

    kw = dict(enum_kw)
    if pure_dp:
        kw.setdefault("accumulate", (1,))
        kw.setdefault("remat", (False,))
        kw.setdefault("levels", (None,))
        kw.setdefault("offload", (False,))
        kw.setdefault("cp_degrees", (1,))
    cands = plan(model, n_devices=world, hbm_bytes=hbm_bytes, batch=batch,
                 sample_batch=sample_batch, loss_fn=loss_fn, **kw)
    if pure_dp:
        for c in cands:
            mesh = c.config["mesh"]
            if mesh.get("dp", 1) == world and \
                    all(v == 1 for k, v in mesh.items() if k != "dp"):
                return c
        raise ValueError(
            f"replan_for_world: no pure-dp candidate covers world="
            f"{world} at batch={batch} (batch must divide by world)")
    return cands[0]


def elastic_fit(build: Callable[[FleetWorkerContext], Dict[str, Any]], *,
                global_batch: int, epochs: int = 1,
                checkpoint_every: int = 2, fit_kw: Optional[Dict] = None,
                replan: bool = True) -> Dict[str, Any]:
    """The worker entry: bootstrap from env, join the fleet, re-plan for
    this generation's world size, resume from the fleet-wide newest
    checkpoint, and run ``Model.fit`` under the fleet protocol.

    ``build(ctx)`` returns ``{"network", "optimizer", "loss", "dataset"}``
    (plus optional ``"callbacks"``/``"loss_fn"``/``"sample_batch"``/
    ``"hbm_bytes"`` for the planner — the per-device memory budget defaults
    to the device's own ``bytes_limit``; a CPU fleet, whose backend reports
    none, must state one). Returns ``{"losses", "plan", "resumed_from", ...}`` on
    completion; a fenced worker exits the process with ``EXIT_FENCED``
    and a coordinator-lost worker with ``EXIT_COORD_LOST`` (see
    ``FleetWorkerContext.exit`` for why the exit is ``os._exit``-fast).
    """
    import numpy as np

    ctx = FleetWorkerContext.from_env()
    ctx.register()
    ctx.init_jax_distributed()
    parts = build(ctx)
    network, optimizer = parts["network"], parts["optimizer"]
    loss, dataset = parts["loss"], parts["dataset"]

    plan_desc = None
    dp = ctx.world
    if replan:
        plan_desc = ctx.replan(network, batch=global_batch,
                               sample_batch=parts.get("sample_batch"),
                               loss_fn=parts.get("loss_fn"),
                               hbm_bytes=parts.get("hbm_bytes"))
        if plan_desc:
            dp = int(plan_desc.get("config", {}).get("mesh", {})
                     .get("dp", ctx.world)) or ctx.world
    if dp != ctx.world:
        raise ValueError(
            f"elastic_fit: planned dp={dp} != world={ctx.world} — the "
            f"CPU fleet executes pure-dp plans only")

    from ...hapi.model import Model

    opt = FleetGradSync(optimizer, ctx) if ctx.world > 1 else optimizer
    model = Model(network)
    model.prepare(optimizer=opt, loss=loss)

    from ...io import DataLoader

    shard = BlockShardedDataset(dataset, global_batch, ctx.rank, ctx.world)
    # an explicit loader: fit would treat the (non-io.Dataset) shard view
    # as an iterable of ready batches otherwise
    loader = DataLoader(shard, batch_size=shard.per, shuffle=False)
    ckpt_dir = None
    resume: Any = False
    if ctx.ckpt_root:
        ckpt_dir = os.path.join(ctx.ckpt_root, f"rank{ctx.rank}")
        if ctx.gen > 0:
            resume = ctx.resume_dir or pick_resume_dir(ctx.ckpt_root) \
                or False
    start_step = 0
    if isinstance(resume, str):
        committed = latest_commit_step(resume)
        start_step = committed + 1 if committed is not None else 0

    losses: List[float] = []

    class _Recorder:
        """Fleet-wide loss per global step: each rank's local loss is the
        mean over ITS shard, so the recorded value is the mean-allreduce
        across ranks (equal shard sizes: mean of means == the global-
        batch mean) — the property that makes loss curves comparable and
        stitchable across world sizes."""

        def __init__(self):
            self._n = 0

        def set_model(self, m):
            pass

        def set_params(self, p):
            pass

        def on_train_batch_end(self, step, logs=None):
            local = float(np.asarray(logs["loss"]))
            if ctx.world > 1:
                local = float(ctx.allreduce(
                    [np.float32(local)], self._n, tag="loss")[0])
            self._n += 1
            losses.append(local)

    cbs = [_Recorder(), FleetCallback(ctx, start_step=start_step)] + \
        list(parts.get("callbacks") or [])
    kw = dict(epochs=epochs, verbose=0, callbacks=cbs)
    if ckpt_dir:
        kw.update(checkpoint_every=checkpoint_every,
                  checkpoint_dir=ckpt_dir, resume=resume)
    kw.update(fit_kw or {})
    out = {"losses": losses, "plan": plan_desc, "rank": ctx.rank,
           "world": ctx.world, "gen": ctx.gen,
           "resumed_from": resume if isinstance(resume, str) else None,
           "start_step": start_step}
    try:
        model.fit(loader, **kw)
    except FleetFenced:
        # torn step: a collective peer died mid-window — the completed
        # steps' losses still reach the caller (on_exit), the abandoned
        # step is gone, the last committed checkpoint is the resume point
        if parts.get("on_exit"):
            try:
                parts["on_exit"](out)
            except Exception:
                pass
        ctx.exit(EXIT_FENCED, reason="fenced_mid_collective")
    if ctx.fenced():
        # graceful drain: fit already committed the preempt checkpoint
        # at the boundary — report through on_exit, then leave fast
        if parts.get("on_exit"):
            try:
                parts["on_exit"](out)
            except Exception:
                pass
        ctx.exit(EXIT_FENCED, reason="fenced_at_boundary")
    ctx.mark_done()
    ctx.close()
    return out
