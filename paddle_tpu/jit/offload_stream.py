"""Streamed parameter offload: beyond-residence training on one chip
(sized under an older set-up's memory budget: 3.08B streamed where
1.83B was the resident ceiling; not re-measured on the current chip).

Reference: python/paddle/distributed/fleet/meta_parallel/sharding/
sharding_stage3.py:50 (param offload) + :737 (TaskFlow prefetch) — the
reference streams each segment's params H2D ahead of use and keeps the
optimizer state host-side.

TPU-native mapping, ONE compiled step end-to-end:
- the transformer stack's [L, ...] stacked parameters (and their optimizer
  state) live in the TPU's PINNED HOST memory space;
- the forward copies one layer's slice into HBM right before its compute
  (XLA emits async copy-start/done — the prefetch), and autodiff's transpose
  of those copies lands the stacked gradient accumulator back in host memory;
- the optimizer update then walks the layers again: slice param/grad/state
  H2D, apply the functional rule on-device, and dynamic-update-slice the new
  values straight back into the host buffers.
Nothing ever crosses to another backend — every transfer is a TPU runtime
DMA, never a hop through the CPU backend.
HBM holds only: edge params (embeddings/head/norms) + their state, one or
two layers' tensors in flight, and remat boundary activations.

Per-layer optimizer state is initialized per SLICE (factored optimizers see
the true [d1, d2] layer shape, not the stacked [L, d1, d2]) — the same
semantics as training the layers unstacked.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd
from ..core.tensor import Tensor
from ..framework import random as random_mod
from ..nn.layer.layers import Layer


# -- latency-hiding streaming lane -------------------------------------------
# The reference hides host<->device traffic behind compute with
# ForwardPostHooks + TaskFlow prefetch (sharding_stage3.py:737); the
# TPU-native counterpart is a background thread issuing jax.device_put while
# the main thread keeps dispatching executables — the same one-thread double
# buffer io/prefetch.py uses for batches, here carrying parameter/optimizer
# stream groups for the offload train path (ZeRO-Offload's delayed, bucketed
# CPU update, Rajbhandari et al.).

_LANE_FAM = None  # lazily-bound "offload_stream" counter family


def _lane_fam():
    global _LANE_FAM
    if _LANE_FAM is None:
        from ..observability import family

        _LANE_FAM = family("offload_stream", ("metric",))
    return _LANE_FAM


_RESIL = None  # lazily-bound (faults injector, transient, retry_policy)


def _resil():
    global _RESIL
    if _RESIL is None:
        from ..distributed.resilience import metrics as rmetrics
        from ..distributed.resilience.faults import injector
        from ..distributed.resilience.retry import retry_policy, transient

        _RESIL = (injector, transient, retry_policy, rmetrics)
    return _RESIL


_PINNED_PROBE = [False, None]  # (probed, sharding-or-None), process-wide


def _probe_pinned_host():
    """Capability probe: a working ``pinned_host`` memory-kind placement
    on the default accelerator, verified by an actual 1-element
    round-trip (some jax builds LIST the memory kind but cannot place
    into it). CPU backends return None — everything is host RAM there
    and tier-1 must stay byte-identical on the direct path."""
    if _PINNED_PROBE[0]:
        return _PINNED_PROBE[1]
    sh = None
    try:
        from ..distributed.meta_parallel.stage_stack import _memory_sharding

        cand = _memory_sharding("pinned_host")
        if cand is not None:
            probe = jax.device_put(np.zeros((1,), np.float32), cand)
            probe.block_until_ready()
            jax.device_put(probe, jax.devices()[0]).block_until_ready()
            sh = cand
    except Exception:
        sh = None
    _PINNED_PROBE[0] = True
    _PINNED_PROBE[1] = sh
    return sh


def pinned_host_supported() -> bool:
    """Does this backend expose a usable pinned_host staging space?"""
    return _probe_pinned_host() is not None


class StreamTransferError(RuntimeError):
    """A lane transfer failed after its retry budget. Carries the failing
    direction, stream-group tag and parameter names so the raise at the
    consumer's ``wait()`` names WHAT was in flight, not just why. The
    original exception is ``__cause__``."""

    def __init__(self, kind: str, tag, names, cause: BaseException):
        self.kind = kind
        self.tag = tag
        self.names = tuple(names or ())
        named = f" params={list(self.names)}" if self.names else ""
        super().__init__(
            f"stream transfer failed: kind={kind} group={tag}{named}: "
            f"{type(cause).__name__}: {cause}")
        self.__cause__ = cause


def plan_stream_groups(nbytes_list: Sequence[int],
                       segment_size: int = 2 ** 20,
                       buffer_max_size: int = 2 ** 23) -> List[List[int]]:
    """Partition parameters (given per-param byte sizes, walk order
    preserved) into contiguous stream groups — the unit the offload lane
    transfers and the host update executes on.

    ``segment_size`` is the reference group_sharded_parallel knob: a group
    closes once it holds at least this many bytes (small params coalesce
    instead of each paying a transfer/dispatch). ``buffer_max_size`` caps
    the staging buffer: a group never grows past it by adding another
    param (one param larger than the cap still gets its own group — it
    cannot be split without changing the update math)."""
    segment_size = max(int(segment_size), 1)
    buffer_max_size = max(int(buffer_max_size), segment_size)
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, nb in enumerate(nbytes_list):
        nb = int(nb)
        if cur and (cur_bytes + nb > buffer_max_size
                    or cur_bytes >= segment_size):
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        groups.append(cur)
    return groups


def _flight_event(kind: str, **data) -> None:
    """Land a lane event in the flight recorder's ring WHEN one exists
    (never creates one — training runs without a recorder pay only an
    attribute read). Telemetry must never mask the event it records."""
    try:
        from ..observability.trace import flight

        rec = flight._RECORDER
        if rec is not None:
            rec.record_event(kind, **data)
    except Exception:
        pass


class _TransferHandle:
    """One in-flight group transfer; ``wait()`` blocks the consumer and
    charges the blocked time to the lane's ``stall_ms``."""

    __slots__ = ("_event", "_box", "_lane", "_nbytes", "_unstaged",
                 "_dispatched", "_dispatch_taken")

    def __init__(self, lane):
        self._event = threading.Event()
        self._dispatched = threading.Event()  # transfers ISSUED (results
        # exist as jax futures) even though bytes may still be in flight
        self._dispatch_taken = False  # a consumer HOLDS the issued
        # futures (set under the lane lock by wait_dispatched)
        self._box: list = [None, None]  # result, exception
        self._lane = lane
        self._nbytes = 0      # staged bytes this handle accounts for
        self._unstaged = False  # staging decrement already applied

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self):
        if not self._event.is_set():
            t0 = time.perf_counter()
            self._event.wait()
            self._lane._note_stall((time.perf_counter() - t0) * 1e3)
        if self._box[1] is not None:
            raise self._box[1]
        return self._box[0]

    def _set_dispatched(self, out) -> None:
        with self._lane._lock:
            self._box[0] = out
        self._dispatched.set()

    def _unpublish_for_retry(self) -> bool:
        """Worker-side half of the retry handshake: withdraw the issued
        futures so the retry can republish. Returns False when a
        consumer already took them — then retrying is unsafe (their
        arrays could not be replaced) and the caller must fail sticky."""
        with self._lane._lock:
            if self._dispatch_taken:
                return False
            self._box[0] = None
            self._dispatched.clear()
            return True

    def wait_dispatched(self):
        """Return the transfer's result arrays as soon as they are ISSUED
        (jax async futures) instead of landed — the cross-step pipeline
        fill: a consumer handing these straight to the next dispatched
        executable lets the runtime sequence the landing while the host
        races ahead to submit the next step's group-0 grad download. A
        transfer that fails after issue surfaces at the next lane
        interaction (the PR-6 sticky-failure contract), not here."""
        t0 = None
        while True:
            if self._event.is_set():
                break  # terminal: landed or failed-for-good
            if self._dispatched.is_set():
                taken = None
                with self._lane._lock:
                    if self._box[0] is not None:
                        # taking the futures forecloses any later retry
                        # (the worker's _unpublish_for_retry checks this
                        # under the same lock)
                        self._dispatch_taken = True
                        taken = self._box[0]
                if taken is not None:
                    if t0 is not None:  # _note_stall takes the lane lock
                        self._lane._note_stall(
                            (time.perf_counter() - t0) * 1e3)
                    return taken
                continue  # republish in flight (a retry withdrew them)
            if t0 is None:
                t0 = time.perf_counter()
            self._dispatched.wait(0.05)
        if t0 is not None:
            self._lane._note_stall((time.perf_counter() - t0) * 1e3)
        if self._box[1] is not None:
            raise self._box[1]
        return self._box[0]


class StreamLane:
    """Double-buffered host<->device transfer lane for stream groups.

    A single worker thread executes submitted transfers in order through a
    bounded two-deep queue (the device ring): while group *i*'s update
    computes, the lane is moving group *i+1* down and group *i-1* up, and a
    third submission blocks until a slot frees — the backpressure that caps
    staging memory at two groups. ``overlap=False`` runs every transfer
    inline at submit (the serialized A/B twin: identical dispatch order,
    nothing hidden).

    Telemetry (``observability`` family ``offload_stream`` + per-lane
    ``stats()``): bytes up/down, transfer/lane-busy ms, consumer stall ms,
    groups in flight. ``overlap_efficiency`` = transfer time hidden behind
    compute / total transfer time.
    """

    _LANE_NO = [0]

    def __init__(self, overlap: bool = True, depth: int = 2,
                 pinned_staging: Optional[bool] = None):
        import os as _os

        self.overlap = bool(overlap)
        self.depth = int(depth)
        if pinned_staging is None:
            pinned_staging = _os.environ.get(
                "PT_OFFLOAD_PINNED_STAGING", "1").strip().lower() not in (
                "0", "false", "off")
        self._pinned_sh = _probe_pinned_host() if pinned_staging else None
        self.pinned_staging = self._pinned_sh is not None
        from ..analysis.lockdep import lock as _named_lock  # lazy: no cycle

        self._lock = _named_lock("jit.StreamLane._lock")
        self._stats = {"h2d_bytes": 0, "d2h_bytes": 0, "transfer_ms": 0.0,
                       "stall_ms": 0.0, "transfers": 0, "in_flight_sum": 0,
                       "retries": 0, "pinned_staged": 0}
        self._staging_bytes = 0  # bytes of submissions not yet landed
        # memory truth: the lane's staging working set (the two-group cap
        # the offload estimator models) rides in the `memory` provider
        try:
            from ..observability.memory import register_component

            StreamLane._LANE_NO[0] += 1
            register_component(
                f"stream_lane#{StreamLane._LANE_NO[0]}:staging",
                type(self).staging_bytes, owner=self)
        except Exception:
            pass
        self.events: List[tuple] = []  # (kind, tag) in submission order
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._seq = 0          # submission index (fault-site id)
        self._failure: Optional[BaseException] = None

    # -- submission -----------------------------------------------------------
    def submit(self, kind: str, arrays, placements, tag=None, names=None
               ) -> _TransferHandle:
        """Enqueue one group transfer. ``kind`` is ``"h2d"`` (params up) or
        ``"d2h"`` (grads/state down); ``placements`` is one sharding/device
        for every array or a per-array sequence; ``names`` (optional) are
        the in-flight parameter names, carried into any raised error.
        Blocks while the two-deep ring is full. A lane that already failed
        a transfer re-raises that failure here — the pipeline is poisoned
        and every subsequent interaction must say so."""
        if self._closed:
            raise RuntimeError("StreamLane is closed")
        if self._failure is not None:
            raise self._failure
        handle = _TransferHandle(self)
        if not isinstance(placements, (list, tuple)):
            placements = [placements] * len(arrays)
        handle._nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
        with self._lock:
            self.events.append((kind, tag))
            self._stats["in_flight_sum"] += self._q.qsize()
            self._staging_bytes += handle._nbytes
            seq = self._seq
            self._seq += 1
        if not self.overlap:
            self._run_job(kind, arrays, placements, handle, tag, names, seq,
                          serialized=True)
            return handle
        if self._thread is None:
            self._thread = threading.Thread(target=self._worker, daemon=True,
                                            name="pt-offload-stream")
            self._thread.start()
        self._q.put((kind, arrays, placements, handle, tag, names, seq))
        if self._failure is not None and not handle._event.is_set():
            # the worker may have poisoned + drained (or exited) while we
            # were blocked in put() — our job could be sitting in a queue no
            # thread reads. Fail it here; idempotent vs the worker's drain.
            handle._box[1] = self._failure
            self._unstage(handle)
            handle._event.set()
        return handle

    def _unstage(self, handle) -> None:
        """Release ``handle``'s staging-byte accounting exactly once —
        called from whichever path completes the job (normal run, the
        poisoned-queue drain, or the submit-side orphan rescue), which can
        race each other."""
        with self._lock:
            if not handle._unstaged:
                handle._unstaged = True
                self._staging_bytes -= handle._nbytes

    def _worker(self):
        while True:
            job = self._q.get()
            if job is None:
                return
            self._run_job(*job)
            if self._failure is not None:
                # the walk is poisoned: fail everything already queued so
                # every consumer wait() raises instead of hanging, then die
                while True:
                    try:
                        job = self._q.get_nowait()
                    except queue.Empty:
                        break
                    if job is None:
                        break
                    job[3]._box[1] = self._failure
                    self._unstage(job[3])
                    job[3]._event.set()
                with self._lock:
                    self._thread = None
                return

    def _transfer_once(self, kind, arrays, placements, tag, seq, handle):
        injector, _transient, _policy, _rm = _resil()
        inj = injector()
        inj.check("slow_transfer", seq=seq, kind=kind, group=tag)
        inj.check("transfer", seq=seq, kind=kind, group=tag)
        if kind == "h2d":
            arrays = self._stage_pinned(arrays)
        out = [jax.device_put(a, p) if p is not None
               else jax.device_put(a)
               for a, p in zip(arrays, placements)]
        # results exist as async futures NOW: a wait_dispatched() consumer
        # may take them and keep pipelining across the step boundary
        handle._set_dispatched(out)
        # the transfer is only *done* when the bytes have landed —
        # blocking HERE (off the consumer thread when overlapped) is
        # what makes stall_ms mean "transfer not hidden"
        for o in out:
            o.block_until_ready()
        return out

    def _stage_pinned(self, arrays):
        """Bounce h2d source buffers living on the CPU *backend* through
        the accelerator's pinned_host memory space when this jax exposes
        one (the reference TaskFlow keeps its staging buffers pinned so
        the device DMA engine uploads without an intermediate pageable
        copy). Probed once; backends without the memory kind — CPU tier-1
        included — take the direct path untouched."""
        if not self.pinned_staging or self._pinned_sh is None:
            return arrays
        staged = []
        for a in arrays:
            try:
                on_cpu = all(d.platform == "cpu" for d in a.devices())
            except Exception:
                on_cpu = False
            staged.append(jax.device_put(a, self._pinned_sh)
                          if on_cpu else a)
        with self._lock:
            self._stats["pinned_staged"] += len(
                [1 for s, a in zip(staged, arrays) if s is not a])
        return staged

    def _run_job(self, kind, arrays, placements, handle, tag, names, seq,
                 serialized=False):
        t0 = time.perf_counter()
        try:
            injector, transient, retry_policy, rmetrics = _resil()
            retries, backoff_ms = retry_policy()
            attempt = 0
            nbytes = 0
            while True:
                try:
                    out = self._transfer_once(kind, arrays, placements, tag,
                                              seq, handle)
                    handle._box[0] = out
                    nbytes = sum(int(getattr(o, "nbytes", 0)) for o in out)
                    break
                except BaseException as e:
                    if attempt < retries and transient(e) \
                            and handle._unpublish_for_retry():
                        # bounded retry-with-backoff: transient transfer
                        # faults (flaky host link, injected) are eaten
                        # here — including landing-phase failures, AS LONG
                        # AS no wait_dispatched() consumer already holds
                        # the failed attempt's futures (those could not be
                        # replaced; _unpublish_for_retry refuses and we
                        # fail sticky — fail-stop beats a silently-
                        # poisoned pipeline)
                        attempt += 1
                        with self._lock:
                            self._stats["retries"] += 1
                        _lane_fam().inc(("retries",))
                        rmetrics.inc("retries")
                        _flight_event("stream_retry", direction=kind, group=tag,
                                      attempt=attempt)
                        time.sleep(backoff_ms * (2 ** (attempt - 1)) / 1e3)
                        continue
                    err = StreamTransferError(kind, tag, names, e)
                    handle._box[1] = err  # surfaces at the consumer's wait()
                    self._failure = err   # ...and at every later interaction
                    _flight_event("stream_error", direction=kind, group=tag,
                                  error=str(e)[:120])
                    break
            ms = (time.perf_counter() - t0) * 1e3
            with self._lock:
                self._stats[f"{kind}_bytes"] += nbytes
                self._stats["transfer_ms"] += ms
                self._stats["transfers"] += 1
                if serialized:
                    # inline transfer: the consumer waited for all of it
                    self._stats["stall_ms"] += ms
            fam = _lane_fam()
            fam.inc((f"{kind}_bytes",), nbytes)
            fam.inc(("transfer_ms",), ms)
            fam.inc(("transfers",))
            fam.inc(("groups_in_flight_sum",), self._q.qsize())
            if serialized:
                fam.inc(("stall_ms",), ms)
        finally:
            self._unstage(handle)
            # the consumer may already be blocked in wait(): it must wake
            # even if the telemetry above throws on this worker thread
            handle._event.set()

    def submit_rows(self, rows, placement=None, kind: str = "h2d",
                    tag=None, names=None) -> "RowStreamHandle":
        """Generic row-stream API: move ONE ``[n, dim]`` row block through
        the lane (default h2d — host-gathered embedding/feature rows up to
        the device). Same overlap/backpressure/retry/telemetry contract
        as the group transfers; the sparse embedding path
        (``sparse.embedding.ShardedEmbeddingTable``) is the flagship
        consumer, streaming per-batch miss rows and prefetching the next
        batch's while the current step computes."""
        handle = self.submit(kind, [rows], [placement], tag=tag,
                             names=names)
        return RowStreamHandle(handle)

    def _note_stall(self, ms: float):
        with self._lock:
            self._stats["stall_ms"] += ms
        _lane_fam().inc(("stall_ms",), ms)

    def staging_bytes(self) -> int:
        """Bytes of submitted-but-not-landed transfers — the lane's live
        staging working set (capped at ~two groups by the ring depth)."""
        with self._lock:
            return max(self._staging_bytes, 0)

    # -- reads ----------------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            s = dict(self._stats)
        s["staging_bytes"] = max(self._staging_bytes, 0)
        s["overlap"] = self.overlap
        s["pinned_staging"] = self.pinned_staging
        s["hidden_ms"] = max(s["transfer_ms"] - s["stall_ms"], 0.0)
        s["overlap_efficiency"] = round(
            s["hidden_ms"] / s["transfer_ms"], 4) if s["transfer_ms"] else 0.0
        return s

    def overlap_efficiency(self) -> float:
        return self.stats()["overlap_efficiency"]

    def reset_stats(self) -> None:
        with self._lock:
            for k in self._stats:
                self._stats[k] = 0 if isinstance(self._stats[k], int) else 0.0
            self.events = []

    def close(self) -> None:
        self._closed = True
        if self._thread is not None:
            self._q.put(None)
            self._thread = None

    def __del__(self):
        # lanes are owned by long-lived step objects; when the step goes,
        # the worker thread must not outlive it
        try:
            self.close()
        except Exception:
            pass


class RowStreamHandle:
    """One in-flight row-block transfer (``StreamLane.submit_rows``)."""

    __slots__ = ("_handle",)

    def __init__(self, handle: _TransferHandle):
        self._handle = handle

    def done(self) -> bool:
        return self._handle.done()

    def rows(self):
        """The landed device rows (blocks; consumer wait charged to the
        lane's ``stall_ms``)."""
        return self._handle.wait()[0]

    def rows_dispatched(self):
        """The rows as soon as the transfer is ISSUED (jax futures) — the
        cross-step fill variant; a post-issue failure surfaces at the
        next lane interaction (PR-6 sticky contract)."""
        return self._handle.wait_dispatched()[0]


@contextlib.contextmanager
def init_on_host():
    """Construct models larger than HBM without touching it: parameter init
    runs on the host CPU backend (the reference's offload models build their
    params host-side too, sharding_stage3 _segment_rank_params). Hand the
    model to StreamedTrainStep, which places every tensor — streamed stacks
    into pinned host memory, edge params into HBM.

    The global rng key moves to the CPU backend for the duration: implicit
    cross-backend reads of an accelerator-resident key inside CPU-placed
    init ops are not something to rely on."""
    cpu = jax.devices("cpu")[0]
    gen = random_mod.default_generator()
    old_key = gen._key
    gen._key = jax.random.wrap_key_data(
        jax.device_put(np.asarray(jax.random.key_data(old_key)), cpu))
    try:
        with jax.default_device(cpu):
            yield
    finally:
        gen._key = old_key


# -- aligned host-slab packing ------------------------------------------------
# The TPU compiler's async host dynamic-update-slice emitter requires the
# written slab to be sublane/lane aligned (bf16: 16x128, f32: 8x128); 1-D or
# oddly-shaped per-layer slices (norm scales, factored optimizer vectors)
# crash it. Such buffers are stored host-side as [L, R, 128] zero-padded
# slabs; the true shape is restored on-device after each slice copy.


def _pack_dims(nelems: int, itemsize: int):
    lanes = 128
    sub = 16 if itemsize == 2 else 8
    r = -(-nelems // lanes)
    r = -(-r // sub) * sub
    return r, lanes


def _needs_pack(slice_shape, itemsize: int) -> bool:
    if (len(slice_shape) >= 2 and slice_shape[-1] % 128 == 0
            and slice_shape[-2] % (16 if itemsize == 2 else 8) == 0):
        return False
    return True


def _pack_np(arr):
    """[L, ...] numpy -> [L, R, 128] aligned slab."""
    L = arr.shape[0]
    flat = arr.reshape(L, -1)
    r, lanes = _pack_dims(flat.shape[1], arr.dtype.itemsize)
    out = np.zeros((L, r * lanes), arr.dtype)
    out[:, :flat.shape[1]] = flat
    return out.reshape(L, r, lanes)


def _unpack_dev(x, true_shape):
    n = 1
    for d in true_shape:
        n *= d
    return x.reshape(-1)[:n].reshape(true_shape)


def _pack_dev(x, packed_shape):
    r, lanes = packed_shape
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, r * lanes - flat.size)).reshape(r, lanes)


def _host_available_bytes():
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def _find_runs(model: Layer):
    from ..distributed.meta_parallel.stage_stack import StackedStageRun

    runs = []

    def walk(layer):
        if isinstance(layer, StackedStageRun):
            runs.append(layer)
        for _, sub in getattr(layer, "_sub_layers", {}).items():
            walk(sub)

    walk(model)
    return runs


class StreamedTrainStep:
    """Single-chip capacity mode: jit.TrainStep's twin for models whose
    stacked decoder weights exceed HBM. Slower per step (every weight
    crosses the PCIe/host path twice) but lifts the resident ceiling from
    ~1.8B toward the host-RAM bound (3.08B measured at batch 2; larger
    sizes stop in the TPU compiler's memory-space assignment, which
    HBM-places the grad chains)."""

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate_host: bool | str = "auto"):
        from ..distributed.meta_parallel.stage_stack import _memory_sharding
        from ..nn.clip import ClipGradByGlobalNorm

        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # donate_host halves the pinned-pool peak (params/state updated in
        # place) but was measured to DOUBLE step time on an older set-up
        # (27.7 -> 54.2 s/step at 2.5B; not re-measured). 'auto' (default)
        # donates only when
        # host RAM could not hold two copies of the parked buffers.
        self._donate_auto = donate_host == "auto"
        self.donate_host = bool(donate_host) and not self._donate_auto
        clip = optimizer._grad_clip
        if clip is not None and not isinstance(clip, ClipGradByGlobalNorm):
            raise NotImplementedError(
                "StreamedTrainStep: only ClipGradByGlobalNorm is supported "
                "for streamed params (other clips are per-tensor — apply "
                "them in the loss or drop grad_clip)")
        self._clip_norm = float(clip.clip_norm) if clip is not None else None
        runs = _find_runs(model)
        if not runs:
            raise ValueError(
                "StreamedTrainStep: the model has no StackedStageRun to "
                "stream (scan_layers=True models only); use jit.TrainStep")
        streamed_ids = {id(p) for r in runs for p in r._parameters.values()}
        opt = optimizer
        self.train_params = [p for p in opt._parameter_list
                             if not p.stop_gradient]
        self.streamed = [p for p in self.train_params
                         if id(p) in streamed_ids]
        self.edge = [p for p in self.train_params if id(p) not in streamed_ids]
        if not self.streamed:
            raise ValueError(
                "StreamedTrainStep: optimizer holds none of the stacked "
                "run's parameters (fleet order: build the stack first)")
        named = dict(model.named_parameters())
        train_ids = {id(p) for p in self.train_params}
        buffers = list(getattr(model, "named_buffers", lambda: [])())
        self.frozen = [p for p in named.values() if id(p) not in train_ids] \
            + [b for _, b in buffers]
        self._host_sh = _memory_sharding("pinned_host")
        self._dev_sh = _memory_sharding("device")
        dev = jax.devices()[0]
        cpu = jax.devices("cpu")[0]

        def to_np(arr):
            return np.asarray(arr)  # CPU-backend or device array: plain D2H

        # true per-layer shapes for streamed params (packing metadata)
        self._true_shape = {}
        self._state_shape = {}
        for r in runs:
            for (safe, _), ts in zip(r._names, r._slice_shapes):
                self._true_shape[id(r._parameters[safe])] = ts

        # per-layer optimizer state, stacked [L, ...] and parked next to the
        # params in pinned host memory; edge params/state live on device
        for p in self.streamed:
            meta = getattr(p, "_stream_meta", None)
            if meta is not None:
                # already parked by a previous StreamedTrainStep: buffers are
                # packed slabs — re-packing would corrupt them, and reading a
                # pinned_host array back through np round-trips HBM
                self._state_shape[id(p)] = meta["state_shapes"]
                continue
            L = p.data.shape[0]
            if id(p) not in opt._accumulators:
                with jax.default_device(cpu):
                    per_layer = [opt._init_state(jnp.asarray(s))
                                 for s in to_np(p.data)]
                    stacked = {
                        k: np.stack([np.asarray(st[k]) for st in per_layer])
                        for k in per_layer[0]
                    } if per_layer and per_layer[0] else {}
            else:
                # pre-existing accumulators (resident steps ran first): park
                # them too — leaving [L, ...] moments device-resident would
                # defeat the offload. Requires per-layer-stacked leaves
                # (elementwise optimizers); factored-over-stack state cannot
                # be reinterpreted per layer
                stacked = {}
                for k, v in opt._accumulators[id(p)].items():
                    if v.shape[:1] != (L,):
                        raise ValueError(
                            f"StreamedTrainStep: existing optimizer state "
                            f"'{k}' for a streamed param has shape "
                            f"{v.shape}, not per-layer [L={L}, ...]; reset "
                            f"the optimizer before switching to streaming")
                    stacked[k] = to_np(v)
            self._state_shape[id(p)] = {
                k: tuple(v.shape[1:]) for k, v in stacked.items()}
            opt._accumulators[id(p)] = {
                k: self._park(v) for k, v in stacked.items()}
            np_data = to_np(p.data)
            p.data = self._park(np_data)
            p._stream_meta = {"state_shapes": self._state_shape[id(p)]}
        for p in self.edge:
            if self._on_cpu(p.data):
                p.data = jax.device_put(to_np(p.data), dev)
            if id(p) not in opt._accumulators:
                opt._accumulators[id(p)] = opt._init_state(p.data)
        for t in self.frozen:
            if self._on_cpu(t.data):
                t.data = jax.device_put(to_np(t.data), dev)
        if self._donate_auto:
            parked = sum(int(p.data.nbytes) for p in self.streamed) + sum(
                int(v.nbytes)
                for p in self.streamed
                for v in opt._accumulators[id(p)].values())
            # no donation needs a second transient copy of the parked pool;
            # donate only when the host could not hold ~1.2x MORE than what
            # is already allocated (the pool itself was parked above, so
            # MemAvailable already excludes one copy) — donation was 2x
            # step time when last measured. /proc/meminfo describes THIS
            # host: pass an explicit bool if the buffers live elsewhere.
            avail = _host_available_bytes()
            self.donate_host = bool(avail is not None
                                    and avail < 1.2 * parked)
        self._jitted = None

    def _park(self, np_arr):
        if self._host_sh is None:
            return jnp.asarray(np_arr)
        np_arr = np.asarray(np_arr)
        if _needs_pack(np_arr.shape[1:], np_arr.dtype.itemsize):
            np_arr = _pack_np(np_arr)
        return jax.device_put(np_arr, self._host_sh)

    @staticmethod
    def _on_cpu(arr) -> bool:
        try:
            return all(d.platform == "cpu" for d in arr.devices())
        except Exception:
            return False

    # -- the one compiled step ------------------------------------------------
    def _build(self, batch_arrays):
        from ..distributed.meta_parallel import stage_stack
        from . import _Binder

        model, loss_fn = self.model, self.loss_fn
        edge, streamed, frozen = self.edge, self.streamed, self.frozen
        opt = self.optimizer
        rule = type(opt)._rule
        hyper = opt._hyper()
        wd = opt._weight_decay
        decoupled = opt._decoupled
        host, devm = self._host_sh, self._dev_sh

        def flag_of(p):
            return 1.0 if (opt._decay_param_fn is None
                           or opt._decay_param_fn(p)) else 0.0

        def apply_rule(p_i, g_i, s_i, lr, step_no, flag):
            g_i = g_i.astype(p_i.dtype)
            if wd and not decoupled and flag:
                g_i = g_i + wd * p_i
            hyper_i = hyper if flag or "wd" not in hyper else \
                dict(hyper, wd=0.0)
            np_, ns = rule(p_i, g_i, s_i, lr, step_no, hyper_i)
            if wd and decoupled and flag:
                np_ = np_ - (lr * wd * p_i).astype(p_i.dtype)
            return np_, ns

        def d2h(x):
            return x if host is None else jax.device_put(x, host)

        def h2d(x):
            return x if devm is None else jax.device_put(x, devm)

        def step_fn(edge_arrays, streamed_arrays, edge_states, stream_states,
                    frozen_arrays, lr, step_no, rngkey, *batch):
            random_mod.default_generator().set_trace_key(rngkey)
            stage_stack._STREAM_MODE[0] = True
            try:
                def loss_of(edge_t, streamed_t):
                    ts = edge + streamed + frozen
                    with _Binder(ts) as b:
                        b.bind(list(edge_t) + list(streamed_t) +
                               list(frozen_arrays))
                        with autograd.no_grad():
                            loss = loss_fn(model, *[Tensor(a) for a in batch])
                    return loss.data.astype(jnp.float32)

                loss_val, (ge, gs) = jax.value_and_grad(
                    loss_of, argnums=(0, 1))(tuple(edge_arrays),
                                             tuple(streamed_arrays))

                # global-norm clip: one extra per-layer pass over the
                # host-resident grads (slice H2D, square, accumulate) BEFORE
                # any update consumes them — same semantics as
                # ClipGradByGlobalNorm over the unstacked grads. Slab
                # padding is zeros and contributes nothing to the norm.
                coef = None
                if self._clip_norm is not None:
                    sq = jnp.float32(0.0)
                    for g in ge:
                        sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for gh in gs:
                        for i in range(gh.shape[0]):
                            g_i = h2d(jax.lax.index_in_dim(
                                gh, i, keepdims=False))
                            sq = sq + jnp.sum(
                                jnp.square(g_i.astype(jnp.float32)))
                    gnorm = jnp.sqrt(sq)
                    coef = jnp.minimum(
                        self._clip_norm / jnp.maximum(gnorm, 1e-12), 1.0)

                def clipped(g):
                    if coef is None:
                        return g
                    return (g.astype(jnp.float32) * coef).astype(g.dtype)

                # edge update: plain on-device fused rule
                new_edge, new_es = [], []
                for p, a, g, s in zip(edge, edge_arrays, ge, edge_states):
                    np_, ns = apply_rule(a, clipped(g), s, lr, step_no,
                                         flag_of(p))
                    new_edge.append(np_)
                    new_es.append(ns)

                # streamed update: walk the layers — slice H2D (unpacking
                # aligned slabs to the true shapes), rule on device, repack
                # and dynamic-update-slice back into the host buffers
                new_streamed, new_ss = [], []
                for p, ph, gh, st in zip(streamed, streamed_arrays, gs,
                                         stream_states):
                    out_p = ph
                    out_s = dict(st)
                    flag = flag_of(p)
                    p_ts = self._true_shape.get(id(p), tuple(ph.shape[1:]))
                    packed = tuple(ph.shape[1:]) != tuple(p_ts)
                    s_ts = self._state_shape.get(id(p), {})
                    for i in range(ph.shape[0]):
                        p_i = h2d(jax.lax.index_in_dim(ph, i, keepdims=False))
                        g_i = h2d(jax.lax.index_in_dim(gh, i, keepdims=False))
                        if packed:
                            p_i = _unpack_dev(p_i, p_ts)
                            g_i = _unpack_dev(g_i, p_ts)
                        g_i = clipped(g_i)
                        s_i = {}
                        for k, v in st.items():
                            sv = h2d(jax.lax.index_in_dim(v, i,
                                                          keepdims=False))
                            ts = s_ts.get(k, tuple(v.shape[1:]))
                            if tuple(v.shape[1:]) != tuple(ts):
                                sv = _unpack_dev(sv, ts)
                            s_i[k] = sv
                        np_, ns = apply_rule(p_i, g_i, s_i, lr, step_no, flag)
                        if packed:
                            np_ = _pack_dev(np_, tuple(ph.shape[1:]))
                        out_p = jax.lax.dynamic_update_index_in_dim(
                            out_p, d2h(np_[None]), i, 0)
                        for k, v in ns.items():
                            nv = v.astype(out_s[k].dtype)
                            if tuple(st[k].shape[1:]) != tuple(
                                    s_ts.get(k, tuple(st[k].shape[1:]))):
                                nv = _pack_dev(nv, tuple(st[k].shape[1:]))
                            out_s[k] = jax.lax.dynamic_update_index_in_dim(
                                out_s[k], d2h(nv[None]), i, 0)
                    new_streamed.append(out_p)
                    new_ss.append(out_s)
                return loss_val, new_edge, new_es, new_streamed, new_ss
            finally:
                stage_stack._STREAM_MODE[0] = False
                random_mod.default_generator().clear_trace_key()

        if host is None:
            return jax.jit(step_fn)
        # outputs that end in host memory must SAY so (XLA rejects programs
        # whose entry outputs were host-moved without a host output layout);
        # prefix pytrees broadcast over the state dicts
        out_sh = (devm, devm, devm, host, host)
        donate = (1, 3) if self.donate_host else ()
        return jax.jit(step_fn, out_shardings=out_sh, donate_argnums=donate)

    def __call__(self, *batch):
        opt = self.optimizer
        arrays = [b.data if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        if self._jitted is None:
            self._jitted = self._build(arrays)
        loss, new_edge, new_es, new_streamed, new_ss = self._jitted(
            [p.data for p in self.edge],
            [p.data for p in self.streamed],
            [opt._accumulators[id(p)] for p in self.edge],
            [opt._accumulators[id(p)] for p in self.streamed],
            [t.data for t in self.frozen],
            jnp.asarray(opt.get_lr(), jnp.float32),
            jnp.asarray(opt._global_step + 1, jnp.int32),
            random_mod.next_key(), *arrays)
        for p, a, s in zip(self.edge, new_edge, new_es):
            p.data = a
            opt._accumulators[id(p)] = s
        for p, a, s in zip(self.streamed, new_streamed, new_ss):
            p.data = a
            opt._accumulators[id(p)] = s
        opt._global_step += 1
        return Tensor(loss)


class _EarlyExit(Exception):
    """Carries the run input captured during an embed-only prefix trace."""

    def __init__(self, value):
        self.value = value


class SegmentedTrainStep:
    """Beyond-StreamedTrainStep capacity: a hand-segmented backward in ONE
    compiled step, with NO stacked [L, ...] gradient accumulator anywhere.

    Reference sharding_stage3.py:50 + :737 streams per-SEGMENT params and
    accumulates grads host-side; the TPU-native mapping here:

    - every layer's params + optimizer state live as SEPARATE per-layer
      pinned-host arrays (no [L, ...] stacks, so XLA's memory-space pass
      has no whole-stack gradient chain to HBM-place — the 3.08B wall of
      StreamedTrainStep);
    - forward: unrolled per-layer walk, each boundary activation copied to
      pinned host right after use;
    - head/embedding gradients: plain jax AD around an independent
      run-output variable (the run is snipped out of the autodiff graph);
    - backward: a manual reverse walk — slice params H2D, jax.vjp of ONE
      layer (recompute-from-boundary == remat), apply the optimizer rule
      immediately, write the updated params/state back to host. A layer's
      gradients die before the next layer's exist.

    Single StackedStageRun models only (the streamed flagship shape); MoE
    aux-loss stacks are not supported on this path.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate_host: bool = False):
        from ..distributed.meta_parallel.stage_stack import _memory_sharding

        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # donation halves the pinned peak (no second copy at the step
        # boundary) at a ~2x step-time cost when last measured (older
        # set-up); off by default — the host holds both copies
        self.donate_host = bool(donate_host)
        if optimizer._grad_clip is not None:
            raise NotImplementedError(
                "SegmentedTrainStep: grad clip needs the norm before any "
                "update; use StreamedTrainStep for clipped streaming")
        runs = _find_runs(model)
        if len(runs) != 1:
            raise ValueError(
                "SegmentedTrainStep supports exactly one StackedStageRun "
                f"(got {len(runs)}); use StreamedTrainStep/TrainStep")
        self.run = runs[0]
        if getattr(self.run, "_segmented_owned", False):
            raise ValueError(
                "SegmentedTrainStep: this model's stacked weights were "
                "already split into a previous SegmentedTrainStep (they "
                "live in that step's per-layer buffers — keep using it, or "
                "rebuild the model from model.state_dict())")
        # the step runs loss_fn in FOUR traced passes (fwd walk, head AD,
        # per-layer vjp recompute, embed vjp); stochasticity ANYWHERE in
        # the model (not just the stacked template — embeddings/pooler too)
        # would draw different rng per pass and silently break the chain
        # rule. Checked: Dropout-family layers (incl. 3D/Alpha) with p>0
        # and ANY float attr whose name mentions dropout — MHA.dropout,
        # RNN.dropout, DiT LabelEmbedding.dropout_prob, functional
        # *dropout_p all drive rng draws.
        from ..nn.layer.common import Dropout, Dropout2D
        from ..nn.layer.extension_r3 import AlphaDropout, Dropout3D
        from ..nn.layer.moe import MoELayer

        scan = list(model.sublayers(include_self=True)) + \
            list(self.run._template[0].sublayers(include_self=True))
        for sub in scan:
            if (isinstance(sub, (Dropout, Dropout2D, Dropout3D,
                                 AlphaDropout))
                    and getattr(sub, "p", 0.0) > 0.0):
                raise NotImplementedError(
                    "SegmentedTrainStep: dropout in the model would "
                    "resample per traced pass (inconsistent gradients); "
                    "use StreamedTrainStep or p=0")
            for attr, val in vars(sub).items():
                if ("dropout" in attr and isinstance(val, float)
                        and val > 0.0):
                    raise NotImplementedError(
                        f"SegmentedTrainStep: {type(sub).__name__}.{attr}="
                        f"{val} drives stochastic masking — inconsistent "
                        f"across traced passes; use StreamedTrainStep")
            if isinstance(sub, MoELayer):
                raise NotImplementedError(
                    "SegmentedTrainStep: MoE aux losses cannot cross the "
                    "segmented boundary; use StreamedTrainStep")
        opt = optimizer
        self.train_params = [p for p in opt._parameter_list
                             if not p.stop_gradient]
        run_param_ids = {id(p) for p in self.run._parameters.values()}
        self.edge = [p for p in self.train_params
                     if id(p) not in run_param_ids]
        named = dict(model.named_parameters())
        train_ids = {id(p) for p in self.train_params}
        buffers = list(getattr(model, "named_buffers", lambda: [])())
        self.frozen = [p for p in named.values()
                       if id(p) not in train_ids
                       and id(p) not in run_param_ids] + \
            [b for _, b in buffers]
        self._host_sh = _memory_sharding("pinned_host")
        self._dev_sh = _memory_sharding("device")
        dev = jax.devices()[0]
        cpu = jax.devices("cpu")[0]

        # split each stacked run param into per-layer HOST arrays + state
        self.depth = self.run.depth
        self._pnames = [safe for safe, _ in self.run._names]
        self._layer_params: List[List] = []   # [L][P] host arrays
        self._layer_states: List[List[dict]] = []
        self._decay_flags: List[float] = []
        stacked_params = [self.run._parameters[s] for s in self._pnames]
        for p in stacked_params:
            if p.stop_gradient:
                raise NotImplementedError(
                    "SegmentedTrainStep: frozen stacked params unsupported")
            self._decay_flags.append(
                1.0 if (opt._decay_param_fn is None
                        or opt._decay_param_fn(p)) else 0.0)
        for i in range(self.depth):
            row, srow = [], []
            for p in stacked_params:
                sl = np.asarray(p.data[i]) if not self._on_cpu(p.data) \
                    else np.asarray(p.data)[i]
                row.append(self._park_whole(sl))
                with jax.default_device(cpu):
                    st = opt._init_state(jnp.asarray(sl))
                srow.append({k: self._park_whole(np.asarray(v))
                             for k, v in st.items()})
            self._layer_params.append(row)
            self._layer_states.append(srow)
        # split complete — only NOW mark ownership (an earlier validation
        # failure must leave the run reusable)
        self.run._segmented_owned = True
        # drop the stacked copies: this step owns the canonical weights now.
        # model.state_dict() is wrapped so ordinary checkpointing still sees
        # the REAL weights (reassembled from the per-layer buffers) instead
        # of silently saving the freed placeholders.
        split_ids = {id(p) for p in stacked_params}
        for p in stacked_params:
            p.data = jnp.zeros((0,), p.data.dtype)
        name_of = {id(p): n for n, p in model.named_parameters()
                   if id(p) in split_ids}
        orig_state_dict = model.state_dict
        pname_index = {s: j for j, s in enumerate(self._pnames)}

        def state_dict_with_segments(*a, **k):
            sd = orig_state_dict(*a, **k)
            arrs = self.state_dict_arrays()
            for pid, name in name_of.items():
                safe = name.rsplit(".", 1)[-1]
                j = pname_index.get(safe)
                if j is not None and name in sd:
                    sd[name] = Tensor(jnp.asarray(arrs[self._pnames[j]]))
            return sd

        model.state_dict = state_dict_with_segments
        for p in self.edge:
            if self._on_cpu(p.data):
                p.data = jax.device_put(np.asarray(p.data), dev)
            if id(p) not in opt._accumulators:
                opt._accumulators[id(p)] = opt._init_state(p.data)
        for t in self.frozen:
            if self._on_cpu(t.data):
                t.data = jax.device_put(np.asarray(t.data), dev)
        self._jitted = None

    def _park_whole(self, np_arr):
        """Park ONE layer's slice on pinned host UNPACKED (true shape).

        StreamedTrainStep._park packs [L, ...] stacks into aligned [L, R,
        128] slabs because its compiled step dynamic-slices INTO the host
        arrays (the async-copy emitter needs sublane/lane alignment). The
        segmented step transfers each buffer WHOLE (h2d/d2h of the full
        array inside one jit), so the true shape is what the template and
        the optimizer rule must see — packing here bound slab-shaped
        weights into the model (r5 regression, caught by the seg bench
        row going red on TPU)."""
        np_arr = np.asarray(np_arr)
        if self._host_sh is None:
            return jnp.asarray(np_arr)
        return jax.device_put(np_arr, self._host_sh)
    _on_cpu = staticmethod(StreamedTrainStep._on_cpu)

    def state_dict_arrays(self):
        """Reassembled stacked host arrays (checkpointing hook)."""
        return {n: np.stack([np.asarray(self._layer_params[i][j])
                             for i in range(self.depth)])
                for j, n in enumerate(self._pnames)}

    def _build(self, batch_arrays):
        from ..distributed.meta_parallel import stage_stack
        from . import _Binder

        model, loss_fn = self.model, self.loss_fn
        run, opt = self.run, self.optimizer
        edge, frozen = self.edge, self.frozen
        rule = type(opt)._rule
        hyper = opt._hyper()
        wd = opt._weight_decay
        decoupled = opt._decoupled
        host, devm = self._host_sh, self._dev_sh
        depth, pnames = self.depth, self._pnames
        template = run._template[0]
        tparams = [dict(template.named_parameters())[orig]
                   for _, orig in run._names]
        flags = self._decay_flags

        def h2d(x):
            return x if devm is None else jax.device_put(x, devm)

        def d2h(x):
            return x if host is None else jax.device_put(x, host)

        def layer_fwd(params_dev, hidden):
            saved = [p.data for p in tparams]
            try:
                for p, a in zip(tparams, params_dev):
                    p.data = a
                with autograd.no_grad():
                    return template(Tensor(hidden)).data
            finally:
                for p, a in zip(tparams, saved):
                    p.data = a

        def apply_rule(p_i, g_i, s_i, lr, step_no, flag):
            g_i = g_i.astype(p_i.dtype)
            if wd and not decoupled and flag:
                g_i = g_i + wd * p_i
            hyper_i = hyper if flag or "wd" not in hyper else \
                dict(hyper, wd=0.0)
            np_, ns = rule(p_i, g_i, s_i, lr, step_no, hyper_i)
            if wd and decoupled and flag:
                np_ = np_ - (lr * wd * p_i).astype(p_i.dtype)
            return np_, ns

        def step_fn(edge_arrays, layer_params, layer_states, edge_states,
                    frozen_arrays, lr, step_no, rngkey, *batch):
            random_mod.default_generator().set_trace_key(rngkey)
            try:
                boundaries: List = []
                captured: dict = {}

                def bind_and_run(edge_t, handler):
                    ts = edge + frozen
                    stage_stack._SEG_HANDLER[0] = handler
                    try:
                        with _Binder(ts) as b:
                            b.bind(list(edge_t) + list(frozen_arrays))
                            with autograd.no_grad():
                                loss = loss_fn(model,
                                               *[Tensor(a) for a in batch])
                        return loss.data.astype(jnp.float32)
                    finally:
                        stage_stack._SEG_HANDLER[0] = None

                # 1) forward walk: real layer compute, boundaries to host
                def fwd_handler(_run, hidden):
                    h = hidden
                    for i in range(depth):
                        boundaries.append(d2h(h))
                        params_dev = [h2d(a) for a in layer_params[i]]
                        h = layer_fwd(params_dev, h)
                    captured["h_out"] = h
                    return h

                bind_and_run(tuple(edge_arrays), fwd_handler)
                h_out = captured["h_out"]

                # 2) head/embedding AD around an independent run output
                def loss_of(edge_t, hv):
                    def const_handler(_run, hidden):
                        captured["h_in"] = hidden
                        return hv
                    return bind_and_run(edge_t, const_handler)

                (loss_val, (g_edge, dh)) = jax.value_and_grad(
                    loss_of, argnums=(0, 1))(tuple(edge_arrays), h_out)

                # 3) reverse walk: per-layer vjp + immediate update
                new_layer_params, new_layer_states = [], []
                for i in range(depth - 1, -1, -1):
                    h_i = h2d(boundaries[i])
                    params_dev = [h2d(a) for a in layer_params[i]]
                    _, vjp = jax.vjp(layer_fwd, params_dev, h_i)
                    dparams, dh = vjp(dh)
                    new_row, new_srow = [], []
                    for a, g, st, flag in zip(params_dev, dparams,
                                              layer_states[i], flags):
                        st_dev = {k: h2d(v) for k, v in st.items()}
                        np_, ns = apply_rule(a, g, st_dev, lr, step_no,
                                             flag)
                        new_row.append(d2h(np_))
                        new_srow.append({k: d2h(v.astype(st[k].dtype))
                                         for k, v in ns.items()})
                    new_layer_params.append(new_row)
                    new_layer_states.append(new_srow)
                new_layer_params.reverse()
                new_layer_states.reverse()

                # 4) embedding-path edge grads: vjp through the captured
                # run INPUT (loss_of's head path never saw it)
                def h_in_of(edge_t):
                    def early_handler(_run, hidden):
                        raise _EarlyExit(hidden)
                    try:
                        bind_and_run(edge_t, early_handler)
                    except _EarlyExit as e:
                        return e.value
                    raise RuntimeError("run was never reached by loss_fn")

                _, vjp_embed = jax.vjp(h_in_of, tuple(edge_arrays))
                (g_embed,) = vjp_embed(dh)
                g_edge = [a + b for a, b in zip(g_edge, g_embed)]

                new_edge, new_es = [], []
                for p, a, g, s in zip(edge, edge_arrays, g_edge,
                                      edge_states):
                    flag = 1.0 if (opt._decay_param_fn is None
                                   or opt._decay_param_fn(p)) else 0.0
                    np_, ns = apply_rule(a, g, s, lr, step_no, flag)
                    new_edge.append(np_)
                    new_es.append(ns)
                return (loss_val, new_edge, new_es, new_layer_params,
                        new_layer_states)
            finally:
                random_mod.default_generator().clear_trace_key()

        donate = (1, 2) if self.donate_host else ()
        if host is None:
            return jax.jit(step_fn, donate_argnums=donate)
        out_sh = (devm, devm, devm, host, host)
        return jax.jit(step_fn, out_shardings=out_sh,
                       donate_argnums=donate)

    def __call__(self, *batch):
        opt = self.optimizer
        arrays = [b.data if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        if self._jitted is None:
            self._jitted = self._build(arrays)
        (loss, new_edge, new_es, new_lp, new_ls) = self._jitted(
            [p.data for p in self.edge],
            self._layer_params, self._layer_states,
            [opt._accumulators[id(p)] for p in self.edge],
            [t.data for t in self.frozen],
            jnp.asarray(opt.get_lr(), jnp.float32),
            jnp.asarray(opt._global_step + 1, jnp.int32),
            random_mod.next_key(), *arrays)
        for p, a, s in zip(self.edge, new_edge, new_es):
            p.data = a
            opt._accumulators[id(p)] = s
        self._layer_params = new_lp
        self._layer_states = new_ls
        opt._global_step += 1
        return Tensor(loss)
