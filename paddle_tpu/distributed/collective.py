"""Collective communication API.

Reference: python/paddle/distributed/collective.py:208-1631 (new_group,
all_reduce/all_gather/broadcast/... over NCCL process groups) and the C++
ProcessGroup contract (collective/ProcessGroup.h:60).

TPU-native semantics (single-controller SPMD): there is one Python process
driving all chips, so "each rank's local tensor" is represented as ONE global
tensor whose leading dim indexes ranks of the group ("stacked layout"). Each
collective is a jitted ``shard_map`` over the group's mesh axis, so it executes
as a real XLA collective on ICI — not a host emulation. Inside an active
``shard_map``/pjit trace the same functions lower to ``lax.p*`` directly.

This dual nature mirrors the reference's two API generations (static collective
ops with ring ids vs dygraph ProcessGroup objects) collapsed into one.
"""
from __future__ import annotations

import functools
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core.tensor import Tensor
from .mesh import MeshEnv, get_mesh_env, require_mesh_env


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A named mesh axis (the process-group analogue)."""

    def __init__(self, axis: str, env: MeshEnv, id: int = 0):
        self.axis = axis
        self.env = env
        self.id = id

    @property
    def nranks(self) -> int:
        return self.env.get_dim(self.axis)

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def rank(self) -> int:
        return 0  # single controller drives all shards

    def get_group_rank(self, rank):
        return rank

    def __repr__(self):
        return f"Group(axis={self.axis!r}, nranks={self.nranks})"


_DEFAULT_GROUP: Optional[Group] = None


def _default_group() -> Group:
    global _DEFAULT_GROUP
    if _DEFAULT_GROUP is None:
        env = require_mesh_env()
        # the world group: the dp axis by default
        _DEFAULT_GROUP = Group("dp", env)
    return _DEFAULT_GROUP


def new_group(ranks=None, backend=None, axis: str = None):
    """Reference collective.py:208. Groups ARE axes here; `axis` selects one."""
    env = require_mesh_env()
    return Group(axis or "dp", env)


def get_group(id=0):
    return _default_group()


def is_initialized() -> bool:
    return get_mesh_env() is not None


def init_parallel_env(**kwargs):
    """Reference: python/paddle/distributed/parallel.py init_parallel_env.
    Single-host: build the mesh over local devices. Multi-host: callers run
    paddle_tpu.distributed.launch which handles jax.distributed.initialize."""
    require_mesh_env()
    return _default_group()


def get_world_size(group: Optional[Group] = None) -> int:
    env = get_mesh_env()
    if env is None:
        return 1
    return (group or _default_group()).nranks


def get_rank(group: Optional[Group] = None) -> int:
    return 0


# ---------------------------------------------------------------------------
# collectives — stacked-global layout, executed as shard_map'ed XLA collectives
# ---------------------------------------------------------------------------

_JIT_CACHE = {}
_COLL_FAM = None  # lazily-bound observability family


def _record_collective(op: str, arr) -> None:
    """Call/byte counters per collective op (observability "collectives"
    family). Host-side bookkeeping only — two dict adds per call."""
    global _COLL_FAM
    try:
        if _COLL_FAM is None:
            from ..observability import family

            _COLL_FAM = family("collectives", ("op", "kind"))
        size = int(getattr(arr, "size", 0) or 0)
        itemsize = 0
        dt = getattr(arr, "dtype", None)
        if dt is not None:
            import numpy as _np

            itemsize = _np.dtype(dt).itemsize
        _COLL_FAM.inc((op, "calls"))
        _COLL_FAM.inc((op, "bytes"), size * itemsize)
    except Exception:  # telemetry must never sink a collective
        pass


def _axis_jit(kind, group: Group, **kw):
    key = (kind, group.axis, id(group.env), tuple(sorted(kw.items())))
    f = _JIT_CACHE.get(key)
    if f is None:
        mesh = group.env.mesh
        ax = group.axis

        if kind == "all_reduce":
            op = kw["op"]

            def body(x):
                red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}[
                    "sum" if op == "avg" else op]
                y = red(x, ax)
                if op == "avg":
                    y = y / jax.lax.psum(jnp.ones((), x.dtype), ax)
                return y

        elif kind == "all_gather":
            def body(x):
                return jax.lax.all_gather(x, ax, axis=0, tiled=True)

        elif kind == "reduce_scatter":
            def body(x):
                return jax.lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True)

        elif kind == "broadcast":
            src = kw["src"]

            def body(x):
                idx = jax.lax.axis_index(ax)
                full = jax.lax.all_gather(x, ax, axis=0)
                return full[src]

        elif kind == "alltoall":
            def body(x):
                # x local: [world, ...]; swap rank/world dims
                return jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0, tiled=True)

        else:
            raise ValueError(kind)

        f = jax.jit(
            shard_map(body, mesh=mesh, in_specs=P(ax), out_specs=_out_spec(kind, ax, **kw))
        )
        _JIT_CACHE[key] = f
    return f


def _out_spec(kind, ax, **kw):
    if kind in ("all_reduce", "broadcast"):
        return P(ax)  # every rank holds the result -> stacked layout preserved
    if kind == "all_gather":
        return P(ax)
    if kind == "reduce_scatter":
        return P(ax)
    if kind == "alltoall":
        return P(ax)
    raise ValueError(kind)


def _prep(tensor, group):
    g = group or _default_group()
    arr = tensor.data if isinstance(tensor, Tensor) else jnp.asarray(tensor)
    n = g.nranks
    if arr.shape[0] % n != 0:
        raise ValueError(
            f"stacked collective needs dim0 divisible by group size {n}, got {arr.shape}")
    return arr, g


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Stacked layout: in [world*b, ...] sharded by rank; out same shape, every
    rank's slice replaced by the reduction."""
    arr, g = _prep(tensor, group)
    _record_collective("all_reduce", arr)
    if g.nranks == 1:
        out = arr
    else:
        out = _axis_jit("all_reduce", g, op=op)(arr)
    if isinstance(tensor, Tensor):
        tensor.data = out
        return tensor
    return Tensor(out)


def all_gather(tensor_list: Optional[List], tensor=None, group=None, sync_op=True):
    """paddle signature: fills tensor_list with every rank's shard.
    Stacked layout: input [world, ...] -> list of world tensors (each [...])."""
    if tensor is None:  # functional style: all_gather(tensor)
        tensor, tensor_list = tensor_list, None
    arr, g = _prep(tensor, group)
    _record_collective("all_gather", arr)
    n = g.nranks
    per = arr.shape[0] // n
    shards = [Tensor(arr[i * per : (i + 1) * per]) for i in range(n)]
    if tensor_list is not None:
        tensor_list.clear()
        tensor_list.extend(shards)
        return tensor_list
    return shards


def broadcast(tensor, src=0, group=None, sync_op=True):
    arr, g = _prep(tensor, group)
    _record_collective("broadcast", arr)
    if g.nranks > 1:
        per = arr.shape[0] // g.nranks
        src_slice = arr[src * per : (src + 1) * per]
        out = jnp.tile(src_slice, (g.nranks,) + (1,) * (arr.ndim - 1))
    else:
        out = arr
    if isinstance(tensor, Tensor):
        tensor.data = out
        return tensor
    return Tensor(out)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # single-controller: reduce == all_reduce then conceptually only dst uses it
    return all_reduce(tensor, op, group, sync_op)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None, sync_op=True):
    arr, g = _prep(tensor, group)
    _record_collective("reduce_scatter", arr)
    if g.nranks == 1:
        return Tensor(arr)
    out = _axis_jit("reduce_scatter", g)(arr)
    return Tensor(out)


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """Stacked: input list of per-rank tensors (or [world, ...] tensor)."""
    if isinstance(in_tensor_list, (list, tuple)):
        arr = jnp.stack([t.data if isinstance(t, Tensor) else jnp.asarray(t)
                         for t in in_tensor_list])
        g = group or _default_group()
    else:
        arr, g = _prep(in_tensor_list, group)
    _record_collective("alltoall", arr)
    if g.nranks > 1:
        flat = arr.reshape((-1,) + arr.shape[2:]) if isinstance(in_tensor_list, (list, tuple)) else arr
        out = _axis_jit("alltoall", g)(flat)
    else:
        out = arr
    if out_tensor_list is not None:
        n = g.nranks
        per = out.shape[0] // n
        out_tensor_list.clear()
        out_tensor_list.extend(Tensor(out[i * per : (i + 1) * per]) for i in range(n))
        return out_tensor_list
    return Tensor(out)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    arr, g = _prep(tensor, group)
    _record_collective("scatter", arr)
    return Tensor(arr)  # single-controller: data already placed


def barrier(group=None):
    env = get_mesh_env()
    if env is not None:
        jax.block_until_ready(jnp.zeros(()))
    return None


# -- point-to-point ----------------------------------------------------------
# Reference contract: ProcessGroup.h:108-114 (send/recv + isend/irecv Tasks).
# Under the single-controller SPMD runtime every "rank" lives in this process,
# so p2p is a host-coordinated device-to-device handoff through a mailbox; the
# in-trace path for compiled pipelines is ppermute (below), which is what the
# 1F1B schedule uses. Across gang-spawned processes (PS trainers, CPU-mesh
# emulation, multi-host) the same API rides the native TCPStore control plane:
# sender claims a sequence number with add() and set()s the pickled payload,
# receiver wait()s on the next sequence key — ordered, typed, inter-process.

_P2P_BOX: dict = {}
_P2P_LOCK = threading.Lock()
_P2P_CV = threading.Condition(_P2P_LOCK)

_P2P_STORE = None          # TCPStore channel for inter-process p2p (sends)
_P2P_RECV_SEQ: dict = {}   # (src, dst, tag) -> highest reserved sequence
_P2P_ABANDONED: dict = {}  # (src, dst, tag) -> seqs reserved but not consumed
_P2P_CHAN_LOCK = threading.Lock()  # guards store init + per-message sequencing
_P2P_RECV_POOL: list = []          # reusable store conns for blocking waits


def _proc_rank_world():
    """(process rank, process world) from launcher env or jax.distributed."""
    import os

    w = os.environ.get("PADDLE_TRAINERS_NUM")
    r = os.environ.get("PADDLE_TRAINER_ID")
    if w is not None and int(w) > 1:
        return int(r or 0), int(w)
    try:
        return jax.process_index(), jax.process_count()
    except Exception:
        return 0, 1


def init_p2p_channel(store=None):
    """Attach a Store for inter-process send/recv.

    With no argument, builds a TCPStore from PADDLE_P2P_ENDPOINT (process
    rank 0 hosts the daemon). The launcher's gang spawn exports this endpoint
    automatically; standalone multi-process setups set it by hand or pass a
    connected TCPStore. PADDLE_MASTER is deliberately NOT used as a fallback:
    that port belongs to the jax.distributed coordinator.
    """
    global _P2P_STORE
    with _P2P_CHAN_LOCK:
        if store is not None:
            _P2P_STORE = store
            return _P2P_STORE
        if _P2P_STORE is not None:
            return _P2P_STORE
    # build the connection with the channel lock RELEASED: the dial-retry
    # loop below can spin for up to 60s, and threads parked on the lock
    # for per-message sequencing must not wedge behind it (CC001)
    import os
    import time

    endpoint = os.environ.get("PADDLE_P2P_ENDPOINT")
    if not endpoint or ":" not in endpoint:
        raise RuntimeError(
            "send/recv across processes needs a store endpoint: set "
            "PADDLE_P2P_ENDPOINT=host:port (process rank 0 hosts the "
            "daemon; paddle_tpu.distributed.launch sets this for gangs) "
            "or call init_p2p_channel(store) with a connected TCPStore")
    from .store import TCPStore

    host, port = endpoint.rsplit(":", 1)
    rank, world = _proc_rank_world()
    if rank == 0:
        built = TCPStore(host="0.0.0.0", port=int(port),
                         is_master=True, world_size=world)
    else:
        deadline = time.time() + 60
        built = last = None
        while time.time() < deadline:
            try:
                built = TCPStore(host=host, port=int(port),
                                 is_master=False, world_size=world)
                break
            except RuntimeError as e:  # master not up yet
                last = e
                time.sleep(0.2)
        if built is None:
            raise RuntimeError(
                f"cannot reach p2p store at {endpoint}: {last}")
    with _P2P_CHAN_LOCK:
        if _P2P_STORE is None:
            _P2P_STORE = built
        elif built is not _P2P_STORE:  # lost an init race: drop ours
            try:
                built.close()
            except Exception:
                pass
        return _P2P_STORE


class _RecvChannel:
    """Checked-out store connection for one blocking recv wait.

    The shared client serializes requests under one lock; parking a wait
    there would deadlock the irecv+send exchange pattern. Connections are
    pooled (not per-thread) because irecv spawns a fresh thread per call —
    a thread-keyed cache would open a new TCP connection per message."""

    def __enter__(self):
        with _P2P_CHAN_LOCK:
            if _P2P_RECV_POOL:
                self.store = _P2P_RECV_POOL.pop()
                return self.store
        from .store import TCPStore

        main = _P2P_STORE
        self.store = TCPStore(host=main.host if main.host != "0.0.0.0"
                              else "127.0.0.1",
                              port=main.port, is_master=False,
                              world_size=main.world_size)
        return self.store

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            # the socket may hold a stale in-flight reply (timed-out wait):
            # discard it rather than hand the desync to the next recv
            try:
                self.store.close()
            except Exception:
                pass
            return False
        with _P2P_CHAN_LOCK:
            _P2P_RECV_POOL.append(self.store)
        return False


def _p2p_pack(data) -> bytes:
    import pickle

    import numpy as np

    arr = np.asarray(data)
    return pickle.dumps({"dtype": str(arr.dtype), "shape": arr.shape,
                         "raw": arr.tobytes()})


def _p2p_unpack(payload: bytes):
    import pickle

    import numpy as np

    from .checkpoint import _np_dtype

    d = pickle.loads(payload)
    return np.frombuffer(d["raw"], dtype=_np_dtype(d["dtype"])).reshape(
        d["shape"])


class P2POp:
    """Op handle (the reference's ProcessGroup::Task role). For async ops the
    result is produced on a background thread; wait() joins it."""

    def __init__(self, thread=None):
        self._thread = thread
        self._exc = None

    def is_completed(self):
        return self._thread is None or not self._thread.is_alive()

    def wait(self, timeout=None):
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                return False
            if self._exc is not None:
                raise self._exc
        return True


def send(tensor, dst=0, group=None, sync_op=True, tag=0, src=None):
    """Send `tensor`'s value to rank `dst`.

    In-process ranks (single controller) use a device-resident mailbox; when
    the launcher gang-spawned multiple processes, the payload travels through
    the native TCPStore channel (see init_p2p_channel). `src` defaults to this
    process's rank; pass it explicitly when emulating multiple ranks in one
    process (single-controller pipeline prototyping).
    """
    prank, world = _proc_rank_world()
    if src is None:
        src = prank if world > 1 else get_rank(group)
    if world > 1 and dst != prank:
        # Multi-process mode: dst/src are PROCESS ranks (one controller per
        # process; PS trainers / CPU gangs). Device-rank p2p inside a compiled
        # program is ppermute's job, not this channel's.
        if not (0 <= dst < world):
            raise ValueError(
                f"send: dst={dst} is not a process rank (world={world}); "
                "across processes send/recv address processes, not devices")
        store = init_p2p_channel()
        seq = store.add(f"_p2p/{src}/{dst}/{tag}/seq", 1)
        store.set(f"_p2p/{src}/{dst}/{tag}/{seq}", _p2p_pack(
            tensor.data if hasattr(tensor, "data") else tensor))
        return P2POp()
    env = get_mesh_env()
    data = tensor.data if hasattr(tensor, "data") else jnp.asarray(tensor)
    if env is not None:
        devices = env.mesh.devices.reshape(-1)
        if dst < len(devices):
            data = jax.device_put(data, devices[dst])
    with _P2P_CV:
        _P2P_BOX.setdefault((src, dst, tag), []).append(data)
        _P2P_CV.notify_all()
    return P2POp()


def recv(tensor, src=0, group=None, sync_op=True, tag=0, dst=None,
         timeout=60.0):
    """Fill `tensor` in place with the next message from rank `src`.

    `dst` defaults to this process's rank; pass the rank you are emulating to
    retrieve a message addressed elsewhere (see send). `timeout` bounds the
    in-process mailbox wait; the inter-process path uses the store's timeout.
    """
    prank, world = _proc_rank_world()
    if dst is None:
        dst = prank if world > 1 else get_rank(group)
    if world > 1 and src != prank:
        if not (0 <= src < world):
            raise ValueError(
                f"recv: src={src} is not a process rank (world={world}); "
                "across processes send/recv address processes, not devices")
        init_p2p_channel()
        key = (src, dst, tag)
        # reserve a sequence so concurrent irecvs on one channel each consume
        # a distinct message exactly once; failed reservations are recycled
        with _P2P_CHAN_LOCK:
            abandoned = _P2P_ABANDONED.setdefault(key, [])
            if abandoned:
                seq = min(abandoned)
                abandoned.remove(seq)
            else:
                seq = _P2P_RECV_SEQ.get(key, 0) + 1
                _P2P_RECV_SEQ[key] = seq
        skey = f"_p2p/{src}/{dst}/{tag}/{seq}"
        # blocking waits ride a pooled dedicated connection: the shared
        # client's lock must stay free so a concurrent send (irecv+send
        # exchange) can proceed while this thread is parked in wait()
        try:
            with _RecvChannel() as store:
                store.wait([skey])
                data = jnp.asarray(_p2p_unpack(store.get(skey)))
        except BaseException:
            with _P2P_CHAN_LOCK:  # let a retry pick this message up
                _P2P_ABANDONED.setdefault(key, []).append(seq)
            raise
        # after a successful read the message is CONSUMED: a delete failure
        # must propagate without recycling the seq (a retry would re-deliver)
        with _RecvChannel() as store:
            store.delete_key(skey)
    else:
        with _P2P_CV:
            ok = _P2P_CV.wait_for(
                lambda: _P2P_BOX.get((src, dst, tag)), timeout=timeout)
            if not ok:
                raise RuntimeError(
                    f"recv: no message from rank {src} to rank {dst} (tag {tag}) "
                    f"after {timeout}s; if the sender used dst!=your rank, pass "
                    f"recv(..., dst=...)")
            data = _P2P_BOX[(src, dst, tag)].pop(0)
    if hasattr(tensor, "data"):
        if tuple(tensor.shape) != tuple(data.shape):
            raise ValueError(
                f"recv: shape mismatch {tuple(data.shape)} vs buffer "
                f"{tuple(tensor.shape)}")
        tensor.data = data.astype(tensor.data.dtype)
        return P2POp()
    return data


def isend(tensor, dst=0, group=None, tag=0):
    # deposit is already non-blocking; reuse the sync path
    return send(tensor, dst, group, sync_op=False, tag=tag)


def irecv(tensor, src=0, group=None, tag=0):
    """Asynchronous receive: returns immediately; wait() joins the background
    receive so 'task = irecv(...); send(...); task.wait()' exchanges work."""
    op = P2POp(thread=None)

    def run():
        try:
            recv(tensor, src, group, sync_op=True, tag=tag)
        except BaseException as e:
            op._exc = e

    t = threading.Thread(target=run, daemon=True,
                         name="pt-collective-irecv")
    op._thread = t
    t.start()
    return op


# -- in-trace collectives (for shard_map bodies: TP/PP/EP internals) ---------

def psum(x, axis: str):
    return jax.lax.psum(x, axis)


def pmean(x, axis: str):
    return jax.lax.pmean(x, axis)


def ppermute(x, axis: str, perm):
    return jax.lax.ppermute(x, axis, perm)


def axis_index(axis: str):
    return jax.lax.axis_index(axis)


def all_to_all_axis(x, axis: str, split_axis: int, concat_axis: int):
    return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
