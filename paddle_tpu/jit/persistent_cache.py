"""Persistent executable cache: cross-process warm starts for compiled XLA.

Reference role: the reference's inference engine ships *serialized
programs* — an ``AnalysisPredictor`` loads an optimized ProgramDesc from
disk and never re-runs the optimization passes; likewise fluid's
``ParallelExecutor`` reuses build results across runs. On TPU the
analogous cold-start tax is XLA compilation: every fresh process pays
seconds-to-minutes compiling the very same programs it compiled yesterday
(training steps, ``to_static`` forwards, every serving bucket warmup).

This module closes that gap with an on-disk cache of **compiled
executables**:

- key = SHA-256 over (lowered StableHLO text, backend platform,
  jax/jaxlib versions, donation metadata, sharding/static metadata) — a
  stale jax upgrade or a changed donation plan is a *different key*, never
  a wrong hit;
- value = ``jax.experimental.serialize_executable`` payload (the AOT
  `compiled.serialize()` path) plus a small header re-verified at load;
- backends that cannot serialize executables degrade to enabling JAX's own
  compilation-cache directory (same disk location, coarser granularity)
  so the warm start still happens one layer down.

Default **off** — nothing changes for code that doesn't opt in. Enable
with ``enable(dir)`` or the env vars ``PT_PERSISTENT_CACHE_DIR=<dir>`` /
``PT_PERSISTENT_CACHE=1`` (read once at import). Corrupt or stale entries
are ignored gracefully (treated as a miss and overwritten).

ONE directory rule (``default_dir``): when ``JAX_COMPILATION_CACHE_DIR``
is set, every cache of this process — these entries and JAX's own
compilation cache — lives there and no code sets another. Unset, the
default is the fixed path ``<checkout>/.cache/jax`` (git-ignored): the
path is part of a cache key, so a directory that moves (tempdir, pid,
time) never hits.

Counters: ``stats()`` reports hits / misses / backend compiles / load
errors, per label — surfaced through ``analysis.retrace`` summaries and
``serving`` ``engine.stats()``.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = ["enable", "disable", "is_enabled", "cache_dir", "stats",
           "reset_stats", "cached_jit", "CachedJit", "clear", "default_dir",
           "enable_jax_compilation_cache", "in_one_stack_chunk"]

_JAX_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_MAGIC = b"PTXC1\n"  # format tag; bump on layout change


class _State:
    def __init__(self):
        self.enabled = False
        self.dir: Optional[str] = None
        self.serialize_broken = False   # backend can't serialize: fallback
        self.lock = threading.Lock()
        self.counters: Dict[str, int] = {
            "hits": 0, "misses": 0, "compiles": 0, "errors": 0}
        self.by_label: Dict[str, Dict[str, int]] = {}


_STATE = _State()


def _env_meta() -> Tuple[str, ...]:
    """Version/platform facet of every cache key."""
    import jax
    import jaxlib

    return (jax.__version__, jaxlib.__version__, jax.default_backend(),
            str(len(jax.devices())))


def default_dir() -> str:
    """The compile-cache directory when the caller names none:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``PT_PERSISTENT_CACHE_DIR``,
    else the fixed ``<checkout>/.cache/jax``."""
    return os.environ.get(_JAX_ENV) or \
        os.environ.get("PT_PERSISTENT_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".cache", "jax")


def enable_jax_compilation_cache() -> str:
    """Turn on JAX's OWN persistent compilation cache (every ``jax.jit``
    compile, not only ``cached_jit`` programs) and return its directory.
    With ``JAX_COMPILATION_CACHE_DIR`` set JAX already points there and
    nothing here sets another; unset, it is pointed at ``default_dir()``."""
    import jax

    path = os.environ.get(_JAX_ENV)
    if not path:
        path = default_dir()
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def enable(path: Optional[str] = None) -> str:
    """Turn the cache on (idempotent). Returns the active directory:
    ``JAX_COMPILATION_CACHE_DIR`` when that is set (it overrides ``path``
    — one process, one cache directory), else ``path``, else the
    directory already active, else ``default_dir()``.

    Entries are unpickled at load, so the directory must not be writable
    by other users: it is created 0700, and a directory owned by someone
    else is refused."""
    path = os.environ.get(_JAX_ENV) or path or _STATE.dir or default_dir()
    os.makedirs(path, mode=0o700, exist_ok=True)
    if hasattr(os, "getuid"):
        st = os.stat(path)
        if st.st_uid != os.getuid():
            raise RuntimeError(
                f"persistent_cache: refusing cache dir {path!r} owned by "
                f"uid {st.st_uid} (entries are unpickled at load; use a "
                f"directory this user owns)")
        if st.st_mode & 0o077:  # pre-existing dir may be wider than 0700
            os.chmod(path, 0o700)
            if os.stat(path).st_mode & 0o022:
                raise RuntimeError(
                    f"persistent_cache: cache dir {path!r} stays "
                    f"group/world-writable; entries are unpickled at load "
                    f"— use a private directory")
    _STATE.dir = path
    _STATE.enabled = True
    return path


def disable() -> None:
    _STATE.enabled = False


def is_enabled() -> bool:
    return _STATE.enabled


def cache_dir() -> Optional[str]:
    return _STATE.dir


def clear() -> int:
    """Delete every cache entry in the active directory; returns count."""
    if not _STATE.dir or not os.path.isdir(_STATE.dir):
        return 0
    n = 0
    for name in os.listdir(_STATE.dir):
        if name.endswith(".ptxc"):
            try:
                os.unlink(os.path.join(_STATE.dir, name))
                n += 1
            except OSError:
                pass
    return n


def stats() -> Dict[str, Any]:
    """Snapshot of the hit/miss/compile counters (plus per-label rows)."""
    with _STATE.lock:
        snap: Dict[str, Any] = dict(_STATE.counters)
        snap["by_label"] = {k: dict(v) for k, v in _STATE.by_label.items()}
    snap["enabled"] = _STATE.enabled
    snap["dir"] = _STATE.dir
    snap["backend_serialize_unsupported"] = _STATE.serialize_broken
    return snap


def reset_stats() -> None:
    with _STATE.lock:
        for k in _STATE.counters:
            _STATE.counters[k] = 0
        _STATE.by_label.clear()


def _count(kind: str, label: Optional[str]) -> None:
    with _STATE.lock:
        _STATE.counters[kind] = _STATE.counters.get(kind, 0) + 1
        if label:
            row = _STATE.by_label.setdefault(
                label, {"hits": 0, "misses": 0, "compiles": 0, "errors": 0})
            row[kind] = row.get(kind, 0) + 1


def _entry_path(key: str) -> str:
    return os.path.join(_STATE.dir or "", key + ".ptxc")


def _write_entry(key: str, header: Dict[str, Any], payload: Tuple) -> None:
    """Atomic write: tmp file + rename so a concurrent reader never sees a
    half-written entry (the corruption the loader must survive anyway).
    A write failure (dir pruned by a tmp cleaner, disk full) is dropped —
    the cache is an optimization, never the thing that sinks a step."""
    path = _entry_path(key)
    blob = _MAGIC + pickle.dumps((header, payload),
                                 protocol=pickle.HIGHEST_PROTOCOL)
    tmp = None
    try:
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _read_entry(key: str, label: Optional[str]) -> Optional[Tuple]:
    """Load (header-verified) payload, or None on missing/corrupt/stale."""
    path = _entry_path(key)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    try:
        if not blob.startswith(_MAGIC):
            raise ValueError("bad magic")
        header, payload = pickle.loads(blob[len(_MAGIC):])
        # belt and braces: versions are part of the key already, but a
        # tampered/renamed file must still be rejected here
        if tuple(header.get("env", ())) != _env_meta():
            raise ValueError("stale entry: environment mismatch")
        return payload
    except Exception:
        _count("errors", label)
        try:
            os.unlink(path)  # evict so the rewrite below lands cleanly
        except OSError:
            pass
        return None


def _fallback_jax_cache() -> None:
    """Backend can't serialize executables: turn on JAX's own on-disk
    compilation cache in the same directory so a later process still skips
    the XLA backend work (coarser: caches at the XLA client layer)."""
    import jax

    if not os.environ.get(_JAX_ENV):  # set: JAX is there already
        jax.config.update("jax_compilation_cache_dir", _STATE.dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


_SHARDING_REPRS: "weakref.WeakKeyDictionary" = None  # type: ignore[assignment]


def _sharding_repr(sharding) -> str:
    """repr(sharding), memoized per object: a train step's leaves mostly
    share a handful of sharding instances, and the enabled-path signature
    runs per call — don't rebuild the same strings every step."""
    global _SHARDING_REPRS
    if _SHARDING_REPRS is None:
        import weakref

        _SHARDING_REPRS = weakref.WeakKeyDictionary()
    try:
        return _SHARDING_REPRS[sharding]
    except (KeyError, TypeError):
        pass
    r = repr(sharding)
    try:
        _SHARDING_REPRS[sharding] = r
    except TypeError:
        pass
    return r


def _abstract_sig(args: Tuple) -> Tuple:
    """Shape/dtype/weak-type AND placement per leaf: an AOT-compiled
    executable is specialized to its input shardings, so same-shape args
    committed elsewhere must be a different entry, not a call-time
    mismatch error (plain jax.jit keys on sharding too)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        if isinstance(leaf, jax.Array):  # fast path: no abstractify
            committed = getattr(leaf, "committed", False)
            sig.append((tuple(leaf.shape), leaf.dtype.name,
                        bool(getattr(leaf, "weak_type", False)),
                        _sharding_repr(leaf.sharding) if committed
                        else None))
            continue
        aval = jax.api_util.shaped_abstractify(leaf)
        sharding = getattr(leaf, "sharding", None)
        committed = getattr(leaf, "committed", False)
        sig.append((tuple(aval.shape), str(aval.dtype),
                    bool(getattr(aval, "weak_type", False)),
                    _sharding_repr(sharding) if committed else None))
    return (tuple(sig), str(treedef))


def in_one_stack_chunk(fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` with every Python frame above this one in ONE
    chunk of the interpreter's stack — what a warm build should run under.

    CPython (3.11 on) keeps a thread's frames in "data stack" chunks of
    16 KiB and UNMAPS a chunk the moment its first frame returns. A call
    site that happens to sit at a chunk's end therefore maps and unmaps 16
    KiB on every call it makes: 6 us where a call costs 40 ns (a loop of 200
    000 trivial calls at recursion depth 268, this sandbox). A ``jax.jit``
    trace is a few hundred frames deep with hot loops at many depths, so
    some sit on a boundary, and WHICH do moves with the size of every frame
    below them: the same window programs of ``laguna-xs2-d5`` built warm in
    7.7 to 17.5 s on the chip's host as nothing but the number of 1 KiB
    frames under ``warmup()`` went from 0 to 7, and in 6.2 s under this
    function (PERF.md section 6, PR 37: what PR 36 measured as "lines added
    to a traced function slow every trace under it").

    The cure is this frame's size: it asks for 2**16 and a few stack slots,
    CPython gives it a chunk of the next power of two (1 MiB, mapped, not
    touched), and the half it does not use holds a few thousand frames."""
    return fn(*args, **kwargs)


in_one_stack_chunk.__code__ = in_one_stack_chunk.__code__.replace(
    co_stacksize=(1 << 16) + 256)


class CachedJit:
    """``jax.jit`` with a persistent per-signature compile step.

    Calls behave exactly like the wrapped jitted function. When the cache
    is enabled, the first call of each abstract signature goes through
    lower → disk lookup → (deserialize | compile+serialize); later calls
    reuse the in-memory executable. When disabled, calls delegate straight
    to ``jax.jit``'s own cache — a single flag check of overhead.
    """

    def __init__(self, fun: Callable, label: Optional[str] = None,
                 donate_argnums: Tuple[int, ...] = (),
                 extra_meta: Tuple = (), **jit_kwargs):
        import jax

        self._label = label or getattr(fun, "__name__", "fn")
        self._donate = tuple(donate_argnums)
        self._extra_meta = tuple(str(m) for m in extra_meta)
        # sharding metadata is part of the key: a re-meshed program must
        # never collide with its single-chip twin
        for k in ("in_shardings", "out_shardings"):
            if k in jit_kwargs:
                self._extra_meta += (k + "=" + repr(jit_kwargs[k]),)
        self._jitted = jax.jit(fun, donate_argnums=self._donate or None,
                               **jit_kwargs)
        self._compiled: Dict[Tuple, Callable] = {}
        self._build_lock = threading.Lock()

    def __call__(self, *args):
        if not _STATE.enabled:
            return self._jitted(*args)
        import jax

        if any(isinstance(l, jax.core.Tracer)
               for l in jax.tree_util.tree_leaves(args)):
            # called under an outer trace (make_jaxpr / nested jit): the
            # AOT lower/compile path needs concrete avals — inline instead
            return self._jitted(*args)
        sig = _abstract_sig(args)
        runner = self._compiled.get(sig)
        if runner is None:
            with self._build_lock:
                runner = self._compiled.get(sig)
                if runner is None:
                    runner = self._build(args, sig)
                    self._compiled[sig] = runner
        return runner(*args)

    # -- compile path ---------------------------------------------------------
    def _key(self, lowered, sig) -> str:
        h = hashlib.sha256()
        h.update(lowered.as_text().encode())
        # sig carries input placements: the HLO text can be identical for
        # two placements whose compiled executables are not interchangeable
        h.update(repr(sig).encode())
        for part in _env_meta() + self._extra_meta:
            h.update(b"\x00" + part.encode())
        h.update(b"\x00donate=" + repr(self._donate).encode())
        return h.hexdigest()

    def _build(self, args, sig) -> Callable:
        lowered = in_one_stack_chunk(self._jitted.lower, *args)
        return self.compile_lowered(lowered, sig)

    def compile_lowered(self, lowered, sig) -> Callable:
        """``lowered`` (this function's, for arguments of signature ``sig``)
        as an executable: from the disk entry where the cache is enabled and
        has one, else compiled (and written there)."""
        if not _STATE.enabled:
            return lowered.compile()
        key = self._key(lowered, sig)
        # serialize_broken gates WRITES only: one program that cannot
        # round-trip must not stop other programs' valid on-disk entries
        # from loading
        payload = _read_entry(key, self._label)
        if payload is not None:
            loaded = self._try_deserialize(payload)
            if loaded is not None:
                _count("hits", self._label)
                return loaded
        _count("misses", self._label)
        compiled = lowered.compile()
        _count("compiles", self._label)
        self._try_serialize(key, compiled)
        return compiled

    def _try_deserialize(self, payload) -> Optional[Callable]:
        try:
            from jax.experimental import serialize_executable

            return serialize_executable.deserialize_and_load(*payload)
        except Exception:
            _count("errors", self._label)
            return None

    def _try_serialize(self, key: str, compiled) -> None:
        if _STATE.serialize_broken or not _STATE.dir:
            return
        try:
            from jax.experimental import serialize_executable

            payload = serialize_executable.serialize(compiled)
            pickle.dumps(payload)  # probe: unpicklable trees = broken entry
        except Exception:
            # this backend (or this program) can't round-trip executables:
            # degrade to jax's own compilation-cache directory
            _STATE.serialize_broken = True
            _fallback_jax_cache()
            return
        _write_entry(key, {"env": _env_meta(), "label": self._label}, payload)

    # introspection used by jit._maybe_audit wrappers
    @property
    def __wrapped__(self):
        return self._jitted

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def trace(self, *args, **kwargs):
        return self._jitted.trace(*args, **kwargs)


def cached_jit(fun: Callable, label: Optional[str] = None,
               donate_argnums: Tuple[int, ...] = (),
               extra_meta: Tuple = (), **jit_kwargs) -> Callable:
    """Drop-in for ``jax.jit`` that persists compiles across processes.

    Always returns a ``CachedJit`` wrapper; when the cache is disabled the
    wrapper is a transparent passthrough to ``jax.jit``, so call sites can
    use this unconditionally.
    """
    return CachedJit(fun, label=label, donate_argnums=donate_argnums,
                     extra_meta=extra_meta, **jit_kwargs)


def _maybe_enable_from_env() -> None:
    d = os.environ.get("PT_PERSISTENT_CACHE_DIR", "").strip()
    flag = os.environ.get("PT_PERSISTENT_CACHE", "").strip().lower()
    if not d and flag not in ("1", "true", "on"):
        return
    try:
        enable(d or None)
    except Exception as e:
        # a bad env var must not make `import paddle_tpu` itself fail —
        # degrade to a disabled cache, loudly
        import warnings

        warnings.warn(f"persistent_cache: disabled ({e})", stacklevel=2)
        _STATE.enabled = False


_maybe_enable_from_env()
