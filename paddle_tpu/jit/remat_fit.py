"""What the layer recompute keeps, chosen to fill the memory the compiled
step has.

A scanned stack under ``use_recompute`` saves a layer's input and replays the
layer in the backward. ``stage_stack.remat_wrap`` keeps, under every policy,
what crossed ``mp`` and the flash kernel's ``o`` / ``lse``. The layer's
projection outputs carry names too (``stage_stack.ATTN_Q`` ... ``MLP_GATE``:
``models/llama.py`` names them), and every one the recompute keeps is a matmul
the backward does not run again — for its bytes, a layer, for the whole
backward. How many of them fit is a property of the compiled program and of
the chip, and arithmetic does not give it (a kept byte cost two in one
program and one in another: PERF.md section 6, PR 54). So where the policy
flag is at its default and the device states a memory limit, the step is
compiled at its first call for a few RUNGS of one ladder and the richest one
whose ``memory_analysis()`` fits under ``bytes_limit`` less ``MARGIN`` runs:

- the lean program (today's set) and ONE rung, picked at ``PRIOR_PRICE``,
  give this program's price of a kept byte; the price picks the next
  candidate; that one is verified by its own ``memory_analysis()`` and, if it
  is over, the walk steps down once (``next_step``; at most ``MAX_COMPILES``);
- the choice is remembered beside the compile cache
  (``persistent_cache.default_dir()``), keyed by what decides it, so a warm
  start compiles — loads — one program;
- a stack whose layers carry none of the names, or whose recompute reads no
  policy, is found before a second compile: it runs today's program.

No flag: ``FLAGS_remat_policy``'s other values keep their meaning and never
come here, and a device with no ``bytes_limit`` (the CPU) runs today's set.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
from typing import (Any, Callable, Dict, Mapping, NamedTuple, Optional,
                    Sequence, Tuple)

import jax

from ..distributed.meta_parallel import stage_stack as ss
from . import persistent_cache

_LOG = logging.getLogger(__name__)

_QKV = (ss.ATTN_Q, ss.ATTN_K, ss.ATTN_V)
# by the bytes a Llama layer's values hold (k + v, q and o one row of the
# hidden width a token each; up and gate four): what each rung keeps BESIDES
# today's set. ``attn_o`` rides with q / k / v where it exists (no ``mp``).
RUNGS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("lean", ()),
    ("kv", (ss.ATTN_K, ss.ATTN_V)),
    ("qkv", _QKV),
    ("qkvo", _QKV + (ss.ATTN_O,)),
    ("qkv_up", _QKV + (ss.MLP_UP,)),
    ("qkvo_up", _QKV + (ss.ATTN_O, ss.MLP_UP)),
    ("up_gate", (ss.MLP_UP, ss.MLP_GATE)),
    ("qkv_up_gate", _QKV + (ss.MLP_UP, ss.MLP_GATE)),
    ("qkvo_up_gate", _QKV + (ss.ATTN_O, ss.MLP_UP, ss.MLP_GATE)),
)
LADDER_NAMES = frozenset(n for _, names in RUNGS for n in names)

# the share of ``bytes_limit`` a chosen program leaves free: what the
# process holds beside the step's program while it runs (the next batch, the
# losses, the allocator's fragments). PERF.md section 6, PR 54 has the runs.
MARGIN = 0.02
# what a kept byte is assumed to cost the program before one rung measured
# it: the dearer of the two prices met (2 x: the one-chip step)
PRIOR_PRICE = 2.0
MAX_COMPILES = 4

_LAST: Dict[str, Any] = {}   # the newest build's numbers, behind the gauges


def bytes_limit() -> Optional[int]:
    """The least ``memory_stats()["bytes_limit"]`` of this process's devices;
    None where a device states none (the CPU)."""
    from ..analysis.memory import device_hbm_bytes

    try:
        return min(device_hbm_bytes(dev) for dev in jax.local_devices())
    except RuntimeError:
        return None


def _policy_flag() -> str:
    from ..framework import flags

    return flags.get_flags("FLAGS_remat_policy")["FLAGS_remat_policy"]


def program_bytes(compiled) -> int:
    """What ``benchmark/rehearse_aot.py:report`` prints as the program."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes
               + ma.generated_code_size_in_bytes)


# -- the chooser: a pure function ---------------------------------------------

def next_step(kept: Sequence[int], measured: Mapping[int, Optional[int]],
              limit: int, margin: float = MARGIN
              ) -> Tuple[Optional[int], Optional[int]]:
    """One move of the walk: ``(rung to compile next, None)`` or ``(None,
    the final rung)``. ``kept[i]`` is what rung ``i`` keeps in bytes, ascending
    from ``kept[0] == 0`` (the lean program); ``measured[i]`` the compiled
    program's bytes at rung ``i`` — None where the compiler refused it, which
    it does not refuse the lean one (that is today's program and its error)."""
    if 0 not in measured:
        return 0, None
    lean, budget = measured[0], limit * (1.0 - margin)
    best = max((i for i, b in measured.items()
                if b is not None and b <= budget), default=0)
    ceiling = min((i for i in measured if i > best), default=len(kept))
    if len(measured) >= MAX_COMPILES:
        return None, best
    priced = max((i for i, b in measured.items() if i and b is not None),
                 default=None)
    # a kept byte costs the program at least itself
    price = PRIOR_PRICE if priced is None else \
        max((measured[priced] - lean) / kept[priced], 1.0)
    reach = [i for i in range(best + 1, ceiling)
             if lean + price * kept[i] <= budget]
    if not reach and priced is None and best + 1 < ceiling \
            and lean + kept[best + 1] <= budget:
        reach = [best + 1]   # too dear at the prior: ask what the least costs
    return (max(reach), None) if reach else (None, best)


# -- what a traced step names, and the ladder it leaves ------------------------

def named_bytes(jaxpr, devices: int = 1) -> Dict[str, int]:
    """``{name: bytes a device}`` of the ladder's names the program REPLAYS:
    ``name`` equations inside a recompute's replayed body (``remat2``), times
    the lengths of the scans around them, over the ``devices`` the step
    spans (a mesh's axes each split the activations; where one does not, a
    kept byte measures dearer and the price says so). A name the forward
    alone holds (no recompute, or kept already) is not a candidate. Values
    that share a name in ONE body (a stack that walks its rows as two
    halves names each half's) add up; the same body met again (the forward's
    trace and the backward's) is the same bytes."""
    found: Dict[str, int] = {}

    def walk(jp, times, replayed):
        here: Dict[str, int] = {}
        for eqn in jp.eqns:
            prim = eqn.primitive.name
            if prim == "name" and replayed \
                    and eqn.params["name"] in LADDER_NAMES:
                aval = eqn.outvars[0].aval
                size = times * aval.size * aval.dtype.itemsize
                name = eqn.params["name"]
                here[name] = here.get(name, 0) + int(size)
            inner = times * eqn.params["length"] if prim == "scan" else times
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inner, replayed or prim == "remat2")
        for name, size in here.items():
            found[name] = max(found.get(name, 0), size)

    walk(jaxpr, 1, False)
    return {name: size // devices for name, size in found.items()}


class Rung(NamedTuple):
    label: str
    names: Tuple[str, ...]
    kept: int


def ladder(named: Mapping[str, int]) -> Tuple[Rung, ...]:
    """``RUNGS`` cut to the names this program replays, by bytes; two rungs
    that keep the same values here are one."""
    seen, out = set(), []
    for label, names in RUNGS:
        names = tuple(n for n in names if n in named)
        if names not in seen:
            seen.add(names)
            out.append(Rung(label, names, sum(named[n] for n in names)))
    return tuple(sorted(out, key=lambda r: r.kept))


# -- the remembered choice ------------------------------------------------------

def memo_key(sig, limit: int) -> str:
    """What decides a rung: the step's abstract signature, the policy flag,
    the device and its limit, the versions, and this ladder (the module's
    own text: a memo does not outlive the code that made it)."""
    dev = jax.local_devices()[0]
    with open(__file__, "rb") as f:
        ladder_code = hashlib.sha256(f.read()).hexdigest()
    parts = (repr(sig), _policy_flag(), dev.device_kind, str(limit),
             *persistent_cache._env_meta(), ladder_code)
    return hashlib.sha256("\x00".join(parts).encode()).hexdigest()


def _memo_path(key: str) -> str:
    return os.path.join(persistent_cache.default_dir(),
                        f"remat_fit-{key[:32]}.json")


def recall(key: str) -> Optional[Dict[str, Any]]:
    try:
        with open(_memo_path(key)) as f:
            memo = json.load(f)
        return memo if {"rung", "names", "kept_bytes"} <= set(memo) else None
    except (OSError, ValueError, TypeError):
        return None


def remember(key: str, memo: Optional[Dict[str, Any]]) -> None:
    """Write the choice (None: forget it). A failed write is dropped: the
    walk is then made again, it is never wrong."""
    path = _memo_path(key)
    try:
        if memo is None:
            os.unlink(path)
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(memo, f)
        os.replace(tmp, path)
    except OSError:
        pass


# -- the step's callable --------------------------------------------------------

def _publish(label: str, memo: Dict[str, Any], compiled: int,
             remembered: bool) -> None:
    from ..observability import gauge

    first = not _LAST
    _LAST.update(memo, compiled=compiled, remembered=remembered)
    if first:
        labels = [lab for lab, _ in RUNGS]
        gauge("train.remat_rung", lambda: labels.index(_LAST["rung"]))
        gauge("train.remat_kept_bytes", lambda: _LAST["kept_bytes"])
        gauge("train.step_program_bytes", lambda: _LAST["program_bytes"])
        gauge("train.step_bytes_limit", lambda: _LAST["bytes_limit"])
        gauge("train.remat_candidates_compiled", lambda: _LAST["compiled"])
        gauge("train.remat_remembered", lambda: int(_LAST["remembered"]))
    _LOG.info(
        "%s: the recompute keeps %s %s (%d bytes in all: %s); program %d of "
        "%d bytes; %d compiled%s", label, memo["rung"], list(memo["names"]),
        memo["kept_bytes"], memo["kept_by_name"], memo["program_bytes"],
        memo["bytes_limit"], compiled,
        ", the remembered choice" if remembered else "")


class FittedStep:
    """The compiled step of ``build()`` — a fresh jitted step each call, as
    ``TrainStep._build`` makes — at the rung that fits, chosen once for each
    batch signature."""

    def __init__(self, build: Callable[[], Any], label: str, limit: int):
        self._build = build
        self._label = label
        self._limit = limit
        # batch signature -> (the names its rung keeps, the executable)
        self._fits: Dict[Tuple, Tuple[Tuple[str, ...], Callable]] = {}

    @staticmethod
    def _batch_key(args) -> Tuple:
        # params, states, frozen, lr, step and key are the step's own; the
        # batch is what a caller can change
        return tuple((a.shape, a.dtype) for a in args[6:])

    def __call__(self, *args):
        key = self._batch_key(args)
        fit = self._fits.get(key)
        if fit is None:
            fit = self._fits[key] = self._fit(args)
        return fit[1](*args)

    def lower(self, *args):
        """Lowered at the rung this process chose for the batch, today's set
        before it chose."""
        names, _ = self._fits.get(self._batch_key(args), ((), None))
        return self._lower(names, args)[2]

    def _lower(self, names, args):
        """(the jitted step, traced, lowered) of a fresh build that keeps
        ``names``."""
        from . import lowerable

        with ss.keeping(names):
            jitted = lowerable(self._build())
            traced = persistent_cache.in_one_stack_chunk(jitted.trace, *args)
        return jitted, traced, traced.lower()

    @staticmethod
    def _compile(jitted, lowered, sig, may_refuse=True):
        """The executable (through the step's own persistent cache, where
        that is on); None where the compiler refused the program for its
        memory and ``may_refuse``."""
        try:
            return jitted.compile_lowered(lowered, sig)
        except Exception as e:  # jaxlib's XlaRuntimeError has no stable home
            if not may_refuse or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return None

    def _fit(self, args):
        """(names, executable) of the rung that fits ``args``' program."""
        limit = self._limit
        sig = persistent_cache._abstract_sig(args)
        key = memo_key(sig, limit)
        memo = recall(key)
        if memo is not None:
            jitted, _, lowered = self._lower(tuple(memo["names"]), args)
            compiled = self._compile(jitted, lowered, sig)
            # (the lean program is today's: it runs whatever it measures)
            if compiled is not None and (not memo["names"] or program_bytes(
                    compiled) <= limit * (1.0 - MARGIN)):
                _publish(self._label, memo, 1, True)
                return tuple(memo["names"]), compiled
            remember(key, None)   # the program under the key has changed
        # the lean program is today's: where the compiler refuses it, its
        # own error is the one to raise
        jitted, traced, lean_lowered = self._lower((), args)
        lean = self._compile(jitted, lean_lowered, sig, may_refuse=False)
        named = named_bytes(traced.jaxpr.jaxpr, max(
            len(a.sharding.device_set)
            for a in jax.tree_util.tree_leaves(args)))
        rungs = ladder(named)
        measured, programs = {0: program_bytes(lean)}, {0: lean}
        while True:
            cand, final = next_step([r.kept for r in rungs], measured, limit)
            if cand is None:
                break
            jitted, _, lowered = self._lower(rungs[cand].names, args)
            if len(measured) == 1 \
                    and lowered.as_text() == lean_lowered.as_text():
                # this recompute reads no policy (a plain jax.checkpoint):
                # every rung is the lean program, and the first shows it
                final = 0
                break
            programs[cand] = self._compile(jitted, lowered, sig)
            measured[cand] = None if programs[cand] is None \
                else program_bytes(programs[cand])
        rung = rungs[final]
        memo = {"rung": rung.label, "names": list(rung.names),
                "kept_bytes": rung.kept,
                "kept_by_name": {n: named[n] for n in rung.names},
                "program_bytes": measured[final], "bytes_limit": limit,
                "measured": {rungs[i].label: b for i, b in measured.items()}}
        remember(key, memo)
        _publish(self._label, memo, len(programs), False)
        return rung.names, programs[final]


def fitted(build: Callable[[], Any], label: str):
    """``build()``'s step as the step object calls it: today's program,
    straight, where the policy flag says what to keep or the device states
    no limit; else a ``FittedStep``."""
    limit = bytes_limit() if _policy_flag() == "" else None
    return build() if limit is None else FittedStep(build, label, limit)
