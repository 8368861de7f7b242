"""Capture-and-compile: the @to_static / CINN-role subsystem.

Reference: python/paddle/fluid/dygraph/dygraph_to_static/ (AST transpiler ->
ProgramDesc -> executor) and paddle2cinn (subgraph JIT). TPU-native redesign:
capture IS tracing — `jax.jit` over the eager op layer. The same eager ops run
under an outer trace, so there is no separate program IR to maintain; XLA is
the compiled executor (InterpreterCore role), and donation replaces the
memory-optimize pass.

Two entry points:
- ``to_static(layer_or_fn)``: compiled forward (inference / eval path).
- ``TrainStep(model, loss_fn, optimizer)``: whole-train-step compilation —
  forward + backward (jax.grad at array level) + fused optimizer update in ONE
  XLA executable with donated buffers. This is the TPU-performance path; the
  eager tape is bypassed entirely.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import autograd
from ..framework import random as random_mod
from ..nn.layer.layers import Layer
from ..observability.trace.parts import step_part
from . import persistent_cache


def _collect_params(layer: Layer):
    named = list(layer.named_parameters())
    buffers = list(layer.named_buffers())
    return named, buffers


# Trace-cache audit hooks (paddle_tpu.analysis.retrace installs these; both
# default None so the path is untouched when auditing is off):
# _TRACE_AUDIT_HOOK(label, jitted) -> callable wraps freshly built compiled
# steps; _TRACE_NEWKEY_HOOK(label, key) records python-level cache-key drift
# (a new to_static specialization == a guaranteed recompile).
_TRACE_AUDIT_HOOK = None
_TRACE_NEWKEY_HOOK = None
_AUDIT_INSTANCE_NO = [0]


def _maybe_audit(label, jitted):
    return _TRACE_AUDIT_HOOK(label, jitted) if _TRACE_AUDIT_HOOK is not None \
        else jitted


_OBS = None  # lazily bound (StepTimeline, trace_cache CounterFamily)


def _obs():
    """(timeline, trace_cache family) — the observability hooks every
    compiled-step call site feeds. One-time late bind; per-call cost after
    that is a tuple load."""
    global _OBS
    if _OBS is None:
        from ..observability import family
        from ..observability.timeline import timeline

        _OBS = (timeline(), family("trace_cache", ("site", "event")))
    return _OBS


_MEMOBS = None  # lazily bound observability.memory (drift + OOM forensics)


def _memobs():
    """The memory-truth module every compiled step consults: an unarmed
    OOM-guard peek per call, drift recording only on cold builds."""
    global _MEMOBS
    if _MEMOBS is None:
        from ..observability import memory as _m

        _MEMOBS = _m
    return _MEMOBS


def _audit_instance_label(kind: str) -> str:
    """Per-instance audit label ("TrainStep#2"): two train steps with
    different batch shapes must not pool signatures in one bucket — that
    would report phantom recompiles."""
    _AUDIT_INSTANCE_NO[0] += 1
    return f"{kind}#{_AUDIT_INSTANCE_NO[0]}"


def make_param_updater(opt, train_params):
    """Per-param optimizer update math (grads -> new params/states): the
    ONE source of the weight-decay coupling / decoupled-decay / rule
    application every compiled step uses — TrainStep, the fused
    AccumulateStep, and ShardedTrainStep's mesh builds all call this, so
    their numerics cannot drift apart."""
    rule = type(opt)._rule
    hyper = opt._hyper()
    wd = opt._weight_decay
    decoupled = opt._decoupled
    wd_flags = tuple(
        1.0 if (opt._decay_param_fn is None or opt._decay_param_fn(p)) else 0.0
        for p in train_params)

    @step_part("optimizer")
    def apply(params, grads, states, lr, step_no):
        new_p, new_s = [], []
        for p, g, s, flag in zip(params, grads, states, wd_flags):
            g = g.astype(p.dtype)
            if wd and not decoupled and flag:
                g = g + wd * p
            hyper_i = hyper if flag or "wd" not in hyper \
                else dict(hyper, wd=0.0)
            np_, ns = rule(p, g, s, lr, step_no, hyper_i)
            if wd and decoupled and flag:
                np_ = np_ - (lr * wd * p).astype(p.dtype)
            new_p.append(np_)
            new_s.append(ns)
        return new_p, new_s

    return apply


class _Binder:
    """Temporarily swap Layer parameter/buffer .data with traced arrays."""

    def __init__(self, tensors: List[Tensor]):
        self.tensors = tensors
        self.saved = None

    def __enter__(self):
        self.saved = [t.data for t in self.tensors]
        return self

    def bind(self, arrays):
        for t, a in zip(self.tensors, arrays):
            t.data = a

    def __exit__(self, *exc):
        for t, a in zip(self.tensors, self.saved):
            t.data = a
        return False


class StaticLayer:
    """Compiled forward wrapper (TranslatedLayer/StaticFunction analogue)."""

    def __init__(self, layer_or_fn, input_spec=None, full_graph=True):
        self._is_layer = isinstance(layer_or_fn, Layer)
        self._target = layer_or_fn
        self._cache = {}
        self._audit_label = None  # assigned per instance on first compile
        # AST-lite dy2static (program_translator.py:775 role): rewrite simple
        # tensor-dependent if/while into runtime-dispatched cond/while_loop.
        # The conversion is scoped to THIS wrapper — the user's layer object
        # keeps its original eager forward (no instance mutation).
        from .dy2static import convert_to_static

        self._converted_forward = None
        if self._is_layer:
            fwd = type(layer_or_fn).forward
            conv = convert_to_static(fwd)
            if conv is not fwd:
                import types as _types

                self._converted_forward = _types.MethodType(conv, layer_or_fn)
        else:
            self._target = convert_to_static(layer_or_fn)

    def __call__(self, *args, **kwargs):
        # Tensor kwargs become traced inputs; everything else is static
        # (part of the compile-cache key), matching paddle's StaticFunction
        # kwargs contract.
        import numpy as _np

        def _is_data(v):
            return isinstance(v, (Tensor, jax.Array, _np.ndarray))

        kw_tensor = {k: v for k, v in sorted(kwargs.items()) if _is_data(v)}
        kw_static = {k: v for k, v in kwargs.items() if k not in kw_tensor}
        try:
            static_key = tuple(sorted(kw_static.items()))
            hash(static_key)
        except TypeError:
            raise TypeError(
                "to_static: non-Tensor keyword arguments must be hashable "
                f"(got {sorted(kw_static)})")
        # positional args: data is traced; plain Python values are STATIC
        # (python semantics preserved, cache key per value) like the
        # reference's StaticFunction
        data_idx = tuple(i for i, a in enumerate(args) if _is_data(a))
        static_args = tuple((i, a) for i, a in enumerate(args)
                            if not _is_data(a))
        try:
            hash(static_args)
        except TypeError:
            raise TypeError(
                "to_static: non-Tensor positional arguments must be hashable")
        arrays = [args[i].data if isinstance(args[i], Tensor) else args[i]
                  for i in data_idx]
        kw_arrays = [v.data if isinstance(v, Tensor) else v
                     for v in kw_tensor.values()]
        kw_names = tuple(kw_tensor)
        if self._is_layer:
            named, buffers = _collect_params(self._target)
            tensors = [p for _, p in named] + [b for _, b in buffers]
            key = ("layer", self._target.training, len(tensors), kw_names,
                   static_key, data_idx, static_args)
        else:
            tensors = []
            key = ("fn", kw_names, static_key, data_idx, static_args)
        _tc = _obs()[1]
        jitted = self._cache.get(key)
        _tc.inc(("to_static", "hit" if jitted is not None else "miss"))
        if jitted is None:
            target, is_layer = self._target, self._is_layer

            converted = self._converted_forward

            def run(param_arrays, input_arrays, kw_input_arrays, rngkey):
                random_mod.default_generator().set_trace_key(rngkey)
                kw = dict(zip(kw_names, (Tensor(a) for a in kw_input_arrays)))
                kw.update(kw_static)
                # interleave traced data and static python args back into
                # the original positional order
                full = dict(static_args)
                for i, a in zip(data_idx, input_arrays):
                    full[i] = Tensor(a)
                pos = [full[i] for i in sorted(full)]
                swapped = False
                try:
                    if is_layer:
                        if converted is not None:
                            # dy2static forward only inside this capture
                            target.forward = converted
                            swapped = True
                        named, buffers = _collect_params(target)
                        ts = [p for _, p in named] + [b for _, b in buffers]
                        with _Binder(ts) as b:
                            b.bind(param_arrays)
                            with autograd.no_grad():
                                out = target(*pos, **kw)
                    else:
                        with autograd.no_grad():
                            out = target(*pos, **kw)
                finally:
                    if swapped:
                        del target.forward  # restore the class method
                    random_mod.default_generator().clear_trace_key()
                return jax.tree_util.tree_map(
                    lambda t: t.data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))

            base = "to_static:" + getattr(self._target, "__name__",
                                          type(self._target).__name__)
            if self._audit_label is None:
                self._audit_label = _audit_instance_label(base)
            if _TRACE_NEWKEY_HOOK is not None:
                # a NEW python-level cache key == a guaranteed recompile
                # (static-arg / kwarg-structure drift): let the auditor
                # attribute it per WRAPPER instance
                _TRACE_NEWKEY_HOOK(self._audit_label, key)
            # each specialization is its own jit cache: give its call-
            # signature bucket a distinct label too, or two specializations
            # of one wrapper would read as phantom signature drift
            jitted = _maybe_audit(
                f"{self._audit_label}/k{len(self._cache)}",
                persistent_cache.cached_jit(
                    run, label=self._audit_label,
                    extra_meta=("to_static", repr(key))))
            self._cache[key] = jitted
        param_arrays = [t.data for t in tensors]
        out = jitted(param_arrays, arrays, kw_arrays, random_mod.next_key())
        return jax.tree_util.tree_map(Tensor, out)

    # paddle API-compat
    @property
    def forward(self):
        return self.__call__


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              **kwargs):
    """@paddle.jit.to_static equivalent (reference: fluid/dygraph/jit.py:163)."""
    if function is None:
        return lambda f: to_static(f, input_spec)
    return StaticLayer(function, input_spec)


def _batch_arrays(batch):
    return [b.data if isinstance(b, Tensor) else jnp.asarray(b)
            for b in batch]


def step_args(step, arrays, key):
    """The compiled step's argument tuple — one signature for TrainStep
    and ShardedTrainStep: (params, states, frozen, lr, step_no, key,
    *batch)."""
    opt = step.optimizer
    return ([p.data for p in step.train_params],
            [opt._accumulators[id(p)] for p in step.train_params],
            [t.data for t in step.frozen],
            jnp.asarray(opt.get_lr(), jnp.float32),
            jnp.asarray(opt._global_step + 1, jnp.int32),
            key, *arrays)


def lowerable(jitted):
    """Peel call-recording wrappers (retrace audit) down to the object
    that can ``.lower()``."""
    while not hasattr(jitted, "lower"):
        jitted = jitted.__wrapped__
    return jitted


class TrainStep:
    """Whole-step compiler: the hybrid of InterpreterCore + generated grad ops.

    usage::
        step = paddle_tpu.jit.TrainStep(model, loss_fn, optimizer)
        loss = step(x, y)            # one XLA executable: fwd+bwd+update

    loss_fn(model, *batch) -> scalar loss Tensor.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, donate=True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.donate = donate
        self._jitted = None
        self._init_opt_state()

    def _init_opt_state(self):
        opt = self.optimizer
        self.train_params = [p for p in opt._parameter_list if not p.stop_gradient]
        named, buffers = _collect_params(self.model)
        train_ids = {id(p) for p in self.train_params}
        self.frozen = [p for _, p in named if id(p) not in train_ids] + \
            [b for _, b in buffers]
        for p in self.train_params:
            if id(p) not in opt._accumulators:
                opt._accumulators[id(p)] = opt._init_state(p.data)

    def _make_updater(self):
        return make_param_updater(self.optimizer, self.train_params)

    def _build(self):
        opt = self.optimizer
        model, loss_fn = self.model, self.loss_fn
        clip = opt._grad_clip
        train_params = self.train_params
        frozen = self.frozen
        updater = self._make_updater()

        def step(params, states, frozen_arrays, lr, step_no, rngkey, *batch):
            random_mod.default_generator().set_trace_key(rngkey)
            try:
                def loss_of(param_arrays):
                    ts = train_params + frozen
                    with _Binder(ts) as b:
                        b.bind(list(param_arrays) + list(frozen_arrays))
                        with autograd.no_grad():
                            loss = loss_fn(model, *[Tensor(a) for a in batch])
                    return loss.data.astype(jnp.float32)

                loss_val, grads = jax.value_and_grad(loss_of)(tuple(params))
                grads = list(grads)
                if clip is not None:
                    grads = clip._apply_jax(grads)
                new_p, new_s = updater(params, grads, states, lr, step_no)
                return loss_val, new_p, new_s
            finally:
                random_mod.default_generator().clear_trace_key()

        donate = (0, 1) if self.donate else ()
        return persistent_cache.cached_jit(step, donate_argnums=donate,
                                           label="TrainStep")

    def accumulate(self, steps: int, remat: bool = False,
                   average: bool = True) -> "AccumulateStep":
        """Fused gradient accumulation: one executable that scans ``steps``
        microbatches (fwd+bwd each, optional remat), accumulates grads in
        fp32, and applies ONE optimizer update — numerically the k
        sequential micro-steps of the eager accumulation recipe (loss
        scaled 1/k when ``average``) without k dispatches or k optimizer
        launches. Call it with the FULL batch; dim 0 must divide by
        ``steps``."""
        return AccumulateStep(self, steps, remat=remat, average=average)

    def lower(self, *batch):
        """AOT-lower the whole step for this batch's shapes WITHOUT running
        it (``jax.stages.Lowered``): ``.compile()`` then answers what only
        the compiled program can — ``memory_analysis()`` (does this batch
        fit?) and ``as_text()`` (are the kernels really in it?). The step's
        state and the RNG stream are untouched."""
        self._ensure_built()
        return lowerable(self._jitted).lower(*step_args(
            self, _batch_arrays(batch), jax.random.key(0)))

    def _ensure_built(self):
        if self._jitted is None:
            from . import remat_fit

            _obs()[1].inc(("train_step", "build"))
            self._jitted = _maybe_audit(
                _audit_instance_label("TrainStep"),
                remat_fit.fitted(self._build, "TrainStep"))

    def __call__(self, *batch):
        tl, _tc = _obs()
        with tl.step():
            cold = self._jitted is None
            self._ensure_built()
            opt = self.optimizer
            arrays = _batch_arrays(batch)
            (params, states, frozen_arrays, lr, step_no,
             key) = step_args(self, (), random_mod.next_key())
            mo = _memobs()
            drift_args = mo.struct_args(
                (params, states, frozen_arrays, lr, step_no, key)
                + tuple(arrays)) if cold and mo.drift_enabled() else None
            # cold call = trace + XLA compile + first run; warm = async
            # dispatch (a warm retrace from signature drift lands here too —
            # analysis.retrace names it)
            with tl.phase("compile" if cold else "host_dispatch"):
                with mo.oom_guard("train_step", label="TrainStep",
                                  step=opt._global_step):
                    loss, new_p, new_s = self._jitted(
                        params, states, frozen_arrays, lr, step_no,
                        key, *arrays)
            if tl.detailed:
                with tl.phase("device_block"):
                    jax.block_until_ready(loss)
            for p, a in zip(self.train_params, new_p):
                p.data = a
            for p, s in zip(self.train_params, new_s):
                opt._accumulators[id(p)] = s
            opt._global_step += 1
            if cold:
                mo.maybe_record_drift(self, arrays, "TrainStep",
                                      self._jitted, drift_args)
        return Tensor(loss)


class AccumulateStep:
    """Fused gradient-accumulation executable (``TrainStep.accumulate``).

    The microbatch loop is a ``lax.scan`` INSIDE one jitted-and-donated
    program: per iteration fwd+bwd on one microbatch (optionally under
    ``jax.checkpoint`` so activations rematerialize instead of living for
    the whole window), gradients accumulated into fp32 carries, then a
    single optimizer update from the window total. Equivalent to the eager
    recipe ``for mb: backward(loss(mb)/k); optimizer.step()`` — the
    lr-equivalent scaling of a full-batch mean loss — with one dispatch
    and no per-microbatch host round-trips.

    Duck-types the TrainStep capture surface (``_build``/``train_params``/
    ``frozen``/``optimizer``/``donate``) so ``analysis.capture`` and the
    HBM estimator model it, donation included.
    """

    def __init__(self, step: TrainStep, steps: int, remat: bool = False,
                 average: bool = True):
        if int(steps) < 1:
            raise ValueError(f"accumulate: steps must be >= 1, got {steps}")
        self._step = step
        self.steps = int(steps)
        self.remat = bool(remat)
        self.average = bool(average)
        self.model = step.model
        self.loss_fn = step.loss_fn
        self.optimizer = step.optimizer
        self.donate = step.donate
        self.train_params = step.train_params
        self.frozen = step.frozen
        self._jitted = None

    def _build(self):
        opt = self.optimizer
        model, loss_fn = self.model, self.loss_fn
        clip = opt._grad_clip
        train_params = self.train_params
        frozen = self.frozen
        k = self.steps
        scale = 1.0 / k if self.average else 1.0
        remat = self.remat
        updater = self._step._make_updater()

        def loss_of(param_arrays, frozen_arrays, mb):
            ts = train_params + frozen
            with _Binder(ts) as b:
                b.bind(list(param_arrays) + list(frozen_arrays))
                with autograd.no_grad():
                    loss = loss_fn(model, *[Tensor(a) for a in mb])
            return loss.data.astype(jnp.float32)

        # grads w.r.t. argnum 0 (params) only; remat recomputes the
        # microbatch forward during backward so window activations never
        # accumulate across scan iterations
        grad_fn = jax.value_and_grad(
            jax.checkpoint(loss_of) if remat else loss_of)

        def step(params, states, frozen_arrays, lr, step_no, rngkey, *batch):
            micro = tuple(
                a.reshape((k, a.shape[0] // k) + a.shape[1:]) for a in batch)
            keys = jax.random.split(rngkey, k)

            def body(acc, xs):
                key_i, mb = xs[0], xs[1:]
                random_mod.default_generator().set_trace_key(key_i)
                try:
                    loss_i, grads = grad_fn(tuple(params), frozen_arrays, mb)
                finally:
                    random_mod.default_generator().clear_trace_key()
                acc2 = [a + g.astype(jnp.float32) * scale
                        for a, g in zip(acc, grads)]
                return acc2, loss_i

            acc0 = [jnp.zeros(p.shape, jnp.float32) for p in train_params]
            accT, losses = jax.lax.scan(body, acc0, (keys,) + micro)
            grads = list(accT)
            if clip is not None:
                grads = clip._apply_jax(grads)
            new_p, new_s = updater(params, grads, states, lr, step_no)
            return jnp.mean(losses), new_p, new_s

        donate = (0, 1) if self.donate else ()
        return persistent_cache.cached_jit(
            step, donate_argnums=donate, label=f"TrainStep.accumulate({k})",
            extra_meta=("accum", k, self.average, self.remat))

    def __call__(self, *batch):
        opt = self.optimizer
        arrays = [b.data if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        for a in arrays:
            if a.ndim == 0 or a.shape[0] % self.steps != 0:
                raise ValueError(
                    f"accumulate({self.steps}): batch dim {a.shape} must "
                    f"divide by the microbatch count")
        tl, tc = _obs()
        with tl.step():
            cold = self._jitted is None
            if cold:
                tc.inc(("accumulate", "build"))
                self._jitted = _maybe_audit(
                    _audit_instance_label(
                        f"TrainStep.accumulate({self.steps})"),
                    self._build())
            params = [p.data for p in self.train_params]
            states = [opt._accumulators[id(p)] for p in self.train_params]
            frozen_arrays = [t.data for t in self.frozen]
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_no = jnp.asarray(opt._global_step + 1, jnp.int32)
            key = random_mod.next_key()
            mo = _memobs()
            drift_args = mo.struct_args(
                (params, states, frozen_arrays, lr, step_no, key)
                + tuple(arrays)) if cold and mo.drift_enabled() else None
            label = f"TrainStep.accumulate({self.steps})"
            with tl.phase("compile" if cold else "host_dispatch"):
                with mo.oom_guard("accumulate", label=label,
                                  step=opt._global_step):
                    loss, new_p, new_s = self._jitted(
                        params, states, frozen_arrays, lr, step_no,
                        key, *arrays)
            if tl.detailed:
                with tl.phase("device_block"):
                    jax.block_until_ready(loss)
            for p, a in zip(self.train_params, new_p):
                p.data = a
            for p, s in zip(self.train_params, new_s):
                opt._accumulators[id(p)] = s
            opt._global_step += 1
            if cold:
                mo.maybe_record_drift(self, arrays, label, self._jitted,
                                      drift_args)
        return Tensor(loss)


def _as_shape_struct(spec, poly_suffix=""):
    """InputSpec/Tensor/array -> jax.ShapeDtypeStruct; None dims become
    symbolic so the exported program accepts any batch size."""
    from jax import export as jexport

    if isinstance(spec, Tensor):
        return jax.ShapeDtypeStruct(tuple(spec.shape), spec.data.dtype)
    if hasattr(spec, "shape") and hasattr(spec, "dtype"):
        shape = tuple(spec.shape)
        dtype = jnp.dtype(str(spec.dtype).replace("paddle.", ""))
        if any(d is None or (isinstance(d, int) and d < 0) for d in shape):
            dims = [f"b{poly_suffix}_{i}"
                    if d is None or (isinstance(d, int) and d < 0) else str(d)
                    for i, d in enumerate(shape)]
            shape = jexport.symbolic_shape(",".join(dims))
        return jax.ShapeDtypeStruct(shape, dtype)
    raise TypeError(f"cannot build a trace signature from {spec!r}")


def save(layer, path, input_spec=None, **configs):
    """jit.save: AOT-export the traced forward (reference: fluid/dygraph/jit.py
    jit.save -> TranslatedLayer artifacts). Artifacts:

    - `<path>.pdmodel`   serialized StableHLO program (jax.export bytes),
      traced as fn(param_arrays, *inputs) for CPU+TPU platforms
    - `<path>.pdiparams` weights as npz (positional, matching the trace)
    - `<path>.pdmeta`    json: state-dict keys + input/output structure
    """
    import json

    from jax import export as jexport

    import numpy as np

    target = layer._target if isinstance(layer, StaticLayer) else layer
    if not isinstance(target, Layer):
        raise TypeError("jit.save expects an nn.Layer (or to_static of one)")
    if input_spec is None:
        raise ValueError(
            "jit.save needs input_spec=[InputSpec(...)|example Tensor, ...] "
            "to trace the forward")
    named, buffers = _collect_params(target)
    tensors = [p for _, p in named] + [b for _, b in buffers]
    keys = [k for k, _ in named] + [k for k, _ in buffers]
    arg_structs = [_as_shape_struct(s, poly_suffix=str(i))
                   for i, s in enumerate(input_spec)]
    param_structs = [jax.ShapeDtypeStruct(tuple(t.data.shape), t.data.dtype)
                     for t in tensors]

    def run(param_arrays, *input_arrays):
        ts = tensors
        with _Binder(ts) as b:
            b.bind(list(param_arrays))
            with autograd.no_grad():
                out = target(*[Tensor(a) for a in input_arrays])
        return jax.tree_util.tree_map(
            lambda t: t.data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor))

    was_training = target.training
    target.eval()  # export inference semantics (dropout off, BN running stats)
    try:
        exp = jexport.export(jax.jit(run), platforms=("cpu", "tpu"))(
            param_structs, *arg_structs)
    finally:
        if was_training:
            target.train()
    with open(path + ".pdmodel", "wb") as f:
        f.write(exp.serialize())
    with open(path + ".pdiparams", "wb") as f:
        np.savez(f, **{f"p{i}": np.asarray(t.data) for i, t in enumerate(tensors)})
    with open(path + ".pdmeta", "w") as f:
        json.dump({"param_keys": keys,
                   "num_inputs": len(arg_structs),
                   "input_specs": [
                       {"shape": [None if not isinstance(d, int) else d
                                  for d in s.shape],
                        "dtype": str(s.dtype)} for s in arg_structs]}, f)


class TranslatedLayer(Layer):
    """Loaded AOT program (reference: fluid/dygraph/io.py TranslatedLayer).

    Parameters are live: set_state_dict updates them and the next call feeds
    the new arrays into the exported executable."""

    def forward(self, *inputs):  # pragma: no cover - bound per-instance in load()
        raise RuntimeError("TranslatedLayer not initialized; use jit.load")


def load(path, **configs):
    """jit.load: rehydrate a jit.save artifact as a callable Layer."""
    import json
    import os

    import numpy as np
    from jax import export as jexport

    with open(path + ".pdmodel", "rb") as f:
        exp = jexport.deserialize(f.read())
    with open(path + ".pdmeta") as f:
        meta = json.load(f)
    data = np.load(path + ".pdiparams")
    arrays = [data[f"p{i}"] for i in range(len(meta["param_keys"]))]

    from ..nn.layer.layers import Parameter

    layer = TranslatedLayer()
    params = []
    for key, arr in zip(meta["param_keys"], arrays):
        p = Parameter(jnp.asarray(arr), name=key.replace(".", "_"))
        # register under the ORIGINAL dotted key: named_parameters/state_dict
        # then expose the same names the source model used, so
        # set_state_dict(trained_net.state_dict()) round-trips
        layer.add_parameter(key, p)
        params.append(p)

    # the exported program still pays an XLA compile per concrete input
    # shape; route it through the persistent cache so a warm process
    # (inference.Predictor load, serving warmup) skips those compiles
    call = persistent_cache.cached_jit(
        exp.call, label=f"jit.load:{os.path.basename(path)}")

    def forward(*inputs):
        arrs = [x.data if isinstance(x, Tensor) else jnp.asarray(x)
                for x in inputs]
        out = call([p.data for p in params], *arrs)
        return jax.tree_util.tree_map(Tensor, out)

    layer.forward = forward
    layer._param_keys = meta["param_keys"]
    layer.eval()
    return layer


def not_to_static(fn=None):
    return fn if fn is not None else (lambda f: f)


def ignore_module(modules):
    return None


class ProgramTranslator:
    """reference dygraph_to_static ProgramTranslator singleton: the
    enable/disable switch for to_static conversion."""

    _instance = None
    _enabled = True

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    @classmethod
    def get_instance(cls):
        return cls()

    def enable(self, enable_to_static: bool):
        ProgramTranslator._enabled = bool(enable_to_static)


class TracedLayer:
    """reference dygraph/jit.py TracedLayer: trace-and-run wrapper. The
    capture machinery is StaticLayer; this keeps the trace/save surface."""

    def __init__(self, layer, inputs):
        self._static = StaticLayer(layer)
        self._layer = layer
        self._inputs = inputs

    @staticmethod
    def trace(layer, inputs):
        tl = TracedLayer(layer, inputs)
        out = tl._static(*inputs)
        return out, tl

    def __call__(self, *args):
        return self._static(*args)

    def save_inference_model(self, path, feed=None, fetch=None):
        from . import save as _save

        return _save(self._layer, path, input_spec=list(self._inputs))


def set_code_level(level=100):
    """reference dy2static debug knob: we have no transpiled-code printer;
    stored for API compat."""
    import os

    os.environ["PT_DY2STATIC_CODE_LEVEL"] = str(level)


def set_verbosity(level=0, also_to_stdout=False):
    import os

    os.environ["PT_DY2STATIC_VERBOSITY"] = str(level)


from .offload_stream import (  # noqa: E402,F401
    SegmentedTrainStep, StreamedTrainStep, init_on_host,
)
