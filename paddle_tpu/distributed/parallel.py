"""DataParallel wrapper + sharded step compiler.

Reference: python/paddle/fluid/dygraph/parallel.py:410 (DataParallel with the
C++ bucketing Reducer, imperative/reducer.cc) — under GSPMD the gradient
all-reduce is inserted by XLA from the batch sharding, so no Reducer exists;
`no_sync` and the constructor surface are preserved.

ShardedTrainStep is the multi-chip twin of jit.TrainStep: parameters are
placed by their `dist_spec` (TP/ZeRO), the batch is sharded over dp, and the
whole fwd+bwd+update step is one pjit'ed executable over the mesh.
"""
from __future__ import annotations

import contextlib
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..core import autograd
from ..framework import random as random_mod
from ..nn.layer.layers import Layer
from .mesh import MeshEnv, get_mesh_env, require_mesh_env


class DataParallel(Layer):
    """reference parallel.py:410. Under SPMD: annotation-only wrapper."""

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        # honesty check (round-2 verdict W8): in EAGER MULTI-PROCESS mode
        # there is no per-step gradient sync at all (the reference reducer's
        # role only exists on the compiled path, where GSPMD fuses it), so
        # no_sync would be vacuous and training would silently diverge
        from .collective import _proc_rank_world

        _, world = _proc_rank_world()
        if world > 1:
            import warnings

            warnings.warn(
                "DataParallel across processes: eager backward does NOT "
                "all-reduce gradients (no reducer exists off the compiled "
                "path). Drive training through ShardedTrainStep / "
                "jit.TrainStep where the data-parallel reduction is part of "
                "the compiled step, or sync gradients explicitly with "
                "paddle.distributed.all_reduce.")

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    @contextlib.contextmanager
    def no_sync(self):
        """Gradient-sync pause (reference parallel.py:540).

        In the reference, backward fires bucketed NCCL all-reduces per step;
        no_sync suppresses them so micro-batch grads accumulate locally. Under
        single-controller GSPMD there is no per-step sync to suppress: grads
        are computed on the global batch view and the cross-replica reduction
        is fused into the one compiled backward, so eager accumulation between
        optimizer steps is communication-free by construction. The context
        manager is therefore a semantic no-op kept for API compatibility."""
        yield

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        return None

    # delegate bookkeeping
    def named_parameters(self, prefix="", include_sublayers=True):
        return self._layers.named_parameters(prefix, include_sublayers)

    def parameters(self, include_sublayers=True):
        return self._layers.parameters(include_sublayers)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)


def param_sharding(p, env: MeshEnv) -> NamedSharding:
    spec = getattr(p, "dist_spec", None)
    return env.sharding_for(spec) if spec is not None else env.replicated()


def zero_partition_spec(shape, env: MeshEnv, axis="sdp") -> Optional[P]:
    """Largest-divisible-dim sharding over the ZeRO axis — the param->rank
    partition of sharding_optimizer_stage2.py:43 expressed as a spec. Returns
    None when nothing divides (that param's state stays replicated)."""
    deg = env.get_dim(axis)
    if deg <= 1:
        return None
    best = None
    for i, s in enumerate(shape):
        if s % deg == 0 and (best is None or s > shape[best]):
            best = i
    if best is None:
        return None
    spec = [None] * len(shape)
    spec[best] = axis
    return P(*spec)


def place_model(model: Layer, env: Optional[MeshEnv] = None):
    """Materialize every parameter/buffer at its mesh placement (the
    broadcast-at-init of TensorParallel/DataParallel wrappers)."""
    env = env or require_mesh_env()
    for _, p in model.named_parameters():
        p.data = jax.device_put(p.data, param_sharding(p, env))
    for _, b in model.named_buffers():
        b.data = jax.device_put(b.data, env.replicated())
    return model


def default_batch_sharding(env: Optional[MeshEnv] = None):
    """leaf -> NamedSharding callable landing batch leaves at the mesh's
    data layout (dim 0 over dp/sdp) — ``ShardedTrainStep.batch_sharding``
    without needing a step object. ``hapi.Model.fit`` uses this to thread
    device prefetch through ``DistributedBatchSampler``-driven loops by
    default, and it is the right ``device_sharding=`` for hand loops too."""
    env = env or require_mesh_env()

    def leaf_sharding(arr):
        data_axes = [ax for ax in ("dp", "sdp") if env.get_dim(ax) > 1]
        shape = getattr(arr, "shape", ())
        if not data_axes or not shape:
            return env.sharding_for(P())
        deg = 1
        for ax in data_axes:
            deg *= env.get_dim(ax)
        if shape[0] % deg != 0:
            # ragged tail batch (drop_last=False): land it replicated
            # instead of failing the device_put mid-prefetch
            return env.sharding_for(P())
        return env.sharding_for(P(tuple(data_axes)))

    return leaf_sharding


class ShardedTrainStep:
    """pjit'ed fwd+bwd+update over the mesh (jit.TrainStep + GSPMD).

    batch_specs: PartitionSpec per batch input (default: shard dim0 over dp
    and sdp — ZeRO's data feeding — and cp if used by the caller's specs).

    scaler: an amp.GradScaler whose loss-scale state machine runs IN-GRAPH
    (scale/good/bad carried as compiled-step state; reference
    dygraph/amp/loss_scaler.py:40 update_loss_scaling). This is what lets
    AMP ride the compiled ppermute pipeline instead of falling back to the
    eager schedule.

    accum_steps: gradient-merge window k (reference
    meta_optimizers/gradient_merge_optimizer.py role): grads accumulate in
    fp32 carried buffers for k calls; the optimizer update applies only at
    window boundaries (averaged when accum_avg). Non-finite micro-steps
    (scaler live) contribute zero and are excluded from the average.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 batch_specs=None, env: Optional[MeshEnv] = None, donate=True,
                 scaler=None, accum_steps=1, accum_avg=True):
        self.env = env or require_mesh_env()
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.batch_specs = batch_specs
        self.donate = donate
        # retain the original object even when disabled: callers key compiled
        # steps by id(scaler), so the id must stay pinned to this object
        self._scaler_ref = scaler
        self.scaler = scaler if (scaler is not None
                                 and getattr(scaler, "_enable", True)) else None
        self.accum_steps = int(accum_steps)
        self.accum_avg = bool(accum_avg)
        self._amp_state = None   # (scale f32, good i32, bad i32, fin b1)
        self._upd_no = None      # applied-update counter (in-graph)
        self._acc = None         # fp32 grad buffers (accum_steps > 1)
        self._goodw = None       # finite micro-steps in current window
        self._win_count = 0      # host-side call index within the window
        self._jitted = None
        inner = getattr(model, "_layers", model)
        self.target = model
        opt = optimizer
        self.train_params = [p for p in opt._parameter_list if not p.stop_gradient]
        from ..nn.layer.layers import check_not_stacked

        check_not_stacked(self.train_params)
        named = dict(model.named_parameters())
        buffers = list(getattr(inner, "named_buffers", lambda: [])())
        train_ids = {id(p) for p in self.train_params}
        self.frozen = [p for p in named.values() if id(p) not in train_ids] + \
            [b for _, b in buffers]
        for p in self.train_params:
            if id(p) not in opt._accumulators:
                opt._accumulators[id(p)] = opt._init_state(p.data)
        place_model(model, self.env)
        # ZeRO stage from group_sharded_parallel: 1 = optimizer state sharded
        # over sdp, 2 = + gradients reduce-scattered, 3 = + params sharded
        # (stage 3 arrives via dist_spec; stages 1/2 shard state while the
        # param stays replicated)
        self.zero_stage = int(getattr(optimizer, "_zero_stage", 0))
        self.offload = bool(getattr(optimizer, "_offload", False))
        if self.offload and (self.scaler is not None or self.accum_steps > 1):
            raise NotImplementedError(
                "ShardedTrainStep: in-graph GradScaler / per-call accum_steps "
                "windows are not supported together with optimizer-state "
                "offload; run the scaler eagerly, or use the fused "
                "step.accumulate(k) which composes with the streaming "
                "offload executor")
        if self.offload:
            # reference sharding_utils.py offload: master weights + optimizer
            # state pinned to host memory; see _build_offload. The update
            # streams per GROUP through a double-buffered lane (the
            # TaskFlow-prefetch role) — group sizing honors the
            # group_sharded_parallel segment_size/buffer_max_size knobs.
            import os as _os

            self._cpu = jax.devices("cpu")[0]
            for p in self.train_params:
                st = opt._accumulators[id(p)]
                opt._accumulators[id(p)] = {
                    k: jax.device_put(v, self._cpu) for k, v in st.items()}
            self._master = [
                jax.device_put(jnp.asarray(p.data, jnp.float32), self._cpu)
                for p in self.train_params]
            self._stream_segment = int(getattr(
                optimizer, "_stream_segment_size", 2 ** 20))
            self._stream_bufmax = int(getattr(
                optimizer, "_stream_buffer_max_size", 2 ** 23))
            self._stream_overlap = _os.environ.get(
                "PT_OFFLOAD_OVERLAP", "1").strip().lower() not in (
                "0", "false", "off")
            # cross-step pipeline fill (PR-5 carried item): hand the final
            # param uploads to the next dispatch as jax futures instead of
            # draining the lane at the step boundary, so the NEXT step's
            # group-0 grad download is submitted while the current step's
            # fwd+bwd executes. Trade-off: taken futures cannot be
            # re-issued, so a transient fault surfacing in the LANDING
            # phase of a taken upload fails sticky instead of retrying
            # (fail-stop + checkpoint resume, the PR-6 outer story);
            # PT_OFFLOAD_EAGER_UPLOAD=0 restores the boundary drain and
            # with it maximal in-lane retry coverage for flaky links.
            self._stream_eager = _os.environ.get(
                "PT_OFFLOAD_EAGER_UPLOAD", "1").strip().lower() not in (
                "0", "false", "off")
            self._stream = None  # (groups, per-group upd execs, clip, lane)
            return
        # place optimizer state at its (possibly ZeRO-sharded) placement
        for p in self.train_params:
            st = opt._accumulators[id(p)]
            sh = self._state_sharding(p)
            opt._accumulators[id(p)] = {k: jax.device_put(v, sh) if v.shape == p.data.shape
                                        else v for k, v in st.items()}

    def _state_sharding(self, p) -> NamedSharding:
        """Optimizer-state placement: like the param, except ZeRO stage 1/2
        shards the state of replicated params over sdp."""
        if getattr(p, "dist_spec", None) is not None or self.zero_stage < 1:
            return param_sharding(p, self.env)
        spec = zero_partition_spec(p.shape, self.env)
        return self.env.sharding_for(spec) if spec is not None else self.env.replicated()

    def _default_batch_spec(self, arr):
        data_axes = [ax for ax in ("dp", "sdp") if self.env.get_dim(ax) > 1]
        if not data_axes or arr.ndim == 0:
            return P()
        return P(tuple(data_axes))

    def batch_sharding(self, arr) -> NamedSharding:
        """NamedSharding for one batch leaf — the hook
        ``io.DevicePrefetcher(loader, sharding=step.batch_sharding)`` uses
        to land prefetched batches already laid out for this step, so the
        compiled program starts without a host transfer OR a reshard."""
        return self.env.sharding_for(self._default_batch_spec(arr))

    def _make_updater(self):
        """Per-param optimizer update math shared by every build variant:
        grads (param dtype) + states -> (new_params, new_states). One
        source with the single-chip compilers (jit.make_param_updater)."""
        from ..jit import make_param_updater

        return make_param_updater(self.optimizer, self.train_params)

    def _make_grad_fn(self, scale_in_graph=False, remat=False):
        """value_and_grad closure over the bound model; returns
        (loss f32, grads in param dtype). When scale_in_graph, the loss is
        multiplied by a traced loss-scale before differentiation. When
        remat, the forward is checkpointed so backward recomputes it
        instead of holding residuals (the accumulate-window memory
        saver)."""
        model, loss_fn = self.target, self.loss_fn
        train_params = self.train_params
        frozen = self.frozen

        from ..jit import _Binder

        def grad_of(params, frozen_arrays, batch, scale=None):
            def loss_of(param_arrays):
                ts = train_params + frozen
                with _Binder(ts) as b:
                    b.bind(list(param_arrays) + list(frozen_arrays))
                    with autograd.no_grad():
                        loss = loss_fn(model, *[Tensor(a) for a in batch])
                loss = loss.data.astype(jnp.float32)
                return loss * scale if scale_in_graph else loss

            if remat:
                loss_of = jax.checkpoint(loss_of)
            return jax.value_and_grad(loss_of)(tuple(params))

        return grad_of

    def _sharding_plan(self, batch_arrays):
        """Input/output placements shared by every build variant."""
        env = self.env
        opt = self.optimizer
        param_sh = [param_sharding(p, env) for p in self.train_params]
        state_sh = [
            {k: (self._state_sharding(p) if v.shape == p.data.shape
                 else env.replicated())
             for k, v in opt._accumulators[id(p)].items()}
            for p in self.train_params
        ]
        frozen_sh = [param_sharding(p, env) for p in self.frozen]
        if self.batch_specs is not None:
            batch_sh = [env.sharding_for(s) for s in self.batch_specs]
        else:
            batch_sh = [env.sharding_for(self._default_batch_spec(a))
                        for a in batch_arrays]
        return param_sh, state_sh, frozen_sh, batch_sh

    def _zero2_plan(self):
        """Per-grad reduce-scatter constraint specs (ZeRO-2), else None."""
        if self.zero_stage < 2:
            return None
        return [
            None if getattr(p, "dist_spec", None) is not None
            else self._state_sharding(p)
            for p in self.train_params
        ]

    def _build(self, batch_arrays):
        env = self.env
        opt = self.optimizer
        clip = opt._grad_clip
        train_params = self.train_params
        frozen = self.frozen
        updater = self._make_updater()
        grad_of = self._make_grad_fn()

        def step(params, states, frozen_arrays, lr, step_no, rngkey, *batch):
            random_mod.default_generator().set_trace_key(rngkey)
            try:
                loss_val, grads = grad_of(params, frozen_arrays, batch)
                grads = list(grads)
                if zero2_shardings is not None:
                    # ZeRO-2: constrain each grad to the optimizer-state shard
                    # spec so XLA emits a reduce-scatter (not all-reduce) and
                    # the update math runs on 1/sdp of each grad
                    grads = [g if sh is None else jax.lax.with_sharding_constraint(g, sh)
                             for g, sh in zip(grads, zero2_shardings)]
                if clip is not None:
                    grads = clip._apply_jax(grads)
                new_p, new_s = updater(params, grads, states, lr, step_no)
                return loss_val, new_p, new_s
            finally:
                random_mod.default_generator().clear_trace_key()

        zero2_shardings = self._zero2_plan()
        param_sh, state_sh, frozen_sh, batch_sh = self._sharding_plan(batch_arrays)
        repl = env.replicated()
        in_shardings = (param_sh, state_sh, frozen_sh, repl, repl, repl, *batch_sh)
        out_shardings = (repl, param_sh, state_sh)
        donate = (0, 1) if self.donate else ()
        from ..jit import persistent_cache

        return persistent_cache.cached_jit(
            step, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=donate, label="ShardedTrainStep")

    def accumulate(self, steps: int, remat: bool = False,
                   average: bool = True) -> "ShardedAccumulateStep":
        """Fused gradient accumulation over the mesh: the multi-chip twin of
        ``jit.TrainStep.accumulate`` — ``steps`` microbatches scanned inside
        ONE pjit'ed executable (fp32 carried accumulators at the grad
        placement, optional remat on the microbatch body), one optimizer
        update per call. Call with the FULL (global) batch; dim 0 must
        divide by ``steps``. Unlike ``accum_steps`` (which spreads the
        window over k calls), this is one dispatch per window."""
        if self.scaler is not None:
            raise NotImplementedError(
                "ShardedTrainStep.accumulate: fused accumulation does not "
                "compose with the in-graph GradScaler; use accum_steps for "
                "the scaler path")
        return ShardedAccumulateStep(self, steps, remat=remat,
                                     average=average)

    # -- in-graph AMP / gradient accumulation --------------------------------
    def _grad_shardings(self):
        """Placement for fp32 grad/accumulator buffers: the ZeRO-2 state shard
        when active, else the param placement."""
        env = self.env
        shs = []
        for p in self.train_params:
            if self.zero_stage >= 2 and getattr(p, "dist_spec", None) is None:
                shs.append(self._state_sharding(p))
            else:
                shs.append(param_sharding(p, env))
        return shs

    def _amp_update(self, fin, amp):
        """Dynamic loss-scale state machine, traced (reference
        python/paddle/fluid/dygraph/amp/loss_scaler.py:40 + the
        update_loss_scaling op). amp = (scale, good, bad, last_fin); the
        trailing flag records whether the LAST step's grads were finite so
        the host GradScaler._found_inf can mirror it (advisor r4)."""
        sc = self.scaler
        scale, good, bad = amp[:3]
        if not getattr(sc, "_dynamic", True):
            return (scale, good, bad, fin)
        good2 = jnp.where(fin, good + 1, 0)
        bad2 = jnp.where(fin, 0, bad + 1)
        incr = fin & (good2 >= sc._incr_every_n_steps)
        decr = (~fin) & (bad2 >= sc._decr_every_n_nan_or_inf)
        scale2 = jnp.where(incr, scale * sc._incr_ratio,
                           jnp.where(decr,
                                     jnp.maximum(scale * sc._decr_ratio, 1.0),
                                     scale))
        good3 = jnp.where(incr, 0, good2)
        bad3 = jnp.where(decr, 0, bad2)
        return (scale2, good3, bad3, fin)

    def _build_amp(self, batch_arrays, boundary):
        """One compiled variant of the scaler/accumulation step.

        boundary=False (accum only, k > 1): fwd+bwd, fold this call's grads
        into the fp32 accumulators — no optimizer math in the executable.
        boundary=True: fold, then apply the update from the window total
        (guarded by found-any-finite when a scaler is live)."""
        env = self.env
        opt = self.optimizer
        clip = opt._grad_clip
        has_scaler = self.scaler is not None
        k = self.accum_steps
        avg = self.accum_avg
        train_params = self.train_params
        updater = self._make_updater()
        grad_of = self._make_grad_fn(scale_in_graph=has_scaler)

        zero2_shardings = self._zero2_plan()

        def micro_grads(params, frozen_arrays, amp, batch):
            """Shared fwd+bwd prefix: unscaled fp32 grads + finite flag."""
            scale = amp[0]
            loss_s, grads = grad_of(params, frozen_arrays, batch,
                                    scale=scale if has_scaler else None)
            grads = [g.astype(jnp.float32) for g in grads]
            if has_scaler:
                inv = 1.0 / scale
                grads = [g * inv for g in grads]
                loss_val = loss_s * inv
            else:
                loss_val = loss_s
            if zero2_shardings is not None:
                grads = [g if sh is None else jax.lax.with_sharding_constraint(g, sh)
                         for g, sh in zip(grads, zero2_shardings)]
            if has_scaler:
                import functools

                fin = functools.reduce(
                    jnp.logical_and,
                    [jnp.all(jnp.isfinite(g)) for g in grads])
            else:
                fin = jnp.asarray(True)
            return loss_val, grads, fin

        def step_accum(params, acc, goodw, amp, frozen_arrays, rngkey, *batch):
            random_mod.default_generator().set_trace_key(rngkey)
            try:
                loss_val, grads, fin = micro_grads(params, frozen_arrays, amp,
                                                   batch)
                new_acc = [a + jnp.where(fin, g, 0.0)
                           for a, g in zip(acc, grads)]
                new_goodw = goodw + fin.astype(jnp.int32)
                amp_out = self._amp_update(fin, amp) if has_scaler else amp
                return loss_val, new_acc, new_goodw, amp_out
            finally:
                random_mod.default_generator().clear_trace_key()

        def step_apply(params, states, acc, goodw, amp, frozen_arrays, lr,
                       upd_no, rngkey, *batch):
            # k == 1 callers pass acc=() and goodw is ignored. upd_no counts
            # APPLIED updates (in-graph, so a fully-skipped scaler window
            # leaves Adam's bias-correction step where it was — matching the
            # eager scaler, which skips optimizer.step() entirely on inf)
            random_mod.default_generator().set_trace_key(rngkey)
            try:
                loss_val, grads, fin = micro_grads(params, frozen_arrays, amp,
                                                   batch)
                if k > 1:
                    total = [a + jnp.where(fin, g, 0.0)
                             for a, g in zip(acc, grads)]
                    ngood = goodw + fin.astype(jnp.int32)
                else:
                    total = grads
                    ngood = fin.astype(jnp.int32)
                step_no = (upd_no + 1).astype(jnp.int32)

                def do_update(ops):
                    params_, states_, g32 = ops
                    g32 = list(g32)
                    if avg and k > 1:
                        denom = jnp.maximum(ngood, 1).astype(jnp.float32)
                        g32 = [g / denom for g in g32]
                    if clip is not None:
                        g32 = clip._apply_jax(g32)
                    new_p, new_s = updater(list(params_), g32, list(states_),
                                           lr, step_no)
                    return tuple(new_p), tuple(new_s)

                def skip_update(ops):
                    params_, states_, _ = ops
                    return tuple(params_), tuple(states_)

                operands = (tuple(params), tuple(states), tuple(total))
                if has_scaler:
                    applied = (ngood > 0).astype(jnp.int32)
                    new_p, new_s = jax.lax.cond(ngood > 0, do_update,
                                                skip_update, operands)
                else:
                    applied = jnp.int32(1)
                    new_p, new_s = do_update(operands)
                acc_out = [jnp.zeros_like(a) for a in acc]
                goodw_out = jnp.zeros_like(goodw)
                amp_out = self._amp_update(fin, amp) if has_scaler else amp
                return loss_val, list(new_p), list(new_s), acc_out, \
                    goodw_out, amp_out, upd_no + applied
            finally:
                random_mod.default_generator().clear_trace_key()

        param_sh, state_sh, frozen_sh, batch_sh = self._sharding_plan(batch_arrays)
        acc_sh = self._grad_shardings() if k > 1 else []
        repl = env.replicated()
        amp_sh = (repl, repl, repl, repl)
        if not boundary:
            in_sh = (param_sh, acc_sh, repl, amp_sh, frozen_sh, repl, *batch_sh)
            out_sh = (repl, acc_sh, repl, amp_sh)
            return jax.jit(step_accum, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=(1,) if self.donate else ())
        in_sh = (param_sh, state_sh, acc_sh, repl, amp_sh, frozen_sh, repl,
                 repl, repl, *batch_sh)
        out_sh = (repl, param_sh, state_sh, acc_sh, repl, amp_sh, repl)
        donate = (0, 1, 2) if self.donate else ()
        return jax.jit(step_apply, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)

    def _init_amp_state(self):
        repl = self.env.replicated()
        sc = self.scaler
        scale = float(getattr(sc, "_scale", 1.0)) if sc is not None else 1.0
        self._amp_state = (
            jax.device_put(jnp.float32(scale), repl),
            jax.device_put(jnp.int32(int(getattr(sc, "_good_steps", 0) or 0)
                                     if sc is not None else 0), repl),
            jax.device_put(jnp.int32(int(getattr(sc, "_bad_steps", 0) or 0)
                                     if sc is not None else 0), repl),
            jax.device_put(jnp.bool_(not getattr(sc, "_found_inf", False)
                                     if sc is not None else True), repl))
        self._upd_no = jax.device_put(
            jnp.int32(int(self.optimizer._global_step)), repl)
        self._goodw = jax.device_put(jnp.int32(0), repl)
        self._win_count = 0
        self._host_versions = self._host_state_version()
        if self.accum_steps > 1:
            self._acc = [
                jax.device_put(jnp.zeros(p.shape, jnp.float32), sh)
                for p, sh in zip(self.train_params, self._grad_shardings())]
        else:
            self._acc = []

    def _host_state_version(self):
        return (int(getattr(self.optimizer, "_state_version", 0)),
                int(getattr(self.scaler, "_state_version", 0) or 0)
                if self.scaler is not None else 0)

    def _call_amp(self, arrays):
        opt = self.optimizer
        k = self.accum_steps
        if self._jitted is None:
            accum = self._build_amp(arrays, boundary=False) if k > 1 else None
            self._jitted = (accum, self._build_amp(arrays, boundary=True))
            self._init_amp_state()
        elif self._host_versions != self._host_state_version():
            # optimizer.set_state_dict / scaler.load_state_dict happened
            # since build: re-seed the in-graph state from the restored host
            # values (discards any partial accumulation window)
            self._init_amp_state()
        jit_accum, jit_apply = self._jitted
        params = [p.data for p in self.train_params]
        frozen_arrays = [t.data for t in self.frozen]
        boundary = (self._win_count + 1) % k == 0
        if not boundary:
            loss, self._acc, self._goodw, self._amp_state = jit_accum(
                params, self._acc, self._goodw, self._amp_state,
                frozen_arrays, random_mod.next_key(), *arrays)
            self._win_count += 1
            self._sync_scaler()
            return Tensor(loss)
        states = [opt._accumulators[id(p)] for p in self.train_params]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        (loss, new_p, new_s, self._acc, self._goodw,
         self._amp_state, self._upd_no) = jit_apply(
            params, states, self._acc, self._goodw, self._amp_state,
            frozen_arrays, lr, self._upd_no, random_mod.next_key(), *arrays)
        for p, a in zip(self.train_params, new_p):
            p.data = a
        for p, s in zip(self.train_params, new_s):
            opt._accumulators[id(p)] = s
        # the authoritative applied-update count lives in-graph (a scaler may
        # have skipped the window); hand the lazy scalar to the optimizer —
        # int() contexts (state_dict, resume) materialize it without a
        # per-step host sync here
        opt._global_step = self._upd_no
        self._win_count = 0
        self._sync_scaler()
        return Tensor(loss)

    def _sync_scaler(self):
        """Mirror the in-graph scale state onto the host GradScaler object
        (lazy jax scalars, no sync) so state_dict()/checkpointing and any
        later eager fall-through see the live scale."""
        sc = self.scaler
        if sc is None or self._amp_state is None:
            return
        sc._scale, sc._good_steps, sc._bad_steps = self._amp_state[:3]
        # found-inf mirrors the last step's finite flag LAZILY (a jax bool;
        # truthiness materializes it) — code inspecting scaler._found_inf
        # after a compiled train_batch sees live state, not the eager-era
        # stale False (advisor r4)
        sc._found_inf = self._amp_state[3] == False  # noqa: E712 (lazy not)

    def discard_accum_window(self):
        """Drop the in-flight gradient-merge window (compiled-path twin of
        HybridParallelOptimizer.discard_merge_window): zero the fp32
        accumulators and rewind to the window start."""
        if self._acc:
            self._acc = [jnp.zeros_like(a) for a in self._acc]
        if self._goodw is not None:
            self._goodw = jnp.zeros_like(self._goodw)
        self._win_count = 0

    def amp_state(self):
        """Materialize the in-graph scaler state (host sync): dict with
        loss_scale / good_steps / bad_steps / updates, or None w/o scaler."""
        if self.scaler is None or self._amp_state is None:
            return None
        scale, good, bad, fin = self._amp_state
        return {"loss_scale": float(scale), "good_steps": int(good),
                "bad_steps": int(bad), "found_inf": not bool(fin),
                "updates": int(self._upd_no)}

    def _build_offload(self, batch_arrays):
        """Mesh fwd+bwd executable of the offload path (grads at their
        param placements, ZeRO-2 reduce-scatter constraint honored); the
        host update side lives in ``_ensure_stream_update``."""
        env = self.env
        model, loss_fn = self.target, self.loss_fn
        train_params = self.train_params
        frozen = self.frozen
        zero2_shardings = self._zero2_plan()

        from ..jit import _Binder

        def fwd_bwd(params, frozen_arrays, rngkey, *batch):
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
                if zero2_shardings is not None:
                    # os_g: constrain grads to the state-shard layout so XLA
                    # emits a reduce-scatter, not an all-reduce (the host
                    # download gathers either way; ICI traffic halves)
                    grads = tuple(
                        g if sh is None
                        else jax.lax.with_sharding_constraint(g, sh)
                        for g, sh in zip(grads, zero2_shardings))
                return loss_val, grads
            finally:
                random_mod.default_generator().clear_trace_key()

        param_sh = [param_sharding(p, env) for p in train_params]
        frozen_sh = [param_sharding(p, env) for p in frozen]
        if self.batch_specs is not None:
            batch_sh = [env.sharding_for(s) for s in self.batch_specs]
        else:
            batch_sh = [env.sharding_for(self._default_batch_spec(a)) for a in batch_arrays]
        repl = env.replicated()
        from ..jit import persistent_cache

        return persistent_cache.cached_jit(
            fwd_bwd, in_shardings=(param_sh, frozen_sh, repl, *batch_sh),
            out_shardings=(repl, tuple(param_sh)),
            label="ShardedTrainStep.offload_fwd",
            extra_meta=("offload_fwd", self.accum_steps))

    def _ensure_stream_update(self):
        """Build the streaming update side once: stream groups (sized by the
        group_sharded_parallel segment_size / buffer_max_size knobs), one
        donated host update executable per group, the device-side clip
        (global-norm clip MUST see the full grad set — it cannot run per
        group), and the transfer lane. Batch-shape independent, so the
        fused accumulate step shares it."""
        if self._stream is not None:
            return self._stream
        opt = self.optimizer
        from ..jit.offload_stream import StreamLane, plan_stream_groups
        from ..optimizer.optimizer import make_master_update

        groups = plan_stream_groups(
            [p.size * 4 for p in self.train_params],  # fp32 master bytes
            self._stream_segment, self._stream_bufmax)
        from ..jit import persistent_cache

        dtypes = [p.data.dtype for p in self.train_params]
        jit_upds = []
        for gi, idx in enumerate(groups):
            upd = make_master_update(
                opt, [self.train_params[i] for i in idx],
                [dtypes[i] for i in idx], with_clip=False)
            jit_upds.append(persistent_cache.cached_jit(
                upd, donate_argnums=(0, 2),  # cpu via placement
                label="ShardedTrainStep.offload_update",
                extra_meta=("offload_upd", gi)))
        clip = opt._grad_clip
        jit_clip = None
        if clip is not None:
            def clip_all(grads):
                return clip._apply_jax([g.astype(jnp.float32) for g in grads])

            jit_clip = jax.jit(clip_all)
        lane = StreamLane(overlap=self._stream_overlap)
        self._param_sh = [param_sharding(p, self.env)
                          for p in self.train_params]
        self._stream = (groups, jit_upds, jit_clip, lane)
        return self._stream

    def _stream_update(self, grads, tl):
        """Latency-hiding group walk: while group *i*'s host update
        computes, the lane is downloading group *i+1*'s grads and uploading
        group *i-1*'s fresh params — steady-state cost approaches
        max(update compute, transfer) instead of their sum. Consumer-side
        blocking is charged to the ``stream_wait`` timeline phase."""
        opt = self.optimizer
        groups, jit_upds, jit_clip, lane = self._ensure_stream_update()
        if jit_clip is not None:
            grads = jit_clip(list(grads))
        cpu = self._cpu
        lr = jax.device_put(jnp.asarray(opt.get_lr(), jnp.float32), cpu)
        step_no = jax.device_put(
            jnp.asarray(opt._global_step + 1, jnp.int32), cpu)
        downs: dict = {}
        ups: list = [None] * len(groups)

        def submit_down(gi):
            downs[gi] = lane.submit(
                "d2h", [grads[i] for i in groups[gi]], cpu, tag=gi)

        submit_down(0)
        if len(groups) > 1:
            submit_down(1)
        for gi, idx in enumerate(groups):
            with tl.phase("stream_wait"):
                g_host = downs.pop(gi).wait()
            if gi + 2 < len(groups):
                submit_down(gi + 2)
            master = [self._master[i] for i in idx]
            states = [opt._accumulators[id(self.train_params[i])]
                      for i in idx]
            new_m, new_s, new_p = jit_upds[gi](master, g_host, states,
                                               lr, step_no)
            for i, m, s in zip(idx, new_m, new_s):
                self._master[i] = m
                opt._accumulators[id(self.train_params[i])] = s
            ups[gi] = lane.submit(
                "h2d", new_p, [self._param_sh[i] for i in idx], tag=gi)
        # drain: with the cross-step fill enabled, take each upload as
        # soon as it is ISSUED (jax futures) — the next step's fwd+bwd
        # dispatch consumes them and the runtime sequences the landing,
        # so the host reaches the next group-0 grad download while the
        # device is still inside fwd+bwd. wait() (the serialized twin and
        # the kill-switch path) blocks until the bytes have landed.
        eager = self._stream_overlap and getattr(self, "_stream_eager", False)
        new_params = [None] * len(self.train_params)
        for gi, idx in enumerate(groups):
            with tl.phase("stream_wait"):
                fresh = ups[gi].wait_dispatched() if eager \
                    else ups[gi].wait()
            for i, a in zip(idx, fresh):
                new_params[i] = a
        return new_params

    def _call_offload(self, arrays, tl):
        from ..jit import _memobs

        opt = self.optimizer
        mo = _memobs()
        cold = self._jitted is None
        if cold:
            self._jitted = self._build_offload(arrays)
        jit_fwd = self._jitted
        params = [p.data for p in self.train_params]
        frozen_arrays = [t.data for t in self.frozen]
        with tl.phase("compile" if cold else "host_dispatch"):
            with mo.oom_guard("sharded_train_step",
                              label="ShardedTrainStep[offload]",
                              step=opt._global_step):
                loss, grads = jit_fwd(params, frozen_arrays,
                                      random_mod.next_key(), *arrays)
                new_params = self._stream_update(grads, tl)
        del grads
        for p, a in zip(self.train_params, new_params):
            p.data = a
        opt._global_step += 1
        if cold:
            mo.maybe_record_drift(self, arrays, "ShardedTrainStep[offload]",
                                  jit_fwd)
        return Tensor(loss)

    def stream_stats(self):
        """Per-step-object lane counters (bytes up/down, transfer/stall ms,
        overlap_efficiency) — None before the first offload step. The
        process-wide view lives in the ``offload_stream`` observability
        family."""
        if not self.offload or self._stream is None:
            return None
        return self._stream[3].stats()

    def stream_schedule(self):
        """(kind, group index) lane submissions in order — the group
        schedule the ordering tests pin. None before the first step."""
        if not self.offload or self._stream is None:
            return None
        return list(self._stream[3].events)

    def _ensure_built(self, arrays):
        if self._jitted is None:
            from ..jit import (_audit_instance_label, _maybe_audit, _obs,
                               remat_fit)

            _obs()[1].inc(("sharded_train_step", "build"))
            # a build reads the batch's ranks alone: hold no batch for it
            like = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
            self._jitted = _maybe_audit(
                _audit_instance_label("ShardedTrainStep"),
                remat_fit.fitted(lambda: self._build(like),
                                 "ShardedTrainStep"))

    def lower(self, *batch):
        """AOT-lower the plain sharded step for this batch's shapes without
        running it (see ``jit.TrainStep.lower``): the compiled text shows
        which collectives GSPMD put in."""
        if self.offload or self.scaler is not None or self.accum_steps > 1:
            raise NotImplementedError(
                "lower() covers the plain ShardedTrainStep executable")
        from ..jit import _batch_arrays, lowerable, step_args

        arrays = _batch_arrays(batch)
        self._ensure_built(arrays)
        return lowerable(self._jitted).lower(
            *step_args(self, arrays, jax.random.key(0)))

    def collectives(self, *batch):
        """Does ``dp`` divide my step, and which of its collectives can
        hide? The compiled step's collectives for this batch's shapes, by
        mesh axis, kind and shape with their ``count`` and how many of
        them are ``async`` start / done pairs — the rest are synchronous
        (``mesh.compiled_collectives``: a TPU reduce-scatter fusion reads
        as ``reduce-scatter``; on the CPU the kinds alone, ``async`` 0).
        It compiles; nothing runs, and the step's own path never calls
        it."""
        from .mesh import compiled_collectives

        return compiled_collectives(
            self.lower(*batch).compile().as_text(), self.env.mesh)

    def __call__(self, *batch):
        from ..jit import _batch_arrays, _obs, step_args

        opt = self.optimizer
        arrays = _batch_arrays(batch)
        tl, tc = _obs()
        if self.offload:
            with tl.step():
                return self._call_offload(arrays, tl)
        if self.scaler is not None or self.accum_steps > 1:
            with tl.step(), tl.phase("host_dispatch"):
                return self._call_amp(arrays)
        with tl.step():
            cold = self._jitted is None
            self._ensure_built(arrays)
            (params, states, frozen_arrays, lr, step_no,
             key) = step_args(self, (), random_mod.next_key())
            from ..jit import _memobs

            mo = _memobs()
            drift_args = mo.struct_args(
                (params, states, frozen_arrays, lr, step_no, key)
                + tuple(arrays)) if cold and mo.drift_enabled() else None
            with tl.phase("compile" if cold else "host_dispatch"):
                with mo.oom_guard("sharded_train_step",
                                  label="ShardedTrainStep",
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
                mo.maybe_record_drift(self, arrays, "ShardedTrainStep",
                                      self._jitted, drift_args)
        return Tensor(loss)


class ShardedAccumulateStep:
    """Fused gradient-accumulation pjit (``ShardedTrainStep.accumulate``).

    One executable over the mesh: ``lax.scan`` over ``steps`` microbatches
    (each sliced from the global batch, so the dp sharding of the inputs
    carries straight into every microbatch), fp32 grad accumulators carried
    at the grad placement, a single optimizer update at the end. Params and
    optimizer state are donated. Duck-types the TrainStep capture surface
    so ``analysis.capture`` / the HBM estimator model it.
    """

    def __init__(self, step: ShardedTrainStep, steps: int,
                 remat: bool = False, average: bool = True):
        if int(steps) < 1:
            raise ValueError(f"accumulate: steps must be >= 1, got {steps}")
        self._step = step
        self.env = step.env
        self.steps = int(steps)
        self.remat = bool(remat)
        self.average = bool(average)
        self.optimizer = step.optimizer
        self.donate = step.donate
        self.train_params = step.train_params
        self.frozen = step.frozen
        self._jitted = None

    def _build_offload(self, batch_arrays):
        """Offload twin: the same fused microbatch scan, but the executable
        returns the window's fp32 grads instead of applying the update —
        the streaming executor (outer._stream_update) walks the host update
        per stream group, exactly like the plain offload step."""
        outer = self._step
        k = self.steps
        scale = 1.0 / k if self.average else 1.0
        grad_of = outer._make_grad_fn(remat=self.remat)
        zero2_shardings = outer._zero2_plan()

        def step(params, frozen_arrays, rngkey, *batch):
            micro = tuple(
                a.reshape((k, a.shape[0] // k) + a.shape[1:]) for a in batch)
            keys = jax.random.split(rngkey, k)

            def body(acc, xs):
                key_i, mb = xs[0], xs[1:]
                random_mod.default_generator().set_trace_key(key_i)
                try:
                    loss_i, grads = grad_of(tuple(params), frozen_arrays, mb)
                finally:
                    random_mod.default_generator().clear_trace_key()
                grads = [g.astype(jnp.float32) * scale for g in grads]
                if zero2_shardings is not None:
                    grads = [g if sh is None
                             else jax.lax.with_sharding_constraint(g, sh)
                             for g, sh in zip(grads, zero2_shardings)]
                acc2 = [a + g for a, g in zip(acc, grads)]
                return acc2, loss_i

            acc0 = [jnp.zeros(p.shape, jnp.float32)
                    for p in self.train_params]
            accT, losses = jax.lax.scan(body, acc0, (keys,) + micro)
            return jnp.mean(losses), tuple(accT)

        param_sh, _state_sh, frozen_sh, batch_sh = \
            outer._sharding_plan(batch_arrays)
        repl = self.env.replicated()
        in_sh = (param_sh, frozen_sh, repl, *batch_sh)
        out_sh = (repl, tuple(param_sh))
        from ..jit import persistent_cache

        return persistent_cache.cached_jit(
            step, in_shardings=in_sh, out_shardings=out_sh,
            label=f"ShardedTrainStep.accumulate({k})[offload]",
            extra_meta=("offload_accum", k, self.average, self.remat))

    def _call_offload(self, arrays, tl):
        from ..jit import _memobs

        outer = self._step
        opt = self.optimizer
        mo = _memobs()
        cold = self._jitted is None
        if cold:
            self._jitted = self._build_offload(arrays)
        params = [p.data for p in self.train_params]
        frozen_arrays = [t.data for t in self.frozen]
        with tl.phase("compile" if cold else "host_dispatch"):
            with mo.oom_guard("sharded_accumulate",
                              label=f"ShardedTrainStep.accumulate"
                                    f"({self.steps})[offload]",
                              step=opt._global_step):
                loss, grads = self._jitted(params, frozen_arrays,
                                           random_mod.next_key(), *arrays)
                new_params = outer._stream_update(grads, tl)
        del grads
        for p, a in zip(self.train_params, new_params):
            p.data = a
        opt._global_step += 1
        return Tensor(loss)

    def _build(self, batch_arrays):
        outer = self._step
        opt = self.optimizer
        clip = opt._grad_clip
        k = self.steps
        scale = 1.0 / k if self.average else 1.0
        updater = outer._make_updater()
        grad_of = outer._make_grad_fn(remat=self.remat)
        zero2_shardings = outer._zero2_plan()

        def step(params, states, frozen_arrays, lr, step_no, rngkey, *batch):
            micro = tuple(
                a.reshape((k, a.shape[0] // k) + a.shape[1:]) for a in batch)
            keys = jax.random.split(rngkey, k)

            def body(acc, xs):
                key_i, mb = xs[0], xs[1:]
                random_mod.default_generator().set_trace_key(key_i)
                try:
                    loss_i, grads = grad_of(tuple(params), frozen_arrays, mb)
                finally:
                    random_mod.default_generator().clear_trace_key()
                grads = [g.astype(jnp.float32) * scale for g in grads]
                if zero2_shardings is not None:
                    grads = [g if sh is None
                             else jax.lax.with_sharding_constraint(g, sh)
                             for g, sh in zip(grads, zero2_shardings)]
                acc2 = [a + g for a, g in zip(acc, grads)]
                return acc2, loss_i

            acc0 = [jnp.zeros(p.shape, jnp.float32)
                    for p in self.train_params]
            accT, losses = jax.lax.scan(body, acc0, (keys,) + micro)
            grads = list(accT)
            if clip is not None:
                grads = clip._apply_jax(grads)
            new_p, new_s = updater(params, grads, states, lr, step_no)
            return jnp.mean(losses), new_p, new_s

        param_sh, state_sh, frozen_sh, batch_sh = \
            outer._sharding_plan(batch_arrays)
        repl = self.env.replicated()
        in_sh = (param_sh, state_sh, frozen_sh, repl, repl, repl, *batch_sh)
        out_sh = (repl, param_sh, state_sh)
        donate = (0, 1) if self.donate else ()
        from ..jit import persistent_cache

        return persistent_cache.cached_jit(
            step, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=donate,
            label=f"ShardedTrainStep.accumulate({k})",
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
        from ..jit import _obs

        tl, tc = _obs()
        if self._step.offload:
            with tl.step():
                return self._call_offload(arrays, tl)
        with tl.step():
            cold = self._jitted is None
            if cold:
                from ..jit import _audit_instance_label, _maybe_audit

                tc.inc(("sharded_accumulate", "build"))
                self._jitted = _maybe_audit(
                    _audit_instance_label(
                        f"ShardedTrainStep.accumulate({self.steps})"),
                    self._build(arrays))
            params = [p.data for p in self.train_params]
            states = [opt._accumulators[id(p)] for p in self.train_params]
            frozen_arrays = [t.data for t in self.frozen]
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_no = jnp.asarray(opt._global_step + 1, jnp.int32)
            key = random_mod.next_key()
            from ..jit import _memobs

            mo = _memobs()
            drift_args = mo.struct_args(
                (params, states, frozen_arrays, lr, step_no, key)
                + tuple(arrays)) if cold and mo.drift_enabled() else None
            label = f"ShardedTrainStep.accumulate({self.steps})"
            with tl.phase("compile" if cold else "host_dispatch"):
                with mo.oom_guard("sharded_accumulate", label=label,
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

    def batch_sharding(self, arr) -> NamedSharding:
        """Prefetch placement hook (see ShardedTrainStep.batch_sharding)."""
        return self._step.batch_sharding(arr)
