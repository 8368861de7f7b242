"""hapi.Model: fit/evaluate/predict loops (reference: python/paddle/hapi/model.py:1014).

TPU-native stance: there is exactly one execution adapter — the eager dygraph
path whose every op is a cached jitted XLA executable — so the reference's
StaticGraphAdapter/DynamicGraphAdapter split (model.py:252,667) collapses into
Model itself. Distributed fit() composes with paddle_tpu.distributed the same
way hand loops do (DistributedBatchSampler + GSPMD-annotated layers).
"""
from __future__ import annotations

import os
import time as _time
from contextlib import nullcontext
from typing import List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..framework import io as fio
from ..metric import Metric
from .callbacks import config_callbacks


def to_list(value):
    if value is None:
        return []
    if isinstance(value, (list, tuple)):
        return list(value)
    return [value]


def _as_tensor(x):
    if isinstance(x, Tensor):
        return x
    return Tensor(np.asarray(x))


def _scalar(t):
    return float(np.asarray(t.data if isinstance(t, Tensor) else t))


def _timeline():
    from ..observability.timeline import timeline

    return timeline()


def _span(name):
    from ..observability.trace import span

    return span(name)


def _resilience():
    from ..distributed import resilience

    return resilience


def _nan_skip_exc():
    from ..core.tensor import NanStepSkipped

    return NanStepSkipped


def _oom_guard(site, **ids):
    """Memory-truth bracket (observability.memory): the deterministic
    ``oom`` fault site (``PT_FAULTS="oom@step=N"``) plus forensics — a
    RESOURCE_EXHAUSTED inside dumps the flight bundle with the memory
    report BEFORE the crash unwinds the loop."""
    from ..observability.memory import oom_guard

    return oom_guard(site, **ids)


def _auto_device_prefetch(loader, device_sharding):
    """fit(prefetch_to_device=None) default: a DistributedBatchSampler-
    driven DataLoader on an active multi-device mesh prefetches to the
    mesh's data placement automatically (the PR-3 follow-up) — the batch
    lands laid out for the sharded step, and the timeline's ``data_wait``
    shows the overlap win. Returns (enable, device_sharding)."""
    from ..io import DataLoader, DistributedBatchSampler

    if not isinstance(loader, DataLoader) or loader.prefetch_to_device:
        return False, device_sharding  # loader already prefetches (or n/a)
    if not isinstance(getattr(loader, "batch_sampler", None),
                      DistributedBatchSampler):
        return False, device_sharding
    try:
        from ..distributed.mesh import get_mesh_env
        from ..distributed.parallel import default_batch_sharding

        env = get_mesh_env()
        if env is None or env.nranks <= 1:
            return False, device_sharding
        if device_sharding is None:
            device_sharding = default_batch_sharding(env)
    except Exception:
        return False, device_sharding
    return True, device_sharding


class Model:
    """A Layer + optimizer + loss + metrics bundle with training loops.

    Reference: python/paddle/hapi/model.py:1014 (class Model). Same public
    surface: prepare / fit / evaluate / predict / train_batch / eval_batch /
    predict_batch / save / load / parameters / summary.
    """

    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = to_list(inputs)
        self._labels = to_list(labels)
        self._loss = None
        self._metrics = []
        self._optimizer = None
        self.mode = "train"
        self.stop_training = False

    # -- single-batch APIs ---------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        if loss is not None and not callable(loss):
            raise TypeError("loss must be a callable (Layer or function)")
        self._loss = loss
        metrics = metrics or []
        for m in to_list(metrics):
            if not isinstance(m, Metric):
                raise TypeError(f"metric {m} is not a paddle_tpu.metric.Metric")
        self._metrics = to_list(metrics)
        self._amp_configs = amp_configs
        return self

    def _compute_loss(self, outputs, labels):
        losses = to_list(self._loss(*(to_list(outputs) + labels)))
        return losses

    def _sparse_tables(self):
        """ShardedEmbeddingTables behind the network's sparse Embedding
        layers (cached per network — the layer-tree walk is not a
        per-step cost)."""
        cached = getattr(self, "_sparse_tables_cache", None)
        if cached is None or cached[0] is not self.network:
            from ..sparse.embedding import sparse_tables

            cached = (self.network, sparse_tables(self.network))
            self._sparse_tables_cache = cached
        return cached[1]

    def train_batch(self, inputs, labels=None, update=True, _loss_scale=1.0):
        tl = _timeline()
        self.network.train()
        self.mode = "train"
        inputs = [_as_tensor(x) for x in to_list(inputs)]
        labels = [_as_tensor(x) for x in to_list(labels)]
        # StepTimeline phases: dispatch (fwd+bwd+update enqueue, async under
        # jax) vs the host blocking on device results (loss/metric readback)
        with tl.phase("host_dispatch"):
            outputs = self.network(*inputs)
            losses = self._compute_loss(outputs, labels)
            total = losses[0]
            for extra in losses[1:]:
                total = total + extra
            if _loss_scale != 1.0:  # gradient accumulation averages micro-batches
                (total * _loss_scale).backward()
            else:
                total.backward()
            # sparse embedding tables: harvest the (unique_ids, rows)
            # gradients every micro-step (the leaves are per-forward);
            # the host row update applies at the SAME boundary as the
            # dense optimizer step, so accumulate(k) composes
            for t in self._sparse_tables():
                t.flush(update=update)
            if update and self._optimizer is not None:
                self._optimizer.step()
                self._optimizer.clear_grad()
        # host BLOCKING on device results (loss/metric readback) — host
        # time, not device time; XPlane correlation owns device_compute_us
        with tl.phase("device_block"):
            metrics = []
            for m in self._metrics:
                metric_outs = m.compute(*(to_list(outputs) + labels))
                metrics.append(m.update(*[np.asarray(
                    t.data if isinstance(t, Tensor) else t) for t in to_list(metric_outs)]))
            loss_vals = [_scalar(l) for l in losses]
        if metrics:
            return loss_vals, metrics[0] if len(metrics) == 1 else metrics
        return loss_vals

    def _check_nan_step_fault(self, gstep: int) -> None:
        """``nan_step`` fault site: a scripted NaN-producing step at an
        exact global step index (``PT_FAULTS="nan_step@step=5"``). Fires
        as ``NanStepSkipped`` when FLAGS_check_nan_inf_action='skip' (the
        loop drops the step and continues); as a RuntimeError otherwise —
        the same two outcomes a REAL non-finite step has under the per-op
        guard."""
        from ..distributed.resilience.faults import injector

        if not injector().peek("nan_step", step=gstep):
            return
        from ..framework import flags as _flags

        msg = f"injected nan_step at step {gstep}"
        if _flags.flag("check_nan_inf_action") == "skip":
            raise _nan_skip_exc()(msg)
        raise RuntimeError(msg)

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        self.mode = "eval"
        from ..core import no_grad

        inputs = [_as_tensor(x) for x in to_list(inputs)]
        labels = [_as_tensor(x) for x in to_list(labels)]
        with no_grad():
            outputs = self.network(*inputs)
            loss_vals = []
            if self._loss is not None:
                loss_vals = [_scalar(l) for l in self._compute_loss(outputs, labels)]
        metrics = []
        for m in self._metrics:
            metric_outs = m.compute(*(to_list(outputs) + labels))
            metrics.append(m.update(*[np.asarray(
                t.data if isinstance(t, Tensor) else t) for t in to_list(metric_outs)]))
        if metrics:
            return loss_vals, metrics[0] if len(metrics) == 1 else metrics
        return loss_vals

    def predict_batch(self, inputs):
        self.network.eval()
        self.mode = "test"
        from ..core import no_grad

        inputs = [_as_tensor(x) for x in to_list(inputs)]
        with no_grad():
            outputs = self.network(*inputs)
        return [np.asarray(o.data) for o in to_list(outputs)]

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    # -- checkpoint ----------------------------------------------------------
    def save(self, path, training=True):
        """Save `<path>.pdparams` (+ `.pdopt` when training). For deployment
        (training=False) export the traced program via paddle_tpu.jit.save.

        Sparse embedding tables are NOT in ``state_dict()`` (their
        canonical rows are host-resident, not Parameters): they save
        alongside as ``<path>.sparse.<table>.npz`` so a plain ``save``
        never silently drops learned embeddings; ``load`` restores
        them."""
        if training:
            for t in self._sparse_tables():
                try:
                    t.save(f"{path}.sparse.{t.name}")
                except NotImplementedError:
                    import warnings

                    warnings.warn(
                        f"Model.save: sparse table {t.name!r} is not "
                        f"LocalShards-backed — its rows are NOT in this "
                        f"checkpoint (a PsShardSource table's authority "
                        f"is the server gang)", RuntimeWarning,
                        stacklevel=2)
        if not training:
            from .. import jit

            jit.save(self.network, path, input_spec=self._inputs or None)
            return
        dirname = os.path.dirname(path)
        if dirname:
            os.makedirs(dirname, exist_ok=True)
        fio.save(self.network.state_dict(), path + ".pdparams")
        if self._optimizer is not None:
            fio.save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        param_state = fio.load(path + ".pdparams")
        missing, unexpected = self.network.set_state_dict(param_state)
        if not skip_mismatch and (missing or unexpected):
            raise ValueError(
                f"state dict mismatch: missing keys {missing}, "
                f"unexpected keys {unexpected} (pass skip_mismatch=True to ignore)")
        if not reset_optimizer and self._optimizer is not None \
                and os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(fio.load(path + ".pdopt"))
        for t in self._sparse_tables():
            sp = f"{path}.sparse.{t.name}.npz"
            if os.path.exists(sp):
                t.load(sp)
            else:
                # never silent: a renamed/auto-numbered table would
                # otherwise keep its fresh random rows after a "load"
                import warnings

                warnings.warn(
                    f"Model.load: no sparse-table checkpoint at {sp!r} — "
                    f"table {t.name!r} keeps its current rows (tables "
                    f"are matched by NAME; give tables stable name= "
                    f"values)", RuntimeWarning, stacklevel=2)
        return self

    # -- loops ---------------------------------------------------------------
    def _make_loader(self, data, batch_size, shuffle, num_workers, drop_last=False):
        from ..io import DataLoader, Dataset

        if data is None:
            return None
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last)
        return data  # any iterable of batches

    def _split_batch(self, batch):
        batch = batch if isinstance(batch, (list, tuple)) else [batch]
        if (self._loss is not None or self._metrics) and len(batch) > 1:
            # convention: last element(s) are labels (reference model.py:1986)
            n_labels = max(1, len(self._labels)) if self._labels else 1
            return list(batch[:-n_labels]), list(batch[-n_labels:])
        return list(batch), []

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None,
            prefetch_to_device=None, device_sharding=None,
            checkpoint_every=None, checkpoint_dir="checkpoints",
            checkpoint_keep=3, resume=False):
        """``checkpoint_every=N`` turns on the fault-tolerant runtime
        (``distributed.resilience``): every N train steps an
        ``AsyncCheckpointer`` snapshots params/optimizer/rng and commits in
        the background (save time hides behind the next steps' compute);
        SIGTERM is trapped and, at the next step boundary, drained into a
        final synchronous commit before the loop stops — a later
        ``fit(..., resume=True)`` continues from exactly that step, on
        whatever device count the relaunch has. ``resume=True`` restores
        the newest verified checkpoint under ``checkpoint_dir`` (epoch,
        step-in-epoch, rng and optimizer state included) and fast-forwards
        the loader to the first unseen batch. ``resume`` also accepts a
        PATH: restore from that directory while new saves keep landing in
        ``checkpoint_dir`` — the elastic fleet uses this to resume every
        rank from the fleet-wide newest commit after a membership change
        (each rank checkpoints into its own dir)."""
        assert train_data is not None, "train_data must be given"
        try:
            # flight recorder: every trained step lands in the bounded
            # ring; anomalies (regression/stall/fault burst), SIGQUIT and
            # preemption auto-dump a pd_dump diagnostic bundle. Ring-append
            # cost per step; must never block training.
            from ..observability.trace import flight_recorder

            flight_recorder()
            # memory truth: per-step watermark stamps into the monitor's
            # history (and, via the recorder's ring, into every bundle)
            from ..observability.memory import memory_monitor

            memory_monitor()
        except Exception:
            pass
        loader = self._make_loader(train_data, batch_size, shuffle, num_workers,
                                   drop_last=drop_last)
        eval_loader = self._make_loader(eval_data, batch_size, False, num_workers)
        auto_prefetch = False
        if prefetch_to_device is None:
            # default = auto: DistributedBatchSampler-driven loaders on an
            # active mesh prefetch to the mesh data placement
            prefetch_to_device, device_sharding = _auto_device_prefetch(
                loader, device_sharding)
            auto_prefetch = prefetch_to_device
        if prefetch_to_device:
            # io.prefetch: a background thread device_puts batch N+1 while
            # batch N trains, so the step never waits on the host transfer.
            # device_sharding: Sharding or leaf->sharding callable (e.g.
            # ShardedTrainStep.batch_sharding) for mesh-placed batches.
            from ..io import DevicePrefetcher

            loader = DevicePrefetcher(loader, sharding=device_sharding)
            # the auto decision was made on the TRAIN loader only — an eval
            # loader with its own sampler/batching keeps its old behavior
            # unless the caller opted in explicitly
            if eval_loader is not None and not auto_prefetch:
                eval_loader = DevicePrefetcher(eval_loader,
                                               sharding=device_sharding)
        steps = len(loader) if hasattr(loader, "__len__") else None
        metric_names = ["loss"] + [n for m in self._metrics for n in to_list(m.name())]
        cbks = config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps, log_freq=log_freq,
            save_freq=save_freq, save_dir=save_dir, verbose=verbose,
            metrics=metric_names)
        ckpt_ctx = None
        start_epoch = 0
        if checkpoint_every is not None:
            rz = _resilience()
            ck = rz.AsyncCheckpointer(checkpoint_dir, model=self.network,
                                      optimizer=self._optimizer,
                                      keep=checkpoint_keep, name="fit")
            rz.install_preemption_handler()
            ckpt_ctx = {"ck": ck, "every": max(int(checkpoint_every), 1),
                        "global_step": 0, "skip_steps": 0, "preempted": False}
            if resume:
                if isinstance(resume, str):
                    # resume FROM another root (the fleet's authoritative
                    # dir) while saving INTO checkpoint_dir
                    meta = rz.resume(resume, model=self.network,
                                     optimizer=self._optimizer)
                else:
                    meta = ck.resume()
                if meta is not None:
                    start_epoch = int(meta.get("epoch") or 0)
                    ckpt_ctx["global_step"] = int(meta["step"]) + 1
                    ckpt_ctx["last_save"] = int(meta["step"])
                    # fast-forward past the batches the saved step consumed
                    sie = meta.get("extra", {}).get("step_in_epoch")
                    if sie is not None:
                        ckpt_ctx["skip_steps"] = int(sie) + 1
                    # the rng state the interrupted EPOCH began with: a
                    # shuffling sampler redraws its permutation from the
                    # global generator at iter() time, so the resumed epoch
                    # must replay the draw from this state (the restored
                    # mid-step rng would yield a different batch order)
                    ckpt_ctx["resume_epoch_rng"] = \
                        meta.get("extra", {}).get("epoch_rng")
        self.stop_training = False
        cbks.on_begin("train")
        try:
            for epoch in range(start_epoch, epochs):
                if self.stop_training:
                    break
                cbks.on_epoch_begin(epoch)
                if ckpt_ctx is not None:
                    ckpt_ctx["epoch"] = epoch
                    if ckpt_ctx.get("resume_epoch_rng") is not None:
                        # resumed epoch: saves must carry the ORIGINAL
                        # epoch-begin rng, not the mid-step restored state
                        ckpt_ctx["epoch_rng"] = ckpt_ctx["resume_epoch_rng"]
                    else:
                        from ..framework import random as _random_mod

                        ckpt_ctx["epoch_rng"] = [
                            int(v) for v in _random_mod.get_rng_state()]
                logs = self._run_one_epoch(loader, cbks, "train",
                                           accumulate_grad_batches, num_iters,
                                           ckpt_ctx=ckpt_ctx)
                cbks.on_epoch_end(epoch, logs)
                if ckpt_ctx is not None and ckpt_ctx["preempted"]:
                    break
                if eval_loader is not None and (epoch % eval_freq == 0 or epoch == epochs - 1):
                    eval_logs = {"steps": len(eval_loader) if hasattr(eval_loader, "__len__") else None,
                                 "metrics": metric_names}
                    cbks.on_begin("eval", eval_logs)
                    eval_logs = self._run_one_epoch(eval_loader, cbks, "eval")
                    cbks.on_end("eval", eval_logs)
            cbks.on_end("train")
        finally:
            if ckpt_ctx is not None:
                ckpt_ctx["ck"].close()  # drain any in-flight save
        return self

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None):
        loader = self._make_loader(eval_data, batch_size, False, num_workers)
        metric_names = ["loss"] + [n for m in self._metrics for n in to_list(m.name())]
        cbks = config_callbacks(callbacks, model=self, log_freq=log_freq,
                                verbose=verbose, metrics=metric_names)
        logs = {"steps": len(loader) if hasattr(loader, "__len__") else None,
                "metrics": metric_names}
        cbks.on_begin("eval", logs)
        logs = self._run_one_epoch(loader, cbks, "eval", num_iters=num_iters)
        cbks.on_end("eval", logs)
        return {k: v for k, v in logs.items() if k in metric_names}

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                verbose=1, callbacks=None):
        loader = self._make_loader(test_data, batch_size, False, num_workers)
        cbks = config_callbacks(callbacks, model=self, verbose=verbose, metrics=[])
        logs = {"steps": len(loader) if hasattr(loader, "__len__") else None}
        cbks.on_begin("predict", logs)
        outputs: List[List[np.ndarray]] = []
        count = 0
        for step, batch in enumerate(loader):
            inputs, _labels = self._split_batch(batch)  # drop labels if present
            cbks.on_batch_begin("predict", step, {})
            outs = self.predict_batch(inputs)
            outputs.append(outs)
            count += outs[0].shape[0] if outs and hasattr(outs[0], "shape") else 1
            cbks.on_batch_end("predict", step, {})
        # regroup from per-batch to per-output (reference model.py:1960)
        n_out = len(outputs[0]) if outputs else 0
        grouped = [[b[i] for b in outputs] for i in range(n_out)]
        if stack_outputs:
            grouped = [np.concatenate(g, axis=0) for g in grouped]
        cbks.on_end("predict", {"samples": count})
        return grouped

    def _run_one_epoch(self, loader, cbks, mode, accumulate_grad_batches=1,
                       num_iters=None, ckpt_ctx=None):
        for m in self._metrics:
            m.reset()
        logs = {}
        count = 0
        pending = False
        nan_window = False  # current accumulation window had a NaN-skip
        tl = _timeline() if mode == "train" else None
        resumed_rng = None
        if mode == "train" and ckpt_ctx is not None and ckpt_ctx["skip_steps"] \
                and ckpt_ctx.pop("resume_epoch_rng", None) is not None:
            # rewind the global generator to the interrupted epoch's begin
            # state so a shuffling sampler redraws the SAME permutation the
            # original epoch trained on; the mid-step state (restored by
            # resume()) comes back right after the fast-forward
            from ..framework import random as _random_mod

            resumed_rng = _random_mod.get_rng_state()
            _random_mod.set_rng_state(
                tuple(int(v) for v in ckpt_ctx["epoch_rng"]))
        it = iter(loader)
        step = 0
        _END = object()
        if mode == "train" and ckpt_ctx is not None and ckpt_ctx["skip_steps"]:
            # resume fast-forward: consume the batches the checkpointed step
            # already trained on, so the loader replays the same sequence
            # the uninterrupted run would have seen
            for _ in range(ckpt_ctx["skip_steps"]):
                if next(it, _END) is _END:
                    break
                step += 1
            ckpt_ctx["skip_steps"] = 0
            if resumed_rng is not None:
                from ..framework import random as _random_mod

                _random_mod.set_rng_state(resumed_rng)
        while True:
            if num_iters is not None and step >= num_iters:
                break
            # one StepTimeline step = wait for the batch + run it; the
            # data_wait phase is where prefetch overlap shows up (near-zero
            # when the DevicePrefetcher keeps the queue fed)
            with (tl.step() if tl is not None else nullcontext()) as st:
                t_wait = _time.perf_counter()
                with (_span("pt.train.data_wait") if tl is not None
                      else nullcontext()):
                    batch = next(it, _END)
                t_got = _time.perf_counter()
                if batch is _END:
                    if st is not None:
                        st.cancel()  # exhausted-loader probe is not a step
                    break
                inputs, labels = self._split_batch(batch)
                cbks.on_batch_begin(mode, step, logs)
                if mode == "train" and self.stop_training:
                    if st is not None:
                        st.cancel()  # cancelled steps record no phases
                    break
                if tl is not None:
                    tl.record("data_wait", (t_got - t_wait) * 1e3, t0=t_wait)
                if mode == "train":
                    update = (step + 1) % accumulate_grad_batches == 0
                    gstep = ckpt_ctx["global_step"] if ckpt_ctx is not None \
                        else step
                    try:
                        self._check_nan_step_fault(gstep)
                        with _oom_guard("fit", step=gstep):
                            outs = self.train_batch(
                                inputs, labels,
                                update=update and not nan_window,
                                _loss_scale=1.0 / accumulate_grad_batches)
                    except _nan_skip_exc() as e:
                        # skip-and-continue: the poisoned step is dropped
                        # whole (grads cleared, no optimizer update) and
                        # training goes on — counted for the monitors.
                        # Mid-accumulation-window the WINDOW is the step:
                        # the earlier micro-grads are already gone, so the
                        # boundary must not apply a partial, mis-scaled sum
                        import warnings

                        if self._optimizer is not None:
                            self._optimizer.clear_grad()
                        for t in self._sparse_tables():
                            t.clear_pending()
                        nan_window = accumulate_grad_batches > 1 and not update
                        pending = False
                        from ..distributed.resilience import metrics as _rm

                        _rm.inc("skipped_steps")
                        warnings.warn(
                            f"fit: skipping non-finite step {gstep}: {e}",
                            RuntimeWarning, stacklevel=2)
                        cbks.on_batch_end(mode, step, logs)
                        if st is not None:
                            st.cancel()
                        if ckpt_ctx is not None:
                            ckpt_ctx["global_step"] = gstep + 1
                        step += 1
                        continue
                    if update and nan_window:
                        # the window contained a dropped step: discard the
                        # partial remainder instead of stepping on it
                        if self._optimizer is not None:
                            self._optimizer.clear_grad()
                        for t in self._sparse_tables():
                            t.clear_pending()
                        nan_window = False
                        pending = False
                        stepped = False
                    else:
                        pending = not update
                        stepped = update
                else:
                    outs = self.eval_batch(inputs, labels)
                if self._metrics and self._loss is not None:
                    loss_vals, metric_vals = outs
                elif self._loss is not None:
                    loss_vals, metric_vals = outs, None
                else:
                    loss_vals, metric_vals = None, outs
                if loss_vals:
                    logs["loss"] = loss_vals[0] if len(loss_vals) == 1 else loss_vals
                if metric_vals is not None:
                    names = [n for m in self._metrics for n in to_list(m.name())]
                    vals = to_list(metric_vals)
                    for n, v in zip(names, vals if len(vals) == len(names) else vals * len(names)):
                        logs[n] = v
                bsz = inputs[0].shape[0] if inputs and hasattr(inputs[0], "shape") else 1
                count += bsz
                logs["batch_size"] = bsz
                cbks.on_batch_end(mode, step, logs)
                if mode == "train" and ckpt_ctx is not None:
                    gs = ckpt_ctx["global_step"]
                    ckpt_ctx["global_step"] = gs + 1
                    # checkpoints only at UPDATE boundaries: a snapshot
                    # taken mid-accumulation-window would lose the window's
                    # accumulated grads (never part of the snapshot) and a
                    # resume could not reproduce the uninterrupted run. A
                    # preemption therefore drains up to k-1 more micro-steps
                    # before its final commit.
                    if stepped:
                        rz = _resilience()
                        if rz.preempted():
                            # SIGTERM landed: drain the lane, commit a final
                            # synchronous checkpoint, stop cleanly —
                            # resume() continues from exactly this step
                            ckpt_ctx["ck"].preempt_commit(
                                step=gs, epoch=ckpt_ctx.get("epoch"),
                                extra={"step_in_epoch": step,
                                       "epoch_rng": ckpt_ctx.get("epoch_rng")})
                            ckpt_ctx["preempted"] = True
                            # the preemption is CONSUMED by this commit — a
                            # later fit() in the same process starts fresh
                            rz.clear_preemption()
                            self.stop_training = True
                            break
                        if gs - ckpt_ctx.get("last_save", -1) \
                                >= ckpt_ctx["every"]:
                            # since-last-save cadence, not (gs+1)%every:
                            # with accumulation only boundary steps are
                            # eligible and the modulo could starve
                            ckpt_ctx["ck"].save_async(
                                step=gs, epoch=ckpt_ctx.get("epoch"),
                                extra={"step_in_epoch": step,
                                       "epoch_rng": ckpt_ctx.get("epoch_rng")})
                            ckpt_ctx["last_save"] = gs
            step += 1
        if nan_window:
            # epoch ended inside a poisoned window: drop its remainder
            if self._optimizer is not None:
                self._optimizer.clear_grad()
            for t in self._sparse_tables():
                t.clear_pending()
        if pending:
            # flush the trailing partial accumulation group
            if self._optimizer is not None:
                self._optimizer.step()
                self._optimizer.clear_grad()
            for t in self._sparse_tables():
                t.flush(update=True)
        for m in self._metrics:
            res = m.accumulate()
            for n, v in zip(to_list(m.name()), to_list(res)):
                logs[n] = v
        logs["samples"] = count
        return logs

    def summary(self, input_size=None, dtype=None):
        from .model_summary import summary

        sizes = input_size
        if sizes is None and self._inputs:
            sizes = [tuple(s.shape) for s in self._inputs]
        assert sizes is not None, "input_size must be given (no InputSpec provided)"
        return summary(self.network, sizes, dtypes=dtype)
