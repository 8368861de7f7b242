"""Compiled pipeline-parallel schedule over the 'pp' mesh axis.

Reference: fleet/meta_parallel/pipeline_parallel.py:80 (forward_backward_pipeline,
the 1F1B schedule) + pp_utils/p2p_communication.py:216 (_p2p_helper stage
handoff). TPU-native mapping: there are no per-stage processes — ONE compiled
program runs a synchronous microbatch pipeline with `lax.ppermute` as the
stage handoff, inside a `shard_map` that is *manual* over 'pp' and *auto*
(GSPMD) over every other axis, so TP/DP/CP sharding inside a stage keeps
working unchanged. Autodiff through the tick scan yields the reverse
(cooldown) pipeline, and `jax.checkpoint` around the stage body bounds live
activation memory to O(microbatch) like 1F1B's early backward does — the
fill/drain bubble matches the reference schedule's (pp-1)/(M+pp-1).

The handoff contract mirrors SendRecvMeta (p2p_communication.py:38): every
stage must map activations of one fixed (shape, dtype) to the same — checked
at trace time instead of via a runtime shape handshake.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..mesh import MeshEnv, require_mesh_env


def ppermute_pipeline(run_stage: Callable, x_mb, pp_size: int, axis: str = "pp",
                      remat: bool = True, with_aux: bool = False, keep=()):
    """Run the microbatch pipeline for THIS device's stage (call inside a
    shard_map manual over `axis`).

    run_stage: [mb, ...] -> [mb, ...] applying the local stage's layers (or
               -> ([mb, ...], scalar aux) when with_aux, e.g. MoE balance loss).
    x_mb:      [M, mb, ...] microbatched input (consumed by stage 0 only).
    Returns [M, mb, ...] outputs of the LAST stage, replicated over `axis`
    (plus the pp-summed aux, bubble ticks masked out, when with_aux).
    """
    M = x_mb.shape[0]
    T = M + pp_size - 1
    idx = lax.axis_index(axis)
    perm = [(i, i + 1) for i in range(pp_size - 1)]
    if remat:
        from .stage_stack import remat_wrap

        run_stage = remat_wrap(run_stage, keep)

    def tick(carry, t):
        state, outs, aux_acc = carry
        inp = jnp.where(idx == 0, x_mb[jnp.clip(t, 0, M - 1)], state)
        res = run_stage(inp)
        out, aux = res if with_aux else (res, None)
        recv = lax.ppermute(out, axis, perm)
        oidx = jnp.clip(t - (pp_size - 1), 0, M - 1)
        valid = t >= (pp_size - 1)
        cur = lax.dynamic_index_in_dim(outs, oidx, 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, out, cur), oidx, 0)
        if with_aux:
            # stage `idx` does real work for microbatch t-idx on ticks
            # idx <= t < idx+M; bubble ticks must not pollute the aux sum
            working = (t >= idx) & (t < idx + M)
            aux_acc = aux_acc + jnp.where(working, aux, 0.0)
        return (recv, outs, aux_acc), None

    state0 = jnp.zeros_like(x_mb[0])
    outs0 = jnp.zeros_like(x_mb)
    aux0 = jnp.zeros((), jnp.float32)
    (_, outs, aux_acc), _ = lax.scan(tick, (state0, outs0, aux0), jnp.arange(T))
    # broadcast the last stage's collected outputs to the whole pp group
    mask = (idx == pp_size - 1).astype(outs.dtype)
    outs = lax.psum(outs * mask, axis)
    if with_aux:
        return outs, lax.psum(aux_acc, axis)
    return outs


def _batch_shard_degree(env) -> int:
    if env is None:
        env = require_mesh_env()
    d = 1
    for ax in ("dp", "sdp"):
        d *= max(env.get_dim(ax), 1)
    return d


def choose_microbatches(batch: int, desired: int, env=None) -> int:
    """Largest M <= desired with batch % (M * d) == 0, so each microbatch
    spans every dp/sdp shard (keeps the pipeline handoff resharding-free).

    This is NOT an extra TPU-side coupling: it is exactly the reference's
    requirement that each dp rank's LOCAL batch split into M integral
    micro-batches (pipeline_parallel.py micro_batch_size * accumulate_steps
    == local batch) — batch % (M*d) == 0 <=> (batch/d) % M == 0. The minimal
    global batch that keeps a desired M is therefore M * d rows.
    Falls back to the largest divisor of batch when nothing spans; warns
    whenever the answer differs from what the caller configured."""
    d = _batch_shard_degree(env)
    chosen = 1
    for m in range(min(desired, max(batch // d, 1)), 0, -1):
        if batch % (m * d) == 0:
            chosen = m
            break
    else:
        for m in range(min(desired, batch), 0, -1):
            if batch % m == 0:
                chosen = m
                break
    if chosen != desired:
        import warnings

        e = env if env is not None else require_mesh_env()
        pp = max(e.get_dim("pp"), 1)
        warnings.warn(
            f"pipeline microbatches clamped {desired} -> {chosen}: each "
            f"microbatch must hold >=1 row from every one of the {d} data "
            f"shards (the same local-batch divisibility constraint as "
            f"multi-process PP), which batch {batch} cannot satisfy for "
            f"M={desired}. Bubble fraction "
            f"{bubble_fraction(desired, pp):.0%} -> "
            f"{bubble_fraction(chosen, pp):.0%}; use a global batch that "
            f"is a multiple of {desired * d} to keep M={desired}")
    return chosen


def bubble_fraction(num_microbatches: int, pp: int) -> float:
    """Fill/drain idle fraction of the synchronous microbatch pipeline:
    (pp-1)/(M+pp-1), same as the reference 1F1B schedule's bubble."""
    return (pp - 1) / (num_microbatches + pp - 1)


def microbatch(x, num_microbatches: int, env=None):
    """[b, ...] -> [M, b/M, ...].

    The batch dim is sharded over dp/sdp (shard-major sample order). A plain
    reshape would land that sharding on the microbatch-INDEX dim, putting each
    tick's microbatch on a subset of dp replicas — GSPMD then replicates
    ("involuntary full rematerialization"). Instead interleave so every dp
    shard contributes 1/dp of EVERY microbatch: [d, M, b/(d*M)] -> swap ->
    [M, d, b/(d*M)] -> merge. All three steps are layout-preserving for a
    dim0-sharded input, so the pipeline sees dp sharding on the mb dim.
    """
    b = x.shape[0]
    M = num_microbatches
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    d = _batch_shard_degree(env)
    if d > 1 and b % (d * M) == 0:
        x = x.reshape((d, M, b // (d * M)) + x.shape[1:])
        x = x.swapaxes(0, 1)
        return x.reshape((M, b // M) + x.shape[3:])
    return x.reshape((M, b // M) + x.shape[1:])


def unmicrobatch(x_mb, env=None):
    """Inverse of microbatch (same interleaving, same env)."""
    M, mb = x_mb.shape[0], x_mb.shape[1]
    b = M * mb
    d = _batch_shard_degree(env)
    if d > 1 and b % (d * M) == 0:
        x = x_mb.reshape((M, d, mb // d) + x_mb.shape[2:])
        x = x.swapaxes(0, 1)
        return x.reshape((b,) + x.shape[3:])
    return x_mb.reshape((b,) + x_mb.shape[2:])


def pipeline_shard_map(stage_fn: Callable, env: MeshEnv, n_stage_args: int,
                       remat: bool = True, with_aux: bool = False, keep=()):
    """Wrap `stage_fn(x_local, *stage_params_local)` into the full pipelined
    [M, mb, ...] -> [M, mb, ...] function.

    stage_params are arrays whose LEADING dim is the stage dim (sharded over
    'pp'); inside, each device sees its own stage's slice. All other mesh
    axes stay auto (GSPMD).
    """
    pp = env.get_dim("pp")

    def pipelined(x_mb, *stage_params):
        def local(x_mb_l, *params_l):
            return ppermute_pipeline(
                lambda h: stage_fn(h, *params_l), x_mb_l, pp, remat=remat,
                with_aux=with_aux, keep=keep)

        out_specs = (P(), P()) if with_aux else P()
        return jax.shard_map(
            local, mesh=env.mesh, in_specs=(P(),) + (P("pp"),) * n_stage_args,
            out_specs=out_specs, axis_names={"pp"}, check_vma=False,
        )(x_mb, *stage_params)

    return pipelined
