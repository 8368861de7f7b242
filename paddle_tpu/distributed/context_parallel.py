"""Context parallelism: ring attention over the 'cp' mesh axis.

SURVEY §5 long-context mandate — the reference snapshot predates CP entirely
(no ring attention / Ulysses; grep yields nothing), so this is designed
TPU-native rather than ported: the sequence dim is sharded over 'cp', each
rank keeps its Q shard resident and the K/V shards ride the ICI ring via
`lax.ppermute`, one hop per step. Per-step partial attention uses the Pallas
flash kernel (kernels/flash_attention.py) with a global-position offset for
causality across chunks, and partial results merge in log-sum-exp space — so
attention memory per chip stays O((s/cp)·d) no matter the global sequence.

Backward rides jax.checkpoint per ring step: activations are recomputed
step-by-step in reverse, and the K/V gradient shards travel the ring back to
their owners through ppermute's transpose.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import MeshEnv, _axes_if_divisible, get_mesh_env


def _cp_shard_map(local, env, axis, batch):
    """``local`` over [batch-major rows, seq, ...] operands, manual over the
    sequence axis AND the data axes the batch splits over: each data
    replica rings its own rows (left to GSPMD, the rows inside the manual
    region are the partitioner's guess — the global batch, at worst)."""
    data = _axes_if_divisible(env, ("dp", "sdp"), batch) or ()
    data = (data,) if isinstance(data, str) else data
    spec = P(data or None, axis)
    return jax.shard_map(local, mesh=env.mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis, *data},
                         check_vma=False)


def _merge(o1, lse1, o2, lse2):
    """Combine two partial attentions of the same queries in lse space.
    Accumulates in fp32 — the caller casts back once after the ring."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return o1.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2, lse


def _ring_local(q, k, v, cp, causal, scale, axis):
    """Per-device body (inside shard_map manual over `axis`).

    q/k/v: [bh, s_loc, d] — this rank's sequence chunk.
    """
    from ..kernels.flash_attention import flash_attention_with_lse

    idx = lax.axis_index(axis)
    s_loc = q.shape[1]
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def partial_attn(k_cur, v_cur, r):
        # k_cur holds the chunk that started on rank (idx - r) mod cp
        src = (idx - r) % cp
        if causal:
            # global causality: qpos = idx*s_loc + i, kpos = src*s_loc + j
            # => mask i + (idx-src)*s_loc >= j. Chunks entirely in the future
            # ((idx-src)*s_loc <= -s_loc) come out fully masked -> lse=-inf-ish
            offset = (idx - src) * s_loc
            return flash_attention_with_lse(q, k_cur, v_cur, offset=offset,
                                            causal=True, scale=scale)
        return flash_attention_with_lse(q, k_cur, v_cur, offset=0,
                                        causal=False, scale=scale)

    o0, lse0 = partial_attn(k, v, 0)
    o0 = o0.astype(jnp.float32)

    def step(carry, r):
        o, lse, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis, perm)
        v_cur = lax.ppermute(v_cur, axis, perm)
        o_r, lse_r = partial_attn(k_cur, v_cur, r)
        o, lse = _merge(o, lse, o_r, lse_r)
        return (o, lse, k_cur, v_cur), None

    if cp > 1:
        step = jax.checkpoint(step)
        (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v),
                                     jnp.arange(1, cp))
    else:
        o, lse = o0, lse0
    return o.astype(q.dtype)


def ring_attention_bhsd(q, k, v, causal=True, scale=None,
                        env: MeshEnv = None, axis: str = "cp", batch: int = 1):
    """q/k/v: [bh, s, d] with s sharded over `axis`. Returns [bh, s, d].
    ``batch`` is b of the batch-major bh rows (1: the rows are not split)."""
    env = env or get_mesh_env()
    cp = env.get_dim(axis) if env is not None else 1
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if cp <= 1:
        from ..kernels.flash_attention import flash_attention_with_lse

        o, _ = flash_attention_with_lse(q, k, v, offset=0, causal=causal,
                                        scale=scale)
        return o

    def local(ql, kl, vl):
        return _ring_local(ql, kl, vl, cp, causal, float(scale), axis)

    return _cp_shard_map(local, env, axis, batch)(q, k, v)


def ring_attention(q, k, v, causal=True, scale=None, env: MeshEnv = None):
    """Paddle layout [b, s, h, d], seq sharded over 'cp'. Differentiable."""
    from ..core.tensor import Tensor

    if isinstance(q, Tensor):
        return _ring_attention_prim(q, k, v, causal=bool(causal),
                                    scale=scale if scale is None else float(scale))
    return _ring_bshd(q, k, v, causal, scale, env)


def _ring_bshd(q, k, v, causal, scale, env=None):
    b, s, h, d = q.shape
    qm = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
    km = jnp.moveaxis(k, 2, 1).reshape(b * h, s, d)
    vm = jnp.moveaxis(v, 2, 1).reshape(b * h, s, d)
    om = ring_attention_bhsd(qm, km, vm, causal=causal, scale=scale, env=env,
                             batch=b)
    return jnp.moveaxis(om.reshape(b, h, s, d), 1, 2)


from ..core.dispatch import primitive  # noqa: E402  (Tensor-level op wrapper)


@primitive("ring_attention")
def _ring_attention_prim(q, k, v, *, causal, scale):
    return _ring_bshd(q, k, v, causal, scale)


# -- Ulysses (all-to-all head-sharded) context parallelism --------------------
# SURVEY §5: "Ulysses a2a over ICI as a mesh axis". Complementary to the ring:
# instead of streaming K/V chunks around, one all_to_all converts the
# sequence sharding into a head sharding (each rank holds ALL positions of
# h/cp heads), runs ordinary flash attention on the full sequence locally,
# and a second all_to_all restores the sequence sharding. Two a2a hops of
# activation-sized traffic versus cp-1 ppermute hops of K/V — the better
# trade at moderate cp degrees when heads divide evenly (DeepSpeed-Ulysses
# recipe, re-expressed as XLA collectives on the mesh).

def ulysses_attention_bshd(q, k, v, causal=True, scale=None,
                           env: MeshEnv = None, axis: str = "cp"):
    """q/k/v: [b, s, h, d] with s (dim 1) sharded over `axis`."""
    env = env or get_mesh_env()
    cp = env.get_dim(axis) if env is not None else 1
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    from ..kernels.flash_attention import flash_attention

    if cp <= 1:
        return flash_attention(q, k, v, causal=causal, scale=scale)
    h = q.shape[2]
    if h % cp != 0:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by cp={cp}; "
            "use ring attention (cp_impl='ring') for this head count")

    def local(ql, kl, vl):
        # [b, s/cp, h, d] -> [b, s, h/cp, d]: scatter heads, gather sequence
        qh = lax.all_to_all(ql, axis, split_axis=2, concat_axis=1, tiled=True)
        kh = lax.all_to_all(kl, axis, split_axis=2, concat_axis=1, tiled=True)
        vh = lax.all_to_all(vl, axis, split_axis=2, concat_axis=1, tiled=True)
        oh = flash_attention(qh, kh, vh, causal=causal, scale=float(scale))
        # [b, s, h/cp, d] -> [b, s/cp, h, d]: scatter sequence, gather heads
        return lax.all_to_all(oh, axis, split_axis=1, concat_axis=2, tiled=True)

    return _cp_shard_map(local, env, axis, q.shape[0])(q, k, v)


@primitive("ulysses_attention")
def _ulysses_attention_prim(q, k, v, *, causal, scale):
    return ulysses_attention_bshd(q, k, v, causal, scale)


def ulysses_attention(q, k, v, causal=True, scale=None, env: MeshEnv = None):
    """Paddle layout [b, s, h, d], seq sharded over 'cp'. Differentiable."""
    from ..core.tensor import Tensor

    if isinstance(q, Tensor):
        return _ulysses_attention_prim(
            q, k, v, causal=bool(causal),
            scale=scale if scale is None else float(scale))
    return ulysses_attention_bshd(q, k, v, causal, scale, env)
