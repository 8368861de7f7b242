"""Expert-parallel collectives (reference:
python/paddle/distributed/models/moe/utils.py + the global_scatter/
global_gather ops, paddle/fluid/operators/collective/global_scatter_op.cc,
global_gather_op.cc).

TPU-native contract: the reference moves ragged per-expert token counts over
NCCL all-to-all; XLA wants static shapes, so these wrappers operate on the
capacity-dense layout — tokens pre-packed per expert with a fixed capacity —
and the all-to-all over the 'ep' mesh axis is a `lax.all_to_all` inside a
shard_map (ragged counts become masks). nn.MoELayer produces/consumes this
layout; the count tensors keep the reference API shape and are used to build
the validity mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....core.tensor import Tensor
from ....core.dispatch import primitive
from ...mesh import require_mesh_env


def _number_count(gate_idx, upper_range):
    """Per-expert token counts from gate indices (reference _number_count op)."""
    return _number_count_p(gate_idx, upper=int(upper_range))


@primitive("number_count", nondiff=True)
def _number_count_p(gate_idx, *, upper):
    flat = gate_idx.reshape(-1)
    return jnp.zeros((upper,), gate_idx.dtype).at[flat].add(1)


number_count = _number_count


def global_scatter(x, local_count, global_count, group=None):
    """Dispatch capacity-dense expert buckets to their owning ep ranks.

    x: [ep, n_expert, capacity, d] — dim 0 is the source rank (sharded over
    'ep'); x[s, e] is rank s's bucket of tokens routed to global expert e.
    Returns the same global shape where out[r, s*(E/ep)+j] = x[s, r*(E/ep)+j]:
    ep rank r now holds, from every source rank, the buckets for its own E/ep
    experts. Counts are the reference API shape (there they size the ragged
    NCCL a2a; here overflow is masked by capacity).
    Reference contract: global_scatter_op.cc.
    """
    return _global_a2a(x, local_count, global_count)


def global_gather(x, local_count, global_count, group=None):
    """Inverse of global_scatter: return expert outputs to their source ranks
    (reference global_gather_op.cc). The block permutation is an involution,
    so this is the same all_to_all."""
    return _global_a2a(x, local_count, global_count)


def _global_a2a(x, local_count, global_count):
    env = require_mesh_env()
    ep = env.get_dim("ep")
    arr = x.data if isinstance(x, Tensor) else jnp.asarray(x)
    if ep > 1 and (arr.shape[0] != ep or arr.shape[1] % ep != 0):
        raise ValueError(
            f"global_scatter/gather expects [ep={ep}, n_expert%ep==0, ...], "
            f"got {arr.shape}")
    return _global_a2a_p(x, local_count, global_count, _env_id=id(env))


@primitive("global_alltoall")
def _global_a2a_p(x, local_count, global_count, *, _env_id):
    env = require_mesh_env()
    ep = env.get_dim("ep")
    # counts -> validity mask: slot c of bucket (s, e) is real iff
    # c < local_count[e] (or local_count[s, e]); garbage beyond the count is
    # zeroed before it crosses the wire (the ragged-a2a contract, densified).
    # Applied on every mesh size so 1-rank and n-rank results agree.
    cap = x.shape[2]
    lc = local_count
    if lc.ndim == 1:
        lc = jnp.broadcast_to(lc[None, :], x.shape[:2])
    mask = jnp.arange(cap)[None, None, :] < lc[:, :, None]  # [ep, E, C]
    x = x * mask[..., None].astype(x.dtype)
    if ep <= 1:
        return x

    def local(xl, lcl, gcl):
        # xl: [1, n_expert, capacity, d] — this rank's buckets for everyone
        y = jax.lax.all_to_all(xl[0], "ep", split_axis=0, concat_axis=0,
                               tiled=True)
        return y[None]

    # the guard above guarantees the native jax.shard_map surface here
    return jax.shard_map(local, mesh=env.mesh, in_specs=(P("ep"), P(), P()),
                         out_specs=P("ep"), axis_names={"ep"},
                         check_vma=False)(x, local_count, global_count)
