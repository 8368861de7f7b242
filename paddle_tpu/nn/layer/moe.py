"""Mixture-of-Experts layers (expert parallelism over the 'ep' mesh axis).

Reference: paddle/fluid/operators/collective/global_scatter_op.cc +
global_gather_op.cc (expert-parallel all-to-all by counts) and
python/paddle/distributed/models/moe/utils.py — the snapshot has only these
primitives, no production MoE layer; BASELINE config 5 (DeepSeekMoE/Qwen2-MoE
4D) requires the full layer.

TPU-native design: capacity-dense GShard-style routing — top-k gate, tokens
packed into a static [E, capacity, d] buffer via one-hot dispatch einsums;
expert weights are stacked on a leading E dim with dist_spec P('ep', ...), so
GSPMD lowers the dispatch/combine einsums into exactly the all_to_all pattern
the reference's global_scatter/global_gather hand-code, and the per-expert
FFNs run as one batched MXU matmul. No ragged shapes, no host round-trips.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.dispatch import primitive
from ...core.tensor import Tensor
from ...observability.trace.parts import part
from .layers import Layer
from .. import initializer as I

# -- aux-loss plumbing --------------------------------------------------------
# MoE layers record their load-balancing loss here; model heads drain it and
# add it to the objective. Works eagerly and under trace (values are traced
# scalars); scan/pipeline stacks thread it explicitly (models/llama.py).

_AUX_STACK = []


@contextlib.contextmanager
def collect_aux():
    bucket = []
    _AUX_STACK.append(bucket)
    try:
        yield bucket
    finally:
        _AUX_STACK.pop()


def record_aux(v):
    if _AUX_STACK:
        _AUX_STACK[-1].append(v)


def drain_aux(bucket):
    """Sum of recorded aux losses as a Tensor (0.0 when none)."""
    if not bucket:
        return None
    total = bucket[0]
    for v in bucket[1:]:
        total = total + v
    return total


def _route(xt, wg, top_k, score="softmax", norm_topk=True, scale=1.0,
           precision=None, bias=None):
    """Router: fp32 scores over ALL ``e`` router outputs (``score``:
    'softmax', or 'sigmoid' as the DeepSeek-V3 family scores), top-k,
    renormalized over the chosen k when ``norm_topk``, times ``scale`` (the
    family's ``routed_scaling_factor``) — and the Switch/GShard
    load-balancing aux (e * sum(frac_tokens * frac_probs)). ``bias`` [e]
    (the family's ``noaux_tc`` correction) moves which k are CHOSEN — the
    top-k of ``score + bias`` — and nothing else: a chosen expert's gate is
    its own score."""
    n, _ = xt.shape
    e = wg.shape[1]
    logits = jnp.matmul(xt.astype(jnp.float32), wg.astype(jnp.float32),
                        precision=precision)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)  # [n, e]
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"router score {score!r}: 'softmax' or 'sigmoid'")
    if bias is None:
        gate_v, gate_i = jax.lax.top_k(probs, top_k)  # [n, k]
    else:
        _chosen, gate_i = jax.lax.top_k(probs + bias.astype(jnp.float32),
                                        top_k)
        gate_v = jnp.take_along_axis(probs, gate_i, axis=-1)
    if norm_topk:
        total = jnp.sum(gate_v, -1, keepdims=True)
        # the softmax form as it always was; the sigmoid family's own epsilon
        gate_v = gate_v / (jnp.maximum(total, 1e-9) if score == "softmax"
                           else total + 1e-20)
    if scale != 1.0:
        gate_v = gate_v * scale
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_i[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return gate_v, gate_i, aux


def _expert_ffn(expert_in, w_gate, w_up, w_down, ep_degree):
    """Batched per-expert SwiGLU on [e, cap, h] buffers (one MXU matmul per
    projection; gate/up separate so the silu(gate)*up multiply stays local
    per mp shard). Inputs/outputs carry checkpoint names so
    FLAGS_remat_policy='moe' can pin them across the remat boundary (the
    backward then rebuilds only g/u from the saved buffer instead of
    re-running dispatch + the down projection)."""
    from jax.ad_checkpoint import checkpoint_name

    expert_in = checkpoint_name(_ep_constraint(expert_in, ep_degree),
                                "moe_buf")
    g = jnp.einsum("ech,ehi->eci", expert_in, w_gate)
    u = jnp.einsum("ech,ehi->eci", expert_in, w_up)
    act = jax.nn.silu(g) * u
    expert_out = jnp.einsum("eci,eih->ech", act, w_down)
    return checkpoint_name(_ep_constraint(expert_out, ep_degree), "moe_out")


@primitive("moe_mlp")
def _moe_mlp(x, wg, w_gate, w_up, w_down, *, top_k, capacity_factor,
             ep_degree, dispatch="index"):
    """Routed expert FFN: [b, s, h] -> ([b, s, h], aux_loss).

    Four dispatch strategies; the capacity modes share drop semantics
    (slot-major: every token's 1st choice outranks any 2nd choice):

    - 'index' (default): capacity slots assigned by a cumsum over the
      [k*n, e] expert one-hot — no argsort, no inverse permutation (the
      choice-major flat order IS the combine order), all row movement plain
      gathers. v5e at the bench shape: 19% faster fwd+bwd than 'sort'.
    - 'sort': tokens argsorted by expert id; each (token, choice) takes the
      next position in its expert's capacity buffer via a gather. The
      TPU-native form of the reference's count-based global_scatter
      (global_scatter_op.cc builds exactly these per-expert contiguous
      buffers from counts).
    - 'gmm': DROPLESS grouped matmul (kernels/grouped_matmul.py, megablox
      Pallas kernel on TPU) — rows sorted by expert, per-expert ragged row
      blocks walked back-to-back on the MXU; no capacity, no padding waste,
      capacity_factor ignored. Single-device experts only (falls back to
      'index' when ep_degree > 1 — ragged row counts can't cross a GSPMD
      all_to_all with static shapes).
    - 'fused': DROPLESS fused routing/dispatch (kernels/pallas/
      moe_dispatch.py) — the whole router (top-k + sort-by-expert
      position counters) is one Pallas kernel and row movement runs as
      scalar-prefetch gathers with gather-only VJPs, feeding the same
      grouped matmul; row order (and therefore output) matches 'gmm'
      without executing the argsort. Single-device experts and
      num_experts <= 128 only (falls back to 'index' outside that).
    - 'einsum': GShard one-hot dispatch/combine einsums. O(n*e*cap)
      intermediates — kept as the oracle for parity tests.

    `dispatch` is a primitive ATTR (cache-key participant): the caller reads
    the flag so a set_flags after the first call still takes effect.
    """
    if dispatch == "fused" and ep_degree <= 1:
        from ...kernels.pallas.moe_dispatch import MAX_EXPERTS, fused_moe_mlp

        if wg.shape[1] <= MAX_EXPERTS:
            return fused_moe_mlp(x, wg, w_gate, w_up, w_down, top_k=top_k)
    if dispatch == "gmm" and ep_degree <= 1:
        return _moe_mlp_gmm(x, wg, w_gate, w_up, w_down, top_k=top_k)
    impl = {"einsum": _moe_mlp_einsum, "sort": _moe_mlp_sort}.get(
        dispatch, _moe_mlp_index)
    return impl(x, wg, w_gate, w_up, w_down, top_k=top_k,
                capacity_factor=capacity_factor, ep_degree=ep_degree)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _idx_dispatch(xt, slot_src, slot, keep, top_k):
    """buf[s] = xt[slot_src[s]] (zero row for empty slots) with a
    GATHER-ONLY backward: XLA's transpose of this gather is a [e*cap, h]
    scatter-add — serialized row writes on TPU, measured at 21% of the MoE
    MLP fwd+bwd. The cotangent is instead gathered back through `slot`
    (d_xt[t] = sum_k d_buf[slot[k,t]] masked by keep) — the same index
    structure, no scatter."""
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, xt.shape[1]), xt.dtype)])
    return xt_pad[slot_src]


def _idx_dispatch_fwd(xt, slot_src, slot, keep, top_k):
    return _idx_dispatch(xt, slot_src, slot, keep, top_k), \
        (slot, keep, xt.shape[0])


def _idx_dispatch_bwd(top_k, res, g_buf):
    slot, keep, n = res
    ec = g_buf.shape[0]
    picked = jnp.where(keep[:, None],
                       g_buf[jnp.clip(slot, 0, ec - 1)],
                       jnp.zeros((), g_buf.dtype))
    d_xt = jnp.sum(picked.reshape(top_k, n, -1), axis=0)
    return d_xt, None, None, None


_idx_dispatch.defvjp(_idx_dispatch_fwd, _idx_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _idx_combine(y, gates, slot, keep, slot_rowsrc, top_k):
    """out[t] = sum_k keep * y[slot[k,t]] * gates[k,t], backward all
    gathers: d_y[s] = d_out[slot_rowsrc[s] % n] * gates[slot_rowsrc[s]]
    (slot_rowsrc maps each slot to its flat choice-major row, built by a
    cheap int32 scatter in the caller), d_gates[r] = <d_out[t_r], y[slot[r]]>."""
    kn = slot.shape[0]
    n = kn // top_k
    ec = y.shape[0]
    contrib = jnp.where(keep[:, None],
                        y[jnp.clip(slot, 0, ec - 1)],
                        jnp.zeros((), y.dtype)) * \
        gates[:, None].astype(y.dtype)
    return jnp.sum(contrib.reshape(top_k, n, -1), axis=0)


def _idx_combine_fwd(y, gates, slot, keep, slot_rowsrc, top_k):
    return _idx_combine(y, gates, slot, keep, slot_rowsrc, top_k), \
        (y, gates, slot, keep, slot_rowsrc)


def _idx_combine_bwd(top_k, res, d_out):
    y, gates, slot, keep, slot_rowsrc = res
    kn = slot.shape[0]
    n = kn // top_k
    ec = y.shape[0]
    # d_y: route each occupied slot back to its token's cotangent row
    occupied = slot_rowsrc < kn
    row = jnp.clip(slot_rowsrc, 0, kn - 1)
    d_y = jnp.where(occupied[:, None],
                    d_out[row % n] * gates[row][:, None].astype(d_out.dtype),
                    jnp.zeros((), d_out.dtype)).astype(y.dtype)
    # d_gates: rowwise dot of the token cotangent with the expert output
    y_rows = jnp.where(keep[:, None],
                       y[jnp.clip(slot, 0, ec - 1)],
                       jnp.zeros((), y.dtype))
    tok = jnp.arange(kn, dtype=jnp.int32) % n
    d_gates = jnp.sum(d_out[tok].astype(jnp.float32) *
                      y_rows.astype(jnp.float32), axis=1).astype(gates.dtype)
    return d_y, d_gates, None, None, None


_idx_combine.defvjp(_idx_combine_fwd, _idx_combine_bwd)


def _moe_mlp_index(x, wg, w_gate, w_up, w_down, *, top_k, capacity_factor,
                   ep_degree):
    """Capacity dispatch without the sort: positions come from a cumsum over
    the [k*n, e] one-hot (GShard's position_in_expert), so there is no
    argsort, no searchsorted, and — because the flat order is choice-major
    by construction — no inverse permutation at combine time. Row movement
    is two gathers FORWARD AND BACKWARD (_idx_dispatch/_idx_combine custom
    vjps); only int32 index vectors are ever scattered."""
    b, s, h = x.shape
    n = b * s
    e = wg.shape[1]
    kn = top_k * n
    cap = max(int(math.ceil(capacity_factor * top_k * n / e)), top_k)

    xt = x.reshape(n, h)
    gate_v, gate_i, aux = _route(xt, wg, top_k)

    # choice-major flattening: all 1st choices precede any 2nd choice, so
    # the running count gives 1st choices capacity priority
    flat_e = gate_i.T.reshape(kn)
    flat_g = gate_v.T.reshape(kn)
    oh = flat_e[:, None] == jnp.arange(e, dtype=flat_e.dtype)[None, :]
    pos = jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1
    pos_in_e = jnp.sum(jnp.where(oh, pos, 0), axis=1)
    keep = pos_in_e < cap
    # dropped entries land on a scratch slot past the buffer
    slot = jnp.where(keep, flat_e * cap + pos_in_e, e * cap)

    # slot -> flat (choice-major) row: the ONE int32 scatter; the token
    # map follows arithmetically (row = k*n + t, so token = row % n with
    # the empty-slot sentinel mapped to n for the zero pad row)
    slot_rowsrc = jnp.full((e * cap + 1,), kn, jnp.int32).at[slot].set(
        jnp.arange(kn, dtype=jnp.int32), mode="drop")[:-1]
    slot_src = jnp.where(slot_rowsrc < kn, slot_rowsrc % n, n)
    # name the routing decisions (~1MB total) so FLAGS_remat_policy='route'
    # pins them across the remat boundary: the backward recompute then
    # skips the router matmul + softmax + top_k + cumsum + int scatters
    from jax.ad_checkpoint import checkpoint_name

    slot = checkpoint_name(slot, "moe_route")
    keep = checkpoint_name(keep, "moe_route")
    slot_src = checkpoint_name(slot_src, "moe_route")
    slot_rowsrc = checkpoint_name(slot_rowsrc, "moe_route")
    flat_g = checkpoint_name(flat_g, "moe_route")
    buf = _idx_dispatch(xt, slot_src, slot, keep, top_k)

    expert_out = _expert_ffn(buf.reshape(e, cap, h), w_gate, w_up,
                             w_down, ep_degree).reshape(e * cap, h)

    out = _idx_combine(expert_out, flat_g, slot, keep, slot_rowsrc, top_k)
    return out.reshape(b, s, h), aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_disp_gather(xt, order, inv, top_k):
    """xs[i] = xt[order[i] // top_k] with a gather-only backward: the
    cotangent is unsorted by `inv` (a gather, not the scatter XLA would
    emit for this op's transpose) and summed over the k choice copies."""
    return jnp.take(xt, order // top_k, axis=0)


def _gmm_disp_fwd(xt, order, inv, top_k):
    return jnp.take(xt, order // top_k, axis=0), (inv, xt.shape[0])


def _gmm_disp_bwd(top_k, res, g):
    inv, n = res
    gt = jnp.take(g, inv, axis=0).reshape(n, top_k, -1).sum(axis=1)
    return gt, None, None


_gmm_disp_gather.defvjp(_gmm_disp_fwd, _gmm_disp_bwd)


@jax.custom_vjp
def _perm_rows(x, perm, inv_perm):
    """x[perm] for a permutation, with the backward expressed as the inverse
    gather instead of XLA's scatter transpose."""
    return jnp.take(x, perm, axis=0)


def _perm_rows_fwd(x, perm, inv_perm):
    return jnp.take(x, perm, axis=0), (inv_perm,)


def _perm_rows_bwd(res, g):
    (inv_perm,) = res
    return jnp.take(g, inv_perm, axis=0), None, None


_perm_rows.defvjp(_perm_rows_fwd, _perm_rows_bwd)


def _moe_mlp_gmm(x, wg, w_gate, w_up, w_down, *, top_k):
    """Dropless expert FFN: sort the k*n (token, choice) rows by expert and
    run the ragged per-expert blocks through one grouped matmul per
    projection (kernels/grouped_matmul.py). Executed FLOPs == activated
    FLOPs — no capacity padding, no drops."""
    from ...kernels.grouped_matmul import grouped_matmul

    b, s, h = x.shape
    n = b * s
    e = wg.shape[1]
    kn = top_k * n

    xt = x.reshape(n, h)
    gate_v, gate_i, aux = _route(xt, wg, top_k)

    flat_e = gate_i.reshape(kn)  # token-major: row t*k+c = choice c of t
    order = jnp.argsort(flat_e, stable=True)
    inv = jnp.zeros((kn,), jnp.int32).at[order].set(
        jnp.arange(kn, dtype=jnp.int32))  # int scatter, not a second sort
    group_sizes = jnp.bincount(flat_e, length=e)

    xs = _gmm_disp_gather(xt, order, inv, top_k)  # [kn, h] expert-grouped
    g_proj = grouped_matmul(xs, w_gate, group_sizes)
    u_proj = grouped_matmul(xs, w_up, group_sizes)
    act = jax.nn.silu(g_proj) * u_proj
    ys = grouped_matmul(act, w_down, group_sizes)  # [kn, h]

    y_tok = _perm_rows(ys, inv, order).reshape(n, top_k, h)
    out = jnp.sum(y_tok * gate_v[:, :, None].astype(x.dtype), axis=1)
    return out.reshape(b, s, h), aux


# ``moe_held_experts_mlp``'s stats under the names a served model hands them
# back by (``ServedModel.program_counters``)
HELD_EXPERTS_COUNTERS = {"moe_pairs_total": "pairs",
                         "moe_held_pairs_total": "held",
                         "moe_experts_hit_total": "experts_hit",
                         "moe_weight_streams_total": "weight_streams"}


def held_experts_counters(stats):
    """A block's third result from ``moe_held_experts_mlp``'s ``stats``
    (``None`` for a layer with no routed experts)."""
    return None if stats is None else {
        name: stats[key] for name, key in HELD_EXPERTS_COUNTERS.items()}


@part("router")
def moe_held_experts_mlp(x, wr, w_gate, w_up, w_down, *, top_k, first,
                         score="sigmoid", norm_topk=True, scale=1.0,
                         valid=None, x_route=None, bias=None):
    """One chip's SHARE of a routed expert layer under expert parallelism:
    route ``x`` [n, h] over all ``E`` router outputs (``wr`` [h, E]), keep
    the (token, choice) pairs whose expert lies in ``[first, first +
    count)`` — the experts this chip holds, ``count = w_gate.shape[0]``
    stacked weights — sort them by expert, run them through three grouped
    matmuls (``kernels/grouped_matmul.py``: megablox on the TPU, whose grid
    covers only the rows that met a held expert) and sum each token's
    weighted results. ``w_gate`` ``None``: an UNGATED expert, two matrices and
    ``down(relu(up(x))^2)`` (two grouped matmuls; up's result leaves the
    kernel float32, is squared there and rounded once into down: a square
    doubles a rounding's relative error). What the other experts would have
    added is NOT here:
    under ``ep`` it arrives by the exchange; a chip alone returns its part.
    With ``first = 0`` and ``count = E`` the share is the WHOLE layer: every
    routed pair is held. The grouped matmuls' tiles follow from the shapes
    (``grouped_matmul.choose_tiling``: the rows a held expert can expect are
    the routed pairs ÷ ``E``), and each hands back the rows' dtype: the
    kernel rounds its float32 accumulator once. ``x_route`` [n, h]: what the
    router scores instead of ``x`` — the float32 input of a model whose
    router is float32, where ``x`` is already rounded to the experts' dtype.
    ``bias`` [E] float32: the router's selection bias (``_route``); ``None``:
    none.

    ``valid`` [n] bool marks the rows that hold a real token (a padded
    prefill window, an idle decode row): the others route nowhere. Returns
    ``(y [n, h] float32, stats)``, ``stats`` int32 scalars: ``pairs`` (routed
    pairs of real tokens), ``held`` (those that met a held expert),
    ``experts_hit`` (held experts that got a row: those whose weights the
    call streams), ``weight_streams`` (the times an expert's gate / up
    weights are streamed: ``experts_hit`` where their tiling holds the
    contraction in one tile, else each such expert once for every row tile
    its rows span — the kernel re-reads a weight block whenever its index
    changed, and a tiled k changes it inside every visit).

    In a device trace (``observability.trace.parts``) the three grouped
    matmuls are the ``experts`` part of the step and everything else here —
    scores, top-k, the sort and gather into their layout, the activation
    between them, the weighted combine back — its ``router``."""
    from ...kernels.grouped_matmul import choose_tiling, grouped_matmul

    n, h = x.shape
    count, _, w = w_up.shape
    kn = top_k * n
    # what leaves the first matmul(s): the rows' dtype into a gate's product,
    # float32 into an ungated expert's square
    mid = x.dtype if w_gate is not None else jnp.dtype(jnp.float32)

    def tiles(k_dim, n_dim, out):
        return choose_tiling(kn, k_dim, n_dim, groups=count,
                             rows_per_group=kn / wr.shape[1],
                             lhs_item=x.dtype.itemsize,
                             rhs_item=w_up.dtype.itemsize,
                             out_item=out.itemsize)

    tile_in, tile_out = tiles(h, w, mid), tiles(w, h, x.dtype)

    def gmm(lhs, rhs, tiling, out=None):
        # the kernel's call alone is ``experts``
        with part("experts"):
            return grouped_matmul(lhs, rhs, group_sizes, tiling=tiling,
                                  out_dtype=lhs.dtype if out is None else out)

    gate_v, gate_i, _aux = _route(x if x_route is None else x_route, wr,
                                  top_k, score=score,
                                  norm_topk=norm_topk, scale=scale,
                                  precision=jax.lax.Precision.HIGHEST,
                                  bias=bias)
    local = gate_i - first                                    # [n, k]
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    # held pairs first, grouped by expert; the rest sort past the last group
    key = jnp.where(held, local, count).reshape(kn)
    order = jnp.argsort(key, stable=True)
    inv = jnp.zeros((kn,), jnp.int32).at[order].set(
        jnp.arange(kn, dtype=jnp.int32))  # int scatter, not a second sort
    group_sizes = jnp.bincount(key, length=count + 1)[:count]

    xs = jnp.take(x, order // top_k, axis=0)                  # [kn, h]
    if w_gate is None:
        act = jnp.square(jax.nn.relu(gmm(xs, w_up, tile_in, mid)))
    else:
        g_proj, u_proj = gmm(xs, w_gate, tile_in), gmm(xs, w_up, tile_in)
        act = jax.nn.silu(g_proj.astype(jnp.float32)) * u_proj
    ys = gmm(act.astype(x.dtype), w_down, tile_out)           # [kn, h]
    # rows past the groups are whatever the kernel left there: select, never
    # multiply
    y_tok = jnp.where(held[:, :, None],
                      jnp.take(ys, inv, axis=0).reshape(n, top_k, h)
                      .astype(jnp.float32), 0.0)
    out = jnp.sum(y_tok * gate_v[:, :, None], axis=1)
    real = jnp.int32(n) if valid is None else jnp.sum(valid, dtype=jnp.int32)
    hit = group_sizes > 0
    experts_hit = jnp.sum(hit, dtype=jnp.int32)
    tm, tk, _ = tile_in
    if tk >= h:
        streams = experts_hit
    else:  # the row tiles each held expert's rows [start, end) span
        ends = jnp.cumsum(group_sizes)
        spans = (ends - 1) // tm - (ends - group_sizes) // tm + 1
        streams = jnp.sum(jnp.where(hit, spans, 0), dtype=jnp.int32)
    stats = {"pairs": real * top_k,
             "held": jnp.sum(held, dtype=jnp.int32),
             "experts_hit": experts_hit, "weight_streams": streams}
    return out, stats


def _moe_mlp_sort(x, wg, w_gate, w_up, w_down, *, top_k, capacity_factor,
                  ep_degree):
    """All [*, h]-row movement is GATHERS — TPU scatters of wide rows
    serialize, so the two scatters here touch only int32 index vectors
    (slot->source map and inverse permutation)."""
    b, s, h = x.shape
    n = b * s
    e = wg.shape[1]
    kn = top_k * n
    cap = max(int(math.ceil(capacity_factor * top_k * n / e)), top_k)

    xt = x.reshape(n, h)
    gate_v, gate_i, aux = _route(xt, wg, top_k)

    # slot-major flattening (all 1st choices before any 2nd choice), then a
    # stable sort by expert groups tokens while preserving choice priority
    flat_e = gate_i.T.reshape(kn)
    flat_g = gate_v.T.reshape(kn)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))  # [e] group offsets
    pos = jnp.arange(kn, dtype=jnp.int32) - starts[sorted_e]
    keep = pos < cap
    # dropped entries land on a scratch slot past the buffer
    slot = jnp.where(keep, sorted_e * cap + pos, e * cap)
    tok = order % n  # flat index j = choice*n + token

    # dispatch: slot -> source token map (int scatter), then one row gather;
    # unfilled slots point at a zero row
    slot_src = jnp.full((e * cap + 1,), n, jnp.int32).at[slot].set(
        tok.astype(jnp.int32), mode="drop")[:-1]
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, h), x.dtype)])
    buf = xt_pad[slot_src]

    expert_out = _expert_ffn(buf.reshape(e, cap, h), w_gate, w_up,
                             w_down, ep_degree).reshape(e * cap, h)

    # combine: gather each kept choice's output row, undo the sort with the
    # inverse permutation (int scatter + gather), then sum the k choices
    contrib = jnp.where(
        keep[:, None],
        expert_out[jnp.clip(slot, 0, e * cap - 1)],
        jnp.zeros((), x.dtype)) * flat_g[order][:, None].astype(x.dtype)
    inv = jnp.zeros((kn,), jnp.int32).at[order].set(
        jnp.arange(kn, dtype=jnp.int32))
    out = jnp.sum(contrib[inv].reshape(top_k, n, h), axis=0)
    return out.reshape(b, s, h), aux


def _moe_mlp_einsum(x, wg, w_gate, w_up, w_down, *, top_k, capacity_factor,
                    ep_degree):
    b, s, h = x.shape
    n = b * s
    e = wg.shape[1]
    cap = max(int(math.ceil(capacity_factor * top_k * n / e)), top_k)

    xt = x.reshape(n, h)
    gate_v, gate_i, aux = _route(xt, wg, top_k)

    # slot-major one-hot so the 1st choice wins capacity over 2nd choices
    oh = jax.nn.one_hot(gate_i.T.reshape(top_k * n), e, dtype=jnp.float32)
    pos = (jnp.cumsum(oh, axis=0) - 1.0) * oh  # [k*n, e] position in expert
    pos_in_e = jnp.sum(pos, axis=-1)  # [k*n]
    keep = (pos_in_e < cap).astype(jnp.float32)[:, None] * oh  # [k*n, e]
    # dispatch/combine [k*n, e, cap]
    cap_oh = jax.nn.one_hot(pos_in_e.astype(jnp.int32), cap, dtype=jnp.float32)
    disp = keep[:, :, None] * cap_oh[:, None, :]
    disp = disp.reshape(top_k, n, e, cap).transpose(1, 0, 2, 3)  # [n, k, e, cap]
    combine = disp * gate_v[:, :, None, None]
    disp = jnp.sum(disp, axis=1)  # [n, e, cap]
    combine = jnp.sum(combine, axis=1)

    expert_in = jnp.einsum("nec,nh->ech", disp.astype(x.dtype), xt)
    expert_out = _expert_ffn(expert_in, w_gate, w_up, w_down, ep_degree)
    out = jnp.einsum("ech,nec->nh", expert_out, combine.astype(x.dtype))
    return out.reshape(b, s, h), aux


def _ep_constraint(t, ep_degree):
    if ep_degree <= 1:
        return t
    from ...distributed.meta_parallel.mp_layers import constrain_spec

    return constrain_spec(t, ("ep", None, None))


class ExpertMLP(Layer):
    """Stacked per-expert SwiGLU FFN weights, expert dim sharded over 'ep'."""

    def __init__(self, num_experts, hidden_size, intermediate_size):
        super().__init__()
        e, h, i = num_experts, hidden_size, intermediate_size
        self.gate = self.create_parameter(
            [e, h, i], default_initializer=I.XavierUniform())
        self.up = self.create_parameter(
            [e, h, i], default_initializer=I.XavierUniform())
        self.down = self.create_parameter(
            [e, i, h], default_initializer=I.XavierUniform())
        self.gate.dist_spec = P("ep", None, "mp")
        self.up.dist_spec = P("ep", None, "mp")
        self.down.dist_spec = P("ep", "mp", None)


class MoELayer(Layer):
    """Gated expert layer (role of the post-snapshot reference MoELayer;
    dispatch = global_scatter, combine = global_gather, both emerging from
    GSPMD on the einsums given the 'ep' placement).

    recompute_interval/group args kept for API shape.
    """

    def __init__(self, d_model, num_experts, intermediate_size=None, top_k=2,
                 capacity_factor=1.25, gate=None, recompute_interval=0,
                 group=None):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = float(capacity_factor)
        intermediate_size = intermediate_size or 4 * d_model
        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.XavierUniform())
        self.experts = ExpertMLP(num_experts, d_model, intermediate_size)

    def forward(self, x):
        from ...distributed.mesh import get_mesh_env
        from ...framework import flags as flags_mod

        env = get_mesh_env()
        ep = env.get_dim("ep") if env is not None else 1
        mode = flags_mod.get_flags("FLAGS_moe_dispatch")["FLAGS_moe_dispatch"]
        out, aux = _moe_mlp(x, self.gate_weight, self.experts.gate,
                            self.experts.up, self.experts.down, top_k=self.top_k,
                            capacity_factor=self.capacity_factor, ep_degree=ep,
                            dispatch=mode)
        record_aux(aux)
        return out
