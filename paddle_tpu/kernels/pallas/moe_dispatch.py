"""Fused MoE routing/dispatch — Pallas kernels feeding ``grouped_matmul``.

The r04 probe pinned the MoE bottleneck on routing/dispatch, not the
expert matmuls (``dispatch_share`` 0.148): the composed paths spend their
time in XLA gather/scatter soup around the FFN. This module is the
dropless fused answer (``FLAGS_moe_dispatch='fused'``):

- **routing kernel** — ONE sequential-grid Pallas kernel does the whole
  router: gate logits (x @ wg on the MXU), f32 softmax, iterative top-k
  select, gate renormalization, AND the "sort by expert" — per-expert
  running counters live in VMEM scratch across the grid, so every
  (token, choice) leaves the kernel with its position in its expert's
  contiguous row block (token-major order, exactly the stable-argsort
  order of the ``gmm`` path — no argsort executed). Per-expert counts
  and the aux-loss sufficient statistics (prob sums, top-1 counts) fall
  out of the same pass.
- **dispatch/combine kernels** — row movement into/out of the grouped
  layout runs as scalar-prefetch Pallas gathers: the destination map is
  prefetched into SMEM and each grid step DMAs exactly one source row
  block, so the wide-row movement never lowers to an XLA scatter (TPU
  serializes those). Custom VJPs keep the backward gather-only too —
  dispatch's backward IS a combine, combine's backward IS a dispatch
  (plus a rowwise dot for the gate grads).

The expert FFN itself stays on ``kernels.grouped_matmul`` (megablox on
TPU, ``ragged_dot`` on CPU). Differentiability through the ROUTER is
preserved by a recompute VJP: the backward re-traces softmax → top-k
pick → renorm → aux in plain XLA from the saved ``gate_i`` (one [n, e]
matmul — noise next to the FFN backward), matching ``_route``'s
gradients exactly.

Constraints: single-device experts (like ``gmm``; ragged groups cannot
cross a static-shape all_to_all) and ``num_experts <= 128`` (the expert
axis rides the lane dimension). ``nn/layer/moe.py`` falls back to the
index path outside them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register_kernel, resolve
from ._common import pick_rows as _pick_rows

__all__ = ["fused_moe_mlp", "fused_route", "MAX_EXPERTS"]

MAX_EXPERTS = 128  # the expert axis rides the lane dim of one block


# ---------------------------------------------------------------------------
# routing: top-k select + position-in-expert in one kernel
# ---------------------------------------------------------------------------

def _routing_kernel(x_ref, wg_ref, gv_ref, gi_ref, pos_ref, cnt_ref,
                    me_ref, ce_ref, carry, me_acc, ce_acc, *, top_k, e):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)
        me_acc[...] = jnp.zeros_like(me_acc)
        ce_acc[...] = jnp.zeros_like(ce_acc)

    x = x_ref[...].astype(jnp.float32)                     # [bn, h]
    wg = wg_ref[...].astype(jnp.float32)                   # [h, e]
    logits = jax.lax.dot_general(x, wg, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    mx = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - mx)
    p = p / jnp.sum(p, axis=-1, keepdims=True)             # [bn, e]
    bn = p.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bn, e), 1)
    klane = jax.lax.broadcasted_iota(jnp.int32, (bn, top_k), 1)
    # everything stays 2-D ([bn, 1] columns, [bn, e] rows): the TPU
    # lowering has no argmax/cumsum/stack on 1-D vectors, so the top-k
    # pick is max + first-lane-at-max, and the outputs are assembled by
    # lane-select instead of stack
    masked = p
    gv = jnp.zeros((bn, top_k), jnp.float32)
    gi = jnp.zeros((bn, top_k), jnp.int32)
    ohs = []
    for c in range(top_k):                                 # iterative top-k
        vmax = jnp.max(masked, axis=-1, keepdims=True)     # [bn, 1]
        idx = jnp.min(jnp.where(masked == vmax, lane, e), axis=-1,
                      keepdims=True)                       # first max lane
        oh = lane == idx                                   # [bn, e]
        ohs.append(oh)
        gv = jnp.where(klane == c, vmax, gv)
        gi = jnp.where(klane == c, idx, gi)
        masked = jnp.where(oh, -1.0, masked)
    gv = gv / jnp.maximum(jnp.sum(gv, axis=-1, keepdims=True), 1e-9)

    # position-in-expert, token-major (row r = t*k + c): the rows of
    # expert x ahead of (t, c) are every pick of x by an earlier token
    # plus this token's earlier choices. The exclusive prefix over tokens
    # is a strictly-lower-triangular matmul on the MXU (the TPU lowering
    # has no cumsum; counts < 2^24 are exact in f32), and the running
    # per-expert counters persist in scratch across the sequential grid —
    # this IS the stable sort-by-expert, without executing a sort
    tot = sum(oh.astype(jnp.float32) for oh in ohs)        # [bn, e]
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    tril = (col < row).astype(jnp.float32)
    excl = jax.lax.dot_general(tril, tot, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    base = carry[...]                                      # [1, e] int32
    ahead = excl.astype(jnp.int32) + base                  # [bn, e]
    pos = jnp.zeros((bn, top_k), jnp.int32)
    for c, oh in enumerate(ohs):
        pos_c = jnp.sum(jnp.where(oh, ahead, 0), axis=-1, keepdims=True)
        pos = jnp.where(klane == c, pos_c, pos)
        ahead = ahead + oh.astype(jnp.int32)
    pos_ref[...] = pos
    carry[...] = base + jnp.sum(tot, axis=0, keepdims=True).astype(jnp.int32)
    me_acc[...] += jnp.sum(p, axis=0, keepdims=True)
    ce_acc[...] += jnp.sum(ohs[0].astype(jnp.float32), axis=0, keepdims=True)
    gv_ref[...] = gv
    gi_ref[...] = gi

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        cnt_ref[...] = carry[...]
        me_ref[...] = me_acc[...]
        ce_ref[...] = ce_acc[...]


def _routing_pallas(xt, wg, top_k, interpret):
    n, h = xt.shape
    e = wg.shape[1]
    bn = _pick_rows(n)
    grid = (n // bn,)
    gv, gi, pos, cnt, me, ce = pl.pallas_call(
        functools.partial(_routing_kernel, top_k=top_k, e=e),
        name="pt_moe_route",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((h, e), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, top_k), lambda i: (i, 0)),
            pl.BlockSpec((bn, top_k), lambda i: (i, 0)),
            pl.BlockSpec((bn, top_k), lambda i: (i, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, top_k), jnp.float32),
            jax.ShapeDtypeStruct((n, top_k), jnp.int32),
            jax.ShapeDtypeStruct((n, top_k), jnp.int32),
            jax.ShapeDtypeStruct((1, e), jnp.int32),
            jax.ShapeDtypeStruct((1, e), jnp.float32),
            jax.ShapeDtypeStruct((1, e), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, e), jnp.int32),
            pltpu.VMEM((1, e), jnp.float32),
            pltpu.VMEM((1, e), jnp.float32),
        ],
        interpret=interpret,
    )(xt, wg)
    return gv, gi, pos, cnt.reshape(e), me.reshape(e), ce.reshape(e)


def _routing_reference(xt, wg, top_k):
    """The jnp reference: identical math, token-major cumsum positions."""
    n, _ = xt.shape
    e = wg.shape[1]
    logits = jnp.matmul(xt.astype(jnp.float32), wg.astype(jnp.float32))
    p = jax.nn.softmax(logits, axis=-1)
    gv, gi = jax.lax.top_k(p, top_k)
    gv = gv / jnp.maximum(jnp.sum(gv, axis=-1, keepdims=True), 1e-9)
    flat_e = gi.reshape(n * top_k)                         # token-major
    oh = flat_e[:, None] == jnp.arange(e, dtype=flat_e.dtype)[None, :]
    ohi = oh.astype(jnp.int32)
    pos = jnp.sum(jnp.where(oh, jnp.cumsum(ohi, axis=0) - 1, 0),
                  axis=-1).reshape(n, top_k)
    cnt = jnp.sum(ohi, axis=0)
    me = jnp.sum(p, axis=0)
    ce = jnp.sum(jax.nn.one_hot(gi[:, 0], e, dtype=jnp.float32), axis=0)
    return gv, gi.astype(jnp.int32), pos.astype(jnp.int32), cnt, me, ce


def _route_diff(xt, wg, gate_i, top_k, e):
    """The differentiable router chain, recomputed from the saved top-k
    pick: softmax -> gather the chosen probs -> renorm, plus the
    Switch/GShard aux. Gradients match ``nn.layer.moe._route`` (the
    top-1 frequency term is piecewise-constant there too)."""
    p = jax.nn.softmax(
        jnp.matmul(xt.astype(jnp.float32), wg.astype(jnp.float32)), axis=-1)
    v = jnp.take_along_axis(p, gate_i, axis=1)
    gate = v / jnp.maximum(jnp.sum(v, axis=-1, keepdims=True), 1e-9)
    me = jnp.mean(p, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_i[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return gate, aux


def _route_impl(xt, wg, top_k, impl):
    gv, gi, pos, cnt, me, ce = (
        _routing_pallas(xt, wg, top_k,
                        interpret=(impl == "interpret"))
        if impl in ("pallas", "interpret")
        else _routing_reference(xt, wg, top_k))
    n = xt.shape[0]
    e = wg.shape[1]
    aux = e * jnp.sum((me / n) * (ce / n))
    # index outputs leave the custom-vjp boundary as FLOATS: an integer
    # output of a custom_vjp gets a float0 tangent, and the scanned
    # decoder stack's linearization materializes those into downstream
    # int arithmetic (cumsum/sub) — float outputs carry ordinary zero
    # tangents instead. Exact for values < 2^24 (kn rows); callers cast
    # back to int32 (a nondiff convert with a symbolic-zero tangent).
    return (gv, gi.astype(jnp.float32), pos.astype(jnp.float32),
            cnt.astype(jnp.float32), aux)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_route(xt, wg, top_k, impl):
    """(gate_v, gate_i, pos_in_expert, counts, aux): the full router in
    one kernel pass; the index outputs ride as f32 (see ``_route_impl``).
    Differentiable in (xt, wg) through gate_v and aux."""
    return _route_impl(xt, wg, top_k, impl)


def _fused_route_fwd(xt, wg, top_k, impl):
    out = _route_impl(xt, wg, top_k, impl)
    return out, (xt, wg, out[1].astype(jnp.int32))


def _fused_route_bwd(top_k, impl, res, cts):
    xt, wg, gate_i = res
    d_gv, _d_gi, _d_pos, _d_cnt, d_aux = cts
    e = wg.shape[1]
    _, vjp = jax.vjp(
        lambda x, w: _route_diff(x, w, gate_i, top_k, e), xt, wg)
    dx, dw = vjp((d_gv.astype(jnp.float32), d_aux.astype(jnp.float32)))
    return dx.astype(xt.dtype), dw.astype(wg.dtype)


fused_route.defvjp(_fused_route_fwd, _fused_route_bwd)


# ---------------------------------------------------------------------------
# row movement: scalar-prefetch gather / weighted combine
# ---------------------------------------------------------------------------

def _gather_kernel(idx_ref, src_ref, out_ref):
    del idx_ref  # consumed by the index maps
    out_ref[...] = src_ref[...]


def _gather_rows(src, idx, impl):
    """out[i] = src[idx[i]] — the grouped-layout gather. One row block
    per grid step, destination-ordered; the index vector rides SMEM via
    scalar prefetch so the DMA engine walks it ahead of compute."""
    if impl == "reference":
        return jnp.take(src, idx, axis=0)
    n = idx.shape[0]
    h = src.shape[1]
    # rows ride as [rows, 1, h]: a (1, h) block of a 2-D array is not a
    # tile the TPU lowering accepts; (1, h) equal to the array's last two
    # dims is
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, 1, h),
                               lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, h), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel, name="pt_moe_dispatch", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, h), src.dtype),
        interpret=(impl == "interpret"),
    )(idx, src[:, None, :]).reshape(n, h)


def _make_combine_kernel(top_k):
    def kernel(dest_ref, g_ref, *refs):
        del dest_ref
        y_refs, out_ref = refs[:top_k], refs[top_k]
        g = g_ref[0].astype(jnp.float32)                   # [1, k]
        acc = jnp.zeros(out_ref.shape[1:], jnp.float32)    # [1, h]
        for c in range(top_k):
            acc += g[:, c:c + 1] * y_refs[c][0].astype(jnp.float32)
        out_ref[0] = acc.astype(out_ref.dtype)
    return kernel


def _combine_rows(y, gates, dest2, impl, out_dtype=None):
    """out[t] = sum_c gates[t, c] * y[dest2[t, c]] — the scatter-back,
    expressed as k gathers + an f32 weighted add per token row."""
    n, k = dest2.shape
    out_dtype = out_dtype or y.dtype
    if impl == "reference":
        rows = jnp.take(y, dest2.reshape(n * k), axis=0).reshape(n, k, -1)
        return jnp.sum(rows.astype(jnp.float32) *
                       gates[..., None].astype(jnp.float32),
                       axis=1).astype(out_dtype)
    h = y.shape[1]
    in_specs = [pl.BlockSpec((1, 1, k), lambda i, d: (i, 0, 0))]
    for c in range(k):
        in_specs.append(pl.BlockSpec(
            (1, 1, h), functools.partial(
                lambda i, d, _c: (d[i * k + _c], 0, 0), _c=c)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, h), lambda i, d: (i, 0, 0)),
    )
    y3 = y[:, None, :]                 # row blocks: see _gather_rows
    # the destination map is prefetched FLAT: a 2-D [n, k] SMEM operand
    # pads its last dim to 128 lanes (2 MiB at n=4096 — over the chip's
    # 1 MiB of SMEM); 1-D costs 4 bytes per entry
    return pl.pallas_call(
        _make_combine_kernel(k), name="pt_moe_combine", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, h), out_dtype),
        interpret=(impl == "interpret"),
    )(dest2.reshape(n * k), gates[:, None, :], *([y3] * k)).reshape(n, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_dispatch(xt, src_tok, dest2, impl):
    """Grouped-layout gather with a GATHER-ONLY backward: the cotangent
    of ``xs[i] = xt[src_tok[i]]`` is a unit-gate combine through the same
    destination map — no [kn, h] scatter ever lowers."""
    return _gather_rows(xt, src_tok, impl)


def _fused_dispatch_fwd(xt, src_tok, dest2, impl):
    return _gather_rows(xt, src_tok, impl), (dest2,)


def _fused_dispatch_bwd(impl, res, g):
    (dest2,) = res
    ones = jnp.ones(dest2.shape, jnp.float32)
    # the gather preserves dtype, so the cotangent's dtype IS xt's
    d_xt = _combine_rows(g, ones, dest2, impl, out_dtype=g.dtype)
    return d_xt, None, None


_fused_dispatch.defvjp(_fused_dispatch_fwd, _fused_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_combine(ys, gates, dest2, g2f, impl):
    """Weighted scatter-back with a gather-only backward (``g2f`` maps
    each grouped row back to its flat (token, choice) row)."""
    return _combine_rows(ys, gates, dest2, impl)


def _fused_combine_fwd(ys, gates, dest2, g2f, impl):
    return _combine_rows(ys, gates, dest2, impl), (ys, gates, dest2, g2f)


def _fused_combine_bwd(impl, res, d_out):
    ys, gates, dest2, g2f = res
    n, k = dest2.shape
    kn = n * k
    src_tok = g2f // k
    gate_sorted = jnp.take(gates.reshape(kn), g2f)
    d_ys = (_gather_rows(d_out, src_tok, impl).astype(jnp.float32) *
            gate_sorted[:, None]).astype(ys.dtype)
    y_rows = _gather_rows(ys, dest2.reshape(kn), impl).reshape(n, k, -1)
    d_gates = jnp.sum(d_out[:, None, :].astype(jnp.float32) *
                      y_rows.astype(jnp.float32), axis=-1
                      ).astype(gates.dtype)
    return d_ys, d_gates, None, None


_fused_combine.defvjp(_fused_combine_fwd, _fused_combine_bwd)


# ---------------------------------------------------------------------------
# the fused dropless MoE MLP
# ---------------------------------------------------------------------------

def fused_moe_mlp(x, wg, w_gate, w_up, w_down, *, top_k, impl=None):
    """Dropless routed expert FFN, fused dispatch: [b, s, h] ->
    ([b, s, h], aux). Row order matches ``_moe_mlp_gmm``'s stable sort
    exactly (token-major positions), so parity with the composed paths
    is tolerance-tight. Executed FLOPs == activated FLOPs — no capacity
    padding, no drops; ``capacity_factor`` does not apply."""
    from ..grouped_matmul import grouped_matmul

    if impl is None:
        impl = resolve("moe_dispatch")
    b, s, h = x.shape
    n = b * s
    e = wg.shape[1]
    if e > MAX_EXPERTS:
        raise ValueError(
            f"fused MoE dispatch supports <= {MAX_EXPERTS} experts "
            f"(lane-dim constraint), got {e}; use FLAGS_moe_dispatch="
            f"'index'")
    kn = top_k * n

    xt = x.reshape(n, h)
    gate_v, gate_i_f, pos_f, counts_f, aux = fused_route(xt, wg, top_k,
                                                         impl)
    # back to ints OUTSIDE the custom-vjp boundary (nondiff converts)
    gate_i = gate_i_f.astype(jnp.int32)
    pos = pos_f.astype(jnp.int32)
    counts = counts_f.astype(jnp.int32)

    # dest[r] = grouped row of flat (token, choice) r: expert block offset
    # + position-in-expert (both from the routing kernel — no argsort)
    offsets = jnp.cumsum(counts) - counts                  # exclusive [e]
    dest2 = (jnp.take(offsets, gate_i) + pos).astype(jnp.int32)  # [n, k]
    dest = dest2.reshape(kn)
    rng = jnp.arange(kn, dtype=jnp.int32)
    # the ONE int32 scatter: grouped row -> flat row (and token = r // k)
    g2f = jnp.zeros((kn,), jnp.int32).at[dest].set(rng)
    src_tok = g2f // top_k

    xs = _fused_dispatch(xt, src_tok, dest2, impl)         # [kn, h] grouped
    g_proj = grouped_matmul(xs, w_gate, counts)
    u_proj = grouped_matmul(xs, w_up, counts)
    act = jax.nn.silu(g_proj) * u_proj
    ys = grouped_matmul(act, w_down, counts)               # [kn, h]

    out = _fused_combine(ys, gate_v, dest2, g2f, impl)
    return out.reshape(b, s, h).astype(x.dtype), aux


register_kernel(
    "moe_dispatch",
    doc="dropless MoE routing+dispatch: one routing kernel (top-k + "
        "sort-by-expert counters), scalar-prefetch gathers, gather-only "
        "VJPs, grouped_matmul FFN")
