"""Flash attention — Pallas TPU kernels (forward + backward).

The fused_attention_op.cu / fmha_ref.h analogue (reference:
paddle/fluid/operators/fused/), re-designed for the MXU:

- forward: q-block × k-block grid with online softmax — fp32 accumulators in
  VMEM scratch persist across the (sequential) k-block grid steps, logits
  never touch HBM, K/V stream one block at a time so VMEM use is
  O(block_q·d + block_k·d) at any sequence length. Also emits the per-row
  log-sum-exp (lse) needed by the backward kernels and by ring-attention
  block merging.
- backward: two Pallas kernels (dk/dv with a q-block inner grid, dq with a
  k-block inner grid) using the saved lse — the standard flash backward; the
  full [sq, sk] probability matrix is never materialized in HBM. lse and
  delta reach them as dense rows, not as lane-padded columns.
- `q_offset`: global-position offset added to q positions for the causal
  mask, so a context-parallel rank can attend a remote K/V chunk with the
  correct global causality (paddle_tpu.distributed.context_parallel rides
  this; offset lands in SMEM as a scalar input).

On CPU (tests / virtual meshes) the same kernels run in Pallas interpret
mode, so one code path is exercised everywhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# From a sweep on an earlier set-up of the v5e (1.16B Llama @ seq 2048;
# (1024,2048) exceeded VMEM), not re-measured by the benchmark: block sizes
# as a function of shape are ROADMAP D4b. Override per-call or via
# FLAGS_flash_block_q/k.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_NEG = -1e30


def _interpret() -> bool:
    # CPU (ring/Ulysses tests on the virtual mesh) interprets; every other
    # backend compiles. No fallback: if the backend cannot be asked, raise.
    return jax.default_backend() == "cpu"


def _pick_block(s: int, pref: int) -> int:
    """Largest block <= pref that divides s (so no rows/keys are dropped)."""
    b = min(pref, s)
    if s % b == 0:
        return b
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if cand <= pref and s % cand == 0:
            return cand
    raise ValueError(
        f"flash attention needs the sequence length ({s}) divisible by a "
        f"block size that is a multiple of 8; pad the sequence")


# -- forward ------------------------------------------------------------------

def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = pl.program_id(1) * block_q
    k_start = ki * block_k

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + off_ref[0] + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # zero masked entries explicitly: for a fully-masked row m_new stays at
        # _NEG and exp(s - m_new) would be 1, turning the row into mean(V)
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip k blocks fully above the (offset) diagonal
        @pl.when(k_start <= q_start + off_ref[0] + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-30))


def _flash_fwd(q, k, v, offset, causal, scale, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    grid = (bh, sq // block_q, sk // block_k)
    off = jnp.asarray(offset, jnp.int32).reshape(1)
    out, lse3 = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        name="pt_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(),
    )(off, q, k, v)
    return out, lse3[..., 0]


# -- backward -----------------------------------------------------------------

def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = pl.program_id(1) * block_k

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = jnp.expand_dims(lse_ref[0, 0], -1)  # [block_q, 1]
        delta = jnp.expand_dims(delta_ref[0, 0], -1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + off_ref[0] + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - lse), 0.0)  # [bq, bk] f32
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(q_start + off_ref[0] + block_q - 1 >= k_start)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = pl.program_id(1) * block_q
    k_start = ki * block_k

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = jnp.expand_dims(lse_ref[0, 0], -1)  # [block_q, 1]
        delta = jnp.expand_dims(delta_ref[0, 0], -1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + off_ref[0] + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(k_start <= q_start + off_ref[0] + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, dlse, offset, causal, scale,
               block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    off = jnp.asarray(offset, jnp.int32).reshape(1)
    # delta_i = sum_d dO*O - dlse folds the lse cotangent into the same ds
    # formula (d lse/d s_ij = p_ij)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    # lse and delta go in as ROWS ([bh, 1, sq], a block [1, 1, block_q]) and
    # the kernels turn a block into the column they subtract: as columns
    # [bh, sq, 1] Mosaic's operands are padded to 128 lanes (64 MiB each at
    # 64 x 2048), which XLA wrote out of the dense delta — and, where the
    # recompute hands lse back from its stack, out of the dense lse — once a
    # layer (3.1 ms of cell 1's step, PERF.md section 6, PR 51)
    if block_q % 128 and block_q != sq and not _interpret():
        raise ValueError(
            f"flash attention's backward reads lse in row blocks: its q "
            f"block ({block_q}) must be a multiple of 128 or the whole "
            f"sequence ({sq})")
    lse = lse[:, None, :]
    delta = delta[:, None, :]

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        name="pt_flash_bwd_dkv",
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(off, q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        name="pt_flash_bwd_dq",
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(off, q, k, v, do, lse, delta)
    return dq, dk, dv


# -- differentiable wrapper (bh, s, d layout) ---------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_lse_bhsd(q, k, v, offset, causal, scale, block_q, block_k,
                    bwd_block_q, bwd_block_k, named):
    return _flash_fwd(q, k, v, offset, causal, scale, block_q, block_k)


def _flash_lse_fwd(q, k, v, offset, causal, scale, block_q, block_k,
                   bwd_block_q, bwd_block_k, named):
    o, lse = _flash_fwd(q, k, v, offset, causal, scale, block_q, block_k)
    if named:
        # the names sit on the values the backward READS (the residuals
        # below), so a recompute whose policy saves them (stage_stack.
        # remat_wrap: every policy) finds its residuals without the forward
        # kernel and drops it from the replayed layer. A name on the op's
        # outputs would save copies nothing reads.
        o = checkpoint_name(o, "flash_o")
        lse = checkpoint_name(lse, "flash_lse")
    return (o, lse), (q, k, v, o, lse, offset)


def _flash_lse_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
                   named, res, cts):
    q, k, v, o, lse, offset = res
    do, dlse = cts
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, dlse, offset, causal, scale,
                            bwd_block_q or block_q, bwd_block_k or block_k)
    return dq, dk, dv, None


_flash_lse_bhsd.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _default_blocks():
    """Tunable via FLAGS_flash_block_q / FLAGS_flash_block_k (live-read so a
    bench sweep or user config changes take effect without re-import).
    FLAGS_flash_bwd_block_q/k override the BACKWARD kernels' tiling
    separately (0 = same as forward): the dkv/dq kernels keep more f32
    operands live in VMEM than the forward, so their best block shape is
    smaller."""
    try:
        from ..framework import flags as flags_mod

        f = flags_mod.get_flags(["FLAGS_flash_block_q", "FLAGS_flash_block_k",
                                 "FLAGS_flash_bwd_block_q",
                                 "FLAGS_flash_bwd_block_k"])
        return (int(f.get("FLAGS_flash_block_q") or DEFAULT_BLOCK_Q),
                int(f.get("FLAGS_flash_block_k") or DEFAULT_BLOCK_K),
                int(f.get("FLAGS_flash_bwd_block_q") or 0),
                int(f.get("FLAGS_flash_bwd_block_k") or 0))
    except Exception:
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, 0, 0


def flash_attention_with_lse(q, k, v, offset=0, causal=False, scale=None,
                             block_q: int = None, block_k: int = None,
                             named: bool = False):
    """q/k/v: [bh, s, d]. Returns (out [bh, sq, d], lse [bh, sq] fp32).
    `offset` shifts q's global positions for the causal mask (ring attention).
    ``named``: when differentiated, the backward's residuals ``o`` / ``lse``
    carry the names ``flash_o`` / ``flash_lse``, which a layer's recompute
    keeps (one layer's attention a call: the dense entry below asks for it;
    the ring's ``cp`` partial calls a layer do not)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    dq_, dk_, bbq, bbk = _default_blocks()
    block_q = dq_ if block_q is None else block_q
    block_k = dk_ if block_k is None else block_k
    return _flash_lse_bhsd(q, k, v, jnp.asarray(offset, jnp.int32),
                           bool(causal), float(scale), int(block_q),
                           int(block_k), int(bbq), int(bbk), bool(named))


def flash_attention(q, k, v, causal: bool = False, scale: float = None,
                    block_q: int = None, block_k: int = None):
    """q/k/v: [batch, seq, heads, head_dim] (paddle layout). Differentiable."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qm = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    km = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
    vm = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, d)
    # self-attention with sk>=sq: rows see the key prefix plus the diagonal
    offset = sk - sq if causal else 0
    om, _ = flash_attention_with_lse(qm, km, vm, offset, causal, float(scale),
                                     block_q, block_k, named=True)
    return jnp.moveaxis(om.reshape(b, h, sq, d), 1, 2)
