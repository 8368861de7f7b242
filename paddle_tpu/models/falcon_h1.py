"""Falcon-H1 causal LM (TII, 2025; HF ``model_type`` ``falcon_h1``): every
block runs a Mamba-2 mixer and a grouped-query attention IN PARALLEL on one
normed input and sums both into one residual, then a SwiGLU MLP; muP
multipliers scale every path. The layer equations are written out in
``models/reference/falcon_h1.py`` (the plain float32 reference this file is
tested against).

One functional block, ``block_fn``, is the model: the ``nn.Layer`` forward
runs it with a dense causal ``attend`` and a fresh state, and
``serving.GenerationEngine`` runs the SAME function through the served-model
seam (``FalconH1Served``) with its paged ``attend`` and its slot-indexed
state arenas. Two programs of one recurrence: a window of tokens from a zero
state runs the chunked scan (chunks of ``mamba_chunk_size``, matmuls in
composed ``jnp``; ``ssd_chunked`` also goes on from a given state, which
this model does not ask of it) and hands back the FINAL state; one token
over a live state
runs one step (``kernels/pallas/ssm_step.py``).

Weights are created on the device, in the configuration's dtype, from
``paddle.seed``: nothing holds a float32 copy of the parameters anywhere.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..framework import random as random_mod
from ..nn import functional as F
from ..observability.trace.parts import part
from ..serving.served_model import ServedModel

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


@dataclass
class FalconH1Config:
    """The published ``config.json`` keys, letter for letter (defaults:
    Falcon-H1-34B-Instruct), plus ``dtype``."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000000000.0
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    tie_word_embeddings: bool = False
    num_logits_to_keep: int = 1
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_norm_before_gate: bool = False
    mamba_rms_norm: bool = True
    mamba_use_mlp: bool = True
    mlp_expansion_factor: int = 8
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    embedding_multiplier: float = 5.656854249492381
    key_multiplier: float = 0.011048543456039804
    lm_head_multiplier: float = 0.0078125
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.mlp_multipliers = tuple(self.mlp_multipliers)
        self.ssm_multipliers = tuple(self.ssm_multipliers)
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_n_heads x mamba_d_head != mamba_d_ssm")
        if self.mamba_n_heads % self.mamba_n_groups or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide into their groups")
        unsupported = [k for k, want in (
            ("attention_bias", False), ("mlp_bias", False),
            ("projectors_bias", False), ("mamba_proj_bias", False),
            ("mamba_conv_bias", True), ("mamba_rms_norm", True),
            ("mamba_norm_before_gate", False), ("hidden_act", "silu"),
            ("tie_word_embeddings", False)) if getattr(self, k) != want]
        if unsupported:
            raise ValueError(f"FalconH1Config: only the published setting "
                             f"of {unsupported} is implemented")

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return FalconH1Served(self)

    @staticmethod
    def falcon_h1_34b(**overrides):
        return FalconH1Config(**overrides)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: every mechanism present (GQA 4/2, two SSM
        groups, conv 4, chunks of 8), every multiplier as published."""
        return FalconH1Config(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, max_position_embeddings=256,
            mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
            mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8,
            dtype="float32"), **overrides})


def mup_vector(cfg: FalconH1Config):
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    return jnp.concatenate([jnp.full(n, m, F32) for n, m in zip(
        (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads),
        cfg.ssm_multipliers)])


# -- the functional model ------------------------------------------------------

# Precision: the weights and every matmul's operands are in the model's dtype
# (bfloat16 as published); the residual stream, the norms, every muP
# multiplier, RoPE, the conv, the recurrence and the logits are float32 (a
# multiplier rounded to bfloat16 would be off by up to 0.4 %, the same way at
# every position). The matmuls accumulate and hand back float32.

@part("norm")
def _rms(x, w, eps):
    """RMSNorm of a float32 stream; float32 out."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


def _mm(a, w):
    """``a @ w`` with ``a`` cast to the weight's dtype, float32 out."""
    return jnp.matmul(a.astype(w.dtype), w, preferred_element_type=F32)


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head at global positions ``pos``
    ([rows, W]); ``x`` is float32 [rows, W, heads, d]."""
    d = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = pos.astype(F32)[..., None] * inv                   # [rows, W, d/2]
    cos, sin = jnp.cos(f)[:, :, None, :], jnp.sin(f)[:, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ssd_chunked(x, dt, a, b, c, chunk: int, initial=None):
    """The Mamba-2 recurrence over a window, by chunks (the
    state-space-duality form: within a chunk a masked matmul, between
    chunks the recurrence on the chunk states), from ``initial`` [R, H, P,
    N] — the state a previous window left — or from ZERO (``None``). ``x``
    [R, W, H, P]; ``dt``
    [R, W, H] (after softplus; 0 where the position holds no token: the
    state passes through it unchanged); ``a`` [H]; ``b``, ``c``
    [R, W, H, N]. float32 throughout, matmuls at full precision (they are
    small, and the final state seeds every later decode step). Returns
    ``y`` [R, W, H, P] (without the ``D x`` skip) and the final state
    [R, H, P, N]."""
    R, W, H, P = x.shape
    pad = (-W) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    nC, Lc = (W + pad) // chunk, chunk
    xr, br, cr = (t.reshape(R, nC, Lc, H, -1) for t in (x, b, c))
    dtr = dt.reshape(R, nC, Lc, H)
    cum = jnp.cumsum(dtr * a, axis=2)                      # [R, nC, L, H]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # [.., l, s, H]
    tri = jnp.tril(jnp.ones((Lc, Lc), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    xdt = xr * dtr[..., None]
    cb = jnp.einsum("rclhn,rcshn->rclsh", cr, br, precision=_HI)
    y = jnp.einsum("rclsh,rcshp->rclhp", cb * decay, xdt, precision=_HI)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)              # [R, nC, L, H]
    states = jnp.einsum("rcsh,rcshp,rcshn->rchpn", to_end, xdt, br,
                        precision=_HI)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # [R, nC, H]
    s = jnp.zeros((R, H, P, b.shape[-1]), F32) if initial is None \
        else initial.astype(F32)
    before = []
    for ci in range(nC):
        before.append(s)
        s = chunk_decay[:, ci][:, :, None, None] * s + states[:, ci]
    before = jnp.stack(before, axis=1)                     # [R, nC, H, P, N]
    y = y + jnp.einsum("rclhn,rchpn,rclh->rclhp", cr, before, jnp.exp(cum),
                       precision=_HI)
    return y.reshape(R, W + pad, H, P)[:, :W], s


def causal_conv(xbc, tail, conv_w, conv_b, valid):
    """The depthwise causal conv over ``xbc`` [R, W, C] (float32) behind the
    ``tail`` [R, kc - 1, C] its previous window left (``None``: a fresh
    sequence, zeros), bias added, before the activation. Returns it and the
    conv's NEXT tail: the ``kc - 1`` inputs that end at the last REAL token
    (``valid`` [R, W])."""
    R, W, C = xbc.shape
    kc = conv_w.shape[1]
    if tail is None:
        tail = jnp.zeros((R, kc - 1, C), F32)
    seq = jnp.concatenate([tail, xbc], axis=1)             # [R, kc-1+W, C]
    conv = sum(seq[:, j:j + W] * conv_w[:, j].astype(F32)
               for j in range(kc)) + conv_b.astype(F32)
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)     # [R]
    new_tail = jax.vmap(lambda sq, n: jax.lax.dynamic_slice_in_dim(
        sq, n, kc - 1, axis=0))(seq, n_valid)
    return conv, new_tail


def mamba_scan(xh, dt, a, bg, cg, d, ssm, chunk: int, step: bool):
    """The recurrence on ``xh`` [R, W, H, P] with ``bg``, ``cg`` [R, W, G,
    N] by group. ``step``: ONE token over the live arena ``ssm`` (the
    kernel, ``kernels/pallas/ssm_step.py``, in place). Else the chunked scan
    from ``ssm`` — a row's own state, or ``None``: zero. Returns ``(y [R, W,
    H, P], the state after it)``."""
    H, G = xh.shape[2], bg.shape[2]
    if not step:
        bh, ch = (jnp.repeat(t, H // G, axis=2) for t in (bg, cg))
        y, ssm = ssd_chunked(xh, dt, a, bh, ch, chunk, ssm)
        return y + d[None, None, :, None] * xh, ssm
    if xh.shape[1] != 1:
        raise ValueError("one token a step over a live state")
    from ..kernels.pallas.ssm_step import ssm_step

    ssm, y = ssm_step(ssm, xh[:, 0], dt[:, 0], a, bg[:, 0], cg[:, 0], d)
    return y[:, None], ssm


def gated_norm(y, z, groups: int, eps: float, w):
    """``RMSNorm(y silu(z))`` over each of ``groups`` contiguous slices of
    the last axis, the weight after."""
    R, W, d = y.shape
    y = y * jax.nn.silu(z)
    yg = y.reshape(R, W, groups, d // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    return yg.reshape(R, W, d) * w.astype(F32)


@part("mixer")
def _ssm_branch(cfg: FalconH1Config, p, u, state, valid):
    """The Mamba-2 mixer on the normed input ``u`` [R, W, h] (float32). ``state``
    None: a fresh sequence (chunked scan from zero; returns the final
    state). ``state`` given: one token over the live arenas (one step).
    Returns ``(y [R, W, h], {"ssm": ..., "conv": ...})``."""
    R, W, _ = u.shape
    H, P, N, G = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                  cfg.mamba_n_groups)
    d_ssm, gn = cfg.mamba_d_ssm, G * cfg.mamba_d_state
    zxbcdt = _mm(u * cfg.ssm_in_multiplier, p["in_w"]) * mup_vector(cfg)
    z, xbc, dt = jnp.split(zxbcdt, [d_ssm, d_ssm + cfg.conv_dim], -1)
    conv, new_tail = causal_conv(
        xbc, None if state is None else state["conv"], p["conv_w"],
        p["conv_b"], valid)
    xs, b, c = jnp.split(jax.nn.silu(conv), [d_ssm, d_ssm + gn], -1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    dt = jnp.where(valid[..., None], dt, 0.0)              # [R, W, H]
    a = -jnp.exp(p["A_log"].astype(F32))
    d = p["D"].astype(F32)
    xh = xs.reshape(R, W, H, P)
    bg, cg = (t.reshape(R, W, G, N) for t in (b, c))
    y, ssm = mamba_scan(xh, dt, a, bg, cg, d,
                        None if state is None else state["ssm"],
                        cfg.mamba_chunk_size, step=state is not None)
    y = gated_norm(y.reshape(R, W, d_ssm), z, G, cfg.rms_norm_eps,
                   p["ssm_norm"])
    y = _mm(y, p["out_w"]) * cfg.ssm_out_multiplier
    return y, {"ssm": ssm, "conv": new_tail}


# The parts of the block (``observability.trace.parts``: what a device trace
# says of a served step). They sit on helpers so that ``block_fn``, which
# every window program traces once a layer, stays short.

@part("attn_proj")
def _qkv(cfg: FalconH1Config, p, u, pos):
    """The attention branch's q, k, v of the normed input, roped, in the
    weights' dtype."""
    R, W, _ = u.shape
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    wd = p["q_w"].dtype
    ua = u * cfg.attention_in_multiplier
    q = _mm(ua, p["q_w"]).reshape(R, W, nh, hd)
    k = (_mm(ua, p["k_w"]) * cfg.key_multiplier).reshape(R, W, kvh, hd)
    v = _mm(ua, p["v_w"]).reshape(R, W, kvh, hd)
    return (_rope(q, pos, cfg.rope_theta).astype(wd),
            _rope(k, pos, cfg.rope_theta).astype(wd), v.astype(wd))


@part("attn_proj")
def _attn_out(cfg: FalconH1Config, p, ctx):
    R, W = ctx.shape[:2]
    return _mm(ctx.reshape(R, W, -1), p["o_w"]) * \
        cfg.attention_out_multiplier


@part("mixer")
def _join(x, a, y):
    """The two branches' outputs meet the stream."""
    return x + a + y


@part("mlp")
def _mlp(cfg: FalconH1Config, p, x, v2):
    g0, g1 = cfg.mlp_multipliers
    m = _mm(v2, p["up_w"]) * jax.nn.silu(_mm(v2, p["gate_w"]) * g0)
    return x + _mm(m, p["down_w"]) * g1


def block_fn(cfg: FalconH1Config, p, x, pos, attend, state, valid):
    """One Falcon-H1 block. ``x`` [R, W, h], the float32 residual stream;
    ``pos`` [R, W] global positions; ``attend(q, k, v) -> ctx`` causal
    attention of ``q`` [R, W, heads, d] given this window's ``k``/``v``
    [R, W, kv_heads, d] (all in the weights' dtype); ``state``/``valid`` as
    in ``serving.served_model``."""
    u = _rms(x, p["input_norm"], cfg.rms_norm_eps)
    a = _attn_out(cfg, p, attend(*_qkv(cfg, p, u, pos)))
    y, state = _ssm_branch(cfg, p, u, state, valid)
    x = _join(x, a, y)
    return _mlp(cfg, p, x, _rms(x, p["ff_norm"], cfg.rms_norm_eps)), state


BLOCK_KEYS = ("input_norm", "q_w", "k_w", "v_w", "o_w", "in_w", "conv_w",
              "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "out_w",
              "ff_norm", "gate_w", "up_w", "down_w")


def _dense_attend(scale):
    """Causal attention within the window: every row a fresh sequence."""
    def attend(q, k, v):
        W, rep = q.shape[1], q.shape[2] // k.shape[2]
        k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
        att = jnp.einsum("rqhd,rkhd->rhqk", q, k).astype(F32) * scale
        att = jnp.where(jnp.tril(jnp.ones((W, W), bool)), att, -1e30)
        return jnp.einsum("rhqk,rkhd->rqhd",
                          jax.nn.softmax(att, -1).astype(q.dtype), v)

    return attend


@primitive("falcon_h1_block")
def _block_op(x, *weights, cfg_items):
    cfg = FalconH1Config(**dict(cfg_items))
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    out, _state = block_fn(cfg, dict(zip(BLOCK_KEYS, weights)),
                           x.astype(F32), pos,
                           _dense_attend(1.0 / math.sqrt(cfg.head_dim)),
                           None, jnp.ones((R, W), bool))
    return out


@primitive("falcon_h1_head")
def _head_op(x, norm_w, head_w, *, eps, mult):
    return _mm(_rms(x.astype(F32), norm_w, eps), head_w) * mult


# -- layers --------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    """``N(0, scale^2)`` drawn on the device in ``dtype``, from the device's
    own bit generator (``rbg``): 5 B threefry normals take a minute."""
    data = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    rbg = jax.random.wrap_key_data(jnp.resize(data, (4,)), impl="rbg")
    return (jax.random.normal(rbg, shape, F32) * scale).astype(dtype)


class _Weights(nn.Layer):
    """A bag of parameters created on the device in their final dtype."""

    def _normal(self, name, shape, std, dtype):
        """``N(0, std^2)``; ``std`` a scalar or one value a column."""
        d = dtype_mod.convert_dtype(dtype)
        data = _draw(random_mod.next_key(), jnp.asarray(std, F32),
                     shape=tuple(shape), dtype=d)
        setattr(self, name, self.create_parameter(
            list(shape), dtype=dtype,
            default_initializer=lambda _s, _d: data))

    def _given(self, name, value):
        setattr(self, name, self.create_parameter(
            list(value.shape), dtype=str(value.dtype),
            default_initializer=lambda _s, _d: value))


class FalconH1Block(_Weights):
    """One block's parameters (``BLOCK_KEYS``) and its forward.

    Random weights, muP-aware: a matrix whose output meets a multiplier
    ``m`` is drawn ``N(0, (1 / (sqrt(fan_in) m))^2)``, so that every path —
    each segment of ``in_proj`` with its own entry of ``ssm_multipliers`` —
    carries unit-scale signal the way a trained muP model's does, and a
    dropped multiplier changes its path by that factor. The SSM's own
    parameters follow Mamba-2's published init (``dt`` log-uniform in
    [1e-3, 1e-1] through ``dt_bias``, ``A`` uniform in [1, 16], ``D`` = 1,
    the conv uniform in +-1/sqrt(d_conv)), kept in float32."""

    def __init__(self, cfg: FalconH1Config):
        super().__init__()
        self._cfg_items = tuple(sorted(dataclasses.asdict(cfg).items()))
        h, dt = cfg.hidden_size, cfg.dtype
        nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        ones = lambda n: jnp.ones((n,), dtype_mod.convert_dtype(dt))  # noqa
        fan = lambda n: 1.0 / math.sqrt(n)                            # noqa
        self._given("input_norm", ones(h))
        self._normal("q_w", (h, nh * hd), fan(h), dt)
        self._normal("k_w", (h, kvh * hd), fan(h) / cfg.key_multiplier, dt)
        self._normal("v_w", (h, kvh * hd), fan(h), dt)
        self._normal("o_w", (nh * hd, h),
                     fan(nh * hd) / cfg.attention_out_multiplier, dt)
        self._normal("in_w", (h, cfg.in_proj_dim),
                     fan(h) / cfg.ssm_in_multiplier / mup_vector(cfg), dt)
        k1, k2, k3, k4 = jax.random.split(random_mod.next_key(), 4)
        bound = fan(cfg.mamba_d_conv)
        self._given("conv_w", jax.random.uniform(
            k1, (cfg.conv_dim, cfg.mamba_d_conv), F32, -bound, bound))
        self._given("conv_b", jax.random.uniform(
            k2, (cfg.conv_dim,), F32, -bound, bound))
        step = jnp.exp(jax.random.uniform(
            k3, (cfg.mamba_n_heads,), F32, math.log(1e-3), math.log(1e-1)))
        self._given("dt_bias", step + jnp.log(-jnp.expm1(-step)))
        self._given("A_log", jnp.log(jax.random.uniform(
            k4, (cfg.mamba_n_heads,), F32, 1.0, 16.0)))
        self._given("D", jnp.ones((cfg.mamba_n_heads,), F32))
        self._given("ssm_norm", ones(cfg.mamba_d_ssm))
        self._normal("out_w", (cfg.mamba_d_ssm, h),
                     fan(cfg.mamba_d_ssm) / cfg.ssm_out_multiplier, dt)
        self._given("ff_norm", ones(h))
        g0, g1 = cfg.mlp_multipliers
        i = cfg.intermediate_size
        self._normal("gate_w", (h, i), fan(h) / g0, dt)
        self._normal("up_w", (h, i), fan(h), dt)
        self._normal("down_w", (i, h), fan(i) / g1, dt)

    def forward(self, hidden):
        return _block_op(hidden, *(getattr(self, k) for k in BLOCK_KEYS),
                         cfg_items=self._cfg_items)


class FalconH1ForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` blocks, final RMSNorm, an untied
    head. ``forward(input_ids)`` is the whole-sequence forward ([batch,
    seq] -> logits); serving goes through ``served_model()``."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        # the two vocabulary-sized matrices first, while the device is
        # empty: drawing one needs a few times its own size
        self._normal("embed_tokens", (v, h), 1.0 / cfg.embedding_multiplier,
                     cfg.dtype)
        # logits spread like a trained LM's (a few units), so that an error
        # in the stream shows in the logprobs the engine reports
        self._normal("lm_head", (h, v),
                     3.0 / math.sqrt(h) / cfg.lm_head_multiplier, cfg.dtype)
        self.layers = nn.LayerList(
            [FalconH1Block(cfg) for _ in range(cfg.num_hidden_layers)])
        self._given("final_layernorm",
                    jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        cfg = self.config
        x = F.embedding(input_ids, self.embed_tokens).astype("float32") * \
            cfg.embedding_multiplier
        for layer in self.layers:
            x = layer(x)
        return _head_op(x, self.final_layernorm, self.lm_head,
                        eps=cfg.rms_norm_eps, mult=cfg.lm_head_multiplier)

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return FalconH1Served(self.config)


class FalconH1Served(ServedModel):
    """Falcon-H1 on the seam: per layer, beside the paged K/V, the SSM
    state ``[slots, heads, d_head, d_state]`` (float32) and the conv tail
    ``[slots, d_conv - 1, conv_dim]``."""

    def __init__(self, cfg: FalconH1Config):
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = 1.0 / math.sqrt(cfg.head_dim)
        self.state_spec = {
            "ssm": ((cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                    F32),
            "conv": ((cfg.mamba_d_conv - 1, cfg.conv_dim), F32)}

    def params(self, model):
        return {"embed": model.embed_tokens.data,
                "head": model.lm_head.data,
                "final_norm": model.final_layernorm.data,
                "layers": [{k: getattr(L, k).data for k in BLOCK_KEYS}
                           for L in model.layers]}

    def param_shapes(self):
        """The ``params`` pytree as shapes alone (an ahead-of-time compile
        for a described chip has no device to hold the weights)."""
        c, dt = self.cfg, dtype_mod.convert_dtype(self.cfg.dtype)
        h, i = c.hidden_size, c.intermediate_size
        qd, kd = c.num_attention_heads * c.head_dim, \
            c.num_key_value_heads * c.head_dim
        sd = jax.ShapeDtypeStruct
        layer = {"input_norm": sd((h,), dt), "q_w": sd((h, qd), dt),
                 "k_w": sd((h, kd), dt), "v_w": sd((h, kd), dt),
                 "o_w": sd((qd, h), dt), "in_w": sd((h, c.in_proj_dim), dt),
                 "conv_w": sd((c.conv_dim, c.mamba_d_conv), F32),
                 "conv_b": sd((c.conv_dim,), F32),
                 "dt_bias": sd((c.mamba_n_heads,), F32),
                 "A_log": sd((c.mamba_n_heads,), F32),
                 "D": sd((c.mamba_n_heads,), F32),
                 "ssm_norm": sd((c.mamba_d_ssm,), dt),
                 "out_w": sd((c.mamba_d_ssm, h), dt),
                 "ff_norm": sd((h,), dt), "gate_w": sd((h, i), dt),
                 "up_w": sd((h, i), dt), "down_w": sd((i, h), dt)}
        return {"embed": sd((c.vocab_size, h), dt),
                "head": sd((h, c.vocab_size), dt),
                "final_norm": sd((h,), dt),
                "layers": [dict(layer) for _ in range(c.num_hidden_layers)]}

    def embed(self, params, tokens, pos):
        return params["embed"][tokens].astype(F32) * \
            self.cfg.embedding_multiplier

    def block(self, p, x, pos, attend, state, valid):
        return block_fn(self.cfg, p, x, pos, attend, state, valid)

    def head(self, params, x):
        # float32 logits: a bfloat16 logit of magnitude 8 is rounded by
        # up to 0.03, which is the size of what the logprobs are held to
        y = _rms(x, params["final_norm"], self.cfg.rms_norm_eps)
        return _mm(y, params["head"]) * self.cfg.lm_head_multiplier
