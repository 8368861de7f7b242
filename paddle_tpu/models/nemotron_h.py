"""Nemotron-H causal LM (NVIDIA, 2025; HF ``model_type`` ``nemotron_h``:
Nemotron-3-Nano-30B-A3B): a stack whose every layer is ONE mixer behind one
RMSNorm — ``x <- x + Mixer_l(N_l(x))`` — and ``hybrid_override_pattern`` says
which, a letter a layer: ``M`` a Mamba-2 mixer, ``E`` a routed expert layer
(sigmoid scores, a selection bias, ungated relu^2 experts beside a shared
one), ``*`` a grouped-query attention with NO rotary embedding (the positions
live in the Mamba layers). The layer equations are written out in
``models/reference/nemotron_h.py`` (the plain float32 reference this file is
tested against).

One functional block, ``block_fn``, dispatches on what a layer's parameters
hold; the ``nn.Layer`` forward runs it with a dense causal ``attend`` and a
fresh state, ``serving.GenerationEngine`` the SAME function through the
served-model seam (``NemotronHServed``). There a layer keeps what its kind
leaves (``cache_spec["layers"]``): an ``M`` layer a recurrent state by slot
(``"state"``), a ``*`` layer K/V pages (``"full"``), an ``E`` layer nothing
(``"none"``). The Mamba-2 pieces are Falcon-H1's (``falcon_h1.causal_conv``,
``mamba_scan``, ``gated_norm``), and a prompt's later chunks go on from the
state and the conv tail the chunk before them left (``resumes_state``); the
expert layer is ``nn.layer.moe.moe_held_experts_mlp`` with no gate matrix,
holding all its experts.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..framework import random as random_mod
from ..nn import functional as F
from ..nn.layer.moe import (HELD_EXPERTS_COUNTERS, _route,
                            held_experts_counters, moe_held_experts_mlp)
from ..observability.trace.parts import part, subpart
from ..serving.served_model import ServedModel, recur
from .falcon_h1 import (_mm, _rms, _Weights, causal_conv, gated_norm,
                        mamba_scan)

F32 = jnp.float32

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# what a token leaves in a layer of each letter (``cache_spec["layers"]``)
LAYER_KINDS = {MAMBA: "state", EXPERTS: "none", ATTENTION: "full"}
# the selection bias's draw: wide enough that it changes one choice in seven
# at the published router width (``tests/test_nemotron_h.py`` holds >= 10
# %): a bias the draw leaves near zero is a mechanism no check can see
ROUTER_BIAS_STD = 0.02


@dataclass
class NemotronHConfig:
    """The published ``config.json`` keys, letter for letter (defaults:
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), plus ``dtype``. Keys the forward
    does not read (initialisation: ``time_step_*`` and
    ``rescale_prenorm_residual``; ``rope_theta`` / ``partial_rotary_factor``:
    the attention applies no rotary embedding; ``expand``: ``mamba_num_heads x
    mamba_head_dim`` sets the inner width) are kept so that a configuration
    file is the published one."""
    attention_bias: bool = False
    chunk_size: int = 128
    conv_kernel: int = 4
    expand: int = 2
    head_dim: int = 128
    hidden_size: int = 2688
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    intermediate_size: int = 1856
    layer_norm_epsilon: float = 1e-5
    mamba_head_dim: int = 64
    mamba_hidden_act: str = "silu"
    mamba_num_heads: int = 64
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    mlp_bias: bool = False
    mlp_hidden_act: str = "relu2"
    model_type: str = "nemotron_h"
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_group: int = 1
    n_groups: int = 8
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 6
    num_hidden_layers: int = 52
    num_key_value_heads: int = 2
    num_logits_to_keep: int = 1
    partial_rotary_factor: float = 1
    rescale_prenorm_residual: bool = True
    residual_in_fp32: bool = False
    rope_theta: float = 10000
    routed_scaling_factor: float = 2.5
    sliding_window: Optional[int] = None
    ssm_state_size: int = 128
    tie_word_embeddings: bool = False
    time_step_floor: float = 1e-4
    time_step_max: float = 0.1
    time_step_min: float = 0.001
    topk_group: int = 1
    use_bias: bool = False
    use_conv_bias: bool = True
    use_mamba_kernels: bool = True
    vocab_size: int = 131072
    dtype: str = "bfloat16"

    def __post_init__(self):
        want = dict(attention_bias=False, mamba_proj_bias=False,
                    mlp_bias=False, use_bias=False, use_conv_bias=True,
                    mamba_hidden_act="silu", mlp_hidden_act="relu2",
                    n_group=1, topk_group=1, n_shared_experts=1,
                    norm_topk_prob=True, sliding_window=None,
                    tie_word_embeddings=False)
        unsupported = [k for k, v in want.items() if getattr(self, k) != v]
        if unsupported:
            raise ValueError(f"NemotronHConfig: only the published setting of "
                             f"{unsupported} is implemented")
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or \
                set(pattern) - set(LAYER_KINDS):
            # ('-', a dense MLP layer, is a letter the family has and this
            # model does not use)
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} must name "
                f"{self.num_hidden_layers} layers, each one of "
                f"{sorted(LAYER_KINDS)}")
        if self.mamba_num_heads % self.n_groups or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide into their groups")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_inner + self.conv_dim + self.mamba_num_heads

    @property
    def expert_lanes(self) -> int:
        """Columns a routed expert's matrices are STORED at:
        ``moe_intermediate_size`` rounded up to whole 128-lane tiles (1856 ->
        1920), the columns past the width zero — ``relu(0)^2 = 0``, so the
        mathematics is the published width's. The chip lays an array whose
        last axis no 128 divides out with another axis last, and the grouped
        matmul's kernel, which wants it row-major, would copy a layer's 1.28
        GB of ``up`` matrices in front of every call (read in the compiled
        text, ``benchmark/rehearse_aot_hybrid.py``)."""
        return -(-self.moe_intermediate_size // 128) * 128

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return NemotronHServed(self)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: every kind of layer, twice (``MEM*EM``), 8
        experts of which 2 a token, 2 state-space groups, chunks of 8."""
        return NemotronHConfig(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=6,
            hybrid_override_pattern="MEM*EM", num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
            n_routed_experts=8, num_experts_per_tok=2,
            intermediate_size=16, moe_intermediate_size=16,
            moe_shared_expert_intermediate_size=32,
            max_position_embeddings=512, dtype="float32"), **overrides})


def as_dict(cfg: NemotronHConfig):
    """The configuration as the reference takes it."""
    return dataclasses.asdict(cfg)


# -- the functional model ------------------------------------------------------

# Precision as in falcon_h1.py: the weights and every matmul's operands in the
# model's dtype, float32 accumulation; the residual stream, the norms, the
# conv, the recurrence and its parameters, the router with its bias, and the
# logits float32.

def layer_shapes(cfg: NemotronHConfig, letter: str):
    """One layer's parameters as ``{name: (shape, dtype)}``: every matrix
    ``[in, out]`` in the model's dtype; the state-space parameters, the conv,
    the router and its selection bias float32."""
    h, dt = cfg.hidden_size, cfg.dtype
    out = {"norm": ((h,), dt)}
    if letter == MAMBA:
        H, d_in = cfg.mamba_num_heads, cfg.mamba_inner
        out.update(in_w=((h, cfg.in_proj_dim), dt),
                   conv_w=((cfg.conv_dim, cfg.conv_kernel), "float32"),
                   conv_b=((cfg.conv_dim,), "float32"),
                   dt_bias=((H,), "float32"), A_log=((H,), "float32"),
                   D=((H,), "float32"), ssm_norm=((d_in,), dt),
                   out_w=((d_in, h), dt))
    elif letter == ATTENTION:
        qd = cfg.num_attention_heads * cfg.head_dim
        kd = cfg.num_key_value_heads * cfg.head_dim
        out.update(q_w=((h, qd), dt), k_w=((h, kd), dt), v_w=((h, kd), dt),
                   o_w=((qd, h), dt))
    else:
        e, i = cfg.n_routed_experts, cfg.expert_lanes
        s = cfg.moe_shared_expert_intermediate_size
        out.update(router=((h, e), "float32"),
                   router_bias=((e,), "float32"),
                   experts_up=((e, h, i), dt), experts_down=((e, i, h), dt),
                   shared_up=((h, s), dt), shared_down=((s, h), dt))
    return out


def layer_keys(cfg: NemotronHConfig, layer: int):
    return tuple(layer_shapes(cfg, cfg.hybrid_override_pattern[layer]))


@part("mixer")
def _mamba(cfg: NemotronHConfig, p, x, u, state, valid, step: bool):
    """``x + Mamba2(u)``. ``state``: ``None`` (a fresh sequence), a row's own
    ``{"ssm", "conv"}`` from its previous chunk, — ``step`` — the slot arenas
    of a round (one token a row), or the ``Carried`` pair of a program that
    carries a round: the projections, the gate and its norm run once over the
    window, the conv and the scan through ``served_model.recur``. Returns the
    stream and the state after the last real token (of a pair: both)."""
    H, P, N, G = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                  cfg.ssm_state_size, cfg.n_groups)
    d_in, gn = cfg.mamba_inner, G * N
    z, xbc, dt = jnp.split(_mm(u, p["in_w"]), [d_in, d_in + cfg.conv_dim], -1)

    def scan(state, step, xbc, dt, valid):
        # the conv behind its tail, then the recurrence from its state (the
        # steps' widths between them, in the order every program had them)
        R, W, _ = xbc.shape
        conv, tail = causal_conv(xbc, None if state is None
                                 else state["conv"], p["conv_w"],
                                 p["conv_b"], valid)
        xs, b, c = jnp.split(jax.nn.silu(conv), [d_in, d_in + gn], -1)
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
        dt = jnp.where(valid[..., None], dt, 0.0)          # [R, W, H]
        with subpart("ssm_scan"):
            y, ssm = mamba_scan(
                xs.reshape(R, W, H, P), dt, -jnp.exp(p["A_log"].astype(F32)),
                b.reshape(R, W, G, N), c.reshape(R, W, G, N),
                p["D"].astype(F32), None if state is None else state["ssm"],
                cfg.chunk_size, step)
        return y.reshape(R, W, d_in), {"ssm": ssm, "conv": tail}

    y, state = recur(scan, state, step, xbc, dt, valid)
    y = gated_norm(y, z, G, cfg.layer_norm_epsilon, p["ssm_norm"])
    return x + _mm(y, p["out_w"]), state


@part("attn_proj")
def _qkv(cfg: NemotronHConfig, p, u):
    """q, k, v of the normed input in the weights' dtype; nothing is
    rotated."""
    R, W, _ = u.shape
    wd, hd = p["q_w"].dtype, cfg.head_dim
    return tuple(_mm(u, p[k]).reshape(R, W, -1, hd).astype(wd)
                 for k in ("q_w", "k_w", "v_w"))


@part("attn_proj")
def _attn_out(p, x, ctx):
    R, W = ctx.shape[:2]
    return x + _mm(ctx.reshape(R, W, -1), p["o_w"])


def _relu2(u, up, down):
    return _mm(jnp.square(jax.nn.relu(_mm(u, up))), down)


@part("mlp")
def _experts(cfg: NemotronHConfig, p, x, u, valid):
    """``x`` + the routed experts (``router`` / ``experts`` inside) + the
    shared one. Returns the stream and the routed-pair counts."""
    R, W, _ = u.shape
    flat = u.reshape(R * W, -1)
    routed, stats = moe_held_experts_mlp(
        flat.astype(p["experts_up"].dtype), p["router"], None,
        p["experts_up"], p["experts_down"], top_k=cfg.num_experts_per_tok,
        first=0, score="sigmoid", norm_topk=cfg.norm_topk_prob,
        scale=float(cfg.routed_scaling_factor),
        valid=None if valid is None else valid.reshape(R * W), x_route=flat,
        bias=p["router_bias"])
    return x + routed.reshape(R, W, -1) + \
        _relu2(u, p["shared_up"], p["shared_down"]), stats


def block_fn(cfg: NemotronHConfig, p, x, pos, attend, state, valid,
             step: bool = False, seen=None):
    """One layer, by what ``p`` holds. ``x`` [R, W, h], the float32 residual
    stream; ``attend(q, k, v) -> ctx`` causal attention (an attention layer's
    alone); ``state`` / ``step`` as ``_mamba`` takes them (a Mamba-2 layer's
    alone); ``valid`` [R, W] bool. ``seen``: a list that gets an expert
    layer's normed input (what its router scores). Returns ``(x, state,
    stats)``: the state of a Mamba-2 layer, the routed-pair counts of an
    expert layer, ``None`` otherwise."""
    u = _rms(x, p["norm"], cfg.layer_norm_epsilon)
    if "in_w" in p:
        x, state = _mamba(cfg, p, x, u, state, valid, step)
        return x, state, None
    if "q_w" in p:
        return _attn_out(p, x, attend(*_qkv(cfg, p, u))), None, None
    if seen is not None:
        seen.append(u)
    x, stats = _experts(cfg, p, x, u, valid)
    return x, None, stats


def _dense_attend(scale, block=None):
    """Causal attention within the window, every row a fresh sequence (the
    ``nn.Layer`` forward); ``block``: at most so many queries are scored at a
    time (``None``: all of them)."""
    def attend(q, k, v):
        W, rep = q.shape[1], q.shape[2] // k.shape[2]
        k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
        B = W if block is None else next(
            b for b in range(min(block, W), 0, -1) if W % b == 0)

        def some(first):
            att = jnp.einsum(
                "rqhd,rkhd->rhqk",
                jax.lax.dynamic_slice_in_dim(q, first, B, 1), k
            ).astype(F32) * scale
            ok = jnp.arange(W)[None, :] <= first + jnp.arange(B)[:, None]
            return jnp.einsum(
                "rhqk,rkhd->rqhd",
                jax.nn.softmax(jnp.where(ok, att, -1e30), -1).astype(q.dtype),
                v)

        if B == W:
            return some(0)
        out = jax.lax.map(some, jnp.arange(0, W, B))       # [W/B, R, B, H, d]
        return jnp.moveaxis(out, 0, 1).reshape(q.shape)

    return attend


def forward_fn(cfg: NemotronHConfig, params, x, block=None, seen=None):
    """The whole stack on the embedded tokens ``x`` [R, W, h], every row a
    fresh sequence. Returns ``(logits [R, W, vocab], states)``: each Mamba-2
    layer's final ``{"ssm", "conv"}``."""
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    attend = _dense_attend(1.0 / math.sqrt(cfg.head_dim), block)
    valid, states = jnp.ones((R, W), bool), []
    for p in params["layers"]:
        x, st, _stats = block_fn(cfg, p, x, pos, attend, None, valid,
                                 seen=seen)
        if st is not None:
            states.append(st)
    return _mm(_rms(x, params["final_norm"], cfg.layer_norm_epsilon),
               params["head"]), states


def _frozen(cfg: NemotronHConfig):
    return tuple(sorted(dataclasses.asdict(cfg).items()))


@primitive("nemotron_h_stack")
def _stack_op(x, norm_w, head_w, *weights, cfg_items):
    cfg = NemotronHConfig(**dict(cfg_items))
    layers, at = [], 0
    for i in range(cfg.num_hidden_layers):
        keys = layer_keys(cfg, i)
        layers.append(dict(zip(keys, weights[at:at + len(keys)])))
        at += len(keys)
    logits, _states = forward_fn(
        cfg, {"layers": layers, "final_norm": norm_w, "head": head_w},
        x.astype(F32))
    return logits


# -- layers --------------------------------------------------------------------

class NemotronHBlock(_Weights):
    """One layer's parameters. Random weights: every matrix ``N(0, 1 /
    fan_in)`` (each path carries unit-scale signal); the router's selection
    bias ``N(0, ROUTER_BIAS_STD^2)``; the state-space parameters follow
    Mamba-2's published init as ``FalconH1Block``'s do (``dt`` log-uniform in
    [``time_step_min``, ``time_step_max``] through ``dt_bias``, floored at
    ``time_step_floor``; ``A`` uniform in [1, 16]; ``D`` = 1; the conv
    uniform in +-1/sqrt(conv_kernel)), kept in float32. A routed expert's
    matrices are stored at ``expert_lanes`` columns."""

    def __init__(self, cfg: NemotronHConfig, layer: int):
        super().__init__()
        letter = cfg.hybrid_override_pattern[layer]
        shapes = layer_shapes(cfg, letter)
        self.keys = tuple(shapes)
        H, kc = cfg.mamba_num_heads, cfg.conv_kernel
        for name, (shape, dt) in shapes.items():
            if name.endswith("norm"):
                self._given(name, jnp.ones(shape,
                                           dtype_mod.convert_dtype(dt)))
            elif name in ("conv_w", "conv_b"):
                bound = 1.0 / math.sqrt(kc)
                self._given(name, jax.random.uniform(
                    random_mod.next_key(), shape, F32, -bound, bound))
            elif name == "dt_bias":
                step = jnp.maximum(jnp.exp(jax.random.uniform(
                    random_mod.next_key(), (H,), F32,
                    math.log(cfg.time_step_min),
                    math.log(cfg.time_step_max))), cfg.time_step_floor)
                self._given(name, step + jnp.log(-jnp.expm1(-step)))
            elif name == "A_log":
                self._given(name, jnp.log(jax.random.uniform(
                    random_mod.next_key(), (H,), F32, 1.0, 16.0)))
            elif name == "D":
                self._given(name, jnp.ones((H,), F32))
            elif name == "router_bias":
                self._normal(name, shape, ROUTER_BIAS_STD, dt)
            elif name in ("experts_up", "experts_down"):
                # N(0, 1 / fan_in) at the published width, zeros past it
                # (``NemotronHConfig.expert_lanes``): a deviation a column of
                # ``up``, a row of ``down``
                w, fan = cfg.moe_intermediate_size, shape[-2]
                real = (jnp.arange(cfg.expert_lanes) < w).astype(F32)
                self._normal(name, shape, real / math.sqrt(fan)
                             if name == "experts_up"
                             else real[:, None] / math.sqrt(w), dt)
            else:
                self._normal(name, shape, 1.0 / math.sqrt(shape[-2]), dt)


class NemotronHForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` layers by the pattern, final RMSNorm,
    an untied head. ``forward(input_ids)`` is the whole-sequence forward
    ([batch, seq] -> logits); serving goes through ``served_model()``."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        # the two vocabulary-sized matrices first, while the device is empty
        self._normal("embed_tokens", (v, h), 1.0, cfg.dtype)
        # logits spread like a trained LM's (``FalconH1ForCausalLM``)
        self._normal("lm_head", (h, v), 3.0 / math.sqrt(h), cfg.dtype)
        self.layers = nn.LayerList(
            [NemotronHBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self._given("norm_f",
                    jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens)
        return _stack_op(
            x, self.norm_f, self.lm_head,
            *(getattr(L, k) for L in self.layers for k in L.keys),
            cfg_items=_frozen(self.config))

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return NemotronHServed(self.config)


@functools.partial(jax.jit, static_argnames=("cfg_items", "block"),
                   donate_argnums=(1,))
def _routed_layer(p, x, n, *, cfg_items, block):
    """One layer over a whole sequence's stream ``x`` [1, T, h] (donated), of
    which the first ``n`` positions hold a token: the stream, a Mamba-2
    layer's state after them, and the experts an expert layer's router
    chooses ``[T, top_k]``."""
    cfg = NemotronHConfig(**dict(cfg_items))
    T = x.shape[1]
    seen = []
    x, state, _stats = block_fn(
        cfg, p, x, jnp.arange(T, dtype=jnp.int32)[None],
        _dense_attend(1.0 / math.sqrt(cfg.head_dim), block), None,
        (jnp.arange(T) < n)[None], seen=seen)
    if not seen:
        return x, state, None
    _gate, idx, _aux = _route(
        seen[0][0], p["router"], cfg.num_experts_per_tok, score="sigmoid",
        norm_topk=cfg.norm_topk_prob, precision=jax.lax.Precision.HIGHEST,
        bias=p["router_bias"])
    return x, None, idx


def routed_experts(cfg: NemotronHConfig, params, tokens, block=512, n=None):
    """The experts the SERVED blocks choose over one sequence, ``[expert
    layers, T, top_k]`` int32, and each Mamba-2 layer's state after the first
    ``n`` tokens (all of them by default; the later ones are padding):
    ``block_fn`` — the function the engine's programs trace, in the model's
    dtype, its kernels and all — over the whole of ``tokens`` at once (a dense
    causal ``attend``, ``block`` queries scored at a time; the chunked scan
    from zero), a layer a program so that a long sequence's temporaries are
    one layer's. What a check compares with the plain reference's choice
    before it reads a logprob (``xing4.routed_experts``) — and the state ONE
    pass of the system's own arithmetic leaves, which a slot's state after
    chunks, rounds and an install is held to."""
    x = params["embed"][jnp.asarray(tokens, jnp.int32)[None]].astype(F32)
    n = jnp.int32(len(tokens) if n is None else n)
    chosen, states = [], []
    for p in params["layers"]:
        x, state, idx = _routed_layer(p, x, n, cfg_items=_frozen(cfg),
                                      block=block)
        if state is not None:
            states.append(state)
        if idx is not None:
            chosen.append(idx)
    return jnp.stack(chosen), states


class NemotronHServed(ServedModel):
    """Nemotron-H on the seam: a cache BY LAYER KIND (``cache_spec``: K/V
    pages in the attention layers alone, ``state_spec``'s arenas — the SSM
    state ``[slots, heads, head_dim, state]`` and the conv tail ``[slots,
    conv_kernel - 1, conv_dim]``, float32 — in the Mamba-2 layers alone,
    nothing in an expert layer). Its ``block`` resumes: a prefill chunk
    handed the state the previous one left goes on from it. Every window
    program hands back the expert layers' routed-pair counts
    (``program_counters``)."""

    resumes_state = True
    program_counters = tuple(HELD_EXPERTS_COUNTERS)

    def __init__(self, cfg: NemotronHConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = 1.0 / math.sqrt(cfg.head_dim)
        self.cache_spec = {
            "kind": "kv_by_layer",
            "layers": [LAYER_KINDS[c] for c in cfg.hybrid_override_pattern]}
        self.state_spec = {
            "ssm": ((cfg.mamba_num_heads, cfg.mamba_head_dim,
                     cfg.ssm_state_size), F32),
            "conv": ((cfg.conv_kernel - 1, cfg.conv_dim), F32)}

    def params(self, model):
        return {"embed": model.embed_tokens.data,
                "head": model.lm_head.data,
                "final_norm": model.norm_f.data,
                "layers": [{k: getattr(L, k).data for k in L.keys}
                           for L in model.layers]}

    def param_shapes(self):
        """The ``params`` pytree as shapes alone (an ahead-of-time compile
        for a described chip has no device to hold the weights)."""
        c, dt = self.cfg, dtype_mod.convert_dtype(self.cfg.dtype)
        sd = jax.ShapeDtypeStruct
        return {"embed": sd((c.vocab_size, c.hidden_size), dt),
                "head": sd((c.hidden_size, c.vocab_size), dt),
                "final_norm": sd((c.hidden_size,), dt),
                "layers": [{k: sd(s, dtype_mod.convert_dtype(d))
                            for k, (s, d) in layer_shapes(c, letter).items()}
                           for letter in c.hybrid_override_pattern]}

    def embed(self, params, tokens, pos):
        return params["embed"][tokens].astype(F32)

    def block(self, p, x, pos, attend, state, valid, step: bool = False):
        """``step``: the engine's word that ``state`` is the slot arenas of a
        decode round (one token a row); else ``state`` is what the row's
        previous chunk left, or ``None``."""
        x, state, stats = block_fn(self.cfg, p, x, pos, attend, state, valid,
                                   step)
        return x, state, held_experts_counters(stats)

    def head(self, params, x):
        # float32 logits, as Falcon-H1's
        return _mm(_rms(x, params["final_norm"], self.cfg.layer_norm_epsilon),
                   params["head"])
