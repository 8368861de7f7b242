"""Xing4.0 causal LM (HF ``model_type`` ``xing4_0``;
``XingChen-AGI/Xing4.0-29B-A4B/config.json``): DeepSeek-V3-style latent
attention (MLA) under YaRN and 64 routed experts beside a shared one (sigmoid
scores, a ``noaux_tc`` selection bias), on a residual path of ``hc_mult`` = 4
streams — manifold-constrained hyper-connections: every sublayer reads a
token-dependent mix of the streams and writes back through a
Sinkhorn-projected (doubly stochastic) mixing matrix. The layer equations are
written out in ``models/reference/xing4.py`` (the plain float32 reference this
file is tested against).

The block is no longer ``x + F(norm(x))``: it is ``u, maps = mhc_pre(X)``,
``y = F(norm(u))``, ``X' = mhc_post(X, y, maps)``, twice a layer
(``kernels/pallas/mhc.py``: one Pallas kernel pair and its jnp reference
behind ``kernels.registry.resolve``). ``F`` is nothing new: the projections
are ``openpangu_moe.mla_in`` / ``mla_out``, RoPE ``laguna._rope`` at
``reference.laguna.yarn_inv_freq``'s frequencies, the experts
``moe_held_experts_mlp`` with every expert held and the selection bias.

One functional block, ``block_fn``, is the model: the ``nn.Layer`` forward
runs it with a dense causal ``attend``, and ``serving.GenerationEngine`` runs
the SAME function through the served-model seam (``Xing4Served``) with its
paged latent ``attend``. Between blocks the four rows ride as ONE trailing
axis of ``hc_mult x hidden_size``: the seam says ``embed -> x``, ``block(x) ->
x``, ``head(x)`` and the stream's width is the model's business — ``embed``
fans one embedding out to the rows, ``head`` folds them.

The multi-token-prediction module (``num_nextn_predict_layers`` 1) is not
here: the main model's logits do not depend on it, the engine drafts from a
separate model only, and the configuration has no key that says how a draft
block consumes a four-row hidden.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..framework import random as random_mod
from ..kernels.pallas import mhc
from ..kernels.registry import resolve
from ..nn import functional as F
from ..nn.layer.moe import (HELD_EXPERTS_COUNTERS, _route,
                            held_experts_counters, moe_held_experts_mlp)
from ..observability.trace.parts import part, subpart
from ..serving.served_model import ServedModel
from .falcon_h1 import F32, _draw, _mm, _rms, _Weights
from .laguna import _rope
from .openpangu_moe import _swiglu, mla_in, mla_out
from .reference.laguna import yarn_inv_freq


def _default_rope_scaling() -> Dict[str, Any]:
    return {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"}


@dataclass
class Xing4Config:
    """The published ``config.json`` keys, letter for letter (defaults:
    Xing4.0-29B-A4B), plus ``dtype``. Every expert and the whole vocabulary
    are held: a model is a RUN of the published layers, nothing narrower."""
    attention_bias: bool = False
    ep_size: int = 1
    first_k_dense_replace: int = 2
    hidden_act: str = "silu"
    hidden_size: int = 3584
    intermediate_size: int = 9216
    kv_lora_rank: int = 512
    max_position_embeddings: int = 262144
    model_type: str = "xing4_0"
    moe_intermediate_size: int = 1024
    moe_layer_freq: int = 1
    n_group: int = 1
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 4
    num_hidden_layers: int = 40
    num_key_value_heads: int = 32
    num_nextn_predict_layers: int = 1
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30
    mhc_h_res_clamp_max: float = 30
    q_lora_rank: int = 768
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000
    rope_scaling: Dict[str, Any] = field(default_factory=_default_rope_scaling)
    routed_scaling_factor: float = 2
    scoring_func: str = "sigmoid"
    tie_word_embeddings: bool = False
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    v_head_dim: int = 128
    vocab_size: int = 131072
    dtype: str = "bfloat16"

    def __post_init__(self):
        want = dict(attention_bias=False, hidden_act="silu",
                    tie_word_embeddings=False, n_shared_experts=1,
                    topk_method="noaux_tc", scoring_func="sigmoid",
                    moe_layer_freq=1, n_group=1, topk_group=1, ep_size=1,
                    num_nextn_predict_layers=0)
        unsupported = [k for k, v in want.items() if getattr(self, k) != v]
        if unsupported:
            # the multi-token-prediction module is a draft head over a
            # four-row hidden the configuration does not describe, and the
            # engine drafts from a separate model only (module docstring)
            raise ValueError(f"Xing4Config: {unsupported} must be "
                             f"{[want[k] for k in unsupported]}")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention has one latent for all heads: "
                             "num_key_value_heads == num_attention_heads")
        if self.rope_scaling.get("type") != "yarn":
            raise ValueError("Xing4Config: rope_scaling.type must be 'yarn', "
                             f"got {self.rope_scaling.get('type')!r}")

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def stream_dim(self) -> int:
        """The trailing width between blocks: ``hc_mult`` rows of hidden."""
        return self.hc_mult * self.hidden_size

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return Xing4Served(self)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: every mechanism present (four streams, a
        dense layer and two expert layers, 8 experts of which 2 a token, one
        shared, YaRN over a short original context)."""
        rope = dict(_default_rope_scaling(), factor=4,
                    original_max_position_embeddings=32)
        return Xing4Config(**{**dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            n_routed_experts=8, num_experts_per_tok=2,
            num_nextn_predict_layers=0, max_position_embeddings=512,
            rope_scaling=rope, dtype="float32"), **overrides})


def as_dict(cfg: Xing4Config) -> Dict[str, Any]:
    """The configuration as the reference takes it."""
    return dataclasses.asdict(cfg)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attn_scale(cfg: Xing4Config) -> float:
    """DeepSeek-V3's: ``(nope + rope)^-0.5 x m^2``, ``m`` from
    ``mscale_all_dim``."""
    rs = cfg.rope_scaling
    m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return m * m / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _rope_of(cfg: Xing4Config):
    """``rope(x, pos)`` over the ``qk_rope_head_dim`` dims: Laguna's rotation
    at Laguna's YaRN frequencies, cos / sin x ``m(mscale) /
    m(mscale_all_dim)``."""
    rs, dr = cfg.rope_scaling, cfg.qk_rope_head_dim
    inv = yarn_inv_freq(dict(rs, rope_type="yarn",
                             rope_theta=cfg.rope_theta), dr)
    scale = _yarn_mscale(float(rs["factor"]), float(rs["mscale"])) / \
        _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return lambda x, pos: _rope(x, pos, inv, dr, scale)


# -- the functional model ------------------------------------------------------

# Precision as in openpangu_moe.py: weights and every matmul's operands in the
# model's dtype, float32 accumulation; the streams, the three maps and their
# parameters, the norms, RoPE, the router and the logits float32.

ATTN_KEYS = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o", "mlp_norm")
HC_KEYS = tuple(f"{sub}_{k}" for sub in ("attn", "mlp")
                for k in ("hc_g", "hc_phi", "hc_b", "hc_a"))
DENSE_KEYS = ATTN_KEYS + HC_KEYS + ("gate_w", "up_w", "down_w")
MOE_KEYS = ATTN_KEYS + HC_KEYS + (
    "router", "router_bias", "experts_gate", "experts_up", "experts_down",
    "shared_gate", "shared_up", "shared_down")
# what ``Xing4Served.params`` adds to a layer: the maps' parameters in the
# kernel's layout (``mhc.pack_params``)
PACKED_KEYS = tuple(f"{sub}_{k}" for sub in ("attn", "mlp")
                    for k in ("hc_proj", "hc_bias"))


# ONE jitted callable each for the two halves of the residual path: a window
# program calls them ten times, and their kernels are then traced once a
# program, not once a call (``laguna._norm``); XLA inlines the calls.

@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "lo", "hi",
                                             "impl"))
def _pre(x, proj, bias, *, n, iters, eps, lo, hi, impl):
    return mhc.mhc_pre(x, proj, bias, n=n, iters=iters, eps=eps, lo=lo,
                       hi=hi, impl=impl)


@functools.partial(jax.jit, static_argnames=("n", "impl"))
def _post(x, y, maps, *, n, impl):
    return mhc.mhc_post(x, y, maps, n=n, impl=impl)


@subpart("mhc")
def mix_in(cfg: Xing4Config, p, sub: str, x):
    """A sublayer's maps from the whole stream ``x`` [R, W, n C] and the
    sublayer's input ``u`` [R, W, C] (``kernels/pallas/mhc.py``)."""
    R, W, nc = x.shape
    u, maps = _pre(x.reshape(R * W, nc), p[sub + "_hc_proj"],
                   p[sub + "_hc_bias"], n=cfg.hc_mult,
                   iters=cfg.hc_sinkhorn_iters,
                   eps=cfg.hc_eps, lo=float(cfg.mhc_h_res_clamp_min),
                   hi=float(cfg.mhc_h_res_clamp_max),
                   impl=resolve("mhc_pre"))
    return u.reshape(R, W, -1), maps


@subpart("mhc")
def mix_out(cfg: Xing4Config, x, y, maps):
    """The streams mixed by ``H_res`` plus ``H_post`` times the sublayer's
    output ``y`` [R, W, C]."""
    R, W, nc = x.shape
    return _post(x.reshape(R * W, nc), y.reshape(R * W, -1), maps,
                 n=cfg.hc_mult, impl=resolve("mhc_post")).reshape(R, W, nc)


@part("mlp")
def _mlp(cfg: Xing4Config, p, v, valid):
    """``F_mlp`` on the normed input ``v`` — dense, or all the routed experts
    (``router`` / ``experts`` inside) beside the shared one. Returns ``(y,
    stats)``."""
    R, W, _ = v.shape
    if "gate_w" in p:
        return _swiglu(v, p["gate_w"], p["up_w"], p["down_w"]), None
    flat = v.reshape(R * W, -1)
    routed, stats = moe_held_experts_mlp(
        flat.astype(p["q_b"].dtype), p["router"], p["experts_gate"],
        p["experts_up"], p["experts_down"], top_k=cfg.num_experts_per_tok,
        first=0, score="sigmoid", norm_topk=cfg.norm_topk_prob,
        scale=float(cfg.routed_scaling_factor),
        valid=None if valid is None else valid.reshape(R * W), x_route=flat,
        bias=p["router_bias"])
    return routed.reshape(R, W, -1) + _swiglu(
        v, p["shared_gate"], p["shared_up"], p["shared_down"]), stats


def block_fn(cfg: Xing4Config, p, x, pos, attend, valid, seen=None):
    """One block. ``x`` [R, W, n C], the float32 streams; ``pos`` [R, W]
    global positions; ``attend(q_lat, q_rope, row) -> ctx``: causal absorbed
    attention (``openpangu_moe.block_fn``'s); ``valid`` [R, W] bool or None.
    A dense layer's ``p`` holds ``gate_w``, an expert layer's ``router``; both
    hold the packed maps' parameters (``PACKED_KEYS``). ``seen``: a list that
    gets the MLP sublayer's normed input ``[R, W, C]`` (what the router
    scores). Returns ``(x, stats)``: the expert layer's routed-pair counts,
    ``None`` for a dense layer."""
    eps = cfg.rms_norm_eps
    with part("attn_proj"):
        u, maps = mix_in(cfg, p, "attn", x)
    q_lat, q_rope, row, kv_b, _c_q = mla_in(
        p, _rms(u, p["attn_norm"], eps), pos, _rope_of(cfg),
        heads=cfg.num_attention_heads, dn=cfg.qk_nope_head_dim,
        dr=cfg.qk_rope_head_dim, dv=cfg.v_head_dim, dc=cfg.kv_lora_rank,
        eps=eps)
    y = mla_out(p, None, attend(q_lat, q_rope, row), kv_b,
                dn=cfg.qk_nope_head_dim, dv=cfg.v_head_dim)
    with part("attn_proj"):
        x = mix_out(cfg, x, y, maps)
    with part("mlp"):
        u, maps = mix_in(cfg, p, "mlp", x)
    v = _rms(u, p["mlp_norm"], eps)
    if seen is not None:
        seen.append(v)
    y, stats = _mlp(cfg, p, v, valid)
    with part("mlp"):
        x = mix_out(cfg, x, y, maps)
    return x, stats


def _dense_attend(scale, block=None):
    """Causal absorbed attention within the window, every row a fresh
    sequence (the ``nn.Layer`` forward); ``block``: at most so many queries
    are scored at a time (the largest divisor of the window under it;
    ``None``: all of them — ``[R, H, W, W]`` float32)."""
    def attend(q_lat, q_rope, row):
        W, dc = q_lat.shape[1], q_lat.shape[-1]
        B = W if block is None else next(
            b for b in range(min(block, W), 0, -1) if W % b == 0)

        def some(first):
            ql, qr = (jax.lax.dynamic_slice_in_dim(q, first, B, 1)
                      for q in (q_lat, q_rope))
            att = (jnp.einsum("rqhc,rkc->rhqk", ql, row[..., :dc],
                              preferred_element_type=F32) +
                   jnp.einsum("rqhd,rkd->rhqk", qr, row[..., dc:],
                              preferred_element_type=F32)) * scale
            seen = jnp.arange(W)[None, :] <= first + jnp.arange(B)[:, None]
            att = jnp.where(seen, att, -1e30)
            return jnp.einsum("rhqk,rkc->rqhc",
                              jax.nn.softmax(att, -1).astype(row.dtype),
                              row[..., :dc], preferred_element_type=F32)

        if B == W:
            return some(0)
        out = jax.lax.map(some, jnp.arange(0, W, B))       # [W/B, R, B, H, dc]
        return jnp.moveaxis(out, 0, 1).reshape(q_lat.shape[0], W,
                                               *out.shape[3:])

    return attend


def _fan_out(e, n: int):
    """One embedding ``[.., C]`` to ``n`` equal rows, ``[.., n C]`` (a
    concatenation: ``jnp.tile`` is a broadcast to ``[.., n, C]`` and a
    relayout of the whole stream on the chip)."""
    return jnp.concatenate([e.astype(F32)] * n, -1)


def _fold(cfg: Xing4Config, x):
    """The ``n`` rows of a stream ``[.., n C]`` summed, ``[.., C]``."""
    return jnp.sum(x.reshape(x.shape[:-1] + (cfg.hc_mult, cfg.hidden_size)),
                   -2)


def forward_fn(cfg: Xing4Config, params, x):
    """The whole stack on the embedded tokens ``x`` [R, W, C], every row a
    fresh sequence: logits ``[R, W, vocab]``."""
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    x = _fan_out(x, cfg.hc_mult)
    attend = _dense_attend(attn_scale(cfg))
    for p in params["layers"]:
        x, _stats = block_fn(cfg, p, x, pos, attend, None)
    return _mm(_rms(_fold(cfg, x), params["final_norm"], cfg.rms_norm_eps),
               params["head"])


def _frozen(cfg: Xing4Config):
    d = dataclasses.asdict(cfg)
    d["rope_scaling"] = tuple(sorted(d["rope_scaling"].items()))
    return tuple(sorted(d.items()))


def _thawed(items) -> Xing4Config:
    d = dict(items)
    d["rope_scaling"] = dict(d["rope_scaling"])
    return Xing4Config(**d)


def layer_keys(cfg: Xing4Config, layer: int):
    return DENSE_KEYS if cfg.is_dense(layer) else MOE_KEYS


def _with_packed(cfg: Xing4Config, p):
    """A layer's parameters plus the maps' in the kernel's layout."""
    out = dict(p)
    for sub in ("attn", "mlp"):
        out[sub + "_hc_proj"], out[sub + "_hc_bias"] = mhc.pack_params(
            *(p[f"{sub}_hc_{k}"] for k in ("g", "phi", "b", "a")),
            cfg.hc_mult)
    return out


@primitive("xing4_stack")
def _stack_op(x, norm_w, head_w, *weights, cfg_items):
    cfg = _thawed(cfg_items)
    layers, at = [], 0
    for i in range(cfg.num_hidden_layers):
        keys = layer_keys(cfg, i)
        layers.append(_with_packed(cfg, dict(zip(keys,
                                                 weights[at:at + len(keys)]))))
        at += len(keys)
    return forward_fn(cfg, {"layers": layers, "final_norm": norm_w,
                            "head": head_w}, x)


# -- layers --------------------------------------------------------------------

def param_shapes(cfg: Xing4Config, layer: int):
    """One layer's parameters as ``{name: (shape, dtype)}``: every matrix
    ``[in, out]`` in the model's dtype; the router, its selection bias and
    the maps' parameters float32."""
    h, dt = cfg.hidden_size, cfg.dtype
    H, dn, dr, dv, dc, dq = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim,
                             cfg.kv_lora_rank, cfg.q_lora_rank)
    n, nc = cfg.hc_mult, cfg.stream_dim
    maps = 2 * n + n * n
    out = {"attn_norm": ((h,), dt), "q_a": ((h, dq), dt),
           "q_a_norm": ((dq,), dt), "q_b": ((dq, H * (dn + dr)), dt),
           "kv_a": ((h, dc + dr), dt), "kv_a_norm": ((dc,), dt),
           "kv_b": ((dc, H * (dn + dv)), dt), "o": ((H * dv, h), dt),
           "mlp_norm": ((h,), dt)}
    for sub in ("attn", "mlp"):
        out.update({sub + "_hc_g": ((nc,), "float32"),
                    sub + "_hc_phi": ((nc, maps), "float32"),
                    sub + "_hc_b": ((maps,), "float32"),
                    sub + "_hc_a": ((3,), "float32")})
    if cfg.is_dense(layer):
        i = cfg.intermediate_size
        out.update(gate_w=((h, i), dt), up_w=((h, i), dt),
                   down_w=((i, h), dt))
    else:
        e, i = cfg.n_routed_experts, cfg.moe_intermediate_size
        out.update(router=((h, e), "float32"),
                   router_bias=((e,), "float32"),
                   experts_gate=((e, h, i), dt), experts_up=((e, h, i), dt),
                   experts_down=((e, i, h), dt), shared_gate=((h, i), dt),
                   shared_up=((h, i), dt), shared_down=((i, h), dt))
    return out


def packed_shapes(cfg: Xing4Config):
    """``PACKED_KEYS`` as ``{name: (shape, dtype)}``."""
    out = {}
    for sub in ("attn", "mlp"):
        out[sub + "_hc_proj"] = ((2, cfg.stream_dim, mhc.LANES), "bfloat16")
        out[sub + "_hc_bias"] = ((1, mhc.LANES), "float32")
    return out


# The draw of the maps' parameters (the check must SEE the path: PERF.md
# section 6): ``phi ~ N(0, 1 / nC)`` so that ``xh phi`` is ``N(0, 1)`` a
# token; the gates 1 on the pre and post maps and ``A_RES`` on the mixing
# matrix; ``b_pre, b_post ~ N(0, B_GATES^2)``, a fixed lean a row, so that the
# rows of a stream grow DIFFERENT (rows that stay copies of one another are
# mixed alike by any matrix whose rows sum to 1, and no check could tell
# ``H_res`` from another); ``b_res = B_DIAG I + N(0, B_STD^2)``. ``H_res``
# then is visibly token-dependent and off the identity, 18 % from doubly
# stochastic after ONE Sinkhorn iteration (the median token's worst column)
# and within 1e-4 after twenty for 994 tokens in 1000 (near a permutation the
# iteration converges slowly: a wider draw — ``3 I + N(0, 1)`` — leaves 1
# token in 10 further off). At hidden 256 the float32 reference with ONE
# iteration reads a median logprob error of 0.22 against this draw and 0.09
# against ``B_GATES`` 0, ``A_RES`` 0.5, beside 0.03 of bfloat16 rounding.
A_RES = 1.0
B_DIAG = 0.5
B_STD = 0.5
B_GATES = 2.0


class Xing4Block(_Weights):
    """One block's parameters. Random weights: every matrix ``N(0, 1 /
    fan_in)`` (each projection carries unit-scale signal), the router's
    selection bias ``N(0, 0.02^2)``, the maps' parameters as above (``g`` 1,
    ``phi ~ N(0, 1 / nC)`` like every matrix)."""

    def __init__(self, cfg: Xing4Config, layer: int):
        super().__init__()
        self.keys = layer_keys(cfg, layer)
        shapes = param_shapes(cfg, layer)
        n = cfg.hc_mult
        for name in self.keys:
            shape, dt = shapes[name]
            if name.endswith("norm") or name.endswith("hc_g"):
                self._given(name, jnp.ones(shape,
                                           dtype_mod.convert_dtype(dt)))
            elif name.endswith("hc_a"):
                self._given(name, jnp.asarray([1.0, 1.0, A_RES], F32))
            elif name.endswith("hc_b"):
                gates = _draw(random_mod.next_key(),
                              jnp.asarray(B_GATES, F32), shape=(2 * n,),
                              dtype=F32)
                noise = _draw(random_mod.next_key(), jnp.asarray(B_STD, F32),
                              shape=(n, n), dtype=F32)
                self._given(name, jnp.concatenate(
                    [gates,
                     (B_DIAG * jnp.eye(n, dtype=F32) + noise).reshape(-1)]))
            elif name == "router_bias":
                self._normal(name, shape, 0.02, dt)
            else:
                self._normal(name, shape, 1.0 / math.sqrt(shape[-2]), dt)


class Xing4ForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` blocks (the first
    ``first_k_dense_replace`` dense), final RMSNorm over the summed streams,
    an untied head. ``forward(input_ids)`` is the whole-sequence forward
    ([batch, seq] -> logits); serving goes through ``served_model()``."""

    def __init__(self, config: Xing4Config):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        self._normal("embed_tokens", (v, h), 1.0, cfg.dtype)
        # logits spread like a trained LM's (``OpenPanguMoEForCausalLM``)
        self._normal("lm_head", (h, v), 3.0 / math.sqrt(h), cfg.dtype)
        self.layers = nn.LayerList(
            [Xing4Block(cfg, i) for i in range(cfg.num_hidden_layers)])
        self._given("norm", jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens).astype("float32")
        return _stack_op(
            x, self.norm, self.lm_head,
            *(getattr(L, k) for L in self.layers for k in L.keys),
            cfg_items=_frozen(self.config))

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return Xing4Served(self.config)


@functools.partial(jax.jit, static_argnames=("cfg_items", "block"),
                   donate_argnums=(1,))
def _routed_layer(p, x, *, cfg_items, block):
    """One block over a whole sequence's streams ``x`` [1, T, n C] (donated)
    and, for an expert layer, the experts its router chooses ``[T, top_k]``."""
    cfg = _thawed(cfg_items)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    seen = []
    x, _stats = block_fn(cfg, p, x, pos,
                         _dense_attend(attn_scale(cfg), block), None, seen)
    if "router" not in p:
        return x, None
    _gate, idx, _aux = _route(
        seen[0][0], p["router"], cfg.num_experts_per_tok, score="sigmoid",
        norm_topk=cfg.norm_topk_prob, precision=jax.lax.Precision.HIGHEST,
        bias=p["router_bias"])
    return x, idx


def routed_experts(cfg: Xing4Config, params, tokens, block=512):
    """The experts the SERVED blocks choose over one sequence, ``[expert
    layers, T, top_k]`` int32: ``block_fn`` — the function the engine's
    programs trace, in the model's dtype, its kernels and all — over the whole
    of ``tokens`` at once with a dense causal ``attend`` (``block`` queries
    scored at a time), a layer a program so that a long sequence's
    temporaries are one layer's. What a check compares with the plain
    reference's choice before it reads a logprob: a token whose experts
    differ is a different function of its stream, not a rounding of the same
    one."""
    x = _fan_out(params["embed"][jnp.asarray(tokens, jnp.int32)[None]],
                 cfg.hc_mult)
    chosen = []
    for p in params["layers"]:
        x, idx = _routed_layer(p, x, cfg_items=_frozen(cfg), block=block)
        if idx is not None:
            chosen.append(idx)
    return jnp.stack(chosen)


class Xing4Served(ServedModel):
    """Xing4.0 on the seam: a layer's cache is ONE latent row a token
    (``cache_spec``), no recurrent state; every window program hands back the
    expert layers' routed-pair counts (``program_counters``). The engine sees
    a stream of trailing width ``hc_mult x hidden_size`` and nothing of its
    rows; it counts the (token, sublayer) mixes a program ran on the host
    (``token_counters``)."""

    program_counters = tuple(HELD_EXPERTS_COUNTERS)

    def __init__(self, cfg: Xing4Config):
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = self.num_kv_heads = cfg.num_attention_heads
        self.head_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = attn_scale(cfg)
        self.cache_spec = {"kind": "latent", "dim": cfg.latent_dim,
                           "value_dim": cfg.kv_lora_rank}
        # two sublayers a layer mix every real token once
        self.token_counters = {
            "mhc_mix_tokens_total": 2 * cfg.num_hidden_layers}

    def params(self, model):
        return {"embed": model.embed_tokens.data,
                "head": model.lm_head.data,
                "final_norm": model.norm.data,
                "layers": [_with_packed(self.cfg, {k: getattr(L, k).data
                                                   for k in L.keys})
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
                            for k, (s, d) in {**param_shapes(c, i),
                                              **packed_shapes(c)}.items()}
                           for i in range(c.num_hidden_layers)]}

    def embed(self, params, tokens, pos):
        return _fan_out(params["embed"][tokens], self.cfg.hc_mult)

    def block(self, p, x, pos, attend, state, valid):
        x, stats = block_fn(self.cfg, p, x, pos, attend, valid)
        return x, None, held_experts_counters(stats)

    def head(self, params, x):
        return _mm(_rms(_fold(self.cfg, x), params["final_norm"],
                        self.cfg.rms_norm_eps), params["head"])
