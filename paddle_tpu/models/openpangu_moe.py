"""openPangu-Ultra-MoE causal LM (HF ``model_type`` ``pangu_ultra_moe``;
``FreedomIntelligence/openPangu-Ultra-MoE-718B/config.json``): DeepSeek-V3
style latent attention (MLA) and sparse experts beside a shared one, with
sandwich norms. The layer equations are written out in
``models/reference/openpangu_moe.py`` (the plain float32 reference this file
is tested against).

One functional block, ``block_fn``, is the model: the ``nn.Layer`` forward
runs it with a dense causal ``attend`` over the window, and
``serving.GenerationEngine`` runs the SAME function through the served-model
seam (``OpenPanguMoEServed``) with its paged latent ``attend``. Attention is
computed in the ABSORBED form — the cache holds ``[c_kv | k_r]``, one row a
token a layer, and ``attend(q_lat, q_rope, row)`` scores every head against
rows — so prefill chunks and decode rounds are one path.

A model may hold a SHARE of each expert layer (expert parallelism: this chip's
``n_routed_experts`` experts are the router's outputs ``held_experts_first
...``; ``router_experts`` is the router's published width) and a slice of the
vocabulary (``vocab_size`` rows). The expert layer then computes its own
experts' part of the result and nothing stands in for the rest.

Weights are created on the device, in the configuration's dtype, from
``paddle.seed``: nothing holds a float32 copy of the parameters anywhere.
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
from ..nn import functional as F
from ..nn.layer.moe import (HELD_EXPERTS_COUNTERS, held_experts_counters,
                            moe_held_experts_mlp)
from ..observability.trace.parts import part
from ..serving.served_model import ServedModel
from .falcon_h1 import F32, _mm, _rms, _rope, _Weights


@dataclass
class OpenPanguMoEConfig:
    """The published ``config.json`` keys, letter for letter (defaults:
    openPangu-Ultra-MoE-718B), plus what a share of the model needs
    (``router_experts``, ``held_experts_first``) and ``dtype``.

    ``n_routed_experts`` counts the experts whose weights THIS model holds;
    ``router_experts`` is the router's width (``None``: the same, the whole
    layer) and ``held_experts_first`` the router output of the first held
    expert. ``vocab_size`` counts the rows of embedding and head held here.
    """
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    hidden_act: str = "silu"
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    attention_bias: bool = False
    sandwich_norm: bool = True
    tie_word_embeddings: bool = False
    router_experts: Optional[int] = None
    held_experts_first: int = 0
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        if not 0 <= self.held_experts_first <= \
                self.router_experts - self.n_routed_experts:
            raise ValueError(
                f"held experts [{self.held_experts_first}, +"
                f"{self.n_routed_experts}) lie outside the router's "
                f"{self.router_experts} outputs")
        unsupported = [k for k, want in (
            ("attention_bias", False), ("sandwich_norm", True),
            ("hidden_act", "silu"), ("tie_word_embeddings", False),
            ("n_shared_experts", 1), ("num_nextn_predict_layers", 0))
            if getattr(self, k) != want]
        if unsupported:
            # the multi-token-prediction module is a draft head: the main
            # model's logits do not depend on it, and the engine drafts from
            # a separate model only (ROADMAP R5)
            raise ValueError(f"OpenPanguMoEConfig: {unsupported} must be "
                             "(False, True, 'silu', False, 1, 0)")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention has one latent for all heads: "
                             "num_key_value_heads == num_attention_heads")

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return OpenPanguMoEServed(self)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: every mechanism present (a dense layer and
        two expert layers, 8 experts of which 2 a token, one shared)."""
        return OpenPanguMoEConfig(**{**dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            n_routed_experts=8, num_experts_per_tok=2,
            num_nextn_predict_layers=0, max_position_embeddings=512,
            dtype="float32"), **overrides})


# -- the functional model ------------------------------------------------------

# Precision as in falcon_h1.py: weights and every matmul's operands in the
# model's dtype, float32 accumulation; the residual stream, the norms, RoPE,
# the router and the logits float32.

ATTN_KEYS = ("input_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")
DENSE_KEYS = ATTN_KEYS + ("gate_w", "up_w", "down_w")
MOE_KEYS = ATTN_KEYS + ("router", "experts_gate", "experts_up",
                        "experts_down", "shared_gate", "shared_up",
                        "shared_down")


# The parts of the block (``observability.trace.parts``) sit on helpers, so
# that ``block_fn``, which every window program traces once a layer, stays
# short; a norm inside one of them is ``norm`` (the innermost part owns).

@part("attn_proj")
def mla_in(p, u, pos, rope, *, heads, dn, dr, dv, dc, eps, rescale=None):
    """Latent attention's projections of the normed input ``u`` (the ONE pair
    of absorbed-MLA helpers: openPangu-Ultra-MoE, GLM-5 and dots3-note all
    call it, with their head widths, ``rope(x, pos)`` their rotary form): the
    absorbed queries ``q_lat`` [R, W, H, dc], their rotary half ``q_rope``,
    the window's own cache rows ``row`` [R, W, dc + dr], the KV up-projection
    by head (its value half comes after ``attend``) and the query latent
    ``c_q`` (what an indexer projects its own queries from). ``rescale``
    ``(a_q, a_kv)``: the normed latents are scaled where they enter ``W_qb``
    and ``W_kvb`` (dots3-note's ``apply_mla_qkv_lora_rescale``) — the cached
    row holds ``a_kv RMSNorm(c_kv)``, and ``c_q`` is handed on unscaled."""
    R, W, _ = u.shape
    wd = p["q_b"].dtype
    c_q = _rms(_mm(u, p["q_a"]), p["q_a_norm"], eps)
    q = _mm(c_q if rescale is None else c_q * rescale[0],
            p["q_b"]).reshape(R, W, heads, dn + dr)
    q_rope = rope(q[..., dn:], pos)
    kva = _mm(u, p["kv_a"])                                  # [R, W, dc+dr]
    c_kv = _rms(kva[..., :dc], p["kv_a_norm"], eps)
    if rescale is not None:
        c_kv = c_kv * rescale[1]
    k_r = rope(kva[..., None, dc:], pos)[:, :, 0]
    row = jnp.concatenate([c_kv, k_r], -1).astype(wd)
    # absorbed: carry q_nope through the head's key half of the KV
    # up-projection, attend against latents, then through its value half
    kv_b = p["kv_b"].reshape(dc, heads, dn + dv)
    q_lat = jnp.einsum("rwhn,chn->rwhc", q[..., :dn].astype(wd),
                       kv_b[..., :dn], preferred_element_type=F32)
    return q_lat.astype(wd), q_rope.astype(wd), row, kv_b, c_q


@part("attn_proj")
def mla_out(p, x, ctx, kv_b, *, dn, dv, post_norm_eps=None, gate=None):
    """The context ``ctx`` [R, W, H, dc] through the value half of the KV
    up-projection and the output projection, onto the stream — through the
    layer's ``post_attn_norm`` first where ``post_norm_eps`` is given (a
    sandwich-norm block), as it is in a pre-norm block; ``x`` ``None``: the
    branch alone (a model whose residual path is not ``x + F``: ``xing4``).
    ``gate`` [R, W, H] float32: each head's output times its gate before the
    output projection (``laguna.head_gate``)."""
    R, W, H = ctx.shape[:3]
    o = jnp.einsum("rwhc,chv->rwhv", ctx.astype(kv_b.dtype), kv_b[..., dn:],
                   preferred_element_type=F32)
    if gate is not None:
        o = o * gate[..., None]
    a = _mm(o.reshape(R, W, H * dv), p["o"])
    if post_norm_eps is not None:
        a = _rms(a, p["post_attn_norm"], post_norm_eps)
    return a if x is None else x + a


def _mla_dims(cfg):
    return dict(heads=cfg.num_attention_heads, dn=cfg.qk_nope_head_dim,
                dr=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
                dc=cfg.kv_lora_rank, eps=cfg.rms_norm_eps)


@part("mlp")
def _swiglu(u, gate, up, down):
    return _mm(jax.nn.silu(_mm(u, gate)) * _mm(u, up), down)


@part("mlp")
def _ffn(cfg: OpenPanguMoEConfig, p, x, v, valid):
    """The layer's MLP on the normed stream ``v`` — dense, or this chip's
    share of the routed experts (``router`` / ``experts`` inside it) beside
    the shared one — onto the stream. Returns ``(x, stats)``."""
    R, W, _ = x.shape
    if "gate_w" in p:
        m, stats = _swiglu(v, p["gate_w"], p["up_w"], p["down_w"]), None
    else:
        flat = v.reshape(R * W, -1)
        routed, stats = moe_held_experts_mlp(
            flat.astype(p["q_b"].dtype), p["router"], p["experts_gate"],
            p["experts_up"], p["experts_down"],
            top_k=cfg.num_experts_per_tok, first=cfg.held_experts_first,
            score="sigmoid", norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor,
            valid=None if valid is None else valid.reshape(R * W))
        m = routed.reshape(R, W, -1) + _swiglu(
            v, p["shared_gate"], p["shared_up"], p["shared_down"])
    return x + _rms(m, p["post_mlp_norm"], cfg.rms_norm_eps), stats


def block_fn(cfg: OpenPanguMoEConfig, p, x, pos, attend, valid):
    """One block. ``x`` [R, W, h], the float32 residual stream; ``pos`` [R,
    W] global positions; ``attend(q_lat, q_rope, row) -> ctx``: causal
    absorbed attention of the window's queries (``q_lat`` [R, W, H,
    kv_lora_rank], ``q_rope`` [R, W, H, qk_rope_head_dim]) given the
    window's own cache rows ``row`` [R, W, latent_dim], returning each head's
    weighted sum of ``c_kv`` [R, W, H, kv_lora_rank]; ``valid`` [R, W] bool
    or None (every position real). A dense layer's ``p`` holds ``gate_w``,
    an expert layer's ``router``. Returns ``(x, stats)``: the expert layer's
    routed-pair counts, ``None`` for a dense layer."""
    eps = cfg.rms_norm_eps
    q_lat, q_rope, row, kv_b, _c_q = mla_in(
        p, _rms(x, p["input_norm"], eps), pos,
        functools.partial(_rope, theta=cfg.rope_theta), **_mla_dims(cfg))
    x = mla_out(p, x, attend(q_lat, q_rope, row), kv_b,
                dn=cfg.qk_nope_head_dim, dv=cfg.v_head_dim, post_norm_eps=eps)
    return _ffn(cfg, p, x, _rms(x, p["pre_mlp_norm"], eps), valid)


def attn_scale(cfg: OpenPanguMoEConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _dense_attend(scale):
    """Causal absorbed attention within the window: every row a fresh
    sequence (the ``nn.Layer`` forward)."""
    def attend(q_lat, q_rope, row):
        W, dc = q_lat.shape[1], q_lat.shape[-1]
        att = (jnp.einsum("rqhc,rkc->rhqk", q_lat, row[..., :dc],
                          preferred_element_type=F32) +
               jnp.einsum("rqhd,rkd->rhqk", q_rope, row[..., dc:],
                          preferred_element_type=F32)) * scale
        att = jnp.where(jnp.tril(jnp.ones((W, W), bool)), att, -1e30)
        return jnp.einsum("rhqk,rkc->rqhc",
                          jax.nn.softmax(att, -1).astype(row.dtype),
                          row[..., :dc], preferred_element_type=F32)

    return attend


@primitive("openpangu_moe_block")
def _block_op(x, *weights, cfg_items, keys):
    cfg = OpenPanguMoEConfig(**dict(cfg_items))
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    out, _stats = block_fn(cfg, dict(zip(keys, weights)), x.astype(F32), pos,
                           _dense_attend(attn_scale(cfg)), None)
    return out


@primitive("openpangu_moe_head")
def _head_op(x, norm_w, head_w, *, eps):
    return _mm(_rms(x.astype(F32), norm_w, eps), head_w)


# -- layers --------------------------------------------------------------------

def param_shapes(cfg: OpenPanguMoEConfig, layer: int):
    """One layer's parameters as ``{name: (shape, dtype)}``: every matrix
    ``[in, out]`` in the model's dtype, the router float32."""
    h, dt = cfg.hidden_size, cfg.dtype
    H, dn, dr, dv, dc, dq = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim,
                             cfg.kv_lora_rank, cfg.q_lora_rank)
    out = {"input_norm": ((h,), dt), "q_a": ((h, dq), dt),
           "q_a_norm": ((dq,), dt), "q_b": ((dq, H * (dn + dr)), dt),
           "kv_a": ((h, dc + dr), dt), "kv_a_norm": ((dc,), dt),
           "kv_b": ((dc, H * (dn + dv)), dt), "o": ((H * dv, h), dt),
           "post_attn_norm": ((h,), dt), "pre_mlp_norm": ((h,), dt),
           "post_mlp_norm": ((h,), dt)}
    if cfg.is_dense(layer):
        i = cfg.intermediate_size
        out.update(gate_w=((h, i), dt), up_w=((h, i), dt),
                   down_w=((i, h), dt))
    else:
        e, i = cfg.n_routed_experts, cfg.moe_intermediate_size
        out.update(router=((h, cfg.router_experts), "float32"),
                   experts_gate=((e, h, i), dt), experts_up=((e, h, i), dt),
                   experts_down=((e, i, h), dt), shared_gate=((h, i), dt),
                   shared_up=((h, i), dt), shared_down=((i, h), dt))
    return out


class OpenPanguMoEBlock(_Weights):
    """One block's parameters and its forward. Random weights, every matrix
    ``N(0, 1 / fan_in)``: each projection carries unit-scale signal, and the
    sandwich norms hand every residual branch on at unit scale. An expert
    layer draws ALL ``router_experts`` columns of the router, so that the
    shares of one layer route alike."""

    def __init__(self, cfg: OpenPanguMoEConfig, layer: int):
        super().__init__()
        self._cfg_items = tuple(sorted(dataclasses.asdict(cfg).items()))
        self.keys = DENSE_KEYS if cfg.is_dense(layer) else MOE_KEYS
        shapes = param_shapes(cfg, layer)
        for name in self.keys:
            shape, dt = shapes[name]
            if name.endswith("norm"):
                self._given(name, jnp.ones(shape,
                                           dtype_mod.convert_dtype(dt)))
            else:
                self._normal(name, shape, 1.0 / math.sqrt(shape[-2]), dt)

    def forward(self, hidden):
        return _block_op(hidden, *(getattr(self, k) for k in self.keys),
                         cfg_items=self._cfg_items, keys=self.keys)


class OpenPanguMoEForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` blocks (the first
    ``first_k_dense_replace`` dense), final RMSNorm, an untied head.
    ``forward(input_ids)`` is the whole-sequence forward ([batch, seq] ->
    logits); serving goes through ``served_model()``."""

    def __init__(self, config: OpenPanguMoEConfig):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        self._normal("embed_tokens", (v, h), 1.0, cfg.dtype)
        # logits spread like a trained LM's (a few units), so that an error
        # in the stream shows in the logprobs the engine reports
        self._normal("lm_head", (h, v), 3.0 / math.sqrt(h), cfg.dtype)
        self.layers = nn.LayerList(
            [OpenPanguMoEBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self._given("norm", jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens).astype("float32")
        for layer in self.layers:
            x = layer(x)
        return _head_op(x, self.norm, self.lm_head,
                        eps=self.config.rms_norm_eps)

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return OpenPanguMoEServed(self.config)


class OpenPanguMoEServed(ServedModel):
    """openPangu-Ultra-MoE on the seam: a layer's cache is ONE latent row a
    token (``cache_spec``), no recurrent state; every window program hands
    back the expert layers' routed-pair counts (``program_counters``)."""

    program_counters = tuple(HELD_EXPERTS_COUNTERS)

    def __init__(self, cfg: OpenPanguMoEConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = self.num_kv_heads = cfg.num_attention_heads
        self.head_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = attn_scale(cfg)
        self.cache_spec = {"kind": "latent", "dim": cfg.latent_dim,
                           "value_dim": cfg.kv_lora_rank}

    def params(self, model):
        return {"embed": model.embed_tokens.data,
                "head": model.lm_head.data,
                "final_norm": model.norm.data,
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
                            for k, (s, d) in param_shapes(c, i).items()}
                           for i in range(c.num_hidden_layers)]}

    def embed(self, params, tokens, pos):
        return params["embed"][tokens].astype(F32)

    def block(self, p, x, pos, attend, state, valid):
        x, stats = block_fn(self.cfg, p, x, pos, attend, valid)
        return x, None, held_experts_counters(stats)

    def head(self, params, x):
        # float32 logits, as Falcon-H1's
        return _mm(_rms(x, params["final_norm"], self.cfg.rms_norm_eps),
                   params["head"])
