"""GLM-5 causal LM (HF ``model_type`` ``glm_moe_dsa``;
``zai-org/GLM-5.2/config.json``): DeepSeek-V3 style latent attention (MLA) in
pre-norm blocks, a learned SPARSE attention over it (DeepSeek Sparse
Attention: a lightning indexer scores every cached token for a query and the
query attends its ``index_topk`` best alone; IndexShare: a ``shared`` layer
owns no indexer and attends the set of the nearest ``full`` layer before it),
and sparse experts beside a shared one with a ``noaux_tc`` selection bias.
The layer equations are written out in ``models/reference/glm_moe_dsa.py``
(the plain float32 reference this file is tested against).

One functional block, ``block_fn``, is the model: the ``nn.Layer`` forward
runs it with a dense-within-window ``attend``, and
``serving.GenerationEngine`` runs the SAME function through the served-model
seam (``GlmMoeDsaServed``) with its paged ``attend``. Attention is computed in
the ABSORBED form against ``[c_kv | k_r]`` rows, one a token a layer
(``openpangu_moe.mla_in`` / ``mla_out``: one pair of helpers for both
models); a ``full`` layer also hands ``attend`` its index queries, weights and
key row — ``attend(q_lat, q_rope, row, index=(qI, wI, kI))`` — and a
``shared`` layer hands nothing more: the ``attend`` keeps the selection.

A model may hold a SHARE of each expert layer (``n_routed_experts`` experts
from router output ``held_experts_first``; the router keeps its
``router_experts`` outputs) and a RUN of the published layers
(``num_hidden_layers`` of them from published layer ``layer_offset``: both
per-layer lists stay whole and the model reads its own entries).

Weights are created on the device, in the configuration's dtype, from
``paddle.seed``: nothing holds a float32 copy of the parameters anywhere.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..kernels.pallas.dsa_index import exact_topk_bias
from ..nn import functional as F
from ..nn.layer.moe import (HELD_EXPERTS_COUNTERS, held_experts_counters,
                            moe_held_experts_mlp)
from ..observability.trace.parts import part, subpart
from ..serving.served_model import ServedModel
from .falcon_h1 import F32, _mm, _rms, _Weights
from .openpangu_moe import _swiglu, mla_in, mla_out

# GLM-5.2's per-layer lists (78 layers): three leading layers that are dense
# and own an indexer, then ``shared, shared, shared, full`` expert layers
_INDEXER_TYPES = ["full"] * 3 + ["shared", "shared", "shared", "full"] * 18 \
    + ["shared"] * 3
_MLP_TYPES = ["dense"] * 3 + ["sparse"] * 75
# the indexer's key LayerNorm (the family's inference code; not in the config)
INDEX_NORM_EPS = 1e-6
# how much wider than 1 / sqrt(fan_in) the query up-projection is drawn
# (``GlmMoeDsaBlock``)
QUERY_GAIN = 2.5


@dataclass
class GlmMoeDsaConfig:
    """The published ``config.json`` keys, letter for letter (defaults:
    GLM-5.2), plus what a share of the model needs (``layer_offset``,
    ``router_experts``, ``held_experts_first``) and ``dtype``.

    ``num_hidden_layers`` counts the layers THIS model holds, published
    layers ``layer_offset ...``; ``n_routed_experts`` the experts whose
    weights it holds, ``router_experts`` the router's width (``None``: the
    same) and ``held_experts_first`` the router output of the first held
    expert."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    head_dim: int = 192
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_head_dim: int = 256
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_topk_freq: int = 4
    index_skip_topk_offset: int = 3
    index_topk_pattern: Optional[str] = None
    index_share_for_mtp_iteration: bool = True
    indexer_rope_interleave: bool = True
    indexer_types: List[str] = field(
        default_factory=lambda: list(_INDEXER_TYPES))
    mlp_layer_types: List[str] = field(
        default_factory=lambda: list(_MLP_TYPES))
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    ep_size: int = 1
    hidden_act: str = "silu"
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    rope_interleave: bool = True
    rope_parameters: Dict = field(
        default_factory=lambda: {"rope_theta": 8000000,
                                 "rope_type": "default"})
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    model_type: str = "glm_moe_dsa"
    layer_offset: int = 0
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
        want = dict(attention_bias=False, hidden_act="silu",
                    tie_word_embeddings=False, n_shared_experts=1,
                    num_nextn_predict_layers=0, n_group=1, topk_group=1,
                    topk_method="noaux_tc", scoring_func="sigmoid",
                    rope_interleave=True, indexer_rope_interleave=True,
                    index_topk_pattern=None, moe_layer_freq=1)
        unsupported = [k for k, v in want.items() if getattr(self, k) != v]
        if unsupported:
            # the multi-token-prediction module is a draft head: the main
            # model's logits do not depend on it, and the engine drafts from
            # a separate model only (ROADMAP R5)
            raise ValueError(f"GlmMoeDsaConfig: {unsupported} must be "
                             f"{[want[k] for k in unsupported]}")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError("GlmMoeDsaConfig: rope_type must be 'default'")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention has one latent for all heads: "
                             "num_key_value_heads == num_attention_heads")
        if self.qk_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError("qk_head_dim is qk_nope_head_dim + "
                             "qk_rope_head_dim")
        last = self.layer_offset + self.num_hidden_layers
        for name in ("indexer_types", "mlp_layer_types"):
            if len(getattr(self, name)) < last:
                raise ValueError(
                    f"{name} has {len(getattr(self, name))} entries, the "
                    f"model holds published layers {self.layer_offset}-"
                    f"{last - 1}")
        if self.layer_kinds()[0] != "full":
            raise ValueError(
                "the model's first layer must own an indexer: a 'shared' "
                "layer attends the set of a 'full' layer before it")

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])

    def layer_kinds(self) -> List[str]:
        """``full`` / ``shared`` of the layers held here."""
        return list(self.indexer_types[
            self.layer_offset:self.layer_offset + self.num_hidden_layers])

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[self.layer_offset + layer] == "dense"

    def is_full(self, layer: int) -> bool:
        return self.indexer_types[self.layer_offset + layer] == "full"

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return GlmMoeDsaServed(self)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: every mechanism present (published layers
        2-6 of a 7-layer pattern: a dense layer with its own indexer, then
        ``shared, shared, shared, full`` expert layers; 8 experts of which 2
        a token, one shared; 6 index keys a query)."""
        return GlmMoeDsaConfig(**{**dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=5,
            first_k_dense_replace=1, layer_offset=2,
            indexer_types=list(_INDEXER_TYPES[:7]),
            mlp_layer_types=list(_MLP_TYPES[:7]), num_attention_heads=4,
            num_key_value_heads=4, head_dim=12, q_lora_rank=24,
            kv_lora_rank=16, qk_head_dim=12, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=10, index_n_heads=4,
            index_head_dim=8, index_topk=6, n_routed_experts=8,
            num_experts_per_tok=2, num_nextn_predict_layers=0,
            max_position_embeddings=512, dtype="float32"), **overrides})


# -- the functional model ------------------------------------------------------

# Precision as in falcon_h1.py: weights and every matmul's operands in the
# model's dtype, float32 accumulation; the residual stream, the norms, RoPE,
# the router, the index weights and the logits float32.

ATTN_KEYS = ("input_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o", "post_attn_norm")
INDEX_KEYS = ("index_q", "index_k", "index_k_norm", "index_k_bias", "index_w")
DENSE_MLP_KEYS = ("gate_w", "up_w", "down_w")
MOE_MLP_KEYS = ("router", "router_bias", "experts_gate", "experts_up",
                "experts_down", "shared_gate", "shared_up", "shared_down")


def layer_keys(cfg: GlmMoeDsaConfig, layer: int):
    return ATTN_KEYS + (INDEX_KEYS if cfg.is_full(layer) else ()) + \
        (DENSE_MLP_KEYS if cfg.is_dense(layer) else MOE_MLP_KEYS)


def rope_pairs(x, pos, theta):
    """INTERLEAVED RoPE over the whole last dim at global positions ``pos``
    ([rows, W]): dims ``(2i, 2i + 1)`` are one pair, turned by ``pos x
    theta^(-2i / d)``; ``x`` is float32 [rows, W, heads, d]. (The family's
    code de-interleaves first and leaves the head permuted; queries and keys
    are permuted alike, so every dot product is this one's.)"""
    d = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = pos.astype(F32)[..., None] * inv                   # [rows, W, d/2]
    cos, sin = jnp.cos(f)[:, :, None, :], jnp.sin(f)[:, :, None, :]
    xp = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xp[..., 0], xp[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape)


@part("norm")
def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def _rope_first(x, pos, dr, theta):
    """The first ``dr`` dims of each head roped, the rest as they are."""
    return jnp.concatenate([rope_pairs(x[..., :dr], pos, theta),
                            x[..., dr:]], -1)


# The parts of the block (``observability.trace.parts``) sit on helpers, so
# that ``block_fn``, which every window program traces once a layer, stays
# short; a norm inside one of them is ``norm`` (the innermost part owns).

@part("attn_proj")
@subpart("indexer")
def _index_in(cfg: GlmMoeDsaConfig, p, u, c_q, pos):
    """The lightning indexer's projections: the index queries ``qI`` [R, W,
    Hi, Di] from the query latent, the token's index key ``kI`` [R, W, Di]
    (the second cache row) and the heads' weights ``wI`` [R, W, Hi] float32
    from the normed input, the scale ``Hi^-1/2 Di^-1/2`` folded in."""
    R, W, _ = u.shape
    Hi, Di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    wd, theta = p["index_q"].dtype, cfg.rope_theta
    qi = _rope_first(_mm(c_q, p["index_q"]).reshape(R, W, Hi, Di), pos, dr,
                     theta)
    ki = _layer_norm(_mm(u, p["index_k"]), p["index_k_norm"],
                     p["index_k_bias"], INDEX_NORM_EPS)
    ki = _rope_first(ki[:, :, None, :], pos, dr, theta)[:, :, 0]
    wi = _mm(u, p["index_w"]) * (Hi ** -0.5 * Di ** -0.5)
    return qi.astype(wd), wi, ki.astype(wd)


@part("mlp")
def _ffn(cfg: GlmMoeDsaConfig, p, x, v, valid):
    """The layer's MLP on the normed stream ``v`` — dense, or this chip's
    share of the routed experts (``router`` / ``experts`` inside it) beside
    the shared one — onto the stream. Returns ``(x, stats)``."""
    R, W, _ = x.shape
    if "gate_w" in p:
        return x + _swiglu(v, p["gate_w"], p["up_w"], p["down_w"]), None
    flat = v.reshape(R * W, -1)
    routed, stats = moe_held_experts_mlp(
        flat.astype(p["q_b"].dtype), p["router"], p["experts_gate"],
        p["experts_up"], p["experts_down"], top_k=cfg.num_experts_per_tok,
        first=cfg.held_experts_first, score="sigmoid",
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        valid=None if valid is None else valid.reshape(R * W), x_route=flat,
        bias=p["router_bias"])
    return x + routed.reshape(R, W, -1) + _swiglu(
        v, p["shared_gate"], p["shared_up"], p["shared_down"]), stats


def block_fn(cfg: GlmMoeDsaConfig, p, x, pos, attend, valid):
    """One pre-norm block. ``x`` [R, W, h], the float32 residual stream;
    ``pos`` [R, W] global positions; ``attend(q_lat, q_rope, row,
    index=None) -> ctx``: causal absorbed attention of the window's queries
    (``q_lat`` [R, W, H, kv_lora_rank], ``q_rope`` [R, W, H,
    qk_rope_head_dim]) given the window's own cache rows ``row`` [R, W,
    latent_dim], over the keys each query SELECTED: a ``full`` layer (its
    ``p`` holds ``index_q``) hands ``index = (qI, wI, kI)`` and ``attend``
    scores, selects and keeps the selection; a ``shared`` layer hands none and
    gets the kept one. ``valid`` [R, W] bool or None. Returns ``(x, stats)``:
    the expert layer's routed-pair counts, ``None`` for a dense layer."""
    eps = cfg.rms_norm_eps
    u = _rms(x, p["input_norm"], eps)
    q_lat, q_rope, row, kv_b, c_q = mla_in(
        p, u, pos, lambda t, at: rope_pairs(t, at, cfg.rope_theta),
        heads=cfg.num_attention_heads, dn=cfg.qk_nope_head_dim,
        dr=cfg.qk_rope_head_dim, dv=cfg.v_head_dim, dc=cfg.kv_lora_rank,
        eps=eps)
    if "index_q" in p:
        ctx = attend(q_lat, q_rope, row, index=_index_in(cfg, p, u, c_q, pos))
    else:
        ctx = attend(q_lat, q_rope, row)
    x = mla_out(p, x, ctx, kv_b, dn=cfg.qk_nope_head_dim, dv=cfg.v_head_dim)
    return _ffn(cfg, p, x, _rms(x, p["post_attn_norm"], eps), valid)


def attn_scale(cfg: GlmMoeDsaConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_head_dim)


class _DenseAttend:
    """Causal absorbed attention within the window over the selected keys,
    every row a fresh sequence (the ``nn.Layer`` forward): the selection of
    the last ``full`` layer is kept for the ``shared`` layers behind it.
    ``selected`` lists each ``full`` layer's ``[R, W, W]`` bool mask (what
    the tests compare with the reference's sets)."""

    def __init__(self, scale: float, topk: int):
        self.scale, self.topk = scale, topk
        self.bias, self.selected = None, []

    def __call__(self, q_lat, q_rope, row, index=None):
        W, dc = q_lat.shape[1], q_lat.shape[-1]
        if index is not None:
            qi, wi, ki = index
            per_head = jnp.einsum("rqjd,rkd->rqjk", qi, ki,
                                  preferred_element_type=F32)
            sc = jnp.sum(jnp.maximum(per_head, 0.0) * wi[..., None], 2)
            sc = jnp.where(jnp.tril(jnp.ones((W, W), bool)), sc, -jnp.inf)
            self.bias, _n = exact_topk_bias(sc, self.topk)
            self.selected.append(self.bias == 0)
        att = (jnp.einsum("rqhc,rkc->rhqk", q_lat, row[..., :dc],
                          preferred_element_type=F32) +
               jnp.einsum("rqhd,rkd->rhqk", q_rope, row[..., dc:],
                          preferred_element_type=F32)) * self.scale
        att = att + self.bias[:, None]
        return jnp.einsum("rhqk,rkc->rqhc",
                          jax.nn.softmax(att, -1).astype(row.dtype),
                          row[..., :dc], preferred_element_type=F32)


def _frozen(cfg: GlmMoeDsaConfig):
    """The configuration as a hashable primitive attribute."""
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        return tuple(v) if isinstance(v, list) else v

    return tuple(sorted((k, freeze(v))
                        for k, v in dataclasses.asdict(cfg).items()))


def _thawed(items) -> GlmMoeDsaConfig:
    d = dict(items)
    d["rope_parameters"] = dict(d["rope_parameters"])
    for k in ("indexer_types", "mlp_layer_types"):
        d[k] = list(d[k])
    return GlmMoeDsaConfig(**d)


def forward_fn(cfg: GlmMoeDsaConfig, params, x):
    """The whole stack on the embedded stream ``x`` [R, W, h], every row a
    fresh sequence: ``(logits [R, W, vocab], selected)``."""
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    attend = _DenseAttend(attn_scale(cfg), cfg.index_topk)
    x = x.astype(F32)
    for p in params["layers"]:
        x, _stats = block_fn(cfg, p, x, pos, attend, None)
    return _mm(_rms(x, params["final_norm"], cfg.rms_norm_eps),
               params["head"]), attend.selected


@primitive("glm_moe_dsa_stack")
def _stack_op(x, norm_w, head_w, *weights, cfg_items):
    cfg = _thawed(cfg_items)
    layers, at = [], 0
    for i in range(cfg.num_hidden_layers):
        keys = layer_keys(cfg, i)
        layers.append(dict(zip(keys, weights[at:at + len(keys)])))
        at += len(keys)
    logits, _sel = forward_fn(cfg, {"layers": layers, "final_norm": norm_w,
                                    "head": head_w}, x)
    return logits


# -- layers --------------------------------------------------------------------

def param_shapes(cfg: GlmMoeDsaConfig, layer: int):
    """One layer's parameters as ``{name: (shape, dtype)}``: every matrix
    ``[in, out]`` in the model's dtype; the router, its selection bias and
    the index key's LayerNorm float32."""
    h, dt = cfg.hidden_size, cfg.dtype
    H, dn, dr, dv, dc, dq = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim,
                             cfg.kv_lora_rank, cfg.q_lora_rank)
    out = {"input_norm": ((h,), dt), "q_a": ((h, dq), dt),
           "q_a_norm": ((dq,), dt), "q_b": ((dq, H * (dn + dr)), dt),
           "kv_a": ((h, dc + dr), dt), "kv_a_norm": ((dc,), dt),
           "kv_b": ((dc, H * (dn + dv)), dt), "o": ((H * dv, h), dt),
           "post_attn_norm": ((h,), dt)}
    if cfg.is_full(layer):
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        out.update(index_q=((dq, Hi * Di), dt), index_k=((h, Di), dt),
                   index_k_norm=((Di,), "float32"),
                   index_k_bias=((Di,), "float32"), index_w=((h, Hi), dt))
    if cfg.is_dense(layer):
        i = cfg.intermediate_size
        out.update(gate_w=((h, i), dt), up_w=((h, i), dt),
                   down_w=((i, h), dt))
    else:
        e, i = cfg.n_routed_experts, cfg.moe_intermediate_size
        out.update(router=((h, cfg.router_experts), "float32"),
                   router_bias=((cfg.router_experts,), "float32"),
                   experts_gate=((e, h, i), dt), experts_up=((e, h, i), dt),
                   experts_down=((e, i, h), dt), shared_gate=((h, i), dt),
                   shared_up=((h, i), dt), shared_down=((i, h), dt))
    return out


class GlmMoeDsaBlock(_Weights):
    """One block's parameters. Random weights, every matrix ``N(0, 1 /
    fan_in)`` so that each projection carries unit-scale signal and every
    residual branch is handed on near unit scale — but the query
    up-projection ``q_b``, drawn ``QUERY_GAIN`` times wider: attention
    scores then spread over a few units (std 2.5 where unit-scale queries
    give 1), so that a softmax over 2048 selected rows is led by a handful of
    them and WHICH rows were selected shows in the logits (with flat scores
    it is their mean, and a wrong selection is invisible). The router's
    selection bias is ``N(0, 0.02^2)``: enough to move one routing choice in
    a few, as a trained ``noaux_tc`` bias does. An expert layer draws ALL
    ``router_experts`` columns of router and bias, so that the shares of one
    layer route alike."""

    def __init__(self, cfg: GlmMoeDsaConfig, layer: int):
        super().__init__()
        self.keys = layer_keys(cfg, layer)
        shapes = param_shapes(cfg, layer)
        for name in self.keys:
            shape, dt = shapes[name]
            if name.endswith("norm"):
                self._given(name, jnp.ones(shape,
                                           dtype_mod.convert_dtype(dt)))
            elif name == "index_k_bias":
                self._given(name, jnp.zeros(shape, F32))
            elif name == "router_bias":
                self._normal(name, shape, 0.02, dt)
            else:
                gain = QUERY_GAIN if name == "q_b" else 1.0
                self._normal(name, shape, gain / math.sqrt(shape[-2]), dt)


class GlmMoeDsaForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` blocks (published layers
    ``layer_offset ...``), final RMSNorm, an untied head.
    ``forward(input_ids)`` is the whole-sequence forward ([batch, seq] ->
    logits; every query attends its ``index_topk`` selected keys); serving
    goes through ``served_model()``."""

    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        self._normal("embed_tokens", (v, h), 1.0, cfg.dtype)
        # logits spread like a trained LM's (a few units), so that an error
        # in the stream shows in the logprobs the engine reports
        self._normal("lm_head", (h, v), 3.0 / math.sqrt(h), cfg.dtype)
        self.layers = nn.LayerList(
            [GlmMoeDsaBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self._given("norm", jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens).astype("float32")
        return _stack_op(
            x, self.norm, self.lm_head,
            *(getattr(L, k) for L in self.layers for k in L.keys),
            cfg_items=_frozen(self.config))

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return GlmMoeDsaServed(self.config)


class GlmMoeDsaServed(ServedModel):
    """GLM-5 on the seam: a layer's cache is ONE latent row a token and, in a
    ``full`` layer, one index key row beside it (``cache_spec`` with an
    ``index`` group), no recurrent state; every window program hands back the
    expert layers' routed-pair counts (``program_counters``)."""

    program_counters = tuple(HELD_EXPERTS_COUNTERS)

    def __init__(self, cfg: GlmMoeDsaConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = self.num_kv_heads = cfg.num_attention_heads
        self.head_dim = cfg.qk_head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = attn_scale(cfg)
        self.cache_spec = {
            "kind": "latent", "dim": cfg.latent_dim,
            "value_dim": cfg.kv_lora_rank,
            "index": {"dim": cfg.index_head_dim, "heads": cfg.index_n_heads,
                      "topk": cfg.index_topk, "layers": cfg.layer_kinds()}}

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
