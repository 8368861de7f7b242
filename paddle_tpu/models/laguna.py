"""Laguna-XS.2 causal LM (HF ``model_type`` ``laguna``; ``poolside/
Laguna-XS.2/config.json``): sliding-window and full attention layers mixed
(three of four layers see the last ``sliding_window`` keys only), a different
number of query heads in each kind over the same 8 K/V heads, RoPE by layer
kind (YaRN over half of each head in a full layer, plain over the whole head
in a sliding one), one sigmoid gate a head on the attention's output, and
sparse experts beside a shared one after a leading dense layer. The layer
equations are written out in ``models/reference/laguna.py`` (the plain
float32 reference this file is tested against).

One functional block, ``block_fn``, is the model: the ``nn.Layer`` forward
runs it with a dense causal ``attend`` over the window, and
``serving.GenerationEngine`` runs the SAME function through the served-model
seam (``LagunaServed``) with its paged ``attend`` — which, for a model that
declares its layers' kinds (``cache_spec["kind"] == "kv_by_layer"``), carries
the layer's kind as ``attend.kind``. The block learns its head count from
its own parameters.

Weights are created on the device, in the configuration's dtype, from
``paddle.seed``: nothing holds a float32 copy of the parameters anywhere.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..kernels.pallas.rmsnorm import rms_norm
from ..nn import functional as F
from ..nn.layer.moe import (HELD_EXPERTS_COUNTERS, held_experts_counters,
                            moe_held_experts_mlp)
from ..observability.trace.parts import part
from ..serving.served_model import ServedModel
from .falcon_h1 import F32, _mm, _Weights
from .reference.laguna import rope_of

FULL, SLIDING = "full_attention", "sliding_attention"


def _default_rope() -> Dict[str, Any]:
    return {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}


@dataclass
class LagunaConfig:
    """The published ``config.json`` keys, letter for letter (defaults:
    Laguna-XS.2), plus ``dtype``. The three per-layer lists hold one entry a
    layer; a model cut in depth (``num_hidden_layers`` below their length)
    keeps their first ``num_hidden_layers`` entries."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: bool = True
    sliding_window: int = 512
    rope_parameters: Dict[str, Any] = field(default_factory=_default_rope)
    layer_types: List[str] = field(
        default_factory=lambda: [FULL, SLIDING, SLIDING, SLIDING] * 10)
    moe_apply_router_weight_on_input: bool = False
    partial_rotary_factor: float = 0.5
    mlp_layer_types: List[str] = field(
        default_factory=lambda: ["dense"] + ["sparse"] * 39)
    moe_routed_scaling_factor: float = 2.5
    num_attention_heads_per_layer: List[int] = field(
        default_factory=lambda: [48, 64, 64, 64] * 10)
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            # a model cut in depth is the published model's first layers
            setattr(self, name, list(getattr(self, name))[:n])
            if len(getattr(self, name)) != n:
                raise ValueError(f"LagunaConfig: {name} holds "
                                 f"{len(getattr(self, name))} entries for "
                                 f"{n} layers")
        unsupported = [k for k, want in (
            ("attention_bias", False), ("tie_word_embeddings", False),
            ("gating", True), ("moe_apply_router_weight_on_input", False))
            if getattr(self, k) != want]
        if unsupported:
            raise ValueError(f"LagunaConfig: {unsupported} must be "
                             "(False, False, True, False)")
        bad = [h for h in self.num_attention_heads_per_layer
               if h % self.num_key_value_heads]
        if bad or set(self.layer_types) - {FULL, SLIDING} or \
                set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("LagunaConfig: query heads must divide over "
                             "the K/V heads; layer kinds are "
                             f"{FULL}/{SLIDING} and dense/sparse")

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return LagunaServed(self)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: every mechanism present (window 8, both
        layer kinds with their own head counts and RoPE, the dense layer and
        four sparse ones, 8 experts of which 2 a token, one shared)."""
        rope = _default_rope()
        rope[FULL].update(original_max_position_embeddings=32, factor=8)
        return LagunaConfig(**{**dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=512,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, sliding_window=8,
            rope_parameters=rope,
            layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
            mlp_layer_types=["dense"] + ["sparse"] * 4,
            num_attention_heads_per_layer=[4, 6, 6, 6, 4],
            dtype="float32"), **overrides})


def as_dict(cfg: LagunaConfig) -> Dict[str, Any]:
    """The configuration as the reference takes it."""
    return dataclasses.asdict(cfg)


# -- the functional model ------------------------------------------------------

# Precision as in falcon_h1.py: weights and every matmul's operands in the
# model's dtype, float32 accumulation; the residual stream, the norms, RoPE,
# the gate, the router and the logits float32.

ATTN_KEYS = ("input_norm", "q", "k", "v", "g", "o", "post_attn_norm")
DENSE_KEYS = ATTN_KEYS + ("gate_w", "up_w", "down_w")
MOE_KEYS = ATTN_KEYS + ("router", "experts_gate", "experts_up",
                        "experts_down", "shared_gate", "shared_up",
                        "shared_down")


@part("mlp")
def _swiglu(u, gate, up, down):
    return _mm(jax.nn.silu(_mm(u, gate)) * _mm(u, up), down)


# ONE jitted callable each for the norm and the expert layer: a window
# program calls them 11 and 4 times, and their kernels are then traced and
# lowered once a program, not once a call (2.4 s of a program's lowering
# before: PERF.md section 6, PR 34); XLA inlines the calls

@part("norm")
@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rms_norm(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def _experts(flat, valid, router, gate, up, down, *, top_k, scale):
    """The routed experts' part of a sparse layer, all of them held: ``flat``
    [n, h] float32 (the router scores it as it is; the experts take it in
    their own dtype)."""
    return moe_held_experts_mlp(
        flat.astype(gate.dtype), router, gate, up, down, top_k=top_k,
        first=0, score="sigmoid", norm_topk=True, scale=scale, valid=valid,
        x_route=flat)


def _rope(x, pos, inv_freq, dim, scale):
    """Rotate-half RoPE over the first ``dim`` dims of a head at global
    positions ``pos`` ([rows, W]); ``x`` is float32 [rows, W, heads, d]."""
    f = pos.astype(F32)[..., None] * jnp.asarray(inv_freq, F32)
    cos = (jnp.cos(f) * scale)[:, :, None, :]
    sin = (jnp.sin(f) * scale)[:, :, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], -1)


# The parts of the block (``observability.trace.parts``) sit on helpers, so
# that ``block_fn``, which every window program traces once a layer, stays
# short; ``_experts`` is ``router`` and its grouped matmuls ``experts``
# (``moe_held_experts_mlp``).

@part("attn_proj")
def head_gate(u, w):
    """The sigmoid gate a head on a layer's attention output, float32 ``[R,
    W, H]`` of the normed input ``u``: over K/V heads here, over latent
    attention's in ``dots3_note``."""
    return jax.nn.sigmoid(_mm(u, w))


@part("attn_proj")
def _qkvg(cfg: LagunaConfig, p, u, pos, kind):
    """The layer's roped queries and keys, its values (in the weights'
    dtype) and its gate a head [R, W, H] of the normed input ``u``; the head
    count is the query projection's."""
    R, W, _ = u.shape
    G, d = cfg.num_key_value_heads, cfg.head_dim
    H = p["q"].shape[-1] // d
    wd = p["q"].dtype
    inv, dim, fac = rope_of(as_dict(cfg),
                            SLIDING if kind == "window" else FULL)
    q = _rope(_mm(u, p["q"]).reshape(R, W, H, d), pos, inv, dim, fac)
    k = _rope(_mm(u, p["k"]).reshape(R, W, G, d), pos, inv, dim, fac)
    v = _mm(u, p["v"]).reshape(R, W, G, d)
    return q.astype(wd), k.astype(wd), v.astype(wd), head_gate(u, p["g"])


@part("attn_proj")
def _attn_out(p, x, ctx, gate):
    R, W = ctx.shape[:2]
    ctx = ctx.astype(F32) * gate[..., None]                    # [R, W, H, d]
    return x + _mm(ctx.reshape(R, W, -1), p["o"])


@part("mlp")
def _ffn(cfg: LagunaConfig, p, x, u, valid):
    """The layer's MLP on the normed stream ``u`` — dense, or the routed
    experts (``router`` / ``experts`` inside it) beside the shared one —
    onto the stream. Returns ``(x, stats)``."""
    R, W, _ = x.shape
    if "gate_w" in p:
        return x + _swiglu(u, p["gate_w"], p["up_w"], p["down_w"]), None
    routed, stats = _experts(
        u.reshape(R * W, -1),
        None if valid is None else valid.reshape(R * W), p["router"],
        p["experts_gate"], p["experts_up"], p["experts_down"],
        top_k=cfg.num_experts_per_tok, scale=cfg.moe_routed_scaling_factor)
    return x + routed.reshape(R, W, -1) + _swiglu(
        u, p["shared_gate"], p["shared_up"], p["shared_down"]), stats


def block_fn(cfg: LagunaConfig, p, x, pos, attend, valid):
    """One block. ``x`` [R, W, h], the float32 residual stream; ``pos`` [R,
    W] global positions; ``attend(q, k, v) -> ctx``: causal attention of the
    window's queries ``q`` [R, W, H_l, d] given the window's own keys and
    values [R, W, G, d], within the layer's window if ``attend.kind`` is
    sliding; ``valid`` [R, W] bool or None (every position real). The head
    count is the query projection's; a dense layer's ``p`` holds ``gate_w``,
    a sparse layer's ``router``. Returns ``(x, stats)``: the expert layer's
    routed-pair counts, ``None`` for a dense layer."""
    eps = cfg.rms_norm_eps
    q, k, v, gate = _qkvg(cfg, p, _norm(x, p["input_norm"], eps), pos,
                          attend.kind)
    x = _attn_out(p, x, attend(q, k, v), gate)
    return _ffn(cfg, p, x, _norm(x, p["post_attn_norm"], eps), valid)


def attn_scale(cfg: LagunaConfig) -> float:
    return 1.0 / math.sqrt(cfg.head_dim)


class _DenseAttend:
    """Causal attention within the window, every row a fresh sequence (the
    ``nn.Layer`` forward): keys ``j <= i`` and, in a sliding layer, ``j > i
    - window``."""

    def __init__(self, kind: str, window: int, scale: float):
        self.kind, self.scale = kind, scale       # "full" / "window"
        self.window = window if kind == "window" else None

    def __call__(self, q, k, v):
        R, W, H, d = q.shape
        G = k.shape[2]
        i, j = jnp.arange(W)[:, None], jnp.arange(W)[None, :]
        seen = j <= i
        if self.window is not None:
            seen = seen & (j > i - self.window)
        att = jnp.einsum("rqghd,rkgd->rghqk", q.reshape(R, W, G, H // G, d),
                         k, preferred_element_type=F32) * self.scale
        att = jnp.where(seen, att, -1e30)
        out = jnp.einsum("rghqk,rkgd->rqghd",
                         jax.nn.softmax(att, -1).astype(v.dtype), v,
                         preferred_element_type=F32)
        return out.reshape(R, W, H, d)


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v


def _thaw_config(items) -> LagunaConfig:
    def thaw(v):       # a tuple of pairs is a dict, any other tuple a list
        if isinstance(v, tuple):
            if v and all(isinstance(x, tuple) and len(x) == 2
                         and isinstance(x[0], str) for x in v):
                return {k: thaw(x) for k, x in v}
            return [thaw(x) for x in v]
        return v

    return LagunaConfig(**{k: thaw(v) for k, v in items})


@primitive("laguna_block")
def _block_op(x, *weights, cfg_items, keys, kind):
    cfg = _thaw_config(cfg_items)
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    out, _stats = block_fn(
        cfg, dict(zip(keys, weights)), x.astype(F32), pos,
        _DenseAttend(kind, cfg.sliding_window, attn_scale(cfg)), None)
    return out


@primitive("laguna_head")
def _head_op(x, norm_w, head_w, *, eps):
    return _mm(_norm(x.astype(F32), norm_w, eps), head_w)


# -- layers --------------------------------------------------------------------

def param_shapes(cfg: LagunaConfig, layer: int):
    """One layer's parameters as ``{name: (shape, dtype)}``: every matrix
    ``[in, out]`` in the model's dtype, the router float32."""
    h, dt, d = cfg.hidden_size, cfg.dtype, cfg.head_dim
    H, G = cfg.num_attention_heads_per_layer[layer], cfg.num_key_value_heads
    out = {"input_norm": ((h,), dt), "q": ((h, H * d), dt),
           "k": ((h, G * d), dt), "v": ((h, G * d), dt), "g": ((h, H), dt),
           "o": ((H * d, h), dt), "post_attn_norm": ((h,), dt)}
    if cfg.mlp_layer_types[layer] == "dense":
        i = cfg.intermediate_size
        out.update(gate_w=((h, i), dt), up_w=((h, i), dt),
                   down_w=((i, h), dt))
    else:
        e, i = cfg.num_experts, cfg.moe_intermediate_size
        s = cfg.shared_expert_intermediate_size
        out.update(router=((h, e), "float32"),
                   experts_gate=((e, h, i), dt), experts_up=((e, h, i), dt),
                   experts_down=((e, i, h), dt), shared_gate=((h, s), dt),
                   shared_up=((h, s), dt), shared_down=((s, h), dt))
    return out


class LagunaBlock(_Weights):
    """One block's parameters and its forward. Random weights, every matrix
    ``N(0, 1 / fan_in)``: each projection carries unit-scale signal."""

    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self._cfg_items = _freeze(dataclasses.asdict(cfg))
        self.kind = "window" if cfg.layer_types[layer] == SLIDING \
            else "full"
        self.keys = DENSE_KEYS if cfg.mlp_layer_types[layer] == "dense" \
            else MOE_KEYS
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
                         cfg_items=self._cfg_items, keys=self.keys,
                         kind=self.kind)


class LagunaForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` blocks, final RMSNorm, an untied
    head. ``forward(input_ids)`` is the whole-sequence forward ([batch, seq]
    -> logits); serving goes through ``served_model()``."""

    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        self._normal("embed_tokens", (v, h), 1.0, cfg.dtype)
        # logits spread like a trained LM's (a few units), so that an error
        # in the stream shows in the logprobs the engine reports
        self._normal("lm_head", (h, v), 3.0 / math.sqrt(h), cfg.dtype)
        self.layers = nn.LayerList(
            [LagunaBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self._given("norm", jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens).astype("float32")
        for layer in self.layers:
            x = layer(x)
        return _head_op(x, self.norm, self.lm_head,
                        eps=self.config.rms_norm_eps)

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return LagunaServed(self.config)


class LagunaServed(ServedModel):
    """Laguna on the seam: K and V of ``[8, 128]`` a token a layer, the
    layers of two kinds (``cache_spec``: a full layer keeps every token, a
    window layer the last ``sliding_window``); no recurrent state; every
    window program hands back the expert layers' routed-pair counts
    (``program_counters``: all experts are held, so held = routed)."""

    program_counters = tuple(HELD_EXPERTS_COUNTERS)

    def __init__(self, cfg: LagunaConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = max(cfg.num_attention_heads_per_layer)
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = attn_scale(cfg)
        self.cache_spec = {
            "kind": "kv_by_layer", "window": cfg.sliding_window,
            "layers": ["window" if t == SLIDING else "full"
                       for t in cfg.layer_types]}

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
        return _mm(_norm(x, params["final_norm"], self.cfg.rms_norm_eps),
                   params["head"])
