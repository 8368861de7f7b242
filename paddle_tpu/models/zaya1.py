"""ZAYA1 causal LM (Zyphra, 2025; HF ``model_type`` ``zaya``: ZAYA1-8B): every
layer is a compressed convolutional attention (CCA) sublayer, then a top-1
expert sublayer whose router is a small MLP fed by the previous layer's
router, both under residual scaling. The layer equations are written out in
``models/reference/zaya1.py`` (the plain float32 reference this file is
tested against).

CCA attends in a latent: 8 query heads and 2 K/V heads of 128 from a stream
of 2048, the queries and keys mixed over the SEQUENCE by two causal
convolutions of kernel 2 before they are normed, rotated and cached, and the
second value head read from the PREVIOUS token. So a layer's memory is of two
kinds at once: K/V pages (the finished keys, the shifted values) and a small
tail by slot — the previous token's pre-conv queries and keys, its first-conv
output and its value projection (``cache_spec["layers"]`` ``"full+state"``).
The router of layer ``l`` reads the router representation of layer ``l - 1``
(exponential depth averaging): the stream between the blocks is the residual
stream WIDENED by ``router_hidden_size`` columns.

One functional block, ``block_fn``; the ``nn.Layer`` forward runs it with a
dense causal ``attend`` and a fresh tail, ``serving.GenerationEngine`` the
SAME function through the served-model seam (``Zaya1Served``): a prompt's
later chunks go on from the tail the chunk before them left, a decode round
steps it (``served_model.recur``). The expert sublayer is
``nn.layer.moe.moe_held_experts_mlp`` with the router MLP's last matrix as
its router and the MLP's hidden layer as what it scores.
"""
from __future__ import annotations

import dataclasses
import json
import math
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..framework import random as random_mod
from ..nn import functional as F
from ..nn.layer.moe import (HELD_EXPERTS_COUNTERS, held_experts_counters,
                            moe_held_experts_mlp)
from ..observability.trace.parts import part, subpart
from ..serving.served_model import ServedModel, recur
from .falcon_h1 import _mm, _rms, _Weights
from .nemotron_h import _dense_attend

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

# what a layer keeps beside its pages (``cache_spec["layers"]``)
LAYER_KIND = "full+state"
# The draws of what would otherwise sit at a neutral value, where no check
# could see it dropped (the configuration's ``assumed``): the selection bias
# wide enough to move one choice in five of a top-1 over 16 (``tests/
# test_zaya1.py`` holds >= 10 % at the published router widths), the
# temperature away from 1, the depth-averaging gate away from 0, the four
# residual scales away from (1, 0, 1, 0).
ROUTER_BIAS_STD = 0.02
TAU_RANGE = (0.6, 1.6)
EDA_RANGE = (0.3, 0.7)
SCALE_RANGE, SHIFT_STD = (0.8, 1.2), 0.02
# logits spread like a trained LM's through a TIED head: the embedding is
# N(0, 1 / hidden) — a row of unit norm, so a logit is the normed stream's
# projection on it — and the final norm's weight this. (An embedding of unit
# SCALE would make every token predict itself: its own row is still a
# thirtieth of the final stream, and 2048 dimensions of agreement outweigh
# the other 262 271 logits — a random tied model that only repeats its last
# token checks nothing.)
LOGIT_STD = 3.0

_ROPE = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                    "rope_type": "default"},
         "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                            "rope_type": "default"},
         "rope_type": "default"}


@dataclass
class Zaya1Config:
    """The published ``config.json`` keys, letter for letter (defaults:
    ZAYA1-8B), plus ``dtype``. ``rope_parameters["hybrid_sliding"]`` and
    ``sliding_window`` name a kind of layer this model has none of; they are
    kept so that a configuration file is the published one."""
    attention_bias: bool = False
    cca_time0: int = 2
    cca_time1: int = 2
    head_dim: int = 128
    hidden_act: str = "silu"
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = ("hybrid",) * 40
    lm_head_bias: bool = False
    max_position_embeddings: int = 131072
    model_type: str = "zaya"
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 8
    num_experts: int = 16
    num_experts_per_tok: int = 1
    num_hidden_layers: int = 40
    num_key_value_heads: int = 2
    partial_rotary_factor: float = 0.5
    rms_norm_eps: float = 1e-5
    rope_parameters: Any = field(default_factory=lambda: json.loads(
        json.dumps(_ROPE)))
    router_hidden_size: int = 256
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = True
    vocab_size: int = 262272
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if isinstance(self.rope_parameters, str):   # ``_frozen``'s form
            self.rope_parameters = json.loads(self.rope_parameters)
        want = dict(attention_bias=False, lm_head_bias=False, cca_time0=2,
                    cca_time1=2, hidden_act="silu", num_experts_per_tok=1,
                    sliding_window=None, tie_word_embeddings=True)
        unsupported = [k for k, v in want.items() if getattr(self, k) != v]
        if unsupported:
            raise ValueError(f"Zaya1Config: only the published setting of "
                             f"{unsupported} is implemented")
        if self.layer_types != ("hybrid",) * self.num_hidden_layers:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each 'hybrid' (a CCA sublayer, then an expert sublayer): "
                f"got {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.num_key_value_heads != 2:
            raise ValueError(
                "CCA's values are TWO heads (the current token's and the "
                "previous token's) and the query heads divide over them")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError("partial_rotary_factor must leave an even "
                             "number of a head's dimensions to rotate")

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["hybrid"]["rope_theta"])

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def q_dim(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def mix_dim(self) -> int:
        """Channels the two convolutions mix: the latent queries and keys."""
        return (self.num_attention_heads + self.num_key_value_heads) * \
            self.head_dim

    @property
    def tail_dim(self) -> int:
        """What a slot keeps of its previous token: the pre-conv queries and
        keys, the first conv's output, the value to shift."""
        return 2 * self.mix_dim + self.head_dim

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return Zaya1Served(self)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: 3 layers, 4 / 2 heads of 8, 8 experts."""
        return Zaya1Config(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=3,
            layer_types=("hybrid",) * 3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, num_experts=8,
            moe_intermediate_size=16, router_hidden_size=16,
            max_position_embeddings=512, dtype="float32"), **overrides})


def as_dict(cfg: Zaya1Config):
    """The configuration as the reference takes it."""
    return dataclasses.asdict(cfg)


# -- the functional model ------------------------------------------------------

# Precision as in falcon_h1.py: the weights and every matmul's operands in the
# model's dtype, float32 accumulation; the residual stream with its scales,
# the norms, the depthwise conv, the tail, the temperature, RoPE, the whole
# router (down-projection, depth averaging, MLP, softmax, bias) and the logits
# float32.

def layer_shapes(cfg: Zaya1Config):
    """One layer's parameters as ``{name: (shape, dtype)}``: every matrix
    ``[in, out]`` in the model's dtype; the depthwise conv, the temperature,
    the residual scales and the router float32."""
    h, d, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    C, G = cfg.mix_dim, cfg.mix_dim // cfg.head_dim
    r, e, w = cfg.router_hidden_size, cfg.num_experts, \
        cfg.moe_intermediate_size
    return {
        "norm1": ((h,), dt),
        "qk_w": ((h, C), dt),              # Wq | Wk
        "v_w": ((h, 2 * d), dt),           # Wv1 (this token) | Wv2 (shifted)
        "conv1": ((2, C), "float32"),      # [0]: this position, [1]: the last
        "conv2": ((2, G, d, d), dt),       # the same, a matrix a head
        "tau": ((cfg.num_key_value_heads,), "float32"),
        "o_w": ((cfg.q_dim, h), dt),
        "scale1": ((4, h), "float32"),     # a, b, c, e
        "norm2": ((h,), dt),
        "router_norm": ((h,), dt),
        "router_down": ((h, r), "float32"),
        "router_eda": ((r,), "float32"),
        "router_w1": ((r, r), "float32"),
        "router_w2": ((r, r), "float32"),
        "router_w3": ((r, e), "float32"),
        "router_bias": ((e,), "float32"),
        "experts_gate": ((e, h, w), dt),
        "experts_up": ((e, h, w), dt),
        "experts_down": ((e, w, h), dt),
        "scale2": ((4, h), "float32"),
    }


LAYER_KEYS = tuple(layer_shapes(Zaya1Config.tiny()))


def _rope(cfg: Zaya1Config, x, pos):
    """Rotate-half RoPE over the FIRST ``rotary_dim`` of a head at global
    positions ``pos`` [R, W]; ``x`` float32 [R, W, heads, d]."""
    n = cfg.rotary_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, n, 2, dtype=F32) / n))
    f = pos.astype(F32)[..., None] * inv                   # [R, W, n/2]
    cos, sin = jnp.cos(f)[:, :, None, :], jnp.sin(f)[:, :, None, :]
    x1, x2 = x[..., :n // 2], x[..., n // 2:n]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., n:]], -1)


def _unit(x, eps):
    """``sqrt(d) x / ||x||`` as a weightless RMS norm."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


@part("attn_proj")
def _latent(p, u):
    """The latent queries and keys ``[R, W, mix_dim]`` and the two value
    projections ``[R, W, 2 d]`` of the normed input, float32."""
    return _mm(u, p["qk_w"]), _mm(u, p["v_w"])


@part("attn_proj")
@subpart("cca_mix")
def _mix(cfg: Zaya1Config, p, z, vv, pos, state, valid, step: bool):
    """From the latent projections to what the cache takes: the two causal
    convolutions and the value shift through ``served_model.recur`` — behind
    ``state``'s tail (``None``: a fresh sequence; a row's own ``{"tail"}``
    from its previous chunk; — ``step`` — the slot arenas of a round; or the
    ``Carried`` pair) — then the q-k mean, the norm, the temperature and the
    partial RoPE. Returns ``(q, k, v)`` in the weights' dtype and the tail
    after the last real token."""
    R, W, C = z.shape
    nh, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    G = C // d

    def convs(state, step, z, v2, valid):
        R, W, _ = z.shape
        tail = jnp.zeros((R, cfg.tail_dim), F32) if state is None \
            else state["tail"]

        def behind(seq, first):       # seq shifted one position to the right
            return jnp.concatenate([first[:, None], seq[:, :-1]], 1)

        w1 = p["conv1"].astype(F32)
        c1 = z * w1[0] + behind(z, tail[:, :C]) * w1[1]
        w2 = p["conv2"]
        c2 = sum(jnp.einsum("rwgc,gcd->rwgd",
                            s.reshape(R, W, G, d).astype(w2.dtype), w2[j],
                            preferred_element_type=F32)
                 for j, s in enumerate((c1, behind(c1, tail[:, C:2 * C]))))
        out = jnp.concatenate(
            [c2.reshape(R, W, C), behind(v2, tail[:, 2 * C:])], -1)
        # the next tail: what the row's last REAL token leaves; a row with
        # none (a padded row, an idle slot) keeps the one it has
        n = jnp.sum(valid, axis=1).astype(jnp.int32)
        last = jnp.take_along_axis(
            jnp.concatenate([z, c1, v2], -1),
            jnp.maximum(n - 1, 0)[:, None, None], axis=1)[:, 0]
        return out, {"tail": jnp.where((n > 0)[:, None], last, tail)}

    y, state = recur(convs, state, step, z, vv[..., d:], valid)
    zq = z[..., :nh * d].reshape(R, W, nh, d)
    zk = z[..., nh * d:].reshape(R, W, kv, d)
    mean = 0.5 * (zq + jnp.repeat(zk, nh // kv, axis=2))
    q = y[..., :nh * d].reshape(R, W, nh, d) + mean
    k = y[..., nh * d:C].reshape(R, W, kv, d) + \
        jnp.mean(mean.reshape(R, W, kv, nh // kv, d), axis=3)
    q = _unit(q, cfg.rms_norm_eps)
    k = _unit(k, cfg.rms_norm_eps) * p["tau"].astype(F32)[:, None]
    v = jnp.stack([vv[..., :d], y[..., C:]], axis=2)       # [R, W, 2, d]
    wd = p["qk_w"].dtype
    return (_rope(cfg, q, pos).astype(wd), _rope(cfg, k, pos).astype(wd),
            v.astype(wd)), state


def _scaled(s, x, y):
    """Residual scaling: ``(a x + b) + (c y + e)``, float32."""
    s = s.astype(F32)
    return (s[0] * x + s[1]) + (s[2] * y + s[3])


@part("attn_proj")
def _attn_out(p, x, ctx):
    R, W = ctx.shape[:2]
    return _scaled(p["scale1"], x, _mm(ctx.reshape(R, W, -1), p["o_w"]))


def router_mlp(p, r):
    """The router MLP's hidden layer (what ``router_w3`` scores) from a
    layer's router representation ``r`` — the last ``router_hidden_size``
    columns of the stream the block hands on: two gelu layers, float32 at
    full precision."""
    def mm(a, w):
        return jnp.matmul(a, w.astype(F32), precision=_HI)

    hid = jax.nn.gelu(mm(r, p["router_w1"]), approximate=False)
    return jax.nn.gelu(mm(hid, p["router_w2"]), approximate=False)


@part("router")
def router_hidden(cfg: Zaya1Config, p, x, r_prev):
    """The router up to its last matrix, float32 at full precision: the
    down-projection of the normed stream, the depth averaging with the
    previous layer's representation ``r_prev``, ``router_mlp``. Returns the
    MLP's hidden layer and this layer's representation (what the next layer
    averages in)."""
    r = jnp.matmul(_rms(x, p["router_norm"], cfg.rms_norm_eps),
                   p["router_down"].astype(F32), precision=_HI) + \
        p["router_eda"].astype(F32) * r_prev
    return router_mlp(p, r), r


def routed_share(cfg: Zaya1Config, p, u, hid, valid, first: int = 0):
    """What the experts ``[first, first + p["experts_up"].shape[0])`` add for
    the normed input ``u`` [R, W, h] under the router's hidden ``hid``: the
    whole sublayer's sum where ``p`` holds all of them. Returns it (before
    the residual scaling) and the routed-pair counts."""
    R, W, h = u.shape
    routed, stats = moe_held_experts_mlp(
        u.reshape(R * W, h).astype(p["experts_up"].dtype), p["router_w3"],
        p["experts_gate"], p["experts_up"], p["experts_down"],
        top_k=cfg.num_experts_per_tok, first=first, score="softmax",
        norm_topk=False, valid=None if valid is None else
        valid.reshape(R * W), x_route=hid.reshape(R * W, -1),
        bias=p["router_bias"])
    return routed.reshape(R, W, h), stats


@part("mlp")
def _experts(cfg: Zaya1Config, p, x, r_prev, valid):
    """The expert sublayer under its residual scaling (``router`` / ``experts``
    inside). Returns the stream, this layer's router representation and the
    routed-pair counts."""
    hid, r = router_hidden(cfg, p, x, r_prev)
    routed, stats = routed_share(
        cfg, p, _rms(x, p["norm2"], cfg.rms_norm_eps), hid, valid)
    return _scaled(p["scale2"], x, routed), r, stats


def block_fn(cfg: Zaya1Config, p, x, pos, attend, state, valid,
             step: bool = False):
    """One layer. ``x`` [R, W, h + router_hidden_size] float32: the residual
    stream and, behind it, the previous layer's router representation;
    ``attend(q, k, v) -> ctx`` causal attention; ``state`` / ``step`` as
    ``_mix`` takes them; ``valid`` [R, W] bool. Returns ``(x, state,
    stats)``."""
    h = cfg.hidden_size
    x, r_prev = x[..., :h], x[..., h:]
    z, vv = _latent(p, _rms(x, p["norm1"], cfg.rms_norm_eps))
    qkv, state = _mix(cfg, p, z, vv, pos, state, valid, step)
    x = _attn_out(p, x, attend(*qkv))
    x, r, stats = _experts(cfg, p, x, r_prev, valid)
    return jnp.concatenate([x, r], -1), state, stats


def widen(cfg: Zaya1Config, x):
    """The stream a stack starts from: the embedded tokens and a zero router
    representation behind them (``r_{-1} = 0``)."""
    return jnp.concatenate(
        [x.astype(F32),
         jnp.zeros(x.shape[:-1] + (cfg.router_hidden_size,), F32)], -1)


def head_fn(cfg: Zaya1Config, params, x):
    """Final norm of the residual stream (the router's columns are not read)
    and the tied head: float32 logits."""
    y = _rms(x[..., :cfg.hidden_size], params["final_norm"], cfg.rms_norm_eps)
    embed = params["embed"]
    return jax.lax.dot_general(
        y.astype(embed.dtype), embed, (((y.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=F32)


def forward_fn(cfg: Zaya1Config, params, x, block=None):
    """The whole stack on the embedded tokens ``x`` [R, W, h], every row a
    fresh sequence. Returns ``(logits [R, W, vocab], tails)``: each layer's
    final ``{"tail"}``."""
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    attend = _dense_attend(1.0 / math.sqrt(cfg.head_dim), block)
    valid, x, tails = jnp.ones((R, W), bool), widen(cfg, x), []
    for p in params["layers"]:
        x, st, _stats = block_fn(cfg, p, x, pos, attend, None, valid)
        tails.append(st)
    return head_fn(cfg, params, x), tails


def _frozen(cfg: Zaya1Config):
    items = dataclasses.asdict(cfg)
    items["rope_parameters"] = json.dumps(items["rope_parameters"],
                                          sort_keys=True)
    return tuple(sorted(items.items()))


@primitive("zaya1_stack")
def _stack_op(x, norm_w, embed_w, *weights, cfg_items):
    cfg = Zaya1Config(**dict(cfg_items))
    n = len(LAYER_KEYS)
    layers = [dict(zip(LAYER_KEYS, weights[i * n:(i + 1) * n]))
              for i in range(cfg.num_hidden_layers)]
    logits, _tails = forward_fn(
        cfg, {"layers": layers, "final_norm": norm_w, "embed": embed_w}, x)
    return logits


# -- layers --------------------------------------------------------------------

class Zaya1Block(_Weights):
    """One layer's parameters. Random weights: every matrix ``N(0, 1 /
    fan_in)`` (each path carries unit-scale signal), the two conv taps
    ``1 / sqrt(2)`` of that each; what a draw could leave at its neutral
    value is drawn away from it (the module's constants): the selection bias,
    the temperature, the depth-averaging gate, the four residual scales."""

    def __init__(self, cfg: Zaya1Config):
        super().__init__()
        key = random_mod.next_key

        def uniform(shape, lo, hi):
            return jax.random.uniform(key(), shape, F32, lo, hi)

        for name, (shape, dt) in layer_shapes(cfg).items():
            if name.startswith(("norm", "router_norm")):
                self._given(name, jnp.ones(shape,
                                           dtype_mod.convert_dtype(dt)))
            elif name == "conv1":
                self._given(name, uniform(shape, -1.0, 1.0))
            elif name == "conv2":
                self._normal(name, shape, 1.0 / math.sqrt(2 * shape[-2]), dt)
            elif name == "tau":
                self._given(name, uniform(shape, *TAU_RANGE))
            elif name == "router_eda":
                self._given(name, uniform(shape, *EDA_RANGE))
            elif name == "router_bias":
                self._normal(name, shape, ROUTER_BIAS_STD, dt)
            elif name.startswith("scale"):
                a, c = (uniform(shape[1:], *SCALE_RANGE) for _ in "ac")
                b, e = (jax.random.normal(key(), shape[1:], F32) * SHIFT_STD
                        for _ in "be")
                self._given(name, jnp.stack([a, b, c, e]))
            else:
                self._normal(name, shape, 1.0 / math.sqrt(shape[-2]), dt)


class Zaya1ForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` layers, final RMSNorm, the head TIED
    to the embedding. ``forward(input_ids)`` is the whole-sequence forward
    ([batch, seq] -> logits); serving goes through ``served_model()``."""

    def __init__(self, config: Zaya1Config):
        super().__init__()
        self.config = cfg = config
        h = cfg.hidden_size
        # the vocabulary-sized matrix first, while the device is empty
        self._normal("embed_tokens", (cfg.vocab_size, h), 1.0 / math.sqrt(h),
                     cfg.dtype)
        self.layers = nn.LayerList(
            [Zaya1Block(cfg) for _ in range(cfg.num_hidden_layers)])
        self._given("norm_f", jnp.full((h,), LOGIT_STD,
                                       dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens)
        return _stack_op(
            x, self.norm_f, self.embed_tokens,
            *(getattr(L, k) for L in self.layers for k in LAYER_KEYS),
            cfg_items=_frozen(self.config))

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return Zaya1Served(self.config)


class Zaya1Served(ServedModel):
    """ZAYA1 on the seam: every layer keeps memory of BOTH kinds
    (``cache_spec["layers"]`` ``"full+state"``) — K/V pages of 2 heads x 128
    holding the finished keys and the shifted values, and ``state_spec``'s
    tail ``[slots, tail_dim]`` float32. Its ``block`` resumes: a prefill chunk
    handed the tail the previous one left goes on from it, so the largest
    bucket carries the round (``carries_rounds``). The stream between blocks
    is ``hidden_size + router_hidden_size`` wide (module docstring):
    ``embed`` starts the router's columns at zero and ``head`` does not read
    them. Every window program hands back the routed-pair counts
    (``program_counters``)."""

    resumes_state = True
    program_counters = tuple(HELD_EXPERTS_COUNTERS)

    def __init__(self, cfg: Zaya1Config):
        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = 1.0 / math.sqrt(cfg.head_dim)
        self.cache_spec = {"kind": "kv_by_layer",
                           "layers": [LAYER_KIND] * cfg.num_hidden_layers}
        self.state_spec = {"tail": ((cfg.tail_dim,), F32)}

    def params(self, model):
        return {"embed": model.embed_tokens.data,
                "final_norm": model.norm_f.data,
                "layers": [{k: getattr(L, k).data for k in LAYER_KEYS}
                           for L in model.layers]}

    def param_shapes(self):
        """The ``params`` pytree as shapes alone (an ahead-of-time compile
        for a described chip has no device to hold the weights)."""
        c, dt = self.cfg, dtype_mod.convert_dtype(self.cfg.dtype)
        sd = jax.ShapeDtypeStruct
        return {"embed": sd((c.vocab_size, c.hidden_size), dt),
                "final_norm": sd((c.hidden_size,), dt),
                "layers": [{k: sd(s, dtype_mod.convert_dtype(d))
                            for k, (s, d) in layer_shapes(c).items()}
                           for _ in range(c.num_hidden_layers)]}

    def embed(self, params, tokens, pos):
        return widen(self.cfg, params["embed"][tokens])

    def block(self, p, x, pos, attend, state, valid, step: bool = False):
        """``step``: the engine's word that ``state`` is the slot arenas of a
        decode round (one token a row); else ``state`` is what the row's
        previous chunk left, or ``None``."""
        x, state, stats = block_fn(self.cfg, p, x, pos, attend, state, valid,
                                   step)
        return x, state, held_experts_counters(stats)

    def head(self, params, x):
        return head_fn(self.cfg, params, x)
