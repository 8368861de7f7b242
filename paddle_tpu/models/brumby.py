"""Brumby-14B-Base (Manifest AI, 2025; HF ``model_type`` ``brumby``):
Qwen3-14B's shapes with every attention layer replaced by a POWER RETENTION layer
(arXiv:2507.04239; degree 2) — a gated linear attention whose memory is, per
K/V head, a float32 state ``S`` [D, head_dim] and a normaliser, whatever the
context: there is no K/V cache at all. The layer's equations are written out
in ``models/reference/brumby.py`` (the plain float32 attention form this file
is tested against) and ``kernels/pallas/power_retention.py`` (the recurrent
form, the tiled ``phi`` and the two kernels).

One functional block, ``block_fn``, is the model: the ``nn.Layer`` forward
runs it over whole sequences from a zero state (the chunked form), and
``serving.GenerationEngine`` runs the SAME function through the served-model
seam (``BrumbyServed``): a prefill chunk from the state the prompt's previous
chunk left (``None``: from zero) with ``retention_chunk``, one token over the
slot arenas with ``retention_step``. ``attend`` is not used: the model
declares ``cache_spec = {"kind": "none"}`` and is handed ``None``.

What the published ``config.json`` does not carry is ``BrumbyConfig``'s second
group of fields, with the defaults this repository ASSUMES (the configuration
file lists them under ``assumed``).

Weights are created on the device, in the configuration's dtype, from
``paddle.seed``: nothing holds a float32 copy of the parameters anywhere.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..nn import functional as F
from ..observability.trace.parts import part, subpart
from ..serving.served_model import ServedModel
from .falcon_h1 import _Weights, _mm, _rms, _rope

F32 = jnp.float32


@dataclass
class BrumbyConfig:
    """The published ``config.json`` keys, letter for letter (defaults:
    Brumby-14B-Base), then what it does not carry, then ``dtype``."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    hidden_act: str = "silu"
    max_position_embeddings: int = 32768
    max_window_layers: int = 40
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    attention_bias: bool = False
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    model_type: str = "brumby"
    # -- assumed (the config.json carries none of it) --------------------------
    retention_power: int = 2            # p: even, so every weight is >= 0
    retention_eps: float = 1e-6         # beside the normaliser
    retention_chunk: int = 128          # the inner chunk c
    # the gate's weight W_g [hidden, kv_heads] is N(0, (gate_std / sqrt(h))^2)
    # and its bias-free logit is shifted by gate_shift: e^lambda =
    # sigmoid(shift + std n) spans ~0.9 .. 0.999 (shift 4.5, std 1.2: the
    # 2.5 % and 97.5 % points are 0.895 and 0.9990)
    gate_shift: float = 4.5
    gate_std: float = 1.2
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.retention_power != 2:
            raise ValueError("power retention of degree 2 alone is built "
                             f"(phi is the symmetric square), got "
                             f"{self.retention_power}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.hidden_act != "silu" or self.attention_bias or \
                self.rope_scaling is not None or self.tie_word_embeddings \
                or self.sliding_window is not None or self.use_sliding_window:
            raise ValueError("only the published form is built: silu, no "
                             "bias, no rope scaling, no sliding window, an "
                             "untied head")

    def served_model(self):
        return BrumbyServed(self)

    @staticmethod
    def tiny(**overrides):
        """A CPU-sized preset of the same structure (float32)."""
        base = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=8,
                    max_position_embeddings=256, max_window_layers=2,
                    retention_chunk=8, dtype="float32")
        base.update(overrides)
        return BrumbyConfig(**base)


# -- the functional model ------------------------------------------------------

# Precision, as Falcon-H1's: weights and matmul operands in the model's dtype,
# the residual stream, norms, RoPE, the gate, the retention's state and its
# arithmetic and the logits in float32.

def _head_norm(x, w, eps):
    """RMSNorm over the ``head_dim`` of every head; float32."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


@part("attn_proj")
def _qkvg(cfg: BrumbyConfig, p, u, pos):
    """q, k (head-normed, roped), v in the weights' dtype and the gate's log,
    float32 ``[R, W, kv_heads]`` (<= 0)."""
    R, W, _ = u.shape
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    wd = p["q_w"].dtype
    q = _head_norm(_mm(u, p["q_w"]).reshape(R, W, nh, hd), p["q_norm"],
                   cfg.rms_norm_eps)
    k = _head_norm(_mm(u, p["k_w"]).reshape(R, W, kvh, hd), p["k_norm"],
                   cfg.rms_norm_eps)
    v = _mm(u, p["v_w"]).reshape(R, W, kvh, hd)
    log_g = jax.nn.log_sigmoid(_mm(u, p["g_w"]) + cfg.gate_shift)
    return (_rope(q, pos, cfg.rope_theta).astype(wd),
            _rope(k, pos, cfg.rope_theta).astype(wd), v.astype(wd), log_g)


@part("attention")
@subpart("retention")
def _retain(cfg: BrumbyConfig, q, k, v, log_g, state, valid, step: bool):
    """The retention itself. ``step``: one token over the slot arenas;
    else a window from ``state`` (``None``: from zero). Returns the heads'
    outputs ``[R, W, heads, head_dim]`` (float32) and the state."""
    from ..kernels.pallas import power_retention as pr

    if step:
        S, Z, y = pr.retention_step(state["S"], state["z"], q[:, 0], k[:, 0],
                                    v[:, 0], log_g[:, 0], valid[:, 0])
        return y[:, None], {"S": S, "z": Z}
    if state is None:
        R, kvh, hd = q.shape[0], cfg.num_key_value_heads, cfg.head_dim
        state = {"S": jnp.zeros((R, kvh, pr.phi_dim(hd), hd), F32),
                 "z": jnp.zeros((R, kvh, hd, hd), F32)}
    S, Z, y = pr.retention_chunk(state["S"], state["z"], q, k, v, log_g,
                                 valid, chunk=cfg.retention_chunk)
    return y, {"S": S, "z": Z}


@part("attention")
@subpart("retention")
def _advanced(valid, step: bool):
    """What the call advanced, for the engine's counters (int32 scalars the
    window program sums over its layers): the rows of a round, the valid
    positions of a chunk."""
    n = jnp.sum(valid).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    return {"retention_steps_total": n if step else zero,
            "retention_chunk_tokens_total": zero if step else n}


@part("attn_proj")
def _attn_out(p, x, y):
    R, W = y.shape[:2]
    return x + _mm(y.reshape(R, W, -1), p["o_w"])


@part("mlp")
def _mlp(p, x, v2):
    m = _mm(v2, p["up_w"]) * jax.nn.silu(_mm(v2, p["gate_w"]))
    return x + _mm(m, p["down_w"])


def block_fn(cfg: BrumbyConfig, p, x, pos, state, valid, step: bool = False):
    """One Brumby block. ``x`` [R, W, h], the float32 residual stream;
    ``pos`` [R, W] global positions; ``state``: ``None`` (a fresh sequence),
    the rows' state ``{"S", "z"}`` a previous window left, or — ``step`` —
    the slot arenas; ``valid`` [R, W]: the positions that hold a token."""
    u = _rms(x, p["input_norm"], cfg.rms_norm_eps)
    y, state = _retain(cfg, *_qkvg(cfg, p, u, pos), state, valid, step)
    x = _attn_out(p, x, y)
    return _mlp(p, x, _rms(x, p["post_norm"], cfg.rms_norm_eps)), state


BLOCK_KEYS = ("input_norm", "q_w", "k_w", "v_w", "g_w", "q_norm", "k_norm",
              "o_w", "post_norm", "gate_w", "up_w", "down_w")


@primitive("brumby_block")
def _block_op(x, *weights, cfg_items):
    cfg = BrumbyConfig(**dict(cfg_items))
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    out, _state = block_fn(cfg, dict(zip(BLOCK_KEYS, weights)),
                           x.astype(F32), pos, None, jnp.ones((R, W), bool))
    return out


@primitive("brumby_head")
def _head_op(x, norm_w, head_w, *, eps):
    return _mm(_rms(x.astype(F32), norm_w, eps), head_w)


# -- layers --------------------------------------------------------------------

class BrumbyBlock(_Weights):
    """One block's parameters (``BLOCK_KEYS``, stored ``[in, out]``) and its
    forward. Random weights: every matrix ``N(0, 1 / fan_in)``, so that each
    path carries unit-scale signal; the gate's as ``BrumbyConfig`` says."""

    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self._cfg_items = tuple(sorted(
            (k, v) for k, v in dataclasses.asdict(cfg).items()))
        h, i, dt = cfg.hidden_size, cfg.intermediate_size, cfg.dtype
        nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        ones = lambda n: jnp.ones((n,), dtype_mod.convert_dtype(dt))  # noqa
        fan = lambda n: 1.0 / math.sqrt(n)                            # noqa
        self._given("input_norm", ones(h))
        self._normal("q_w", (h, nh * hd), fan(h), dt)
        self._normal("k_w", (h, kvh * hd), fan(h), dt)
        self._normal("v_w", (h, kvh * hd), fan(h), dt)
        self._normal("g_w", (h, kvh), cfg.gate_std * fan(h), dt)
        self._given("q_norm", ones(hd))
        self._given("k_norm", ones(hd))
        self._normal("o_w", (nh * hd, h), fan(nh * hd), dt)
        self._given("post_norm", ones(h))
        self._normal("gate_w", (h, i), fan(h), dt)
        self._normal("up_w", (h, i), fan(h), dt)
        self._normal("down_w", (i, h), fan(i), dt)

    def forward(self, hidden):
        return _block_op(hidden, *(getattr(self, k) for k in BLOCK_KEYS),
                         cfg_items=self._cfg_items)


class BrumbyForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` blocks, final RMSNorm, an untied
    head. ``forward(input_ids)`` is the whole-sequence forward ([batch, seq]
    -> logits) in the chunked form; serving goes through ``served_model()``."""

    def __init__(self, config: BrumbyConfig):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        # the two vocabulary-sized matrices first, while the device is empty
        self._normal("embed_tokens", (v, h), 1.0, cfg.dtype)
        # logits spread like a trained LM's (a few units), so that an error
        # in the stream shows in the logprobs the engine reports
        self._normal("lm_head", (h, v), 3.0 / math.sqrt(h), cfg.dtype)
        self.layers = nn.LayerList(
            [BrumbyBlock(cfg) for _ in range(cfg.num_hidden_layers)])
        self._given("final_layernorm",
                    jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens).astype("float32")
        for layer in self.layers:
            x = layer(x)
        return _head_op(x, self.final_layernorm, self.lm_head,
                        eps=self.config.rms_norm_eps)

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return BrumbyServed(self.config)


class BrumbyServed(ServedModel):
    """Brumby on the seam: NOTHING paged (``cache_spec`` of kind ``"none"``)
    and per layer the retention's state by slot — ``S`` ``[slots, kv_heads,
    D, head_dim]`` and the dense normaliser ``z`` ``[slots, kv_heads,
    head_dim, head_dim]``, float32. Its ``block`` resumes: a prefill chunk
    handed the state the previous one left goes on from it."""

    cache_spec = {"kind": "none"}
    resumes_state = True
    program_counters = ("retention_steps_total",
                        "retention_chunk_tokens_total")

    def __init__(self, cfg: BrumbyConfig):
        from ..kernels.pallas.power_retention import phi_dim

        self.cfg = cfg
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = 1.0 / math.sqrt(cfg.head_dim)
        kvh, hd = cfg.num_key_value_heads, cfg.head_dim
        self.state_spec = {"S": ((kvh, phi_dim(hd), hd), F32),
                           "z": ((kvh, hd, hd), F32)}

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
        h, i, hd = c.hidden_size, c.intermediate_size, c.head_dim
        qd, kd = c.num_attention_heads * hd, c.num_key_value_heads * hd
        sd = jax.ShapeDtypeStruct
        layer = {"input_norm": sd((h,), dt), "q_w": sd((h, qd), dt),
                 "k_w": sd((h, kd), dt), "v_w": sd((h, kd), dt),
                 "g_w": sd((h, c.num_key_value_heads), dt),
                 "q_norm": sd((hd,), dt), "k_norm": sd((hd,), dt),
                 "o_w": sd((qd, h), dt), "post_norm": sd((h,), dt),
                 "gate_w": sd((h, i), dt), "up_w": sd((h, i), dt),
                 "down_w": sd((i, h), dt)}
        return {"embed": sd((c.vocab_size, h), dt),
                "head": sd((h, c.vocab_size), dt),
                "final_norm": sd((h,), dt),
                "layers": [dict(layer) for _ in range(c.num_hidden_layers)]}

    def embed(self, params, tokens, pos):
        return params["embed"][tokens].astype(F32)

    def block(self, p, x, pos, attend, state, valid, step: bool = False):
        """``step``: the engine's word that ``state`` is the slot arenas of a
        decode round (one token a row); else ``state`` is what the rows'
        previous chunk left, or ``None``."""
        x, state = block_fn(self.cfg, p, x, pos, state, valid, step)
        return x, state, _advanced(valid, step)

    def reference_state(self, state):
        """One slot's (or any rows') state of a layer on the minimal
        symmetric square a reference holds: ``{"S": [.., kv_heads, d (d + 1)
        / 2, d], "z": [.., kv_heads, d (d + 1) / 2]}``."""
        from ..kernels.pallas.power_retention import canonical_state

        S, z = canonical_state(state["S"], state["z"])
        return {"S": S, "z": z}

    def head(self, params, x):
        # float32 logits, as Falcon-H1's
        return _mm(_rms(x, params["final_norm"], self.cfg.rms_norm_eps),
                   params["head"])
