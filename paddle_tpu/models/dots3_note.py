"""dots3-note causal LM (HF ``model_type`` ``dots3_note``;
``dots-studio/dots3-note-prev/config.json``, the language model alone): latent
attention (MLA) of TWO kinds in pre-norm blocks — ``full_attention`` layers
(128 heads, KV rank 512) under a learned sparse attention (a lightning indexer
of their own in every one, DeepSeek Sparse Attention's), and
``sliding_attention`` layers with their own ranks, head count and row width
(64 heads, KV rank 1024, keys of 192 + 64) that see the last
``sliding_window_size`` positions — a sigmoid gate a head on the attention
output of both, and sparse experts beside a shared one with a ``noaux_tc``
selection bias. The layer equations are written out in
``models/reference/dots3_note.py`` (the plain float32 reference this file is
tested against).

Nothing of the block is new code: the projections are ``openpangu_moe.mla_in``
/ ``mla_out`` (with the latents' rescale and the head gate as arguments), the
indexer and the MLP ``glm_moe_dsa._index_in`` / ``_ffn``, the gate
``laguna.head_gate``. One functional block, ``block_fn``, is the model: the
``nn.Layer`` forward runs it with a dense-within-window ``attend``, and
``serving.GenerationEngine`` runs the SAME function through the served-model
seam (``Dots3NoteServed``) with its paged ``attend``, which carries the
layer's kind (``attend.kind``: what the block picks its sizes and its RoPE
base by).

A model may hold a SHARE of each expert layer and a RUN of the published
layers, as ``glm_moe_dsa``. The vision tower, the audio encoder and the
multi-token-prediction module of the published model have no keys in the
language model's configuration and are not here.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..framework import dtype as dtype_mod
from ..nn import functional as F
from ..nn.layer.moe import HELD_EXPERTS_COUNTERS, held_experts_counters
from ..serving.served_model import ServedModel
from .falcon_h1 import F32, _mm, _rms, _Weights
from .glm_moe_dsa import _DenseAttend as _SelectedAttend
from .glm_moe_dsa import (DENSE_MLP_KEYS, INDEX_KEYS, MOE_MLP_KEYS,
                          QUERY_GAIN, _ffn, _frozen, _index_in, rope_pairs)
from .laguna import head_gate
from .openpangu_moe import mla_in, mla_out

# the published pattern (46 layers): two leading full layers, then
# ``sliding, sliding, sliding, full`` eleven times (13 full, 33 sliding)
_LAYER_TYPES = ["full_attention"] * 2 + \
    (["sliding_attention"] * 3 + ["full_attention"]) * 11


@dataclass
class Dots3NoteConfig:
    """The published ``config.json`` keys, letter for letter (defaults:
    dots3-note-prev), plus what a share of the model needs (``layer_offset``,
    ``router_experts``, ``held_experts_first``) and ``dtype``; the counts mean
    what ``GlmMoeDsaConfig``'s do."""
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    layer_types: List[str] = field(default_factory=lambda: list(_LAYER_TYPES))
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000
    attention_gate_type: str = "headwise"
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    sliding_window_size: int = 513
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000
    swa_attention_gate_type: str = "headwise"
    apply_mla_qkv_lora_rescale: bool = True
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1
    hidden_act: str = "silu"
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-5
    rope_scaling: Optional[dict] = None
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    model_type: str = "dots3_note"
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
                    topk_method="noaux_tc", scoring_func="sigmoid",
                    moe_layer_freq=1, rope_scaling=None,
                    attention_gate_type="headwise",
                    swa_attention_gate_type="headwise")
        unsupported = [k for k, v in want.items() if getattr(self, k) != v]
        if unsupported:
            raise ValueError(f"Dots3NoteConfig: {unsupported} must be "
                             f"{[want[k] for k in unsupported]}")
        for pre in ("", "swa_"):
            if getattr(self, pre + "num_key_value_heads") != \
                    getattr(self, pre + "num_attention_heads"):
                raise ValueError(
                    "latent attention has one latent for all heads: "
                    f"{pre}num_key_value_heads == {pre}num_attention_heads")
        last = self.layer_offset + self.num_hidden_layers
        if len(self.layer_types) < last:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries, the model "
                f"holds published layers {self.layer_offset}-{last - 1}")
        if set(self.layer_types) - {"full_attention", "sliding_attention"}:
            raise ValueError("layer_types: 'full_attention' or "
                             f"'sliding_attention', got {self.layer_types}")

    def layer_kinds(self) -> List[str]:
        """``full`` / ``window`` of the layers held here."""
        held = self.layer_types[
            self.layer_offset:self.layer_offset + self.num_hidden_layers]
        return ["window" if t == "sliding_attention" else "full"
                for t in held]

    def is_dense(self, layer: int) -> bool:
        return self.layer_offset + layer < self.first_k_dense_replace

    def is_full(self, layer: int) -> bool:
        return self.layer_kinds()[layer] == "full"

    def dims(self, kind: str) -> dict:
        """A layer kind's sizes: heads, query and KV ranks, key (nope / rope)
        and value head widths, RoPE base, and the latents' rescale."""
        pre = "swa_" if kind == "window" else ""
        get = lambda name: getattr(self, pre + name)  # noqa: E731
        dq, dc = get("q_lora_rank"), get("kv_lora_rank")
        rescale = (math.sqrt(self.hidden_size / dq),
                   math.sqrt(self.hidden_size / dc)) \
            if self.apply_mla_qkv_lora_rescale else None
        return dict(heads=get("num_attention_heads"), dq=dq, dc=dc,
                    dn=get("qk_nope_head_dim"), dr=get("qk_rope_head_dim"),
                    dv=get("v_head_dim"), theta=float(get("rope_theta")),
                    rescale=rescale)

    def attn_scale(self, kind: str) -> float:
        d = self.dims(kind)
        return 1.0 / math.sqrt(d["dn"] + d["dr"])

    def served_model(self):
        """The served-model protocol from the configuration alone (shapes,
        no weights): what an ahead-of-time compile needs."""
        return Dots3NoteServed(self)

    @staticmethod
    def tiny(**overrides):
        """The CPU tests' size: every mechanism present (published layers
        0-4: the dense full layer, a full expert layer, three window expert
        layers; the two kinds' ranks, heads and row widths all different; 8
        experts of which 2 a token, one shared; 6 index keys a query; a
        window of 9)."""
        return Dots3NoteConfig(**{**dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=5,
            layer_types=list(_LAYER_TYPES[:5]), num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=10,
            index_n_heads=4, index_head_dim=8, index_topk=6,
            sliding_window_size=9, swa_num_attention_heads=2,
            swa_num_key_value_heads=2, swa_q_lora_rank=20,
            swa_kv_lora_rank=24, swa_qk_nope_head_dim=12,
            swa_qk_rope_head_dim=4, swa_v_head_dim=6, n_routed_experts=8,
            num_experts_per_tok=2, max_position_embeddings=512,
            dtype="float32"), **overrides})


# -- the functional model ------------------------------------------------------

# Precision as in glm_moe_dsa.py. Parts (``observability.trace.parts``) are
# the imported helpers' own.

ATTN_KEYS = ("input_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o", "g", "post_attn_norm")


def layer_keys(cfg: Dots3NoteConfig, layer: int):
    return ATTN_KEYS + (INDEX_KEYS if cfg.is_full(layer) else ()) + \
        (DENSE_MLP_KEYS if cfg.is_dense(layer) else MOE_MLP_KEYS)


def block_fn(cfg: Dots3NoteConfig, p, x, pos, attend, valid):
    """One pre-norm block of the kind ``attend.kind`` names (``full`` /
    ``window``). ``x`` [R, W, h], the float32 residual stream; ``pos`` [R, W]
    global positions; ``attend(q_lat, q_rope, row, index=None) -> ctx``:
    causal absorbed attention of the window's queries given the window's own
    cache rows ``row`` [R, W, kv rank + rope dim of the kind] — a ``full``
    layer hands ``index = (qI, wI, kI)`` and attends the keys it selects, a
    ``window`` layer the last ``sliding_window_size`` positions. ``valid`` [R,
    W] bool or None. Returns ``(x, stats)``: the expert layer's routed-pair
    counts, ``None`` for a dense layer."""
    eps, d = cfg.rms_norm_eps, cfg.dims(attend.kind)
    u = _rms(x, p["input_norm"], eps)
    q_lat, q_rope, row, kv_b, c_q = mla_in(
        p, u, pos, lambda t, at: rope_pairs(t, at, d["theta"]),
        heads=d["heads"], dn=d["dn"], dr=d["dr"], dv=d["dv"], dc=d["dc"],
        eps=eps, rescale=d["rescale"])
    if "index_q" in p:     # from the UNSCALED query latent
        ctx = attend(q_lat, q_rope, row, index=_index_in(cfg, p, u, c_q, pos))
    else:
        ctx = attend(q_lat, q_rope, row)
    x = mla_out(p, x, ctx, kv_b, dn=d["dn"], dv=d["dv"],
                gate=head_gate(u, p["g"]))
    return _ffn(cfg, p, x, _rms(x, p["post_attn_norm"], eps), valid)


class _DenseAttend(_SelectedAttend):
    """Causal absorbed attention within the window, every row a fresh
    sequence (the ``nn.Layer`` forward), of one layer kind: a ``full`` layer
    over the keys its indexer selects (GLM-5.2's dense form; ``selected``
    gets each one's ``[R, W, W]`` bool mask: what the tests compare with the
    reference's sets), a ``window`` layer over the keys ``i - window < j <=
    i``."""

    def __init__(self, cfg: Dots3NoteConfig, kind: str, selected: list):
        super().__init__(cfg.attn_scale(kind), cfg.index_topk)
        self.kind, self.selected = kind, selected
        self.window = cfg.sliding_window_size

    def __call__(self, q_lat, q_rope, row, index=None):
        if index is None:
            W = q_lat.shape[1]
            i, j = jnp.arange(W)[:, None], jnp.arange(W)[None, :]
            self.bias = jnp.where((j <= i) & (j > i - self.window),
                                  0.0, -1e30)[None]
        return super().__call__(q_lat, q_rope, row, index)


def _thawed(items) -> Dots3NoteConfig:
    d = dict(items)
    d["layer_types"] = list(d["layer_types"])
    return Dots3NoteConfig(**d)


def forward_fn(cfg: Dots3NoteConfig, params, x):
    """The whole stack on the embedded stream ``x`` [R, W, h], every row a
    fresh sequence: ``(logits [R, W, vocab], selected)``."""
    R, W, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (R, W))
    selected: list = []
    x = x.astype(F32)
    for p, kind in zip(params["layers"], cfg.layer_kinds()):
        x, _stats = block_fn(cfg, p, x, pos,
                             _DenseAttend(cfg, kind, selected), None)
    return _mm(_rms(x, params["final_norm"], cfg.rms_norm_eps),
               params["head"]), selected


@primitive("dots3_note_stack")
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

def param_shapes(cfg: Dots3NoteConfig, layer: int):
    """One layer's parameters as ``{name: (shape, dtype)}``: every matrix
    ``[in, out]`` in the model's dtype; the router, its selection bias and
    the index key's LayerNorm float32."""
    h, dt = cfg.hidden_size, cfg.dtype
    d = cfg.dims(cfg.layer_kinds()[layer])
    H, dn, dr, dv, dc, dq = (d[k] for k in ("heads", "dn", "dr", "dv", "dc",
                                            "dq"))
    out = {"input_norm": ((h,), dt), "q_a": ((h, dq), dt),
           "q_a_norm": ((dq,), dt), "q_b": ((dq, H * (dn + dr)), dt),
           "kv_a": ((h, dc + dr), dt), "kv_a_norm": ((dc,), dt),
           "kv_b": ((dc, H * (dn + dv)), dt), "o": ((H * dv, h), dt),
           "g": ((h, H), dt), "post_attn_norm": ((h,), dt)}
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


class Dots3NoteBlock(_Weights):
    """One block's parameters. Random weights as ``GlmMoeDsaBlock`` draws them
    (every matrix ``N(0, 1 / fan_in)``, the query up-projection
    ``QUERY_GAIN`` times wider, the router's selection bias ``N(0,
    0.02^2)``, an expert layer ALL ``router_experts`` columns of router and
    bias) — and ``q_b`` / ``kv_b``, whose inputs arrive rescaled by ``a_q`` /
    ``a_kv``, that much narrower, so that they too hand on unit-scale signal
    (a trained model's weights have absorbed the factor; without this the
    scores of random weights would spread over tens of units and every
    softmax would be one key)."""

    def __init__(self, cfg: Dots3NoteConfig, layer: int):
        super().__init__()
        self.keys = layer_keys(cfg, layer)
        shapes = param_shapes(cfg, layer)
        a_q, a_kv = cfg.dims(cfg.layer_kinds()[layer])["rescale"] or (1, 1)
        gains = {"q_b": QUERY_GAIN / a_q, "kv_b": 1.0 / a_kv}
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
                self._normal(name, shape,
                             gains.get(name, 1.0) / math.sqrt(shape[-2]), dt)


class Dots3NoteForCausalLM(_Weights):
    """Embedding, ``num_hidden_layers`` blocks (published layers
    ``layer_offset ...``), final RMSNorm, an untied head.
    ``forward(input_ids)`` is the whole-sequence forward ([batch, seq] ->
    logits); serving goes through ``served_model()``."""

    def __init__(self, config: Dots3NoteConfig):
        super().__init__()
        self.config = cfg = config
        h, v = cfg.hidden_size, cfg.vocab_size
        self._normal("embed_tokens", (v, h), 1.0, cfg.dtype)
        # logits spread like a trained LM's (``GlmMoeDsaForCausalLM``)
        self._normal("lm_head", (h, v), 3.0 / math.sqrt(h), cfg.dtype)
        self.layers = nn.LayerList(
            [Dots3NoteBlock(cfg, i) for i in range(cfg.num_hidden_layers)])
        self._given("norm", jnp.ones((h,), dtype_mod.convert_dtype(cfg.dtype)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens).astype("float32")
        return _stack_op(
            x, self.norm, self.lm_head,
            *(getattr(L, k) for L in self.layers for k in L.keys),
            cfg_items=_frozen(self.config))

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        return Dots3NoteServed(self.config)


class Dots3NoteServed(ServedModel):
    """dots3-note on the seam: a LATENT cache whose layers are of two kinds
    (``cache_spec`` ``latent`` with ``layers``): a ``full`` layer leaves a row
    of 512 + 64 and an index key (the ``index`` group names the full layers
    alone), a ``window`` layer a row of 1024 + 64 (``window_row``: its width,
    value width, softmax scale and heads) that the next
    ``sliding_window_size - 1`` tokens read. No recurrent state; every window program hands back the
    expert layers' routed-pair counts (``program_counters``)."""

    program_counters = tuple(HELD_EXPERTS_COUNTERS)

    def __init__(self, cfg: Dots3NoteConfig):
        self.cfg = cfg
        full, win = cfg.dims("full"), cfg.dims("window")
        kinds = cfg.layer_kinds()
        self.num_layers = cfg.num_hidden_layers
        self.num_heads = self.num_kv_heads = full["heads"]
        self.head_dim = full["dn"] + full["dr"]
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.attn_scale = cfg.attn_scale("full")
        self.cache_spec = {
            "kind": "latent", "dim": full["dc"] + full["dr"],
            "value_dim": full["dc"], "layers": kinds,
            "window": cfg.sliding_window_size,
            "window_row": {"dim": win["dc"] + win["dr"],
                           "value_dim": win["dc"],
                           "scale": cfg.attn_scale("window"),
                           "heads": win["heads"]},
            "index": {"dim": cfg.index_head_dim, "heads": cfg.index_n_heads,
                      "topk": cfg.index_topk,
                      "layers": ["full" if kind == "full" else None
                                 for kind in kinds]}}

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
        return _mm(_rms(x, params["final_norm"], self.cfg.rms_norm_eps),
                   params["head"])
