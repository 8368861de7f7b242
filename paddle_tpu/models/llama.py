"""Llama-family causal LM — the flagship model (BASELINE.md config 3).

Built TPU-first on the framework's own layers:
- tensor parallel via Column/RowParallelLinear + VocabParallelEmbedding
  (GSPMD shard specs over the 'mp' axis),
- sequence/context parallel via activation shard constraints: between
  sublayers the sequence lies over 'cp' and over 'mp' (Megatron's sequence-
  parallel form: reduce-scatter + all-gather around the tensor-parallel
  matmuls, mesh.activation_spec's "rows"); attention sees it whole per head,
- attention through F.scaled_dot_product_attention -> Pallas flash kernel,
- activation recompute per decoder layer (jax.checkpoint),
- GQA (num_key_value_heads < num_attention_heads).

No counterpart exists in the reference snapshot (it predates Llama); the layer
recipe follows the public architecture, expressed in this framework's API.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

from .. import nn
from ..core.dispatch import primitive
from ..core.tensor import Tensor
from ..nn import functional as F
from ..ops import creation, manipulation
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    _mp_degree, mark_sharding,
)
from ..distributed.mesh import activation_spec, get_mesh_env
from ..distributed.meta_parallel.stage_stack import (
    ATTN_K, ATTN_O, ATTN_Q, ATTN_V, MLP_GATE, MLP_UP, StackedStageRun,
    name_for_recompute,
)
from ..observability.trace.parts import part


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    scan_layers: bool = True  # lax.scan over decoder stack: O(1) compile in depth
    pp_microbatches: int = 0  # microbatches for the pp pipeline (0 = 2*pp)
    ce_chunk: int = 2048  # fused lm_head+CE token-chunk size
    cp_impl: str = "ring"  # context-parallel attention: 'ring' | 'ulysses'
    dtype: str = "bfloat16"

    @staticmethod
    def llama2_7b(**overrides):
        return LlamaConfig(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
            max_position_embeddings=4096), **overrides})

    @staticmethod
    def llama3_8b(**overrides):
        return LlamaConfig(**{**dict(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192, rope_theta=500000.0), **overrides})

    @staticmethod
    def tiny(**overrides):
        return LlamaConfig(**{**dict(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=256, dtype="float32"), **overrides})


@dataclass
class LlamaMoEConfig(LlamaConfig):
    """DeepSeekMoE/Qwen2-MoE-style config (BASELINE config 5): every MLP is a
    top-k routed expert layer over the 'ep' mesh axis."""
    num_experts: int = 8
    top_k: int = 2
    moe_intermediate_size: int = 0  # 0 = intermediate_size
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01

    @staticmethod
    def tiny(**overrides):
        return LlamaMoEConfig(**{**dict(
            vocab_size=256, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=256, dtype="float32",
            num_experts=4, top_k=2), **overrides})


@primitive("rope_apply")
def _rope(x, *, theta, pos_offset, impl):
    # x: [b, s, h, d]; rotate-half RoPE in fp32
    from ..kernels.pallas import rope

    if impl == "reference":  # plain jnp: GSPMD partitions it itself
        return rope.rope_apply(x, theta, pos_offset, impl)
    from ..distributed.mesh import activation_spec, run_kernel_on_mesh

    # seq stays unsplit, so every shard sees global positions; no residuals
    spec = activation_spec(x.shape, "bshd")
    return run_kernel_on_mesh(
        *rope.rope_halves(theta, pos_offset, impl), (x,), in_specs=(spec,),
        out_specs=spec, res_specs=())


def apply_rotary_pos_emb(x: Tensor, theta: float = 10000.0, pos_offset: int = 0) -> Tensor:
    # the implementation is a primitive ATTR (cache-key participant): a
    # change of it retraces and the retrace auditor names it
    from ..kernels.registry import resolve

    return _rope(x, theta=float(theta), pos_offset=int(pos_offset),
                 impl=resolve("rope"))


def _mark_seq(h: Tensor, layout: str = "rows") -> Tensor:
    """Anchor [b, s, d] activations between sublayers to the mesh's
    ``"rows"`` layout (``mesh.activation_spec``: batch over dp/sdp, seq over
    cp and mp) — the same spec the norm kernels run under — or, behind the
    vocabulary-parallel embedding, to ``"gathered"``."""
    spec = activation_spec(h.shape, layout)
    if spec is None or all(part is None for part in spec) or (
            layout != "rows" and spec == activation_spec(h.shape, "rows")):
        return h  # no mesh, nothing to split, or the anchor behind says it
    return mark_sharding(h, *spec)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        self.q_proj = ColumnParallelLinear(h, self.num_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, self.num_kv_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, self.num_kv_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(self.num_heads * self.head_dim, h,
                                        has_bias=False, input_is_parallel=True)

    @part("attn_proj")
    def forward(self, hidden, cache=None):
        b, s = hidden.shape[0], hidden.shape[1]
        q = manipulation.reshape(self.q_proj(hidden), [b, s, self.num_heads, self.head_dim])
        k = manipulation.reshape(self.k_proj(hidden), [b, s, self.num_kv_heads, self.head_dim])
        v = manipulation.reshape(self.v_proj(hidden), [b, s, self.num_kv_heads, self.head_dim])
        pos = 0 if cache is None else cache[0].shape[1]
        q = apply_rotary_pos_emb(q, self.config.rope_theta, pos)
        k = apply_rotary_pos_emb(k, self.config.rope_theta, pos)
        if cache is not None:
            k = manipulation.concat([cache[0], k], axis=1)
            v = manipulation.concat([cache[1], v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None
            # what attention reads, named where it is whole: a recompute
            # that keeps these (stage_stack.remat_wrap) replays neither the
            # three projections nor RoPE
            q = name_for_recompute(q, ATTN_Q)
            k = name_for_recompute(k, ATTN_K)
            v = name_for_recompute(v, ATTN_V)
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = manipulation.repeat_interleave(k, rep, axis=2)
            v = manipulation.repeat_interleave(v, rep, axis=2)
        env = get_mesh_env()
        with part("attention"):
            if cache is None and env is not None and env.get_dim("cp") > 1:
                # context parallel over the cp axis: K/V ring (default) or
                # Ulysses a2a head sharding, per config.cp_impl
                if getattr(self.config, "cp_impl", "ring") == "ulysses":
                    from ..distributed.context_parallel import ulysses_attention

                    out = ulysses_attention(q, k, v, causal=True)
                else:
                    from ..distributed.context_parallel import ring_attention

                    out = ring_attention(q, k, v, causal=True)
            else:
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=cache is None, training=self.training)
        out = manipulation.reshape(out, [b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if cache is not None:
            return out, new_cache
        # where mp splits the rows, o_proj named its sum itself (MP_OUT)
        return out if _mp_degree() > 1 else name_for_recompute(out, ATTN_O)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, i, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(h, i, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(i, h, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        act = F.silu(name_for_recompute(self.gate_proj(x), MLP_GATE))
        return self.down_proj(
            act * name_for_recompute(self.up_proj(x), MLP_UP))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        if getattr(config, "num_experts", 0) > 1:
            from ..nn.layer.moe import MoELayer

            self.mlp = MoELayer(
                config.hidden_size, config.num_experts,
                intermediate_size=config.moe_intermediate_size or config.intermediate_size,
                top_k=config.top_k, capacity_factor=config.capacity_factor)
        else:
            self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, hidden):
        hidden = _mark_seq(hidden)
        # residual add + post-attention norm as ONE op: where the Pallas
        # kernel runs, the attn output, the residual stream and the norm's
        # read/write collapse into one HBM pass (kernels/pallas/rmsnorm.py);
        # the first norm of the layer has no preceding add
        with part("norm"):
            normed = self.input_layernorm(hidden)
        attn_out = self.self_attn(normed)
        with part("norm"):
            mlp_in, hidden = F.rms_norm_residual(
                attn_out, hidden, self.post_attention_layernorm.weight,
                self.post_attention_layernorm._epsilon)
        # the MLP and the residual add behind it; an expert layer names its
        # own router / experts inside
        with part("mlp"):
            hidden = hidden + self.mlp(mlp_in)
        return _mark_seq(hidden)


class ScanDecoderStack(StackedStageRun):
    """The decoder stack as ONE lax.scan over stacked per-layer parameters.

    TPU-first: compile time and program size are O(1) in depth (an unrolled
    32-layer graph breaks compile budgets), weights for layer l live in the
    leading dim of each stacked parameter — which shards over 'pp' when that
    axis is active (stage-placed weights, the GSPMD pipeline idiom). The
    stacking/pipelining machinery is the framework-generic StackedStageRun
    (distributed.meta_parallel.stage_stack); this subclass only supplies the
    independently-initialized LlamaDecoderLayer protos and config plumbing.
    """

    def __init__(self, config: LlamaConfig):
        protos = [LlamaDecoderLayer(config)
                  for _ in range(config.num_hidden_layers)]
        super().__init__(protos, num_microbatches=config.pp_microbatches,
                         recompute=config.use_recompute)
        self.config = config


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        if config.scan_layers:
            self.layers = ScanDecoderStack(config)
        else:
            self.layers = nn.LayerList(
                [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        with part("embed"):
            # the vocabulary-parallel sum lands whole; the stream's shard of
            # it is then a local slice
            hidden = _mark_seq(self.embed_tokens(input_ids), "gathered")
        hidden = _mark_seq(hidden)
        if self.config.scan_layers:
            hidden = self.layers(hidden)
        else:
            for layer in self.layers:
                if self.config.use_recompute and self.training:
                    from ..distributed.utils_recompute import recompute

                    hidden = recompute(layer, hidden)
                else:
                    hidden = layer(hidden)
        with part("norm"):
            return self.norm(hidden)


def _data_degree() -> int:
    """How many data replicas (dp x sdp) the live mesh has; 1 with no mesh."""
    env = get_mesh_env()
    return env.get_dim("dp") * env.get_dim("sdp") if env is not None else 1


@primitive("fused_linear_ce")
def _fused_linear_ce(hidden2d, w, labels1d, *, chunk, ignore_index, groups=1):
    """lm_head matmul + softmax CE scanned over token chunks: the [N, vocab]
    logits tensor never materializes (compile-size + HBM win for 32k+ vocabs;
    plays the c_softmax_with_cross_entropy fused-kernel role).

    ``groups`` is the mesh's data degree (``_data_degree()``): the rows are
    batch-major, so they fall into ``groups`` contiguous runs, one a data
    replica. Each run is chunked on its own and the scan walks the chunks
    with the runs riding along as a leading dim of the body — a scanned dim
    cannot be sharded, so chunking across the runs would make every replica
    compute the head for the whole global batch."""
    import jax

    n, width = hidden2d.shape
    g = groups if n % groups == 0 else 1
    lead = (g,) if g > 1 else ()
    m = n // g  # rows a data replica holds
    n_chunks = max(m // chunk, 1)
    c = -(-m // n_chunks)  # ceil: every token contributes
    pad = n_chunks * c - m
    h3 = hidden2d.reshape(*lead, m, width)
    l2 = labels1d.reshape(*lead, m)
    if pad:
        none = ((0, 0),) * len(lead)
        h3 = jnp.pad(h3, (*none, (0, pad), (0, 0)))
        l2 = jnp.pad(l2, (*none, (0, pad)),
                     constant_values=ignore_index)  # padded rows masked
    h3 = h3.reshape(*lead, n_chunks, c, width)
    l2 = l2.reshape(*lead, n_chunks, c)
    if lead:  # scan over the chunks, never over the runs
        h3, l2 = jnp.moveaxis(h3, 1, 0), jnp.moveaxis(l2, 1, 0)

    def body(acc, xs):
        h, lab = xs
        logits = jnp.matmul(h, w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        mask = lab != ignore_index
        safe = jnp.where(mask, lab, 0).astype(jnp.int32)
        picked = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        loss_sum = -jnp.sum(jnp.where(mask, picked, 0.0))
        cnt = jnp.sum(mask)
        return (acc[0] + loss_sum, acc[1] + cnt), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (h3, l2))
    return total / jnp.maximum(count, 1)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False, gather_output=False)
        if config.tie_word_embeddings:
            self.lm_head.weight = self.llama.embed_tokens.weight
        if config.dtype == "bfloat16":
            self.to(dtype="bfloat16")

    def forward(self, input_ids, labels=None):
        from ..nn.layer import moe as moe_mod

        with moe_mod.collect_aux() as bucket:
            hidden = self.llama(input_ids)
        aux = moe_mod.drain_aux(bucket)
        if labels is not None:
            return self._linear_ce(hidden, labels, aux)
        if aux is not None:
            moe_mod.record_aux(aux)  # re-raise for an outer collector
        with part("head"):
            return self.lm_head(hidden)

    @part("head")
    def _linear_ce(self, hidden, labels, aux):
        # fused chunked lm_head+CE: full logits never hit HBM
        h = hidden[:, :-1, :]
        lab = labels[:, 1:]
        h2 = manipulation.reshape(h, [-1, self.config.hidden_size])
        lab1 = manipulation.reshape(lab, [-1])
        loss = _fused_linear_ce(h2, self.lm_head.weight, lab1,
                                chunk=getattr(self.config, "ce_chunk", 2048),
                                ignore_index=-100, groups=_data_degree())
        if aux is not None:
            loss = loss + getattr(self.config, "aux_loss_weight", 0.0) * aux
        return loss

    @part("head")
    def loss_from_logits(self, logits, labels):
        v = self.config.vocab_size
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        flat_logits = manipulation.reshape(shift_logits, [-1, v])
        flat_labels = manipulation.reshape(shift_labels, [-1])
        flat_logits = manipulation.cast(flat_logits, "float32")
        return F.cross_entropy(flat_logits, flat_labels)


def llama_flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """Model FLOPs per token (fwd+bwd, standard 6N + attention term) for MFU."""
    n_params = llama_param_count(config)
    attn = 12 * config.num_hidden_layers * config.hidden_size * seq_len
    return 6 * n_params + attn


def llama_param_count(config: LlamaConfig) -> int:
    h, i, v, L = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_hidden_layers)
    kvh = config.num_key_value_heads * (h // config.num_attention_heads)
    per_layer = h * h + 2 * h * kvh + h * h + 3 * h * i + 2 * h
    return L * per_layer + 2 * v * h + h


def llama_moe_param_counts(config: "LlamaMoEConfig"):
    """(total, activated-per-token) parameter counts for the MoE variant:
    every token runs attention + embeddings + gate but only top_k of the
    num_experts expert FFNs."""
    h, v, L = (config.hidden_size, config.vocab_size,
               config.num_hidden_layers)
    i = config.moe_intermediate_size or config.intermediate_size
    kvh = config.num_key_value_heads * (h // config.num_attention_heads)
    attn_layer = h * h + 2 * h * kvh + h * h + 2 * h
    expert = 3 * h * i
    gate = h * config.num_experts
    shared = L * (attn_layer + gate) + 2 * v * h + h
    total = shared + L * config.num_experts * expert
    activated = shared + L * config.top_k * expert
    return total, activated


def llama_moe_flops_per_token(config: "LlamaMoEConfig", seq_len: int) -> float:
    """Model FLOPs per token for MFU on the MoE flagship: 6 * ACTIVATED
    params + attention term (the standard sparse-model MFU convention —
    capacity-factor overcompute counts as overhead, not useful flops)."""
    _, activated = llama_moe_param_counts(config)
    attn = 12 * config.num_hidden_layers * config.hidden_size * seq_len
    return 6 * activated + attn
