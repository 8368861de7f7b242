"""GPT-2/3-family causal LM (reference lineage: PaddleNLP/fleetx GPT configs;
the reference repo ships the distributed machinery these models train on).

Same TPU-first idioms as models/llama.py: Column/RowParallelLinear over 'mp',
activation shard constraints over dp/sdp/cp, flash attention, fused chunked
lm_head+CE, optional jax.checkpoint recompute. Differences from Llama: learned
absolute position embeddings, pre-LN blocks with biases, GELU MLP, tied
embedding head.
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from ..ops import creation, manipulation
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)
from .llama import _data_degree, _fused_linear_ce, _mark_seq


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0  # 0 = 4*hidden
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    attention_probs_dropout_prob: float = 0.0
    hidden_dropout_prob: float = 0.0
    use_recompute: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size

    @staticmethod
    def gpt2_small(**overrides):
        return GPTConfig(**{**dict(hidden_size=768, num_hidden_layers=12,
                                   num_attention_heads=12), **overrides})

    @staticmethod
    def gpt2_xl(**overrides):
        return GPTConfig(**{**dict(hidden_size=1600, num_hidden_layers=48,
                                   num_attention_heads=25), **overrides})

    @staticmethod
    def gpt3_6_7b(**overrides):
        return GPTConfig(**{**dict(hidden_size=4096, num_hidden_layers=32,
                                   num_attention_heads=32,
                                   max_position_embeddings=2048), **overrides})

    @staticmethod
    def tiny(**overrides):
        return GPTConfig(**{**dict(vocab_size=256, hidden_size=128,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   max_position_embeddings=128,
                                   dtype="float32"), **overrides})


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, has_bias=True,
                                             gather_output=False)
        self.out_proj = RowParallelLinear(h, h, has_bias=True,
                                          input_is_parallel=True)
        self.dropout_p = config.attention_probs_dropout_prob

    def forward(self, hidden, cache=None, use_cache=False):
        b, s = hidden.shape[0], hidden.shape[1]
        qkv = manipulation.reshape(self.qkv_proj(hidden),
                                   [b, s, 3, self.num_heads, self.head_dim])
        q = manipulation.squeeze(manipulation.slice(qkv, [2], [0], [1]), [2])
        k = manipulation.squeeze(manipulation.slice(qkv, [2], [1], [2]), [2])
        v = manipulation.squeeze(manipulation.slice(qkv, [2], [2], [3]), [2])
        if cache is not None:
            k = manipulation.concat([cache[0], k], axis=1)
            v = manipulation.concat([cache[1], v], axis=1)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,  # bottom-right aligned: cache-safe
            dropout_p=self.dropout_p if self.training else 0.0)
        out = manipulation.reshape(out, [b, s, self.num_heads * self.head_dim])
        out = self.out_proj(out)
        if use_cache:
            return out, (k, v)
        return out


class GPTBlock(nn.Layer):
    """Pre-LN transformer block (GPT-2 recipe)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, config.layer_norm_epsilon)
        self.fc_in = ColumnParallelLinear(config.hidden_size,
                                          config.intermediate_size,
                                          has_bias=True, gather_output=False)
        self.fc_out = RowParallelLinear(config.intermediate_size,
                                        config.hidden_size, has_bias=True,
                                        input_is_parallel=True)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, hidden, cache=None, use_cache=False):
        attn_out = self.attn(self.ln_1(hidden), cache=cache, use_cache=use_cache)
        if use_cache:
            attn_out, new_cache = attn_out
        hidden = hidden + self.dropout(attn_out)
        mlp = self.fc_out(F.gelu(self.fc_in(self.ln_2(hidden)), approximate=True))
        hidden = hidden + self.dropout(mlp)
        hidden = _mark_seq(hidden)
        if use_cache:
            return hidden, new_cache
        return hidden


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.embed_positions = nn.Embedding(config.max_position_embeddings,
                                            config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        self.layers = nn.LayerList(
            [GPTBlock(config) for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, config.layer_norm_epsilon)

    def forward(self, input_ids, position_offset=0, caches=None,
                use_cache=False):
        s = input_ids.shape[1]
        pos = creation.arange(position_offset, position_offset + s, dtype="int64")
        hidden = self.embed_tokens(input_ids) + self.embed_positions(pos)
        hidden = _mark_seq(self.drop(hidden))
        new_caches = []
        for i, layer in enumerate(self.layers):
            if use_cache:
                hidden, c = layer(hidden, cache=None if caches is None
                                  else caches[i], use_cache=True)
                new_caches.append(c)
            elif self.config.use_recompute and self.training:
                from ..distributed.utils_recompute import recompute

                hidden = recompute(layer, hidden)
            else:
                hidden = layer(hidden)
        hidden = self.ln_f(hidden)
        if use_cache:
            return hidden, new_caches
        return hidden


class GPTForCausalLM(nn.Layer):
    """Tied-embedding LM head + fused chunked CE (llama.py _fused_linear_ce)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.dtype == "bfloat16":
            self.to(dtype="bfloat16")

    def forward(self, input_ids, labels=None):
        hidden = self.gpt(input_ids)
        w = self.gpt.embed_tokens.weight  # [vocab, hidden] -> use transposed
        if labels is not None:
            h2 = manipulation.reshape(hidden[:, :-1, :],
                                      [-1, self.config.hidden_size])
            lab1 = manipulation.reshape(labels[:, 1:], [-1])
            return _fused_linear_ce(h2, manipulation.transpose(w, [1, 0]),
                                    lab1, chunk=2048, ignore_index=-100,
                                    groups=_data_degree())
        return hidden.matmul(manipulation.transpose(w, [1, 0]))

    def served_model(self):
        """This model on ``serving.GenerationEngine``'s seam."""
        from ..serving.served_model import GPTServed

        return GPTServed(self.config)

    def generate(self, input_ids, max_new_tokens=16, use_cache=True):
        """Greedy decode. With use_cache the prefill runs once and each new
        token reuses the per-layer KV cache (O(1) attention reads per step)."""
        from ..ops import reduction as R

        w_t = manipulation.transpose(self.gpt.embed_tokens.weight, [1, 0])
        out = input_ids
        if not use_cache:
            for _ in range(max_new_tokens):
                logits = self.forward(out)
                nxt = R.argmax(logits[:, -1, :], axis=-1)
                out = manipulation.concat(
                    [out, manipulation.reshape(nxt, [-1, 1]).astype("int64")],
                    axis=1)
            return out
        hidden, caches = self.gpt(out, use_cache=True)
        for step in range(max_new_tokens):
            logits = hidden[:, -1, :].matmul(w_t)
            nxt = manipulation.reshape(
                R.argmax(logits, axis=-1), [-1, 1]).astype("int64")
            out = manipulation.concat([out, nxt], axis=1)
            if step + 1 < max_new_tokens:  # last token needs no lookahead
                hidden, caches = self.gpt(nxt, position_offset=out.shape[1] - 1,
                                          caches=caches, use_cache=True)
        return out


def gpt_param_count(config: GPTConfig) -> int:
    h, L = config.hidden_size, config.num_hidden_layers
    i = config.intermediate_size
    # qkv (3h^2+3h) + out_proj (h^2+h) + mlp (2hi+i+h) + 2 LN (4h)
    per_layer = 4 * h * h + 2 * h * i + i + 9 * h
    return (L * per_layer + config.vocab_size * h
            + config.max_position_embeddings * h + 2 * h)


# -- pipeline-parallel preset -------------------------------------------------
# Reference: fleetx GPTForPretrainingPipe (PipelineLayer of SharedLayerDesc
# embedding + GPTBlock LayerDescs + tied head), trained via
# PipelineParallel.train_batch. Here the PipelineLayer auto-detects the
# homogeneous GPTBlock run and ppermute-pipelines it over the mesh's pp axis.

class _GPTEmbeddingPipe(nn.Layer):
    """ids -> hidden (token + learned position embeddings); doubles as the
    tied LM head via SharedLayerDesc forward_func."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.embed_positions = nn.Embedding(config.max_position_embeddings,
                                            config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids):
        s = input_ids.shape[1]
        pos = creation.arange(0, s, dtype="int64")
        hidden = self.embed_tokens(input_ids) + self.embed_positions(pos)
        return _mark_seq(self.drop(hidden))


def _gpt_tied_logits(embed: _GPTEmbeddingPipe, hidden):
    return hidden.matmul(manipulation.transpose(embed.embed_tokens.weight,
                                                [1, 0]))


class _GPTFinalNormPipe(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_f = nn.LayerNorm(config.hidden_size, config.layer_norm_epsilon)

    def forward(self, hidden):
        return self.ln_f(hidden)


def _gpt_shifted_ce(logits, labels):
    b, s, v = logits.shape
    lg = manipulation.reshape(logits[:, :-1, :], [-1, v]).astype("float32")
    lab = manipulation.reshape(labels[:, 1:], [-1])
    return F.cross_entropy(lg, lab)


def GPTForCausalLMPipe(config: GPTConfig, **pipeline_kwargs):
    """PipelineLayer view of GPTForCausalLM: same math (tied embeddings,
    pre-LN blocks), expressed as LayerDescs so fleet's PipelineParallel
    train_batch drives the compiled ppermute pipeline for the block run."""
    from ..distributed.meta_parallel import (LayerDesc, PipelineLayer,
                                             SharedLayerDesc)

    descs = [
        SharedLayerDesc("embed", _GPTEmbeddingPipe, None, "embed_tokens.weight",
                        config),
        *[LayerDesc(GPTBlock, config) for _ in range(config.num_hidden_layers)],
        LayerDesc(_GPTFinalNormPipe, config),
        SharedLayerDesc("embed", _GPTEmbeddingPipe, _gpt_tied_logits,
                        "embed_tokens.weight", config),
    ]
    pipe = PipelineLayer(layers=descs, loss_fn=_gpt_shifted_ce,
                         **pipeline_kwargs)
    if config.dtype == "bfloat16":
        pipe.to(dtype="bfloat16")
    return pipe
