"""Operations a model REQUIRES, computed from its shapes (the benchmark's own
arithmetic: the yardstick does not move when the program's does).

``decoder_train_flops_per_token`` counts, for one token of a sequence of
``seq`` tokens trained with a causal language-model loss:

- every matrix multiplication with a parameter, forward and backward
  (2 FLOPs per multiply-add, x3 for forward + two backward products):
  the four attention projections (GQA: K and V are ``kv_heads * head_dim``
  wide), the three SwiGLU projections, and the output head;
- causal attention: QK^T and PV over the lower triangle only, i.e. half of
  ``4 * seq * heads * head_dim`` per token forward, x3 with backward.

Not counted: the input embedding (a lookup, no FLOPs), norms, RoPE, softmax
and the optimizer (memory-bound, O(params) per step, not per token), and any
recomputation (operations the schedule repeats are not required ones).
"""


def decoder_matmul_params(cfg: dict) -> int:
    """Parameters that meet a token in a matrix multiplication."""
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or h // heads
    q = heads * head_dim
    kv = cfg.get("num_key_value_heads", heads) * head_dim
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def decoder_param_count(cfg: dict) -> int:
    """All parameters held (embedding, untied head, norms included)."""
    h = cfg["hidden_size"]
    embed = cfg["vocab_size"] * h
    tied = bool(cfg.get("tie_word_embeddings", False))
    norms = (2 * cfg["num_hidden_layers"] + 1) * h
    return decoder_matmul_params(cfg) + (0 if tied else embed) + norms


def decoder_train_flops_per_token(cfg: dict, seq: int) -> float:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    attn = 6 * seq * heads * head_dim * cfg["num_hidden_layers"]
    return 6.0 * decoder_matmul_params(cfg) + attn


def mfu_pct(flops_per_token: float, tokens_per_s: float, chips: int,
            peak_flops_per_s: float) -> float:
    return 100.0 * flops_per_token * tokens_per_s / (chips * peak_flops_per_s)
