"""Plain reference for openPangu-Ultra-MoE (HF ``model_type``
``pangu_ultra_moe``; ``FreedomIntelligence/openPangu-Ultra-MoE-718B``,
``config.json``): DeepSeek-V2/V3-style latent attention (MLA), sandwich
norms, leading dense layers, then routed experts beside a shared one.

``h`` = ``hidden_size`` 7680, ``H`` = 128 heads, RMSNorm eps ``rms_norm_eps``
1e-5, ``rope_theta`` 25 600 000 (no scaling), ``hidden_act`` silu, no biases,
untied head. With ``x`` the residual stream ``[T, h]``:

*Block* (``sandwich_norm``: four RMSNorm weights a layer)::

    x = x + N_post_attn(Attn(N_in(x)))
    x = x + N_post_mlp(MLP(N_pre_mlp(x)))

*Attention (MLA)*, on ``u = N_in(x)``::

    c_q = RMSNorm(u W_qa)                              # q_lora_rank 1536
    q   = c_q W_qb -> per head [q_nope (128) | q_rope (64)]
    q_rope = RoPE(q_rope)
    [c_kv (512) | k_r (64)] = u W_kva
    c_kv = RMSNorm(c_kv) ;  k_r = RoPE(k_r)     # ONE head, shared by all 128
    [k_nope_h (128) | v_h (128)] = c_kv W_kvb                    # per head
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_r(s)) / sqrt(192)
    o_h = sum_{s <= t} softmax_s(score_h(t, .)) v_h(s)
    out = concat_h(o_h) W_o                                 # 16384 -> 7680

A cache of this layer holds ``[c_kv | k_r]`` after norm and RoPE: 576 values
a token. The *absorbed* form (equal in exact arithmetic; what the served
model computes): ``q_lat_h = q_nope_h (W_kvb^{K,h})^T`` (512), ``score =
q_lat_h.c_kv + q_rope_h.k_r``, ``o_h = (sum_s p c_kv(s)) W_kvb^{V,h}``. This
file computes the NON-absorbed form above.

*MLP.* Layers ``0 .. first_k_dense_replace - 1``: SwiGLU at
``intermediate_size`` 18432. The others, on ``u = N_pre_mlp(x)``::

    s   = sigmoid(float32(u) W_r)                 # 256 scores, float32
    idx = top8(s)
    g   = routed_scaling_factor (2.5) * s[idx] / (sum s[idx] + 1e-20)
    y   = sum_k g_k E_{idx_k}(u) + E_shared(u)
    E(u) = (silu(u W_g) * (u W_u)) W_d            # moe_intermediate_size 2048

*Head*: final RMSNorm, ``lm_head``.

Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
full causal attention over the whole sequence — no cache, no pages, no
kernels, no batching. One block is a few small jitted functions called in a
Python loop, a layer's weights upcast when it runs, attention a group of
heads at a time (128 heads of 4096 x 4096 float32 scores are 8.6 GB), the
routed experts one at a time, the head a slice of the vocabulary at a time,
so that the published widths fit one chip beside the served model's weights.

A SHARE of the model (expert parallelism, a sliced vocabulary): ``cfg`` says
which experts are held (``n_routed_experts`` of them from router output
``held_experts_first``; the router keeps its ``router_experts`` outputs) and
the weights hold ``vocab_size`` rows. What the experts that are not held
would have added is left out — that partial result goes on to the next
layer — and the logits are over the slice.

Departures from the published description: none in the layer. Assumed (the
published ``config.json`` does not say, set by the family's convention):
sigmoid scores with no correction bias and no group-limited choice (the
config has no ``n_group`` / ``topk_group`` / ``scoring_func`` key); the
sandwich order above; RoPE as rotate-half over the 64 dims; softmax scale
``192^-0.5``; weights stored ``[in, out]``, ``W_qb`` and ``W_kvb`` head-major
(``[.., H, nope | rope]``, ``[.., H, k_nope | v]``). The multi-token-
prediction module (``num_nextn_predict_layers`` 1) is a draft head the main
model's logits do not depend on: not here.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

ATTN_KEYS = ("input_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")
DENSE_MLP_KEYS = ("gate_w", "up_w", "down_w")
SHARED_KEYS = ("shared_gate", "shared_up", "shared_down")
EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")

_HEAD_GROUP = 8   # heads whose [T, T] scores are alive at once

# The check's control (PERF.md section 6): a function every matmul operand
# and the would-be cache row ``[c_kv | k_r]`` pass through, e.g.
# ``lambda x: jax.lax.reduce_precision(x, 4, 3)`` (fp8-e4m3) — set before the
# first call, by the control run alone. ``None``: float32 as described.
ROUND = None


def _r(x):
    return x if ROUND is None else ROUND(x)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over the whole last dim; ``x`` is ``[T, heads, d]``."""
    t, _h, d = x.shape
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _frozen(cfg: Dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


def held_experts(cfg: Dict):
    """``(first, count, router width)`` of the share ``cfg`` describes."""
    count = cfg["n_routed_experts"]
    return (int(cfg.get("held_experts_first") or 0), count,
            int(cfg.get("router_experts") or count))


@functools.partial(jax.jit, static_argnames=("cfgt",))
def _attention(x, w, *, cfgt):
    """``x + N_post_attn(Attn(N_in(x)))`` over the whole sequence."""
    cfg = dict(cfgt)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) if k.endswith("norm") else _r(v.astype(F32))
             for k, v in w.items()}
        t, eps = x.shape[0], cfg["rms_norm_eps"]
        H, dn, dr, dv, dc = (cfg["num_attention_heads"],
                             cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                             cfg["v_head_dim"], cfg["kv_lora_rank"])
        u = _r(_rms(x, w["input_norm"], eps))
        c_q = _r(_rms(u @ w["q_a"], w["q_a_norm"], eps))
        q = (c_q @ w["q_b"]).reshape(t, H, dn + dr)
        q_nope = _r(q[..., :dn])
        q_rope = _r(_rope(q[..., dn:], cfg["rope_theta"]))
        kva = u @ w["kv_a"]
        c_kv = _r(_rms(kva[:, :dc], w["kv_a_norm"], eps))
        k_r = _r(_rope(kva[:, None, dc:], cfg["rope_theta"])[:, 0])  # [T, dr]
        kv_b = w["kv_b"].reshape(dc, H, dn + dv)
        causal = jnp.tril(jnp.ones((t, t), bool))

        G = min(_HEAD_GROUP, H)
        assert H % G == 0

        def heads(g):   # a group of heads at a time: [G, T, T] scores
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=g * G, slice_size=G)
            kv = jnp.einsum("tc,chn->thn", c_kv, sl(kv_b, axis=1))
            att = (jnp.einsum("qhd,khd->hqk", sl(q_nope, axis=1),
                              _r(kv[..., :dn])) +
                   jnp.einsum("qhd,kd->hqk", sl(q_rope, axis=1), k_r)) \
                / np.sqrt(dn + dr)
            att = jnp.where(causal, att, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", _r(jax.nn.softmax(att, -1)),
                              _r(kv[..., dn:]))

        o = jax.lax.map(heads, jnp.arange(H // G))         # [H/G, T, G, dv]
        o = o.transpose(1, 0, 2, 3).reshape(t, H * dv)
        return x + _rms(_r(o) @ w["o"], w["post_attn_norm"], eps)


@jax.jit
def _swiglu(u, gate, up, down):
    with jax.default_matmul_precision("highest"):
        gate, up, down = (_r(m.astype(F32)) for m in (gate, up, down))
        u = _r(u)
        return _r(jax.nn.silu(u @ gate) * (u @ up)) @ down


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "scale"))
def _route(u, router, *, top_k, norm, scale):
    """Gate of every (token, router output): 0 where it is not among the
    token's top-k. ``[T, E]`` float32."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u.astype(F32) @ router.astype(F32))
        val, idx = jax.lax.top_k(s, top_k)
        if norm:
            val = val / (jnp.sum(val, -1, keepdims=True) + 1e-20)
        val = val * scale
        rows = jnp.arange(u.shape[0])[:, None]
        return jnp.zeros_like(s).at[rows, idx].set(val)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


def mlp_branch(u, get: Callable[[str], jax.Array], cfg: Dict, layer: int,
               n_live=None):
    """``MLP(u)`` before its post-norm, and the number of routed (token,
    choice) pairs of the first ``n_live`` positions that met a held expert
    (0 for a dense layer)."""
    if layer < cfg["first_k_dense_replace"]:
        return _swiglu(u, *(get(k) for k in DENSE_MLP_KEYS)), 0
    first, count, _width = held_experts(cfg)
    gates = _route(u, get("router"), top_k=cfg["num_experts_per_tok"],
                   norm=bool(cfg["norm_topk_prob"]),
                   scale=float(cfg["routed_scaling_factor"]))
    y = _swiglu(u, *(get(k) for k in SHARED_KEYS))
    wg, wu, wd = (get(k) for k in EXPERT_KEYS)
    for e in range(count):                     # one held expert at a time
        y = y + gates[:, first + e, None] * _swiglu(u, wg[e], wu[e], wd[e])
    live = gates[:u.shape[0] if n_live is None else n_live,
                 first:first + count] > 0
    return y, int(jnp.sum(live))


def mlp(x, get: Callable[[str], jax.Array], cfg: Dict, layer: int,
        n_live=None):
    """``x + N_post_mlp(MLP(N_pre_mlp(x)))`` and the held pairs."""
    eps = cfg["rms_norm_eps"]
    y, held_pairs = mlp_branch(_norm(x, get("pre_mlp_norm"), eps=eps), get,
                               cfg, layer, n_live)
    return x + _norm(y, get("post_mlp_norm"), eps=eps), held_pairs


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray, n_live=None):
    """The normed last hidden state ``[T, hidden]`` and the held pairs of
    the first ``n_live`` positions, summed over the expert layers."""
    x = get("embed", -1)[jnp.asarray(ids)].astype(F32)
    cfgt, held_pairs = _frozen(cfg), 0
    for layer in range(cfg["num_hidden_layers"]):
        x = _attention(x, {k: get(k, layer) for k in ATTN_KEYS}, cfgt=cfgt)
        x, n = mlp(x, functools.partial(get, layer=layer), cfg, layer, n_live)
        held_pairs += n
    return _norm(x, get("final_norm", -1), eps=cfg["rms_norm_eps"]), held_pairs


@functools.partial(jax.jit, static_argnames=("lo", "size"))
def _head_slice(y, head, *, lo, size):
    with jax.default_matmul_precision("highest"):
        return _r(y) @ _r(jax.lax.dynamic_slice_in_dim(
            head, lo, size, axis=1).astype(F32))


def logits(get, cfg: Dict, ids: np.ndarray, vocab_slices: int = 1):
    """``[T, vocab]`` float32 logits of one full forward."""
    y, _n = final_hidden(get, cfg, ids)
    head, v = get("head", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    return jnp.concatenate([_head_slice(y, head, lo=lo, size=min(size, v - lo))
                            for lo in range(0, v, size)], -1)


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 4, with_pairs: bool = False):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``,
    by one full forward. The sequence is padded at its END to ``pad_to`` so
    that one compiled shape serves every request (attention is causal and
    everything else is position-wise: padding after a position cannot reach
    it). ``with_pairs`` also returns the held pairs of ``tokens[:-1]``: what
    a server that emitted ``tokens[-1]`` last has routed."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    y, held_pairs = final_hidden(get, cfg, ids, n - 1)
    head, v = get("head", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    lse = jnp.full(pad_to, -jnp.inf, F32)
    picked = jnp.zeros(pad_to, F32)
    for lo in range(0, v, size):
        lg = _head_slice(y, head, lo=lo, size=min(size, v - lo))
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(lg, -1))
        here = (nxt >= lo) & (nxt < lo + lg.shape[1])
        col = jnp.clip(jnp.asarray(nxt) - lo, 0, lg.shape[1] - 1)
        picked = jnp.where(here, jnp.take_along_axis(
            lg, col[:, None], -1)[:, 0], picked)
    out = np.asarray(picked - lse)[:n - 1]
    return (out, held_pairs) if with_pairs else out
