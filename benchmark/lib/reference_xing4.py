"""Plain reference for Xing4.0 (HF ``model_type`` ``xing4_0``;
``XingChen-AGI/Xing4.0-29B-A4B``, ``config.json``): DeepSeek-V3-style latent
attention (MLA) under YaRN and routed experts beside a shared one, on a
residual path of ``n = hc_mult`` streams — manifold-constrained
hyper-connections (mHC), mixed by a Sinkhorn-projected matrix.

The layer, written down (``n`` = ``hc_mult`` 4, ``C`` = ``hidden_size`` 3584;
everything on the residual path float32):

- Entry: ``X_0[i] = E[token]`` for ``i = 0..n-1``. Exit: ``h = sum_i X_L[i]``,
  ``logits = RMSNorm(h) W_head``.
- Each layer has two sublayers ``s`` (attention, then MLP), each with its OWN
  ``g [nC]``, ``phi_pre [nC, n]``, ``phi_post [nC, n]``, ``phi_res [nC, n^2]``,
  ``b_pre [n]``, ``b_post [n]``, ``b_res [n, n]``, scalars ``a_pre``,
  ``a_post``, ``a_res``:

  - ``xh = vec(X) / sqrt(mean(vec(X)^2) + hc_eps) * g``
  - ``H_pre = sigmoid(a_pre (xh phi_pre) + b_pre)``;
    ``H_post = 2 sigmoid(a_post (xh phi_post) + b_post)``
  - ``M = exp(clamp(a_res mat(xh phi_res) + b_res, mhc_h_res_clamp_min,
    mhc_h_res_clamp_max))``; ``hc_sinkhorn_iters`` times:
    ``M <- M / (column sums + hc_eps)``, then ``M <- M / (row sums + hc_eps)``;
    ``H_res = M``
  - ``u = sum_i H_pre[i] X[i]``; ``y = F_s(RMSNorm_s(u))``;
    ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``

- ``F_attn``: DeepSeek-V3's MLA at the row's widths (``q_lora_rank`` 768,
  ``kv_lora_rank`` 512, 32 heads of 128 + 64, ``v_head_dim`` 128; pre-norm: no
  sandwich norms)::

      c_q = RMSNorm(u W_qa) ;  q = c_q W_qb -> per head [q_nope | q_rope]
      [c_kv | k_r] = u W_kva ;  c_kv = RMSNorm(c_kv)
      q_rope = RoPE(q_rope) ;  k_r = RoPE(k_r)     # ONE key head for all 32
      [k_nope_h | v_h] = c_kv W_kvb
      score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_r(s)) * scale
      o_h = sum_{s <= t} softmax_s(score_h(t, .)) v_h(s) ;  out = concat(o_h) W_o

  RoPE on the 64 rope dims with YaRN (``factor`` 64,
  ``original_max_position_embeddings`` 4096, ``beta_fast`` 32, ``beta_slow``
  1, theta 1e4); softmax scale ``192^-0.5 x m^2``, ``m = 0.1 x
  mscale_all_dim x ln 64 + 1 = 1.41589``; cos / sin x ``m(mscale) /
  m(mscale_all_dim)`` = 1. A cache of this layer holds ``[c_kv | k_r]``, 576
  values a token; the served model computes the ABSORBED form, this file the
  non-absorbed one above.
- ``F_mlp``: layers ``0 .. first_k_dense_replace - 1`` SwiGLU of 9216; then
  ``s = sigmoid(float32(u) W_r)`` over 64, the top 4 of ``s + bias``
  (``n_group`` = ``topk_group`` = 1: no grouping), ``g = 2.0 s_sel / (sum
  s_sel + 1e-20)``, the 4 experts' SwiGLU of 1024 summed by ``g``, plus the
  shared SwiGLU of 1024.

Parameters of a sublayer's mHC as stored: ``<s>_hc_g [nC]``, ``<s>_hc_phi
[nC, 2n + n^2]`` (columns ``phi_pre | phi_post | phi_res``, the last row-major
``i n + j``), ``<s>_hc_b [2n + n^2]`` (``b_pre | b_post | b_res``) and
``<s>_hc_a [3]`` (``a_pre, a_post, a_res``), ``<s>`` in ``attn``, ``mlp``; all
float32.

Assumed (the published ``config.json`` gives the five keys ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max`` and no
modelling code was read): the mHC equations are the published
manifold-constrained hyper-connections (arXiv 2512.24880) read onto those
keys — ``hc_eps`` in the stream's norm AND the Sinkhorn denominators, columns
before rows, the clamp before ``exp``, one mHC a sublayer with the sublayer's
own pre-norm inside ``F``, replicate in / sum out (arXiv 2409.19606), a norm
weight ``g``; rotate-half RoPE; DeepSeek-V3's YaRN ``mscale`` convention;
weights ``[in, out]``, ``W_qb`` and ``W_kvb`` head-major. The
multi-token-prediction module (``num_nextn_predict_layers`` 1) is a draft
head the main model's logits do not depend on, and the config has no key
that says how it consumes a four-row hidden: not here.

Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
full causal attention over the whole sequence, Sinkhorn a Python loop — no
cache, no pages, no kernels, no batching. A layer's weights are upcast when it
runs, attention a group of heads at a time, the routed experts one at a time,
the head a slice of the vocabulary at a time, so that the published widths
fit one chip beside the served model's weights.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

ATTN_KEYS = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o")
DENSE_MLP_KEYS = ("gate_w", "up_w", "down_w")
SHARED_KEYS = ("shared_gate", "shared_up", "shared_down")
EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")
HC_KEYS = ("hc_g", "hc_phi", "hc_b", "hc_a")

_HEAD_GROUP = 2   # heads whose [T, T] scores are alive at once

# The check's controls (benchmark/controls_hyper.py; PERF.md section 6), set
# before the first call by a control run alone. ``ROUND``: a function every
# matmul operand of ``F`` and the would-be cache row ``[c_kv | k_r]`` pass
# through, e.g. ``lambda x: jax.lax.reduce_precision(x, 8, 3)``.
# ``SINKHORN_ITERS``: iterations instead of the configuration's.
# ``H_RES_IDENTITY``: ``H_res = I`` (the mixing of the streams left out).
ROUND = None
SINKHORN_ITERS = None
H_RES_IDENTITY = False


def _r(x):
    return x if ROUND is None else ROUND(x)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attn_scale(cfg: Dict) -> float:
    """``(nope + rope)^-0.5 x m^2``, ``m`` from ``mscale_all_dim``."""
    rs = cfg["rope_scaling"]
    m = yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope_tables(cfg: Dict, t: int):
    """``cos, sin`` ``[T, rope dim / 2]`` of positions ``0 .. T-1`` under
    YaRN: each frequency a blend of ``theta^(-2i/d)`` and the same divided by
    ``factor``, by a linear ramp between the dims that turn ``beta_fast`` and
    ``beta_slow`` times over the original context; both times
    ``m(mscale) / m(mscale_all_dim)``."""
    rs, d = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, factor = float(cfg["rope_theta"]), float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])
    freqs = base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def turns_at(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(turns_at(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(turns_at(float(rs["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * freqs)) * (1.0 - keep) + (1.0 / freqs) * keep
    f = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None]
    m = yarn_mscale(factor, float(rs["mscale"])) / \
        yarn_mscale(factor, float(rs["mscale_all_dim"]))
    return jnp.cos(f) * m, jnp.sin(f) * m


def _rope(x, cos, sin):
    """Rotate-half RoPE over the whole last dim; ``x`` is ``[T, heads, d]``."""
    d = x.shape[-1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _frozen(cfg: Dict):
    flat = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool))}
    flat.update({"rope_scaling." + k: v
                 for k, v in cfg["rope_scaling"].items()
                 if isinstance(v, (int, float))})
    return tuple(sorted(flat.items()))


def _thawed(cfgt) -> Dict:
    cfg, rs = {}, {}
    for k, v in cfgt:
        if k.startswith("rope_scaling."):
            rs[k.split(".", 1)[1]] = v
        else:
            cfg[k] = v
    cfg["rope_scaling"] = rs
    return cfg


# -- the residual path ---------------------------------------------------------

def sinkhorn(m, iters: int, eps: float):
    """``iters`` times: columns to sum 1, then rows; ``m`` is ``[.., n, n]``
    (``[i, j]``: row ``i``, column ``j``)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
    return m


@functools.partial(jax.jit, static_argnames=("cfgt", "iters", "identity"))
def _mix_in(x, w, *, cfgt, iters, identity):
    """The three maps of a sublayer from the whole stream ``x`` [T, n, C], and
    the sublayer's input ``u`` [T, C]."""
    cfg = dict(cfgt)
    with jax.default_matmul_precision("highest"):
        t, n, c = x.shape
        eps = cfg["hc_eps"]
        v = x.reshape(t, n * c)
        xh = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) \
            * w["hc_g"]
        z, a, b = xh @ w["hc_phi"], w["hc_a"], w["hc_b"]
        h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
        m = jnp.exp(jnp.clip(
            a[2] * z[:, 2 * n:].reshape(t, n, n) + b[2 * n:].reshape(n, n),
            cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
        h_res = sinkhorn(m, iters, eps)
        if identity:
            h_res = jnp.broadcast_to(jnp.eye(n, dtype=F32), h_res.shape)
        u = jnp.einsum("ti,tic->tc", h_pre, x)
        return u, h_post, h_res


@jax.jit
def _mix_out(x, y, h_post, h_res):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("tij,tjc->tic", h_res, x) + \
            h_post[:, :, None] * y[:, None, :]


def mix_in(x, get, cfg: Dict, sub: str):
    w = {k: get(f"{sub}_{k}").astype(F32) for k in HC_KEYS}
    iters = cfg["hc_sinkhorn_iters"] if SINKHORN_ITERS is None \
        else SINKHORN_ITERS
    return _mix_in(x, w, cfgt=_frozen(cfg), iters=int(iters),
                   identity=bool(H_RES_IDENTITY))


# -- the sublayers -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfgt",))
def _attention(u, w, *, cfgt):
    """``F_attn(u)``: ``Attn(RMSNorm(u))`` over the whole sequence."""
    cfg = _thawed(cfgt)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) if k.endswith("norm") else _r(v.astype(F32))
             for k, v in w.items()}
        t, eps = u.shape[0], cfg["rms_norm_eps"]
        H, dn, dr, dv, dc = (cfg["num_attention_heads"],
                             cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                             cfg["v_head_dim"], cfg["kv_lora_rank"])
        cos, sin = rope_tables(cfg, t)
        u = _r(_rms(u, w["attn_norm"], eps))
        c_q = _r(_rms(u @ w["q_a"], w["q_a_norm"], eps))
        q = (c_q @ w["q_b"]).reshape(t, H, dn + dr)
        q_nope = _r(q[..., :dn])
        q_rope = _r(_rope(q[..., dn:], cos, sin))
        kva = u @ w["kv_a"]
        c_kv = _r(_rms(kva[:, :dc], w["kv_a_norm"], eps))
        k_r = _r(_rope(kva[:, None, dc:], cos, sin)[:, 0])         # [T, dr]
        kv_b = w["kv_b"].reshape(dc, H, dn + dv)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scale = attn_scale(cfg)

        G = min(_HEAD_GROUP, H)
        assert H % G == 0

        def heads(g):   # a group of heads at a time: [G, T, T] scores
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=g * G, slice_size=G)
            kv = jnp.einsum("tc,chn->thn", c_kv, sl(kv_b, axis=1))
            att = (jnp.einsum("qhd,khd->hqk", sl(q_nope, axis=1),
                              _r(kv[..., :dn])) +
                   jnp.einsum("qhd,kd->hqk", sl(q_rope, axis=1), k_r)) * scale
            att = jnp.where(causal, att, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", _r(jax.nn.softmax(att, -1)),
                              _r(kv[..., dn:]))

        o = jax.lax.map(heads, jnp.arange(H // G))         # [H/G, T, G, dv]
        o = o.transpose(1, 0, 2, 3).reshape(t, H * dv)
        return _r(o) @ w["o"]


@jax.jit
def _swiglu(u, gate, up, down):
    with jax.default_matmul_precision("highest"):
        gate, up, down = (_r(m.astype(F32)) for m in (gate, up, down))
        u = _r(u)
        return _r(jax.nn.silu(u @ gate) * (u @ up)) @ down


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "scale"))
def _route(u, router, bias, *, top_k, norm, scale):
    """``(gates [T, E], chosen [T, top_k])``: the gate of every (token,
    expert), 0 where it is not among the token's top-k of ``s + bias``; a
    chosen expert's gate is its own score."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u.astype(F32) @ router.astype(F32))
        _v, idx = jax.lax.top_k(s + bias.astype(F32), top_k)
        val = jnp.take_along_axis(s, idx, -1)
        if norm:
            val = val / (jnp.sum(val, -1, keepdims=True) + 1e-20)
        val = val * scale
        rows = jnp.arange(u.shape[0])[:, None]
        return jnp.zeros_like(s).at[rows, idx].set(val), idx


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


def mlp_branch(u, get: Callable[[str], jax.Array], cfg: Dict, layer: int):
    """``F_mlp(u)`` and the experts the router chose (``[T, top_k]`` int32;
    ``None`` for a dense layer)."""
    v = _norm(u, get("mlp_norm"), eps=cfg["rms_norm_eps"])
    if layer < cfg["first_k_dense_replace"]:
        return _swiglu(v, *(get(k) for k in DENSE_MLP_KEYS)), None
    gates, chosen = _route(v, get("router"), get("router_bias"),
                           top_k=cfg["num_experts_per_tok"],
                           norm=bool(cfg["norm_topk_prob"]),
                           scale=float(cfg["routed_scaling_factor"]))
    y = _swiglu(v, *(get(k) for k in SHARED_KEYS))
    wg, wu, wd = (get(k) for k in EXPERT_KEYS)
    for e in range(cfg["n_routed_experts"]):         # one expert at a time
        y = y + gates[:, e, None] * _swiglu(v, wg[e], wu[e], wd[e])
    return y, chosen


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray):
    """The normed last hidden state ``[T, hidden]`` (the streams summed) and
    every expert layer's choice, ``[expert layers, T, top_k]``."""
    e = get("embed", -1)[jnp.asarray(ids)].astype(F32)
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], cfg["hc_mult"],
                                         e.shape[1]))
    cfgt, chosen = _frozen(cfg), []
    for layer in range(cfg["num_hidden_layers"]):
        lget = functools.partial(get, layer=layer)
        u, h_post, h_res = mix_in(x, lget, cfg, "attn")
        y = _attention(u, {k: lget(k) for k in ATTN_KEYS}, cfgt=cfgt)
        x = _mix_out(x, y, h_post, h_res)
        u, h_post, h_res = mix_in(x, lget, cfg, "mlp")
        y, idx = mlp_branch(u, lget, cfg, layer)
        x = _mix_out(x, y, h_post, h_res)
        if idx is not None:
            chosen.append(np.asarray(idx))
    return _norm(jnp.sum(x, 1), get("final_norm", -1),
                 eps=cfg["rms_norm_eps"]), np.stack(chosen)


@functools.partial(jax.jit, static_argnames=("lo", "size"))
def _head_slice(y, head, *, lo, size):
    with jax.default_matmul_precision("highest"):
        return _r(y) @ _r(jax.lax.dynamic_slice_in_dim(
            head, lo, size, axis=1).astype(F32))


def logits(get, cfg: Dict, ids: np.ndarray, vocab_slices: int = 1):
    """``[T, vocab]`` float32 logits of one full forward."""
    y, _chosen = final_hidden(get, cfg, ids)
    head, v = get("head", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    return jnp.concatenate([_head_slice(y, head, lo=lo, size=min(size, v - lo))
                            for lo in range(0, v, size)], -1)


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 4, with_chosen: bool = False):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``,
    by one full forward. The sequence is padded at its END to ``pad_to`` so
    that one compiled shape serves every request (attention is causal and
    everything else is position-wise: padding after a position cannot reach
    it). ``with_chosen`` also returns the experts chosen at ``tokens[:-1]``,
    ``[expert layers, len - 1, top_k]``."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    y, chosen = final_hidden(get, cfg, ids)
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
    return (out, chosen[:, :n - 1]) if with_chosen else out
