"""Plain reference for ZAYA1 (HF ``model_type`` ``zaya``; ``Zyphra/ZAYA1-8B``,
``config.json``; mechanisms from arXiv:2510.04476, Compressed Convolutional
Attention, and arXiv:2511.17127, the ZAYA1 technical report): every layer is a
CCA sublayer, then a top-1 expert sublayer routed by an MLP that reads the
previous layer's router, both under residual scaling.

With ``x`` the residual stream ``[T, hidden]``, ``N`` an RMSNorm at
``rms_norm_eps``, ``d = head_dim``, weights ``[in, out]``, no bias::

    x0 = embed[ids] ;  r_{-1} = 0 ;  logits = N_f(x_L) embed^T

    CCA:  u = N_1(x)
          q~ = u Wq  (heads x d) ;  k~ = u Wk  (kv_heads x d)
          v  = [u_t Wv1 ; u_{t-1} Wv2]          # K/V head 0 | K/V head 1
          z  = [q~ ; k~]
          c1_t = z_t * a0 + z_{t-1} * a1        # depthwise, cca_time0 = 2
          c2_t = c1_t B0_g + c1_{t-1} B1_g      # d x d a head g, cca_time1 = 2
          m_i = (q~_i + k~_{i // rep}) / 2      # rep = heads / kv_heads
          q_i = c2(q)_i + m_i ;  k_j = c2(k)_j + mean_{i // rep = j} m_i
          q_i <- q_i / sqrt(mean(q_i^2) + eps)  # = sqrt(d) q_i / ||q_i||
          k_j <- tau_j k_j / sqrt(mean(k_j^2) + eps)
          q, k <- RoPE on the first partial_rotary_factor x d dims
          y = softmax_causal(q k^T / sqrt(d)) v  Wo
          x <- (a * x + b) + (c * y + e)
    MoE:  r_l = N_r(x) Wd + g_l * r_{l-1}
          s = gelu(gelu(r_l W1) W2) W3 ;  p = softmax(s)
          e* = argmax(p + bias) ;  u = N_2(x)
          y = p_{e*} down_{e*}(silu(gate_{e*}(u)) * up_{e*}(u))
          x <- (a' * x + b') + (c' * y + e')

Positions before 0 read zeros (``z_{-1} = c1_{-1} = 0``, ``u_{-1} Wv2 = 0``).

Straightforward ``jax.numpy``: float32,
``default_matmul_precision("highest")``, one full forward over the whole
sequence — the convolutions as explicit shifts, dense causal attention,
every expert by a plain loop — no kernels, no cache, no chunks, no batching. A layer's weights are upcast when it runs, an
expert at a time, and the head a slice of the vocabulary at a time, so that
the published widths fit one chip beside the served model's own weights.

Assumed (the published ``config.json`` does not say, and no modelling code
was read; the configuration file lists each with its sentence): the shifted
value is K/V head 1; the convs have no bias; the L2 norm is the weightless
RMS form above and ``tau`` multiplies the keys after it; RoPE pairs a head's
dimension ``j`` with ``j + rotary/2`` (rotate-half); the router norms the
stream with a weight of its own, its MLP is three matrices with exact gelu
between them and no bias, ``g_l`` multiplies the previous layer's
representation AFTER that layer's own averaging; the gate is the chosen
expert's own probability; residual scaling is four vectors a sublayer.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

CCA_KEYS = ("norm1", "qk_w", "v_w", "conv1", "conv2", "tau", "o_w", "scale1")
ROUTER_KEYS = ("router_norm", "router_down", "router_eda", "router_w1",
               "router_w2", "router_w3", "router_bias")

_HEAD_GROUP = 2   # query heads whose [T, T] scores are alive at once

# The check's control (benchmark/controls_cca.py; PERF.md section 6), set
# before the first call by a control run alone: a function every matmul
# operand passes through, e.g. ``lambda x: jax.lax.reduce_precision(x, 8, 3)``.
ROUND = None
# ... its two narrower kin, the precisions the configuration's ``assumed``
# states float32 for: ``ROUND_ROUTER``, a function every operand of the
# router's four matmuls passes through, and ``ROUND_STREAM``, one the residual
# stream passes through after every sublayer (``lambda x:
# jax.lax.reduce_precision(x, 8, 7)``: a bfloat16 router, a bfloat16 stream)
ROUND_ROUTER = None
ROUND_STREAM = None
# ... and the mechanisms a control leaves out, one at a time (a set of
# "qk_mean", "value_shift", "tau", "eda", "bias", "scales"): a run with one
# of them must come out not correct
DROPPED = frozenset()


def _r(x):
    return x if ROUND is None else ROUND(x)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _frozen(cfg: Dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def _theta(cfg: Dict) -> float:
    return float(cfg["rope_parameters"]["hybrid"]["rope_theta"])


def _behind(seq):
    """``seq`` [T, ...] one position later: row ``t`` holds ``seq[t - 1]``,
    row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(seq[:1]), seq[:-1]], 0)


def _rope(x, n, theta):
    """``x`` [T, heads, d]: rotate the first ``n`` dims, pairing ``j`` with
    ``j + n/2``."""
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=F32) / n))
    f = jnp.arange(t, dtype=F32)[:, None] * inv            # [T, n/2]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :n // 2], x[..., n // 2:n]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., n:]], -1)


def _scaled(s, x, y):
    out = x + y if "scales" in DROPPED else \
        (s[0] * x + s[1]) + (s[2] * y + s[3])
    return out if ROUND_STREAM is None else ROUND_STREAM(out)


@functools.partial(jax.jit, static_argnames=("cfgt", "theta", "dropped"))
def _cca(x, w, n_live, *, cfgt, theta, dropped):
    """``x`` after the CCA sublayer, and what a cache holds of the first
    ``n_live`` positions beside its keys and values: the last of them's
    ``[z ; c1 ; u Wv2]``."""
    cfg = dict(cfgt)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t, eps = x.shape[0], cfg["rms_norm_eps"]
        nh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        rep, groups = nh // kv, nh + kv
        u = _r(_rms(x, w["norm1"], eps))
        z = u @ _r(w["qk_w"])                              # [T, (nh + kv) d]
        vv = u @ _r(w["v_w"])                              # [T, 2 d]
        v_now, v_next = vv[:, :d], vv[:, d:]
        c1 = z * w["conv1"][0] + _behind(z) * w["conv1"][1]
        c1g, c1b = (_r(s).reshape(t, groups, d) for s in (c1, _behind(c1)))
        c2 = jnp.einsum("tgc,gcd->tgd", c1g, _r(w["conv2"][0])) + \
            jnp.einsum("tgc,gcd->tgd", c1b, _r(w["conv2"][1]))
        zq, zk = z[:, :nh * d].reshape(t, nh, d), z[:, nh * d:].reshape(
            t, kv, d)
        mean = 0.5 * (zq + jnp.repeat(zk, rep, axis=1))
        if "qk_mean" in dropped:
            mean = jnp.zeros_like(mean)
        q = c2[:, :nh] + mean
        k = c2[:, nh:] + jnp.mean(mean.reshape(t, kv, rep, d), axis=2)
        q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + eps)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + eps)
        if "tau" not in dropped:
            k = k * w["tau"][:, None]
        n_rot = int(d * cfg["partial_rotary_factor"])
        q, k = _r(_rope(q, n_rot, theta)), _r(_rope(k, n_rot, theta))
        v = _r(jnp.stack(
            [v_now, v_next if "value_shift" in dropped else _behind(v_next)],
            axis=1))                                       # [T, 2, d]
        causal = jnp.tril(jnp.ones((t, t), bool))
        G = min(_HEAD_GROUP, rep)
        assert rep % G == 0

        def heads(g):   # G query heads of ONE K/V head at a time
            j = g * G // rep
            att = jnp.einsum(
                "qhd,kd->hqk", jax.lax.dynamic_slice_in_dim(q, g * G, G, 1),
                jax.lax.dynamic_index_in_dim(k, j, 1, False)) / np.sqrt(d)
            att = jnp.where(causal, att, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", _r(jax.nn.softmax(att, -1)),
                              jax.lax.dynamic_index_in_dim(v, j, 1, False))

        o = jax.lax.map(heads, jnp.arange(nh // G))        # [nh/G, T, G, d]
        o = o.transpose(1, 0, 2, 3).reshape(t, nh * d)
        tail = jax.lax.dynamic_index_in_dim(
            jnp.concatenate([z, c1, v_next], -1),
            jnp.maximum(n_live - 1, 0), 0, False)
        return _scaled(w["scale1"], x, _r(o) @ _r(w["o_w"])), \
            {"tail": jnp.where(n_live > 0, tail, 0.0)}


@functools.partial(jax.jit, static_argnames=("eps", "dropped"))
def _route(x, r_prev, w, *, eps, dropped):
    """``(gates [T, E], chosen [T, 1], r_l)``: the gate of every (token,
    expert), 0 but at the one expert chosen — its own probability."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        rr = ROUND_ROUTER or (lambda a: a)
        r = rr(_rms(x, w["router_norm"], eps)) @ rr(w["router_down"])
        if "eda" not in dropped:
            r = r + w["router_eda"] * r_prev
        hid = jax.nn.gelu(rr(r) @ rr(w["router_w1"]), approximate=False)
        hid = jax.nn.gelu(rr(hid) @ rr(w["router_w2"]), approximate=False)
        p = jax.nn.softmax(rr(hid) @ rr(w["router_w3"]), -1)
        pick = p if "bias" in dropped else p + w["router_bias"]
        idx = jnp.argmax(pick, -1)[:, None]
        val = jnp.take_along_axis(p, idx, -1)
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.zeros_like(p).at[rows, idx].set(val), idx, r


@jax.jit
def _swiglu(u, gate, up, down):
    with jax.default_matmul_precision("highest"):
        gate, up, down = (_r(m.astype(F32)) for m in (gate, up, down))
        u = _r(u)
        return _r(jax.nn.silu(u @ gate) * (u @ up)) @ down


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


def experts(x, r_prev, get: Callable[[str], jax.Array], cfg: Dict,
            first: int = 0, count=None):
    """``x`` after the expert sublayer, the expert the router chose (``[T,
    1]``) and this layer's router representation. ``first`` / ``count``:
    only the experts ``[first, first + count)`` add their part (a chip's
    share of the layer; all of them by default)."""
    eps = cfg["rms_norm_eps"]
    gates, chosen, r = _route(x, r_prev, {k: get(k) for k in ROUTER_KEYS},
                              eps=eps, dropped=DROPPED)
    u = _norm(x, get("norm2"), eps=eps)
    gate, up, down = (get("experts_" + m) for m in ("gate", "up", "down"))
    count = cfg["num_experts"] - first if count is None else count
    y = jnp.zeros_like(x)
    for e in range(first, first + count):            # one expert at a time
        y = y + gates[:, e, None] * _swiglu(u, gate[e - first],
                                            up[e - first], down[e - first])
    return _scaled(get("scale2").astype(F32), x, y), chosen, r


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray, n=None, on_router=None):
    """The normed last hidden state ``[T, hidden]``, every layer's choice
    ``[layers, T, 1]`` and each layer's ``{"tail"}`` after the first ``n``
    positions (all by default). ``on_router(layer, x, r_prev)``: called with
    what each layer's router reads — the stream after the CCA sublayer and
    the previous layer's representation — so that a check can hand another
    router the SAME input."""
    x = get("embed", -1)[jnp.asarray(ids)].astype(F32)
    r = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), F32)
    n_live = jnp.int32(len(ids) if n is None else n)
    cfgt, chosen, tails = _frozen(cfg), [], []
    for layer in range(cfg["num_hidden_layers"]):
        lget = functools.partial(get, layer=layer)
        x, tail = _cca(x, {k: lget(k) for k in CCA_KEYS}, n_live, cfgt=cfgt,
                       theta=_theta(cfg), dropped=DROPPED)
        tails.append(tail)
        if on_router is not None:
            on_router(layer, x, r)
        x, idx, r = experts(x, r, lget, cfg)
        chosen.append(np.asarray(idx))
    return _norm(x, get("final_norm", -1), eps=cfg["rms_norm_eps"]), \
        np.stack(chosen), tails


@functools.partial(jax.jit, static_argnames=("lo", "size"))
def _head_slice(y, embed, *, lo, size):
    with jax.default_matmul_precision("highest"):
        return _r(y) @ _r(jax.lax.dynamic_slice_in_dim(
            embed, lo, size, axis=0).astype(F32)).T


def logits(get, cfg: Dict, ids: np.ndarray, vocab_slices: int = 1):
    """``[T, vocab]`` float32 logits of one full forward."""
    y, _chosen, _tails = final_hidden(get, cfg, ids)
    embed, v = get("embed", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    return jnp.concatenate(
        [_head_slice(y, embed, lo=lo, size=min(size, v - lo))
         for lo in range(0, v, size)], -1)


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 8, on_router=None):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``, by
    one full forward; beside it the experts chosen at ``tokens[:-1]``
    (``[layers, len - 1, 1]``) and each layer's tail after ``tokens[:-1]``:
    what a server that emitted ``tokens[-1]`` last holds (it has consumed
    every token but that one). The sequence is padded at its END to
    ``pad_to`` so that one compiled shape serves every request (attention and
    the convs are causal, the rest position-wise: padding after a position
    cannot reach it). ``on_router``: ``final_hidden``'s."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    y, chosen, tails = final_hidden(get, cfg, ids, n - 1, on_router)
    embed, v = get("embed", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    lse = jnp.full(pad_to, -jnp.inf, F32)
    picked = jnp.zeros(pad_to, F32)
    for lo in range(0, v, size):
        lg = _head_slice(y, embed, lo=lo, size=min(size, v - lo))
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(lg, -1))
        here = (nxt >= lo) & (nxt < lo + lg.shape[1])
        col = jnp.clip(jnp.asarray(nxt) - lo, 0, lg.shape[1] - 1)
        picked = jnp.where(here, jnp.take_along_axis(
            lg, col[:, None], -1)[:, 0], picked)
    return np.asarray(picked - lse)[:n - 1], chosen[:, :n - 1], tails
