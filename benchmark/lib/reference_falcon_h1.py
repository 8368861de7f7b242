"""Plain reference for Falcon-H1 (TII, 2025; HF ``model_type`` ``falcon_h1``,
``tiiuae/Falcon-H1-34B-Instruct/config.json``): every block runs a Mamba-2
mixer and a grouped-query attention IN PARALLEL on one normed input and sums
both into one residual, then a SwiGLU MLP; muP multipliers scale every path.

With ``h`` the residual stream ``[T, hidden]``, RMSNorm eps ``rms_norm_eps``,
all projections without bias except the conv::

    x0   = embed[ids] * embedding_multiplier
    u    = rmsnorm(h; input_layernorm)
    # attention branch (GQA, RoPE theta over the whole head, rotate-half,
    # scale 1/sqrt(head_dim), causal)
    q,k,v = (u*attention_in_multiplier) @ Wq, Wk, Wv ; k = k*key_multiplier
    a    = softmax(rope(q) rope(k)^T / sqrt(head_dim)) v @ Wo
           * attention_out_multiplier
    # Mamba-2 branch (d_ssm = heads x d_head, n_groups, d_state, d_conv)
    zxbcdt = ((u*ssm_in_multiplier) @ W_in) * mup_vector
             # mup_vector: ssm_multipliers[0..4] over z | x | B | C | dt
    z | xBC | dt = split(d_ssm | d_ssm + 2*groups*d_state | heads)
    xBC = silu(causal_depthwise_conv1d(xBC, k=d_conv) + b_conv)
    x | B | C = split(d_ssm | groups*d_state | groups*d_state)
    dt = softplus(dt + dt_bias) ;  A = -exp(A_log)             # per head
    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D x_t       # heads of a group share B_t, C_t
    y  = grouped_rmsnorm(y * silu(z); n_groups) @ W_out * ssm_out_multiplier
    h  = h + a + y
    v  = rmsnorm(h; pre_ff_layernorm)
    h  = h + (up(v) * silu(gate(v) * mlp_multipliers[0])) @ W_down
         * mlp_multipliers[1]
    logits = rmsnorm(h_L; final_layernorm) @ W_head * lm_head_multiplier

Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
a full forward over the whole sequence, the recurrence as a plain
``lax.scan`` over time — no chunking, no cache, no pages, no batching. One
block is one small jitted function called in a Python loop, its weights upcast
when it runs, and the head walks the vocabulary in slices (a float32 copy of a
261120 x 5120 head is 5.3 GB), so that the published widths fit one chip
beside the served model's own weights.

Assumed (the published ``config.json`` does not say): the grouped RMSNorm's
groups are contiguous slices of ``d_ssm`` (``n_groups`` of them) and its
weight multiplies after the normalisation; RoPE is the rotate-half form over
the whole head; weights are stored ``[in, out]``; the conv weight is
``[channels, d_conv]`` with tap ``d_conv - 1`` on the current position.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

BLOCK_KEYS = ("input_norm", "q_w", "k_w", "v_w", "o_w", "in_w", "conv_w",
              "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "out_w",
              "ff_norm", "gate_w", "up_w", "down_w")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over the whole head; ``x`` is ``[T, heads, d]``."""
    t, _h, d = x.shape
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dims(cfg: Dict):
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    d_ssm = cfg["mamba_d_ssm"]
    assert heads * p == d_ssm, (heads, p, d_ssm)
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return heads, p, d_ssm, gn


def mup_vector(cfg: Dict):
    heads, _p, d_ssm, gn = dims(cfg)
    m = cfg["ssm_multipliers"]
    return jnp.concatenate([jnp.full(n, v, F32) for n, v in zip(
        (d_ssm, d_ssm, gn, gn, heads), m)])


def ssm_recurrence(x, dt, a, b, c, d, n=None):
    """The selective state-space recurrence, one step a position.
    ``x`` [T, heads, P]; ``dt`` [T, heads] (after softplus); ``a`` [heads]
    (negative); ``b``, ``c`` [T, heads, N] (already given to each head);
    ``d`` [heads]. Returns ``y`` [T, heads, P] and the final state
    [heads, P, N] — with ``n``, the state after the first ``n`` positions
    (the later ones are a padded tail: they leave it as it is)."""
    def step(s, xs):
        x_t, dt_t, b_t, c_t, live = xs
        s = jnp.where(live, jnp.exp(dt_t * a)[:, None, None] * s +
                      (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :], s)
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x_t

    t = x.shape[0]
    s0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[-1]), F32)
    live = jnp.arange(t) < (t if n is None else n)
    s, y = jax.lax.scan(step, s0, (x, dt, b, c, live))
    return y, s


def _frozen(cfg: Dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, list))))


@functools.partial(jax.jit, static_argnames=("cfgt",))
def _block(x, w, n_live, *, cfgt):
    cfg = dict(cfgt)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t = x.shape[0]
        eps = cfg["rms_norm_eps"]
        nh, kvh, hd = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        heads, p, d_ssm, gn = dims(cfg)
        groups, n, kc = (cfg["mamba_n_groups"], cfg["mamba_d_state"],
                         cfg["mamba_d_conv"])
        u = _rms(x, w["input_norm"], eps)
        # -- attention branch
        ua = u * cfg["attention_in_multiplier"]
        q = (ua @ w["q_w"]).reshape(t, nh, hd)
        k = (ua @ w["k_w"]).reshape(t, kvh, hd) * cfg["key_multiplier"]
        v = (ua @ w["v_w"]).reshape(t, kvh, hd)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        k, v = (jnp.repeat(a, nh // kvh, axis=1) for a in (k, v))
        att = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(att, -1), v)
        a = o.reshape(t, nh * hd) @ w["o_w"] * cfg["attention_out_multiplier"]
        # -- Mamba-2 branch
        zxbcdt = ((u * cfg["ssm_in_multiplier"]) @ w["in_w"]) * mup_vector(cfg)
        z, xbc, dt = jnp.split(zxbcdt, [d_ssm, 2 * d_ssm + 2 * gn], -1)
        pad = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), F32), xbc])
        conv = sum(pad[j:j + t] * w["conv_w"][:, j] for j in range(kc))
        xbc = jax.nn.silu(conv + w["conv_b"])
        xs, b, c = jnp.split(xbc, [d_ssm, d_ssm + gn], -1)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        rep = heads // groups
        b, c = (jnp.repeat(m.reshape(t, groups, n), rep, axis=1)
                for m in (b, c))
        y, s = ssm_recurrence(xs.reshape(t, heads, p), dt,
                              -jnp.exp(w["A_log"]), b, c, w["D"], n_live)
        # what a cache would hold after n_live positions: the recurrence's
        # state and the conv's last d_conv - 1 inputs
        # (and, a head at a time, the log of what is left by then of the
        # state the first position wrote: near 0 is a long memory)
        live = (jnp.arange(t) < n_live)[:, None]
        state = {"ssm": s, "conv": jax.lax.dynamic_slice_in_dim(
            pad, n_live, kc - 1, axis=0),
            "log_decay": jnp.sum(jnp.where(live, -dt * jnp.exp(w["A_log"]),
                                           0.0), axis=0)}
        y = y.reshape(t, d_ssm) * jax.nn.silu(z)
        yg = y.reshape(t, groups, d_ssm // groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        y = yg.reshape(t, d_ssm) * w["ssm_norm"]
        y = y @ w["out_w"] * cfg["ssm_out_multiplier"]
        x = x + a + y
        # -- MLP
        v2 = _rms(x, w["ff_norm"], eps)
        g0, g1 = cfg["mlp_multipliers"]
        m = (v2 @ w["up_w"]) * jax.nn.silu((v2 @ w["gate_w"]) * g0)
        return x + m @ w["down_w"] * g1, state


def block(x, w, cfg: Dict, n=None):
    """One block over the whole sequence ``x`` [T, hidden]; returns the
    stream and the mixer's state after the first ``n`` positions (all of
    them by default)."""
    return _block(x, w, jnp.int32(x.shape[0] if n is None else n),
                  cfgt=_frozen(cfg))


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("lo", "size", "mult"))
def _head_slice(y, head, *, lo, size, mult):
    with jax.default_matmul_precision("highest"):
        w = jax.lax.dynamic_slice_in_dim(head, lo, size, axis=1).astype(F32)
        return (y @ w) * mult


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray, n=None):
    """The normed last hidden state ``[T, hidden]`` and, per layer, the
    mixer's state after the first ``n`` positions."""
    x = get("embed", -1)[jnp.asarray(ids)].astype(F32) * \
        cfg["embedding_multiplier"]
    states = []
    for layer in range(cfg["num_hidden_layers"]):
        x, st = block(x, {k: get(k, layer) for k in BLOCK_KEYS}, cfg, n)
        states.append(st)
    return _final_norm(x, get("final_norm", -1),
                       eps=cfg["rms_norm_eps"]), states


def logits(get, cfg: Dict, ids: np.ndarray, vocab_slices: int = 1):
    """``[T, vocab]`` float32 logits of one full forward."""
    y, _states = final_hidden(get, cfg, ids)
    head, v = get("head", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    parts = [_head_slice(y, head, lo=lo, size=min(size, v - lo),
                         mult=cfg["lm_head_multiplier"])
             for lo in range(0, v, size)]
    return jnp.concatenate(parts, -1)


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 8, with_state: bool = False):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``,
    by one full forward. The sequence is padded at its END to ``pad_to`` so
    that one compiled shape serves every request (attention, conv and
    recurrence are all causal: padding after a position cannot reach it).
    The log-softmax runs over the vocabulary a slice at a time.
    ``with_state`` also returns, per layer, the mixer's state after
    ``tokens[:-1]``: what a server that emitted ``tokens[-1]`` last holds
    (it has consumed every token but that one)."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    y, states = final_hidden(get, cfg, ids, n - 1)
    head, v = get("head", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    lse = jnp.full(pad_to, -jnp.inf, F32)
    picked = jnp.zeros(pad_to, F32)
    for lo in range(0, v, size):
        lg = _head_slice(y, head, lo=lo, size=min(size, v - lo),
                         mult=cfg["lm_head_multiplier"])
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(lg, -1))
        here = (nxt >= lo) & (nxt < lo + lg.shape[1])
        col = jnp.clip(jnp.asarray(nxt) - lo, 0, lg.shape[1] - 1)
        picked = jnp.where(here, jnp.take_along_axis(
            lg, col[:, None], -1)[:, 0], picked)
    out = np.asarray(picked - lse)[:n - 1]
    return (out, states) if with_state else out
