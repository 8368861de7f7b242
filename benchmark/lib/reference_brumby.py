"""Plain reference for Brumby-14B-Base (``model_type`` ``brumby``): the power
retention layer in its ATTENTION form, straight ``jax.numpy`` in float32 at
``highest`` matmul precision — no kernel, no cache, no recurrence, no chunks
(rows are taken a block at a time, in the attention form and in the MLP, only
so that 16 k tokens fit). The system
under test (``paddle_tpu/models/brumby.py`` behind ``GenerationEngine``)
computes the same function in the recurrent form; the two are equal in exact
arithmetic, and that identity is the test.

One layer, for token ``t``, earlier token ``s <= t``, query head ``h``, its
K/V head ``kappa = h // (heads / kv_heads)``, ``d = head_dim``, power ``p``::

    u        = N_in(x)
    q_h(t)   = RoPE(N_q(u W_q[h]), t)        k_kappa(t) = RoPE(N_k(u W_k[kappa]), t)
    v_kappa(t) = u W_v[kappa]                lambda_kappa(t) = log sigmoid(float32(u W_g)[kappa] + gate_shift)
    w_h(t, s) = exp(sum_{r = s+1..t} lambda_kappa(r)) (q_h(t) . k_kappa(s) / sqrt(d))^p
    a_h(t)   = sum_{s <= t} w_h(t, s) v_kappa(s) / (sum_{s <= t} w_h(t, s) + eps)
    x <- x + [a_0 .. a_{H-1}] W_o ;  x <- x + W_d(silu(v W_gate) * (v W_up)),  v = N_post(x)

then the final norm and the untied head. For the check of a server's state
the reference also returns, per layer, what the recurrence would hold after
the first ``n`` tokens, BUILT FROM THE DEFINITION and not by recurring::

    S_kappa = sum_{s < n} exp(sum_{r = s+1..n-1} lambda_kappa(r)) phi(k_kappa(s) / d^{1/4}) v_kappa(s)^T
    z_kappa = the same sum of phi(k_kappa(s) / d^{1/4})

with ``phi`` the minimal symmetric square (``x_i x_j`` for ``i <= j``, weight
``sqrt 2`` off the diagonal: ``d (d + 1) / 2`` = 8256 rows at 128), for which
``phi(a) . phi(b) = (a . b)^2``.

ASSUMED (the published ``config.json`` carries none of it; the configuration
file lists the same items): ``p = 2``; the gate is a linear map to one scalar
a K/V head through ``log sigmoid``, in float32, no bias (``gate_shift`` is a
property of the random weights' draw, 0 for a trained model's); the
normalisation by the decayed sum of weights with ``eps = 1e-6``; the ``1 /
sqrt(d)`` scale inside the power; ``q_norm`` / ``k_norm`` and rotate-half RoPE
kept from Qwen3; the state shared by the query heads of a K/V head.

A ``cfg`` here is the configuration file's dict; ``get(name, layer)`` hands
out one weight (``layer`` -1: a top-level one), stored ``[in, out]``.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

BLOCK_KEYS = ("input_norm", "q_w", "k_w", "v_w", "g_w", "q_norm", "k_norm",
              "o_w", "post_norm", "gate_w", "up_w", "down_w")
ROW_BLOCK = 256     # query rows a step of the attention form scores
# The check's control (PERF.md section 6, PR 46), set before the first call by
# a builder who wants the reference WRONG on purpose: every matmul operand
# through ``ROUND`` (``lambda x: jax.lax.reduce_precision(x, 8, 3)``: what a
# scaled fp8 matmul keeps). ``None``: float32 as described.
ROUND = None


def _r(x):
    return x if ROUND is None else ROUND(x)


def _mm(a, w):
    return _r(a) @ _r(w)


def _frozen(cfg: Dict):
    return tuple((k, cfg[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "retention_power", "retention_eps",
        "gate_shift"))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over the whole head; ``x`` [T, heads, d] at positions
    0 .. T-1."""
    t, _, d = x.shape
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = jnp.arange(t, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def phi(x):
    """The minimal symmetric square over the last axis: ``phi(a) . phi(b) =
    (a . b)^2``."""
    i, j = np.triu_indices(x.shape[-1])
    return x[..., i] * x[..., j] * np.where(i == j, 1.0, math.sqrt(2.0)
                                            ).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("cfgt",))
def _block(x, w, n_live, *, cfgt):
    cfg = dict(cfgt)
    nh, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, p = cfg["rms_norm_eps"], cfg["retention_power"]
    t, grp = x.shape[0], nh // kvh
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        u = _rms(x, w["input_norm"], eps)
        q = _r(_rope(_rms(_mm(u, w["q_w"]).reshape(t, nh, hd), w["q_norm"],
                          eps), cfg["rope_theta"]))
        k = _r(_rope(_rms(_mm(u, w["k_w"]).reshape(t, kvh, hd), w["k_norm"],
                          eps), cfg["rope_theta"]))
        v = _r(_mm(u, w["v_w"]).reshape(t, kvh, hd))
        lam = jax.nn.log_sigmoid(_mm(u, w["g_w"]) + cfg["gate_shift"])
        cum = jnp.cumsum(lam, axis=0)
        qg = q.reshape(t // ROW_BLOCK, ROW_BLOCK, kvh, grp, hd)
        at = jnp.arange(t).reshape(t // ROW_BLOCK, ROW_BLOCK)

        def rows(args):
            qb, tb = args                     # [rb, kvh, grp, hd], [rb]
            s = jnp.einsum("tkgd,skd->kgts", qb, k) / math.sqrt(hd)
            seg = cum[tb].T[:, :, None] - cum.T[:, None, :]   # [kvh, rb, T]
            seen = jnp.arange(t)[None, None, :] <= tb[None, :, None]
            wt = s ** p * jnp.exp(jnp.where(seen, seg, -jnp.inf))[:, None]
            num = jnp.einsum("kgts,skd->tkgd", _r(wt), v)
            den = jnp.sum(wt, axis=-1)                        # [kvh, grp, rb]
            return num / (jnp.moveaxis(den, -1, 0)[..., None]
                          + cfg["retention_eps"])

        a = jax.lax.map(rows, (qg, at)).reshape(t, nh * hd)
        # what the recurrence holds after n_live tokens, from the definition
        live = jnp.arange(t)[:, None] < n_live
        end = jnp.take(cum, n_live - 1, axis=0)               # [kvh]
        to_end = jnp.exp(jnp.where(live, end[None, :] - cum, -jnp.inf))

        def head_state(args):
            kh, vh, wh = args                 # [T, hd], [T, hd], [T]
            f = phi(kh / hd ** 0.25) * wh[:, None]
            return f.T @ vh, jnp.sum(f, axis=0)

        S, z = jax.lax.map(head_state, (jnp.moveaxis(k, 1, 0),
                                        jnp.moveaxis(v, 1, 0), to_end.T))
        state = {"S": S, "z": z,
                 "log_decay": jnp.sum(jnp.where(live, lam, 0.0), axis=0)}
        x = x + _mm(a, w["o_w"])

        def mlp(xb):                          # a block of rows: [rb, hidden]
            v2 = _rms(xb, w["post_norm"], eps)
            m = _mm(v2, w["up_w"]) * jax.nn.silu(_mm(v2, w["gate_w"]))
            return xb + _mm(m, w["down_w"])

        x = jax.lax.map(mlp, x.reshape(t // ROW_BLOCK, ROW_BLOCK, -1))
        return x.reshape(t, -1), state


def block(x, w, cfg: Dict, n=None):
    """One block over the whole sequence ``x`` [T, hidden] (padded here to
    whole row blocks); returns the stream and the retention's state after the
    first ``n`` positions (all by default)."""
    t = x.shape[0]
    pad = (-t) % ROW_BLOCK
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    out, state = _block(xp, w, jnp.int32(t if n is None else n),
                        cfgt=_frozen(cfg))
    return out[:t], state


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("lo", "size"))
def _head_slice(y, head, *, lo, size):
    with jax.default_matmul_precision("highest"):
        w = jax.lax.dynamic_slice_in_dim(head, lo, size, axis=1).astype(F32)
        return _mm(y, w)


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray, n=None):
    """The normed last hidden state ``[T, hidden]`` and, per layer, the
    retention's state after the first ``n`` positions."""
    x = get("embed", -1)[jnp.asarray(ids)].astype(F32)
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
    parts = [_head_slice(y, head, lo=lo, size=min(size, v - lo))
             for lo in range(0, v, size)]
    return jnp.concatenate(parts, -1)


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 8, with_state: bool = False):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``,
    by one full forward. The sequence is padded at its END to ``pad_to`` so
    that a few compiled shapes serve every request (the layer is causal:
    padding after a position cannot reach it). The log-softmax runs over the
    vocabulary a slice at a time. ``with_state`` also returns, per layer, the
    retention's state after ``tokens[:-1]``: what a server that emitted
    ``tokens[-1]`` last holds (it has consumed every token but that one)."""
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
        lg = _head_slice(y, head, lo=lo, size=min(size, v - lo))
        lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(lg, -1))
        here = (nxt >= lo) & (nxt < lo + lg.shape[1])
        col = jnp.clip(jnp.asarray(nxt) - lo, 0, lg.shape[1] - 1)
        picked = jnp.where(here, jnp.take_along_axis(
            lg, col[:, None], -1)[:, 0], picked)
    out = np.asarray(picked - lse)[:n - 1]
    return (out, states) if with_state else out
