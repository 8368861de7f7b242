"""Plain reference for GPT-2 (Radford et al. 2019; HF ``gpt2*``): learned
positions, pre-LayerNorm blocks with biases, multi-head causal attention
(``c_attn`` split into equal thirds Q, K, V), a 4x MLP with the tanh
approximation of GELU (``gelu_new``), a final LayerNorm and an output head
tied to the token embedding.

Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
a full forward over the whole sequence — no cache, no pages, no batching.
One block is one small jitted function called in a Python loop.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def block(x, w, *, heads, eps):
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t, h = x.shape
        hd = h // heads
        y = _ln(x, w["ln1_w"], w["ln1_b"], eps)
        qkv = y @ w["qkv_w"] + w["qkv_b"]
        q, k, v = (a.reshape(t, heads, hd) for a in jnp.split(qkv, 3, -1))
        att = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(att, -1), v)
        x = x + o.reshape(t, h) @ w["out_w"] + w["out_b"]
        y = _ln(x, w["ln2_w"], w["ln2_b"], eps)
        m = jax.nn.gelu(y @ w["fc_in_w"] + w["fc_in_b"], approximate=True)
        return x + m @ w["fc_out_w"] + w["fc_out_b"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logprobs(x, lnf_w, lnf_b, embed, nxt, *, eps):
    """log p(nxt[i] | tokens[:i+1]) for every position i."""
    with jax.default_matmul_precision("highest"):
        y = _ln(x, lnf_w.astype(F32), lnf_b.astype(F32), eps)
        logp = jax.nn.log_softmax(y @ embed.astype(F32).T, axis=-1)
        return jnp.take_along_axis(logp, nxt[:, None], -1)[:, 0]


BLOCK_KEYS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
              "ln2_w", "ln2_b", "fc_in_w", "fc_in_b", "fc_out_w", "fc_out_b")


def next_token_logprobs(get: Callable[[str, int], jax.Array], cfg: Dict,
                        tokens: np.ndarray, pad_to: int) -> np.ndarray:
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``,
    by one full forward. The sequence is padded at its END to ``pad_to`` so
    that one compiled shape serves every request (causal attention: padding
    after a position cannot reach it)."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    eps = float(cfg["layer_norm_epsilon"])
    x = get("embed", -1)[ids].astype(F32) + \
        get("pos", -1)[:pad_to].astype(F32)
    for layer in range(cfg["n_layer"]):
        x = block(x, {k: get(k, layer) for k in BLOCK_KEYS},
                  heads=cfg["n_head"], eps=eps)
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    lp = head_logprobs(x, get("lnf_w", -1), get("lnf_b", -1),
                       get("embed", -1), jnp.asarray(nxt), eps=eps)
    return np.asarray(lp)[:n - 1]
