"""Plain reference for the Llama-family decoder that ``models/llama.py``
runs (InternLM2 here): RMSNorm, rotary positions (rotate-half convention,
as the published HF code), grouped-query causal attention, SwiGLU, no
biases, untied output head; the causal language-model loss in float32.

Straightforward ``jax.numpy``: float32 throughout, matmuls at
``default_matmul_precision("highest")`` (on a TPU a float32 matmul otherwise
runs in bf16 passes), no kernels, no cache, no scan, no recompute. One layer
is one small jitted function called in a Python loop, so only one layer's
weights are ever held in float32 and the whole model is never one program.

Departures from the published model, each noted: InternLM2 stores a fused
``wqkv``; three separate projections are the same mathematics. Documents
packed into one row attend across their boundaries, as the system under test
does (no document mask).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x: [b, s, heads, d]; rotate-half convention
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=F32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                              "theta"))
def decoder_layer(x, w, *, heads, kv_heads, eps, theta):
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        b, s, h = x.shape
        hd = w["wq"].shape[1] // heads
        y = _rms(x, w["norm1"], eps)
        q = _rope((y @ w["wq"]).reshape(b, s, heads, hd), theta)
        k = _rope((y @ w["wk"]).reshape(b, s, kv_heads, hd), theta)
        v = (y @ w["wv"]).reshape(b, s, kv_heads, hd)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, heads * hd)
        x = x + o @ w["wo"]
        y = _rms(x, w["norm2"], eps)
        return x + (jax.nn.silu(y @ w["wg"]) * (y @ w["wu"])) @ w["wd"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_loss_sum(x, norm, w_head, labels, *, eps):
    """Sum of next-token cross-entropies of one chunk of rows."""
    with jax.default_matmul_precision("highest"):
        y = _rms(x, norm.astype(F32), eps)[:, :-1]
        logits = y @ w_head.astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tgt = labels[:, 1:]
        return -jnp.sum(jnp.take_along_axis(logp, tgt[..., None], -1))


def causal_lm_loss(get: Callable[[str, int], jax.Array], cfg: Dict,
                   ids: np.ndarray, rows: int = 1) -> float:
    """Mean next-token cross-entropy of ``ids`` ``[batch, seq]``.

    ``get(name, layer)`` hands over one weight (any float type, laid out
    ``[in, out]``) on the device the reference runs on: ``embed``, ``head``,
    ``final_norm`` (layer ``-1``) and per layer ``wq wk wv wo wg wu wd norm1
    norm2``."""
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))
    ids = np.asarray(ids)
    embed = get("embed", -1)
    chunks = [jnp.asarray(ids[r:r + rows], jnp.int32)
              for r in range(0, ids.shape[0], rows)]
    xs = [embed[c].astype(F32) for c in chunks]
    del embed
    # layers outermost: each layer's weights are fetched (on a mesh:
    # gathered from their shards) once, not once per chunk of rows
    for layer in range(cfg["num_hidden_layers"]):
        w = {k: get(k, layer) for k in
             ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "norm1", "norm2")}
        xs = [decoder_layer(x, w, **kw) for x in xs]
    norm, head = get("final_norm", -1), get("head", -1)
    total = sum(float(head_loss_sum(x, norm, head, c, eps=kw["eps"]))
                for x, c in zip(xs, chunks))
    return total / (ids.shape[0] * (ids.shape[1] - 1))
