"""Attention functionals.

The fused-attention hot op (reference: paddle/fluid/operators/fused/
fused_attention_op.cu + fmha_ref.h) re-designed TPU-first: a single fused
primitive that XLA maps onto MXU matmuls, with a Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py) engaged on TPU for long sequences.

Layout convention (paddle's): q/k/v are [batch, seq, num_heads, head_dim].
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from ...core.dispatch import primitive
from ...core.tensor import Tensor
from ...framework import random as random_mod


def _sdpa_xla(q, k, v, mask, *, causal, scale, dropout_p, key=None):
    # [b, s, h, d] -> attention over s with batched heads
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if causal:
        qs, ks = q.shape[1], k.shape[1]
        causal_mask = jnp.tril(jnp.ones((qs, ks), bool), k=ks - qs)
        logits = jnp.where(causal_mask, logits, -1e30)
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@primitive("sdpa")
def _sdpa(q, k, v, *, causal, scale, impl="xla"):
    if impl == "flash":
        # no fallback: a kernel the chip's compiler refuses must surface
        # (a silent XLA softmax here once hid every such refusal)
        from ...distributed.mesh import (activation_spec,
                                         run_forward_kernel_on_mesh)
        from ...kernels.flash_attention import flash_attention

        # under a live mesh: batch over dp/sdp, heads over mp, one
        # full-manual shard_map (GSPMD cannot partition a Mosaic kernel)
        spec = activation_spec(q.shape, "bshd")
        return run_forward_kernel_on_mesh(
            lambda ql, kl, vl: flash_attention(ql, kl, vl, causal=causal,
                                               scale=scale),
            (q, k, v), (spec, spec, spec), spec)
    return _sdpa_xla(q, k, v, None, causal=causal, scale=scale,
                     dropout_p=0.0)


@primitive("sdpa_mask")
def _sdpa_mask(q, k, v, mask, *, causal, scale):
    return _sdpa_xla(q, k, v, mask, causal=causal, scale=scale, dropout_p=0.0)


@primitive("sdpa_dropout")
def _sdpa_dropout(q, k, v, rngkey, *, causal, scale, dropout_p):
    return _sdpa_xla(q, k, v, None, causal=causal, scale=scale,
                     dropout_p=dropout_p, key=rngkey)


@primitive("sdpa_mask_dropout")
def _sdpa_mask_dropout(q, k, v, mask, rngkey, *, causal, scale, dropout_p):
    return _sdpa_xla(q, k, v, mask, causal=causal, scale=scale,
                     dropout_p=dropout_p, key=rngkey)


def attention_backend(sq: int, sk: int, head_dim: int,
                      platform: str = None) -> str:
    """Which kernel ``scaled_dot_product_attention`` lands on for a
    (platform, shape): ``'flash'`` (Pallas) or ``'xla'`` (fused-XLA
    softmax). The old hard-coded "TPU + long sequence" heuristic is now
    a documented threshold — ``FLAGS_flash_min_seq`` (live-read): both
    q and kv sequences must reach it, on top of the kernel's structural
    constraints (block-divisible sequences, MXU-friendly head_dim).

    The decision is passed to the ``sdpa`` primitive as an ATTR, so it
    participates in the jit cache key: a threshold-driven path flip
    shows up as a new cache key the ``analysis.retrace`` auditor names
    (``op:sdpa`` label) instead of silently recompiling.
    """
    if os.environ.get("PADDLE_TPU_DISABLE_FLASH", "0") == "1":
        return "xla"
    if platform is None:
        platform = jax.devices()[0].platform
    if platform == "cpu":
        return "xla"
    from ...distributed.mesh import kernel_mesh_ok
    from ...framework import flags as flags_mod

    if not kernel_mesh_ok():  # inside the pipeline's manual region
        return "xla"

    if not flags_mod.flag("use_pallas_flash_attention"):
        return "xla"
    min_seq = int(flags_mod.flag("flash_min_seq"))
    if sq < min_seq or sk < min_seq:
        return "xla"
    # structural: block-divisible sequences, MXU-friendly head_dim
    if sq % 128 or sk % 128 or head_dim not in (64, 128, 256):
        return "xla"
    return "flash"


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None, name=None):
    """q/k/v: [batch, seq, heads, head_dim]. attn_mask: additive float mask
    broadcastable to [b, h, sq, sk]."""
    d = query.shape[-1]
    s = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    if dropout_p > 0.0 and training:
        rk = random_mod.next_key()
        if attn_mask is None:
            return _sdpa_dropout(query, key, value, rk, causal=bool(is_causal),
                                 scale=s, dropout_p=float(dropout_p))
        return _sdpa_mask_dropout(query, key, value, attn_mask, rk,
                                  causal=bool(is_causal), scale=s, dropout_p=float(dropout_p))
    if attn_mask is None:
        impl = attention_backend(query.shape[1], key.shape[1],
                                 query.shape[3])
        return _sdpa(query, key, value, causal=bool(is_causal), scale=s,
                     impl=impl)
    return _sdpa_mask(query, key, value, attn_mask, causal=bool(is_causal), scale=s)


flash_attention = scaled_dot_product_attention  # paddle.nn.functional.flash_attention alias
