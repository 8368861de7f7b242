"""Laguna-XS.2 (HF ``model_type`` ``laguna``; ``poolside/Laguna-XS.2/
config.json``): the plain reference.

One forward in plain ``jax.numpy``, float32 at
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, one dense causal mask with the window in it. What the benchmark
compares the served model with (``benchmark/lib/reference_laguna.py`` is a
copy of this file) and what ``tests/test_laguna.py`` compares
``paddle_tpu.models.laguna`` with. On the chip it runs in blocks so that a
16k-token sequence fits beside the model and so that ONE compiled shape of
each piece serves every sequence (a run's first compile of it is part of the
run): everything position-wise a block of ``BLOCK`` tokens at a time (a
layer's weights upcast at a time, an expert at a time, the head a slice of
the vocabulary at a time), attention a K/V head and 1024 queries at a time
against the whole padded sequence's keys.

The layer (``x`` the residual stream, float32; ``N`` an RMSNorm with weight,
eps 1e-6; ``H_l`` = ``num_attention_heads_per_layer[l]``, 48 in a
``full_attention`` layer and 64 in a ``sliding_attention`` one; G = 8 K/V
heads; d = 128)::

    u = N1(x);  q = u Wq [H_l, d];  k = u Wk [G, d];  v = u Wv [G, d]
    g = sigmoid(u Wg) [H_l]
    q, k <- RoPE_kind(q, k, pos)
      full:    rotate-half over the FIRST 64 dims of a head (partial rotary
               0.5), inverse frequencies YaRN-blended (theta 5e5 over 64
               dims, factor 64 over 4096 positions, beta_fast 64, beta_slow
               1: ``yarn_inv_freq``), cos and sin times attention_factor
               1.41589
      sliding: rotate-half over all 128 dims, theta 1e4, no scaling
    a_h = softmax_j(q_h . k_(h // (H_l / G)),j / sqrt(128)) v_j
          over keys j <= i, and in a sliding layer also j > i - 512
          (512 keys, the token's own among them)
    x <- x + [g_h a_h]_h Wo
    u = N2(x)
    layer 0 (``mlp_layer_types`` "dense"):
          x <- x + (silu(u W1) * (u W3)) W2                      width 8192
    layers 1-39 ("sparse"):
          s = sigmoid(float32(u) Wr) [256];  T = top-8 of s
          w_e = 2.5 s_e / sum_T s
          x <- x + sum_{e in T} w_e (silu(u W1e) * (u W3e)) W2e
                 + (silu(u W1s) * (u W3s)) W2s                   width 512
    logits = N_f(x) W_head

Assumed (the published ``config.json`` does not say; each is also in the
benchmark configuration's ``assumed`` list — correct both together, never
one): the gate (``gating: true``; ``"per-head"`` in the sibling
Laguna-S-2.1) is a sigmoid of a per-head linear map of the normed input and
multiplies the head's context before ``Wo`` (the published 33.4 B leaves no
room for an elementwise gate); the router scores with a sigmoid and
normalises its top 8 before the factor 2.5 (``norm_topk_prob: true`` in the
sibling; no correction bias, no groups, no soft cap); the routed weight
multiplies the expert's OUTPUT (``moe_apply_router_weight_on_input:
false``); the shared expert has no gate of its own; no norm on q and k;
pre-norm residuals; weights stored ``[in, out]``, ``Wq`` head-major with the
``H_l / G`` query heads of a K/V head adjacent.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

ATTN_KEYS = ("input_norm", "q", "k", "v", "g", "o", "post_attn_norm")
DENSE_MLP_KEYS = ("gate_w", "up_w", "down_w")
SHARED_KEYS = ("shared_gate", "shared_up", "shared_down")
EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")

BLOCK = 2048          # tokens a position-wise piece takes at a time
_QUERY_BLOCK = 1024   # queries whose [H_l / G, block, T] scores are alive

# The check's controls (PERF.md section 6), set before the first call by a
# control run alone. ``ROUND``: a function every matmul operand and the
# would-be cached key and value pass through, e.g. ``lambda x:
# jax.lax.reduce_precision(x, 8, 3)``; ``None``: float32 as described.
# ``NO_WINDOW``: the sliding layers see every earlier key.
ROUND = None
NO_WINDOW = False


def _r(x):
    return x if ROUND is None else ROUND(x)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(rope: Dict, dim: int) -> np.ndarray:
    """Inverse frequencies of the ``dim`` rotated dims of a head, float64.
    ``rope_type`` "default": ``theta^(-2i / dim)``. "yarn" (HF
    ``_compute_yarn_parameters``): each frequency a blend of that and the
    same divided by ``factor``, by a linear ramp between the dims that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos_freqs
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp       # 1: the frequency as it is
    return (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation) + \
        (1.0 / pos_freqs) * extrapolation


def rope_of(cfg: Dict, kind: str):
    """``(inverse frequencies, rotated dims, factor on cos and sin)`` of a
    layer of ``kind`` (``"full_attention"`` / ``"sliding_attention"``)."""
    rope = cfg["rope_parameters"][kind]
    dim = int(round(cfg["head_dim"] * float(
        rope.get("partial_rotary_factor", 1.0))))
    scale = float(rope.get("attention_factor", 1.0)) \
        if rope.get("rope_type", "default") == "yarn" else 1.0
    return yarn_inv_freq(rope, dim), dim, scale


def _rope(x, first, inv_freq, dim, scale):
    """Rotate-half RoPE over the first ``dim`` dims of a head at positions
    ``first, first + 1, ...``; ``x`` is ``[T, heads, d]``."""
    pos = (first + jnp.arange(x.shape[0])).astype(F32)
    f = pos[:, None] * jnp.asarray(inv_freq, F32)[None]
    cos = (jnp.cos(f) * scale)[:, None, :]
    sin = (jnp.sin(f) * scale)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], -1)


class _Frozen:
    """A configuration as a hashable static argument."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._key = repr(sorted((k, repr(v)) for k, v in cfg.items()
                                if k not in ("system", "rehearsal",
                                             "deployment", "assumed",
                                             "reduced", "source", "name")))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key


def _upcast(w):
    return {k: v.astype(F32) if k.endswith("norm") else _r(v.astype(F32))
            for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _project(x, first, w, *, frozen, kind):
    """A block of positions ``first, first + 1, ...``: its roped queries
    ``[B, H_l, d]`` and keys ``[B, G, d]``, its values and its gates ``[B,
    H_l]``. The head count is the query projection's."""
    cfg = frozen.cfg
    with jax.default_matmul_precision("highest"):
        w = _upcast(w)
        b, d, G = x.shape[0], cfg["head_dim"], cfg["num_key_value_heads"]
        u = _r(_rms(x, w["input_norm"], cfg["rms_norm_eps"]))
        inv, dim, fac = rope_of(cfg, kind)
        q = _r(_rope((u @ w["q"]).reshape(b, -1, d), first, inv, dim, fac))
        k = _r(_rope((u @ w["k"]).reshape(b, G, d), first, inv, dim, fac))
        v = _r((u @ w["v"]).reshape(b, G, d))
        return q, k, v, jax.nn.sigmoid(u @ w["g"])


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _attend(x, first, q, gate, k, v, wo, *, frozen, kind):
    """``x + [g_h a_h]_h Wo`` for a block of queries at positions ``first,
    ...`` against the whole sequence's keys and values ``[T, G, d]``: one
    dense causal mask with the window in it."""
    cfg = frozen.cfg
    with jax.default_matmul_precision("highest"):
        b, H, d = q.shape
        t, G = k.shape[0], k.shape[1]
        Hg = H // G
        window = None if NO_WINDOW or kind != "sliding_attention" \
            else int(cfg["sliding_window"])
        qb = min(_QUERY_BLOCK, b)
        assert b % qb == 0, (b, qb)
        kpos = jnp.arange(t)[None, :]

        def block(i):     # one K/V head's query heads, one block of queries
            g, c = i // (b // qb), i % (b // qb)
            qs = jax.lax.dynamic_slice(q, (c * qb, g * Hg, 0), (qb, Hg, d))
            kg = jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
            vg = jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
            qpos = (first + c * qb + jnp.arange(qb))[:, None]
            seen = kpos <= qpos
            if window is not None:
                seen = seen & (kpos > qpos - window)
            att = jnp.einsum("qhd,kd->hqk", qs, kg) / np.sqrt(d)
            att = jnp.where(seen, att, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", _r(jax.nn.softmax(att, -1)), vg)

        o = jax.lax.map(block, jnp.arange(G * (b // qb)))  # [G*nb, qb, Hg, d]
        o = o.reshape(G, b, Hg, d).transpose(1, 0, 2, 3).reshape(b, H, d)
        o = (o * gate[:, :, None]).reshape(b, H * d)
        return x + _r(o) @ _r(wo.astype(F32))


@jax.jit
def _swiglu(u, gate, up, down):
    with jax.default_matmul_precision("highest"):
        gate, up, down = (_r(m.astype(F32)) for m in (gate, up, down))
        u = _r(u)
        return _r(jax.nn.silu(u @ gate) * (u @ up)) @ down


@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def _route(u, router, *, top_k, scale):
    """Weight of every (token, expert): 0 where the expert is not among the
    token's top-k. ``[T, E]`` float32."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u.astype(F32) @ router.astype(F32))
        val, idx = jax.lax.top_k(s, top_k)
        val = val / (jnp.sum(val, -1, keepdims=True) + 1e-20) * scale
        rows = jnp.arange(u.shape[0])[:, None]
        return jnp.zeros_like(s).at[rows, idx].set(val)


@jax.jit
def _experts(u, gates, wg, wu, wd):
    """``sum_e gates[:, e] expert_e(u)``, one expert's weights upcast at a
    time."""
    def one(y, e):
        pick = functools.partial(jax.lax.dynamic_index_in_dim, index=e,
                                 axis=0, keepdims=False)
        w = jax.lax.dynamic_index_in_dim(gates, e, 1)          # [T, 1]
        return y + w * _swiglu(u, pick(wg), pick(wu), pick(wd)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(wg.shape[0]))
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


def mlp_branch(u, get: Callable[[str], jax.Array], cfg: Dict, layer: int,
               n_live=None):
    """``MLP(u)``, and the routed (token, expert) pairs of the first
    ``n_live`` positions (0 for the dense layer)."""
    if cfg["mlp_layer_types"][layer] == "dense":
        return _swiglu(u, *(get(k) for k in DENSE_MLP_KEYS)), 0
    gates = _route(u, get("router"), top_k=cfg["num_experts_per_tok"],
                   scale=float(cfg["moe_routed_scaling_factor"]))
    y = _swiglu(u, *(get(k) for k in SHARED_KEYS)) + \
        _experts(u, gates, *(get(k) for k in EXPERT_KEYS))
    live = gates[:u.shape[0] if n_live is None else n_live] > 0
    return y, int(jnp.sum(live))


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray, n_live=None):
    """The normed last hidden state of the first ``n_live`` positions (all of
    them if ``None``), as blocks of ``BLOCK`` positions, and the routed pairs
    of those positions summed over the sparse layers. ``len(ids)`` is the
    padded length: at most ``BLOCK``, or a whole number of blocks. Only the
    blocks that hold a live position are computed (attention is causal and
    everything else is position-wise: what follows a position cannot reach
    it); the keys of the others stay zero behind the mask."""
    t = len(ids)
    n_live = t if n_live is None else n_live
    size = min(BLOCK, t)
    assert t % size == 0, (t, size)
    starts = list(range(0, max(n_live, 1), size))
    embed = get("embed", -1)
    xs = [embed[jnp.asarray(ids[a:a + size])].astype(F32) for a in starts]
    frozen, pairs, eps = _Frozen(cfg), 0, cfg["rms_norm_eps"]
    G, d = cfg["num_key_value_heads"], cfg["head_dim"]
    rest = jnp.zeros((t - len(starts) * size, G, d), F32)
    for layer in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][layer]
        w = {k: get(k, layer) for k in ATTN_KEYS}
        wp = {k: w[k] for k in ("input_norm", "q", "k", "v", "g")}
        proj = [_project(x, a, wp, frozen=frozen, kind=kind)
                for x, a in zip(xs, starts)]
        k_all = jnp.concatenate([p[1] for p in proj] + [rest])
        v_all = jnp.concatenate([p[2] for p in proj] + [rest])
        for i, ((q, _k, _v, gate), a) in enumerate(zip(proj, starts)):
            x = _attend(xs[i], a, q, gate, k_all, v_all, w["o"],
                        frozen=frozen, kind=kind)
            y, n = mlp_branch(
                _norm(x, get("post_attn_norm", layer), eps=eps),
                functools.partial(get, layer=layer), cfg, layer,
                min(max(n_live - a, 0), size))
            xs[i], pairs = x + y, pairs + n
    norm = get("final_norm", -1)
    return [_norm(x, norm, eps=eps) for x in xs], pairs


@functools.partial(jax.jit, static_argnames=("size",))
def _head_slice(y, head, lo, *, size):
    with jax.default_matmul_precision("highest"):
        return _r(y) @ _r(jax.lax.dynamic_slice_in_dim(
            head, lo, size, axis=1).astype(F32))


def _vocab_slices(v: int, n: int):
    """``(lo, size)`` of ``n`` slices of the vocabulary, equal but the last."""
    size = -(-v // n)
    return [(lo, min(size, v - lo)) for lo in range(0, v, size)]


def logits(get, cfg: Dict, ids: np.ndarray, vocab_slices: int = 1):
    """``[T, vocab]`` float32 logits of one full forward."""
    ys, _n = final_hidden(get, cfg, ids)
    head = get("head", -1)
    return jnp.concatenate([
        jnp.concatenate([_head_slice(y, head, lo, size=size) for lo, size in
                         _vocab_slices(cfg["vocab_size"], vocab_slices)], -1)
        for y in ys])


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 8, with_pairs: bool = False):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``,
    by one full forward. The sequence is padded at its END to ``pad_to`` (at
    most ``BLOCK``, or a whole number of blocks) so that one compiled shape
    of each piece serves every request. ``with_pairs`` also returns the
    routed pairs of ``tokens[:-1]``: what a server that emitted
    ``tokens[-1]`` last has routed."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    ys, pairs = final_hidden(get, cfg, ids, n - 1)
    head, out = get("head", -1), []
    for b, y in enumerate(ys):
        want = jnp.asarray(nxt[b * len(y):(b + 1) * len(y)])
        lse = jnp.full(len(y), -jnp.inf, F32)
        picked = jnp.zeros(len(y), F32)
        for lo, size in _vocab_slices(cfg["vocab_size"], vocab_slices):
            lg = _head_slice(y, head, lo, size=size)
            lse = jnp.logaddexp(lse, jax.scipy.special.logsumexp(lg, -1))
            here = (want >= lo) & (want < lo + size)
            col = jnp.clip(want - lo, 0, size - 1)
            picked = jnp.where(here, jnp.take_along_axis(
                lg, col[:, None], -1)[:, 0], picked)
        out.append(np.asarray(picked - lse))
    out = np.concatenate(out)[:n - 1]
    return (out, pairs) if with_pairs else out
