"""Plain reference for GLM-5 (HF ``model_type`` ``glm_moe_dsa``;
``zai-org/GLM-5.2``, ``config.json``): DeepSeek-V3-style latent attention
(MLA) in pre-norm blocks, a learned sparse attention over it (DeepSeek Sparse
Attention with IndexShare), leading dense layers, then routed experts beside a
shared one.

``h`` = ``hidden_size`` 6144, ``H`` = 64 heads, RMSNorm eps ``rms_norm_eps``
1e-5, ``rope_theta`` 8 000 000 interleaved (no scaling), ``hidden_act`` silu,
no biases, untied head. With ``x`` the residual stream ``[T, h]``, token
``t`` and cached token ``s <= t``:

*Block* (pre-norm; two RMSNorm weights a layer beside the two inside the
attention)::

    x = x + Attn(N_in(x))
    x = x + MLP(N_post(x))

*Attention (MLA)*, on ``u = N_in(x)``::

    c_q = RMSNorm(u W_qa)                              # q_lora_rank 2048
    q   = c_q W_qb -> per head [q_nope (192) | q_rope (64)]
    q_rope = RoPE(q_rope, t)
    [c_kv (512) | k_r (64)] = u W_kva
    c_kv = RMSNorm(c_kv) ;  k_r = RoPE(k_r, s)   # ONE head, shared by all 64
    [k_nope_h (192) | v_h (256)] = c_kv W_kvb                    # per head
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_r(s)) / sqrt(256)
    o_h(t) = sum_{s in S_t} softmax_{s in S_t}(score_h(t, .)) v_h(s)
    out = concat_h(o_h) W_o                                 # 16384 -> 6144

*Indexer*, in a layer whose ``indexer_types`` entry is ``full``::

    qI_j(t) = c_q WI_q[j]          # 32 heads of 128; the first 64 roped
    kI(s)   = LayerNorm(u_s WI_k)  # 128; the first 64 roped
    w_j(t)  = (u_t WI_w)_j * 32^-1/2 * 128^-1/2
    I(t, s) = sum_j w_j(t) * relu(qI_j(t) . kI(s))
    S_t     = the index_topk (2048) tokens s <= t of largest I(t, s)
              (every s <= t while t + 1 <= 2048)

A ``shared`` layer owns no indexer and uses ``S_t`` of the nearest ``full``
layer before it (IndexShare). A cache of a layer holds ``[c_kv | k_r]`` (576
values a token) and, in a ``full`` layer, ``kI`` (128). The *absorbed* form
(equal in exact arithmetic; what the served model computes) scores ``q_nope_h
(W_kvb^{K,h})^T`` against ``c_kv``; this file computes the NON-absorbed form
above, under a mask built from ``I`` and ``jax.lax.top_k``.

*MLP.* A ``dense`` layer of ``mlp_layer_types``: SwiGLU at
``intermediate_size`` 12288. A ``sparse`` one, on ``v = N_post(x)``::

    s   = sigmoid(float32(v) W_r)                 # 256 scores, float32
    idx = top8(s + b)           # b: the noaux_tc correction, selection only
    g   = routed_scaling_factor (2.5) * s[idx] / (sum s[idx] + 1e-20)
    y   = sum_k g_k E_{idx_k}(v) + E_shared(v)
    E(v) = (silu(v W_g) * (v W_u)) W_d            # moe_intermediate_size 2048

*Head*: final RMSNorm, ``lm_head``.

Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``
— no cache, no pages, no kernels, no batching. It computes in BLOCKS so that a
49 k-token sequence at the published widths fits one chip beside the served
model's bfloat16 weights: ``BLOCK`` positions of everything position-wise at
a time, a layer's weights upcast where they are used, a sub-block of queries'
index scores and a group of heads' attention scores at a time, one expert at
a time, the head a slice of the vocabulary at a time; ONE shape of each piece
is compiled. Only the blocks that hold a live position are computed.

A SHARE of the model: ``cfg`` says which experts are held
(``n_routed_experts`` from router output ``held_experts_first``; the router
keeps its ``router_experts`` outputs) and which published layers
(``num_hidden_layers`` from ``layer_offset``; both per-layer lists are read
from there). What the experts that are not held would have added is left out.

Departures from the published description: none in the layer. Assumed (the
published ``config.json`` does not say; the family's inference code does):
the roped half of an index head is its FIRST 64 dims; ``kI``'s LayerNorm has
a weight and a bias and eps 1e-6; the index weights carry ``n_heads^-1/2
head_dim^-1/2``; ``shared`` means the set of the nearest ``full`` layer
before; RoPE is the interleaved form (dims ``2i, 2i + 1`` a pair; the
family's code also permutes the head afterwards, which no dot product sees);
the softmax scale is ``256^-0.5``; weights are stored ``[in, out]``, ``W_qb``
and ``W_kvb`` head-major. LEFT OUT: the Hadamard rotation of ``qI`` / ``kI``
(orthonormal: every ``qI . kI`` is as it was) and the fp8 storage of ``kI`` it
exists for — the configuration states bfloat16; ``n_group`` = ``topk_group`` =
1 makes the group-limited choice the plain one; the multi-token-prediction
module (``num_nextn_predict_layers`` 1) is a draft head the main model's
logits do not depend on.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

ATTN_KEYS = ("input_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o", "post_attn_norm")
INDEX_KEYS = ("index_q", "index_k", "index_k_norm", "index_k_bias", "index_w")
DENSE_MLP_KEYS = ("gate_w", "up_w", "down_w")
SHARED_KEYS = ("shared_gate", "shared_up", "shared_down")
EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")

BLOCK = 2048          # positions a position-wise piece takes at a time
_INDEX_QUERIES = 128  # queries whose [32, T] per-head index scores are alive
_QUERY_BLOCK = 512    # queries whose [G, T] attention scores are alive
_HEAD_GROUP = 8       # heads whose keys and values [T, G, .] are alive
INDEX_NORM_EPS = 1e-6

# The check's controls (PERF.md section 6), set before the first call by a
# control run alone. ``ROUND``: a function every matmul operand and both
# would-be cache rows (``[c_kv | k_r]``, ``kI``) pass through, e.g. ``lambda
# x: jax.lax.reduce_precision(x, 8, 3)``; ``None``: float32 as described.
# ``RECENT``: every layer attends the ``index_topk`` most recent tokens
# instead of ``S_t`` — a selection that is wrong in the most plausible way.
ROUND = None
RECENT = False


def _r(x):
    return x if ROUND is None else ROUND(x)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, first, theta):
    """Interleaved RoPE over the whole last dim at positions ``first, first +
    1, ...``: dims ``(2i, 2i + 1)`` turn by ``pos x theta^(-2i / d)``; ``x``
    is ``[T, heads, d]``."""
    t, _h, d = x.shape
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    f = (first + jnp.arange(t)).astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape)


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


def held_experts(cfg: Dict):
    """``(first, count, router width)`` of the share ``cfg`` describes."""
    count = cfg["n_routed_experts"]
    return (int(cfg.get("held_experts_first") or 0), count,
            int(cfg.get("router_experts") or count))


def layer_lists(cfg: Dict):
    """``(indexer kinds, mlp kinds)`` of the layers ``cfg`` holds."""
    lo = int(cfg.get("layer_offset") or 0)
    hi = lo + cfg["num_hidden_layers"]
    return cfg["indexer_types"][lo:hi], cfg["mlp_layer_types"][lo:hi]


def _upcast(w):
    return {k: v.astype(F32) if "norm" in k or k.endswith("bias")
            else _r(v.astype(F32)) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("frozen", "full"))
def _project(x, first, w, *, frozen, full):
    """A block of positions ``first, first + 1, ...``: its queries by head
    ``q_nope`` [B, H, 192] and ``q_rope`` [B, H, 64] (roped), its would-be
    cache row ``c_kv`` [B, 512] and ``k_r`` [B, 64] — and, in a ``full``
    layer, its index queries [B, 32, 128], weights [B, 32] and index key [B,
    128] (else three ``None``)."""
    cfg = frozen.cfg
    with jax.default_matmul_precision("highest"):
        w = _upcast(w)
        b, eps, theta = x.shape[0], cfg["rms_norm_eps"], \
            cfg["rope_parameters"]["rope_theta"]
        H, dn, dr, dc = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["kv_lora_rank"])
        u = _r(_rms(x, w["input_norm"], eps))
        c_q = _r(_rms(u @ w["q_a"], w["q_a_norm"], eps))
        q = (c_q @ w["q_b"]).reshape(b, H, dn + dr)
        q_nope, q_rope = _r(q[..., :dn]), _r(_rope(q[..., dn:], first, theta))
        kva = u @ w["kv_a"]
        c_kv = _r(_rms(kva[:, :dc], w["kv_a_norm"], eps))
        k_r = _r(_rope(kva[:, None, dc:], first, theta)[:, 0])
        if not full:
            return q_nope, q_rope, c_kv, k_r, None, None, None
        Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
        qi = (c_q @ w["index_q"]).reshape(b, Hi, Di)
        qi = jnp.concatenate([_rope(qi[..., :dr], first, theta),
                              qi[..., dr:]], -1)
        ki = _layer_norm(u @ w["index_k"], w["index_k_norm"],
                         w["index_k_bias"], INDEX_NORM_EPS)
        ki = jnp.concatenate([_rope(ki[:, None, :dr], first, theta)[:, 0],
                              ki[:, dr:]], -1)
        wi = (u @ w["index_w"]) * (Hi ** -0.5 * Di ** -0.5)
        return q_nope, q_rope, c_kv, k_r, _r(qi), wi, _r(ki)


@functools.partial(jax.jit, static_argnames=("topk",))
def _select(first, qi, wi, ki, *, topk):
    """``S_t`` of a block of queries at positions ``first, ...`` against the
    whole sequence's index keys ``ki`` [T, 128]: the positions of the
    ``topk`` largest ``I(t, s)``, ``s <= t``, as ``[B, topk]`` int32 (a row
    with fewer than ``topk`` visible keys lists masked positions too: the
    causal mask takes them out again)."""
    with jax.default_matmul_precision("highest"):
        b, t = qi.shape[0], ki.shape[0]
        nq = min(_INDEX_QUERIES, b)
        assert b % nq == 0, (b, nq)
        kpos = jnp.arange(t)[None, :]

        def sub(c):
            q = jax.lax.dynamic_slice_in_dim(qi, c * nq, nq)
            w = jax.lax.dynamic_slice_in_dim(wi, c * nq, nq)
            per_head = jnp.einsum("qjd,kd->qjk", q, ki)        # [nq, 32, T]
            score = jnp.sum(w[:, :, None] * jnp.maximum(per_head, 0.0), 1)
            qpos = (first + c * nq + jnp.arange(nq))[:, None]
            score = jnp.where(kpos <= qpos, score, -jnp.inf)
            return jax.lax.top_k(score, min(topk, t))[1].astype(jnp.int32)

        return jax.lax.map(sub, jnp.arange(b // nq)).reshape(b, -1)


def _unpacked(bits, t):
    """``[B, >= t / 8]`` uint8 (bit ``s % 8`` of byte ``s // 8``: position
    ``s``) as ``[B, t]`` bool."""
    on = (bits[:, :-(-t // 8), None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return on.reshape(bits.shape[0], -1)[:, :t].astype(bool)


@functools.partial(jax.jit, static_argnames=("topk",))
def _agreement(first, sel, bits, *, topk):
    """Of the keys a block of queries at positions ``first, ...`` selects
    (``sel`` [B, topk]: ``_select``'s, a query's in falling order of score),
    how many a GIVEN selection holds too (``bits``: ``_unpacked``'s form) —
    ``(shared, selected, shared of the leading half, leading half)``, each
    ``[B]``: query ``t`` selects ``min(t + 1, topk)``, and the half of
    ``topk`` with the largest scores lies ``topk / 2`` places clear of the
    ``topk``-th score, where two roundings of one score disagree."""
    qpos = first + jnp.arange(sel.shape[0])[:, None]
    given = (jnp.take_along_axis(bits, sel // 8, 1) >> (sel % 8).astype(
        jnp.uint8)) & 1
    both = (sel <= qpos) & (given == 1)
    n = jnp.minimum(qpos[:, 0] + 1, topk)
    half = topk // 2
    return (jnp.sum(both, 1), n, jnp.sum(both[:, :half], 1),
            jnp.minimum(n, half))


@functools.partial(jax.jit, static_argnames=("frozen",))
def _attend(x, first, q_nope, q_rope, c_kv, k_r, sel, kv_b, wo, *, frozen):
    """``x + [o_h]_h W_o`` for a block of queries at positions ``first, ...``
    against the whole sequence's would-be cache rows (``c_kv`` [T, 512],
    ``k_r`` [T, 64]), each query over its selected positions ``sel`` [B,
    topk] int32 — or a GIVEN selection, ``sel`` uint8 bits (``_unpacked``) —
    (``RECENT``: over the ``topk`` most recent instead). NON-absorbed: a
    group of heads' keys and values are made from ``c_kv`` first."""
    cfg = frozen.cfg
    with jax.default_matmul_precision("highest"):
        b, H, dn = q_nope.shape
        t, dv = c_kv.shape[0], cfg["v_head_dim"]
        kv_b = _r(kv_b.astype(F32)).reshape(-1, H, dn + dv)
        G, qb = min(_HEAD_GROUP, H), min(_QUERY_BLOCK, b)
        assert H % G == 0 and b % qb == 0, (H, G, b, qb)
        kpos = jnp.arange(t)[None, :]
        scale = 1.0 / np.sqrt(dn + q_rope.shape[-1])
        given = sel.dtype == jnp.uint8
        topk = cfg["index_topk"] if given else sel.shape[1]

        def heads(g):
            wg = jax.lax.dynamic_slice_in_dim(kv_b, g * G, G, axis=1)
            kv = jnp.einsum("tc,chn->thn", c_kv, wg)         # [T, G, dn+dv]
            k_nope, v = _r(kv[..., :dn]), _r(kv[..., dn:])

            def queries(c):
                qn = jax.lax.dynamic_slice(q_nope, (c * qb, g * G, 0),
                                           (qb, G, dn))
                qr = jax.lax.dynamic_slice(q_rope, (c * qb, g * G, 0),
                                           (qb, G, q_rope.shape[-1]))
                qpos = (first + c * qb + jnp.arange(qb))[:, None]
                seen = kpos <= qpos
                if RECENT:
                    seen = seen & (kpos > qpos - topk)
                else:
                    rows = jax.lax.dynamic_slice_in_dim(sel, c * qb, qb)
                    seen = seen & (_unpacked(rows, t) if given else
                                   jnp.zeros((qb, t), bool).at[
                                       jnp.arange(qb)[:, None], rows]
                                   .set(True))
                att = (jnp.einsum("qhd,khd->hqk", qn, k_nope) +
                       jnp.einsum("qhd,kd->hqk", qr, k_r)) * scale
                att = jnp.where(seen, att, -jnp.inf)
                return jnp.einsum("hqk,khd->qhd",
                                  _r(jax.nn.softmax(att, -1)), v)

            return jax.lax.map(queries, jnp.arange(b // qb))  # [nb, qb, G, dv]

        o = jax.lax.map(heads, jnp.arange(H // G))      # [H/G, nb, qb, G, dv]
        o = o.reshape(H // G, b, G, dv).transpose(1, 0, 2, 3) \
            .reshape(b, H * dv)
        return x + _r(o) @ _r(wo.astype(F32))


@jax.jit
def _swiglu(u, gate, up, down):
    with jax.default_matmul_precision("highest"):
        gate, up, down = (_r(m.astype(F32)) for m in (gate, up, down))
        u = _r(u)
        return _r(jax.nn.silu(u @ gate) * (u @ up)) @ down


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "scale"))
def _route(u, router, bias, *, top_k, norm, scale):
    """Gate of every (token, router output): 0 where it is not among the
    token's top-k of ``s + b``. ``[T, E]`` float32."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u.astype(F32) @ router.astype(F32))
        _chosen, idx = jax.lax.top_k(s + bias.astype(F32), top_k)
        val = jnp.take_along_axis(s, idx, -1)
        if norm:
            val = val / (jnp.sum(val, -1, keepdims=True) + 1e-20)
        val = val * scale
        rows = jnp.arange(u.shape[0])[:, None]
        return jnp.zeros_like(s).at[rows, idx].set(val)


@jax.jit
def _experts(u, gates, wg, wu, wd):
    """``sum_e gates[:, e] expert_e(u)`` over the stacked experts, one
    expert's weights upcast at a time."""
    def one(y, e):
        pick = functools.partial(jax.lax.dynamic_index_in_dim, index=e,
                                 axis=0, keepdims=False)
        g = jax.lax.dynamic_index_in_dim(gates, e, 1)            # [T, 1]
        return y + g * _swiglu(u, pick(wg), pick(wu), pick(wd)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(wg.shape[0]))
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


def mlp_branch(u, get: Callable[[str], jax.Array], cfg: Dict, dense: bool,
               n_live=None):
    """``MLP(u)``, and the number of routed (token, choice) pairs of the
    first ``n_live`` positions that met a held expert (0 for a dense
    layer)."""
    if dense:
        return _swiglu(u, *(get(k) for k in DENSE_MLP_KEYS)), 0
    first, count, _width = held_experts(cfg)
    gates = _route(u, get("router"), get("router_bias"),
                   top_k=cfg["num_experts_per_tok"],
                   norm=bool(cfg["norm_topk_prob"]),
                   scale=float(cfg["routed_scaling_factor"]))
    held = gates[:, first:first + count]
    y = _swiglu(u, *(get(k) for k in SHARED_KEYS)) + \
        _experts(u, held, *(get(k) for k in EXPERT_KEYS))
    live = held[:u.shape[0] if n_live is None else n_live] > 0
    return y, int(jnp.sum(live))


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray, n_live=None, selected=None, given=None,
                 agreement=None):
    """The normed last hidden state of the first ``n_live`` positions (all of
    them if ``None``), as blocks of ``BLOCK`` positions, and the held routed
    pairs of those positions summed over the expert layers. ``len(ids)`` is
    the padded length: at most ``BLOCK``, or a whole number of blocks. Only
    the blocks that hold a live position are computed (attention is causal
    and everything else is position-wise: what follows a position cannot
    reach it); the cache rows of the others stay zero behind the mask.
    ``selected``: a list that gets each ``full`` layer's ``S_t`` as ``[T_live,
    topk]`` positions (the tests' hook). ``given``: a selection a ``full``
    layer, ``[>= n_live, >= T / 8]`` uint8 bits (``_unpacked``; what a served
    engine selected: ``GenerationEngine.selected_keys``) — every layer then
    attends THAT and not its own ``S_t``, which is still computed and
    compared with it: ``agreement`` (a list) gets ``_agreement``'s four
    ``[n_live]`` counts a ``full`` layer."""
    t = len(ids)
    n_live = t if n_live is None else n_live
    size = min(BLOCK, t)
    assert t % size == 0, (t, size)
    starts = list(range(0, max(n_live, 1), size))
    embed = get("embed", -1)
    xs = [embed[jnp.asarray(ids[a:a + size])].astype(F32) for a in starts]
    frozen, pairs, eps = _Frozen(cfg), 0, cfg["rms_norm_eps"]
    kinds, mlps = layer_lists(cfg)
    n_rest = t - len(starts) * size
    topk, sels = int(cfg["index_topk"]), None

    def whole(parts):
        """The sequence's rows from the live blocks', zeros behind them."""
        rest = jnp.zeros((n_rest,) + parts[0].shape[1:], F32)
        return jnp.concatenate(list(parts) + [rest])

    for layer in range(cfg["num_hidden_layers"]):
        full = kinds[layer] == "full"
        w = {k: get(k, layer)
             for k in ATTN_KEYS[:-1] + (INDEX_KEYS if full else ())}
        wp = {k: v for k, v in w.items() if k not in ("kv_b", "o")}
        project = functools.partial(_project, w=wp, frozen=frozen, full=full)
        # the would-be cache rows of the whole sequence first (a block's
        # queries are made again when its turn comes: 25 blocks of them at
        # once are 3 GB)
        rows = [project(x, a)[2:] for x, a in zip(xs, starts)]
        c_kv, k_r = whole([r[0] for r in rows]), whole([r[1] for r in rows])
        if full:
            ki = whole([r[4] for r in rows])
            sels = [None if RECENT else
                    _select(a, r[2], r[3], ki, topk=topk)
                    for r, a in zip(rows, starts)]
            if selected is not None and not RECENT:
                selected.append(np.concatenate([np.asarray(s) for s in sels]))
            if given is not None and not RECENT:
                bits = np.zeros((len(starts) * size, -(-t // 8)), np.uint8)
                mine = given[kinds[:layer + 1].count("full") - 1][:n_live]
                bits[:len(mine), :mine.shape[1]] = mine[:, :bits.shape[1]]
                bits[len(mine):, 0] = 1   # a padded row attends position 0
                bits = [jnp.asarray(bits[a:a + size]) for a in starts]
                if agreement is not None:
                    both = [_agreement(a, s, b, topk=topk)
                            for a, s, b in zip(starts, sels, bits)]
                    agreement.append(tuple(
                        np.concatenate([np.asarray(p[i]) for p in both])
                        [:n_live] for i in range(4)))
                sels = bits
        del rows
        for i, a in enumerate(starts):
            q_nope, q_rope = project(xs[i], a)[:2]
            sel = jnp.zeros((size, min(topk, t)), jnp.int32) \
                if sels[i] is None else sels[i]
            x = _attend(xs[i], a, q_nope, q_rope, c_kv, k_r, sel, w["kv_b"],
                        w["o"], frozen=frozen)
            y, n = mlp_branch(
                _norm(x, get("post_attn_norm", layer), eps=eps),
                functools.partial(get, layer=layer), cfg,
                mlps[layer] == "dense", min(max(n_live - a, 0), size))
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


def logits(get, cfg: Dict, ids: np.ndarray, vocab_slices: int = 1,
           selected=None):
    """``[T, vocab]`` float32 logits of one full forward."""
    ys, _n = final_hidden(get, cfg, ids, selected=selected)
    head = get("head", -1)
    return jnp.concatenate([
        jnp.concatenate([_head_slice(y, head, lo, size=size) for lo, size in
                         _vocab_slices(cfg["vocab_size"], vocab_slices)], -1)
        for y in ys])


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 8, with_pairs: bool = False,
                        given=None, agreement=None):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``,
    by one full forward. The sequence is padded at its END to ``pad_to`` (at
    most ``BLOCK``, or a whole number of blocks) so that one compiled shape
    of each piece serves every request. ``with_pairs`` also returns the held
    routed pairs of ``tokens[:-1]``: what a server that emitted
    ``tokens[-1]`` last has routed to the experts it holds. ``given`` /
    ``agreement``: ``final_hidden``'s (a selection to attend, and how far the
    reference's own agrees with it)."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    ys, pairs = final_hidden(get, cfg, ids, n - 1, given=given,
                             agreement=agreement)
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
