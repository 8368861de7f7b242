"""Plain reference for dots3-note (HF ``model_type`` ``dots3_note``;
``dots-studio/dots3-note-prev``, ``config.json``; the language model alone):
latent attention (MLA) of two kinds in pre-norm blocks — ``full_attention``
layers under a learned sparse attention (a lightning indexer in every one) and
``sliding_attention`` layers with their own ranks, heads and row width — a
sigmoid gate a head on the attention output of both, a leading dense layer,
then routed experts beside a shared one.

``h`` = ``hidden_size`` 5120, RMSNorm eps ``rms_norm_eps`` 1e-5, ``hidden_act``
silu, no biases, untied head. With ``x`` the residual stream ``[T, h]``, token
``t`` and cached token ``s <= t``:

*Block* (pre-norm; two RMSNorm weights a layer beside the two inside the
attention)::

    x = x + Attn(N_in(x))
    x = x + MLP(N_post(x))

*Full layer* (``layer_types`` ``full_attention``: published layers 0, 1, 5,
9, ..., 45), on ``u = N_in(x)``, ``H`` = 128 heads::

    c_q = RMSNorm(u W_qa)                              # q_lora_rank 1024
    q   = (a_q c_q) W_qb -> per head [q_nope (128) | q_rope (64)]
    q_rope = RoPE(q_rope, t)                           # theta 8e7
    [c (512) | k_r (64)] = u W_kva
    c = a_kv RMSNorm(c) ;  k_r = RoPE(k_r, s)   # ONE head, shared by all 128
    [k_nope_h (128) | v_h (128)] = c W_kvb                       # per head
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_r(s)) / sqrt(192)
    o_h(t) = sum_{s in S_t} softmax_{s in S_t}(score_h(t, .)) v_h(s)
    g   = sigmoid(u W_g)                               # one gate a head
    out = concat_h(g_h o_h) W_o                        # 16384 -> 5120

    qI_j(t) = c_q WI_q[j]     # 64 heads of 128, from the UNSCALED c_q; the
    kI(s)   = LayerNorm(u_s WI_k)  # first 64 dims of both roped (theta 8e7)
    w_j(t)  = (u_t WI_w)_j * 64^-1/2 * 128^-1/2
    I(t, s) = sum_j w_j(t) * relu(qI_j(t) . kI(s))
    S_t     = the index_topk (2048) tokens s <= t of largest I(t, s)
              (every s <= t while t + 1 <= 2048)

*Window layer* (``sliding_attention``, three of every four): the same with the
``swa_`` sizes — q rank 1024, kv rank 1024, 64 heads of 192 + 64, values 128,
RoPE theta 5e4, scale ``(192 + 64)^-1/2``, 64 gates — no indexer, and ``S_t =
{s : t - 512 <= s <= t}`` (``sliding_window_size`` 513 counts the query's own
position).

``a_q = (h / q_lora_rank)^1/2`` and ``a_kv = (h / kv_lora_rank)^1/2`` of the
layer's kind when ``apply_mla_qkv_lora_rescale`` is true, applied to the
normed latents where they enter ``W_qb`` and ``W_kvb``; ``k_r`` is not
scaled. A full layer's cache holds ``[c | k_r]`` (576 values a token) and
``kI`` (128), a window layer's ``[c | k_r]`` (1088). The served model computes
the *absorbed* form (equal in exact arithmetic); this file the NON-absorbed
one, dense over every key under a mask (the selection's, or the window's).

*MLP.* Layer 0: SwiGLU at ``intermediate_size`` 13824. Layers 1-45, on ``v =
N_post(x)``::

    s   = sigmoid(float32(v) W_r)                 # 256 scores, float32
    idx = top8(s + b)           # b: the noaux_tc correction, selection only
    g   = routed_scaling_factor (1) * s[idx] / (sum s[idx] + 1e-20)
    y   = sum_k g_k E_{idx_k}(v) + E_shared(v)
    E(v) = (silu(v W_g) * (v W_u)) W_d            # moe_intermediate_size 1536

*Head*: final RMSNorm, ``lm_head``.

Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``
— no cache, no pages, no kernels, no batching. It computes in BLOCKS so that a
67 k-token sequence at the published widths fits one chip beside the served
model's bfloat16 weights (``BLOCK`` positions of everything position-wise at a
time, a layer's weights upcast where they are used, a sub-block of queries'
index scores and a group of heads' attention scores at a time, one expert at a
time, the head a slice of the vocabulary at a time); only the blocks that hold
a live position are computed.

A SHARE of the model: ``cfg`` says which experts are held
(``n_routed_experts`` from router output ``held_experts_first``; the router
keeps its ``router_experts`` outputs) and which published layers
(``num_hidden_layers`` from ``layer_offset``). What the experts that are not
held would have added is left out.

Departures from the published description: none in the layer. ASSUMED (the
published ``config.json`` does not say): the rescale factor — the config gives
one boolean; the factor is the published convention of the one public family
with such a switch (LongCat-Flash's ``mla_scale_q_lora`` /
``mla_scale_kv_lora``: ``(hidden / rank)^1/2``); the pre-norm placement; RoPE
in the interleaved form (dims ``2i, 2i + 1`` a pair), as ``glm_moe_dsa``; that
``sliding_window_size`` counts the query's own position; the indexer beyond
``index_n_heads`` / ``index_head_dim`` / ``index_topk`` (``kI``'s LayerNorm
with weight, bias and eps 1e-6, the first 64 dims roped, the weights' scale,
ties to the earlier position: DeepSeek Sparse Attention's published inference
code); no ``n_group`` (one group); weights stored ``[in, out]``, ``W_qb`` and
``W_kvb`` head-major. LEFT OUT: the vision tower, the audio encoder and the
multi-token-prediction module, which have no keys in this configuration.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

ATTN_KEYS = ("input_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o", "g", "post_attn_norm")
INDEX_KEYS = ("index_q", "index_k", "index_k_norm", "index_k_bias", "index_w")
DENSE_MLP_KEYS = ("gate_w", "up_w", "down_w")
SHARED_KEYS = ("shared_gate", "shared_up", "shared_down")
EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")

BLOCK = 2048          # positions a position-wise piece takes at a time
_INDEX_QUERIES = 64   # queries whose [64, T] per-head index scores are alive
_QUERY_BLOCK = 512    # queries whose [G, T] attention scores are alive
_HEAD_GROUP = 8       # heads whose keys and values [T, G, .] are alive
INDEX_NORM_EPS = 1e-6

# The check's controls (PERF.md section 6), set before the first call by a
# control run alone. ``ROUND``: a function every matmul operand and the
# would-be cache rows (``[c | k_r]`` of both kinds, ``kI``) pass through, e.g.
# ``lambda x: jax.lax.reduce_precision(x, 8, 3)``; ``None``: float32 as
# described. ``NO_WINDOW``: the window layers see every earlier key.
ROUND = None
NO_WINDOW = False


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
    """``(attention kinds, mlp kinds)`` of the layers ``cfg`` holds:
    ``full`` / ``window`` and ``dense`` / ``sparse``."""
    lo = int(cfg.get("layer_offset") or 0)
    held = range(lo, lo + cfg["num_hidden_layers"])
    return (["window" if cfg["layer_types"][i] == "sliding_attention"
             else "full" for i in held],
            ["dense" if i < cfg["first_k_dense_replace"] else "sparse"
             for i in held])


def dims(cfg: Dict, kind: str):
    """``(H, dn, dr, dv, dc, theta, a_q, a_kv)`` of a layer kind."""
    pre = "swa_" if kind == "window" else ""
    dq, dc = cfg[pre + "q_lora_rank"], cfg[pre + "kv_lora_rank"]
    on = bool(cfg["apply_mla_qkv_lora_rescale"])
    h = cfg["hidden_size"]
    return (cfg[pre + "num_attention_heads"], cfg[pre + "qk_nope_head_dim"],
            cfg[pre + "qk_rope_head_dim"], cfg[pre + "v_head_dim"], dc,
            float(cfg[pre + "rope_theta"]),
            float(np.sqrt(h / dq)) if on else 1.0,
            float(np.sqrt(h / dc)) if on else 1.0)


def _upcast(w):
    return {k: v.astype(F32) if "norm" in k or k.endswith("bias")
            else _r(v.astype(F32)) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _project(x, first, w, *, frozen, kind):
    """A block of positions ``first, first + 1, ...`` in a layer of ``kind``:
    its queries by head ``q_nope`` [B, H, dn] and ``q_rope`` [B, H, dr]
    (roped), its would-be cache row ``c`` [B, dc] (rescaled) and ``k_r`` [B,
    dr], its gates [B, H] — and, in a ``full`` layer, its index queries [B,
    64, 128], weights [B, 64] and index key [B, 128] (else three ``None``)."""
    cfg = frozen.cfg
    with jax.default_matmul_precision("highest"):
        w = _upcast(w)
        b, eps = x.shape[0], cfg["rms_norm_eps"]
        H, dn, dr, _dv, dc, theta, a_q, a_kv = dims(cfg, kind)
        u = _r(_rms(x, w["input_norm"], eps))
        c_q = _rms(u @ w["q_a"], w["q_a_norm"], eps)
        q = (_r(a_q * c_q) @ w["q_b"]).reshape(b, H, dn + dr)
        q_nope, q_rope = _r(q[..., :dn]), _r(_rope(q[..., dn:], first, theta))
        kva = u @ w["kv_a"]
        c = _r(a_kv * _rms(kva[:, :dc], w["kv_a_norm"], eps))
        k_r = _r(_rope(kva[:, None, dc:], first, theta)[:, 0])
        gate = jax.nn.sigmoid(u @ w["g"])
        if kind != "full":
            return q_nope, q_rope, c, k_r, gate, None, None, None
        Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
        qi = (_r(c_q) @ w["index_q"]).reshape(b, Hi, Di)
        qi = jnp.concatenate([_rope(qi[..., :dr], first, theta),
                              qi[..., dr:]], -1)
        ki = _layer_norm(u @ w["index_k"], w["index_k_norm"],
                         w["index_k_bias"], INDEX_NORM_EPS)
        ki = jnp.concatenate([_rope(ki[:, None, :dr], first, theta)[:, 0],
                              ki[:, dr:]], -1)
        wi = (u @ w["index_w"]) * (Hi ** -0.5 * Di ** -0.5)
        return q_nope, q_rope, c, k_r, gate, _r(qi), wi, _r(ki)


@functools.partial(jax.jit, static_argnames=("topk",))
def _select(first, qi, wi, ki, *, topk):
    """``S_t`` of a block of queries at positions ``first, ...`` against the
    whole sequence's index keys ``ki`` [T, 128]: the positions of the
    ``topk`` largest ``I(t, s)``, ``s <= t``, as ``[B, topk]`` int32 (a row
    with fewer than ``topk`` visible keys lists masked positions too: the
    causal mask takes them out again)."""
    with jax.default_matmul_precision("highest"):
        b, t = qi.shape[0], ki.shape[0]
        # the most queries at a time that divide the block
        nq = next(n for n in range(min(_INDEX_QUERIES, b), 0, -1)
                  if b % n == 0)
        kpos = jnp.arange(t)[None, :]

        def sub(c):
            q = jax.lax.dynamic_slice_in_dim(qi, c * nq, nq)
            w = jax.lax.dynamic_slice_in_dim(wi, c * nq, nq)
            per_head = jnp.einsum("qjd,kd->qjk", q, ki)        # [nq, 64, T]
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


@functools.partial(jax.jit, static_argnames=("frozen", "kind"))
def _attend(x, first, q_nope, q_rope, c, k_r, gate, sel, kv_b, wo, *, frozen,
            kind):
    """``x + [g_h o_h]_h W_o`` for a block of queries at positions ``first,
    ...`` against the whole sequence's would-be cache rows (``c`` [T, dc],
    ``k_r`` [T, dr]). A ``full`` layer: each query over its selected
    positions ``sel`` [B, topk] int32 — or a GIVEN selection, ``sel`` uint8
    bits (``_unpacked``). A ``window`` layer (``sel`` ignored): over the
    ``sliding_window_size`` positions up to its own (``NO_WINDOW``: over every
    earlier one). NON-absorbed, dense over every key under the mask."""
    cfg = frozen.cfg
    with jax.default_matmul_precision("highest"):
        b, H, dn = q_nope.shape
        t, dv = c.shape[0], dims(cfg, kind)[3]
        kv_b = _r(kv_b.astype(F32)).reshape(-1, H, dn + dv)
        G, qb = min(_HEAD_GROUP, H), min(_QUERY_BLOCK, b)
        assert H % G == 0 and b % qb == 0, (H, G, b, qb)
        kpos = jnp.arange(t)[None, :]
        scale = 1.0 / np.sqrt(dn + q_rope.shape[-1])
        given = sel.dtype == jnp.uint8
        window = int(cfg["sliding_window_size"])

        def heads(g):
            wg = jax.lax.dynamic_slice_in_dim(kv_b, g * G, G, axis=1)
            kv = jnp.einsum("tc,chn->thn", c, wg)            # [T, G, dn+dv]
            k_nope, v = _r(kv[..., :dn]), _r(kv[..., dn:])

            def queries(ci):
                qn = jax.lax.dynamic_slice(q_nope, (ci * qb, g * G, 0),
                                           (qb, G, dn))
                qr = jax.lax.dynamic_slice(q_rope, (ci * qb, g * G, 0),
                                           (qb, G, q_rope.shape[-1]))
                qpos = (first + ci * qb + jnp.arange(qb))[:, None]
                seen = kpos <= qpos
                if kind == "window":
                    if not NO_WINDOW:
                        seen = seen & (kpos > qpos - window)
                else:
                    rows = jax.lax.dynamic_slice_in_dim(sel, ci * qb, qb)
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
        o = o.reshape(H // G, b, G, dv).transpose(1, 0, 2, 3)   # [b, H/G, G, dv]
        o = o.reshape(b, H, dv) * gate[:, :, None]
        return x + _r(o.reshape(b, H * dv)) @ _r(wo.astype(F32))


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
    engine selected: ``GenerationEngine.selected_keys``) — a ``full`` layer
    then attends THAT and not its own ``S_t``, which is still computed and
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
    topk = int(cfg["index_topk"])

    def whole(parts):
        """The sequence's rows from the live blocks', zeros behind them."""
        rest = jnp.zeros((n_rest,) + parts[0].shape[1:], F32)
        return jnp.concatenate(list(parts) + [rest])

    for layer in range(cfg["num_hidden_layers"]):
        kind = kinds[layer]
        full = kind == "full"
        w = {k: get(k, layer)
             for k in ATTN_KEYS[:-1] + (INDEX_KEYS if full else ())}
        wp = {k: v for k, v in w.items() if k not in ("kv_b", "o")}
        project = functools.partial(_project, w=wp, frozen=frozen, kind=kind)
        # the would-be cache rows of the whole sequence first (a block's
        # queries are made again when its turn comes)
        rows = [project(x, a)[2:] for x, a in zip(xs, starts)]
        c, k_r = whole([r[0] for r in rows]), whole([r[1] for r in rows])
        sels = [None] * len(starts)
        if full:
            ki = whole([r[5] for r in rows])
            sels = [_select(a, r[3], r[4], ki, topk=topk)
                    for r, a in zip(rows, starts)]
            if selected is not None:
                selected.append(np.concatenate([np.asarray(s) for s in sels]))
            if given is not None:
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
            q_nope, q_rope, _c, _k, gate = project(xs[i], a)[:5]
            sel = jnp.zeros((size, 1), jnp.int32) if sels[i] is None \
                else sels[i]
            x = _attend(xs[i], a, q_nope, q_rope, c, k_r, gate, sel,
                        w["kv_b"], w["o"], frozen=frozen, kind=kind)
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
           selected=None, given=None):
    """``[T, vocab]`` float32 logits of one full forward."""
    ys, _n = final_hidden(get, cfg, ids, selected=selected, given=given)
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
