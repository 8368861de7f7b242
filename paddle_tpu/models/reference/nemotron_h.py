"""Plain reference for Nemotron-H (HF ``model_type`` ``nemotron_h``;
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, ``config.json``): a stack
whose every layer is ONE mixer behind one RMSNorm, by the letters of
``hybrid_override_pattern`` — ``M`` Mamba-2, ``E`` routed experts, ``*``
attention.

With ``x`` the residual stream ``[T, hidden]``, ``N`` an RMSNorm at
``layer_norm_epsilon``, weights ``[in, out]``, no bias but the conv's::

    x0 = embed[ids] ;  x <- x + Mixer_l(N_l(x)) ;  logits = N_f(x_L) W_head

    M:  [z | xBC | dt] = u W_in        # inner | inner + 2 groups x state | heads
        xBC = silu(causal_depthwise_conv1d(xBC, k = conv_kernel) + b_conv)
        [x | B | C] = split(inner | groups x state | groups x state)
        dt = softplus(dt + dt_bias) ;  a_h = -exp(A_log_h)     # per head
        S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t (outer) B_t^g   # g = h // (heads / groups)
        y_t = S_t C_t^g + D_h x_t
        out = N_gated(y * silu(z)) W_out   # RMSNorm over each contiguous group
    *:  q, k, v = u W_q, u W_k, u W_v ;  causal softmax at head_dim^-1/2,
        query head h reads K/V head h // (heads / kv_heads) ;  out = ctx W_o
    E:  s = sigmoid(float32(u) W_r) ;  chosen = top-k of s + bias
        g = routed_scaling_factor s_chosen / (sum s_chosen + 1e-20)
        out = sum_e g_e relu(u W_up^e)^2 W_down^e + relu(u W_up^s)^2 W_down^s

Straightforward ``jax.numpy``: float32, ``default_matmul_precision("highest")``,
one full forward over the whole sequence — the Mamba-2 layer BY ITS
RECURRENCE, a token at a time (``lax.scan`` over ``t``: not the chunked form
the system runs), dense causal attention, every expert by a plain loop — no
kernels, no cache, no chunks, no batching. A layer's weights are upcast when
it runs, the attention a group of heads at a time and the head a slice of the
vocabulary at a time, so that the published widths fit one chip beside the
served model's own weights.

Assumed (the published ``config.json`` does not say, and no modelling code
was read): the attention applies NO rotary embedding (``rope_theta`` and
``partial_rotary_factor`` are keys this model type does not read: the
positions live in the Mamba-2 layers); ``in_proj``'s columns are ``z | x | B
| C | dt``; the gated norm's groups are contiguous slices of the inner width
(``n_groups`` of them) and its weight multiplies after the normalisation;
``n_group`` / ``topk_group`` 1 mean no group limit in the router (``n_groups``
8 is the mixer's); the conv weight is ``[channels, conv_kernel]`` with tap
``conv_kernel - 1`` on the current position; ``dt`` is not clamped after the
softplus (``time_step_*`` are initialisation keys).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

MAMBA_KEYS = ("norm", "in_w", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "ssm_norm", "out_w")
ATTN_KEYS = ("norm", "q_w", "k_w", "v_w", "o_w")
EXPERT_KEYS = ("norm", "router", "router_bias", "experts_up", "experts_down",
               "shared_up", "shared_down")
KEYS = {"M": MAMBA_KEYS, "*": ATTN_KEYS, "E": EXPERT_KEYS}

_HEAD_GROUP = 2   # query heads whose [T, T] scores are alive at once

# The check's control (benchmark/controls_hybrid.py; PERF.md section 6), set
# before the first call by a control run alone: a function every matmul
# operand passes through, e.g. ``lambda x: jax.lax.reduce_precision(x, 8, 3)``.
ROUND = None


def _r(x):
    return x if ROUND is None else ROUND(x)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _frozen(cfg: Dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def ssm_recurrence(x, dt, a, b, c, d, n):
    """The selective state-space recurrence, one step a position. ``x`` [T,
    heads, P]; ``dt`` [T, heads] (after softplus); ``a`` [heads] (negative);
    ``b``, ``c`` [T, heads, N] (already given to each head); ``d`` [heads].
    Returns ``y`` [T, heads, P] and the state [heads, P, N] after the first
    ``n`` positions (the later ones are a padded tail: they leave it)."""
    def step(s, xs):
        x_t, dt_t, b_t, c_t, live = xs
        s = jnp.where(live, jnp.exp(dt_t * a)[:, None, None] * s +
                      (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :], s)
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x_t

    s0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[-1]), F32)
    s, y = jax.lax.scan(step, s0, (x, dt, b, c, jnp.arange(x.shape[0]) < n))
    return y, s


@functools.partial(jax.jit, static_argnames=("cfgt",))
def _mamba(x, w, n_live, *, cfgt):
    cfg = dict(cfgt)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t, eps = x.shape[0], cfg["layer_norm_epsilon"]
        heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        groups, n, kc = cfg["n_groups"], cfg["ssm_state_size"], \
            cfg["conv_kernel"]
        d_in, gn = heads * p, groups * n
        u = _rms(x, w["norm"], eps)
        z, xbc, dt = jnp.split(_r(u) @ _r(w["in_w"]),
                               [d_in, 2 * d_in + 2 * gn], -1)
        pad = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), F32), xbc])
        conv = sum(pad[j:j + t] * w["conv_w"][:, j] for j in range(kc))
        xs, b, c = jnp.split(jax.nn.silu(conv + w["conv_b"]),
                             [d_in, d_in + gn], -1)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        b, c = (jnp.repeat(m.reshape(t, groups, n), heads // groups, axis=1)
                for m in (b, c))
        y, s = ssm_recurrence(xs.reshape(t, heads, p), dt,
                              -jnp.exp(w["A_log"]), b, c, w["D"], n_live)
        # what a cache holds after n_live positions: the recurrence's state
        # and the conv's last conv_kernel - 1 inputs (and, a head at a time,
        # the log of what is left by then of what the first position wrote:
        # near 0 is a long memory)
        live = (jnp.arange(t) < n_live)[:, None]
        state = {"ssm": s, "conv": jax.lax.dynamic_slice_in_dim(
            pad, n_live, kc - 1, axis=0),
            "log_decay": jnp.sum(jnp.where(live, -dt * jnp.exp(w["A_log"]),
                                           0.0), axis=0)}
        y = y.reshape(t, d_in) * jax.nn.silu(z)
        yg = y.reshape(t, groups, d_in // groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        y = yg.reshape(t, d_in) * w["ssm_norm"]
        return x + _r(y) @ _r(w["out_w"]), state


@functools.partial(jax.jit, static_argnames=("cfgt",))
def _attention(x, w, *, cfgt):
    cfg = dict(cfgt)
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t = x.shape[0]
        nh, kvh, hd = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        u = _r(_rms(x, w["norm"], cfg["layer_norm_epsilon"]))
        q = _r((u @ _r(w["q_w"])).reshape(t, nh, hd))
        k = _r((u @ _r(w["k_w"])).reshape(t, kvh, hd))
        v = _r((u @ _r(w["v_w"])).reshape(t, kvh, hd))
        causal = jnp.tril(jnp.ones((t, t), bool))
        G = min(_HEAD_GROUP, nh // kvh)
        assert (nh // kvh) % G == 0

        def heads(g):   # G query heads of ONE K/V head at a time
            kv = g * G // (nh // kvh)
            att = jnp.einsum(
                "qhd,kd->hqk", jax.lax.dynamic_slice_in_dim(q, g * G, G, 1),
                jax.lax.dynamic_index_in_dim(k, kv, 1, False)) / np.sqrt(hd)
            att = jnp.where(causal, att, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", _r(jax.nn.softmax(att, -1)),
                              jax.lax.dynamic_index_in_dim(v, kv, 1, False))

        o = jax.lax.map(heads, jnp.arange(nh // G))        # [nh/G, T, G, hd]
        o = o.transpose(1, 0, 2, 3).reshape(t, nh * hd)
        return x + _r(o) @ _r(w["o_w"])


@jax.jit
def _relu2(u, up, down):
    with jax.default_matmul_precision("highest"):
        up, down = _r(up.astype(F32)), _r(down.astype(F32))
        return _r(jnp.square(jax.nn.relu(_r(u) @ up))) @ down


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
        rows = jnp.arange(u.shape[0])[:, None]
        return jnp.zeros_like(s).at[rows, idx].set(val * scale), idx


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w.astype(F32), eps)


def experts(x, get: Callable[[str], jax.Array], cfg: Dict, first: int = 0,
            count=None, shared: bool = True):
    """``x + F_E(N(x))`` and the experts the router chose (``[T, top_k]``).
    ``first`` / ``count``: only the routed experts ``[first, first + count)``
    add their part (a chip's share of the layer; all of them by default);
    ``shared``: whether the shared expert adds its."""
    u = _norm(x, get("norm"), eps=cfg["layer_norm_epsilon"])
    gates, chosen = _route(u, get("router"), get("router_bias"),
                           top_k=cfg["num_experts_per_tok"],
                           norm=bool(cfg["norm_topk_prob"]),
                           scale=float(cfg["routed_scaling_factor"]))
    y = _relu2(u, get("shared_up"), get("shared_down")) if shared \
        else jnp.zeros_like(x)
    up, down = get("experts_up"), get("experts_down")
    count = cfg["n_routed_experts"] - first if count is None else count
    for e in range(first, first + count):            # one expert at a time
        y = y + gates[:, e, None] * _relu2(u, up[e - first], down[e - first])
    return x + y, chosen


def final_hidden(get: Callable[[str, int], jax.Array], cfg: Dict,
                 ids: np.ndarray, n=None):
    """The normed last hidden state ``[T, hidden]``, every expert layer's
    choice ``[expert layers, T, top_k]`` and each Mamba-2 layer's state
    ``{"ssm", "conv", "log_decay"}`` after the first ``n`` positions (all by
    default)."""
    x = get("embed", -1)[jnp.asarray(ids)].astype(F32)
    n_live = jnp.int32(len(ids) if n is None else n)
    cfgt, chosen, states = _frozen(cfg), [], []
    for layer, letter in enumerate(cfg["hybrid_override_pattern"]):
        lget = functools.partial(get, layer=layer)
        if letter == "M":
            x, st = _mamba(x, {k: lget(k) for k in MAMBA_KEYS}, n_live,
                           cfgt=cfgt)
            states.append(st)
        elif letter == "*":
            x = _attention(x, {k: lget(k) for k in ATTN_KEYS}, cfgt=cfgt)
        else:
            x, idx = experts(x, lget, cfg)
            chosen.append(np.asarray(idx))
    return _norm(x, get("final_norm", -1), eps=cfg["layer_norm_epsilon"]), \
        np.stack(chosen), states


@functools.partial(jax.jit, static_argnames=("lo", "size"))
def _head_slice(y, head, *, lo, size):
    with jax.default_matmul_precision("highest"):
        return _r(y) @ _r(jax.lax.dynamic_slice_in_dim(
            head, lo, size, axis=1).astype(F32))


def logits(get, cfg: Dict, ids: np.ndarray, vocab_slices: int = 1):
    """``[T, vocab]`` float32 logits of one full forward."""
    y, _chosen, _states = final_hidden(get, cfg, ids)
    head, v = get("head", -1), cfg["vocab_size"]
    size = -(-v // vocab_slices)
    return jnp.concatenate([_head_slice(y, head, lo=lo, size=min(size, v - lo))
                            for lo in range(0, v, size)], -1)


def next_token_logprobs(get, cfg: Dict, tokens: np.ndarray, pad_to: int,
                        vocab_slices: int = 4):
    """``out[i] = log p(tokens[i+1] | tokens[:i+1])`` for ``i < len - 1``, by
    one full forward; beside it the experts chosen at ``tokens[:-1]``
    (``[expert layers, len - 1, top_k]``) and each Mamba-2 layer's state after
    ``tokens[:-1]``: what a server that emitted ``tokens[-1]`` last holds (it
    has consumed every token but that one). The sequence is padded at its END
    to ``pad_to`` so that one compiled shape serves every request (attention,
    conv and recurrence are causal, the rest position-wise: padding after a
    position cannot reach it)."""
    n = len(tokens)
    ids = np.zeros(pad_to, np.int32)
    ids[:n] = tokens
    nxt = np.zeros(pad_to, np.int32)
    nxt[:n - 1] = tokens[1:]
    y, chosen, states = final_hidden(get, cfg, ids, n - 1)
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
    return np.asarray(picked - lse)[:n - 1], chosen[:, :n - 1], states
