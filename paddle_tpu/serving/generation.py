"""Continuous batching for causal-LM generation (paged KV cache).

The static-batch decode loop (``GPTForCausalLM.generate``) holds the whole
batch until its slowest sequence finishes, and its KV cache grows one token
per step — a new XLA program per step. Serving inverts both decisions:

- the KV cache is a fixed-size **page pool** ``[num_pages, page_len, heads,
  dim]`` per layer (``serving.paged_kv``): each sequence holds a page
  *table* instead of a ``max_seq_len`` slot row, requests sharing a system
  prompt share its ref-counted pages through the **prefix cache** (no
  re-prefill), and admission is bounded by pool pages, not worst-case slot
  length;
- each sequence owns a slot only while it is generating — a finished
  sequence releases its slot (and pages) and a queued prompt joins
  mid-flight at the next step boundary; slot-join order is
  **deadline-aware** (earliest deadline first; expired requests shed
  before prefill);
- prefill, decode, and speculative verify are ONE executable family: a
  fixed-shape **window step** that embeds ``W`` tokens per slot, writes
  their K/V through the page tables, attends length-masked against the
  gathered pages, and returns the greedy argmax at every window position.
  ``W=1`` is classic decode; ``W=k+1`` scores a draft model's ``k``
  proposals in one call (speculative decoding — emitted tokens are always
  the target model's own argmaxes, so the output is token-for-token the
  greedy path); ``W=bucket`` prefills a prompt suffix. Every ``W`` comes
  from a closed set, so steady state never retraces.

Greedy decoding (matching ``generate``'s argmax contract).
"""
from __future__ import annotations

import functools
import itertools
import math
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..observability.trace.parts import part, subpart
from ..observability.trace.request_trace import span
from .base import (BadRequest, DeadlineExceeded, EngineBase, EngineClosed,
                   _oom_guard, _tracer)
from .paged_kv import (HostPagePool, PagedKVPool, PoolExhausted, SlotPages,
                       token_blocks)
from .served_model import (Carried, GPTServed, ServedModel, flatten_params,
                           nest_params)
from .speculative import greedy_accept

__all__ = ["GenerationConfig", "GenerationEngine", "flatten_gpt_params",
           "nest_gpt_params"]

_GEN_NO = itertools.count(1)

# EDF fairness bound: a request WITHOUT a deadline is ordered as if due
# this long after arrival, so sustained deadline-bearing traffic can
# delay it by at most the horizon — never starve it. Ordering only;
# shedding still applies to explicit deadlines alone.
_EDF_DEFAULT_HORIZON_S = 300.0

# Size-distribution histograms the online tuner derives serving shapes
# from. Edges must be fine enough that a quantile-cover over bucket
# UPPER bounds still lands near the true p99 (derivation collapses each
# bucket to its upper edge), and identical across every replica so the
# fleet merge is exact.
PROMPT_TOKEN_BUCKETS = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128,
                        192, 256, 384, 512, 768, 1024, 1536, 2048, 4096)
SLOT_BUCKETS = (0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 128)


def _injector():
    from ..distributed.resilience.faults import injector

    return injector()


class GenerationConfig:
    """Page pool + prompt bucket + speculative-decode declaration.

    ``prefill_buckets``: the widths of the one-row prefill programs. A
    prompt longer than the largest is prefilled in chunks of it, so the
    largest bucket is also the most tokens one program writes: what
    ``window_pages`` (the window layers' pool, where the cache has such
    layers) must cover is ``PagedKVPool``'s to say."""

    def __init__(self, max_slots: int = 4, max_seq_len: Optional[int] = None,
                 prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128),
                 max_queue: int = 256, eos_token_id: Optional[int] = None,
                 donate_cache: bool = True, page_len: int = 16,
                 num_pages: Optional[int] = None, prefix_cache: bool = True,
                 draft_model=None, spec_tokens: int = 4,
                 warm_pool_bytes: int = 0, warm_admit_threshold: int = 2,
                 window_pages: Optional[int] = None):
        self.max_slots = int(max_slots)
        self.max_seq_len = max_seq_len  # None: model max_position_embeddings
        self.window_pages = window_pages
        self.prefill_buckets = tuple(sorted({int(b)
                                             for b in prefill_buckets}))
        self.max_queue = int(max_queue)
        self.eos_token_id = eos_token_id
        self.donate_cache = donate_cache
        self.page_len = int(page_len)
        # None: slots' worst case + a couple of cached prefixes' worth
        self.num_pages = num_pages
        self.prefix_cache = bool(prefix_cache)
        self.draft_model = draft_model       # GPTForCausalLM or None
        self.spec_tokens = int(spec_tokens)  # draft proposals per round
        # warm tier: evicted prefix pages spill (int8) to host RAM and
        # restore instead of re-prefilling. 0 = off (the default keeps
        # the device tier bit-exact; int8 restores are approximate KV)
        self.warm_pool_bytes = int(warm_pool_bytes)
        self.warm_admit_threshold = int(warm_admit_threshold)


class _GenRequest:
    __slots__ = ("prompt", "max_new_tokens", "future", "t_submit",
                 "generated", "trace", "t_decode0", "deadline",
                 "pages", "on_token", "logprobs", "want_logprobs")

    def __init__(self, prompt, max_new_tokens, future, t_submit,
                 deadline=None, on_token=None, want_logprobs=False):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.future = future
        self.t_submit = t_submit
        self.deadline = deadline  # absolute monotonic seconds, or None
        self.on_token = on_token  # per-token stream callback, or None
        self.want_logprobs = bool(want_logprobs)
        self.generated: List[int] = []
        self.logprobs: List[float] = []  # behavior logprob per token
        self.trace = None      # request-scoped trace id
        self.t_decode0 = None  # decode-phase start (prefill done)
        self.pages = None      # what it asks of the pool (``PageDemand``)

    def edf_key(self) -> Tuple[float, float]:
        eff = self.deadline if self.deadline is not None \
            else self.t_submit + _EDF_DEFAULT_HORIZON_S
        return (eff, self.t_submit)


class _Slot:
    __slots__ = ("req", "length", "last_token", "t0", "pages", "freed")

    def __init__(self, pages: SlotPages):
        self.freed = 0   # which release of the engine's freed it (0: none)
        self.req: Optional[_GenRequest] = None
        self.length = 0
        self.last_token = 0
        self.t0 = 0.0  # residency start (occupancy track)
        self.pages = pages   # the pool's: its tables, filled and emptied there


class _Admission:
    """A prompt joining a slot: its pages, the window calls of its prefill
    and what the host has not read of them yet."""

    __slots__ = ("slot_no", "req", "chunks", "table", "m", "outs",
                 "carried", "counters", "state")

    def __init__(self, slot_no: int, req: _GenRequest):
        self.slot_no = slot_no
        self.req = req
        self.chunks: Optional[List[Tuple[int, int, int]]] = None  # by _join
        self.table = None     # the slot's page table, on the device
        self.m = 0            # leading pages borrowed from the prefix cache
        self.outs: List[Tuple[Any, Any]] = []  # (next, logprob) a call sent
        # the decode round each call sent carried (None: it carried none),
        # until the host has read it
        self.carried: List[Optional["_Round"]] = []
        # what each call sent counted on the device (``_run_window``'s
        # ``counted``), added to the metrics when THAT call is read
        self.counters: List[List[Dict[str, Any]]] = []
        # a model whose block resumes: the recurrent state the last call
        # sent left the row in (per layer, ``[1, ...]`` arrays on the
        # device), which the next call starts from and is donated
        self.state = None

    @property
    def sent(self) -> bool:
        """Every call of the prefill is dispatched: the last one's output
        holds the prompt's first token."""
        return self.chunks is not None and len(self.outs) == len(self.chunks)


class _Round:
    """One decode round: the requests whose rows it advances, its operands
    on the host and its outputs, unread, on the device."""

    __slots__ = ("rows", "k", "tokens", "lengths", "tables", "nxt", "lp",
                 "counters", "pool_bound")

    def __init__(self, rows, k, tokens, lengths, tables):
        self.rows: List[Tuple[int, _GenRequest]] = rows
        self.k = k            # draft proposals a row (0: classic decode)
        self.tokens, self.lengths, self.tables = tokens, lengths, tables
        self.nxt = self.lp = None
        self.counters: List[Dict[str, Any]] = []
        # it went out ahead with a slot free, because the pool held none of
        # the prompts that waited (``_run_ahead``)
        self.pool_bound = False


def _unread(flying) -> Dict[int, Tuple[_GenRequest, int]]:
    """The rows whose next token ``flying`` — a program dispatched and not
    yet read, or ``None`` — still holds on the device: ``slot -> (request,
    step)``. A round's token (a round of its own, or the one a prefill call
    carried) is cached at the row's length and moves it on: step 1. The first
    token of a prompt whose every call is out is not cached yet: step 0."""
    if flying is None:
        return {}
    if isinstance(flying, _Round):
        return {i: (req, 1) for i, req in flying.rows}
    rnd = flying.carried[-1]
    rows = {} if rnd is None else {i: (req, 1) for i, req in rnd.rows}
    if flying.sent:
        rows[flying.slot_no] = (flying.req, 0)
    return rows


def _served(model_or_cfg) -> ServedModel:
    """The protocol object of a model — or of a bare GPT config, which is
    what the callers that predate the seam (tests, the AOT rehearsal) hand
    the program builders."""
    if isinstance(model_or_cfg, ServedModel):
        return model_or_cfg
    if hasattr(model_or_cfg, "served_model"):
        return model_or_cfg.served_model()
    return GPTServed(model_or_cfg)


def _extract_gpt_params(model):
    """The live weights of a ``GPTForCausalLM`` as the engine's pytree
    (``GPTServed.params``; kept under its old name for
    ``benchmark/rehearse_aot.py``: ROADMAP D14)."""
    return GPTServed(model.config).params(model)


flatten_gpt_params = flatten_params
nest_gpt_params = nest_params


def _build_decode_step(cfg, max_slots: int, max_len: int, donate: bool,
                       label: str):
    """One fixed-shape SLOT-ARENA executable: the served model's embed,
    blocks and head at one token a slot, attending against dense
    ``[S, max_len, nh, hd]`` caches (length-masked), greedy argmax. The
    draft model's decode path — small enough that a dense per-slot arena
    beats paging overhead. Cache buffers are donated so XLA updates in
    place."""
    import jax
    import jax.numpy as jnp

    sm = _served(cfg)
    scale = sm.attn_scale

    def step(params, k_caches, v_caches, tokens, lengths):
        # tokens/lengths: [slots] int32; caches: per-layer [S, max_len, nh, hd]
        S = max_slots
        x = sm.embed(params, tokens[:, None], lengths[:, None])    # [S, 1, h]
        pos = jnp.arange(max_len)
        mask = pos[None, :] <= lengths[:, None]                    # [S, L]
        slot_idx = jnp.arange(S)
        wr = jnp.minimum(lengths, max_len - 1)
        new_k, new_v = [], []
        for p, kc, vc in zip(params["layers"], k_caches, v_caches):
            def attend(q, k1, v1):
                kk = kc.at[slot_idx, wr].set(k1[:, 0])
                vv = vc.at[slot_idx, wr].set(v1[:, 0])
                new_k.append(kk)
                new_v.append(vv)
                logits = jnp.einsum("shd,sLhd->shL", q[:, 0], kk)
                logits = logits.astype(jnp.float32) * scale
                logits = jnp.where(mask[:, None, :], logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
                return jnp.einsum("shL,sLhd->shd", probs, vv)[:, None]

            x, _ = sm.block(p, x, lengths[:, None], attend, None, None)
        logits = sm.head(params, x)[:, 0]                          # [S, vocab]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, new_k, new_v

    from ..jit import persistent_cache

    return persistent_cache.cached_jit(
        step, donate_argnums=(1, 2) if donate else (), label=label)


class _InParts:
    """A served model as a window program calls it, every call under its
    part of the step (``observability.trace.parts``): ``embed`` and ``head``
    under theirs, and the engine's own ``attend`` handed to ``block`` under
    ``cache_write`` — the rows' scatter into the arenas; the attention
    callables inside it are ``attention`` themselves, and the innermost
    part owns an op. What a block does between is the block's to name.
    Wrapped here, once a build, so that the traced ``step`` bodies hold no
    line for it."""

    def __init__(self, sm: ServedModel):
        self._sm = sm
        self.embed = part("embed")(sm.embed)
        self.head = part("head")(sm.head)

    def __getattr__(self, name):
        return getattr(self._sm, name)

    def block(self, p, x, pos, attend, state, valid, **step):
        return self._sm.block(p, x, pos, None if attend is None else
                              part("cache_write")(attend), state, valid,
                              **step)


@part("head")
def _pick(logits):
    """The greedy pick at every position and its behavior logprob — the
    post-training ledger rides it (f32: bf16 logits renormalize poorly and
    these numbers cross processes)."""
    import jax
    import jax.numpy as jnp

    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lf = logits.astype(jnp.float32)
    return nxt, (jnp.max(lf, axis=-1) -
                 jax.scipy.special.logsumexp(lf, axis=-1))


@part("head")
def _last_real(x, n_valid, W: Optional[int] = None):
    """The stream at the last real position of each row's (first ``W``)
    tokens, ``[rows, 1, h]``: a prefill computes its head there alone — an
    admission reads nothing else, and a 256 x 261120 float32 logits tensor
    is 267 MB."""
    import jax.numpy as jnp

    last = jnp.maximum(n_valid - 1, 0)[:, None, None]
    return jnp.take_along_axis(x if W is None else x[:, :W], last, axis=1)


def _latent_query(pad: int):
    """``(q_lat, q_rope) -> q``: every head of a token as one slab against
    the latent arena's rows, padded with ``pad`` zeros to whole lanes."""
    import jax.numpy as jnp

    @part("attention")
    def latent_query(q_lat, q_rope):
        return jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_lat.shape[:-1] + (pad,),
                                      q_lat.dtype)], -1)

    return latent_query


# -- where a window program's keys and values land ----------------------------
# ONE set of helpers for every builder and cache kind: two granularities of one
# write. ``write_rows`` is the scatter of single rows that every program had:
# a decode round, a verify window, a prefill that starts or ends inside a
# page. ``write_pages`` is a one-row prefill's whose ``W`` tokens are exactly
# ``W / page_len`` whole, fresh pages (``_whole_pages``): XLA walks a scatter's
# indices one by one (67 ns a 256-byte row, a fifth of Laguna's carrying call,
# PERF.md section 6, PR 40), so it is handed one index a page, not one a
# (token, head). All of it is the ``cache_write`` part of the step.


def _whole_pages(rows: int, W: int, page_len: int, prefill: bool,
                 paged: bool = False) -> int:
    """Pages a window program writes WHOLE: ``W / page_len`` for a one-row
    prefill of whole pages into a latent or by-layer cache, else 0 (the
    program scatters rows). ``paged``: the ``[pages, page_len, heads, dim]``
    arenas of GPT-2 and Falcon-H1, whose ``attend`` is not this path's. The
    shapes alone decide; that the call STARTS on a page boundary is the
    engine's to keep (``GenerationEngine._chunk_pages``)."""
    return W // page_len if prefill and rows == 1 and not paged \
        and W % page_len == 0 else 0


@part("cache_write")
def pages_of(table, blk):
    """Page ids of blocks ``blk`` ``[rows, n]`` through ``table`` ``[rows,
    B]``: blocks past the table (or past a request's allocation: table entry
    0) land in the scratch page — never another slot's pages."""
    import jax.numpy as jnp

    B = table.shape[1]
    pidx = jnp.take_along_axis(table, jnp.minimum(blk, B - 1), axis=1)
    return jnp.where(blk < B, pidx, 0)


@part("cache_write")
def flat_rows(table, pos, PL: int):
    """Rows of a ``[P, PL, ...]`` arena (K/V pages of whole tokens, latent
    rows) seen flat, one a token at ``pos`` ``[rows, n]``."""
    return (pages_of(table, pos // PL) * PL + pos % PL).reshape(-1)


@part("cache_write")
def flat_kv(table, pos, PL: int, kvh: int):
    """Rows of a ``[P, kvh, PL, hd]`` arena seen flat, one a (token, K/V
    head): a page is ``[kv heads, PL, dim]``, so token (page, offset) of
    head g is row ``(page * kvh + g) * PL + offset``."""
    import jax.numpy as jnp

    return (((pages_of(table, pos // PL)[..., None] * kvh + jnp.arange(kvh))
             * PL + (pos % PL)[..., None]).reshape(-1))


@part("cache_write")
def chunk_pages(table, lengths, n: int, PL: int):
    """The ``n`` page ids of a one-row call that starts at ``lengths`` ``[1]``,
    a page boundary. Blocks past the allocation are the scratch page, several
    of a call's maybe: harmless, it is never read unmasked."""
    import jax.numpy as jnp

    return pages_of(table, lengths[:, None] // PL + jnp.arange(n)).reshape(-1)


def chunk_where(table, lengths, pos, n_pages: int, PL: int, kvh: int = 0):
    """Where a window's tokens land, as their write takes it: the ``n_pages``
    page ids of a program that writes whole pages (``write_pages``), else a
    flat row a token (``kvh`` 0: a ``[P, PL, ...]`` arena) or a (token, K/V
    head) (``write_rows``)."""
    if n_pages:
        return chunk_pages(table, lengths, n_pages, PL)
    return flat_kv(table, pos, PL, kvh) if kvh else flat_rows(table, pos, PL)


def write_rows(arena, idx, rows, lead: int = 2):
    """``rows`` at rows ``idx`` ``[n]`` of the arena seen flat over its first
    ``lead`` axes: 2 for ``[P, PL, ...]`` (a row a token), 3 for ``[P, kvh,
    PL, hd]`` (a row a (token, K/V head), ``flat_kv``)."""
    row = arena.shape[lead:]
    return arena.reshape((-1,) + row).at[idx].set(
        rows.reshape((-1,) + row)).reshape(arena.shape)


def write_pages(arena, page_ids, toks):
    """The ``n * PL`` tokens ``toks`` of whole pages — latent rows ``[..,
    DL]`` for an arena ``[P, PL, DL]``; keys or values ``[.., kvh, hd]``,
    re-laid once to ``[n, kvh, PL, hd]``, for an arena ``[P, kvh, PL, hd]`` —
    at ``page_ids`` ``[n]``: what ``write_rows`` leaves at those positions."""
    import jax.numpy as jnp

    if arena.ndim == 3:
        return arena.at[page_ids].set(toks.reshape((-1,) + arena.shape[1:]))
    _P, kvh, PL, hd = arena.shape
    return arena.at[page_ids].set(
        jnp.swapaxes(toks.reshape(-1, PL, kvh, hd), 1, 2))


def _program_name(label: str, carries: bool = False) -> str:
    """A window program's jitted name, from the label it already has
    (``serving:<engine>:prefill2048`` -> ``pt_prefill2048``, ``_carry`` where
    it carries a round): what a device trace's ``XLA Modules`` line calls
    it, ``jit_pt_prefill2048_carry``."""
    tail = "".join(c if c.isalnum() else "_" for c in label.rsplit(":", 1)[-1])
    return f"pt_{tail}" + ("_carry" if carries else "")


def _attention(sm, layout, attends: Optional[Dict], name: str):
    """The jitted attention callable ``name`` of a window program of ``sm``,
    whose cache is ``layout`` (``paged_kv.CacheLayout``):
    ``"paged"`` (K/V arenas), ``"latent"`` (and ``"latent_window"``, the
    window layers' of a latent cache of two layer kinds), the
    ``"index_select"`` and ``"sparse"`` of a latent cache with an index row
    (``_Sparse``), or the ``"full"`` / ``"window"`` of a cache of two layer
    kinds. ONE jitted callable for every layer of a program: the kernel is
    traced and lowered once a program and called L times (the 36 kernel
    traces of a GPT-2-large program were most of warmup's time, PERF.md
    section 6, PR 28); XLA inlines the calls. And ONE for every program that
    is handed the same ``attends`` dict (an engine hands all of its programs
    one): ``jax.jit`` caches a trace by the callable and its operands'
    shapes, so the program that carries a round finds the round's shape
    traced by the decode program (a Pallas kernel's body costs 0.7–1.4 s to
    trace a shape on the chip's host, PERF.md section 2). ``None``: the
    program keeps its own. Every call of the callable is the ``attention``
    part of the step (``observability.trace.parts``): the kernel, and in the
    program that carries a round the slices and the join around its two
    calls."""
    import jax

    if attends is not None and name in attends:
        return attends[name]
    scale = sm.attn_scale
    attention = part("attention")
    if name == "paged":
        from ..kernels.pallas.paged_attention import paged_attention

        @jax.jit
        def paged_attend(q, kk, vv, tables, pos):
            return paged_attention(q, kk, vv, tables, pos, scale=scale)

        fn = attention(paged_attend)
    elif name == "latent":
        from ..kernels.pallas.mla_paged_attention import mla_paged_attention

        dv = layout.rows["full"].value_dim

        @jax.jit
        def latent_attend(q, arena, tables, lengths):
            return mla_paged_attention(q, arena, tables, lengths, dv=dv,
                                       scale=scale)

        fn = attention(latent_attend)
    elif name == "latent_window":
        # a latent cache's window layers: their own row (its value width and
        # softmax scale) and the walk from the window's first block
        from ..kernels.pallas.mla_paged_attention import mla_paged_attention

        own = layout.rows["window"]
        dv, window = own.value_dim, layout.window
        own_scale = float(scale if own.scale is None else own.scale)

        @jax.jit
        def latent_window_attend(q, arena, tables, lengths):
            return mla_paged_attention(q, arena, tables, lengths, dv=dv,
                                       scale=own_scale, window=window)

        fn = attention(latent_window_attend)
    elif name == "index_select":
        import jax.numpy as jnp

        from ..kernels.pallas.dsa_index import (NEG, dsa_index_scores,
                                                exact_topk_bias)

        topk = layout.index.topk

        @jax.jit
        def index_select(qi, wi, index_arena, tables, lengths, live):
            # the window's index scores against the paged index keys, then
            # the exact top-k of each token's as the bias the selected
            # attention reads, and how many keys the LIVE tokens selected
            W = qi.shape[1]
            scores = dsa_index_scores(qi, wi, index_arena, tables, lengths)
            bias, n = exact_topk_bias(scores[:, :W], topk,
                                      jnp.max(lengths) + W)
            rest = ((0, 0), (0, scores.shape[1] - W), (0, 0))
            return (jnp.pad(bias, rest, constant_values=NEG),
                    jnp.sum(jnp.where(live, n, 0)))

        fn = attention(subpart("indexer")(index_select))
    elif name == "sparse":
        from ..kernels.pallas.mla_sparse_attention import mla_sparse_attention

        dv = layout.rows["full"].value_dim

        @jax.jit
        def sparse_attend(q, arena, tables, lengths, bias):
            return mla_sparse_attention(q, arena, tables, lengths, bias,
                                        dv=dv, scale=scale)

        fn = attention(sparse_attend)
    else:
        from ..kernels.pallas.ranged_paged_attention import \
            ranged_paged_attention

        window = None if name == "full" else layout.window
        # window tokens a row -> query heads a K/V head, noted as a shape is
        # traced (the query's own shape says how many heads the layer has):
        # what ``_count_walk`` needs to ask the kernel's chooser for the tiles
        # of the calls it dispatches
        walks: Dict[int, int] = {}

        @jax.jit
        def ranged_attend(q, kk, vv, table, lengths):
            walks[q.shape[1]] = q.shape[2] // kk.shape[1]
            return ranged_paged_attention(q, kk, vv, table, lengths,
                                          window=window, scale=scale)

        fn = attention(ranged_attend)
        fn.walks = walks
    if attends is not None:
        attends[name] = fn
    return fn


class _Sparse:
    """What a latent cache that declares an index row (``layout.index``: a
    learned sparse attention) adds to a window program. A ``full`` layer's
    ``attend`` gets ``index = (qI, wI, kI)``: the key row lands in the layer's
    index arena (``v_arenas[arena_of[layer]]``: the SAME page table and
    allocator as the latent rows), ``select`` scores the window's tokens
    against the paged index keys and takes each token's exact top-k — as the
    additive bias ``attend`` (``mla_sparse_attention``) reads, and the number
    of keys the live tokens selected — and the program KEEPS both for the
    ``shared`` layers that follow (the layer loop is unrolled Python).
    ``counters`` names what the program hands back beside the model's own."""

    def __init__(self, sm, layout, attends: Optional[Dict], prefill: bool,
                 decode: bool):
        kinds = layout.index.layers
        self.arena_of = {li: n for n, li in enumerate(
            i for i, kind in enumerate(kinds) if kind == "full")}
        # a layer the list names nothing for (``None``: a window layer of a
        # cache of two kinds) selects nothing and attends its own range
        self.selects = [kind is not None for kind in kinds]
        self.select = _attention(sm, layout, attends, "index_select")
        self.attend = _attention(sm, layout, attends, "sparse")
        # a prompt's row, a round's rows, or (a carrying program) both
        self.counters = ("attn_keys_selected_prefill_total",) * prefill + \
            ("attn_keys_selected_decode_total",) * decode


def packed_selection(bias):
    """A selection's additive bias ``[.., L]`` (0 at a selected key) as bits,
    ``[.., L // 8]`` uint8: bit ``s % 8`` of byte ``s // 8`` is key ``s``
    (``np.unpackbits(.., bitorder="little")``)."""
    import jax.numpy as jnp

    on = (bias == 0).reshape(bias.shape[:-1] + (-1, 8))
    return jnp.sum(on * (1 << jnp.arange(8, dtype=jnp.uint8)), -1,
                   dtype=jnp.uint8)


def _counted(counter_names, counted, sparse=None, selected=(), picked=()):
    """A window program's last result: the model's ``program_counters``
    summed over its layers and, with an index row, the keys its live tokens
    selected summed over the layers (``selected``: the layers' scalars, once
    for each of ``sparse.counters``) — and under ``"selection"`` WHICH keys,
    where the program was built to say (``picked``: ``packed_selection`` of
    every "full" layer's)."""
    out = {name: sum((c[name] for c in counted[1:]), counted[0][name])
           for name in counter_names or () if counted}
    if sparse is not None:
        out.update(zip(sparse.counters, map(sum, selected)))
    if picked:
        out["selection"] = list(picked)
    return out


def _build_window_step(served, max_slots: int, n_blocks: int, page_len: int,
                       window: int, donate: bool, label: str,
                       fused: bool = True, prefill: bool = False,
                       carry: int = 0, attends: Optional[Dict] = None,
                       aligned: bool = True, selection: bool = False):
    """The PAGED executable family: embed ``W = window`` tokens per slot
    at positions ``lengths + [0..W)``, run the served model's blocks — each
    block's ``attend(q, k, v)`` writes K/V through the page tables into the
    pool arenas and attends each window token causally against the page
    pool — and return the greedy argmax at every window position.

    One body serves four roles, at two row counts. At ``max_slots`` rows,
    W=1 is the decode step and W=k+1 scores a draft model's k proposals
    (speculative verify); rows whose page table is all-zero write only the
    scratch page. At ONE row, W=bucket prefills a prompt suffix (cold
    prefill is the zero-prefix special case): an admission serves exactly
    one request, so its program has that request's row and no other, and
    touches that request's pages only. The arenas are the whole pool at
    either row count. And THE CARRIED STEP, ``R = carry`` > 0: ONE program
    for a prompt's row of W tokens AND the ``R`` rows of a decode round, so
    the running sequences advance while a prompt is prefilled and the
    layers' weights (the experts' above all) are read once for both. Only a
    one-row prefill of a model whose ``carries_rounds`` is true has one: the
    cache's kernel takes each row's own range of pages, and what recurs
    resumes.

    ``step(params, k_arenas, v_arenas, tables, tokens, lengths,
    n_valid=None, state=None)`` returns ``(next, logprob, k_arenas,
    v_arenas, state)`` — and, for a model that declares ``program_counters``,
    a sixth result ``counters``. Under a carry each of ``tables``,
    ``tokens``, ``lengths`` and ``n_valid`` is a PAIR — the prompt's ``[1,
    ...]`` operand as a prefill takes it, the round's ``[R, ...]`` operand
    as a decode step takes it — and ``next`` / ``logprob`` come back as
    pairs too (``[1, 1]`` and ``[R, 1]``), the counters over all the
    program's tokens, once. Everything position-wise in a block (embedding,
    norms, projections, router, experts) runs ONCE over the ``W + R``
    tokens, one row ``[1, W + R, h]`` with the positions and the ``valid``
    mask of both parts; ``attend`` alone splits it, through two helpers:
    ``land`` writes the chunk's keys and values (or latent rows) through the
    prompt's table and the round's through theirs, ``call`` runs the layer's
    kernel twice (the chunk's shape, the round's) and joins the results. The
    head runs on ``1 + R`` rows. A round row that is idle has ``n_valid`` 0
    and an all-zero table: it costs its grid step and no bytes. For a model
    that keeps recurrent state ``state`` is a pair under a carry as well, in
    and out — the prompt's own row from its previous chunk (``None``: the
    from-zero program) and the slot arenas, both donated: the block of a
    layer that keeps state (``"state"``, or ``"full+state"``: then beside
    its ``attend``) is handed its own of both as a ``served_model.Carried``
    and hands back the chunk's final row and its arenas advanced one step in
    place (``served_model.recur``: the conv and the scan split at ``W`` as
    ``call`` splits an attention, nothing else in the block does); a row of
    the arenas whose ``r_valid`` is 0 — an idle slot, the JOINING one — keeps
    its state and its tail. All of that is Python at trace time (``if R:``,
    ``if R and stateful``): a program that carries nothing traces none of
    it, and one that carries and keeps no state lowers the text it lowered
    before a state could ride. What the arenas and ``tables`` are, by layer,
    is the cache's layout (``paged_kv.CacheLayout``, where the format of
    ``cache_spec`` is described; ``ServedModel.cache_layout``); what each
    kind's ``attend`` takes and returns is the protocol's
    (``serving.served_model``). A layer's rows land through its paging kind's
    table in its arena and its kind's kernel attends them
    (``_attention``); a layer that pages nothing gets ``attend=None``, and
    with nothing paged at all ``tables`` is ``None``. With an index row each
    layer attends the keys its query SELECTED (``_Sparse``); built with
    ``selection`` the program also names them (``counters["selection"]``:
    ``GenerationEngine.selected_keys``, a check's — no program that serves a
    request is built so).

    The window's keys and values (or latent rows) land through
    ``write_rows``, one scatter index a token — but a ONE-ROW prefill whose
    window is whole pages, into a latent or by-layer cache, writes them
    through ``write_pages``, one index a page (``_whole_pages``: the shapes
    decide). ``aligned`` is the caller's word that every prefill call starts
    on a page boundary (``GenerationEngine._aligned``); ``False`` keeps every
    program to rows.

    ``counters`` is the model's ``program_counters`` summed over the layers
    (int32 scalars). ``n_valid`` (``[rows]``: real tokens in each row's
    window) gives the blocks ``valid = arange(W) < n_valid``. A ``prefill``
    program (one fresh sequence a row, ``n_valid`` required) computes the
    head at the last real position only (``[rows, 1]`` outputs): an
    admission reads nothing else, and a 256 x 261120 float32 logits tensor
    is 267 MB. ``state`` is the protocol's (``serving.served_model``):
    ``None`` (from zero) or a row's own in a prefill, which returns the
    rows' FINAL state for the engine to install; the slot arenas in a round,
    donated like the K/V arenas and advanced one step in place; ``step=``
    tells a block that resumes which it holds.

    Attention is the cache kind's kernel (``_attention``): on the TPU the
    Pallas kernel attends straight against the page table (the dense
    ``kc[tables]`` gathered context never materializes), elsewhere its jnp
    reference gathers and attends — ``kernels.registry.resolve`` decides
    as the program is traced. ``fused`` selects nothing: it is accepted,
    as ``True`` only, for ``benchmark/rehearse_aot*.py`` (ROADMAP D14).
    ``attends``: the dict an engine's programs share their jitted attention
    callables in (``_attention``).

    The parts of the step (``observability.trace.parts``: what a device
    trace says of this program) are set OUTSIDE ``step``'s body — the
    protocol's methods once a build (``_InParts``), the attention callables
    (``_attention``), the helpers above — so that the body reads as the
    model step and nothing else (and every program is built under
    ``persistent_cache.in_one_stack_chunk``: PERF.md section 6, PRs 36-37).
    """
    import jax.numpy as jnp

    sm = _InParts(_served(served))
    # (the tables say n_blocks; under a carry a block sees N = W + R tokens)
    R, S, W, PL = int(carry), max_slots, window, page_len
    if R:
        assert not selection, "a carrying program serves requests"
        if not (prefill and S == 1 and sm.carries_rounds):
            raise ValueError(
                "only a one-row prefill of a model whose cache's kernel "
                "takes each row's own range, and whose recurrent state, if "
                "it keeps one, resumes, carries a decode round "
                "(ServedModel.carries_rounds)")
    layout = sm.cache_layout(PL)
    stateful, latent, unpaged = layout.stateful, layout.latent, \
        not layout.paged
    # (keys and values a K/V head's tokens contiguous: the ranged kernel's)
    by_layer = layout.ranged and not latent
    # declared kinds: tables stacked, one a paging kind. ``k_arenas`` /
    # ``v_arenas`` hold the paging layers' arenas alone, ``state`` the layers'
    # that keep a row. By layer: the paging kind whose table and pool it uses
    # (``None``: no pages, no ``attend``) and whether it keeps a row
    kinds, table_kinds = layout.by_layer, layout.table_kinds
    paging, keeping = zip(*layout.keeps)
    counter_names = sm.program_counters
    # a model whose block resumes is told which of its two state conventions
    # a program uses: the slot arenas of a round, or a row's own state
    step_kw = {"step": not prefill} if sm.resumes_state else {}
    if stateful and not prefill and window != 1:
        raise ValueError(
            "a model with recurrent state decodes one token a round: "
            f"no {window}-token window over live state")
    if fused is not True:
        raise ValueError(
            "fused= no longer selects a path: kernels.registry.resolve "
            "decides; for the jnp reference call kernels.pallas."
            "paged_attention.paged_attention(..., impl='reference')")

    sparse = None
    if selection and not layout.index:
        raise ValueError("selection=True: the model's cache_spec declares no "
                         "index row, so nothing is selected")
    if latent:
        # (row width, lanes, kernel, query slab) by paging kind: a window
        # layer's rows may have a width of their own
        lat = {kind: (row.dim, row.width, _attention(
            sm, layout, attends, "latent_window" if kind == "window"
            else "latent"), _latent_query(row.width - row.dim))
            for kind, row in layout.rows.items()}
        if layout.index:
            sparse = _Sparse(sm, layout, attends, prefill,
                             bool(R) or not prefill)
    elif by_layer:
        ranged = {kind: _attention(sm, layout, attends, kind)
                  for kind in table_kinds if layout.layers_of[kind]}
    elif not unpaged:
        paged_attend = _attention(sm, layout, attends, "paged")

    # a one-row prefill of whole pages writes them whole (``write_pages``),
    # every other program its rows (``write_rows``)
    n_pages = _whole_pages(S, W, PL, prefill, not layout.ranged) \
        if aligned else 0
    rows_of = functools.partial(write_rows, lead=3) if by_layer else write_rows
    write = write_pages if n_pages else rows_of
    # a by-layer arena's row is a (token, K/V head), every other's a token
    heads = sm.num_kv_heads if by_layer else 0

    # Under a carry what is a row's own — its table, its length, where its
    # tokens land, what it selected — is a (chunk, round) PAIR, as the
    # operands arrive; ``land`` and ``call`` split the pairs, so the
    # ``attend`` of a cache kind is written once
    def land(arena, where, rows):
        """The window's ``rows`` into ``arena`` at ``where``
        (``chunk_where``). Under a carry the chunk's ``[:, :W]`` as the
        program writes, whole pages where they are whole pages, and the
        round's ``[:, W:]`` as rows, always: the two share no page but the
        scratch one."""
        if not R:
            return write(arena, where, rows)
        return rows_of(write(arena, where[0], rows[:, :W]), where[1],
                       rows[:, W:])

    @part("attention")
    def both(kernel, q, chunk, round_):
        """The chunk's queries ``q[:, :W]`` against the prompt's ``(table,
        start)``, the round's, one a row, against theirs; joined as the
        block handed them in."""
        ctx = kernel(q[:, :W], *chunk)
        r_ctx = kernel(jnp.swapaxes(q[:, W:], 0, 1), *round_)      # [R, 1]
        return jnp.concatenate([ctx, jnp.swapaxes(r_ctx, 0, 1)], 1)

    def call(kernel, q, *own):
        """The layer's kernel, once: ``kernel(q, *own)``. Under a carry
        twice, the chunk's shape and the round's (the same jitted callable,
        ``_attention``), each against its own of every pair (``both``)."""
        return both(kernel, q, *zip(*own)) if R else kernel(q, *own)

    # ``state`` too is a pair under a carry, of a model that keeps one: the
    # prompt's own row from its previous chunk (``None``: from zero) and the
    # slot arenas. A state layer's block gets its own of both (``Carried``)
    # and hands back the chunk's final row and the arenas, advanced
    def states_of(state):
        if not (R and stateful):
            return iter(state or ())
        return (Carried(row, arenas, W) for row, arenas in zip(
            state[0] or itertools.repeat(None), state[1]))

    def state_out(new_state):
        if not stateful:
            return None
        return tuple(map(list, zip(*new_state))) if R else new_state

    def step(params, k_arenas, v_arenas, tables, tokens, lengths,
             n_valid=None, state=None):
        # tables: [S, B] page ids; tokens: [S, W]; lengths: [S] (int32)
        if R:
            # each of them and ``n_valid`` a pair, the prompt's [1, ..] and
            # the round's [R, ..]; ONE row of N = W + R tokens to everything
            # position-wise in a block
            (tokens, r_tokens), (n_valid, r_valid) = tokens, n_valid
            pos = lengths[0][:, None] + jnp.arange(W)              # [1, W]
            r_pos = lengths[1][:, None]                            # [R, 1]
            xpos = jnp.concatenate([pos, r_pos.reshape(1, R)], 1)  # [1, N]
            tokens = jnp.concatenate([tokens, r_tokens.reshape(1, R)], 1)
        else:
            pos = xpos = lengths[:, None] + jnp.arange(W)          # [S, W]
        x = sm.embed(params, tokens, xpos)                         # [S, W, h]
        # (a carrying program makes its mask BEFORE its tokens' places, a
        # row-only one after: the order is the lowered text's, which keys the
        # compile cache)
        if R:
            valid = jnp.concatenate(
                [jnp.arange(W)[None, :] < n_valid[:, None],
                 (r_valid > 0)[None, :]], 1)                       # [1, N]

        def places(table):
            # where the window's tokens land through ``table`` (``land``)
            if not R:
                return chunk_where(table, lengths, pos, n_pages, PL, heads)
            return (
                chunk_where(table[0], lengths[0], pos, n_pages, PL, heads),
                chunk_where(table[1], lengths[1], r_pos, 0, PL, heads))

        if kinds:
            by_kind = {kind: tuple(t[i] for t in tables) if R else tables[i]
                       for i, kind in enumerate(table_kinds)}
            where_of = {kind: places(t) for kind, t in by_kind.items()}
        elif not unpaged:
            where = places(tables)
        if not R:
            valid = None if n_valid is None else \
                jnp.arange(W)[None, :] < n_valid[:, None]          # [S, W]
        new_k, new_v, new_state, counted = [], [], [], []
        held, selected, picked = [None, None], [], []
        arenas, values, states = iter(k_arenas), iter(v_arenas), \
            states_of(state)
        for li, p in enumerate(params["layers"]):
            # a layer that pages nothing (every layer of a cache of kind
            # "none"; a "state" or "none" layer): no arena, no table, and no
            # ``attend``
            kind, keeps = paging[li], keeping[li]
            pages = kind is not None
            kc = next(arenas) if pages else None
            vc = None if latent or not pages else next(values)
            # the layer's own table and places: its kind's, where there are two
            own_where, own_tables = (None, None) if not pages else \
                (where_of[kind], by_kind[kind]) if kinds else (where, tables)

            def attend_latent(q_lat, q_rope, row, index=None):
                # the window's rows land in their pages, then every head of
                # a token rides as one slab against the pages its row's
                # length covers (rows and queries padded to whole lanes)
                dl, DL, latent_attend, latent_query = lat[kind]
                lanes = [(0, 0)] * (row.ndim - 1) + [(0, DL - dl)]
                arena = land(kc, own_where, jnp.pad(row, lanes))
                new_k.append(arena)
                q = latent_query(q_lat, q_rope)
                if sparse is None or not sparse.selects[li]:
                    return call(
                        lambda q, t, at: latent_attend(q, arena, t, at),
                        q, own_tables, lengths)
                if index is not None:     # a "full" layer scores and selects
                    qi, wi, ki = index
                    keys = land(v_arenas[sparse.arena_of[li]], own_where, ki)
                    new_v.append(keys)
                    if R:       # the chunk's tokens, then the round's rows
                        rows = functools.partial(jnp.swapaxes, axis1=0,
                                                 axis2=1)
                        held[:] = zip(
                            sparse.select(qi[:, :W], wi[:, :W], keys,
                                          own_tables[0], lengths[0],
                                          valid[:, :W]),
                            sparse.select(rows(qi[:, W:]), rows(wi[:, W:]),
                                          keys, own_tables[1], lengths[1],
                                          rows(valid[:, W:])))
                    else:
                        held[:] = sparse.select(
                            qi, wi, keys, own_tables, lengths,
                            jnp.ones((S, W), bool) if valid is None
                            else valid)
                    if selection:
                        picked.append(packed_selection(held[0][:, :W]))
                selected.append(held[1])
                return call(
                    lambda q, t, at, bias: sparse.attend(q, arena, t, at,
                                                         bias),
                    q, own_tables, lengths, held[0])

            def attend(q, k1, v1):
                kk = write_rows(kc, where, k1)
                vv = write_rows(vc, where, v1)
                new_k.append(kk)
                new_v.append(vv)
                # key j of the slot's pages is visible iff j <= pos[s, w]
                return paged_attend(q, kk, vv, tables, pos)

            def attend_ranged(q, k1, v1):
                kk = land(kc, own_where, k1)
                vv = land(vc, own_where, v1)
                new_k.append(kk)
                new_v.append(vv)
                return call(lambda q, t, at: ranged[kind](q, kk, vv, t, at),
                            q, own_tables, lengths)

            attend_ranged.kind = attend_latent.kind = kind
            out = sm.block(p, x, xpos, None if not pages else
                           attend_latent if latent else
                           attend_ranged if by_layer else attend,
                           next(states) if keeps and state is not None
                           else None, valid, **step_kw)
            x, st = out[0], out[1]
            if keeps:
                new_state.append(st)
            if counter_names and len(out) > 2 and out[2] is not None:
                counted.append(out[2])
        if prefill:
            # the head at the last real position alone, [S, 1, h] — and at
            # every row of the round a prompt carries, [1, 1 + R, h]
            x = jnp.concatenate([_last_real(x, n_valid, W), x[:, W:]], 1) \
                if R else _last_real(x, n_valid)
        nxt, logp = _pick(sm.head(params, x))       # [S, W], [S, W] f32
        if R:       # ([1, 1], [R, 1]): the prompt's, then the round's
            nxt, logp = ((a[:, :1], a[0, 1:, None]) for a in (nxt, logp))
        out = (nxt, logp, new_k, new_v, state_out(new_state))
        if not counter_names and sparse is None:
            return out
        return out + (_counted(counter_names, counted, sparse,
                               zip(*selected) if R else [selected], picked),)

    donate_argnums = (1, 2, 7) if stateful else (1, 2)
    step.__name__ = _program_name(label, carries=bool(R))

    from ..jit import persistent_cache

    return persistent_cache.cached_jit(
        step, donate_argnums=donate_argnums if donate else (), label=label)


class GenerationEngine(EngineBase):
    """Continuous-batching generation server over any causal LM that
    implements the served-model protocol (``serving.served_model``:
    ``model.served_model()`` — ``GPTForCausalLM``, ``FalconH1ForCausalLM``).

    ::

        eng = GenerationEngine(model, GenerationConfig(max_slots=4))
        eng.start()
        fut = eng.submit(prompt_ids, max_new_tokens=8, deadline_ms=None)
        full = fut.result()          # np.int64 [len(prompt) + generated]
        eng.stats()
        eng.close()

    Requests queue under admission control (``QueueFull`` beyond
    ``max_queue``); a prompt joins the decode batch as soon as a slot AND
    enough KV pages free — it never waits for the running sequences to
    finish. Slot-join order is earliest-deadline-first; requests that
    expire while queued are shed with ``DeadlineExceeded`` before any
    device time is spent. With ``prefix_cache`` on, a prompt whose leading
    page-blocks are already cached reuses those pages and prefills only
    its suffix. With a ``draft_model``, each decode round proposes
    ``spec_tokens`` draft tokens and verifies them in one window-step call
    — output stays token-for-token the target model's greedy path.
    """

    _close_timeout = 60.0  # an in-flight decode batch may take a while

    def __init__(self, model, config: Optional[GenerationConfig] = None,
                 name: Optional[str] = None):
        self.config = config or GenerationConfig()
        super().__init__(name or f"gen#{next(_GEN_NO)}")

        model.eval()  # serving semantics: dropout off
        self.model = model
        sm = self._sm = _served(model)
        self.max_len = int(self.config.max_seq_len or sm.max_positions)
        if self.max_len > sm.max_positions:
            raise ValueError(
                f"max_seq_len {self.max_len} exceeds the model's position "
                f"table ({sm.max_positions})")
        pl = self._pl = self.config.page_len
        # the one parse of the model's ``cache_spec`` — and what such a cache
        # cannot use: refused in words, never switched off silently
        layout = sm.cache_layout(pl)
        asked = {"prefix_cache": self.config.prefix_cache,
                 "draft_model": self.config.draft_model is not None,
                 "warm_pool": self.config.warm_pool_bytes}
        for feature, why in layout.refuses.items():
            if asked.get(feature):
                raise ValueError(why.format(model=type(model).__name__))
        for b in self.config.prefill_buckets:
            if b > self.max_len:
                raise ValueError(
                    f"prefill bucket {b} exceeds max_seq_len {self.max_len}")
        self._params = sm.params(model)
        dtype = self._params["embed"].dtype
        S = self.config.max_slots
        # every prefill call starts on a page boundary: at 0 or behind whole
        # cached blocks (``_join``), then in steps of the largest bucket
        # (``_prefill_chunks``) — where that bucket is whole pages. What a
        # one-row prefill program's page write rests on (``_whole_pages``);
        # with any other bucket list every program scatters rows
        self._aligned = self.config.prefill_buckets[-1] % pl == 0
        # nothing paged: no page table in the programs, admission by slots
        # alone, and ``max_seq_len`` bounds positions only
        self._n_blocks = B = -(-self.max_len // pl) if layout.paged else 0
        # (the allocator wants a usable page beside the scratch one; nobody
        # takes it)
        num_pages = self.config.num_pages if layout.paged else 2
        if num_pages is None:
            # every slot's worst case + two cached prefixes' worth + scratch
            num_pages = S * B + 2 * B + 1
        warm = None
        if self.config.warm_pool_bytes and self.config.prefix_cache:
            warm = HostPagePool(
                capacity_bytes=self.config.warm_pool_bytes,
                admit_threshold=self.config.warm_admit_threshold)
        self._pool = PagedKVPool(
            layout, num_pages, dtype, prefix_cache=self.config.prefix_cache,
            warm_pool=warm, max_slots=S, n_blocks=B,
            window_pages=self.config.window_pages,
            chunk=self.config.prefill_buckets[-1])
        self._layout = layout
        # cross-thread ops the worker must execute (the allocator and
        # the arenas are worker-owned): (fn, Future) pairs — the KV
        # export/install seam the page shipper rides
        self._ops: deque = deque()

        import jax

        donate = self.config.donate_cache and jax.default_backend() != "cpu"
        self._donate = donate
        # a round's tokens are COMMITTED to the arenas' device, as a window
        # program's outputs are: a round that takes its tokens from the
        # round before it on the device (``_send_round``) and one that takes
        # them from the host are then one signature to ``jax.jit``, lowered
        # once (a second lowering of a 36-layer program is seconds)
        self._device = next(iter(jax.tree_util.tree_leaves(
            (self._pool.k, self._pool.state))[0].devices()))
        # and so are the arenas, from the start, as every program hands them
        # back: a program's FIRST call then has the signature of all its
        # later ones (the decode program used to be built twice in every
        # warm-up, the first build never called again). No copy: the
        # committed array is the same buffer
        pool = self._pool
        pool.k, pool.v, pool.state = jax.device_put(
            (pool.k, pool.v, pool.state), self._device)
        # the jitted attention callables, shared by this engine's window
        # programs (``_build_window_step``: a kernel traced at a shape once)
        self._attends: Dict[str, Any] = {}
        self._state_install_fn = None
        self._token_feed_fn = None
        self._decode_no = -1  # rounds dispatched (the decode_fault site's step)
        # (rows, W, prefill) -> compiled window step
        self._windows: Dict[Tuple[int, int, bool], Any] = {}

        # -- speculative decoding (draft model) --------------------------------
        self.spec_k = 0
        self._spec_on = True  # brownout toggle: set_speculative(False)
        if self.config.draft_model is not None:
            import jax.numpy as jnp

            dm = self.config.draft_model
            dm.eval()
            dsm = _served(dm)
            if dsm.state_spec is not None:
                raise ValueError(
                    "a draft model with recurrent state is refused: its "
                    "slot arena is dense K/V only")
            if dsm.vocab_size != sm.vocab_size:
                raise ValueError(
                    f"draft vocab {dsm.vocab_size} != target vocab "
                    f"{sm.vocab_size}")
            if dsm.max_positions < self.max_len:
                raise ValueError(
                    f"draft position table ({dsm.max_positions}) "
                    f"shorter than max_seq_len {self.max_len}")
            self.spec_k = max(1, self.config.spec_tokens)
            self._draft = dm
            self._dparams = dsm.params(dm)
            ddtype = self._dparams["embed"].dtype
            dlen = B * pl
            darena = (S, dlen, dsm.num_kv_heads, dsm.head_dim)
            self._dk = [jnp.zeros(darena, ddtype)
                        for _ in range(dsm.num_layers)]
            self._dv = [jnp.zeros(darena, ddtype)
                        for _ in range(dsm.num_layers)]
            from .. import jit as jit_mod

            dlabel = f"serving:{self.name}:draft_decode"
            self._draft_step = jit_mod._maybe_audit(
                dlabel, _build_decode_step(dsm, S, dlen, donate,
                                           label=dlabel))
            ilabel = f"serving:{self.name}:draft_insert"
            self._dinsert = jit_mod._maybe_audit(
                ilabel, jit_mod.persistent_cache.cached_jit(
                    lambda cache, kv, slot: jax.lax.dynamic_update_slice(
                        cache, kv, (slot, 0, 0, 0)),
                    donate_argnums=(0,) if donate else (), label=ilabel))

        self._slots = [_Slot(self._pool.slot_pages()) for _ in range(S)]
        self._releases = itertools.count(1)   # stamps ``_Slot.freed``
        # in-place weight push (post-training): a pending swap applies at
        # the first ZERO-ACTIVE step boundary — admission pauses while it
        # pends so in-flight requests finish on the version they started
        self._pending_swap = None  # (params_tree, version, Future) or None
        # memory truth: the page pool's K/V bytes (plus the draft model's
        # slot arena) ride in the `memory` provider — the fixed device
        # buffers continuous batching holds
        try:
            from ..observability.memory import register_component

            register_component(f"serving:{self.name}:kv_pages",
                               type(self)._kv_pool_bytes, owner=self)
            if layout.stateful:
                register_component(f"serving:{self.name}:state",
                                   type(self)._state_pool_bytes, owner=self)
        except Exception:
            pass
        # hub families: prefix-cache and speculative-decode truth for the
        # process-wide /metrics surface (per-engine labels)
        try:
            from ..observability import family, histogram

            self._fam_prefix = family("prefix_cache", ("engine", "event"))
            self._fam_spec = family("speculative", ("engine", "event"))
            # time-to-first-token: observed HERE (the replica knows when
            # its first token left prefill), so the fleet's SLO layer can
            # compute TTFT percentiles from merged buckets alone
            self._hist_ttft = histogram("ttft_ms")
            # request-size / occupancy truth for the online tuner: the
            # merged fleet feed of these two histograms is what derives
            # prefill buckets and slot counts (paddle_tpu.tuning.shapes)
            self._hist_prompt = histogram("prompt_tokens",
                                          PROMPT_TOKEN_BUCKETS)
            self._hist_slots = histogram("gen_active_slots", SLOT_BUCKETS)
        except Exception:
            self._fam_prefix = self._fam_spec = self._hist_ttft = None
            self._hist_prompt = self._hist_slots = None
        # slot-occupancy history: (slot, t0, t1, tokens) per residency —
        # the timeline track behind the pd_top occupancy view and the
        # chrome-trace slots:<engine> process
        self._slot_hist: deque = deque(maxlen=512)
        self._residencies = 0
        self._t_start = time.monotonic()
        self.metrics.gauge("slot_occupancy", self.slot_occupancy)
        self.metrics.gauge("kv_headroom", self.kv_headroom)
        self.metrics.gauge("kv_pool_bytes", self._kv_pool_bytes)
        if layout.index or layout.by_layer:
            self.metrics.gauge("kv_pool_bytes_by_kind",
                               self._pool.bytes_by_kind)
        if layout.by_layer:
            self.metrics.gauge("kv_pages_live_by_kind",
                               self._pool.live_pages_by_kind)
        if layout.stateful:
            self.metrics.gauge("state_pool_bytes", self._state_pool_bytes)
        # prefix-cache truth (hits/misses/evictions) rides the snapshot
        # so pd_top / render_snapshot show the warm-tier tuning baseline
        self.metrics.gauge("prefix_cache", self._prefix_cache_stats)

    def _prefix_cache_stats(self) -> Dict[str, Any]:
        trie = self._pool.trie
        if trie is None:
            return {}
        st = trie.stats()
        st["misses"] = st["lookups"] - st["hits"]
        if self._pool.warm is not None:
            st["warm"] = self._pool.warm.stats()
        return st

    # -- executables ----------------------------------------------------------
    def _window(self, rows: int, W: int, prefill: bool = False):
        """The compiled window step for ``rows`` rows of ``W`` tokens:
        ``max_slots`` rows for a decode or verify round, ONE row for a
        prefill (an admission serves one request). Built once per pair; the
        pairs come from the closed set {(S, 1), (S, spec_k+1)} ∪
        {(1, bucket)}, so steady state never retraces."""
        key = (rows, W, prefill)
        fn = self._windows.get(key)
        if fn is None:
            from .. import jit as jit_mod

            role = "prefill" if prefill else "window"
            label = f"serving:{self.name}:{role}{W}"
            fn = jit_mod._maybe_audit(
                label, _build_window_step(
                    self._sm, rows, self._n_blocks, self._pl, W,
                    self._donate, label=label, prefill=prefill,
                    carry=self._carried_rows(W) if prefill else 0,
                    attends=self._attends, aligned=self._aligned))
            self._windows[key] = fn
        return fn

    def _chunk_pages(self, W: int) -> int:
        """Pages the ``W``-token prefill program writes whole (0: it
        scatters rows), as its builder decided from the same facts."""
        return _whole_pages(1, W, self._pl, True, not self._layout.ranged) \
            if self._aligned else 0

    def _carried_rows(self, W: int) -> int:
        """The decode rows the ``W``-token prefill program carries: a whole
        round's, ``max_slots``, in the LARGEST bucket's program of a model
        that qualifies (``ServedModel.carries_rounds``), else none. The
        carrying program takes the row-only program's place, it does not
        stand beside it, and the smaller buckets' programs stay what they
        were: set-up builds as many programs as ever and only one of them
        grew. A draft model's proposals cross the host between two rounds,
        so with one nothing is carried."""
        if W == self.config.prefill_buckets[-1] and self._sm.carries_rounds \
                and not self.spec_k:
            return self.config.max_slots
        return 0

    def warmup(self):
        """Compile the whole steady-state executable set up front (decode,
        speculative verify, every one-row prefill bucket, the token feed,
        draft steps) against the scratch page — a warm replica restarting
        under the persistent cache loads them all from disk with zero fresh
        XLA compiles. What is left of such a start is Python, tracing and
        lowering each program, so the whole of it runs in one chunk of the
        interpreter's stack (``persistent_cache.in_one_stack_chunk``: 6 s
        where it took 8 to 18, PERF.md section 6, PR 37)."""
        from ..jit import persistent_cache

        return persistent_cache.in_one_stack_chunk(self._warmup)

    def _warmup(self):
        import jax
        import jax.numpy as jnp

        S = self.config.max_slots

        def operands(rows, W, prefill, tokens=None):
            if tokens is None:
                tokens = np.zeros((rows, W), np.int32)
                tokens = jnp.asarray(tokens) if prefill else \
                    jax.device_put(tokens, self._device)
            return (jnp.zeros(self._pool.tables_shape(rows), jnp.int32),
                    tokens,
                    jnp.zeros(rows, jnp.int32),
                    np.full(rows, int(prefill), np.int32))

        def scratch(rows, W, prefill, feed=None):
            # a decode round in which no row is valid (its tokens ``feed``);
            # a prefill of one token — with the round it carries, no row of
            # it valid, where it carries one — and the install of its row
            # (slot 0 is free). What the scratch runs counted is dropped:
            # they routed nothing real. Returns (a prefill's [1, 1] token, a
            # round's [S, 1] tokens), None for what the program has not
            carries = prefill and self._carried_rows(W)
            ops = operands(rows, W, prefill, None if carries else feed)
            if carries:
                ops = tuple(zip(ops, operands(S, 1, False, feed)))
            tables, tokens, lengths, n_valid = ops
            # a program's first call is its build: trace, lower, load or
            # compile (``n_valid`` a pair: a carrying program)
            with span("pt.serve.warmup_program",
                      label=f"{'prefill' if prefill else 'window'}{W}",
                      rows=rows + int(carries)):
                nxt, _lp, row, _counted = self._run_window(
                    rows, W, tables, tokens, lengths, n_valid=n_valid,
                    prefill=prefill)
                jax.block_until_ready(nxt)
            if row is not None and self._sm.resumes_state:
                # the same bucket from the state a chunk left (``row`` is
                # donated): the later chunks of a long prompt
                with span("pt.serve.warmup_program",
                          label=f"prefill{W}:resume",
                          rows=rows + int(carries)):
                    nxt, _lp, row, _counted = self._run_window(
                        rows, W, tables, tokens, lengths, n_valid=n_valid,
                        prefill=True, state=row)
                    jax.block_until_ready(nxt)
            if row is not None:
                self._install_state(0, row)
            return nxt if carries else (nxt, None) if prefill else (None, nxt)

        _first, after_round = scratch(S, 1, False)
        if self.spec_k:
            scratch(S, self.spec_k + 1, False)
        for b in self.config.prefill_buckets:
            after_prefill, after_carried = scratch(1, b, True)
        if not self.spec_k:
            # a round that goes out ahead of a read — one of its own, or the
            # one the largest bucket's prefill call carries — takes its
            # tokens from the device: the unread round's own output, or a
            # prompt's first token fed into the host's rows or into that
            # output (``_round_feed``). Every form runs here: were one a
            # signature of its own after all, its lowering would fall here
            # and not on a request
            feeds = [after_round, self._feed_token(
                np.zeros((S, 1), np.int32), after_prefill, 0)]
            if after_carried is not None:
                feeds += [after_carried, self._feed_token(
                    after_carried, after_prefill, 0)]
            for feed in feeds:
                scratch(S, 1, False, feed)
                if after_carried is not None:
                    scratch(1, self.config.prefill_buckets[-1], True, feed)
        if self.spec_k:
            zeros = jnp.zeros(S, jnp.int32)
            _n, self._dk, self._dv = self._draft_step(
                self._dparams, self._dk, self._dv, zeros, zeros)
            # the draft PREFILL path too (its per-bucket insert
            # executables + the draft forward's op set) — slot 0's
            # garbage rows are overwritten at the first real admit
            for b in self.config.prefill_buckets:
                self._draft_prefill(0, np.zeros(b, dtype=np.int64))
        return self

    @property
    def _wbound(self) -> int:
        """Window pages a decoding slot holds at most (a runner reads it)."""
        return self._pool.window_bound

    def _count_slide(self, released: int, taken: int) -> None:
        """Window pages that changed hands (``PagedKVPool.slide``)."""
        if released:
            self.metrics.inc("window_pages_released_total", released)
        if taken:
            self.metrics.inc("window_pages_taken_total", taken)

    @staticmethod
    def _keys_in_window(lo: int, hi: int, window: int) -> int:
        """Keys the queries at positions ``[lo, hi)`` see within ``window``,
        summed: position ``i`` sees ``min(i + 1, window)``."""
        m = min(hi, max(lo, window - 1))
        return (m * (m + 1) - lo * (lo + 1)) // 2 + (hi - m) * window

    def _count_keys(self, full: int, windowed: int,
                    decode: bool = False) -> None:
        """``full``: cached positions a program's queries see, summed;
        ``windowed``: the same within the window. Each kind of layer scored
        its own, once a layer; a decode round's part of the window layers'
        is counted apart too (a round reads its keys once a row, a chunk's
        tokens share theirs: the two have different floors)."""
        layers = self._layout.layers_of
        self.metrics.inc("attn_keys_full_total", full * layers["full"])
        if not self._layout.window:
            return
        self.metrics.inc("attn_keys_window_total",
                         windowed * layers["window"])
        self.metrics.inc(
            f"attn_keys_window_{'decode' if decode else 'prefill'}_total",
            windowed * layers["window"])

    def _count_walk(self, W: int, keys, live=None,
                    decode: bool = False) -> None:
        """What the two kinds' kernel calls of a program dispatched walk:
        ``keys`` cached tokens in front of each row's ``W`` window tokens (one
        number a row), ``live`` which rows hold a sequence (``None``: all —
        the kernel starts no walk for an idle row). The pages the kernel's
        tiles DMA for them (``walk_cost``: every tile of a row walks its
        range again) and the pages that hold a key in range, once a layer of
        the kind; a decode round's part is counted apart too, as its keys
        are. And how often the kernel's pipeline engaged: the idle rows it
        skipped, and the grid steps whose first block was in flight."""
        layout = self._layout
        if layout.latent:
            # a latent cache's window kernel alone walks a range (a full
            # layer attends what it selected, over every visible page): the
            # latent rows its tiles DMA, and the rows inside their windows
            from ..kernels.pallas.mla_paged_attention import window_walk

            heads = layout.rows["window"].heads or self._sm.num_heads
            walked, inside = window_walk(W, heads, self._pl, layout.window,
                                         keys)
            for what, n in (("walked", walked), ("in", inside)):
                self.metrics.inc(f"attn_rows_{what}_window_total",
                                 n * layout.layers_of["window"])
            return
        from ..kernels.pallas.ranged_paged_attention import \
            choose_tiles, walk_cost

        (G, PL, d), itemsize = layout.page, \
            self._params["embed"].dtype.itemsize
        idle = 0 if live is None else len(live) - int(np.count_nonzero(live))
        for kind, layers in layout.layers_of.items():
            shape = (W, self._attends[kind].walks[W], G, PL, d,
                     None if kind == "full" else layout.window)
            cost = walk_cost(len(keys), *shape, keys,
                             choose_tiles(*shape, itemsize), itemsize, live)
            self.metrics.inc("attn_rows_idle_skipped_total", idle * layers)
            self.metrics.inc("attn_steps_prefetched_total",
                             cost["prefetched"] * layers)
            for what in ("walked", "in_range"):
                n = cost["pages" if what == "walked" else "pages_in_range"]
                self.metrics.inc(f"attn_pages_{what}_{kind}_total",
                                 n * layers)
                if decode:
                    self.metrics.inc(
                        f"attn_pages_{what}_{kind}_decode_total", n * layers)

    def _run_window(self, rows: int, W: int, tables, tokens, lengths,
                    n_valid, prefill: bool = False, state=None):
        """Call the ``(rows, W)`` window program on the pool's arenas and
        rebind what it donates. ``n_valid`` is ``[rows]`` int32, host side.
        A ``prefill`` returns its outputs at the last real position only
        and, for a model with recurrent state, starts every layer from
        zero, stops the recurrence at ``n_valid`` and hands back the row's
        FINAL state — or, handed ``state`` (what the prompt's previous chunk
        returned; only a model whose block resumes: it is donated), goes on
        from it; any other round of such a model advances the state
        arenas in place. A model with nothing paged gets no tables. Returns
        ``(next, logprob, row, counted)``; ``row`` is ``None`` but for that
        prefill, and ``counted`` holds the device
        scalars of a model that declares ``program_counters`` (a list of at
        most one dict: what ``_count_programs`` takes once the call is
        done). For a prefill that carries a round (``_carried_rows``) every
        operand after ``W`` is a pair, the prompt's then the round's, and so
        are ``next`` and ``logprob`` — and, made here, the state of a model
        that keeps one: ``state`` beside the slot arenas, which the call
        advances one step in place as a round of its own does."""
        import jax
        import jax.numpy as jnp

        pool, fn = self._pool, self._window(rows, W, prefill)
        # a state model's carrying call: the row's state AND the arenas
        pair = bool(prefill and self._layout.stateful
                    and self._carried_rows(W))
        nxt, lp, pool.k, pool.v, state, *counted = fn(
            self._params, pool.k, pool.v,
            tables if self._layout.paged else None, tokens, lengths,
            jax.tree_util.tree_map(jnp.asarray, n_valid),
            (state, pool.state) if pair else state if prefill
            else pool.state)
        if pair:
            state, pool.state = state
        elif not prefill:
            pool.state, state = state, None
        return nxt, lp, state, counted

    def _count_tokens(self, real: int) -> None:
        """What the ``real`` tokens of a program just dispatched add to the
        served model's ``token_counters``."""
        for name, each in self._sm.token_counters.items():
            self.metrics.inc(name, each * real)

    def _count_programs(self, counted: List[Dict[str, Any]]) -> None:
        """Add what window programs counted to the metrics. The caller hands
        over the scalars of programs whose result it has just read — they
        are done, so this waits for nothing, and above all not for the
        program that went out after them."""
        import jax

        for counters in jax.device_get(counted):  # one transfer for all
            for name, v in counters.items():
                self.metrics.inc(name, int(v))

    def _feed_token(self, tokens, first, slot_no: int):
        """A decode round's ``[max_slots, 1]`` tokens with row ``slot_no``
        taken from ``first``, the ``[1, 1]`` output of a prefill the host
        has not read yet: the round can go out behind the prefill without
        the token crossing the host."""
        import jax

        fn = self._token_feed_fn
        if fn is None:
            from .. import jit as jit_mod

            label = f"serving:{self.name}:token_feed"
            fn = self._token_feed_fn = jit_mod._maybe_audit(
                label, jit_mod.persistent_cache.cached_jit(
                    lambda tokens, first, slot: jax.lax.dynamic_update_slice(
                        tokens, first, (slot, 0)), label=label))
        return fn(tokens, first, np.int32(slot_no))

    def _state_pool_bytes(self) -> int:
        """Bytes held by the slot-indexed recurrent-state arenas."""
        return self._pool.state_bytes()

    def _install_state(self, slot_no: int, row) -> None:
        """Write one row's final prefill state (per layer, ``[1, ...]``
        arrays) over slot ``slot_no``'s row of the state arenas, in place:
        ONE donated program for all layers. The whole row is overwritten,
        so nothing of the slot's previous tenant survives."""
        import jax

        fn = self._state_install_fn
        if fn is None:
            from .. import jit as jit_mod

            def install(arenas, rows, slot):
                return jax.tree_util.tree_map(
                    lambda a, r: jax.lax.dynamic_update_slice(
                        a, r.astype(a.dtype),
                        (slot,) + (0,) * (a.ndim - 1)), arenas, rows)

            label = f"serving:{self.name}:state_install"
            fn = self._state_install_fn = jit_mod._maybe_audit(
                label, jit_mod.persistent_cache.cached_jit(
                    install, donate_argnums=(0,) if self._donate else (),
                    label=label))
        self._pool.state = fn(self._pool.state, row, np.int32(slot_no))

    def slot_state(self, slot_no: int):
        """The recurrent state at one slot's row: per layer, ``{name:
        array}`` (copies on the device). A released slot's row stays as
        its last tenant left it until the next admission overwrites it, so
        on an idle or closed engine this is the FINAL state of the last
        request the slot served — what a check compares with a reference
        (the worker owns the arenas: do not call it under load)."""
        if not self._layout.stateful:
            raise ValueError(f"{type(self.model).__name__} declares no "
                             "recurrent state")
        return [{name: arena[slot_no] for name, arena in layer.items()}
                for layer in self._pool.state]

    def selected_keys(self, tokens) -> List[np.ndarray]:
        """WHICH keys each position of ``tokens`` attends, for a check of a
        learned sparse attention's selection (a latent cache with an index
        row; ``attn_keys_selected_*_total`` count the keys, this names them):
        one ``[len(tokens), Lp // 8]`` uint8 array a ``"full"`` layer, bit
        ``s % 8`` of byte ``s // 8`` of row ``t`` set where position ``t``
        attends position ``s`` (``np.unpackbits(.., bitorder="little")``;
        ``Lp``: the positions a row's page table covers,
        ``dsa_index.padded_context``). ``tokens`` go as ONE prompt through a
        build of the largest prefill bucket's program that also hands its
        selections back — the same ``_build_window_step``, page table, arenas,
        kernels and chunk offsets as a served prompt's, on the caller's thread
        and through pages it takes from the pool as a joining prompt does. So
        only a closed engine may (as ``release_caches``), and what the pool
        held is overwritten."""
        import jax
        import jax.numpy as jnp

        if not self._layout.index:
            raise ValueError("selected_keys: the model's cache declares no "
                             "index row, so nothing is selected")
        if not self._closed or self._thread is not None:
            raise RuntimeError("selected_keys: close() the engine first")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        W, PL, pool = self.config.prefill_buckets[-1], self._pl, self._pool
        # (no block is a key: the prompt shares nothing with the prefix cache)
        demand = pool.demand(tokens, 0)._replace(blocks=[])
        if demand.total > min(self._n_blocks, pool.num_pages - 1):
            raise ValueError(f"selected_keys: {len(tokens)} tokens are past "
                             "max_seq_len or the pool")
        label = f"serving:{self.name}:selection{W}"
        fn = self._windows.get((1, W, "selection"))
        if fn is None:
            fn = self._windows[1, W, "selection"] = _build_window_step(
                self._sm, 1, self._n_blocks, PL, W, self._donate, label=label,
                prefill=True, attends=self._attends, aligned=self._aligned,
                selection=True)
        pages, out = self._slots[0].pages, []   # (every slot is free)
        pool.join(pages, demand)
        for lo in range(0, len(tokens), W):
            chunk = np.zeros((1, W), np.int32)
            n = min(W, len(tokens) - lo)
            chunk[0, :n] = tokens[lo:lo + n]
            pool.slide(pages, lo, lo + n - 1)
            _nxt, _lp, pool.k, pool.v, _state, counted = fn(
                self._params, pool.k, pool.v,
                jnp.asarray(pool.row_tables(pages)), jnp.asarray(chunk),
                jnp.asarray([lo], jnp.int32), jnp.asarray([n], jnp.int32),
                None)
            out.append([np.asarray(a)[0, :n]
                        for a in jax.device_get(counted["selection"])])
        pool.release(pages)
        return [np.concatenate(layer) for layer in zip(*out)]

    def release_caches(self) -> None:
        """Give both caches' device buffers back (K/V arenas, state arenas,
        the draft's slot arena). Only a closed engine may: nothing can be
        served afterwards. For a caller that needs the memory while the
        model's weights stay — the benchmark's float32 reference."""
        if not self._closed or self._thread is not None:
            raise RuntimeError("release_caches: close() the engine first")
        self._pool.k, self._pool.v, self._pool.state = [], [], None
        if self.spec_k:
            self._dk, self._dv = [], []

    def _kv_pool_bytes(self) -> int:
        """Bytes held by the paged K/V pool (all layers), plus the draft
        model's slot arena when speculative decoding is on."""
        n = self._pool.bytes()
        if self.spec_k:
            n += sum(int(c.nbytes) for c in self._dk) + \
                sum(int(c.nbytes) for c in self._dv)
        return n

    # -- submission -----------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 16,
               deadline_ms: Optional[float] = None,
               on_token=None, return_logprobs: bool = False,
               trace_parent: Optional[str] = None) -> "Future":
        """Queue one prompt (1-D int array). The future resolves to the
        full sequence (prompt + generated) as a 1-D np.int64 array. A
        ``deadline_ms`` bounds QUEUE time: expired requests are shed with
        ``DeadlineExceeded`` before prefill, and queued requests join
        slots earliest-deadline-first. ``on_token(t)`` (optional) fires
        once per emitted token IN ORDER, before the future resolves — the
        streaming seam the fleet RPC uses for replay/dedup bookkeeping;
        callbacks run on the engine worker thread and must be cheap.

        ``return_logprobs=True`` makes the future resolve to ``(full_seq,
        logprobs)`` — a float32 array, one behavior logprob per GENERATED
        token (the greedy pick's log-softmax under the weights that
        emitted it) — and calls ``on_token(t, lp)`` with two arguments.
        This is the post-training trajectory ledger: a replayed-after-
        failover request re-derives the same logprobs because greedy
        decoding re-walks the same tokens under the same weights."""
        self.metrics.inc("requests_total")
        fut: Future = Future()
        prompt = np.asarray(prompt_ids)
        if prompt.ndim != 1 or prompt.size == 0 or \
                not np.issubdtype(prompt.dtype, np.integer):
            self.metrics.inc("errors_total")
            fut.set_exception(BadRequest(
                "prompt must be a non-empty 1-D integer array"))
            return fut
        if max_new_tokens < 1:
            self.metrics.inc("errors_total")
            fut.set_exception(BadRequest("max_new_tokens must be >= 1"))
            return fut
        # observed BEFORE the bucket check: the tuner must see the true
        # request-size distribution, rejected oversizes included — a
        # shape that keeps rejecting traffic is exactly what it fixes
        if self._hist_prompt is not None:
            self._hist_prompt.observe(len(prompt))
        if self._prefill_bucket(len(prompt)) is None and \
                ((self._layout.stateful and not self._sm.resumes_state)
                 or self.spec_k):
            # a longer prompt is prefilled in chunks against its own cached
            # pages, or from the state its previous chunk left where the
            # model's block resumes; a recurrent state that cannot (a prefill
            # starts it from zero) and a draft model (its dense arena takes
            # one whole bucket) cannot
            self.metrics.inc("errors_total")
            fut.set_exception(BadRequest(
                f"prompt length {len(prompt)} exceeds the largest prefill "
                f"bucket {self.config.prefill_buckets[-1]}"))
            return fut
        if len(prompt) + max_new_tokens > self.max_len:
            # the model's position table (max_seq_len) cannot address the
            # asked-for continuation (len(out) == len(prompt) +
            # max_new_tokens is part of the contract)
            self.metrics.inc("errors_total")
            fut.set_exception(BadRequest(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len {self.max_len}"))
            return fut
        pages = self._pool.demand(prompt, max_new_tokens)
        if pages.total > self._pool.allocator.usable_pages:
            # paged admission bound: POOL capacity, not slot length — a
            # request that could never hold enough pages is rejected; one
            # that merely has to wait for pages stays queued
            self.metrics.inc("errors_total")
            fut.set_exception(BadRequest(
                f"request needs {pages.total} KV pages; the pool holds "
                f"{self._pool.allocator.usable_pages}"))
            return fut
        t_submit = time.monotonic()
        deadline = None if deadline_ms is None \
            else t_submit + deadline_ms / 1000.0
        req = _GenRequest(prompt.astype(np.int64), int(max_new_tokens), fut,
                          t_submit, deadline, on_token=on_token,
                          want_logprobs=return_logprobs)
        req.pages = pages
        # ``trace_parent`` is the fleet-minted context carried over the
        # submit frame: this engine's spans nest under it when the
        # supervisor's collector merges traces across processes
        tr = _tracer()
        req.trace = tr.start(self.name, kind="generate",
                             parent=trace_parent,
                             prompt_len=len(prompt),
                             max_new_tokens=int(max_new_tokens),
                             deadline_ms=deadline_ms)
        tr.span(req.trace, "admission", req.t_submit, time.monotonic())
        try:
            self._enqueue(req, self.config.max_queue)
        except Exception as e:  # QueueFull/EngineClosed backpressure
            tr.finish(req.trace, ok=False, error=type(e).__name__)
            raise
        return fut

    def _prefill_bucket(self, n: int) -> Optional[int]:
        for b in self.config.prefill_buckets:
            if b >= n:
                return b if b <= self.max_len else None
        return None

    def _prefill_chunks(self, start: int, end: int
                        ) -> List[Tuple[int, int, int]]:
        """The window calls that prefill prompt positions ``[start, end)``:
        ``(lo, hi, W)`` each — whole chunks of the largest bucket, then the
        smallest bucket that holds the rest. One call when the suffix fits
        a bucket (every prompt of a model whose requests stay within the
        buckets)."""
        C = self.config.prefill_buckets[-1]
        chunks = []
        while end - start > C:
            chunks.append((start, start + C, C))
            start += C
        chunks.append((start, end, self._prefill_bucket(end - start)))
        return chunks

    def set_speculative(self, enabled: bool) -> None:
        """Brownout lever: toggle draft-model speculation per decode
        round. Off = classic W=1 decode (already warmed), shedding the
        draft's k dense steps per round under overload. The draft's
        prompt prefill keeps running so a later re-enable stays correct —
        only its proposal quality degrades until its cache catches up
        (the target verifies every token, so output never changes)."""
        self._spec_on = bool(enabled)

    def speculative_enabled(self) -> bool:
        return bool(self.spec_k) and self._spec_on

    # -- in-place weight push (post-training fast path) -----------------------
    def _coerce_swap_state(self, state) -> Dict[str, Any]:
        """Validate an incoming weight set against the live tree and land
        it device-ready. Accepts a model of the served class, the nested
        param pytree, or the flat ``{dotted_name: array}`` wire shape."""
        import jax.numpy as jnp

        if hasattr(state, "served_model"):
            state = state.served_model().params(state)
        if "layers" not in state:
            state = nest_params(dict(state))

        def conv(old, new, path):
            if new is None:
                raise ValueError(f"swap_weights: missing param {path!r}")
            arr = jnp.asarray(np.asarray(new), dtype=old.dtype)
            if arr.shape != old.shape:
                raise ValueError(
                    f"swap_weights: {path!r} shape {arr.shape} != live "
                    f"shape {old.shape}")
            return arr

        if len(state.get("layers", ())) != len(self._params["layers"]):
            raise ValueError(
                f"swap_weights: {len(state.get('layers', ()))} layers != "
                f"live {len(self._params['layers'])}")
        new = {k: conv(v, state.get(k), k)
               for k, v in self._params.items() if k != "layers"}
        new["layers"] = [
            {k: conv(v, state["layers"][i].get(k), f"layers.{i}.{k}")
             for k, v in L.items()}
            for i, L in enumerate(self._params["layers"])]
        return new

    def swap_weights(self, state, version: Optional[int] = None,
                     timeout: Optional[float] = None) -> int:
        """Replace the TARGET model's served weights in place — the
        weight-push fast path (seconds, not a respawn). The swap is
        staged and applied by the worker at the first step boundary with
        zero active slots: admission pauses while it pends, so every
        in-flight request finishes bit-identically on the weight version
        it started on, and the first request admitted afterwards runs
        the new version. The prefix cache is dropped at the boundary
        (old-version KV pages are garbage under new weights). The draft
        model keeps its weights — it only PROPOSES; the swapped target
        verifies every token, so output correctness is version-pure
        (only acceptance rate can drift). Returns the new
        ``weight_version`` once applied."""
        params = self._coerce_swap_state(state)
        fut: Future = Future()
        with self._cond:
            if self._closed:
                raise EngineClosed("engine closed")
            if self._pending_swap is not None:
                raise RuntimeError("a weight swap is already pending")
            ver = int(version) if version is not None \
                else self.weight_version + 1
            self._pending_swap = (params, ver, fut)
            self._cond.notify_all()
            started = self._thread is not None
        if not started:
            self._apply_swap()  # no worker: nothing in flight to drain
        return fut.result(timeout=120.0 if timeout is None else timeout)

    def _apply_swap(self) -> None:
        """Land the staged weights (worker thread at a zero-active
        boundary, or inline when no worker runs)."""
        with self._cond:
            pend, self._pending_swap = self._pending_swap, None
        if pend is None:
            return
        params, ver, fut = pend
        try:
            self._params = params
            trie = self._pool.trie
            if trie is not None:  # cached prefixes are old-version KV
                trie.release_all(self._pool.allocator)
            self.weight_version = ver
            self.metrics.inc("weight_swaps")
            if not fut.done():
                fut.set_result(ver)
        except Exception as e:  # pragma: no cover - validation ran already
            if not fut.done():
                fut.set_exception(e)

    # -- router probes --------------------------------------------------------
    def kv_headroom(self) -> float:
        """Free fraction of the KV page pool (load-aware dispatch input)."""
        a = self._pool.allocator
        return round(a.free_pages / max(a.usable_pages, 1), 4)

    def prefix_match_tokens(self, prompt_ids, blocks=None) -> int:
        """Tokens of ``prompt_ids`` whose KV pages this engine already
        caches (prefix-affinity probe; takes no refs, bumps no LRU). A
        caller probing several replicas may pass the precomputed
        ``token_blocks(prompt, page_len, limit=(p-1)//page_len)``."""
        trie = self._pool.trie
        if trie is None:
            return 0
        if blocks is None:
            prompt = np.asarray(prompt_ids).reshape(-1)
            blocks = token_blocks(prompt, self._pl,
                                  limit=(len(prompt) - 1) // self._pl)
        return trie.match_len(blocks) * self._pl

    # -- KV page transfer (disaggregated prefill/decode) ----------------------
    def _run_on_worker(self, fn, timeout: float = 60.0):
        """Run ``fn()`` on the engine worker thread and return its result
        — the allocator and the K/V arenas are worker-owned, so export/
        install must serialize with decode at a step boundary. Runs
        inline when no worker thread exists yet."""
        with self._cond:
            if self._closed:
                raise EngineClosed("engine closed")
            started = self._thread is not None
            if started:
                fut: Future = Future()
                self._ops.append((fn, fut))
                self._cond.notify_all()
        if not started:
            return fn()
        return fut.result(timeout=timeout)

    def _drain_ops(self) -> None:
        """Execute queued cross-thread ops (worker thread, step boundary)."""
        while True:
            with self._cond:
                if not self._ops:
                    return
                fn, fut = self._ops.popleft()
            try:
                res = fn()
            except Exception as e:
                if not fut.done():
                    fut.set_exception(e)
            else:
                if not fut.done():
                    fut.set_result(res)

    def _refuse_kv_transfer(self, what: str) -> None:
        why = self._layout.refuses.get("kv_transfer")
        if why is not None:
            raise RuntimeError(f"{what}: " + why.format(
                model=type(self.model).__name__))

    def export_kv_pages(self, prompt_ids):
        """Read the cached KV of ``prompt_ids``' full prompt blocks out of
        the page pool as host arrays — the page shipper's source side.
        Returns ``(n_pages, k_stacks, v_stacks)`` with per-layer
        ``[n, page_len, heads, dim]`` stacks. Raises ``KeyError`` when the
        prompt's blocks are not all cached (caller falls back to
        re-prefill)."""
        self._refuse_kv_transfer("export_kv_pages")
        prompt = np.asarray(prompt_ids).reshape(-1)
        blocks = token_blocks(prompt, self._pl)

        def _export():
            trie = self._pool.trie
            if trie is None:
                raise KeyError("prefix cache disabled: nothing to export")
            if not blocks:
                return 0, [], []
            pages = trie.match(blocks, self._pl, self._pool.allocator)
            try:
                if len(pages) < len(blocks):
                    raise KeyError(
                        f"only {len(pages)}/{len(blocks)} prompt blocks "
                        f"cached — cannot export")
                k_stacks, v_stacks = self._pool.read_pages(pages)
                return len(pages), k_stacks, v_stacks
            finally:
                for pg in pages:
                    self._pool.allocator.release(pg)

        out = self._run_on_worker(_export)
        self.metrics.inc("kv_exports")
        return out

    def install_kv_pages(self, prompt_ids, k_stacks, v_stacks) -> int:
        """Install shipped page CONTENTS for ``prompt_ids``' full prompt
        blocks: allocate pages, scatter-write the K/V, and adopt the
        chain into the prefix cache — the page shipper's sink side. The
        next submit sharing this prompt prefix reuses the pages instead
        of prefilling. Returns pages newly adopted (blocks already
        cached keep their pages — first writer wins)."""
        self._refuse_kv_transfer("install_kv_pages")
        prompt = np.asarray(prompt_ids).reshape(-1)
        blocks = token_blocks(prompt, self._pl)
        n = len(blocks)
        got = int(k_stacks[0].shape[0]) if k_stacks else 0
        if got != n:
            raise BadRequest(
                f"{got} shipped pages != {n} full prompt blocks")

        def _install():
            trie = self._pool.trie
            if trie is None:
                raise BadRequest("prefix cache disabled: cannot install")
            if n == 0:
                return 0
            pages = self._pool.allocate(n)
            try:
                self._pool.write_pages(pages, k_stacks, v_stacks)
                adopted = trie.insert(blocks, pages, self._pool.allocator)
            finally:
                # the trie holds its own refs on adopted pages; ours drop
                # (unadopted duplicates free harmlessly here)
                for pg in pages:
                    self._pool.allocator.release(pg)
            return adopted

        out = self._run_on_worker(_install)
        self.metrics.inc("kv_installs")
        self.metrics.inc("kv_pages_installed", out)
        return out

    # -- the continuous-batching loop -----------------------------------------
    def _active(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s.req is not None]

    def _next_request(self) -> Tuple[Optional[_GenRequest], bool]:
        """Shed expired queued requests, then pick the earliest-deadline
        queued request whose KV pages can be allocated right now. Beside the
        pick, whether the pool BINDS: prompts wait and it can hold none of
        them — a statement about the whole queue, not its head, made under
        the lock that an arrival takes."""
        now = time.monotonic()
        shed: List[_GenRequest] = []
        picked: Optional[_GenRequest] = None
        with self._cond:
            for r in list(self._queue):
                if r.deadline is not None and now > r.deadline:
                    self._queue.remove(r)
                    shed.append(r)
            order = sorted(self._queue, key=_GenRequest.edf_key)
            for r in order:
                if self._pool.can_allocate(r.pages):
                    self._queue.remove(r)
                    picked = r
                    break
            bound = picked is None and bool(self._queue)
        for r in shed:  # outside the lock: future callbacks may re-submit
            self.metrics.inc("shed_total")
            if not r.future.done():
                r.future.set_exception(DeadlineExceeded(
                    "deadline expired while queued"))
            _tracer().finish(r.trace, ok=False, error="DeadlineExceeded")
        return picked, bound

    def _free_slot(self) -> Optional[int]:
        """The free slot that has been free the longest (one never used
        first, the lowest index among equals). So at most ``max_slots``
        requests that reach a drained engine together are served in slots of
        their own whatever ends while they join — a carried round can end a
        short request before the last of them is admitted — and a finished
        request's row of the state arenas (``slot_state``) is the last one
        overwritten."""
        free = [i for i, s in enumerate(self._slots) if s.req is None]
        return min(free, key=lambda i: (self._slots[i].freed, i),
                   default=None)

    def _worker(self):
        """The continuous-batching loop. Each turn takes ONE window program
        (a prompt's prefill, one call or several, or a decode round) from its
        dispatch to the host's read of its result, and dispatches the program
        after it BEFORE that read wherever what comes next does not hang on
        the result (``_run_ahead``): the device then starts the next program
        the moment this one ends instead of idling for a host round trip. A
        round goes out so with no slot free, or with a slot free and prompts
        waiting of which the pool holds none, unless a request ends behind
        the unread program; with a slot free and nobody waiting nothing does.
        ``prog`` is the program that went out ahead of its turn, if one did;
        with none in flight the worker is at a boundary and decides with
        everything read, as it always did. Prompts are admitted back to back
        while slots and pages last, chunked or not: a prefill call of the
        largest bucket carries the running sequences' round
        (``_carried_round``), so an admission holds nobody."""
        prog = None
        while True:
            if prog is None:
                # cross-thread ops (KV export/install) land at the step
                # boundary, before admission — an installed prefix is
                # visible to the very next admit
                self._drain_ops()
                # a staged weight swap lands at the first zero-active step
                # boundary (admission pauses below until it does, so the
                # active set drains and in-flight work stays version-pure)
                if self._pending_swap is not None and not self._active():
                    self._apply_swap()
                # admit a queued prompt into a free slot (join mid-flight,
                # earliest deadline first, bounded by KV page headroom)
                if self._pending_swap is None:
                    free = self._free_slot()
                    req = None if free is None else self._next_request()[0]
                    if req is not None:
                        prog = _Admission(free, req)
            if isinstance(prog, _Admission):
                adm = prog
                try:
                    prog = self._admit(adm)
                    continue
                except PoolExhausted:
                    # transient: pages freed by in-flight releases will
                    # cover it — requeue at the front, and a decode round
                    # (below) before it is tried again
                    self._requeue(adm.req)
                    prog = None
            if prog is None and not self._active():
                with self._cond:
                    if self._closed and not self._queue:
                        pend, self._pending_swap = self._pending_swap, None
                        if pend is not None and not pend[2].done():
                            pend[2].set_exception(
                                EngineClosed("engine closed"))
                        while self._ops:
                            _fn, fut = self._ops.popleft()
                            if not fut.done():
                                fut.set_exception(
                                    EngineClosed("engine closed"))
                        return
                    if not self._queue and not self._ops:
                        # untimed: submit/close/op notify — no idle polling
                        self.metrics.inc("idle_waits")
                        with span("pt.serve.idle_wait"):
                            self._cond.wait()
                continue
            prog = self._decode_once(prog)

    def _requeue(self, req: _GenRequest) -> None:
        with self._cond:
            self._queue.appendleft(req)
        self.metrics.inc("admits_requeued")

    def _run_ahead(self, flying):
        """Dispatch the program that follows ``flying`` — a round or a
        prompt's last prefill call, dispatched and not yet read — if what
        follows is decided whatever ``flying`` returns; else ``None``, and
        the worker reads, then decides. Decided means:

        - with a slot free and a prompt waiting whose pages the pool has,
          that prompt's first prefill call comes next: slot and pages do not
          hang on the unread result. A call of the largest bucket CARRIES
          the round that would otherwise have to wait behind it
          (``_carried_round``), so a chunked admission holds nobody and
          owes nobody a round;
        - with no slot free, OR with a slot free and prompts waiting of which
          the pool holds none (``_next_request`` picked nothing from a queue
          that is not empty, or the pick's join met ``PoolExhausted`` and is
          back at the queue's front), and neither a slot nor a page to come
          free when ``flying`` is read, no prompt can join whatever
          arrives, so a round comes next. Every request's remaining budget
          is known here: a round behind which one ends is NOT sent — its
          pages come back at the read and a prompt that waits may then fit,
          so the worker reads first. One that ends on EOS instead is the one
          wasted row: ``_emit_round`` drops its extra token, and a prompt its
          pages make room for joins one round later. ``pool_bound`` marks the
          round that went out under the second condition
          (``rounds_ahead_pool_bound_total``).

        Either takes the tokens ``flying`` has not handed over from its own
        output on the device (``_round_feed``).

        With a slot free and NOBODY waiting nothing goes out: the next
        arrival would otherwise prefill behind a round dispatched on a guess
        and pay up to that round in first-token latency. A draft model's
        proposals cross the host, so speculative decoding keeps one program
        at a time; a staged weight swap and cross-thread ops wait for a
        boundary at which nothing is in flight, so they stop it too. A fault
        here fails the requests of the program that was to go out, as it
        would have a turn later."""
        if self.spec_k or self._pending_swap is not None:
            return None
        with self._cond:
            if self._ops:
                return None
        free = self._free_slot()
        if free is not None:
            req, pool_bound = self._next_request()
            if req is not None:
                adm = _Admission(free, req)
                try:
                    self._join(adm)
                    self._send_chunk(adm, flying,
                                     self._carried_round(adm, flying))
                    return adm
                except PoolExhausted:
                    self._requeue(req)
                    pool_bound = True
                except Exception as e:  # isolate: fail this prompt only
                    self._fail_admission(adm, e)
                    return None
            if not pool_bound:
                return None
        rnd = self._build_round(flying)
        if not rnd.rows or len(rnd.rows) < len(self._active()):
            return None
        rnd.pool_bound = free is not None
        try:
            self._send_round(rnd, flying)
        except Exception as e:
            self._fail_rows(rnd.rows, e)
            return None
        return rnd

    def _join(self, adm: _Admission) -> None:
        """Give a prompt its slot and its pages: borrow its cached prefix
        pages, allocate private pages for the rest, and lay out the window
        calls that prefill ONLY the uncached suffix. ``PoolExhausted`` leaves
        the slot free."""
        import jax.numpy as jnp

        req, s = adm.req, self._slots[adm.slot_no]
        p = len(req.prompt)
        # all its pages, or ``PoolExhausted`` and the pool as it was
        with span("pt.serve.page_table"):
            m, taken = self._pool.join(s.pages, req.pages)
        self._count_slide(0, taken)
        chunks = self._prefill_chunks(m * self._pl, p)
        # the slot is taken from here on; its first token comes with the
        # read of the last prefill call
        s.req, s.length, s.last_token = req, p, 0
        # the suffix goes through the ONE-ROW window step: one call if it
        # fits a bucket, else chunks of the largest back to back, each
        # attending to the pages the earlier ones wrote
        adm.m = m
        adm.chunks = chunks
        adm.table = jnp.asarray(self._pool.row_tables(s.pages))

    def _carried_round(self, adm: _Admission, flying) -> Optional[_Round]:
        """The decode round the next window call of ``adm``'s prefill
        carries, built as the round that would go out behind ``flying`` is
        (``_build_round``; the joining prompt has no token yet and gets no
        row); ``None`` for a call that carries none (``_carried_rows``). With
        no sequence running the round has no row and the program runs all
        the same: every one of its decode rows is idle."""
        if not self._carried_rows(adm.chunks[len(adm.outs)][2]):
            return None
        return self._build_round(flying, joining=adm)

    def _send_chunk(self, adm: _Admission, flying=None,
                    rnd: Optional[_Round] = None) -> None:
        """Dispatch the next window call of a prompt's prefill, behind
        ``flying`` if that program is still unread (``adm`` itself, while
        its call before this one is), and with it the round ``rnd`` it
        carries (``_carried_round``, built by the caller: what it carries is
        an argument of the call's span). Behind a prompt's LAST call
        go, dispatched and not waited for, what the next program may need of
        it: the install of its recurrent state, and its full blocks' adoption
        by the prefix cache (any program that reads those pages runs behind
        the one that writes them)."""
        import jax.numpy as jnp

        req = adm.req
        lo, hi, Wc = adm.chunks[len(adm.outs)]
        pages = self._chunk_pages(Wc)
        if pages and lo % self._pl:
            # the invariant ``_aligned`` states; it fails this prompt only
            raise AssertionError(
                f"a {Wc}-token prefill call that writes whole pages starts "
                f"at {lo}, inside a page of {self._pl}: it would overwrite "
                "cached keys")
        if self._layout.window and adm.outs:
            # a later call's window pages, as it goes out
            s = self._slots[adm.slot_no]
            with span("pt.serve.page_table"):
                self._count_slide(*self._pool.slide(s.pages, lo, hi - 1))
                adm.table = jnp.asarray(self._pool.row_tables(s.pages))
        tokens = np.zeros((1, Wc), dtype=np.int32)
        tokens[0, :hi - lo] = req.prompt[lo:hi]
        tables, tokens = adm.table, jnp.asarray(tokens)
        lengths = jnp.asarray(np.array([lo], dtype=np.int32))
        n_valid = np.array([hi - lo], dtype=np.int32)
        if rnd is not None:
            # the prompt's operand, then the round's: as ``_send_round``
            # hands them to a round of its own
            tables = (tables, jnp.asarray(rnd.tables))
            tokens = (tokens, self._round_feed(rnd, flying))
            lengths = (lengths, jnp.asarray(rnd.lengths))
            n_valid = (n_valid, self._round_valid(rnd))
        # a later chunk of a model whose block resumes starts from the state
        # the chunk before it left (donated to this call), the first from
        # zero; what this one leaves goes to the next, or into the slot's row
        resumed, adm.state = adm.state, None
        with _oom_guard("generation", label=f"serving:{self.name}:prefill",
                        engine=self.name, bucket=Wc):
            nxt, lp, row, counted = self._run_window(
                1, Wc, tables, tokens, lengths, n_valid=n_valid,
                prefill=True, state=resumed)
        if resumed is not None:
            self.metrics.inc("state_resumes_total")
        if rnd is not None:
            (nxt, rnd.nxt), (lp, rnd.lp) = nxt, lp
        adm.outs.append((nxt, lp))
        adm.carried.append(rnd)
        adm.counters.append(counted)
        self.metrics.inc("prefill_chunks_total")
        if flying is not None:
            self.metrics.inc("programs_run_ahead_total")
        # token-rows the prefill program ran (rows x W): what
        # stats()["prefill_fill_rate"] divides the real tokens by
        self.metrics.inc("prefill_window_tokens_total", Wc)
        # how the call's tokens reached the cache: whole pages, or one row a
        # token (the chunk's where its program scatters, the carried round's)
        self.metrics.inc("kv_pages_written_total", pages)
        self.metrics.inc("kv_rows_written_total", 0 if not self._layout.paged
                         else (0 if pages else Wc)
                         + (0 if rnd is None else self.config.max_slots))
        # cached positions the chunk's queries see, summed (token w of the
        # chunk sees lo + w + 1)
        n = hi - lo
        self._count_tokens(n + (0 if rnd is None else len(rnd.rows)))
        self.metrics.inc("attn_keys_prefill_total", n * lo + n * (n + 1) // 2)
        layout = self._layout
        if layout.index:
            # every layer that owns an indexer scored them all, once a layer
            self.metrics.inc("index_keys_scored_prefill_total",
                             (n * lo + n * (n + 1) // 2)
                             * layout.index.indexers)
        if layout.by_layer:
            self._count_keys(n * lo + n * (n + 1) // 2,
                             self._keys_in_window(lo, hi, layout.window))
            self._count_walk(Wc, [lo])
        if len(adm.outs) < len(adm.chunks):
            adm.state = row if self._sm.resumes_state else None
            return
        if row is not None:
            with span("pt.serve.state_install", slot=adm.slot_no):
                self._install_state(adm.slot_no, row)
            self.metrics.inc("state_installs_total")
        if self._pool.trie is not None:
            # its full blocks into the prefix cache
            with span("pt.serve.page_table"):
                self._pool.adopt(self._slots[adm.slot_no].pages, req.pages)

    def _round_between(self, adm: _Admission) -> Optional[_Round]:
        """A decode round of the running sequences, dispatched between two
        chunks of ``adm``'s prompt: for a model with recurrent state whose
        block resumes and whose calls carry no round (``carries_rounds``:
        Brumby), where a prompt of many chunks would else hold every running
        sequence for all of them. The joining slot has no row; everything
        before this round is read, so its tokens are the host's — which is
        why NOTHING goes out behind a call that carried a round: that round
        is unread, and a second one from the host's tokens and lengths would
        advance every running sequence's state twice on one token. ``None``
        too where the model is another kind or nothing runs; a fault fails
        the round's requests alone."""
        if not (self._layout.stateful and self._sm.resumes_state) or \
                adm.carried[-1] is not None:
            return None
        rnd = self._build_round(None, joining=adm)
        if not rnd.rows:
            return None
        try:
            self._send_round(rnd)
        except Exception as e:
            self._fail_rows(rnd.rows, e)
            return None
        return rnd

    def _fail_admission(self, adm: _Admission, e: Exception) -> None:
        req, s = adm.req, self._slots[adm.slot_no]
        if not req.future.done():
            req.future.set_exception(e)
        _tracer().finish(req.trace, ok=False, error=type(e).__name__)
        self.metrics.inc("errors_total")
        if s.req is req or s.req is None:
            self._pool.release(s.pages)
            s.req, s.length, s.last_token = None, 0, 0

    def _admit(self, adm: _Admission):
        """Take a prompt from its join (made here at a boundary, in
        ``_run_ahead`` when its first call went out ahead) to its first
        token: every window call of its prefill is dispatched, the NEXT
        program is dispatched behind it where that is decided — the next
        chunk always, after the last call whatever ``_run_ahead`` finds —
        and only then is the call waited for, so a ``pt.serve.prefill_chunk``
        span is one call's time on the device, from the end of the program
        before it (or its own dispatch, if later) to its own end. A call
        that carried a round (``_carried_round``; the span's ``carried`` is
        its live rows) hands the round's tokens over as soon as it is read:
        they are emitted and counted as a round's are (``_read_round``). The
        first generated token is the window's argmax at the last real prompt
        position (matching ``generate``'s contract). Returns the program
        that went out behind the last call, if one did."""
        req, slot_no = adm.req, adm.slot_no
        s = self._slots[slot_no]
        p = len(req.prompt)
        after = None
        try:
            with span("pt.serve.admit", trace_id=req.trace, slot=slot_no,
                      prompt_len=p) as sp:
                t0 = time.monotonic()
                if adm.chunks is None:
                    self._join(adm)
                # the queue span lands only once the join is certain — a
                # PoolExhausted requeue must not double-record queue time
                _tracer().span(req.trace, "queue", req.t_submit, t0)
                chunks, m = adm.chunks, adm.m
                W = chunks[-1][2]
                sp.args.update(bucket=W, prefix_blocks=m, chunks=len(chunks))
                with span("pt.serve.prefill_dispatch", bucket=W,
                          prefix_blocks=m, rows=1):
                    for i, (lo, _hi, Wc) in enumerate(chunks):
                        ahead = i < len(adm.outs)
                        rnd = adm.carried[i] if ahead else \
                            self._carried_round(adm, None)
                        with span("pt.serve.prefill_chunk", start=lo, W=Wc,
                                  ahead=int(ahead), carried=0 if rnd is None
                                  else len(rnd.rows),
                                  pages=self._chunk_pages(Wc)):
                            if not ahead:
                                self._send_chunk(adm, None, rnd)
                            between = None
                            if i + 1 < len(chunks):
                                between = self._round_between(adm)
                                self._send_chunk(
                                    adm, adm, self._carried_round(adm, adm))
                            else:
                                after = self._run_ahead(adm)
                            adm.outs[i][0].block_until_ready()
                            # a call's counts land with the call, not with
                            # the prompt's last: a stretch of the counters
                            # then holds the programs that ran in it
                            self._count_programs(adm.counters[i])
                            adm.carried[i] = None
                            if rnd is not None and rnd.rows:
                                self._read_round(rnd, carried=True)
                        if between is not None:
                            # outside the chunk's span: that is one call's
                            # time on the device, and this is a round's
                            self._read_round(between)
                if s.req is not req:
                    return after  # failed with the round that went out ahead
                # a prefill returns its last real position only
                nxt, lp = adm.outs[-1]
                with span("pt.serve.prefill_sync"):
                    first = int(np.asarray(nxt)[0, 0])
                    first_lp = float(np.asarray(lp)[0, 0])
                # draft model prefills the WHOLE prompt through its own
                # forward (the draft is small; its dense slot arena has no
                # prefix cache)
                if self.spec_k:
                    self._draft_prefill(slot_no, req.prompt)
                if self._pool.trie is not None:
                    self.metrics.inc("prefix_hit_tokens", m * self._pl)
                    if self._fam_prefix is not None:
                        self._fam_prefix.inc((self.name, "lookup_tokens"), p)
                        self._fam_prefix.inc((self.name, "hit_tokens"),
                                             m * self._pl)
                self.metrics.inc("prompt_tokens_total", p)
                self.metrics.inc("prefills_total")
                if m:
                    self.metrics.inc("prefix_hits")
                self.metrics.observe_queue_wait((t0 - req.t_submit) * 1e3)
                t1 = time.monotonic()
                _tracer().span(req.trace, "prefill", t0, t1, bucket=W,
                               prompt_len=p, slot=slot_no, prefix_blocks=m)
                if self._hist_ttft is not None:
                    self._hist_ttft.observe((t1 - req.t_submit) * 1e3)
                req.t_decode0 = t1
                s.last_token = first
                s.t0 = t1  # slot residency opens (occupancy track)
                self._note_token(req, first, first_lp)
                self._emit_finish_check(slot_no)
        except PoolExhausted:
            raise
        except Exception as e:  # isolate: fail this prompt only
            self._fail_admission(adm, e)
            for rnd in adm.carried:  # and the rows whose tokens it held
                if rnd is not None:
                    self._fail_rows(rnd.rows, e)
        return after

    def _note_token(self, req: _GenRequest, t: int, lp: float) -> None:
        """One emitted token: record it (token + behavior logprob) and
        fire the stream callback (a client callback must never sink the
        decode batch)."""
        req.generated.append(int(t))
        req.logprobs.append(float(lp))
        if req.on_token is not None:
            try:
                if req.want_logprobs:
                    req.on_token(int(t), float(lp))
                else:
                    req.on_token(int(t))
            except Exception:
                pass

    def _draft_prefill(self, slot_no: int, prompt: np.ndarray):
        """Land the draft model's K/V for the whole prompt in its slot
        arena (the draft proposes from position ``len(prompt)`` on)."""
        import jax.numpy as jnp

        from ..core import autograd
        from ..core.tensor import Tensor

        p = len(prompt)
        bucket = self._prefill_bucket(p)
        padded = np.zeros((1, bucket), dtype=np.int64)
        padded[0, :p] = prompt
        with autograd.no_grad():
            _h, caches = self._draft.gpt(Tensor(jnp.asarray(padded)),
                                         use_cache=True)
        slot = np.int32(slot_no)
        for li, (k, v) in enumerate(caches):
            self._dk[li] = self._dinsert(self._dk[li], k.data, slot)
            self._dv[li] = self._dinsert(self._dv[li], v.data, slot)

    def _build_round(self, flying=None,
                     joining: Optional[_Admission] = None) -> _Round:
        """The decode round that follows ``flying`` (a program dispatched and
        not yet read; ``None``: everything is read): a row for every request
        that is still running once ``flying`` is read, at the length it has
        then. A request whose budget or context ``flying``'s token completes
        gets no row; a row whose token is still on the device is left 0
        (``_round_feed`` feeds it). ``joining``: the prompt whose prefill
        call carries this round; it has no token yet and gets no row."""
        S, B = self.config.max_slots, self._n_blocks
        k = self.spec_k if self._spec_on else 0
        with span("pt.serve.decode_build"):
            tokens = np.zeros((S, k + 1), dtype=np.int32)
            lengths = np.zeros(S, dtype=np.int32)
            pool = self._pool
            slide, put_tables = pool.slide, pool.put_tables
            tables = np.zeros(pool.tables_shape(S), dtype=np.int32)
            unread = _unread(flying)
            rows, released, taken = [], 0, 0
            for i, s in enumerate(self._slots):
                req, length = s.req, s.length
                if req is None or \
                        (joining is not None and i == joining.slot_no):
                    continue
                if i in unread and unread[i][0] is req:
                    length += unread[i][1]
                    if len(req.generated) + 1 >= req.max_new_tokens \
                            or length >= self.max_len - 1:
                        continue
                else:
                    tokens[i, 0] = s.last_token
                lengths[i] = at = min(length, self.max_len - 1)
                # the slot's window layers move on to this position
                moved = slide(s.pages, at, at)
                released += moved[0]
                taken += moved[1]
                put_tables(tables, i, s.pages)
                rows.append((i, req))
            self._count_slide(released, taken)
        return _Round(rows, k, tokens, lengths, tables)

    def _round_feed(self, rnd: _Round, flying=None):
        """A built round's ``[max_slots, 1]`` tokens, on the device. Behind
        an unread ``flying`` they come from ``flying``'s own output: a
        round's argmaxes as they are — a round of its own or the one a
        prefill call carried (every row of this round had one in that) — and
        the first token of a prompt whose last call is out through
        ``_feed_token``. All are committed to the arenas' device: one
        signature of the program that takes them."""
        import jax

        if flying is None:
            return jax.device_put(rnd.tokens, self._device)
        if isinstance(flying, _Round):
            return flying.nxt
        carried = flying.carried[-1]
        feed = rnd.tokens if carried is None else carried.nxt
        if flying.sent:
            return self._feed_token(feed, flying.outs[-1][0], flying.slot_no)
        return feed

    def _round_valid(self, rnd: _Round) -> np.ndarray:
        """``n_valid`` of a round's program: 1 for a row the round advances,
        0 for an idle one (its state, if the model has one, stays as it
        is)."""
        n_valid = np.zeros(self.config.max_slots, dtype=np.int32)
        n_valid[[i for i, _req in rnd.rows]] = 1
        return n_valid

    def _send_round(self, rnd: _Round, flying=None) -> None:
        """Dispatch a built round, its tokens from ``_round_feed``."""
        import jax.numpy as jnp

        S, k, tokens = self.config.max_slots, rnd.k, rnd.tokens
        # chaos site: scripted decode fault at an exact decode-step index
        # (PT_FAULTS="decode_fault@step=2") — the round's requests fail,
        # their slots release, queued prompts keep being admitted
        self._decode_no += 1
        _injector().check("decode_fault", engine=self.name,
                          step=self._decode_no)
        with span("pt.serve.decode_dispatch"):
            if k:  # draft proposal: k dense decode steps, all slots
                cur = jnp.asarray(tokens[:, 0])
                for j in range(k):
                    with _oom_guard("generation",
                                    label=f"serving:{self.name}:draft",
                                    engine=self.name, step=self._decode_no):
                        nd, self._dk, self._dv = self._draft_step(
                            self._dparams, self._dk, self._dv, cur,
                            jnp.asarray(rnd.lengths + j))
                    tokens[:, j + 1] = np.asarray(nd)
                    cur = nd
            with _oom_guard("generation", label=f"serving:{self.name}:decode",
                            engine=self.name, step=self._decode_no):
                rnd.nxt, rnd.lp, _row, rnd.counters = self._run_window(
                    S, k + 1, jnp.asarray(rnd.tables),
                    self._round_feed(rnd, flying), jnp.asarray(rnd.lengths),
                    n_valid=self._round_valid(rnd))
        if self._layout.paged:
            self.metrics.inc("kv_rows_written_total", S * (k + 1))
        self._count_tokens(len(rnd.rows) * (k + 1))
        if flying is not None:
            self.metrics.inc("programs_run_ahead_total")
        if rnd.pool_bound:
            self.metrics.inc("rounds_ahead_pool_bound_total")

    def _fail_rows(self, rows, e: Exception) -> None:
        """A fault in a round fails the requests it was to advance (those
        that have not ended since) and releases their slots."""
        now, failed = time.monotonic(), 0
        for i, req in rows:
            if self._slots[i].req is not req:
                continue
            if not req.future.done():
                req.future.set_exception(e)
            self._release_slot(i, now, failed=True, error=type(e).__name__)
            failed += 1
        self.metrics.inc("errors_total", failed)
        self.metrics.inc("batch_failures")

    def _decode_once(self, rnd: Optional[_Round] = None):
        """One decode round, to the emission of its tokens; ``rnd`` is the
        round if it went out ahead of its turn, else it is built and
        dispatched here. Without a draft model this is the classic
        W=1 step (one token per active slot). With one, the draft
        proposes ``k`` tokens per slot (k dense decode steps), the target
        scores all k+1 window positions in ONE verify call, and each slot
        advances by its accepted run plus the target's own next token —
        emitted tokens are target argmaxes, so greedy output is unchanged.
        The program after this round is dispatched before this one is read
        where ``_run_ahead`` finds it decided, and is returned."""
        ahead = rnd is not None
        n_active = len(rnd.rows) if ahead else len(self._active())
        k = rnd.k if ahead else self.spec_k if self._spec_on else 0
        after = None
        try:
            with span("pt.serve.decode_round", n_active=n_active, W=k + 1,
                      ahead=int(ahead),
                      pool_bound=int(ahead and rnd.pool_bound)):
                t_dec = time.monotonic()
                if not ahead:
                    rnd = self._build_round()
                    self._send_round(rnd)
                after = self._run_ahead(rnd)
                self._read_round(rnd, t_dec)
        except Exception as e:  # decode fault: fail the round's requests
            self._fail_rows(rnd.rows if rnd is not None else
                            [(i, self._slots[i].req) for i in self._active()],
                            e)
        return after

    def _read_round(self, rnd: _Round, t_dec: Optional[float] = None,
                    carried: bool = False) -> None:
        """Read a dispatched round's tokens, count what a round counts and
        emit. ``carried``: the round rode a prefill call (the program's own
        counters are the admission's). It is counted as a decode step like
        any other — occupancy and the kernels' roofline readers see the work
        that was done — and in ``rounds_carried_total`` besides."""
        S, k, n_active = self.config.max_slots, rnd.k, len(rnd.rows)
        if self._hist_slots is not None:
            # concurrent-occupancy sample per decode window: the
            # distribution the tuner derives max_slots from
            self._hist_slots.observe(n_active)
        with span("pt.serve.decode_sync"):
            n = np.asarray(rnd.nxt)  # [S, W] target argmaxes
            lpn = np.asarray(rnd.lp)  # [S, W] their logprobs (f32)
        self._count_programs(rnd.counters)
        fr = self._flight()
        if fr is not None and t_dec is not None:
            # decode steps land in the flight ring
            fr.record_serving_step(self.name, "decode",
                                   (time.monotonic() - t_dec) * 1e3,
                                   n_active)
        self.metrics.inc("decode_steps")
        if carried:
            self.metrics.inc("rounds_carried_total")
        self.metrics.inc("slot_rounds", n_active)
        # cached positions the round's queries see, summed over its rows
        self.metrics.inc("attn_keys_decode_total",
                         int(rnd.lengths.sum()) + n_active)
        layout = self._layout
        if layout.index:
            self.metrics.inc("index_keys_scored_decode_total",
                             (int(rnd.lengths.sum()) + n_active)
                             * layout.index.indexers)
        if layout.by_layer:
            seen = rnd.lengths[[i for i, _req in rnd.rows]] + 1
            self._count_keys(int(seen.sum()), int(
                np.minimum(seen, layout.window).sum()), decode=True)
            # the rows of the call that walk: the live sequences'
            self._count_walk(k + 1, rnd.lengths, self._round_valid(rnd) > 0,
                             decode=True)
        self.metrics.observe_occupancy(n_active / S)
        with span("pt.serve.emit"):
            emitted_total = self._emit_round(rnd, n, lpn)
        self.metrics.inc("tokens_total", emitted_total)
        if k:
            self.metrics.inc("spec_rounds")
            if self._fam_spec is not None:
                self._fam_spec.inc((self.name, "rounds"))
                self._fam_spec.inc((self.name, "emitted"), emitted_total)

    def _emit_round(self, rnd: _Round, n, lpn) -> int:
        """Accept, emit, finish and release, row by row; returns the
        tokens emitted this round."""
        k, tokens = rnd.k, rnd.tokens
        emitted_total = 0
        for i, req in rnd.rows:
            s = self._slots[i]
            if s.req is not req:
                # it ended on EOS while this round, dispatched ahead, held
                # a row for it: the extra token is dropped
                continue
            if k:
                a = greedy_accept(tokens[i, 1:k + 1], n[i, :k])
                # cap the advance at k so the draft cache stays in sync
                # (the all-accepted bonus would outrun what the draft saw)
                adv = min(a + 1, k)
                emit = [int(tokens[i, j + 1]) for j in range(adv - 1)]
                emit.append(int(n[i, adv - 1]))
                self.metrics.inc("spec_proposed", k)
                self.metrics.inc("spec_accepted", adv - 1)
                if self._fam_spec is not None:
                    self._fam_spec.inc((self.name, "proposed"), k)
                    self._fam_spec.inc((self.name, "accepted"), adv - 1)
            else:
                emit = [int(n[i, 0])]
            # every emitted token e IS the target argmax at window
            # position e (greedy_accept admits a draft token only when it
            # equals n[i, e]), so lpn[i, e] is its behavior logprob
            for e, t in enumerate(emit):
                s.length += 1
                s.last_token = t
                self._note_token(s.req, t, lpn[i, e])
                emitted_total += 1
                if self._emit_finish_check(i):
                    break
        return emitted_total

    def _emit_finish_check(self, slot_no: int) -> bool:
        """Finish-and-release when the slot's request is done (budget
        reached, EOS, or context exhausted). Returns True when released."""
        s = self._slots[slot_no]
        req = s.req
        eos = self.config.eos_token_id
        done = (len(req.generated) >= req.max_new_tokens
                or (eos is not None and req.generated[-1] == eos)
                or s.length >= self.max_len - 1)
        if not done:
            return False
        full = np.concatenate([req.prompt,
                               np.asarray(req.generated, dtype=np.int64)])
        if not req.future.done():
            if req.want_logprobs:
                req.future.set_result(
                    (full, np.asarray(req.logprobs, dtype=np.float32)))
            else:
                req.future.set_result(full)
        now = time.monotonic()
        self.metrics.observe_latency((now - req.t_submit) * 1e3)
        self.metrics.inc("responses_total")
        self.metrics.mark_done()
        self._release_slot(slot_no, now, failed=False)
        return True

    def _release_slot(self, slot_no: int, now: float, failed: bool,
                      error: Optional[str] = None):
        """Close the residency: decode span + completion on the request's
        trace, one span on the slot-occupancy track, history row for the
        pd_top occupancy view — and the KV pages go back to the pool."""
        s = self._slots[slot_no]
        req = s.req
        if req is not None:
            tr = _tracer()
            tokens = len(req.generated)
            if req.t_decode0 is not None:
                tr.span(req.trace, "decode", req.t_decode0, now,
                        tokens=tokens, slot=slot_no)
            tr.finish(req.trace, ok=not failed, error=error,
                      latency_ms=round((now - req.t_submit) * 1e3, 3))
            t0 = s.t0 or now
            tr.slot_span(self.name, slot_no, t0, now, req.trace,
                         tokens=tokens)
            self._slot_hist.append((slot_no, t0, now, tokens))
            self._residencies += 1
        self._pool.release(s.pages)
        if self._layout.stateful and req is not None:
            # the row's state is dead from here: no decode round advances
            # an idle row, and the next admission overwrites all of it
            self.metrics.inc("state_resets_total")
        s.req = None
        s.length = 0
        s.last_token = 0
        s.t0 = 0.0
        s.freed = next(self._releases)

    # -- observability --------------------------------------------------------
    def slot_occupancy(self, window_s: float = 60.0) -> Dict[str, Any]:
        """Per-slot busy fraction over the recent window (history + live
        residencies) — the compact occupancy view pd_top renders."""
        now = time.monotonic()
        horizon = max(now - window_s, self._t_start)
        span = max(now - horizon, 1e-6)
        busy = {i: 0.0 for i in range(self.config.max_slots)}
        for slot, t0, t1, _tokens in list(self._slot_hist):
            lo, hi = max(t0, horizon), min(t1, now)
            if hi > lo:
                busy[slot] = busy.get(slot, 0.0) + (hi - lo)
        for i, s in enumerate(self._slots):
            if s.req is not None and s.t0:
                busy[i] = busy.get(i, 0.0) + (now - max(s.t0, horizon))
        return {
            "slots": self.config.max_slots,
            "active": len(self._active()),
            "busy_frac": {str(i): round(min(b / span, 1.0), 4)
                          for i, b in busy.items()},
            "residencies": self._residencies,
            "window_s": round(span, 1),
        }

    def stats(self) -> Dict[str, Any]:
        snap = self._stats_base()
        snap["max_slots"] = self.config.max_slots
        snap["active_slots"] = len(self._active())
        snap["kv_pages"] = self._pool.stats()
        c = snap["counters"]
        pt = c.get("prompt_tokens_total", 0)
        snap["prefix_hit_rate"] = round(
            c.get("prefix_hit_tokens", 0) / pt, 4) if pt else 0.0
        # share of the prefill programs' token-rows that held a real token
        wt = c.get("prefill_window_tokens_total", 0)
        snap["prefill_fill_rate"] = round(
            (pt - c.get("prefix_hit_tokens", 0)) / wt, 4) if wt else 0.0
        rounds = c.get("slot_rounds", 0)  # per-SEQUENCE decode rounds
        snap["effective_tokens_per_step"] = round(
            c.get("tokens_total", 0) / rounds, 3) if rounds else 0.0
        # share of the window programs that went out while the one before
        # them was still unread (the device did not wait for the host there)
        programs = c.get("decode_steps", 0) + c.get("prefill_chunks_total", 0)
        snap["run_ahead_rate"] = round(
            c.get("programs_run_ahead_total", 0) / programs, 4) \
            if programs else 0.0
        # share of the decode steps that rode a prefill call of the largest
        # bucket (the carried step) instead of holding the device alone
        steps = c.get("decode_steps", 0)
        snap["carried_round_rate"] = round(
            c.get("rounds_carried_total", 0) / steps, 4) if steps else 0.0
        pairs = c.get("moe_pairs_total", 0)
        if pairs:  # an expert layer that holds a share of its experts
            snap["moe_held_share"] = round(
                c.get("moe_held_pairs_total", 0) / pairs, 5)
        hit = c.get("moe_experts_hit_total", 0)
        if hit:  # times a held expert's gate / up weights were streamed a
            # call: 1 where the grouped matmul holds its contraction whole
            snap["moe_weight_streams_per_expert"] = round(
                c.get("moe_weight_streams_total", 0) / hit, 4)
        for name in self._sm.token_counters:
            # "<what>_total" -> "<what>_per_s", since the engine's start
            snap[name[:-len("_total")] + "_per_s"] = round(
                c.get(name, 0) / self.metrics.uptime_s(), 3)

        def pages(what, kind):  # the prefill calls', the decode rounds'
            decode = c.get(f"attn_pages_{what}_{kind}_decode_total", 0)
            return c.get(f"attn_pages_{what}_{kind}_total", 0) - decode, decode

        for kind in ("full", "window"):
            # pages the ranged kernel's tiles DMA'd for every page that held
            # a key in range: 1 where a row's range is walked once, T / 2 and
            # more where the T tiles of a chunk each walk the context again
            for part, walked, held in zip(("prefill", "decode"),
                                          pages("walked", kind),
                                          pages("in_range", kind)):
                if held:
                    snap.setdefault("attn_walk_amplification", {}).setdefault(
                        kind, {})[part] = round(walked / held, 3)
        inside = c.get("attn_rows_in_window_total", 0)
        if inside:  # a latent cache's window kernel: the rows its tiles
            # DMA'd for every row inside their queries' windows
            snap.setdefault("attn_walk_amplification", {})["latent_window"] = \
                round(c.get("attn_rows_walked_window_total", 0) / inside, 3)
        if self.spec_k:
            prop = c.get("spec_proposed", 0)
            snap["spec_acceptance"] = round(
                c.get("spec_accepted", 0) / prop, 4) if prop else 0.0
        return snap
