"""Serve cells of a model whose every layer keeps memory of TWO kinds at once
— K/V pages AND a small tail by slot: a compressed convolutional attention,
whose latent queries and keys are mixed over the sequence by two causal convs
before they are cached (the engine's ``cache_spec["layers"]`` kind
``"full+state"``: ``Zaya1ForCausalLM`` is the first) — and whose top-1 expert
sublayer is routed by an MLP fed by the previous layer's router, behind
``serving.GenerationEngine`` under the open loop of ``runners/serve.py``. The
window, its bookkeeping, the tracer in two steps and the result line are
``serve_latent.run``'s and the server ``serve_hybrid.Server`` — called, not
copied: this runner's ``_check`` and ``_kernel_shapes`` take the place of that
module's while it runs (``serve_window._in_place_of``). What is this file's:

- ``correct``: as many seeded requests of the cell's own lengths as the pool
  holds at once (``together``: 212 of 256 slots at the published sizes, where
  pages bind before slots) go TOGETHER through the engine that served the
  window — their slots' tails live, the largest bucket's calls CARRYING the
  running sequences' rounds; every ``check_every``-th asks for logprobs. (NO
  prompt of this cell is chunked: the longest, 2048 tokens, is one call of the
  largest bucket, so no chip run of it RESUMES a tail — the counters must read
  ``state_resumes_total`` 0 there — and the resumed programs are compiled for
  the chip, run at the rehearsal's sizes on the CPU, where every prompt passes
  the largest bucket, and held by ``tests/test_zaya1.py``; PERF.md section 7
  asks the next ``benchmark`` issue for prompts past 2048.) Then the engine is
  closed, the checked requests' FINAL tails are read from their slots' rows,
  the caches are given back, and the plain reference (``system.reference``:
  float32 at ``highest``, the convs as explicit shifts, dense attention, every
  expert by a loop) computes from ONE full forward over the engine's own
  output: (i) the expert its routers choose at every (layer, token) — compared
  FIRST, with what the SERVED blocks choose over the same tokens in ONE dense
  pass (``served_choices``, below: the engine's programs hand back counts, not
  choices, so what its rounds and carried chunks themselves chose is held by
  the logprobs alone) and with what the served ROUTER alone chooses when it is
  handed the reference's own stream at every layer (``_router_alone``: the
  same float32 input on both sides, so the router's own arithmetic and
  nothing else): top-1 has no second choice to hide behind; (ii) the
  next-token logprobs of what the decode ROUNDS emitted (median and rms; the
  99th percentile and the maximum are printed); (iii) each checked slot's
  final tail in every layer — the median layer against the reference, and the
  FIRST layer's against one pass of the served blocks over the whole sequence
  (what chunks, carried rounds, the install and hundreds of single steps did
  to a tail: the program against the program, no reference in it). The
  counters are held exactly: one routed pair a (token, layer), every one held,
  and a resumed call for every chunk but a prompt's first (the limits, below);
- the readers get ``shapes.ranged`` and ``shapes.moe`` (three-matrix experts:
  ``lib/moe_cost.py`` as it is).
"""
from __future__ import annotations

import functools
import importlib
import json
import math
from typing import Dict

import numpy as np

from ..lib import part_time, traffic
from ..lib.harness import read_layer_metric, say
from . import serve, serve_latent
from .serve import _complete
from .serve_hybrid import Server, _delta
from .serve_recurrent import _slots_in_send_order, _weights_getter
from .serve_window import _in_place_of

# The engine multiplies in bfloat16 (float32 residual stream with its scales,
# depthwise conv, tail, temperature, RoPE, norms, the whole router and the
# logits), mixes the latent through the slot's tail — a chunk from the row's,
# a round one step through the arenas, the largest bucket both in one program
# —, runs the experts as three grouped matmuls and attends bfloat16 pages;
# the reference is one float32 forward at `highest`, the convs explicit
# shifts. Six limits; any one failing is not correct. Readings on the chip
# (my chip runs, PR 59; PERF.md section 6):
#   as configured, 17 runs at 10 and 12 requests/s, a seed each (the two sets
#   of six, the traced runs, the run after the review):
#     agreement 0.9896-0.9939, median 0.0091-0.0154, rms 0.025-0.037,
#     p99 0.110-0.160, max 0.26-0.43; the median layer's tail against the
#     reference 0.0040-0.0057; the first layer's tail against ONE pass of the
#     served blocks 0.0 (bit for bit, every run); the reference's logprob of
#     an emitted token -3.0 to -3.5 (the model does not repeat its input)
#   control (i), `controls_cca.py low_precision` - the reference with every
#   matmul operand rounded to 3 mantissa bits (`lax.reduce_precision(x, 8,
#   3)`: what a scaled fp8 matmul keeps, the nearest precision below the
#   bfloat16 the configuration states), seed 5900000201:
#     agreement 0.8893, median 0.203, rms 0.332, p99 0.89, tail median 0.113
#     (the first layer against one pass does not involve the reference: 0.0)
#   control (ii), `controls_cca.py bfloat16_tail` - the engine's convs hand
#   back their tail rounded to bfloat16 at every write, same seed: agreement
#   0.9900, median 0.0108, rms 0.029, tail median 0.0052 - none of them sees
#   it - and the first layer against one pass 0.00145
#   controls (iii), `controls_cca.py drop:tau` / `drop:eda` - the reference
#   WITHOUT the temperature / without the depth averaging, same seed:
#     agreement 0.661 / 0.585, median 1.00 / 0.207, rms 1.83 / 0.30,
#     tail median 0.367 / 0.093: a dropped mechanism fails every limit that
#     involves the reference
#   controls (iv), `controls_cca.py bfloat16_router` / `bfloat16_stream` - the
#   reference with the router's matmul operands / the residual stream after
#   every sublayer at bfloat16's 7 mantissa bits (the two precisions the
#   configuration's `assumed` states float32 for), same seed, where the run as
#   configured reads 0.9910 / 0.0111 / 0.0305 / 0.0049:
#     agreement 0.9881 / 0.9887, median 0.0120 / 0.0220, rms 0.035 / 0.042,
#     tail median 0.0051 / 0.0080: the limits above let BOTH pass. A bfloat16
#     router flips 0.39 % of the choices beside the 0.9 % that the bfloat16
#     matmuls' noise in the stream flips anyway, and lies inside the seeds'
#     range: ROUTER_ALONE, below, was added for it (0.99613 against 1.0 as
#     configured: not correct by that limit and by no other). A bfloat16
#     stream doubles the median at its own seed, but the seeds themselves
#     read up to 0.0154: no limit stands between 0.0154 and 0.0220 with room
#     on both sides, so it COMES OUT CORRECT - these limits cannot tell a
#     bfloat16 stream from the float32 one (PERF.md section 7 (1)).
#
# (i) ROUTER_AGREEMENT: the share of (layer, token) at which the served
# blocks' top-1 over the same tokens in one dense pass (`served_choices`) is
# the reference's. A router compares 16 float32 probabilities (+ bias): where the
# first and the second lie within the bfloat16 noise of the stream the two
# choose differently - 0.66-1.04 % of the choices - and with ONE choice a flip
# swaps a token's whole expert branch, so it moves every later layer's stream
# a little (the last layers agree least: 0.98-0.99). A wrong stream moves every
# router's input: control (i) misses 11.1 %. The limit (3.5 % missed) sits 3.4
# x above the largest seen and 3.2 x under control (i).
ROUTER_AGREEMENT = 0.965
# ... and ROUTER_ALONE: the share at which the SERVED router, handed the
# reference's own stream and previous representation at every layer of the
# reference's forward (`on_router`; `_router_alone`), picks the reference's
# expert. Both read the same float32 input, so nothing but the router's own
# arithmetic tells them apart: float32 at full precision on both sides flips
# only at a tie of the first two of `p + bias` to seven digits - as
# configured 1.0 on each of 8 requests (seed 5900000431: not one of 167 880
# choices differs; 1.0 on the CPU too) - and a router whose matmuls read
# bfloat16 operands misses 0.33-0.46 % a request, 0.99613 over the eight
# (control (iv)). The limit, 0.05 % missed, sits 7.7 x under the control and
# 84 choices above what the run as configured read.
ROUTER_ALONE = 0.9995
# (ii) |engine logprob - reference logprob| over the 3900-7000 tokens the 8
# checked requests' decode ROUNDS emitted. Rounding moves every token a
# little: the MEDIAN reads it alone (limit 3.2 x above the largest seen, 4.1 x
# under control (i)). A token that meets a router flip in its 20 choices has a
# whole expert branch changed in that layer and its logprob moves by tenths
# (4-23 tokens of thousands are off by more than 0.2): those set the RMS
# (limit 2.7 x above the largest seen, 3.3 x under control (i)); the 99th
# percentile and the maximum are extremes of them and are printed, not
# limited.
LOGPROB_MEDIAN = 0.05
LOGPROB_RMS = 0.10
# (iii) what a checked request leaves in its slot - every layer's tail
# ``[z ; c1 ; u Wv2]`` of its last consumed token, relative norm - held to
# two things. TAIL_MEDIAN, against the REFERENCE, the median over layers and
# checked requests: a stale tenant, a wrong row, a tail not installed are O(1)
# in every layer; a flip upstream moves a few (the worst layer of a run reads
# 0.007-0.063). Limit 4.4 x above the largest seen, 4.5 x under control (i).
# TAIL_RTOL, against ONE PASS of the served blocks over the whole sequence
# (the same dtype and rounding, no chunks, no rounds, no slots), the FIRST
# layer's tail, which no expert sublayer precedes and which is a function of
# the last two tokens' embeddings alone: what the prefill call, the rounds
# carried or alone, the install and 64-3000 single steps did to a tail, to
# float32 arithmetic - it reads 0.0. A tail kept in bfloat16 reads 0.00145:
# the limit sits 4.8 x under control (ii). The logprobs cannot tell a
# bfloat16 tail from the float32 the configuration states: this limit can.
TAIL_MEDIAN = 0.025
TAIL_RTOL = 3e-4

_last_check: Dict = {}


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) /
                 max(np.linalg.norm(want), 1e-30))


def together(engine: Dict, tr: Dict) -> int:
    """How many seeded requests of the cell's own lengths go through the
    check TOGETHER: a slot each, and no more than the pool holds at once (a
    request keeps ``ceil((prompt + new) / page_len)`` pages from its
    admission on) — a checked request's final tail is read from its slot's
    row afterwards, so nobody may have taken the slot after it. At the
    published sizes the pages bind before the slots do, as they do under the
    cell's traffic."""
    PL, pages = int(engine["page_len"]), int(engine["num_pages"]) - 1
    for n in range(int(engine["max_slots"]), 0, -1):
        lens = traffic.lognormal_quantiles(n, tr["prompt_len"]) + \
            traffic.lognormal_quantiles(n, tr["output_len"])[::-1]
        if sum(-(-int(t) // PL) for t in lens) <= pages:
            return n
    raise ValueError("the pool holds no request of the cell's lengths")


def _top1(cfg, p, hid):
    """The expert ``moe_held_experts_mlp`` picks for the router MLP's hidden
    layer ``hid`` [T, 256]: its own ``_route`` with the model's arguments."""
    import jax

    from paddle_tpu.nn.layer.moe import _route

    _gate, idx, _aux = _route(
        hid, p["router_w3"], cfg.num_experts_per_tok, score="softmax",
        norm_topk=False, precision=jax.lax.Precision.HIGHEST,
        bias=p["router_bias"])
    return idx


@functools.lru_cache(maxsize=None)
def _served_layer(cfg_items, block):
    """The program of one layer over a whole sequence's widened stream ``x``
    [1, T, ..] (donated), of which the first ``n`` positions hold a token:
    the stream, the tail after them, the expert the router chooses ``[T,
    1]`` — from the router representation the block hands on behind the
    stream."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import zaya1
    from paddle_tpu.models.nemotron_h import _dense_attend

    cfg = zaya1.Zaya1Config(**dict(cfg_items))
    attend = _dense_attend(1.0 / math.sqrt(cfg.head_dim), block)

    def layer(p, x, n):
        T = x.shape[1]
        x, state, _stats = zaya1.block_fn(
            cfg, p, x, jnp.arange(T, dtype=jnp.int32)[None], attend, None,
            (jnp.arange(T) < n)[None])
        return x, state, _top1(
            cfg, p, zaya1.router_mlp(p, x[0, :, cfg.hidden_size:]))

    return jax.jit(layer, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _router_alone(cfg_items):
    """The program of the SERVED router by itself: the expert it chooses
    ``[T, 1]`` for a stream ``x`` [T, h] and a previous representation
    ``r_prev`` [T, 256] that somebody else made — the reference, at every
    layer of its own forward (``on_router``), so that the two routers read
    the SAME float32 input and differ by their own arithmetic alone."""
    import jax

    from paddle_tpu.models import zaya1

    cfg = zaya1.Zaya1Config(**dict(cfg_items))
    return jax.jit(lambda p, x, r_prev: _top1(
        cfg, p, zaya1.router_hidden(cfg, p, x, r_prev)[0]))


def served_choices(cfg, params, tokens, block=512, n=None):
    """The experts the SERVED blocks choose over one sequence, ``[layers, T,
    1]`` int32, and each layer's tail after the first ``n`` tokens (all of
    them by default; the later ones are padding): ``models.zaya1.block_fn`` —
    the function the engine's programs trace, in the model's dtype — over the
    whole of ``tokens`` at once (a dense causal ``attend``, ``block`` queries
    scored at a time; the convs from zero), a layer a program. NOT what the
    engine's rounds chose: one pass of the same arithmetic over the same
    tokens. What the check compares with the plain reference's choice before
    it reads a logprob — and the tail ONE pass of the system's own arithmetic
    leaves, which a slot's tail after rounds and an install is held to."""
    import jax.numpy as jnp

    from paddle_tpu.models import zaya1

    layer = _served_layer(zaya1._frozen(cfg), block)
    x = zaya1.widen(cfg, params["embed"][jnp.asarray(tokens, jnp.int32)[None]])
    n = jnp.int32(len(tokens) if n is None else n)
    chosen, tails = [], []
    for p in params["layers"]:
        x, state, idx = layer(p, x, n)
        tails.append(state)
        chosen.append(idx)
    return jnp.stack(chosen), tails


def _check(server: Server, ctx) -> Dict:
    from paddle_tpu.models import zaya1

    spec = ctx["spec"]
    tr, cfg, eng = spec.workload["traffic"], spec.config, server.eng
    e = server.engine_cfg
    n = together(e, tr)
    every = int(spec.workload.get("check_every", 32))
    p_lens = traffic.lognormal_quantiles(n, tr["prompt_len"])
    o_lens = traffic.lognormal_quantiles(n, tr["output_len"])[::-1]
    rng = np.random.default_rng(np.random.SeedSequence([ctx["seed"], 99]))
    order = rng.permutation(n)  # the quantiles come sorted: spread them
    reqs = [traffic.Request(i, 0.0, rng.integers(
        0, cfg["vocab_size"], int(p_lens[k]), dtype=np.int64),
        int(o_lens[k])) for i, k in enumerate(order)]
    checked = reqs[::every]
    c0 = server.counters()
    for r in reqs:
        server.send(r, logprobs=r.index % every == 0)
    server.drain(reqs, timeout=float(spec.workload.get("check_timeout_s",
                                                       900)))
    c1 = server.counters()
    server.close()
    slots = _slots_in_send_order(server, n)
    # every request its own slot: a released slot's row keeps its last
    # tenant's final tail (``together``: the set fits the pool at once)
    complete = slots is not None and len(set(slots)) == n and \
        all(_complete(r) for r in reqs)
    held = {} if not complete else \
        {r.index: eng.slot_state(slots[r.index]) for r in checked}
    cache_dtype = str(eng._pool.k[0].dtype)
    complete = complete and cache_dtype == cfg["system"]["cache_dtype"] and \
        all(str(a.dtype) == cfg["system"]["state_dtype"]
            for st in held.values() for layer in st for a in layer.values())
    eng.release_caches()
    got = _delta(c1, c0)
    # every token but a request's last goes once through every layer's one
    # choice, every pair is held, and every prefill call but a prompt's first
    # resumed
    consumed = sum(len(r.prompt) + r.max_new - 1 for r in reqs)
    layers = cfg["num_hidden_layers"]
    pairs_ok = got["moe_pairs_total"] == got["moe_held_pairs_total"] == \
        consumed * cfg["num_experts_per_tok"] * layers
    largest = max(e["prefill_buckets"])
    calls = sum(-(-len(r.prompt) // largest) for r in reqs)
    resumed_ok = got["prefill_chunks_total"] == calls and \
        got["state_resumes_total"] == calls - n and \
        got["state_installs_total"] == n
    reference = importlib.import_module(
        "benchmark.lib." + cfg["system"]["reference"])
    pad = int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"])
    get = _weights_getter(server.model)
    mcfg = server.model.config
    params = {"embed": get("embed", -1), "layers": get("layers", -1)}
    errs, agree, alone, by_request, emitted = [], [], [], [], []
    router = _router_alone(zaya1._frozen(mcfg))
    ref_tails, own_tails, first = [], [], []
    for r in checked if complete else ():
        full, lps = r.result
        full = np.asarray(full)
        p = len(r.prompt)
        ok = full.shape == (p + r.max_new,) and (full[:p] == r.prompt).all()
        complete = complete and bool(ok)
        own = []        # the served router on the reference's own stream
        want, chosen, tails = reference.next_token_logprobs(
            get, cfg, full, pad, on_router=lambda layer, x, r: own.append(
                np.asarray(router(params["layers"][layer], x, r))))
        own = np.stack(own)[:, :len(full) - 1] == chosen
        alone.append((float(own.mean()), own.size))
        ids = np.zeros(pad, np.int32)
        ids[:len(full)] = full
        mine, one_pass = served_choices(mcfg, params, ids, n=len(full) - 1)
        same = np.asarray(mine)[:, :len(full) - 1] == chosen
        agree.append((float(same.mean()), same.size))
        errs.append(np.abs(np.asarray(lps, np.float64) - want[p - 1:]))
        emitted.append((float(np.median(want[p - 1:])),
                        len(np.unique(full[p:])) / r.max_new))
        slot = held.pop(r.index)
        vs_ref = [_rel(s["tail"], t["tail"]) for s, t in zip(slot, tails)]
        vs_own = [_rel(s["tail"], t["tail"][0])
                  for s, t in zip(slot, one_pass)]
        ref_tails += vs_ref
        own_tails += vs_own
        first.append(vs_own[0])
        by_request.append({"prompt": p, "tokens": int(errs[-1].size),
                           "agreement": round(agree[-1][0], 4),
                           "router_alone": round(alone[-1][0], 6),
                           "agreement_by_layer": [
                               round(float(a), 3) for a in same.mean((1, 2))],
                           "median": float(np.median(errs[-1])),
                           "tail_vs_reference_median":
                           round(float(np.median(vs_ref)), 5),
                           "tail_vs_reference_max": round(max(vs_ref), 4),
                           "tail_vs_one_pass_first": float(vs_own[0]),
                           "tail_vs_one_pass_max": round(max(vs_own), 5)})
    errs = np.concatenate(errs) if errs else np.array([np.inf])
    complete = complete and bool(np.isfinite(errs).all()) and bool(ref_tails)
    worst, rms = float(errs.max()), float(np.sqrt(np.mean(errs ** 2)))
    median, p99 = float(np.median(errs)), float(np.percentile(errs, 99))
    agreed, agreed_alone = (
        sum(a * w for a, w in pairs) / max(sum(w for _a, w in pairs), 1)
        for pairs in (agree, alone))
    inf = float("inf")
    t_ref = float(np.median(ref_tails)) if complete else inf
    t_first = max(first) if complete else inf
    t_own = max(own_tails) if complete else inf
    if not complete:
        worst = rms = median = p99 = inf
        agreed = agreed_alone = 0.0
    say("serve.correct", requests=n, checked=len(checked), complete=complete,
        cache_dtype=cache_dtype, rows=json.dumps(
            sorted(slots[r.index] for r in checked) if slots else None),
        router_agreement=agreed, agreement_limit=ROUTER_AGREEMENT,
        router_alone_agreement=agreed_alone, alone_limit=ROUTER_ALONE,
        logprob_median_abs_err=median, median_limit=LOGPROB_MEDIAN,
        logprob_rms_err=rms, rms_limit=LOGPROB_RMS, logprob_p99_abs_err=p99,
        logprob_max_abs_err=worst, over_0p2=int((errs > 0.2).sum()),
        compared=int(errs.size),
        # (a model that only repeats itself would read a logprob of 0 and one
        # distinct token: what the next two say it is not)
        reference_logprob_median=float(np.median([m for m, _d in emitted]))
        if emitted else inf,
        distinct_tokens_share=float(np.mean([d for _m, d in emitted]))
        if emitted else 0.0, tail_median_rel_err=t_ref,
        tail_median_rtol=TAIL_MEDIAN,
        tail_max_vs_reference=max(ref_tails) if complete else inf,
        tail_first_layer_rel_err=t_first, first_layer_rtol=TAIL_RTOL,
        tail_worst_vs_one_pass=t_own, pairs_exact=pairs_ok,
        resumed_exact=resumed_ok, by_request=json.dumps(by_request),
        counters=json.dumps(got))
    _last_check.update(router_agreement=agreed,
                       router_alone_agreement=agreed_alone,
                       tail_median_rel_err=t_ref,
                       tail_first_layer_rel_err=t_first,
                       tail_worst_vs_one_pass=t_own)
    return {"ok": complete and pairs_ok and resumed_ok
            and agreed >= ROUTER_AGREEMENT and agreed_alone >= ROUTER_ALONE
            and median <= LOGPROB_MEDIAN
            and rms <= LOGPROB_RMS and t_ref <= TAIL_MEDIAN
            and t_first <= TAIL_RTOL,
            "max_abs_err": worst, "rms_err": rms, "median_abs_err": median,
            "p99_abs_err": p99, "held_pairs": got["moe_held_pairs_total"],
            "held_pairs_reference": got["moe_pairs_total"]}


def _kernel_shapes(spec, traced: Dict) -> Dict:
    """What the readers of the ranged attention and the gated grouped matmuls
    need: the published widths and what the traced window's calls covered
    (``traced``: the engine's counters from the profiler's start to its stop;
    empty untraced)."""
    cfg = spec.config
    layers = cfg["num_hidden_layers"]
    covered = None
    if traced:
        # every layer pages: every cached key a query saw, once a layer
        covered = {
            "full": {"keys_decode": traced["attn_keys_decode_total"] * layers,
                     "keys_prefill":
                     traced["attn_keys_prefill_total"] * layers},
            "rows_decode": traced["slot_rounds"]}
    return {
        "ranged": {"kv_heads": cfg["num_key_value_heads"],
                   "head_dim": cfg["head_dim"], "itemsize": 2, "window": None,
                   "layers": {"full": {
                       "count": layers,
                       "heads": layers * cfg["num_attention_heads"]}},
                   "traced": covered},
        "moe": {"hidden": cfg["hidden_size"],
                "width": cfg["moe_intermediate_size"], "itemsize": 2,
                "traced": {"rows": traced["moe_held_pairs_total"],
                           "experts_hit": traced["moe_experts_hit_total"]}
                if traced else None}}


# This cell's new reader (``layer_metrics/serve.cca_mix_share_pct.py``) is NOT
# an entry of ``BENCHMARK.json``: ``tests/bench/test_train_parts.py`` pins the
# END of ``per_layer`` to the train readers, a metric put in the middle reads
# as a change to what was there, and neither file is this PR's to edit
# (PERF.md section 7 (n)). A traced run reads it all the same, into ``notes``.
NOTED_READERS = ("serve.cca_mix_share_pct",)
# ... and an accepted reader whose list ``tests/bench/test_retention_cells.py``
# pins to its own cell: read from the window's counters, traced or not
PINNED_READERS = ("serve.state_resumed_chunks_pct",)


def run(ctx) -> Dict:
    with _in_place_of(serve_latent, Server=Server, _check=_check,
                      _kernel_shapes=_kernel_shapes, _delta=_delta):
        out = serve_latent.run(ctx)
    out["notes"].update(_last_check)
    # and the readers whose cell lists tests pin to other cells: what they
    # would read here goes into ``notes`` under their own names
    for name in ("router", "experts"):
        share = part_time.share(out["shapes"], name)
        if share is not None:
            out["notes"][f"part_{name}_share_pct"] = share
    for name in PINNED_READERS + (NOTED_READERS if ctx["trace"] else ()):
        value = read_layer_metric(name).reduce(
            out.get("trace"), out["counters"], out["spans"], out["shapes"])
        if value is not None:
            out["notes"][name[len("serve."):]] = float(value)
    return out


def sweep(ctx, rates) -> None:
    with _in_place_of(serve, Server=Server):
        serve.sweep(ctx, rates)
