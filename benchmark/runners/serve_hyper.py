"""Serve cells of a model whose residual path is SEVERAL streams
(manifold-constrained hyper-connections: ``Xing4ForCausalLM`` is the first)
around latent attention and a whole expert layer, behind
``serving.GenerationEngine`` under the open loop of ``runners/serve.py``. The
window, its bookkeeping, the tracer in two steps and the result line are
``serve_latent.run``'s — called, not copied: this runner's ``Server``,
``_check``, ``_kernel_shapes`` and counter names take the place of that
module's while it runs (``serve_window._in_place_of``). What differs:

- the engine gets its page pool from the configuration
  (``system.engine.num_pages``: every expert and the whole vocabulary are on
  the chip, and the default pool of slots x max_seq_len would not fit
  beside them);
- ``correct``: ``max_slots`` seeded requests of the cell's own lengths go
  TOGETHER through the engine that served the window (chunked prefill, the
  carried step, decode through the latent cache); every ``check_every``-th
  asks for logprobs. Then the engine is closed, its caches are given back,
  and the plain reference computes on the chip at ``highest`` precision, from
  ONE full forward over the engine's own output: the experts its router
  chooses at every (expert layer, token) and the next-token logprobs. The
  router's choice comes first — the share of the reference's top-4 that the
  SERVED blocks took over the same tokens (``models.xing4.routed_experts``:
  ``block_fn``, the function the engine's programs trace, in bfloat16 with
  its kernels, over the whole sequence at once) — then the logprobs (median
  and rms; the 99th percentile and the maximum are printed), the routed pairs
  and the residual path's mixes counted exactly and every pair held (the
  limits, below);
- the readers get ``shapes.mla``, ``shapes.moe`` and ``shapes.mhc``: the
  (token, sublayer) mixes the traced window's programs ran, from the
  engine's ``mhc_mix_tokens_total`` at the profiler's start and stop.
"""
from __future__ import annotations

import importlib
import json
import time
from typing import Dict

import numpy as np

from ..lib import part_time, traffic
from ..lib.harness import say
from . import serve, serve_latent
from .serve import _complete
from .serve_recurrent import _weights_getter, model_config
from .serve_window import _in_place_of

# The engine multiplies in bfloat16 (float32 streams, maps, router, norms and
# logits), scores every head against bfloat16 latent rows in the absorbed
# form, prefills in chunks and mixes its four streams with the Pallas pair
# (three bfloat16 passes on the skinny projection); the reference is one
# float32 forward at `highest`, non-absorbed, Sinkhorn a Python loop. Three
# limits; any one failing is not correct (the 99th percentile and the maximum
# are printed, not limited: below). Readings on the chip (my chip runs, PR 53;
# PERF.md section 6):
#   as configured, 29 seeds: agreement 0.9677-0.9888, median 0.0221-0.0816,
#                            rms 0.226-0.760, p99 0.95-3.57, max 1.60-8.68
#   control (i) - the reference with every matmul operand of F and the
#   would-be cache row rounded to 3 mantissa bits (`lax.reduce_precision(x, 8,
#   3)`: the nearest precision below the bfloat16 the configuration states),
#   seed 5300000201:      agreement 0.8419, median 0.858, rms 1.860, p99 5.58
#   control (ii) - the reference with hc_sinkhorn_iters 1, same seed:
#                         agreement 0.8995, median 0.422, rms 1.503, p99 4.91
#   control (iii) - the reference with H_res = I, same seed:
#                         agreement 0.7159, median 2.315, rms 3.192, p99 7.64
#   (the configured run of that seed: 0.9815, 0.0325, 0.501, 2.58)
# Two kinds of error, as in serve_latent.py. Rounding moves every token a
# little: the MEDIAN reads it alone. It is 3 x what cells 6-12 read (0.010-
# 0.013) because four rows fan the rounding of every sublayer's output back
# through H_post (0 to 2) and H_res, and it grows with what a request has
# emitted (a request's own median: 0.022 after 20 tokens, 0.10 after 227), so
# it moves with WHICH requests a seed checks: 0.022-0.082. Its limit sits 2.2
# x above the largest seen and 2.3 x under the weakest control, (ii); (i) and
# (iii) miss it by 4.8 x and 12.9 x. And a router compares 64 float32 scores:
# where the 4th and the 5th lie within the bfloat16 noise of the stream the
# served blocks and the reference choose differently - 1.1-3.2 % of the
# choices, so a token in four to six meets one flip in its 16 - and every
# expert is held, so each such token's MLP branch changes and its logprob
# moves by tenths to units: 11-24 % of the tokens are off by more than 0.2.
# Those set the rms (limit 1.38 x above the largest seen, 1.43 x under (ii)),
# and the 99th percentile and the maximum, which are extremes of them:
# between the p99's largest reading (3.57) and control (ii)'s (4.91) no limit
# has room on both sides, so it has NONE, as the maximum has none in
# serve_latent.py. The AGREEMENT reads the choice itself: a wrong stream (a
# mix left out, a map from one iteration) moves every router's input, and the
# share of the reference's choices that the served blocks miss goes from
# 0.011-0.032 to 0.10 and more; its limit (0.055 missed) sits 1.7 x above the
# largest seen and 1.8 x under (ii). Each control fails the median by more
# than 2 x, and (i) and (iii) the agreement and the rms too.
ROUTER_AGREEMENT = 0.945
LOGPROB_MEDIAN = 0.18
LOGPROB_RMS = 1.05

_WINDOW_COUNTERS = serve_latent._WINDOW_COUNTERS + (
    "mhc_mix_tokens_total", "rounds_carried_total", "kv_pages_written_total",
    "kv_rows_written_total")


def _delta(c1: Dict, c0: Dict, names=_WINDOW_COUNTERS) -> Dict:
    return {k: c1.get(k, 0) - c0.get(k, 0) for k in names}


class Server(serve.Server):
    """The system under test; the request side is ``serve.Server``'s."""

    def __init__(self, ctx):
        import paddle_tpu as paddle
        from paddle_tpu import models, serving

        spec = ctx["spec"]
        self.cfg, self.engine_cfg = spec.config, spec.config["system"]["engine"]
        paddle.seed(ctx["seed"] % (2 ** 31 - 1))
        t = time.perf_counter()
        self.model = getattr(models, self.cfg["system"]["model_class"])(
            model_config(self.cfg))
        self.model.eval()
        t_model = time.perf_counter() - t
        e = self.engine_cfg
        self.eng = serving.GenerationEngine(
            self.model, serving.GenerationConfig(
                max_slots=e["max_slots"], max_seq_len=e["max_seq_len"],
                page_len=e["page_len"],
                prefill_buckets=tuple(e["prefill_buckets"]),
                prefix_cache=e["prefix_cache"], max_queue=e["max_queue"],
                num_pages=e["num_pages"]))
        t = time.perf_counter()
        self.eng.warmup()
        say("serve.setup", model_s=round(t_model, 2),
            warmup_s=round(time.perf_counter() - t, 2),
            cache_hits=ctx["compiles"].hits,
            cache_misses=ctx["compiles"].misses,
            kv_pool_bytes=self.eng._kv_pool_bytes(), **e)
        self.eng.start()
        # engine spans are on time.monotonic, this side on perf_counter
        self.clock_offset = time.perf_counter() - time.monotonic()

    def say_cache(self, when: str) -> None:
        st = self.eng.stats()
        kv = st["kv_pages"]
        say("serve.cache", when=when, active_slots=st["active_slots"],
            pages_live=kv["pages_live"], pages_peak=kv["pages_peak"],
            pages=kv["pages_total"])


def agreement(mine: np.ndarray, theirs: np.ndarray) -> float:
    """The share of ``theirs`` ``[.., k]`` (each row a SET of experts) that
    ``mine`` holds in the same row."""
    hit = (mine[..., :, None] == theirs[..., None, :]).any(-2)
    return float(hit.mean())


def _check(server: Server, ctx) -> Dict:
    from paddle_tpu.models import xing4

    spec = ctx["spec"]
    tr, cfg, eng = spec.workload["traffic"], spec.config, server.eng
    n = int(server.engine_cfg["max_slots"])
    every = int(spec.workload.get("check_every", 16))
    p_lens = traffic.lognormal_quantiles(n, tr["prompt_len"])
    o_lens = traffic.lognormal_quantiles(n, tr["output_len"])[::-1]
    rng = np.random.default_rng(np.random.SeedSequence([ctx["seed"], 99]))
    order = rng.permutation(n)  # the quantiles come sorted: spread them
    reqs = [traffic.Request(i, 0.0, rng.integers(
        0, cfg["vocab_size"], int(p_lens[k]), dtype=np.int64),
        int(o_lens[k])) for i, k in enumerate(order)]
    checked = reqs[::every]
    server.say_cache("after_window")
    c0 = server.counters()
    for r in reqs:
        server.send(r, logprobs=r.index % every == 0)
    server.drain(reqs, timeout=float(spec.workload.get("check_timeout_s",
                                                       240)))
    c1 = server.counters()
    server.say_cache("after_check")
    server.close()
    complete = all(_complete(r) for r in reqs)
    cache_dtype = str(eng._pool.k[0].dtype)
    complete = complete and cache_dtype == cfg["system"]["cache_dtype"]
    eng.release_caches()
    got = _delta(c1, c0)
    # every token but a request's last goes once through every expert layer
    # and twice through every layer's residual path
    consumed = sum(len(r.prompt) + r.max_new - 1 for r in reqs)
    layers = cfg["num_hidden_layers"]
    pairs_ok = got["moe_pairs_total"] == consumed * \
        cfg["num_experts_per_tok"] * (layers - cfg["first_k_dense_replace"])
    held_ok = got["moe_held_pairs_total"] == got["moe_pairs_total"]
    mixes_ok = got["mhc_mix_tokens_total"] == consumed * 2 * layers
    reference = importlib.import_module(
        "benchmark.lib." + cfg["system"]["reference"])
    pad = int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"])
    get = _weights_getter(server.model)
    mcfg = server.model.config
    params = {"embed": get("embed", -1), "layers": get("layers", -1)}
    errs, agree, by_request = [], [], []
    for r in checked if complete else ():
        full, lps = r.result
        full = np.asarray(full)
        p = len(r.prompt)
        ok = full.shape == (p + r.max_new,) and (full[:p] == r.prompt).all()
        complete = complete and bool(ok)
        want, chosen = reference.next_token_logprobs(get, cfg, full, pad,
                                                     with_chosen=True)
        ids = np.zeros(pad, np.int32)
        ids[:len(full)] = full
        mine = np.asarray(xing4.routed_experts(mcfg, params, ids))
        agree.append((agreement(mine[:, :len(full) - 1], chosen),
                      chosen.size))
        errs.append(np.abs(np.asarray(lps, np.float64) - want[p - 1:]))
        by_request.append({"prompt": p, "tokens": int(errs[-1].size),
                           "agreement": round(agree[-1][0], 4),
                           "median": float(np.median(errs[-1]))})
    errs = np.concatenate(errs) if errs else np.array([np.inf])
    complete = complete and bool(np.isfinite(errs).all())
    worst, rms = float(errs.max()), float(np.sqrt(np.mean(errs ** 2)))
    median, p99 = float(np.median(errs)), float(np.percentile(errs, 99))
    agreed = sum(a * w for a, w in agree) / max(sum(w for _a, w in agree), 1)
    if not complete:
        worst = rms = median = p99 = float("inf")
        agreed = 0.0
    say("serve.correct", requests=n, checked=len(checked), complete=complete,
        cache_dtype=cache_dtype, router_agreement=agreed,
        agreement_limit=ROUTER_AGREEMENT, logprob_max_abs_err=worst,
        logprob_rms_err=rms, rms_limit=LOGPROB_RMS,
        logprob_median_abs_err=median, median_limit=LOGPROB_MEDIAN,
        logprob_p99_abs_err=p99,
        over_0p2=int((errs > 0.2).sum()), compared=int(errs.size),
        pairs_exact=pairs_ok, all_pairs_held=held_ok, mixes_exact=mixes_ok,
        by_request=json.dumps(by_request), counters=json.dumps(got))
    return {"ok": complete and pairs_ok and held_ok and mixes_ok
            and agreed >= ROUTER_AGREEMENT and rms <= LOGPROB_RMS
            and median <= LOGPROB_MEDIAN,
            "max_abs_err": worst, "rms_err": rms, "median_abs_err": median,
            "p99_abs_err": p99, "router_agreement": agreed,
            "held_pairs": got["moe_held_pairs_total"],
            "held_pairs_reference": got["moe_pairs_total"]}


_latent_shapes = serve_latent._kernel_shapes   # theirs, before mine replaces it


def _kernel_shapes(spec, traced: Dict) -> Dict:
    """``serve_latent``'s ``mla`` and ``moe`` (the published widths and what
    the traced window's calls covered) and ``mhc``: the streams' shape and
    the (token, sublayer) mixes the traced window's programs ran."""
    cfg = spec.config
    return {**_latent_shapes(spec, traced),
            "mhc": {"streams": cfg["hc_mult"], "hidden": cfg["hidden_size"],
                    "itemsize": 4,
                    "traced": {"mixes": traced["mhc_mix_tokens_total"]}
                    if traced else None}}


def run(ctx) -> Dict:
    checked = {}

    def check(server, ctx):
        checked.update(_check(server, ctx))
        return checked

    with _in_place_of(serve_latent, Server=Server, _check=check,
                      _kernel_shapes=_kernel_shapes, _delta=_delta):
        out = serve_latent.run(ctx)
    c = out["counters"]
    pages = c["kv_pages_written_total"] * \
        ctx["spec"].config["system"]["engine"]["page_len"]
    # the readers whose cell lists tests pin to other cells: what they would
    # read here goes into ``notes`` under their own names
    out["notes"].update(
        router_agreement=checked["router_agreement"],
        mhc_mix_tokens=c["mhc_mix_tokens_total"],
        carried_rounds_pct=100.0 * c["rounds_carried_total"]
        / max(c["decode_steps"], 1),
        page_write_pct=100.0 * pages
        / max(pages + c["kv_rows_written_total"], 1))
    for name in ("router", "experts"):
        share = part_time.share(out["shapes"], name)
        if share is not None:
            out["notes"][f"part_{name}_share_pct"] = share
    return out


def sweep(ctx, rates) -> None:
    with _in_place_of(serve, Server=Server):
        serve.sweep(ctx, rates)


