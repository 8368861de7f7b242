"""Serve cells of a model whose paged cache holds ONE latent row a token (the
engine's ``cache_spec``: ``OpenPanguMoEForCausalLM`` is the first) and whose
expert layers hold a share of their experts, behind
``serving.GenerationEngine`` under the open loop of ``runners/serve.py``. The
load thread, the request bookkeeping, the warm-up and the sweep are
``serve.py``'s, the server (model and engine from the configuration's
``system`` group) and the two-step tracer ``serve_recurrent.py``'s: imported,
not copied. What differs:

- prompts are longer than the largest prefill bucket, so an admission is a
  run of window calls (chunked prefill); the engine runs with no prefix cache;
- ``correct``: ``max_slots`` seeded requests of the cell's own lengths go
  TOGETHER through the engine that served the window (chunked prefill, then
  decode through the latent cache, every slot live); every
  ``check_every``-th of them asks for logprobs. When they are done the engine
  is closed, its caches are given back, and the plain reference computes, on
  the chip at ``highest`` precision — one layer's weights upcast at a time,
  attention a group of heads at a time, one expert at a time, the head a
  slice of the vocabulary at a time — the next-token logprobs over the
  engine's own output from ONE full forward, and the routed pairs that met a
  held expert. Logprobs (median, rms, 99th percentile) and the held share
  are compared, the routed pairs counted exactly, and the cache's dtype is
  the configuration's (the limits, below);
- a traced run stops the profiler, lets the load end and the engine drain,
  and only then reduces the trace;
- the readers get ``shapes.kind = "serve"``, ``shapes.mla`` and
  ``shapes.moe``: what the traced window's ``pt_mla_paged_attention`` and
  ``gmm`` calls covered, from the engine's counters at the profiler's start
  and stop.
"""
from __future__ import annotations

import importlib
import json
import statistics
import time
from typing import Dict

import numpy as np

from ..lib import traffic
from ..lib.harness import say
from ..lib.stats import percentile
from . import serve
from .serve import _complete, _host_warm, _offer, _sleep_until
from .serve_recurrent import Server, _Tracer, _weights_getter

# The engine multiplies in bfloat16 (float32 residual stream, router, norms
# and logits), scores every head against bfloat16 latent rows in the absorbed
# form and prefills in chunks; the reference is one float32 forward at
# `highest`, non-absorbed. Four limits; any one failing is not correct.
#
# |engine logprob - reference logprob| over the ~1600 tokens the 8 checked
# requests emit: median, rms and 99th percentile (the maximum is printed, not
# limited: below). Readings on the chip (my chip runs, PR 32; PERF.md
# section 6):
#   as configured, 16 seeds: median 0.0097-0.0111, rms 0.042-0.066,
#                            p99 0.11-0.30, max 0.48-1.45
#   control — the reference with every matmul operand and the would-be cache
#   row rounded to 3 mantissa bits (fp8-e4m3's, `lax.reduce_precision(x, 8,
#   3)`: what a scaled fp8 matmul keeps, the nearest precision below the
#   bfloat16 the configuration states), seed 3200000029:
#                            median 0.264, rms 0.444, p99 1.28, max 2.15
# Two kinds of error. Rounding moves every token a little: the MEDIAN reads
# it alone, 25 x apart between the two readings; its limit sits 5 x from
# each. And a router decides by comparing 256 float32 scores: where the 8th
# and the 9th lie within the bfloat16 noise of their inputs the engine and the
# reference choose differently, and if either choice is a held expert that
# token's MLP branch changes by a third before its norm and its logprob moves
# by tenths. About 1 token in 90 is off by more than 0.2 so; those set the
# rms and the p99 (limits 2-2.6 x above the largest seen, 2.1-2.6 x under
# the control) and the maximum, which is an extreme of them and moves with
# the seed (0.48-1.45) to within 1.5 x of the control's: no limit between the
# two readings has room on both sides, and one above both (3.0 at first) is
# set by no reading, so the maximum has NO limit (REVIEW, PR 32). A wrong
# page, mask, position, chunk offset or expert moves every token after it:
# the median and the p99 read that.
LOGPROB_MEDIAN = 0.05
LOGPROB_RMS = 0.17
LOGPROB_P99 = 0.6
# The routed (token, choice) pairs that met a held expert, over exactly the
# positions of the reference's forward (the recount: the checked requests'
# own output but its last token, prefilled once more): the engine's count
# against the reference's, relative. A router flip moves it by one either
# way (as configured 0.00003-0.0015 of the count, the control 0.0030); a wrong
# held range, or padding that routes, by far more. The recount runs PREFILL
# programs only: the held count of the decode rounds is not held against the
# reference (the program sums its counters over rows, so a request's share of
# a round cannot be read), only their routed pairs are counted exactly
# (`pairs_exact`: an idle row that routed would show there).
HELD_PAIRS_RTOL = 0.005

_WINDOW_COUNTERS = (
    "decode_steps", "slot_rounds", "tokens_total", "prompt_tokens_total",
    "prefills_total", "prefill_chunks_total", "moe_pairs_total",
    "moe_held_pairs_total", "moe_experts_hit_total",
    "attn_keys_decode_total", "attn_keys_prefill_total")


def _delta(c1: Dict, c0: Dict, names=_WINDOW_COUNTERS) -> Dict:
    return {k: c1.get(k, 0) - c0.get(k, 0) for k in names}


def _check(server: Server, ctx) -> Dict:
    """``max_slots`` seeded requests of the cell's lengths, sent together
    through the engine that served the window (the worker admits all of them,
    one a slot: every row of the latent cache live); every ``check_every``-th
    asks for logprobs. Then, the engine closed and its caches given back, the
    plain reference runs over the engine's own output."""
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
    c0 = server.counters()
    for r in reqs:
        server.send(r, logprobs=r.index % every == 0)
    server.drain(reqs, timeout=float(spec.workload.get("check_timeout_s",
                                                       240)))
    c1 = server.counters()
    complete = all(_complete(r) for r in reqs)
    # the recount: what the checked requests consumed (all but their last
    # token) goes through the engine once more, as prompts of one new token,
    # so that the engine's routed pairs over exactly the positions of the
    # reference's ONE forward can be read from its counters
    again = [traffic.Request(r.index, 0.0, np.asarray(r.result[0])[:-1], 1)
             for r in checked] if complete else []
    for r in again:
        server.send(r)
    server.drain(again, timeout=120)
    c2 = server.counters()
    server.close()
    complete = complete and all(_complete(r) for r in again)
    cache_dtype = str(eng._pool.k[0].dtype)
    complete = complete and cache_dtype == cfg["system"]["cache_dtype"]
    eng.release_caches()
    got, recount = _delta(c1, c0), _delta(c2, c1)
    # every token but a request's last is consumed once by every expert layer
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    per_token = cfg["num_experts_per_tok"] * layers
    pairs_ok = got["moe_pairs_total"] == per_token * sum(
        len(r.prompt) + r.max_new - 1 for r in reqs) and \
        recount["moe_pairs_total"] == per_token * sum(
            len(r.prompt) for r in again)
    reference = importlib.import_module(
        "benchmark.lib." + cfg["system"]["reference"])
    pad = int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"])
    get = _weights_getter(server.model)
    errs, held_ref = [], 0
    for r in checked if complete else ():
        full, lps = r.result
        full = np.asarray(full)
        p = len(r.prompt)
        ok = full.shape == (p + r.max_new,) and (full[:p] == r.prompt).all()
        complete = complete and bool(ok)
        want, held = reference.next_token_logprobs(get, cfg, full, pad,
                                                   with_pairs=True)
        errs.append(np.abs(np.asarray(lps, np.float64) - want[p - 1:]))
        held_ref += held
    errs = np.concatenate(errs) if errs else np.array([np.inf])
    complete = complete and bool(np.isfinite(errs).all())
    worst, rms = float(errs.max()), float(np.sqrt(np.mean(errs ** 2)))
    median, p99 = float(np.median(errs)), float(np.percentile(errs, 99))
    held_got = recount["moe_held_pairs_total"]
    held_err = abs(held_got - held_ref) / max(held_ref, 1)
    if not complete:
        worst = rms = median = p99 = held_err = float("inf")
    say("serve.correct", requests=n, checked=len(checked), complete=complete,
        cache_dtype=cache_dtype, logprob_max_abs_err=worst,
        logprob_rms_err=rms, rms_limit=LOGPROB_RMS,
        logprob_median_abs_err=median, median_limit=LOGPROB_MEDIAN,
        logprob_p99_abs_err=p99, p99_limit=LOGPROB_P99,
        over_0p2=int((errs > 0.2).sum()), compared=int(errs.size),
        pairs_exact=pairs_ok, held_pairs=held_got,
        held_pairs_reference=held_ref, held_pairs_rel_err=held_err,
        held_rtol=HELD_PAIRS_RTOL,
        held_share_all=got["moe_held_pairs_total"]
        / max(got["moe_pairs_total"], 1), counters=json.dumps(got))
    return {"ok": complete and pairs_ok and rms <= LOGPROB_RMS
            and median <= LOGPROB_MEDIAN and p99 <= LOGPROB_P99
            and held_err <= HELD_PAIRS_RTOL,
            "max_abs_err": worst, "rms_err": rms, "median_abs_err": median,
            "p99_abs_err": p99, "held_pairs": held_got,
            "held_pairs_reference": held_ref}


def _kernel_shapes(spec, traced: Dict) -> Dict:
    """What the readers of the two new kernels need: the published widths and
    what the traced window's calls covered (``traced`` is the engine's
    counters from the profiler's start to its stop; empty untraced)."""
    from paddle_tpu.serving.paged_kv import latent_width

    cfg = spec.config
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    layers = cfg["num_hidden_layers"]
    return {
        "mla": {"heads": cfg["num_attention_heads"],
                "row_width": latent_width(latent), "latent_dim": latent,
                "value_dim": cfg["kv_lora_rank"], "itemsize": 2,
                "layers": layers,
                "traced": {"keys_decode": traced["attn_keys_decode_total"],
                           "rows_decode": traced["slot_rounds"],
                           "keys_prefill": traced["attn_keys_prefill_total"]}
                if traced else None},
        "moe": {"hidden": cfg["hidden_size"],
                "width": cfg["moe_intermediate_size"], "itemsize": 2,
                "traced": {"rows": traced["moe_held_pairs_total"],
                           "experts_hit": traced["moe_experts_hit_total"]}
                if traced else None}}


def run(ctx) -> Dict:
    spec, seed, seconds = ctx["spec"], ctx["seed"], ctx["seconds"]
    tr = spec.workload["traffic"]
    assert tr["kind"] == "open_loop", tr["kind"]
    server = Server(ctx)
    traced: Dict = {}
    try:
        _host_warm(server, ctx)
        tail = float(spec.workload.get("trace_seconds", 1)) \
            if ctx["trace"] else 0.0
        reqs = traffic.open_loop_schedule(tr, spec.config["vocab_size"],
                                          seed, seconds)
        reqs_all = list(reqs)
        if tail:  # the same mix goes on under the profiler
            extra = traffic.open_loop_schedule(
                tr, spec.config["vocab_size"], seed + 1, tail)
            for r in extra:
                r.due += seconds
            reqs_all += extra
        misses_before = ctx["compiles"].misses
        c0 = server.counters()
        setup_s = time.time() - ctx["t_process_start"]
        th, t0 = _offer(server, reqs_all)
        t_end = t0 + seconds
        _sleep_until(t_end)
        c1 = server.counters()
        compiled_in_window = ctx["compiles"].misses - misses_before
        tracer = _Tracer(spec.name, ctx["trace"])
        if tail:
            tracer.start()
            t_start = server.counters()
            _sleep_until(t_end + tail)
            traced = _delta(server.counters(), t_start)
            tracer.stop()
            if ctx.get("dump_trace"):
                from ..lib import trace_dump

                trace_dump.dump(tracer, ctx["dump_trace"])
        th.join()
        server.drain(reqs_all, timeout=float(
            spec.workload.get("drain_timeout_s", 120)))
        t_drained = time.perf_counter()
        tracer.reduce()  # the engine is idle now
        spans = server.engine_spans()
        check = _check(server, ctx)
    finally:
        server.close()

    # -- this side's numbers, over ALL requests due in the window ---------------
    ttft, gaps, late, streamed, failed = [], [], [], 0, 0
    for r in reqs:
        due = t0 + r.due
        late.append((r.t_send - due) * 1e3)
        if not _complete(r):
            failed += 1  # failed, refused or cut short
        first = r.stamps[0] if r.stamps else t_drained
        ttft.append((first - due) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(r.stamps, r.stamps[1:])
                    if b <= t_end)
        streamed += sum(1 for t in r.stamps if t <= t_end)
    in_win = lambda xs: [ms for t, ms in xs if t0 <= t < t_end]  # noqa: E731
    window = _delta(c1, c0)
    e2e = {"serve_tokens_per_s": streamed / seconds,
           "itl_p95_ms": percentile(gaps, 95) if gaps else float("nan"),
           "setup_s": setup_s}
    say("serve.window", requests=len(reqs), failed=failed,
        rate_rps=tr["rate_rps"], ttft_p50_ms=statistics.median(ttft),
        ttft_p95_ms=percentile(ttft, 95),
        itl_p50_ms=statistics.median(gaps) if gaps else None,
        itl_p95_ms=e2e["itl_p95_ms"], gaps=len(gaps),
        serve_tokens_per_s=e2e["serve_tokens_per_s"],
        offered_tokens_per_s=sum(r.max_new for r in reqs) / seconds,
        setup_s=setup_s, compiled_in_window=compiled_in_window,
        counters=json.dumps(window), traced=json.dumps(traced))
    e = server.engine_cfg
    return {
        "correct": check["ok"] and failed == 0 and compiled_in_window == 0,
        "attempted": len(reqs), "failed": failed,
        "end_to_end": e2e,
        "units": {"serve_tokens_per_s": "tokens/s", "itl_p95_ms": "ms",
                  "setup_s": "s"},
        "counters": {**window, "window_s": seconds,
                     "max_slots": e["max_slots"]},
        "spans": {"gen_late_ms": late, "ttft_ms": ttft, "itl_ms": gaps,
                  "queue_ms": in_win(spans["queue"]),
                  "prefill_ms": in_win(spans["prefill"])},
        "shapes": {"kind": "serve", "chips": spec.chips,
                   **_kernel_shapes(spec, traced)},
        "trace": tracer.summary,
        "notes": {"requests": len(reqs),
                  "ttft_p50_ms": statistics.median(ttft),
                  "ttft_mean_ms": statistics.fmean(ttft),
                  "itl_p50_ms": statistics.median(gaps) if gaps else None,
                  "itl_p95_ms": e2e["itl_p95_ms"],
                  "itl_mean_ms": statistics.fmean(gaps) if gaps else None,
                  "streamed_tokens": streamed, "gaps": len(gaps),
                  "prefill_chunks": window["prefill_chunks_total"],
                  "decode_rounds": window["decode_steps"],
                  "logprob_max_abs_err": check["max_abs_err"],
                  "logprob_rms_err": check["rms_err"],
                  "logprob_median_abs_err": check["median_abs_err"],
                  "logprob_p99_abs_err": check["p99_abs_err"],
                  "held_pairs": check["held_pairs"],
                  "held_pairs_reference": check["held_pairs_reference"],
                  "cache_misses": ctx["compiles"].misses},
    }


def sweep(ctx, rates) -> None:
    """``serve.sweep`` builds its ``Server`` by name: this runner's takes its
    place while it runs, and nothing else of the sweep differs."""
    theirs, serve.Server = serve.Server, Server
    try:
        serve.sweep(ctx, rates)
    finally:
        serve.Server = theirs
