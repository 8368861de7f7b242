"""Serve cells of a model whose LATENT paged cache is of two layer kinds (the
engine's ``cache_spec`` ``latent`` with ``layers``: ``full`` layers keep a
latent row and an index key for every token and attend the keys a lightning
indexer selects; ``window`` layers keep rows of their own width for the last
``sliding_window_size`` positions, from a pool of their own;
``Dots3NoteForCausalLM`` is the first) and whose expert layers hold a share of
their experts, behind ``serving.GenerationEngine`` under the open loop of
``runners/serve.py``. The window, its bookkeeping, the tracer in two steps and
the result line are ``serve_latent.run``'s — called, not copied: this runner's
``Server``, ``_check``, ``_kernel_shapes`` and counter names take the place of
that module's while it runs, as ``serve_sparse.py`` and ``serve_window.py`` do.
What differs:

- the engine gets BOTH page pools from the configuration
  (``system.engine.num_pages`` for the full layers' latent rows and index
  keys, ``window_pages`` for the window layers' rows); ``serve.setup`` and
  ``serve.cache`` print each kind's bytes and live pages;
- ``correct`` is ``serve_sparse.py``'s check in two steps — WHICH keys the
  engine's chunk program selects in each full layer
  (``GenerationEngine.selected_keys``) against the plain reference's own
  ``S_t``, then the logprobs against the reference GIVEN that selection —
  with what the window layers add: of the checked requests one is the
  shortest prompt past ``long_prompt`` tokens and one the longest below
  ``short_prompt`` (nothing is selected away below ``index_topk`` positions:
  the full layers are dense there and the window layers alone cut), every
  checked prompt is long enough that every window layer has given pages back,
  and the keys the window layers attended are counted exactly (``sum min(t +
  1, window)`` over positions and window layers) beside the selected ones;
- the readers get ``shapes.mla_window`` (the window layers' widths and what
  the traced window's ``pt_mla_window_attention`` calls covered),
  ``shapes.moe``, ``shapes.ranged.layers`` (the layer counts by kind, which is
  all ``serve.window_keys_pct`` reads of it) and ``shapes.dsa``; what the
  readers whose cell lists tests pin to other cells would read here goes into
  the run's ``notes`` (PERF.md section 7).
"""
from __future__ import annotations

import importlib
import json
import time
from typing import Dict

import numpy as np

from ..lib import harness, part_time, traffic
from ..lib.harness import say
from . import serve, serve_latent, serve_sparse
from .serve import _complete
from .serve_recurrent import _weights_getter, model_config
from .serve_sparse import keys_selected
from .serve_window import _in_place_of

# The engine multiplies in bfloat16 (float32 residual stream, router, norms,
# gates, index weights and logits), scores every head against bfloat16 latent
# rows in the absorbed form — 128 heads against rows of 576 under the
# indexer's exact top-2048 in a full layer, 64 heads against rows of 1088
# within 513 positions in a window layer — and the reference is one float32
# forward at `highest`, non-absorbed, dense under masks. TWO comparisons, as in
# `serve_sparse.py` and for its reasons (an index score of random weights says
# nothing about a key's attention weight, so a key swapped at the 2048th score
# may lead a head's softmax):
#
# (1) THE SELECTION ITSELF, summed over the two full layers: of the
# reference's own `S_t`, what the engine's chunk program selected too — every
# key, and the LEADING HALF (the 1024 keys of largest score, a thousand places
# clear of the threshold). A request's own share is held. Readings on the chip
# (my chip runs, PR 50; PERF.md section 6), the worst checked request of a run:
#   as configured, 31 seeds (4 requests, 54-85 k positions a run): every key
#       0.99649-0.99664, leading half 1.0 in every run (no key of 55-87
#       million missed: 64 index heads fold twice cell 10's, whose leading
#       half reads 0.99986)
#   control `low_precision` (the reference's operands and both would-be cache
#   rows at 3 mantissa bits), seed 5000000711: every key 0.9273, leading half
#       0.99978
SELECTION_SHARED = 0.96       # 11 x the configured deficit, 1/1.8 of the control's
SELECTION_LEAD_SHARED = 0.9999  # the configured deficit is 0; 1/2.2 of the control's
#
# (2) THE LOGPROBS, against the reference GIVEN that selection: what is left
# is rounding, the router's near-ties (cell 6's two kinds) and whatever the
# served programs do differently from the reference — a wrong window edge,
# table, page given back too early, RoPE base, gate or rescale moves every
# token after it. |engine - reference| over the 1360-2780 tokens the 4 checked
# requests emit; any one limit failing is not correct (the maximum is printed,
# not limited: 0.24-0.94):
#   as configured, 31 seeds: median 0.0120-0.0163, rms 0.030-0.046,
#       p99 0.110-0.193, the worst request's own median 0.0207-0.0352
#   control `low_precision`, seed 5000000711: median 0.226, rms 0.335,
#       p99 0.867, request median 0.233
#   control `no_window` (the reference's window layers see every key), seed
#       5000000713: median 0.508, rms 0.775, p99 2.00, request median 0.552
#       (its selection agrees, 0.99650 / 1.0: the full layers come first and
#       the check attends the engine's selection)
# `low_precision` fails every limit of both comparisons, `no_window` every
# limit of the second. The short checked request (< 2048 tokens: the full
# layers dense) is where a fault of the window layers alone would show, the
# long one (> 32768) where one of the selection or of pages long given back
# would: each request's OWN median is held.
LOGPROB_MEDIAN = 0.06     # 3.7 x the largest reading, 1/3.8 of low_precision
LOGPROB_RMS = 0.14        # 3.1 x, 1/2.4
LOGPROB_P99 = 0.45        # 2.3 x, 1/1.9
REQUEST_MEDIAN = 0.08     # 2.3 x, 1/2.9
# the held routed pairs of the recount against the reference's (cell 6's rule
# and limit): 0.00001-0.00076 as configured, 0.0002 in `low_precision`, 0.0019
# in `no_window`
HELD_PAIRS_RTOL = 0.005

_WINDOW_COUNTERS = serve_sparse._WINDOW_COUNTERS + (
    "attn_keys_full_total", "attn_keys_window_total",
    "attn_keys_window_decode_total", "attn_keys_window_prefill_total",
    "attn_rows_walked_window_total", "attn_rows_in_window_total",
    "window_pages_released_total", "window_pages_taken_total")


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
                num_pages=e["num_pages"], window_pages=e["window_pages"]))
        t = time.perf_counter()
        self.eng.warmup()
        pool = self.eng._pool
        say("serve.setup", model_s=round(t_model, 2),
            warmup_s=round(time.perf_counter() - t, 2),
            cache_hits=ctx["compiles"].hits,
            cache_misses=ctx["compiles"].misses,
            kv_pool_bytes=self.eng._kv_pool_bytes(),
            kv_pool_bytes_by_kind=json.dumps(pool.bytes_by_kind()),
            layer_kinds=",".join(pool.layer_kinds), **e)
        self.eng.start()
        # engine spans are on time.monotonic, this side on perf_counter
        self.clock_offset = time.perf_counter() - time.monotonic()

    def say_cache(self, when: str) -> None:
        """Each kind's live pages against its pool: a window layer's cache is
        bounded by slots x (window + chunk slack), not by tokens cached."""
        st = self.eng.stats()
        kv, c = st["kv_pages"], st["counters"]
        say("serve.cache", when=when, active_slots=st["active_slots"],
            full_pages_live=kv["pages_live"], full_pages_peak=kv["pages_peak"],
            full_pages=kv["pages_total"],
            window_pages_live=kv["window"]["pages_live"],
            window_pages_peak=kv["window"]["pages_peak"],
            window_pages=kv["window"]["pages_total"],
            window_pages_a_slot=self.eng._wbound,
            window_pages_taken_total=c.get("window_pages_taken_total", 0),
            window_pages_released_total=c.get("window_pages_released_total",
                                              0))


def _check_lengths(n: int, tr: Dict, every: int, long_prompt: int,
                   short_prompt: int, rng):
    """The ``n`` requests' (prompt, output) lengths in sending order: the
    cell's quantiles, spread by ``rng``, with the shortest prompt past
    ``long_prompt`` moved into the first checked place and the longest one
    below ``short_prompt`` into the second (the other checked places keep
    what the spread gave them: typical lengths)."""
    p_lens = traffic.lognormal_quantiles(n, tr["prompt_len"])
    o_lens = traffic.lognormal_quantiles(n, tr["output_len"])[::-1]
    order = list(rng.permutation(n))  # the quantiles come sorted: spread them
    past = [k for k in range(n) if p_lens[k] > long_prompt][:1]
    below = [k for k in range(n) if p_lens[k] < short_prompt][-1:]
    for place, k in zip((0, every), past + below if past else below):
        if place < n:
            at = order.index(k)
            order[at], order[place] = order[place], order[at]
    return [(int(p_lens[k]), int(o_lens[k])) for k in order]


def _check(server: Server, ctx) -> Dict:
    spec = ctx["spec"]
    tr, cfg, eng = spec.workload["traffic"], spec.config, server.eng
    n = int(server.engine_cfg["max_slots"])
    every = int(spec.workload.get("check_every", 16))
    long_prompt = int(spec.workload.get("long_prompt", 32768))
    short_prompt = int(spec.workload.get("short_prompt", 2048))
    rng = np.random.default_rng(np.random.SeedSequence([ctx["seed"], 99]))
    lens = _check_lengths(n, tr, every, long_prompt, short_prompt, rng)
    reqs = [traffic.Request(i, 0.0, rng.integers(
        0, cfg["vocab_size"], p, dtype=np.int64), o)
        for i, (p, o) in enumerate(lens)]
    checked = reqs[::every]
    timeout = float(spec.workload.get("check_timeout_s", 900))
    server.say_cache("after_window")
    c0 = server.counters()
    for r in reqs:
        server.send(r, logprobs=r.index % every == 0)
    server.drain(reqs, timeout=timeout)
    c1 = server.counters()
    server.say_cache("after_check")
    complete = all(_complete(r) for r in reqs)
    # the recount (cell 6's): what the checked requests consumed goes through
    # once more, as prompts of one new token, so that the engine's held pairs
    # over exactly the positions of the reference's ONE forward can be read
    again = [traffic.Request(r.index, 0.0, np.asarray(r.result[0])[:-1], 1)
             for r in checked] if complete else []
    for r in again:
        server.send(r)
    server.drain(again, timeout=timeout)
    c2 = server.counters()
    server.close()
    complete = complete and all(_complete(r) for r in again)
    pool = eng._pool
    cache_dtypes = sorted({str(a.dtype) for a in pool.k + pool.v})
    complete = complete and len(pool.v) > 0 and \
        cache_dtypes == [cfg["system"]["cache_dtype"]] and \
        pool.allocator.live_pages == pool.window_allocator.live_pages == 0
    # WHICH keys the engine selects for what each checked request consumed:
    # its own chunk program, kernels, both page tables and arenas once more
    given = {r.index: eng.selected_keys(np.asarray(r.result[0])[:-1])
             for r in checked} if complete else {}
    eng.release_caches()
    got, recount = _delta(c1, c0), _delta(c2, c1)
    reference = importlib.import_module(
        "benchmark.lib." + cfg["system"]["reference"])
    kinds, mlps = reference.layer_lists(cfg)
    n_full, n_window = kinds.count("full"), kinds.count("window")
    # every token but a request's last is consumed once by every layer
    per_token = cfg["num_experts_per_tok"] * mlps.count("sparse")
    consumed = [len(r.prompt) + r.max_new - 1 for r in reqs]
    pairs_ok = got["moe_pairs_total"] == per_token * sum(consumed) and \
        recount["moe_pairs_total"] == per_token * sum(
            len(r.prompt) for r in again)
    topk, window = int(cfg["index_topk"]), int(cfg["sliding_window_size"])
    selected = got["attn_keys_selected_prefill_total"] + \
        got["attn_keys_selected_decode_total"]
    selected_again = recount["attn_keys_selected_prefill_total"] + \
        recount["attn_keys_selected_decode_total"]
    keys_ok = selected == keys_selected(consumed, topk, n_full) and \
        selected_again == keys_selected(
            [len(r.prompt) for r in again], topk, n_full)
    # position t attends min(t + 1, window) keys of a window layer: the
    # selected layers' count with the window for top-k
    window_ok = got["attn_keys_window_total"] == keys_selected(
        consumed, window, n_window) and \
        got["window_pages_released_total"] > 0
    page_len = int(server.engine_cfg["page_len"])
    lengths_ok = any(len(r.prompt) > long_prompt for r in checked) and \
        any(len(r.prompt) < short_prompt for r in checked) and \
        all(len(r.prompt) + r.max_new > window + page_len
            for r in checked)      # every window layer gave pages back
    get = _weights_getter(server.model)
    # one padded length for every request: a whole number of the reference's
    # blocks (it computes only the blocks a request reaches)
    pad = int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"])
    if pad > reference.BLOCK:
        pad = -(-pad // reference.BLOCK) * reference.BLOCK
    errs, held_ref, by_request, shared = [], 0, [], []
    for r in checked if complete else ():
        full, lps = r.result
        full = np.asarray(full)
        p = len(r.prompt)
        ok = full.shape == (p + r.max_new,) and (full[:p] == r.prompt).all()
        complete = complete and bool(ok)
        t = time.perf_counter()
        agreement = []
        want, held = reference.next_token_logprobs(
            get, cfg, full, pad, with_pairs=True, given=given.pop(r.index),
            agreement=agreement)
        errs.append(np.abs(np.asarray(lps, np.float64) - want[p - 1:]))
        # of the reference's own S_t, what the engine selected too: summed
        # over the full layers, every key and the leading half apart
        if len(agreement) != n_full:
            agreement = [(np.zeros(1), np.ones(1)) * 2]
        counts = np.sum(agreement, 0)          # [4, positions]
        shared.append(counts)
        by_request.append({"prompt": p, "tokens": int(errs[-1].size),
                           "median": float(np.median(errs[-1])),
                           "max": float(errs[-1].max()),
                           "over_0p02": int((errs[-1] > 0.02).sum()),
                           "selection_shared": float(
                               counts[0].sum() / counts[1].sum()),
                           "selection_lead_shared": float(
                               counts[2].sum() / counts[3].sum()),
                           "position_min": float(
                               (counts[0] / counts[1]).min()),
                           "reference_s": round(time.perf_counter() - t, 1)})
        held_ref += held
    errs = np.concatenate(errs) if errs else np.array([np.inf])
    shared = np.concatenate(shared, 1) if shared else \
        np.array([[0.0], [1.0]] * 2)
    complete = complete and bool(np.isfinite(errs).all())
    sel_shared = float(shared[0].sum() / shared[1].sum())
    lead_shared = float(shared[2].sum() / shared[3].sum())
    by_shared = min((r["selection_shared"] for r in by_request), default=0.0)
    by_lead = min((r["selection_lead_shared"] for r in by_request),
                  default=0.0)
    worst, rms = float(errs.max()), float(np.sqrt(np.mean(errs ** 2)))
    median, p99 = float(np.median(errs)), float(np.percentile(errs, 99))
    by_median = max((r["median"] for r in by_request), default=float("inf"))
    held_got = recount["moe_held_pairs_total"]
    held_err = abs(held_got - held_ref) / max(held_ref, 1)
    if not complete:
        worst = rms = median = p99 = by_median = held_err = float("inf")
    say("serve.correct", requests=n, checked=len(checked), complete=complete,
        checked_prompts=json.dumps([len(r.prompt) for r in checked]),
        checked_lengths_ok=lengths_ok, cache_dtypes=json.dumps(cache_dtypes),
        logprob_max_abs_err=worst, logprob_rms_err=rms,
        rms_limit=LOGPROB_RMS, logprob_median_abs_err=median,
        median_limit=LOGPROB_MEDIAN, logprob_p99_abs_err=p99,
        p99_limit=LOGPROB_P99, worst_request_median=by_median,
        request_median_limit=REQUEST_MEDIAN,
        over_0p02=int((errs > 0.02).sum()), compared=int(errs.size),
        selection_shared=sel_shared, worst_request_shared=by_shared,
        shared_limit=SELECTION_SHARED, selection_lead_shared=lead_shared,
        worst_request_lead_shared=by_lead, lead_limit=SELECTION_LEAD_SHARED,
        positions=int(shared.shape[1]), pairs_exact=pairs_ok,
        keys_selected_exact=keys_ok, keys_selected=selected,
        window_keys_exact=window_ok,
        window_keys=got["attn_keys_window_total"], held_pairs=held_got,
        held_pairs_reference=held_ref, held_pairs_rel_err=held_err,
        held_rtol=HELD_PAIRS_RTOL, by_request=json.dumps(by_request),
        counters=json.dumps(got))
    return {"ok": complete and pairs_ok and keys_ok and window_ok
            and lengths_ok and rms <= LOGPROB_RMS
            and median <= LOGPROB_MEDIAN and p99 <= LOGPROB_P99
            and by_median <= REQUEST_MEDIAN
            and by_shared >= SELECTION_SHARED
            and by_lead >= SELECTION_LEAD_SHARED
            and held_err <= HELD_PAIRS_RTOL,
            "max_abs_err": worst, "rms_err": rms, "median_abs_err": median,
            "p99_abs_err": p99, "held_pairs": held_got,
            "held_pairs_reference": held_ref,
            "selection_shared": sel_shared,
            "selection_lead_shared": lead_shared}


def _kernel_shapes(spec, traced: Dict) -> Dict:
    """What the readers need of the widths and of what the traced window's
    calls covered (``traced`` is the engine's counters from the profiler's
    start to its stop; empty untraced)."""
    from paddle_tpu.serving.paged_kv import latent_width

    cfg = spec.config
    kinds, _mlps = importlib.import_module(
        "benchmark.lib." + cfg["system"]["reference"]).layer_lists(cfg)
    n_full, n_window = kinds.count("full"), kinds.count("window")
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    wide = cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]
    covered = windowed = None
    if traced:
        covered = {
            "rows_decode": traced["slot_rounds"],
            "keys_decode": traced["attn_keys_decode_total"],
            "keys_prefill": traced["attn_keys_prefill_total"],
            "scored_decode": traced["index_keys_scored_decode_total"],
            "scored_prefill": traced["index_keys_scored_prefill_total"],
            "selected_decode": traced["attn_keys_selected_decode_total"],
            "selected_prefill": traced["attn_keys_selected_prefill_total"]}
        windowed = {   # summed over the window layers
            "rows_decode": traced["slot_rounds"] * n_window,
            "keys_decode": traced["attn_keys_window_decode_total"],
            "keys_prefill": traced["attn_keys_window_prefill_total"]}
    return {
        "mla_window": {"heads": cfg["swa_num_attention_heads"],
                       "row_width": latent_width(wide), "latent_dim": wide,
                       "value_dim": cfg["swa_kv_lora_rank"], "itemsize": 2,
                       "window": cfg["sliding_window_size"],
                       "layers": n_window, "traced": windowed},
        # the layer counts by kind: all ``serve.window_keys_pct`` reads of it
        "ranged": {"layers": {"full": {"count": n_full},
                              "window": {"count": n_window}},
                   "traced": None},
        # the full layers alone score and attend a selection
        "dsa": {"heads": cfg["num_attention_heads"],
                "row_width": latent_width(latent), "latent_dim": latent,
                "value_dim": cfg["kv_lora_rank"],
                "index_heads": cfg["index_n_heads"],
                "index_dim": cfg["index_head_dim"],
                "topk": cfg["index_topk"], "itemsize": 2,
                "layers": n_full, "full_layers": n_full, "traced": covered},
        "moe": {"hidden": cfg["hidden_size"],
                "width": cfg["moe_intermediate_size"], "itemsize": 2,
                "traced": {"rows": traced["moe_held_pairs_total"],
                           "experts_hit": traced["moe_experts_hit_total"]}
                if traced else None}}


# the readers whose cell lists tests pin to other cells: what they would read
# here goes into ``notes`` under their own names, through their own code
_PINNED_READERS = (
    "serve.index_scores_share_pct", "serve.index_scores_roofline_pct",
    "serve.sparse_attention_share_pct", "serve.sparse_attention_roofline_pct",
    "serve.indexer_share_pct")


def run(ctx) -> Dict:
    checked = {}

    def check(server, ctx):
        checked.update(_check(server, ctx))
        return checked

    with _in_place_of(serve_latent, Server=Server, _check=check,
                      _kernel_shapes=_kernel_shapes, _delta=_delta):
        out = serve_latent.run(ctx)
    c = out["counters"]
    cfg = ctx["spec"].config
    pages = c["kv_pages_written_total"] * cfg["system"]["engine"]["page_len"]
    dense = (c["attn_keys_decode_total"] + c["attn_keys_prefill_total"]) * \
        out["shapes"]["dsa"]["full_layers"]
    out["notes"].update(
        carried_rounds_pct=100.0 * c["rounds_carried_total"]
        / max(c["decode_steps"], 1),
        page_write_pct=100.0 * pages
        / max(pages + c["kv_rows_written_total"], 1),
        index_selected_pct=100.0 * (c["attn_keys_selected_decode_total"]
                                    + c["attn_keys_selected_prefill_total"])
        / max(dense, 1),
        window_pages_taken=c["window_pages_taken_total"],
        window_pages_released=c["window_pages_released_total"],
        selection_shared=checked["selection_shared"],
        selection_lead_shared=checked["selection_lead_shared"])
    for name in ("router", "experts"):
        share = part_time.share(out["shapes"], name)
        if share is not None:
            out["notes"][f"part_{name}_share_pct"] = share
    if ctx["trace"]:
        for name in _PINNED_READERS:
            value = harness.read_layer_metric(name).reduce(
                out.get("trace"), c, out.get("spans", {}), out["shapes"])
            if value is not None:
                out["notes"][name.split(".", 1)[1]] = float(value)
    return out


def sweep(ctx, rates) -> None:
    with _in_place_of(serve, Server=Server):
        serve.sweep(ctx, rates)
