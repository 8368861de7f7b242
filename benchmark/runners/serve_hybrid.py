"""Serve cells of a model whose layers are ONE mixer each and keep memory BY
KIND — a recurrent state by slot in the Mamba-2 layers, K/V pages in the
attention layers, nothing in the expert layers (the engine's
``cache_spec["layers"]`` with ``"state"`` / ``"full"`` / ``"none"``:
``NemotronHForCausalLM`` is the first) — behind ``serving.GenerationEngine``
under the open loop of ``runners/serve.py``. The window, its bookkeeping, the
tracer in two steps and the result line are ``serve_latent.run``'s — called,
not copied: this runner's ``Server``, ``_check``, ``_kernel_shapes`` and
counter names take the place of that module's while it runs
(``serve_window._in_place_of``, as ``serve_hyper.py`` does). What is this
file's:

- ``correct``: ``max_slots`` seeded requests of the cell's own lengths go
  TOGETHER through the engine that served the window — every slot's state
  live, the long prompts prefilled in chunks that RESUME the state-space
  state and the conv tail with decode rounds between them; every
  ``check_every``-th asks for logprobs. Then the engine is closed, the
  checked requests' FINAL state is read from their slots' rows, the caches
  are given back, and the plain reference (``system.reference``: float32 at
  ``highest``, the Mamba-2 layers BY THEIR RECURRENCE a token at a time, dense
  attention, every expert by a loop) computes from ONE full forward over the
  engine's own output: (i) the experts its routers choose at every (expert
  layer, token) — compared first, with what the SERVED blocks choose over the
  same tokens (``models.nemotron_h.routed_experts``); (ii) the next-token
  logprobs of what the decode ROUNDS emitted (median and rms; the 99th
  percentile and the maximum are printed); (iii) each checked slot's final
  SSM state and conv tail — by the median head against the reference, and
  the first Mamba-2 layer's worst head and tail against ONE pass of the served
  blocks over the whole sequence (the same call: what chunks, rounds and slots
  did to a state). The counters are held exactly: every routed pair
  held, and a resumed call for every chunk but a prompt's first (the limits,
  below);
- the readers get ``shapes.ssm_step``, ``shapes.ranged`` and
  ``shapes.moe_relu2`` — NOT ``shapes.moe``: ``lib/moe_cost.py`` reckons the
  three matrices of a gated expert, ``lib/moe_relu2_cost.py`` this model's
  two.
"""
from __future__ import annotations

import importlib
import json
import statistics
import time
from typing import Dict

import numpy as np

from ..lib import part_time, traffic
from ..lib.harness import read_layer_metric, say
from . import serve, serve_latent
from .serve import _complete
from .serve_hyper import agreement
from .serve_recurrent import (_rel_err, _slots_in_send_order,
                              _weights_getter, model_config)
from .serve_window import _in_place_of

# The engine multiplies in bfloat16 (float32 residual stream, conv,
# recurrence, state, router, norms and logits), runs the Mamba-2 layers as a
# chunked scan that RESUMES across a prompt's 2048-token calls and as one
# Pallas step a round, the experts as two grouped matmuls, and attends
# bfloat16 pages; the reference is one float32 forward at `highest`, the
# recurrence a token at a time. Five limits; any one failing is not correct.
# Readings on the chip (my chip runs, PR 57; PERF.md section 6):
#   as configured, 17 seeds (15 at 12 requests/s, 2 at 8):
#     agreement 0.9794-0.9819, median 0.0188-0.0217, rms 0.497-0.568,
#     p99 2.49-2.82, max 5.0-8.2; the median head's state error against the
#     reference 0.0059-0.0175; the first Mamba-2 layer's worst head or tail
#     against ONE pass of the served blocks 0.00009-0.00042
#   control (i), `controls_hybrid.py low_precision` - the reference with every
#   matmul operand rounded to 3 mantissa bits (`lax.reduce_precision(x, 8,
#   3)`: what a scaled fp8 matmul keeps, the nearest precision below the
#   bfloat16 the configuration states), seed 5700000201:
#     agreement 0.8108, median 1.211, rms 2.152, p99 6.25, median head 0.212
#     (the first layer against one pass does not involve the reference:
#     0.00019, as configured)
#   control (ii), `controls_hybrid.py bfloat16_state` - the engine's scan and
#   step hand back their SSM state rounded to bfloat16 at every write, same
#   seed: agreement 0.9802, median 0.0179, rms 0.454, median head 0.0085 -
#   none of them sees it - and the first layer against one pass 0.0276
#   (the configured run of that seed: 0.9798, 0.0189, 0.518, 0.0106, 0.00019)
#
# (i) ROUTER_AGREEMENT: the share of the reference's top-6 (a SET a (token,
# expert layer)) that the served blocks took over the same tokens
# (`models.nemotron_h.routed_experts`). A router compares 128 float32 scores:
# where the 6th and the 7th lie within the bfloat16 noise of the stream the two
# choose differently - 1.8-2.1 % of the choices. A wrong stream moves every
# router's input: the control misses 18.9 %. The limit (6 % missed) sits 2.9 x
# above the largest seen and 3.2 x under the control.
ROUTER_AGREEMENT = 0.94
# (ii) |engine logprob - reference logprob| over the 2600-5400 tokens the 8
# checked requests' decode ROUNDS emitted. Two kinds of error, as in
# serve_latent.py. Rounding moves every token a little: the MEDIAN reads it
# alone (limit 7 x above the largest seen, 8 x under the control). And every
# expert is held, so a token that meets one router flip in its 24 choices (one
# in three does) has a sixth of an MLP branch changed and its logprob moves by
# tenths to units: 16-17 % of the tokens are off by more than 0.2. Those set
# the RMS (limit 1.94 x above the largest seen, 1.96 x under the control) and
# the 99th percentile and the maximum, which are extremes of them and are
# printed, not limited (the maximum's largest reading, 8.2, is within 1.13 x
# of the control's 9.3).
LOGPROB_MEDIAN = 0.15
LOGPROB_RMS = 1.1
# (iii) what a checked request leaves in its slot - every Mamba-2 layer's SSM
# state, a head at a time, and conv tail, relative norm - held to TWO things,
# because the issue's statistic (the worst head of any layer against the
# reference) reads the routers' flips and not the state's keeping: a head with
# a long memory keeps the contribution of every token whose upstream expert
# choice differed, and the worst head of 256 reads 0.27-0.93 as configured
# (0.19-0.63 even against one pass of the served blocks, whose own flips
# differ), which leaves no room under the O(1) of a real fault.
# STATE_HEAD_MEDIAN, against the REFERENCE, the median over all heads, layers
# and checked requests: a stale tenant, a wrong row, a state not installed are
# O(1) in every head, flips move a few. Limit 2.9 x above the largest seen,
# 4.2 x under control (i).
# STATE_RTOL, against ONE PASS of the served blocks over the whole sequence
# (the same dtype, kernels and rounding, no chunks, no rounds, no slots), the
# worst head and the tail of the FIRST Mamba-2 layer, which no expert layer
# precedes: what the chunks that resumed, the rounds between them, the
# install and 64-1500 single steps did to a state, to float32 arithmetic. A
# chunk that started from zero, a tail not carried, a round that advanced a
# joining slot are O(0.1-1) there; a state kept in bfloat16 reads 0.0276.
# Limit 7 x above the largest seen, 9 x under control (ii). The logprobs
# cannot tell a bfloat16 state from the float32 the configuration states
# (PR 28's finding, read again here): this limit can.
STATE_HEAD_MEDIAN = 0.05
STATE_RTOL = 0.003

_WINDOW_COUNTERS = serve_latent._WINDOW_COUNTERS + (
    "state_resumes_total", "state_installs_total", "attn_keys_full_total",
    "kv_pages_written_total", "kv_rows_written_total")

_last_check: Dict = {}


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
        kv = self.eng.stats()["kv_pages"]
        say("serve.setup", model_s=round(t_model, 2),
            warmup_s=round(time.perf_counter() - t, 2),
            cache_hits=ctx["compiles"].hits,
            cache_misses=ctx["compiles"].misses,
            kv_pool_bytes=self.eng._kv_pool_bytes(),
            state_pool_bytes=self.eng._state_pool_bytes(),
            layers_by_kind=json.dumps(kv["layers_by_kind"]),
            arenas=json.dumps(kv["arenas"]), **e)
        self.eng.start()
        # engine spans are on time.monotonic, this side on perf_counter
        self.clock_offset = time.perf_counter() - time.monotonic()


def _state_errors(held, states):
    """One request's final state in its slot against another's (the plain
    reference's, or the served blocks' own over the whole sequence): every
    head's relative error (all layers, one list), the worst conv tail's, and
    per layer the error of the head with the longest memory (``states``'
    ``log_decay`` nearest 0, where it says; else the head's own)."""
    heads, tails, long_memory = [], [], []
    for got, want in zip(held, states):
        err = _rel_err(got["ssm"], want["ssm"])
        heads += [float(e) for e in err]
        tails.append(float(_rel_err(got["conv"], want["conv"])))
        if "log_decay" in want:
            long_memory.append(int(np.argmax(np.asarray(want["log_decay"]))))
    return heads, max(tails), long_memory


def _rel_tail(got, want) -> float:
    return float(_rel_err(got["conv"], want["conv"][0]))


def _check(server: Server, ctx) -> Dict:
    from paddle_tpu.models import nemotron_h

    spec = ctx["spec"]
    tr, cfg, eng = spec.workload["traffic"], spec.config, server.eng
    e = server.engine_cfg
    n = int(e["max_slots"])
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
                                                       600)))
    c1 = server.counters()
    server.close()
    slots = _slots_in_send_order(server, n)
    complete = slots is not None and len(set(slots)) == n and \
        all(_complete(r) for r in reqs)
    # a released slot's row keeps its last tenant's final state
    held = {} if not complete else \
        {r.index: eng.slot_state(slots[r.index]) for r in checked}
    cache_dtype = str(eng._pool.k[0].dtype)
    complete = complete and cache_dtype == cfg["system"]["cache_dtype"] and \
        all(str(a.dtype) == cfg["system"]["state_dtype"]
            for st in held.values() for layer in st for a in layer.values())
    eng.release_caches()
    got = _delta(c1, c0)
    # every token but a request's last goes once through every expert layer,
    # every pair is held, and every prefill call but a prompt's first resumed
    consumed = sum(len(r.prompt) + r.max_new - 1 for r in reqs)
    expert_layers = cfg["hybrid_override_pattern"].count("E")
    pairs_ok = got["moe_pairs_total"] == got["moe_held_pairs_total"] == \
        consumed * cfg["num_experts_per_tok"] * expert_layers
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
    errs, agree, by_request = [], [], []
    ref_heads, sys_heads, sys_long, tails, first = [], [], [], [], []
    H = cfg["mamba_num_heads"]
    for r in checked if complete else ():
        full, lps = r.result
        full = np.asarray(full)
        p = len(r.prompt)
        ok = full.shape == (p + r.max_new,) and (full[:p] == r.prompt).all()
        complete = complete and bool(ok)
        want, chosen, states = reference.next_token_logprobs(get, cfg, full,
                                                             pad)
        ids = np.zeros(pad, np.int32)
        ids[:len(full)] = full
        mine, one_pass = nemotron_h.routed_experts(mcfg, params, ids,
                                                   n=len(full) - 1)
        agree.append((agreement(np.asarray(mine)[:, :len(full) - 1], chosen),
                      chosen.size))
        errs.append(np.abs(np.asarray(lps, np.float64) - want[p - 1:]))
        slot = held.pop(r.index)
        heads, tail, longest = _state_errors(slot, states)
        own, own_tail, _ = _state_errors(
            slot, [{k: v[0] for k, v in st.items()} for st in one_pass])
        ref_heads += heads
        sys_heads += own
        first += own[:H] + [_rel_tail(slot[0], one_pass[0])]
        sys_long += [own[li * H + h] for li, h in enumerate(longest)]
        tails += [tail, own_tail]
        by_request.append({"prompt": p, "tokens": int(errs[-1].size),
                           "agreement": round(agree[-1][0], 4),
                           "median": float(np.median(errs[-1])),
                           "ref_head_median": round(float(np.median(heads)),
                                                    5),
                           "ref_head_max": round(max(heads), 4),
                           "own_head_max_by_layer": [
                               round(max(own[i:i + H]), 5)
                               for i in range(0, len(own), H)],
                           "own_long": [round(own[li * H + h], 5)
                                        for li, h in enumerate(longest)]})
    errs = np.concatenate(errs) if errs else np.array([np.inf])
    complete = complete and bool(np.isfinite(errs).all()) and bool(ref_heads)
    worst, rms = float(errs.max()), float(np.sqrt(np.mean(errs ** 2)))
    median, p99 = float(np.median(errs)), float(np.percentile(errs, 99))
    agreed = sum(a * w for a, w in agree) / max(sum(w for _a, w in agree), 1)
    inf = float("inf")
    s_ref = float(np.median(ref_heads)) if complete else inf
    s_own = max(sys_heads + tails) if complete else inf
    s_first = max(first) if complete else inf
    l_own = statistics.median(sys_long) if complete else inf
    if not complete:
        worst = rms = median = p99 = inf
        agreed = 0.0
    say("serve.correct", requests=n, checked=len(checked), complete=complete,
        cache_dtype=cache_dtype, rows=json.dumps(
            sorted(slots[r.index] for r in checked) if slots else None),
        router_agreement=agreed, agreement_limit=ROUTER_AGREEMENT,
        logprob_median_abs_err=median, median_limit=LOGPROB_MEDIAN,
        logprob_rms_err=rms, rms_limit=LOGPROB_RMS, logprob_p99_abs_err=p99,
        logprob_max_abs_err=worst, over_0p2=int((errs > 0.2).sum()),
        compared=int(errs.size), state_head_median_rel_err=s_ref,
        head_median_rtol=STATE_HEAD_MEDIAN,
        state_head_p99_vs_reference=float(np.percentile(ref_heads, 99))
        if complete else inf,
        state_head_max_vs_reference=max(ref_heads) if complete else inf,
        state_first_layer_rel_err=s_first, first_layer_rtol=STATE_RTOL,
        state_worst_head_vs_one_pass=s_own,
        state_head_p99_vs_one_pass=float(np.percentile(sys_heads, 99))
        if complete else inf,
        long_memory_state_rel_err=l_own,
        pairs_exact=pairs_ok, resumed_exact=resumed_ok,
        by_request=json.dumps(by_request), counters=json.dumps(got))
    _last_check.update(router_agreement=agreed,
                       state_head_median_rel_err=s_ref,
                       state_first_layer_rel_err=s_first,
                       state_worst_head_vs_one_pass=s_own,
                       long_memory_state_rel_err=l_own)
    return {"ok": complete and pairs_ok and resumed_ok
            and agreed >= ROUTER_AGREEMENT and median <= LOGPROB_MEDIAN
            and rms <= LOGPROB_RMS and s_ref <= STATE_HEAD_MEDIAN
            and s_first <= STATE_RTOL,
            "max_abs_err": worst, "rms_err": rms, "median_abs_err": median,
            "p99_abs_err": p99, "held_pairs": got["moe_held_pairs_total"],
            "held_pairs_reference": got["moe_pairs_total"]}


def _kernel_shapes(spec, traced: Dict) -> Dict:
    """What the readers of ``pt_ssm_step``, the ranged attention and the
    two-matrix grouped matmuls need: the published widths and what the traced
    window's calls covered (``traced``: the engine's counters from the
    profiler's start to its stop; empty untraced)."""
    cfg = spec.config
    attending = cfg["hybrid_override_pattern"].count("*")
    covered = None
    if traced:
        # one kind of paging layer: every cached key a query saw, once a layer
        covered = {
            "full": {"keys_decode":
                     traced["attn_keys_decode_total"] * attending,
                     "keys_prefill":
                     traced["attn_keys_prefill_total"] * attending},
            "rows_decode": traced["slot_rounds"]}
    return {
        "ssm_step": {"rows": cfg["system"]["engine"]["max_slots"],
                     "heads": cfg["mamba_num_heads"],
                     "d_head": cfg["mamba_head_dim"],
                     "d_state": cfg["ssm_state_size"],
                     "groups": cfg["n_groups"]},
        "ranged": {"kv_heads": cfg["num_key_value_heads"],
                   "head_dim": cfg["head_dim"], "itemsize": 2, "window": None,
                   "layers": {"full": {
                       "count": attending,
                       "heads": attending * cfg["num_attention_heads"]}},
                   "traced": covered},
        "moe_relu2": {"hidden": cfg["hidden_size"],
                      "width": cfg["moe_intermediate_size"], "itemsize": 2,
                      "traced": {"rows": traced["moe_held_pairs_total"],
                                 "experts_hit":
                                 traced["moe_experts_hit_total"]}
                      if traced else None}}


# This cell's two new readers (``layer_metrics/<name>.py``) are NOT entries of
# ``BENCHMARK.json``: ``tests/bench/test_train_parts.py`` pins the END of
# ``per_layer`` to the train readers, a metric put in the middle reads as a
# change to what was there, and neither file is this PR's to edit (PERF.md
# section 7). A traced run reads them all the same, into ``notes``.
NOTED_READERS = ("serve.relu2_experts_roofline_pct",
                 "serve.ssm_scan_share_pct")
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
    for name in ("router", "experts", "mixer"):
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
