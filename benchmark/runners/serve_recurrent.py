"""Serve cells of a model with recurrent state beside its paged K/V (the
engine's ``state_spec``: ``FalconH1ForCausalLM`` is the first) behind
``serving.GenerationEngine`` under the open loop of ``runners/serve.py``,
whose pieces (the load thread, the request bookkeeping, the warm-up, the
sweep) are imported, not copied. The configuration names the model:
``system.model_class`` / ``system.config_class`` (in ``paddle_tpu.models``; the
config class's fields are the configuration's keys, letter for letter) and
``system.reference`` (the plain reference under ``benchmark/lib``). What
differs from ``runners/serve.py``:

- the model and the engine's configuration (no prefix cache: a recurrent
  state cannot resume from cached K/V pages);
- ``correct``: ``max_slots`` seeded requests of the cell's own lengths go
  TOGETHER through the engine that served the window, so that every row of
  both caches is live; every ``check_every``-th of them asks for logprobs.
  When they are done the engine is closed, the checked requests' FINAL
  recurrent state is read from their slots' rows, the caches are given back,
  and the plain reference computes, on the chip at ``highest`` precision, one
  layer's weights upcast at a time and the head a slice of the vocabulary at
  a time, the next-token logprobs over the engine's own output and the state
  the recurrence holds after it. Logprobs AND state are compared: the
  logprobs cannot tell a state kept in bfloat16 from the float32 the
  configuration states; the state can (the limits, below);
- a traced run stops the profiler, lets the load end and the engine drain,
  and only then reduces the trace (``xplane.summarize`` under a running load
  took 97 s of a 360 s limit in PR 26);
- the readers get ``shapes.kind = "serve"``, so every serve reader applies,
  and ``shapes.ssm_step``: what one ``pt_ssm_step`` call covers.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import statistics
import time
from typing import Dict

import numpy as np

from ..lib import harness, traffic
from ..lib.harness import say
from ..lib.stats import percentile
from . import serve
from .serve import _complete, _host_warm, _offer, _sleep_until

# The engine multiplies in bfloat16 (float32 residual stream, state and
# logits) and batches over pages and slots; the reference is one float32
# forward at `highest`. Four limits; any one failing is not correct.
#
# |engine logprob - reference logprob| over the tokens the checked requests
# emit, maximum and rms. Readings on the chip (my chip runs, PR 28; PERF.md
# section 6):
#   as configured, per seed:        max 0.043-0.075, rms 0.0160-0.0180
#   matmul operands rounded to fp8 (e4m3: the nearest precision below the
#   bfloat16 the configuration states): max 0.98, rms 0.287
#   ssm_out_multiplier or key_multiplier dropped: max 14.7-17.9, rms 7.6-8.5
# The maximum is an extreme of ~1250 draws and moves with the seed, so its
# limit sits at ~3 x the largest seen and ~1/5 of the fp8 reading; the rms
# hardly moves, and its limit sits at 3 x it and 1/6 of the fp8 reading. A
# wrong page, mask, position or slot row is O(1) on one token and meets the
# first.
LOGPROB_ATOL = 0.2
LOGPROB_RMS = 0.05
# ||engine state - reference state|| / ||reference state|| of what a checked
# request leaves in its slot, a head at a time for the SSM state, a layer at a
# time for the conv tail. Two limits (my chip runs, PR 28; PERF.md section 6):
#
# STATE_RTOL, on the worst head and the worst conv tail of any layer and
# request: a wrong row, a stale tenant or a recurrence run past the prompt is
# O(1). As configured the worst head reads 0.0139-0.0217 and the worst tail
# 0.0056-0.0059 (13 seeds); the limit is 2-3 x the first. It does NOT see the
# state's precision (kept in bfloat16 the worst head reads 0.0182 / 0.0207).
#
# STATE_LONG_RTOL, on the median over requests and layers of the head with the
# LONGEST memory (the reference says which: the head whose first write has
# decayed least). The logprobs cannot tell a state kept in bfloat16 from the
# float32 the configuration states (max 0.059, rms 0.018: as float32 reads),
# and neither can a head that forgets in a few steps, whose error is its
# inputs' bfloat16 matmuls (about 1 %). A head that sums hundreds of steps
# averages that down and accumulates a rounding of 2^-9 a step instead:
#   as configured (float32), 13 seeds:         median 0.00269-0.00299
#   state rounded to bfloat16 at every write:  median 0.0109 / 0.0119 (2 seeds)
#   (within one seed the heads it is taken over read 0.0010-0.0053 in float32
#   and 0.0055-0.0207 in bfloat16: hence a median, not a maximum)
# The limit is the two readings' geometric mean, about 2 x from either.
STATE_RTOL = 0.05
STATE_LONG_RTOL = 0.006


def model_config(cfg: Dict):
    """The configuration's keys as the model's config class: every field of
    the class but ``dtype`` is a key of the file, letter for letter."""
    models = importlib.import_module("paddle_tpu.models")
    cls = getattr(models, cfg["system"]["config_class"])
    return cls(**{f.name: cfg[f.name] for f in dataclasses.fields(cls)
                  if f.name != "dtype"}, dtype=cfg["system"]["dtype"])


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
                prefix_cache=e["prefix_cache"], max_queue=e["max_queue"]))
        t = time.perf_counter()
        self.eng.warmup()
        say("serve.setup", model_s=round(t_model, 2),
            warmup_s=round(time.perf_counter() - t, 2),
            cache_hits=ctx["compiles"].hits,
            cache_misses=ctx["compiles"].misses,
            state_pool_bytes=self.eng._state_pool_bytes(),
            kv_pool_bytes=self.eng._kv_pool_bytes(), **e)
        self.eng.start()
        # engine spans are on time.monotonic, this side on perf_counter
        self.clock_offset = time.perf_counter() - time.monotonic()


class _Tracer(harness.Tracer):
    """``harness.Tracer`` with its stop in two: the profiler ends with the
    traced window, the reduction waits until the engine is idle."""

    def stop(self) -> None:
        if not self.enabled or self._span is None:
            return
        import jax

        from ..lib import xplane

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        self.xplane = xplane.find_xplane(self.dir)

    def reduce(self) -> None:
        if self.xplane is not None:
            from ..lib import xplane

            self.summary = xplane.summarize(xplane.read_xplane(self.xplane))


def _weights_getter(model):
    params = model.served_model().params(model)

    def get(name: str, layer: int):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return get


def _slots_in_send_order(server: Server, n: int):
    """The slot each of the last ``n`` finished requests was served in, in
    the order they were sent (one thread sent them, so their traces' start
    times are in that order)."""
    from paddle_tpu.observability.trace.request_trace import tracer

    mine = [t for t in tracer().drain_finished(max_n=1 << 20)
            if t["engine"] == server.eng.name]
    mine.sort(key=lambda t: min(s["t0"] for s in t["spans"]))
    slots = [next(s["args"]["slot"] for s in t["spans"]
                  if s["name"] == "prefill") for t in mine[-n:]]
    return slots if len(slots) == n else None


def _rel_err(got, want):
    """||got - want|| / ||want|| over the last two axes: a number for each
    of the leading ones (a head at a time for the SSM state)."""
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    num = jnp.sqrt(jnp.sum((got - want) ** 2, axis=(-2, -1)))
    den = jnp.sqrt(jnp.sum(want ** 2, axis=(-2, -1)))
    return np.asarray(num / jnp.maximum(den, 1e-30))


def _state_errors(held, states):
    """One request's final state in its slot against the reference's: the
    worst head's and the worst conv tail's relative error, and per layer the
    relative error of the head with the longest memory (the reference's
    ``log_decay`` nearest 0)."""
    worst_ssm, worst_conv, long_memory = 0.0, 0.0, []
    for got, want in zip(held, states):
        heads = _rel_err(got["ssm"], want["ssm"])
        worst_ssm = max(worst_ssm, float(np.max(heads)))
        worst_conv = max(worst_conv,
                         float(_rel_err(got["conv"], want["conv"])))
        long_memory.append(float(
            heads[int(np.argmax(np.asarray(want["log_decay"])))]))
    return worst_ssm, worst_conv, long_memory


def _check(server: Server, ctx) -> Dict:
    """``max_slots`` seeded requests of the cell's lengths, sent together
    through the engine that served the window (the worker admits all of them
    before its next round: one a slot, every row of both caches live); every
    ``check_every``-th asks for logprobs. Then, the engine closed, the checked
    requests' final state is read from their slots, the caches are given
    back, and the plain reference runs over the engine's own output."""
    spec = ctx["spec"]
    tr, cfg, eng = spec.workload["traffic"], spec.config, server.eng
    n = int(server.engine_cfg["max_slots"])
    every = int(spec.workload.get("check_every", 4))
    p_lens = traffic.lognormal_quantiles(n, tr["prompt_len"])
    o_lens = traffic.lognormal_quantiles(n, tr["output_len"])[::-1]
    rng = np.random.default_rng(np.random.SeedSequence([ctx["seed"], 99]))
    # spread the lengths over the rows: the quantiles come sorted
    order = rng.permutation(n)
    reqs = [traffic.Request(i, 0.0, rng.integers(
        0, cfg["vocab_size"], int(p_lens[k]), dtype=np.int64),
        int(o_lens[k])) for i, k in enumerate(order)]
    checked = reqs[::every]
    for r in reqs:
        server.send(r, logprobs=r.index % every == 0)
    server.drain(reqs, timeout=120)
    server.close()
    slots = _slots_in_send_order(server, n)
    complete = slots is not None and len(set(slots)) == n and \
        all(_complete(r) for r in reqs)
    # a released slot's row keeps its last tenant's final state
    held = {} if not complete else \
        {r.index: eng.slot_state(slots[r.index]) for r in checked}
    want_dtype = cfg["system"]["ssm_state_dtype"]
    complete = complete and all(
        str(a.dtype) == want_dtype for st in held.values()
        for layer in st for a in layer.values())
    eng.release_caches()
    reference = importlib.import_module(
        "benchmark.lib." + cfg["system"]["reference"])
    pad = int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"])
    get = _weights_getter(server.model)
    worst, sq, count, s_err, c_err, long_memory = 0.0, 0.0, 0, 0.0, 0.0, []
    for r in checked if complete else ():
        full, lps = r.result
        full = np.asarray(full)
        p = len(r.prompt)
        ok = full.shape == (p + r.max_new,) and (full[:p] == r.prompt).all()
        complete = complete and bool(ok)
        want, states = reference.next_token_logprobs(get, cfg, full, pad,
                                                     with_state=True)
        d = np.asarray(lps, np.float64) - want[p - 1:]
        err = float(np.max(np.abs(d)))
        worst = max(worst, err if np.isfinite(err) else float("inf"))
        sq, count = sq + float(np.sum(d * d)), count + d.size
        es, ec, el = _state_errors(held.pop(r.index), states)
        s_err, c_err = max(s_err, es), max(c_err, ec)
        long_memory += el
    rms = (sq / count) ** 0.5 if count else float("inf")
    l_err = statistics.median(long_memory) if long_memory else 0.0
    if not complete:
        worst = s_err = c_err = l_err = float("inf")
    say("serve.correct", requests=n, checked=len(checked), complete=complete,
        rows=json.dumps(sorted(slots[r.index] for r in checked)
                        if slots else None),
        logprob_max_abs_err=worst, atol=LOGPROB_ATOL, logprob_rms_err=rms,
        rms_limit=LOGPROB_RMS, ssm_state_rel_err=s_err,
        conv_state_rel_err=c_err, state_rtol=STATE_RTOL,
        long_memory_state_rel_err=l_err, long_memory_rtol=STATE_LONG_RTOL)
    return {"ok": complete and worst <= LOGPROB_ATOL and rms <= LOGPROB_RMS
            and max(s_err, c_err) <= STATE_RTOL and l_err <= STATE_LONG_RTOL,
            "max_abs_err": worst, "rms_err": rms, "ssm_state_rel_err": s_err,
            "conv_state_rel_err": c_err, "long_memory_state_rel_err": l_err}


def run(ctx) -> Dict:
    spec, seed, seconds = ctx["spec"], ctx["seed"], ctx["seconds"]
    tr = spec.workload["traffic"]
    assert tr["kind"] == "open_loop", tr["kind"]
    server = Server(ctx)
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
            _sleep_until(t_end + tail)
            tracer.stop()
            if ctx.get("dump_trace"):
                from ..lib import trace_dump

                trace_dump.dump(tracer, ctx["dump_trace"])
        th.join()
        server.drain(reqs_all, timeout=float(
            spec.workload.get("drain_timeout_s", 60)))
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
    window = {k: c1.get(k, 0) - c0.get(k, 0) for k in
              ("decode_steps", "slot_rounds", "tokens_total",
               "prompt_tokens_total", "prefills_total",
               "state_installs_total", "state_resets_total")}
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
        counters=json.dumps(window))
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
        # what one pt_ssm_step call covers (lib/ssm_cost.py reads it)
        "shapes": {"kind": "serve", "chips": spec.chips,
                   "ssm_step": {"rows": e["max_slots"],
                                "heads": spec.config["mamba_n_heads"],
                                "d_head": spec.config["mamba_d_head"],
                                "d_state": spec.config["mamba_d_state"],
                                "groups": spec.config["mamba_n_groups"]}},
        "trace": tracer.summary,
        "notes": {"requests": len(reqs),
                  "ttft_p50_ms": statistics.median(ttft),
                  "ttft_mean_ms": statistics.fmean(ttft),
                  "itl_p50_ms": statistics.median(gaps) if gaps else None,
                  "itl_mean_ms": statistics.fmean(gaps) if gaps else None,
                  "streamed_tokens": streamed,
                  "gaps": len(gaps), "logprob_max_abs_err":
                  check["max_abs_err"], "logprob_rms_err": check["rms_err"],
                  "ssm_state_rel_err": check["ssm_state_rel_err"],
                  "conv_state_rel_err": check["conv_state_rel_err"],
                  "long_memory_state_rel_err":
                  check["long_memory_state_rel_err"],
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
