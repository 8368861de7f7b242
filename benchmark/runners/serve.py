"""Serve cells: ``GPTForCausalLM`` behind ``serving.GenerationEngine`` under
an open loop. Began as a copy of ``chip_smoke.py``'s ``serve_phase`` (PR 21),
which ran on the chip.

Set-up: build the model from the seed, build the engine as the configuration
states it, compile its window steps (W = 1 and the prefill buckets — the
shapes this traffic uses and no others), start the worker, send a few
requests through it. Window: one thread sends each request of the schedule
when it is due; the engine's worker streams tokens back through
``on_token``; every time is taken on this side, from when the request was
DUE. After the window (outside it): the stragglers drain, and seeded check
requests are compared with the plain reference. In a traced run the load
goes on past the window for a few seconds under the profiler.
"""
from __future__ import annotations

import json
import statistics
import threading
import time
from typing import Dict, List

import numpy as np

from ..lib import harness, reference_gpt2, traffic
from ..lib.harness import say, span
from ..lib.stats import percentile

# The engine computes in bf16 and batches a window over pages; the reference
# is one float32 forward. Through 36 blocks the rounding of a logit of
# magnitude 2-10 compounds to a few hundredths (GPT-2 small, 12 blocks:
# 0.004 measured in PR 21). A wrong page, mask or position is O(1), and an
# 8-bit matmul is several tenths. Measured on the chip (PR 23): 0.010-0.012.
LOGPROB_ATOL = 0.05

_BLOCK = {"ln1_w": "ln_1.weight", "ln1_b": "ln_1.bias",
          "qkv_w": "attn.qkv_proj.weight", "qkv_b": "attn.qkv_proj.bias",
          "out_w": "attn.out_proj.weight", "out_b": "attn.out_proj.bias",
          "ln2_w": "ln_2.weight", "ln2_b": "ln_2.bias",
          "fc_in_w": "fc_in.weight", "fc_in_b": "fc_in.bias",
          "fc_out_w": "fc_out.weight", "fc_out_b": "fc_out.bias"}
_TOP = {"embed": "gpt.embed_tokens.weight",
        "pos": "gpt.embed_positions.weight",
        "lnf_w": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}


def _weights_getter(model):
    state = model.state_dict()

    def get(name: str, layer: int):
        key = _TOP[name] if layer < 0 else \
            f"gpt.layers.{layer}.{_BLOCK[name]}"
        return state[key].data

    return get


class Server:
    """The system under test and this side's view of its requests."""

    def __init__(self, ctx):
        import paddle_tpu as paddle
        from paddle_tpu import serving
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        spec = ctx["spec"]
        cfg, sysc = spec.config, spec.config["system"]
        self.cfg, self.engine_cfg = cfg, sysc["engine"]
        paddle.seed(ctx["seed"] % (2 ** 31 - 1))
        t = time.perf_counter()
        self.model = GPTForCausalLM(GPTConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
            num_hidden_layers=cfg["n_layer"],
            num_attention_heads=cfg["n_head"],
            intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
            max_position_embeddings=cfg["n_positions"],
            layer_norm_epsilon=cfg["layer_norm_epsilon"],
            dtype=sysc["dtype"]))
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
            cache_misses=ctx["compiles"].misses, **e)
        self.eng.start()
        # engine spans are on time.monotonic, this side on perf_counter
        self.clock_offset = time.perf_counter() - time.monotonic()

    def send(self, r: traffic.Request, logprobs: bool = False) -> None:
        def on_token(*_a):
            r.stamps.append(time.perf_counter())

        def on_done(fut):
            r.done = time.perf_counter()
            err = fut.exception()
            if err is not None:
                r.error = type(err).__name__
            else:
                r.result = fut.result()

        try:
            with span("submit"):
                fut = self.eng.submit(r.prompt, max_new_tokens=r.max_new,
                                      on_token=on_token,
                                      return_logprobs=logprobs)
            fut.add_done_callback(on_done)
        except Exception as e:  # QueueFull / EngineClosed: refused
            r.done, r.error = time.perf_counter(), type(e).__name__

    def counters(self) -> Dict[str, float]:
        return dict(self.eng.stats()["counters"])

    def drain(self, requests: List[traffic.Request], timeout: float) -> None:
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline and \
                any(r.done is None for r in requests):
            time.sleep(0.01)

    def engine_spans(self) -> Dict[str, List]:
        """The engine's own ``queue`` and ``prefill`` spans, as
        (start on this side's clock, milliseconds)."""
        from paddle_tpu.observability.trace.request_trace import tracer

        out: Dict[str, List] = {"queue": [], "prefill": []}
        for tr in tracer().drain_finished(max_n=1 << 20):
            if tr["engine"] != self.eng.name:
                continue
            for s in tr["spans"]:
                if s["name"] in out:
                    out[s["name"]].append((s["t0"] + self.clock_offset,
                                           s["dur_us"] / 1e3))
        return out

    def close(self) -> None:
        self.eng.close()


def _offer(server: Server, requests: List[traffic.Request]):
    """Start the open loop on its own thread; returns (thread, t0)."""
    t0 = time.perf_counter()
    th = threading.Thread(
        target=traffic.run_open_loop, name="bench-load",
        args=(requests, server.send), kwargs={"t0": t0})
    th.start()
    return th, t0


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _complete(r: traffic.Request) -> bool:
    return r.error is None and r.done is not None and \
        len(r.stamps) == r.max_new


def _check(server: Server, ctx) -> Dict:
    """Seeded requests of every bucket, sent together, against the plain
    reference: the logprob the engine reported for each token it emitted
    vs. one float32 forward over the engine's own output."""
    spec = ctx["spec"]
    tr, cfg = spec.workload["traffic"], spec.config
    n = int(spec.workload.get("check_requests", 8))
    p_lens = traffic.lognormal_quantiles(n, tr["prompt_len"])
    o_lens = traffic.lognormal_quantiles(n, tr["output_len"])[::-1]
    rng = np.random.default_rng(np.random.SeedSequence([ctx["seed"], 99]))
    reqs = [traffic.Request(i, 0.0, rng.integers(
        0, cfg["vocab_size"], int(p), dtype=np.int64), int(o))
        for i, (p, o) in enumerate(zip(p_lens, o_lens))]
    for r in reqs:
        server.send(r, logprobs=True)
    server.drain(reqs, timeout=120)
    pad = int(tr["prompt_len"]["max"]) + int(tr["output_len"]["max"])
    get = _weights_getter(server.model)
    worst, complete = 0.0, True
    for r in reqs:
        if not _complete(r):
            complete = False
            continue
        full, lps = r.result
        full = np.asarray(full)
        p = len(r.prompt)
        ok = full.shape == (p + r.max_new,) and (full[:p] == r.prompt).all()
        complete = complete and bool(ok)
        want = reference_gpt2.next_token_logprobs(get, cfg, full, pad)
        err = float(np.max(np.abs(np.asarray(lps) - want[p - 1:])))
        worst = max(worst, err if np.isfinite(err) else float("inf"))
    say("serve.correct", requests=n, complete=complete,
        logprob_max_abs_err=worst, atol=LOGPROB_ATOL)
    return {"ok": complete and worst <= LOGPROB_ATOL, "max_abs_err": worst}


def _host_warm(server: Server, ctx) -> None:
    """A few requests through the started engine before the window, so its
    first admissions and the worker's first decode rounds are not in it."""
    cfg = ctx["spec"].config
    rng = np.random.default_rng(7)
    reqs = [traffic.Request(i, 0.0, rng.integers(
        0, cfg["vocab_size"], n, dtype=np.int64), 4)
        for i, n in enumerate(ctx["spec"].workload.get(
            "warm_prompt_lens", [24, 100, 200]))]
    for r in reqs:
        server.send(r)
    server.drain(reqs, timeout=120)
    if not all(_complete(r) for r in reqs):
        raise RuntimeError("serve: a warm-up request did not complete: "
                           + str([(r.error, len(r.stamps)) for r in reqs]))
    server.engine_spans()  # drop the warm-up's spans


def run(ctx) -> Dict:
    spec, seed, seconds = ctx["spec"], ctx["seed"], ctx["seconds"]
    tr = spec.workload["traffic"]
    assert tr["kind"] == "open_loop", tr["kind"]
    server = Server(ctx)
    try:
        _host_warm(server, ctx)
        tail = float(spec.workload.get("trace_seconds", 3)) \
            if ctx["trace"] else 0.0
        reqs = traffic.open_loop_schedule(tr, spec.config["vocab_size"],
                                          seed, seconds)
        if tail:  # the same mix goes on under the profiler
            extra = traffic.open_loop_schedule(
                tr, spec.config["vocab_size"], seed + 1, tail)
            for r in extra:
                r.due += seconds
            reqs_all = reqs + extra
        else:
            reqs_all = reqs
        misses_before = ctx["compiles"].misses
        c0 = server.counters()
        setup_s = time.time() - ctx["t_process_start"]
        th, t0 = _offer(server, reqs_all)
        t_end = t0 + seconds
        _sleep_until(t_end)
        c1 = server.counters()
        compiled_in_window = ctx["compiles"].misses - misses_before
        tracer = harness.Tracer(spec.name, ctx["trace"])
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
               "prompt_tokens_total", "prefix_hit_tokens", "prefills_total")}
    # tokens that reached a client inside the window. (Requests here run for
    # ~10 s of a window of tens of seconds: counting only requests that also
    # COMPLETED inside it would quantise the rate by whole requests. One that
    # never completes is in `failed`.)
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
    return {
        "correct": check["ok"] and failed == 0 and compiled_in_window == 0,
        "attempted": len(reqs), "failed": failed,
        "end_to_end": e2e,
        "units": {"serve_tokens_per_s": "tokens/s", "itl_p95_ms": "ms",
                  "setup_s": "s"},
        "counters": {**window, "window_s": seconds,
                     "max_slots": server.engine_cfg["max_slots"]},
        "spans": {"gen_late_ms": late, "ttft_ms": ttft, "itl_ms": gaps,
                  "queue_ms": in_win(spans["queue"]),
                  "prefill_ms": in_win(spans["prefill"])},
        "shapes": {"kind": "serve", "chips": spec.chips},
        "trace": tracer.summary,
        "notes": {"requests": len(reqs),
                  "ttft_p50_ms": statistics.median(ttft),
                  "ttft_mean_ms": statistics.fmean(ttft),
                  "itl_p50_ms": statistics.median(gaps) if gaps else None,
                  "itl_mean_ms": statistics.fmean(gaps) if gaps else None,
                  "streamed_tokens": streamed,
                  "gaps": len(gaps), "logprob_max_abs_err":
                  check["max_abs_err"],
                  "cache_misses": ctx["compiles"].misses},
    }


def sweep(ctx, rates: List[float]) -> None:
    """Offered rate against what the engine sustains, one process, one
    engine, ``--seconds`` a rate: the table the knee is read from. A rate is
    sustained when completions keep up with arrivals (little is left in
    flight at the end) and the queue wait of the second half of the step is
    not above that of the first."""
    spec, seconds = ctx["spec"], ctx["seconds"]
    server = Server(ctx)
    rows = []
    try:
        _host_warm(server, ctx)
        for i, rate in enumerate(rates):
            tr = dict(spec.workload["traffic"], rate_rps=rate)
            reqs = traffic.open_loop_schedule(
                tr, spec.config["vocab_size"], ctx["seed"] + i, seconds)
            th, t0 = _offer(server, reqs)
            t_end = t0 + seconds
            _sleep_until(t_end)
            done = sum(1 for r in reqs if _complete(r) and r.done <= t_end)
            tokens = sum(1 for r in reqs for t in r.stamps if t <= t_end)
            waiting = sum(1 for r in reqs
                          if r.t_send is not None and not r.stamps)
            th.join()
            server.drain(reqs, timeout=180)
            spans = server.engine_spans()
            q = sorted(spans["queue"])
            half = t0 + seconds / 2
            q1 = [ms for t, ms in q if t < half] or [0.0]
            q2 = [ms for t, ms in q if half <= t < t_end] or [0.0]
            ttft = [((r.stamps[0] if r.stamps else time.perf_counter())
                     - (t0 + r.due)) * 1e3 for r in reqs]
            gaps = [(b - a) * 1e3 for r in reqs
                    for a, b in zip(r.stamps, r.stamps[1:])]
            row = {"rate_rps": rate, "offered": len(reqs),
                   "completed_in_window": done,
                   "waiting_for_first_token_at_end": waiting,
                   "failed": sum(1 for r in reqs if not _complete(r)),
                   "tokens_per_s": tokens / seconds,
                   "offered_tokens_per_s":
                   sum(r.max_new for r in reqs) / seconds,
                   "queue_wait_mean_ms_first_half": statistics.fmean(q1),
                   "queue_wait_mean_ms_second_half": statistics.fmean(q2),
                   "ttft_p50_ms": statistics.median(ttft),
                           "itl_p50_ms": statistics.median(gaps),
                   "itl_p95_ms": percentile(gaps, 95)}
            rows.append(row)
            print("sweep " + json.dumps(row), flush=True)
    finally:
        server.close()
    print(json.dumps({"sweep": rows, "device": ctx["device"]}), flush=True)
