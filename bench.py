"""Flagship benchmark: Llama causal-LM pretrain step on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline target (BASELINE.md): >= 38% MFU for Llama-class pretrain on v5e.
vs_baseline = achieved_MFU / 38.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# memory truth (ISSUE-8): every cold compiled-step build in the bench (and
# its spawned recipe children — env is inherited) records the estimator-
# drift row (predicted live-range peak vs XLA memory_analysis); the
# per-recipe telemetry dumps then carry a populated `memory_drift`
# provider, which tools/ci.sh's memory gate bounds. Flagship-scale models
# are auto-skipped by PT_MEMORY_DRIFT_MAX_PARAM_BYTES.
os.environ.setdefault("PT_MEMORY_DRIFT", "1")

def detect_peak():
    """bf16 peak FLOP/s of the device this process runs on, from the one
    table of peaks (``cost_model.comm.DEVICE_KINDS`` -> ``LINK_TABLES``,
    keyed by the device's own ``device_kind``). An unknown kind raises."""
    from paddle_tpu.cost_model.comm import link_model_for

    return link_model_for().peak_flops


def _time_train_step(step, args, iters):
    """Shared timing harness: warmup/compile with full sync, timed loop with
    a trailing block, and a per-step-sync re-measure when the loop lands
    under 20ms/step (async dispatch measures enqueue time, not execution)."""
    import jax

    float(step(*args))
    float(step(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(*args)
    jax.block_until_ready(loss.data)
    dt = (time.perf_counter() - t0) / iters
    if dt < 0.02:
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(*args)
            float(loss)
        dt = (time.perf_counter() - t0) / iters
    return dt, loss


def _measure(cfg, batch, seq, iters, optimizer_cls=None,
             device_table=False):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaForCausalLM, llama_flops_per_token

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if optimizer_cls is opt.Adafactor:
        optimizer = opt.Adafactor(learning_rate=1e-2,
                                  parameters=model.parameters())
    else:
        optimizer = opt.AdamW(learning_rate=3e-4,
                              parameters=model.parameters(),
                              weight_decay=0.1)
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    dt, loss = _time_train_step(step, (ids, ids), iters)
    tokens_per_sec = batch * seq / dt
    mfu = tokens_per_sec * llama_flops_per_token(cfg, seq) / detect_peak() * 100.0
    n_params = sum(p.size for p in model.parameters())
    out = {
        "mfu": round(mfu, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_s": round(dt, 4),
        "loss": round(float(loss), 4),
        "batch": batch, "seq": seq,
        "params_m": round(n_params / 1e6, 1),
    }
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    out["bytes_limit"] = stats.get("bytes_limit")
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    if device_table:
        try:
            out["device_op_table"] = _device_op_table(step, (ids, ids))
        except Exception as e:  # profiling must never sink the bench
            out["device_op_table_error"] = str(e)[:200]
    return out


def _device_op_table(step, args, top=12):
    """Real device timeline for ONE compiled step via the observability
    XPlane ingestion (``trace.capture_steps``): top device-attributed op
    spans + correlated step/device time — the evidence behind the README
    MFU budget, the same parser ``snapshot()['device_trace']`` feeds.
    Works on CPU (hlo events on the executor threads) and TPU (device
    pids)."""
    from paddle_tpu.observability import trace as otrace

    with otrace.capture_steps() as cap:
        loss = step(*args)
        float(loss)
    if cap.error:
        raise RuntimeError(cap.error)
    cor = cap.result
    dev = cor.summary()["device_compute_us"]
    rows = cor.op_table
    return {
        "step_ms": round(dev["per_step_avg"] / 1e3, 2),
        "steps_correlated": cor.steps_correlated,
        "overlap_efficiency": cor.overlap_efficiency(),
        "scans_ms": {r["op"]: round(r["total_us"] / 1e3, 1)
                     for r in rows if str(r["op"]).startswith("while")},
        "top_ops": [{"op": r["op"], "calls": r["calls"],
                     "total_ms": round(r["total_us"] / 1e3, 2)}
                    for r in rows if not str(r["op"]).startswith("while")
                    ][:top],
    }


def _op_table(cfg, batch, seq, top=10):
    """Top dispatch-level op spans from the framework profiler over eager
    steps (the per-op table VERDICT asks the bench to carry; the compiled
    step is one executable, so op granularity exists on the eager path)."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler as prof
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    model(ids, labels=ids)  # warm the per-op jit caches outside the profile
    p = prof.Profiler(targets=[prof.ProfilerTarget.CPU])
    p.start()
    loss = model(ids, labels=ids)
    float(loss)
    p.stop()
    agg = {}
    for (name, _tid, _ts, dur, _cat) in p.events:
        calls, tot = agg.get(name, (0, 0.0))
        agg[name] = (calls + 1, tot + dur)
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]
    return [{"op": n, "calls": c, "total_us": round(t, 1)}
            for n, (c, t) in rows]


def _moe_dispatch_share(cfg, batch, seq):
    """Fraction of the MoE step spent on routing/dispatch rather than the
    expert matmuls: time the full moe_mlp (the ACTIVE FLAGS_moe_dispatch
    path) against the SAME expert FFN fed a pre-built capacity buffer
    (identical shapes, no routing). The gap is gate + positions + gathers —
    the VERDICT's 'is dispatch the bottleneck' probe, measured on-chip.

    Two defenses against a noisy single-shot probe (round-4's flipped
    signs): each measured call runs an L-step lax.scan whose carry forces
    serial execution of L kernels, and sync is a value fetch. Fresh inputs
    per call defeat request-level caching."""
    import math as _math

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.nn.layer import moe as moe_mod

    paddle.seed(0)
    e = cfg.num_experts
    h = cfg.hidden_size
    i = cfg.moe_intermediate_size or cfg.intermediate_size
    n = batch * seq
    cap = max(int(_math.ceil(cfg.capacity_factor * cfg.top_k * n / e)),
              cfg.top_k)
    mode = _moe_dispatch_flag()
    key = jax.random.key(0)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (batch, seq, h), jnp.bfloat16)
    wg = jax.random.normal(ks[1], (h, e), jnp.float32) * 0.02
    w_gate = jax.random.normal(ks[2], (e, h, i), jnp.bfloat16) * 0.02
    w_up = jax.random.normal(ks[3], (e, h, i), jnp.bfloat16) * 0.02
    w_down = jax.random.normal(ks[4], (e, i, h), jnp.bfloat16) * 0.02
    buf = jax.random.normal(ks[5], (e, cap, h), jnp.bfloat16)
    L = 20

    @jax.jit
    def full_chain(xx):
        def body(c, _):
            out, _aux = moe_mod._moe_mlp.fn(
                c, wg, w_gate, w_up, w_down, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, ep_degree=1,
                dispatch=mode)
            return out.astype(c.dtype), ()
        return jax.lax.scan(body, xx, None, length=L)[0]

    if mode in ("gmm", "fused"):
        # dropless baseline: the same grouped matmuls on k*n pre-grouped
        # rows (the capacity-buffer einsum would execute cf x more rows
        # with a different kernel — not the no-routing twin of this path)
        from paddle_tpu.kernels.grouped_matmul import grouped_matmul

        kn = cfg.top_k * n
        buf = jax.random.normal(ks[5], (kn, 1, h), jnp.bfloat16)
        # distribute the remainder so the baseline multiplies ALL kn rows
        gs = jnp.full((e,), kn // e, jnp.int32).at[:kn % e].add(1)

        @jax.jit
        def ffn_chain(bb):
            def body(c, _):
                c2 = c[:, 0, :]
                g = grouped_matmul(c2, w_gate, gs)
                u = grouped_matmul(c2, w_up, gs)
                out = grouped_matmul(jax.nn.silu(g) * u, w_down, gs)
                return out[:, None, :].astype(c.dtype), ()
            return jax.lax.scan(body, bb, None, length=L)[0]
    else:
        @jax.jit
        def ffn_chain(bb):
            def body(c, _):
                out = moe_mod._expert_ffn(c, w_gate, w_up, w_down,
                                          ep_degree=1)
                return out.astype(c.dtype), ()
            return jax.lax.scan(body, bb, None, length=L)[0]

    def timeit(f, arg):
        float(f(arg)[0, 0, 0])  # compile + warm
        best = 1e9
        for j in range(3):
            a = jnp.add(arg, float(j + 1) * 1e-3)  # j=0 must differ from
            float(a[0, 0, 0])                      # the warm-up values too
            t0 = time.perf_counter()
            out = f(a)
            float(out[0, 0, 0])
            best = min(best, (time.perf_counter() - t0) / L)
        return best

    t_full = timeit(full_chain, x)
    t_ffn = timeit(ffn_chain, buf)
    return {"moe_mlp_us": round(t_full * 1e6, 1),
            "expert_ffn_us": round(t_ffn * 1e6, 1),
            "dispatch_mode": mode,
            "dispatch_share": round(max(1.0 - t_ffn / t_full, 0.0), 3)}


def _moe_dispatch_flag():
    from paddle_tpu.framework import flags as flags_mod

    return flags_mod.get_flags("FLAGS_moe_dispatch")["FLAGS_moe_dispatch"]


def _ab_probe(fn, args, iters=3):
    """(wall_us, device_us) for one jitted callable: wall is best-of-N
    with fresh inputs (defeats request caching), device is the XPlane-
    measured op time of one traced call (the PR-7 parser — CPU hlo
    events and TPU device pids alike; None when the capture fails)."""
    import jax
    import jax.numpy as jnp

    jax.block_until_ready(fn(*args))  # compile + warm
    best = 1e18
    for j in range(iters):
        fresh = jax.tree_util.tree_map(
            lambda a: jnp.add(a, (j + 1) * 1e-3)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype,
                                                      jnp.floating) else a,
            list(args))
        jax.block_until_ready(fresh)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*fresh))
        best = min(best, time.perf_counter() - t0)
    dev_us = None
    try:
        from paddle_tpu.observability import trace as otrace

        with otrace.capture_steps() as cap:
            jax.block_until_ready(fn(*args))
        if cap.error is None and cap.result is not None:
            dev_us = round(sum(r["total_us"]
                               for r in cap.result.op_table), 1)
    except Exception:
        pass
    return round(best * 1e6, 1), dev_us


def _measure_fused_kernels():
    """Per-op fused-vs-composed A/B for the kernels/pallas layer
    (ISSUE-13): each op measured both ways — wall time AND XPlane-
    attributed device time (the PR-7 op-table parser) — plus the fused
    MoE dispatch_share probe and a tolerance-pinned parity row against
    the index-dispatch path. On CPU the fused side runs the composed
    twin of the fused algorithm (the registry's CPU contract), so the
    CPU rows pin the SEAM's cost; the kernel-vs-twin delta is the TPU
    half of the A/B."""
    import math as _math

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework import flags as flags_mod
    from paddle_tpu.kernels.pallas import rmsnorm as _krms
    from paddle_tpu.kernels.pallas import rope as _krope
    from paddle_tpu.kernels.registry import kernel_table
    from paddle_tpu.nn.layer import moe as moe_mod

    paddle.seed(0)
    on_tpu = jax.default_backend() == "tpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    out = {"backend": jax.default_backend(),
           "flag": kernel_table()["flag"]}
    key = jax.random.key(0)
    ks = jax.random.split(key, 8)

    # -- rms_norm(+residual): legacy separate-op chain vs fused ---------------
    b, s, h = (8, 2048, 2048) if on_tpu else (4, 256, 512)
    x = jax.random.normal(ks[0], (b, s, h), dt)
    r = jax.random.normal(ks[1], (b, s, h), dt)
    w = jnp.ones((h,), dt)
    eps = 1e-6

    def _legacy_rms(xx, rr, ww):
        ss = xx + rr
        var = jnp.mean(jnp.square(ss.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        y = (ss.astype(jnp.float32) * jax.lax.rsqrt(var + eps) *
             ww.astype(jnp.float32)).astype(ss.dtype)
        return y, ss

    def _loss(f):
        def g(xx, rr, ww):
            y, ss = f(xx, rr, ww)
            return (jnp.sum(y.astype(jnp.float32)) +
                    jnp.sum(ss.astype(jnp.float32)))
        return jax.jit(jax.grad(g, argnums=(0, 2)))

    legacy_us, legacy_dev = _ab_probe(_loss(_legacy_rms), (x, r, w))
    fused_us, fused_dev = _ab_probe(
        _loss(lambda xx, rr, ww: _krms.rms_norm_residual(xx, rr, ww, eps)),
        (x, r, w))
    out["rms_norm"] = {
        "composed_us": legacy_us, "fused_us": fused_us,
        "composed_device_us": legacy_dev, "fused_device_us": fused_dev,
        "speedup": round(legacy_us / max(fused_us, 1e-9), 3)}

    # -- rope -----------------------------------------------------------------
    nh, hd = (16, 128) if on_tpu else (8, 64)
    xr = jax.random.normal(ks[2], (b, s // 2, nh, hd), dt)
    from paddle_tpu.models.llama import _rope as _rope_prim

    lr_us, lr_dev = _ab_probe(
        jax.jit(jax.grad(lambda z: jnp.sum(_rope_prim.fn(
            z, theta=1e4, pos_offset=0, fused=False)
            .astype(jnp.float32) ** 2))), (xr,))
    fr_us, fr_dev = _ab_probe(
        jax.jit(jax.grad(lambda z: jnp.sum(_krope.rope_apply(z, 1e4, 0)
                                           .astype(jnp.float32) ** 2))),
        (xr,))
    out["rope"] = {
        "composed_us": lr_us, "fused_us": fr_us,
        "composed_device_us": lr_dev, "fused_device_us": fr_dev,
        "speedup": round(lr_us / max(fr_us, 1e-9), 3)}

    # -- MoE dispatch: share probe (fused + index) + parity -------------------
    from paddle_tpu.models.llama import LlamaMoEConfig

    if on_tpu:
        mcfg = _configs()["moe"]
        mb, ms = 8, 2048
    else:
        mcfg = LlamaMoEConfig(
            vocab_size=256, hidden_size=256, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=1024,
            dtype="float32", num_experts=8, top_k=2, capacity_factor=1.25)
        mb, ms = 2, 512
    prior = _moe_dispatch_flag()
    try:
        flags_mod.set_flags({"FLAGS_moe_dispatch": "fused"})
        out["moe_fused"] = _moe_dispatch_share(mcfg, batch=mb, seq=ms)
        flags_mod.set_flags({"FLAGS_moe_dispatch": "index"})
        out["moe_index"] = _moe_dispatch_share(mcfg, batch=mb, seq=ms)
    finally:
        flags_mod.set_flags({"FLAGS_moe_dispatch": prior})
    out["dispatch_share_fused"] = out["moe_fused"]["dispatch_share"]
    out["dispatch_share_index"] = out["moe_index"]["dispatch_share"]

    # parity vs the index path: generous capacity (cap >= k*n/e * cf with
    # cf = e guarantees zero drops), identical weights/inputs
    e, k = mcfg.num_experts, mcfg.top_k
    hm, im = mcfg.hidden_size, (mcfg.moe_intermediate_size
                                or mcfg.intermediate_size)
    pk = jax.random.split(ks[3], 5)
    px = jax.random.normal(pk[0], (2, 64, hm), jnp.float32)
    pwg = jax.random.normal(pk[1], (hm, e), jnp.float32) * 0.1
    pgate = jax.random.normal(pk[2], (e, hm, im), jnp.float32) * 0.05
    pup = jax.random.normal(pk[3], (e, hm, im), jnp.float32) * 0.05
    pdown = jax.random.normal(pk[4], (e, im, hm), jnp.float32) * 0.05
    of, auxf = moe_mod._moe_mlp.fn(px, pwg, pgate, pup, pdown, top_k=k,
                                   capacity_factor=1.0, ep_degree=1,
                                   dispatch="fused")
    oi, auxi = moe_mod._moe_mlp.fn(px, pwg, pgate, pup, pdown, top_k=k,
                                   capacity_factor=float(e), ep_degree=1,
                                   dispatch="index")
    out["dispatch_parity_max_err"] = float(
        jnp.max(jnp.abs(of.astype(jnp.float32) - oi.astype(jnp.float32))))
    out["dispatch_parity_aux_err"] = float(jnp.abs(auxf - auxi))

    # -- paged decode: window step fused seam vs composed gather path ---------
    try:
        from paddle_tpu.models.gpt import GPTConfig
        from paddle_tpu.models import GPTForCausalLM
        from paddle_tpu.serving.generation import (_build_window_step,
                                                   _extract_gpt_params)

        gcfg = GPTConfig(vocab_size=256, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         max_position_embeddings=256)
        gm = GPTForCausalLM(gcfg)
        params = _extract_gpt_params(gm)
        S, PL, B = 4, 16, 16
        P = S * B + 1
        ghd = gcfg.hidden_size // gcfg.num_attention_heads
        karena = [jax.random.normal(ks[4], (P, PL, 4, ghd), jnp.float32)
                  for _ in range(2)]
        varena = [jax.random.normal(ks[5], (P, PL, 4, ghd), jnp.float32)
                  for _ in range(2)]
        tables = jnp.arange(S * B, dtype=jnp.int32).reshape(S, B) + 1
        tokens = jnp.ones((S, 1), jnp.int32)
        lengths = jnp.full((S,), 200, jnp.int32)
        rows = {}
        for name, fused in (("composed", False), ("fused", True)):
            stp = _build_window_step(gcfg, S, B, PL, 1, donate=False,
                                     label=f"bench:paged:{name}",
                                     fused=fused)
            wall, dev = _ab_probe(
                lambda *a: stp(*a)[0],
                (params, karena, varena, tables, tokens, lengths))
            rows[name] = {"wall_us": wall, "device_us": dev}
        out["paged_decode"] = dict(
            rows, ratio=round(rows["fused"]["wall_us"] /
                              max(rows["composed"]["wall_us"], 1e-9), 3))
    except Exception as e:  # the probe must never sink the bench
        out["paged_decode_error"] = str(e)[:200]

    # feed the measured shares back into the persisted planner
    # calibration (topology x jax version) so plan() prices the fused
    # entries from THIS machine's numbers on the next round
    try:
        from paddle_tpu.cost_model import comm as _comm

        _comm.save_calibration(
            _comm.link_model_for(),
            fused={"moe_dispatch": {
                "dispatch_share_composed": max(
                    out["dispatch_share_index"], 0.01),
                "dispatch_share_fused": max(
                    out["dispatch_share_fused"], 0.01)}})
        out["calibration_persisted"] = True
    except Exception:
        out["calibration_persisted"] = False
    return out


def _measure_moe(cfg, batch, seq, iters):
    """MoE flagship (BASELINE config 5, DeepSeekMoE/Qwen2-MoE shape): MFU on
    ACTIVATED flops — capacity-factor overcompute is counted as overhead."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import (LlamaForCausalLM, llama_moe_flops_per_token,
                                   llama_moe_param_counts)

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.Adafactor(learning_rate=1e-2,
                              parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    dt, loss = _time_train_step(step, (ids, ids), iters)
    tokens_per_sec = batch * seq / dt
    act_flops = llama_moe_flops_per_token(cfg, seq)
    mfu = tokens_per_sec * act_flops / detect_peak() * 100.0
    total, activated = llama_moe_param_counts(cfg)
    # executed MFU: counts the capacity-factor overcompute the chip actually
    # performs (cf * expert param flops; the attention term is NOT scaled —
    # only expert FFNs run at capacity)
    i = cfg.moe_intermediate_size or cfg.intermediate_size
    expert_act = cfg.num_hidden_layers * cfg.top_k * 3 * cfg.hidden_size * i
    exec_flops = act_flops + 6 * (cfg.capacity_factor - 1.0) * expert_act
    mfu_exec = tokens_per_sec * exec_flops / detect_peak() * 100.0
    return {
        "mfu_activated": round(mfu, 2),
        "mfu_executed": round(mfu_exec, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_s": round(dt, 4),
        "loss": round(float(loss), 4),
        "batch": batch, "seq": seq,
        "params_total_m": round(total / 1e6, 1),
        "params_activated_m": round(activated / 1e6, 1),
        "num_experts": cfg.num_experts, "top_k": cfg.top_k,
        "capacity_factor": cfg.capacity_factor,
        "dispatch": _moe_dispatch_flag(),
    }


def _measure_dit(cfg, batch, iters):
    """DiT flagship (BASELINE config 4): images/sec + MFU of the DDPM
    training step (eps-prediction objective) at the DiT-XL/2 latent shape."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import DiT, GaussianDiffusion

    paddle.seed(0)
    model = DiT(cfg)
    diffusion = GaussianDiffusion()
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          weight_decay=0.0)
    step = jit.TrainStep(
        model, lambda m, x, y: diffusion.training_loss(m, x, y), optimizer)
    x = paddle.randn([batch, cfg.in_channels, cfg.input_size, cfg.input_size])
    y = paddle.randint(0, cfg.num_classes, [batch])
    dt, loss = _time_train_step(step, (x, y), iters)
    images_per_sec = batch / dt
    n_params = sum(p.size for p in model.parameters())
    tokens = (cfg.input_size // cfg.patch_size) ** 2
    flops_per_image = tokens * (6 * n_params
                                + 12 * cfg.num_hidden_layers
                                * cfg.hidden_size * tokens)
    mfu = images_per_sec * flops_per_image / detect_peak() * 100.0
    return {
        "images_per_sec": round(images_per_sec, 2),
        "mfu": round(mfu, 2),
        "step_time_s": round(dt, 4),
        "loss": round(float(loss), 4),
        "batch": batch,
        "latent": f"{cfg.in_channels}x{cfg.input_size}x{cfg.input_size}",
        "patch": cfg.patch_size, "tokens_per_image": tokens,
        "params_m": round(n_params / 1e6, 1),
    }


def _measure_segmented(cfg, batch, seq, iters):
    """Segmented-offload capacity row (VERDICT r4 next #4): per-layer host
    buffers + hand-segmented backward — no stacked gradient chain for the
    compiler to HBM-place, lifting the streamed 3.08B wall. Reports the
    host-bandwidth model the VERDICT asks for: GB moved per step over the
    measured effective host link."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import (LlamaForCausalLM, llama_flops_per_token,
                                   llama_param_count)

    paddle.seed(0)
    with jit.init_on_host():
        model = LlamaForCausalLM(cfg)
    optimizer = opt.Adafactor(learning_rate=1e-2,
                              parameters=model.parameters())
    step = jit.SegmentedTrainStep(model, lambda m, x, y: m(x, labels=y),
                                  optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    losses = [float(step(ids, ids))]  # compile + step 1
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, ids)
        losses.append(float(loss))
    dt = (time.perf_counter() - t0) / iters
    n_params = llama_param_count(cfg)
    # only the per-layer host buffers cross the link (embeddings/head stay
    # device-resident as edge params)
    pb = float(sum(a.nbytes for row in step._layer_params for a in row))
    L = cfg.num_hidden_layers
    act = 2.0 * batch * seq * cfg.hidden_size * L  # boundary acts, bf16
    # params H2D in fwd + H2D in bwd + updated D2H; factored opt state is
    # O(rows+cols) and ignored; boundaries D2H in fwd + H2D in bwd
    gb_moved = (3 * pb + 2 * act) / 1e9
    tokens_per_sec = batch * seq / dt
    mfu = tokens_per_sec * llama_flops_per_token(cfg, seq) \
        / detect_peak() * 100.0
    return {
        "params_b": round(n_params / 1e9, 3),
        "step_time_s": round(dt, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "mfu": round(mfu, 2),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "batch": batch, "seq": seq,
        "gb_moved_per_step": round(gb_moved, 1),
        "effective_host_gbps": round(gb_moved / dt, 2),
        "mode": "segmented per-layer offload (no stacked grad chain)",
    }


def _measure_stream_ab(cfg, batch, seq, iters=3):
    """Streaming-offload A/B (ISSUE-5 tentpole acceptance): the SAME
    offload train step (ShardedTrainStep + group_sharded_parallel
    offload=True) run twice from one seed — lane serialized (every group
    transfer inline, nothing hidden) vs overlapped (double-buffered
    background lane) — with identical executables and dispatch order, so
    the losses are bit-equal and the delta is pure latency hiding.
    ``overlap_efficiency`` = transfer time hidden behind compute / total
    transfer time, from the lane's own counters."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import LlamaForCausalLM

    # the mesh must cover every device and the batch dim must divide the
    # dp x sdp product (the 8-device CI mesh broke the old dp=1 fallback)
    ndev = len(jax.devices())
    if batch % ndev:
        batch = ndev * max(1, batch // ndev)

    def one(overlap, eager=True):
        paddle.seed(0)
        dist.reset_mesh()
        dist.init_mesh(dp=ndev)
        model = LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                      weight_decay=0.1)
        model, o = dist.group_sharded_parallel(model, o, level="os",
                                               offload=True)
        step = dist.ShardedTrainStep(model,
                                     lambda m, x, y: m(x, labels=y), o)
        step._stream_overlap = overlap
        step._stream_eager = eager
        ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
        losses = [float(step(ids, ids))]  # compile + step 1
        t0 = time.perf_counter()
        for _ in range(iters):
            losses.append(float(step(ids, ids)))
        dt = (time.perf_counter() - t0) / iters
        stats = step.stream_stats()
        groups = len(step._stream[0])
        dist.reset_mesh()
        return dt, losses, stats, groups

    ser_dt, ser_losses, _ser_stats, groups = one(False)
    # PR-5 carried A/B: the drain-at-boundary walk (eager=False) vs the
    # cross-step pipeline fill (default: final uploads handed to the next
    # dispatch as futures, so the next step's group-0 grad download is
    # submitted during fwd+bwd)
    drain_dt, drain_losses, _drain_stats, _ = one(True, eager=False)
    ov_dt, ov_losses, ov_stats, _ = one(True, eager=True)
    steps_total = iters + 1
    return {
        "serialized_step_time_s": round(ser_dt, 4),
        "overlapped_step_time_s": round(ov_dt, 4),
        "step_speedup": round(ser_dt / ov_dt, 3) if ov_dt else None,
        # the two gate-critical entries stay inside _scalar_row's first-8
        # window so a size-capped headline still carries them
        "overlap_efficiency": ov_stats["overlap_efficiency"],
        "losses_bit_equal": bool(np.array_equal(ser_losses, ov_losses)
                                 and np.array_equal(ov_losses, drain_losses)),
        "boundary_drain_step_time_s": round(drain_dt, 4),
        "fill_overlap_speedup": round(drain_dt / ov_dt, 3) if ov_dt else None,
        "pinned_staging": bool(ov_stats.get("pinned_staging")),
        "stream_groups": groups,
        "transfer_ms_per_step": round(
            ov_stats["transfer_ms"] / steps_total, 2),
        "stall_ms_per_step": round(ov_stats["stall_ms"] / steps_total, 2),
        "h2d_mb_per_step": round(
            ov_stats["h2d_bytes"] / steps_total / 1e6, 2),
        "d2h_mb_per_step": round(
            ov_stats["d2h_bytes"] / steps_total / 1e6, 2),
        "loss_first": round(ov_losses[0], 4),
        "loss_last": round(ov_losses[-1], 4),
        "batch": batch, "seq": seq, "iters": iters,
        "mode": "ShardedTrainStep offload update: serialized vs "
                "double-buffered streaming lane",
    }


def _measure_stream(cfg, batch, seq, iters):
    """Streamed-offload capacity row (VERDICT r3 next #3): stacked decoder
    weights + optimizer state live in TPU pinned host memory and stream
    through HBM layer by layer inside ONE compiled step — model sizes far
    beyond the resident ceiling train on one chip. Throughput is
    host-bandwidth-bound by design; the metric here is CAPACITY."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaForCausalLM, llama_flops_per_token

    paddle.seed(0)
    with jit.init_on_host():
        model = LlamaForCausalLM(cfg)
    optimizer = opt.Adafactor(learning_rate=1e-2,
                              parameters=model.parameters())
    step = jit.StreamedTrainStep(model, lambda m, x, y: m(x, labels=y),
                                 optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    losses = [float(step(ids, ids))]  # compile + step 1
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, ids)
        losses.append(float(loss))
    dt = (time.perf_counter() - t0) / iters
    from paddle_tpu.models import llama_param_count

    n_params = llama_param_count(cfg)  # packed host slabs pad p.size
    tokens_per_sec = batch * seq / dt
    mfu = tokens_per_sec * llama_flops_per_token(cfg, seq) \
        / detect_peak() * 100.0
    return {
        "params_b": round(n_params / 1e9, 3),
        "step_time_s": round(dt, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "mfu": round(mfu, 2),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "batch": batch, "seq": seq,
        "mode": "streamed pinned-host offload (params+opt state)",
    }


def _surrogate_cifar(n, seed=0):
    """Deterministic CIFAR-10 stand-in: the sealed image has no real CIFAR
    download, so the parity harness uses 10 fixed class prototypes +
    Gaussian noise — identical bytes on every backend (BASELINE config 1
    demands loss parity vs a single-device CPU reference; the surrogate is
    clearly labeled in the bench row)."""
    rng = np.random.RandomState(seed)
    protos = rng.randn(10, 3, 32, 32).astype("float32")
    ys = rng.randint(0, 10, n).astype("int64")
    xs = (protos[ys] + 0.7 * rng.randn(n, 3, 32, 32)).astype("float32")
    return xs, ys


def _resnet_cifar_losses(steps=12, batch=32, seed=7):
    """Same-seed resnet18 training losses over the deterministic surrogate:
    run on the TPU and on the CPU backend, the curves must match (threefry
    init is backend-independent; divergence measures numerics only). Two
    choices keep the comparison meaningful: matmul/conv precision is pinned
    to f32 (TPU matmuls default to bf16 mantissae — that would measure
    dtype, not correctness), and the lr is gentle (a chaotic loss curve
    amplifies last-ulp differences exponentially)."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.vision.models import resnet18

    jax.config.update("jax_default_matmul_precision", "highest")
    paddle.seed(seed)
    net = resnet18(num_classes=10)
    optim = opt.Momentum(learning_rate=0.01, momentum=0.9,
                         parameters=net.parameters())
    step = jit.TrainStep(net, lambda m, x, y: F.cross_entropy(m(x), y),
                         optim)
    xs, ys = _surrogate_cifar(steps * batch)
    losses = []
    for i in range(steps):
        xb = paddle.to_tensor(xs[i * batch:(i + 1) * batch])
        yb = paddle.to_tensor(ys[i * batch:(i + 1) * batch])
        losses.append(round(float(step(xb, yb)), 5))
    return losses


def _measure_resnet_cifar():
    """BASELINE config 1: loss parity vs the CPU reference (grand-child
    process pinned to the CPU backend) + TPU images/sec at batch 128."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.vision.models import resnet18

    losses_tpu = _resnet_cifar_losses()
    ref = _spawn("resnet_cifar_cpuref", timeout=2400)
    deltas = [abs(a - b) for a, b in zip(losses_tpu, ref["losses"])]

    import jax

    # the parity leg pinned matmuls to f32; throughput measures the
    # production precision
    jax.config.update("jax_default_matmul_precision", "default")
    paddle.seed(7)
    batch = 128
    net = resnet18(num_classes=10)
    optim = opt.Momentum(learning_rate=0.05, momentum=0.9,
                         parameters=net.parameters())
    step = jit.TrainStep(net, lambda m, x, y: F.cross_entropy(m(x), y),
                         optim)
    xs, ys = _surrogate_cifar(batch, seed=1)
    xb, yb = paddle.to_tensor(xs), paddle.to_tensor(ys)
    dt, loss = _time_train_step(step, (xb, yb), iters=16)
    return {
        "images_per_sec": round(batch / dt, 1),
        "step_time_s": round(dt, 5), "batch": batch,
        "loss_parity": {
            "data": "deterministic surrogate CIFAR (no real CIFAR in the "
                    "sealed image)",
            "steps": len(losses_tpu),
            "max_abs_delta": round(max(deltas), 5),
            "final_tpu": losses_tpu[-1], "final_cpu": ref["losses"][-1],
            "losses_tpu": losses_tpu, "losses_cpu": ref["losses"]},
    }


def _surrogate_sst2(n, seq=128, vocab=30522, seed=0, k=16):
    """Deterministic SST-2-shaped binary task: k class-marker tokens planted
    per sentence (disjoint marker sets; real sentiment sentences carry many
    cue words too) — learnable to high accuracy, so a finetune that works
    reaches it and a broken one cannot. A RANDOM-INIT bert-base breaks its
    symmetry-plateau within a few hundred steps at this signal level (the
    r5 bisection showed plateau length scales inversely with markers-per-
    sentence; k=3 needs thousands of steps at this depth/width)."""
    rng = np.random.RandomState(seed)
    markers = rng.choice(np.arange(1000, vocab), 80, replace=False)
    pos, neg = markers[:40], markers[40:]
    ids = rng.randint(1000, vocab, (n, seq)).astype("int64")
    ys = rng.randint(0, 2, n).astype("int64")
    cols = rng.randint(1, seq, (n, k))
    for i in range(n):
        src = pos if ys[i] else neg
        ids[i, cols[i]] = rng.choice(src, k)
    return ids, ys


def _measure_bert_finetune(steps=500, batch=32, seq=128):
    """BASELINE config 2: BERT-base finetune on the SST-2-shaped task —
    held-out accuracy + sequences/sec."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.core import autograd
    from paddle_tpu.models import BertConfig, BertForSequenceClassification

    paddle.seed(11)
    cfg = BertConfig.bert_base(dtype="bfloat16")
    model = BertForSequenceClassification(cfg, num_classes=2)
    sched = opt.lr.LinearWarmup(learning_rate=1e-4, warmup_steps=100,
                                start_lr=0.0, end_lr=1e-4)
    # global-norm clip is the standard BERT finetune recipe and load-
    # bearing here: without it the post-warmup bf16 run can collapse after
    # having fit the task (r5 bisection: loss 0.0 at step 100 -> 0.77)
    from paddle_tpu import nn as pnn

    optim = opt.AdamW(learning_rate=sched, parameters=model.parameters(),
                      weight_decay=0.01,
                      grad_clip=pnn.ClipGradByGlobalNorm(1.0))
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optim)

    ids, ys = _surrogate_sst2(steps * batch + 256)
    train_ids, train_ys = ids[:steps * batch], ys[:steps * batch]
    test_ids, test_ys = ids[steps * batch:], ys[steps * batch:]
    t_train = 0.0
    loss = None
    for i in range(steps):
        xb = paddle.to_tensor(train_ids[i * batch:(i + 1) * batch])
        yb = paddle.to_tensor(train_ys[i * batch:(i + 1) * batch])
        t0 = time.perf_counter()
        loss = step(xb, yb)
        loss = float(loss)
        sched.step()
        if i >= 2:  # skip compile steps
            t_train += time.perf_counter() - t0
    seq_per_sec = (steps - 2) * batch / t_train

    model.eval()
    correct = 0
    with autograd.no_grad():
        for i in range(0, len(test_ys), batch):
            logits = model(paddle.to_tensor(test_ids[i:i + batch]))
            pred = np.argmax(np.asarray(logits.numpy(), dtype="float32"),
                             axis=-1)
            correct += int((pred == test_ys[i:i + batch]).sum())
    acc = correct / len(test_ys)
    return {
        "heldout_accuracy": round(acc, 4),
        "sequences_per_sec": round(seq_per_sec, 1),
        "final_loss": round(loss, 4),
        "steps": steps, "batch": batch, "seq": seq,
        "data": "deterministic SST-2-shaped marker task (no GLUE download "
                "in the sealed image)",
        "params_m": 109.5,
    }


def _measure_warm_path(cfg, batch, seq, iters=4, accum=4):
    """Warm-path trio in one number: steady-state per-microbatch step time
    with async device prefetch (io.DevicePrefetcher) feeding a FUSED
    gradient-accumulation executable (TrainStep.accumulate), next to the
    same model's plain per-call step — the dispatch+transfer overhead the
    warm-path pass removes. Model-size agnostic: runs in the CPU smoke on
    the tiny config and on TPU at flagship shapes."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import io, jit
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          weight_decay=0.1)
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    plain_dt, _ = _time_train_step(step, (ids, ids), iters)

    acc = step.accumulate(accum)
    rng = np.random.RandomState(0)
    wins = [(paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (accum * batch, seq)).astype("int64")),) * 2
        for _ in range(iters + 1)]
    first = True
    loss = None
    t0 = None
    for x, y in io.DevicePrefetcher(wins):
        loss = acc(x, y)
        if first:  # compile window, then start the clock
            float(loss)
            t0 = time.perf_counter()
            first = False
    float(loss)
    per_win = (time.perf_counter() - t0) / iters
    fused_dt = per_win / accum
    # XPlane probe: two traced plain steps so this recipe's telemetry dump
    # carries the device_trace digest (top-k device op table, correlated
    # step device time) — ISSUE-7's "bench telemetry gains the op table"
    device_row = None
    try:
        from paddle_tpu.observability import trace as otrace

        with otrace.capture_steps() as cap:
            for _ in range(2):
                float(step(ids, ids))
        if cap.result is not None and cap.result.op_table:
            s = cap.result.summary(top=4)
            device_row = {
                "steps_correlated": s["steps_correlated"],
                "device_us_avg": s["device_compute_us"]["per_step_avg"],
                "top_op": s["op_table"][0]["op"],
            }
    except Exception:
        pass  # device tracing must never sink the bench
    # memory truth: measured-vs-predicted peak for this recipe's step
    # (ISSUE-8) — the estimator-drift row the cold builds above recorded,
    # plus the process device watermark
    mem_row = None
    try:
        from paddle_tpu.observability.memory import (drift_snapshot,
                                                     memory_monitor)

        d = drift_snapshot()
        recs = d.get("records") or []
        last = recs[-1] if recs else None
        wm = memory_monitor().watermarks()
        mem_row = {
            "predicted_peak_mb": round(last["predicted_bytes"] / 1e6, 2)
            if last and last.get("predicted_bytes") else None,
            "xla_peak_mb": round(last["xla_peak_bytes"] / 1e6, 2)
            if last and last.get("xla_peak_bytes") else None,
            "drift_ratio": last.get("ratio") if last else None,
            "within_bound": d.get("within_bound"),
            "device_watermark_mb": round(max(list(wm.values()) or [0]) / 1e6,
                                         2),
        }
    except Exception:
        pass  # telemetry must never sink the bench
    return {
        "device_trace": device_row,
        "memory": mem_row,
        "plain_step_time_s": round(plain_dt, 4),
        "prefetch_accum_step_time_s": round(fused_dt, 4),
        "accumulate_steps": accum,
        "window_time_s": round(per_win, 4),
        "speedup_vs_plain": round(plain_dt / fused_dt, 3) if fused_dt else None,
        "batch": batch, "seq": seq,
        "mode": "DevicePrefetcher + TrainStep.accumulate (one executable "
                "per window, donated)",
        "telemetry_overhead_us": _telemetry_overhead_probe(),
    }


def _measure_checkpoint_stall(cfg, batch, seq, saves=4, steps_per_save=4):
    """ISSUE-6 A/B: per-save train-thread stall of the synchronous commit
    (d2h + serialize + fsync on the caller) vs AsyncCheckpointer's
    background commit (caller only dispatches the d2h copies; blocking
    serialization hides behind the next steps' compute). Same model, same
    checkpoint root layout, one save per ``steps_per_save`` train steps
    (the periodic-checkpoint shape: the writer hides behind the following
    steps' compute). Acceptance: async stall < 25% of the synchronous
    save time (``stall_ratio``)."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.distributed.resilience import AsyncCheckpointer
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          weight_decay=0.1)
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    float(step(ids, ids))  # compile + first save outside the clock

    def run(sync):
        root = tempfile.mkdtemp(prefix="pt_ckpt_stall_")
        ck = AsyncCheckpointer(root, model=model, optimizer=optimizer,
                               keep=2, name="bench")
        handles = []
        t0 = time.perf_counter()
        try:
            for i in range(saves):
                float(step(ids, ids))
                handles.append(ck.save_async(step=i, sync=sync))
                for _ in range(steps_per_save - 1):
                    # the compute window the async commit hides behind
                    float(step(ids, ids))
            ck.wait()
        finally:
            wall = time.perf_counter() - t0
            ck.close()
            shutil.rmtree(root, ignore_errors=True)
        stall = sum(h.stall_ms for h in handles) / max(len(handles), 1)
        total = sum(h.total_ms for h in handles) / max(len(handles), 1)
        return stall, total, wall

    sync_stall, sync_total, sync_wall = run(sync=True)
    async_stall, async_total, async_wall = run(sync=False)
    # the acceptance ratio: train-thread stall per async save over the
    # synchronous save's full (all-stall) time
    ratio = (async_stall / sync_total) if sync_total else None
    return {
        "sync_save_ms": round(sync_total, 2),
        "sync_stall_ms": round(sync_stall, 2),
        "async_stall_ms": round(async_stall, 2),
        "async_save_ms": round(async_total, 2),
        "stall_ratio": round(ratio, 4) if ratio is not None else None,
        "hidden_frac": round(1.0 - max(async_stall, 0.0)
                             / max(async_total, 1e-9), 4),
        "saves": saves, "steps_per_save": steps_per_save,
        "batch": batch, "seq": seq,
        "sync_wall_s": round(sync_wall, 3),
        "async_wall_s": round(async_wall, 3),
        "mode": "AsyncCheckpointer d2h-dispatch-on-train-thread + "
                "background serialize/commit vs sync=True twin",
    }


def _measure_autoplan(n_top=3, iters=4, batch=16, seq=64):
    """ISSUE-10 tentpole acceptance: predicted-vs-measured ranking
    fidelity of the cost-model planner on the 8-device CPU dryrun mesh
    (the MULTICHIP_r05 config space). ``plan()`` ranks the full candidate
    space for the bench tiny-Llama shape; the top-``n_top`` picks plus
    the median- and worst-ranked feasible candidates are then REALLY
    trained for a few steps each through ``apply_plan`` (the same
    ShardedTrainStep / group_sharded / accumulate path production uses)
    and the measured step times are compared against the predictions:

    - ``top_vs_best_ratio``: top pick's measured time over the best
      measured time (acceptance: <= 1.25);
    - ``beats_median``: top pick strictly faster than the median
      measured candidate;
    - ``rank_corr``: Spearman correlation of predicted vs measured
      ranks over the measured set.
    """
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.auto_parallel import planner
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    ndev = len(jax.devices())
    # the device's own bytes_limit; the CPU host mesh reports none, so
    # there the leg states a budget (tiny models: any budget is feasible)
    stats = jax.local_devices()[0].memory_stats() or {}
    hbm = float(os.environ.get("PT_AUTOPLAN_HBM")
                or stats.get("bytes_limit") or 16e9)
    cfg = LlamaConfig.tiny()
    paddle.seed(0)
    dist.reset_mesh()
    probe = LlamaForCausalLM(cfg)
    cands = dist.plan(probe, n_devices=ndev, hbm_bytes=hbm,
                      batch=batch, seq=seq)
    assert cands and cands[0].feasible, "plan() returned no feasible config"
    del probe

    exe = list(enumerate(cands))
    env_skipped = len(cands) - len(exe)
    # measured set: the top picks + the median- and worst-ranked feasible
    # candidates (a spread the median/ratio acceptance is meaningful
    # over). The median position is pushed OUT of the measured top
    # cluster when the executable list is small — comparing the top pick
    # against a near-tied sibling would turn the gate into a coin flip
    median_pos = min(max(len(exe) // 2, n_top), len(exe) - 1)
    idxs = sorted({*range(min(n_top, len(exe))),
                   median_pos, len(exe) - 1})
    loss_fn = lambda m, x, y: m(x, labels=y)  # noqa: E731

    rows = []
    for pos in idxs:
        rank, cand = exe[pos]
        paddle.seed(0)
        dist.reset_mesh()
        model = LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        _env, step = planner.apply_plan(model, o, cand, loss_fn)
        ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
        float(step(ids, ids))  # compile
        float(step(ids, ids))  # warm
        best = 1e9
        for _ in range(3):  # best-of-3 windows defeats scheduler noise
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = step(ids, ids)
            float(loss)
            best = min(best, (time.perf_counter() - t0) / iters)
        rows.append({"rank": rank, "config": cand.describe(),
                     "predicted_ms": round(cand.predicted_step_s * 1e3, 3),
                     "measured_ms": round(best * 1e3, 3),
                     "predicted_peak_mb": round(
                         cand.predicted_peak_bytes / 1e6, 2)})
        dist.reset_mesh()

    def _spearman(xs, ys):
        rx = np.argsort(np.argsort(xs)).astype(float)
        ry = np.argsort(np.argsort(ys)).astype(float)
        if rx.std() == 0 or ry.std() == 0:
            return None
        return float(np.corrcoef(rx, ry)[0, 1])

    measured = [r["measured_ms"] for r in rows]
    predicted = [r["predicted_ms"] for r in rows]
    top_ms = rows[0]["measured_ms"]
    best_ms = min(measured)
    # "beats the median candidate" = the MEDIAN-RANKED candidate's own
    # measured time (the acceptance's wording) — NOT the sample median of
    # the measured set, which the top-3 cluster dominates (noise between
    # near-tied top picks must not flip the gate)
    median_rank = exe[median_pos][0]
    median_ms = next(r["measured_ms"] for r in rows
                     if r["rank"] == median_rank)
    # a one-candidate space has no median to beat — report None, never a
    # tautological False
    beats = None if median_pos == 0 else bool(top_ms < median_ms)
    corr = _spearman(predicted, measured)
    out = {
        "top_vs_best_ratio": round(top_ms / best_ms, 4) if best_ms else None,
        "beats_median": beats,
        "rank_corr": round(corr, 4) if corr is not None else None,
        "top_is_feasible": bool(cands[0].feasible),
        "candidates_total": len(cands),
        "n_devices": ndev,
        "top_measured_ms": top_ms,
        "top_predicted_ms": rows[0]["predicted_ms"],
        "median_candidate_ms": median_ms,
        "env_skipped": env_skipped,
        "top_config": exe[0][1].describe(),
        "hbm_gb": round(hbm / 1e9, 2),
        "batch": batch, "seq": seq,
        "measured": rows,
        "top8": [c.to_dict() for c in cands[:8]],
        "mode": "plan() over the MULTICHIP config space; top/median/worst "
                "feasible candidates trained via apply_plan",
    }
    return out


def _telemetry_overhead_probe(n=20000):
    """Micro-benchmark of the observability hot path (the ISSUE-4 overhead
    acceptance): per-increment cost of a labeled counter and per-step cost
    of an empty StepTimeline bracket, with no Profiler active. Both are a
    few dict adds — microseconds, invisible next to a multi-ms step."""
    from paddle_tpu import observability as obs

    fam = obs.family("bench_overhead_probe", ("k",))
    t0 = time.perf_counter()
    for _ in range(n):
        fam.inc(("x",))
    inc_us = (time.perf_counter() - t0) / n * 1e6
    tl = obs.StepTimeline()  # fresh instance: same cost, no global skew
    t0 = time.perf_counter()
    for _ in range(n):
        with tl.step():
            with tl.phase("host_dispatch"):
                pass
    step_us = (time.perf_counter() - t0) / n * 1e6
    return {"counter_inc": round(inc_us, 3),
            "timeline_step": round(step_us, 3), "iters": n}


def _measure_serving_warmstart():
    """Child config: time a ServingEngine bucket warmup (AOT compile of
    every declared bucket) under the persistent executable cache, and
    report the cache counters — the parent runs this twice against one
    cache dir to get cold-start vs warm-start."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import serving
    from paddle_tpu.jit import persistent_cache as pcache

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 256), nn.Tanh(), nn.Linear(256, 16))
    net.eval()
    eng = serving.ServingEngine(
        net, buckets=serving.BucketSpec(batch_sizes=(1, 2, 4, 8)),
        input_specs=[((64,), "float32")],
        config=serving.ServingConfig(warmup_on_start=True))
    t0 = time.perf_counter()
    eng.start()
    warmup_s = time.perf_counter() - t0
    snap = pcache.stats()
    eng.close()
    return {"warmup_s": round(warmup_s, 3),
            "buckets_warmed": 4,
            "cache_hits": snap["hits"], "cache_misses": snap["misses"],
            "fresh_xla_compiles": snap["compiles"],
            "cache_enabled": snap["enabled"]}


def _warm_start_probe():
    """Cold vs warm serving startup through the persistent cache: two
    subprocesses share one fresh cache directory; the second must warm its
    buckets from disk with zero fresh XLA compiles."""
    import shutil

    # a fixed path inside the checkout (the path is part of a cache key),
    # emptied first so the first child is really cold; with
    # JAX_COMPILATION_CACHE_DIR set the children use that directory instead
    # (persistent_cache.enable) and "cold" is only as cold as it is
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache",
                     "bench_warmstart")
    shutil.rmtree(d, ignore_errors=True)
    try:
        env = {"PT_PERSISTENT_CACHE_DIR": d}
        cold = _spawn("serving_warmstart", timeout=600, env=env)
        warm = _spawn("serving_warmstart", timeout=600, env=env)
        return {
            "cold_warmup_s": cold["warmup_s"],
            "warm_warmup_s": warm["warmup_s"],
            "speedup": round(cold["warmup_s"] / warm["warmup_s"], 2)
            if warm["warmup_s"] else None,
            "warm_cache_hits": warm["cache_hits"],
            "warm_fresh_xla_compiles": warm["fresh_xla_compiles"],
            "cold": cold, "warm": warm,
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _measure_serving(clients_sweep=(2, 8), per_client=100):
    """Serving smoke (docs/serving.md): closed-loop offered-load sweep over
    the batching engine — N client threads submit-and-wait against one
    ServingEngine; reports throughput + tail latency + occupancy per load
    point. Model is engine-jitted, so this runs the same on CPU CI and
    TPU."""
    import threading

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import serving

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 256), nn.Tanh(), nn.Linear(256, 16))
    net.eval()
    rng = np.random.RandomState(0)
    xs = rng.randn(64, 64).astype("float32")
    rows = []
    for n_clients in clients_sweep:
        eng = serving.ServingEngine(
            net, buckets=serving.BucketSpec(batch_sizes=(1, 2, 4, 8, 16)),
            input_specs=[((64,), "float32")],
            config=serving.ServingConfig(max_batch_wait_ms=1.0,
                                         max_queue=1024))
        eng.start()

        def client(c):
            for j in range(per_client):
                eng.submit([xs[(c * per_client + j) % 64]]).result(timeout=120)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        stats = eng.stats()
        eng.close()
        rows.append({
            "clients": n_clients,
            "throughput_rps": round(n_clients * per_client / dt, 1),
            "p50_ms": stats["latency_ms"]["p50"],
            "p99_ms": stats["latency_ms"]["p99"],
            "batch_occupancy": stats["batch_occupancy"],
            "batches": stats["counters"]["batches_total"],
        })
    out = {"sweep": rows, "requests_per_client": per_client}
    try:
        out["paged_gen"] = _measure_paged_generation()
    except Exception as e:  # the classic sweep must survive regardless
        out["paged_gen_error"] = str(e)[:300]
    return out


def _measure_paged_generation(n_clients=8, per_client=3):
    """ISSUE-12 serving tier: paged-KV generation under the production
    traffic shape — 8 clients sharing a 96-token system prompt. Reports
    prefix_hit_rate + aggregate throughput vs a no-reuse baseline
    (acceptance target >= 1.5x), speculative acceptance / effective
    tokens-per-step with a 1-layer draft, and a 2-replica router fleet vs
    the single engine. Models are tiny and engine-jitted, so the recipe
    runs the same on CPU CI and TPU."""
    import threading

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu import jit as pjit, serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    pattern = np.tile(np.arange(8), 40)

    def train(cfg, steps=70):
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        optimizer = popt.AdamW(learning_rate=3e-3,
                               parameters=model.parameters())
        step = pjit.TrainStep(model, lambda m, x, y: m(x, labels=y),
                              optimizer)
        # train the FULL position window: serving decodes at positions
        # 96..144, which must have seen gradient
        ids = paddle.to_tensor(pattern[None, :160].astype("int64"))
        for _ in range(steps):
            step(ids, ids)
        return model

    target = train(GPTConfig(vocab_size=64, hidden_size=64,
                             num_hidden_layers=2, num_attention_heads=4,
                             max_position_embeddings=160, dtype="float32"))
    draft = train(GPTConfig(vocab_size=64, hidden_size=32,
                            num_hidden_layers=1, num_attention_heads=2,
                            max_position_embeddings=160, dtype="float32"))

    system = pattern[:96].astype("int64")   # the shared 6-block prefix

    def prompts():
        # per-client unique-length tails behind the common system prompt
        # (all aligned continuations: the models stay in-distribution, so
        # the draft's proposals are acceptable ones)
        return [pattern[:97 + c % 8].astype("int64")
                for c in range(n_clients)]

    def gen_cfg(**kw):
        base = dict(max_slots=4, max_seq_len=144, page_len=16,
                    prefill_buckets=(16, 128), max_queue=256)
        base.update(kw)
        return serving.GenerationConfig(**base)

    def run(submit, close=None):
        """Closed-loop shared-prefix traffic; returns (wall_s, rps)."""
        ps = prompts()

        def client(c):
            for _ in range(per_client):
                submit(ps[c], 8).result(timeout=600)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return wall, round(n_clients * per_client / wall, 2)

    out = {"clients": n_clients, "per_client": per_client,
           "system_prompt_tokens": int(len(system))}

    # prefix reuse vs cold baseline (same engine shape, cache off) — each
    # engine closes even on a mid-section failure, so a faulted leg never
    # leaves worker threads/pools skewing the rest of the bench process
    eng_hit = serving.GenerationEngine(target, gen_cfg(prefix_cache=True))
    try:
        eng_hit.start()
        eng_hit.warmup()
        # seed the trie so the TIMED window is steady-state traffic
        eng_hit.submit(prompts()[0], max_new_tokens=2).result(timeout=600)
        _w, hit_rps = run(lambda p, m: eng_hit.submit(p, max_new_tokens=m))
        hs = eng_hit.stats()
        out["prefix_hit_rate"] = hs["prefix_hit_rate"]
        out["hit_throughput_rps"] = hit_rps
        out["retrace_events"] = hs.get("retrace_events")
    finally:
        eng_hit.close()

    eng_cold = serving.GenerationEngine(target, gen_cfg(prefix_cache=False))
    try:
        eng_cold.start()
        eng_cold.warmup()
        eng_cold.submit(prompts()[0], max_new_tokens=2).result(timeout=600)
        _w, cold_rps = run(lambda p, m: eng_cold.submit(p, max_new_tokens=m))
    finally:
        eng_cold.close()
    out["cold_throughput_rps"] = cold_rps
    out["speedup_vs_cold"] = round(hit_rps / cold_rps, 2) if cold_rps else None

    # speculative decoding (pattern-trained draft, k=4)
    eng_spec = serving.GenerationEngine(
        target, gen_cfg(prefix_cache=True, draft_model=draft, spec_tokens=4))
    try:
        eng_spec.start()
        eng_spec.warmup()
        eng_spec.submit(prompts()[0], max_new_tokens=2).result(timeout=600)
        _w, spec_rps = run(lambda p, m: eng_spec.submit(p, max_new_tokens=m))
        ss = eng_spec.stats()
        out["spec_acceptance"] = ss.get("spec_acceptance")
        out["effective_tokens_per_step"] = ss.get("effective_tokens_per_step")
        out["spec_throughput_rps"] = spec_rps
    finally:
        eng_spec.close()

    # 2-replica fleet behind the router vs the single-engine run above
    reps = [serving.GenerationEngine(target, gen_cfg(prefix_cache=True),
                                     name=f"bench_rep{i}") for i in range(2)]
    router = serving.ReplicaRouter(reps, name="bench_fleet")
    with router:
        for r in reps:
            r.warmup()
        router.submit(prompts()[0], max_new_tokens=2).result(timeout=600)
        _w, fleet_rps = run(lambda p, m: router.submit(p, max_new_tokens=m))
        rs = router.stats()
    out["fleet"] = {
        "replicas": len(reps),
        "fleet_rps": fleet_rps,
        "single_rps": hit_rps,
        "per_replica": {name: {"responses": row["responses"],
                               "prefix_hit_rate": row["prefix_hit_rate"]}
                        for name, row in rs["replicas"].items()},
        "affinity_hits": rs["affinity_hits"],
    }
    return out


def _measure_online_tune(n_requests=96, max_new=4):
    """ISSUE-20 recipe: hand-declared vs live-derived serving shapes
    (docs/performance.md, "Online tuning"). A shifted-zipf prompt stream
    — rank-weighted toward short prompts, the whole law shifted +8
    tokens midway, the workload drift the online tuner exists for — is
    replayed twice through the same pattern-trained GPT: once under
    hand-declared prefill buckets sized for an assumed long-prompt mix,
    once under buckets quantile-cover-derived from the stream's own
    length histogram (the exact ServingShapePolicy math). Headline:
    padding-waste fraction + p95 latency per leg; derived waste must be
    <= declared."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.tuning import padding_waste, quantile_cover

    # untrained weights on purpose: only the shape ECONOMICS are timed,
    # and the model is wide enough that prefill compute (which scales
    # with the PADDED length) dominates per-request latency
    pattern = np.tile(np.arange(8), 16)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=32, hidden_size=256,
                                     num_hidden_layers=2,
                                     num_attention_heads=4,
                                     max_position_embeddings=96,
                                     dtype="float32"))
    model.eval()

    # shifted zipf: P(rank r) ~ 1/r^1.3 over short lengths, then the
    # SAME law shifted +8 tokens after the mid-stream workload shift
    rng = np.random.RandomState(7)
    base = np.array([4, 6, 8, 10, 12, 16])
    pz = 1.0 / np.arange(1, len(base) + 1) ** 1.3
    pz /= pz.sum()
    half = n_requests // 2
    lens = [int(rng.choice(base, p=pz)) for _ in range(half)]
    lens += [int(rng.choice(base + 8, p=pz))
             for _ in range(n_requests - half)]

    declared = (48, 64)  # hand-tuned for an assumed long-prompt mix
    derived = quantile_cover(lens, q=1.0, max_waste=0.1, max_buckets=6)

    def run_leg(buckets):
        eng = serving.GenerationEngine(model, serving.GenerationConfig(
            max_slots=2, max_seq_len=96, page_len=8,
            prefill_buckets=tuple(buckets), max_queue=256))
        lat = []
        try:
            eng.start()
            eng.warmup()  # every bucket AOT-compiled BEFORE the stream
            prompts = [
                pattern[(i * 3) % 8:(i * 3) % 8 + n].astype("int64")
                for i, n in enumerate(lens)]
            eng.submit(prompts[0],
                       max_new_tokens=max_new).result(timeout=600)
            for p in prompts:
                t0 = time.perf_counter()
                eng.submit(p, max_new_tokens=max_new).result(timeout=600)
                lat.append((time.perf_counter() - t0) * 1e3)
        finally:
            eng.close()
        lat.sort()
        return {"buckets": [int(b) for b in buckets],
                "waste": round(padding_waste(lens, buckets), 4),
                "p50_ms": round(lat[len(lat) // 2], 2),
                "p95_ms": round(lat[int(len(lat) * 0.95)], 2)}

    a = run_leg(declared)
    b = run_leg(derived)
    # the acceptance bound: padding waste is deterministic given the
    # stream, so the derived shapes must NEVER lose to the declared
    # ones; p95 gets a small tolerance for CI timer noise
    assert b["waste"] <= a["waste"], (a, b)
    assert b["p95_ms"] <= a["p95_ms"] * 1.05, (a, b)
    return {"requests": n_requests, "shift_at": half,
            "declared": a, "derived": b,
            "waste_saved": round(a["waste"] - b["waste"], 4),
            "p95_speedup": round(a["p95_ms"] / b["p95_ms"], 2)
            if b["p95_ms"] else None}


def _measure_kv_migration(page_counts=(2, 4, 6), iters=4):
    """ISSUE-18 recipe: disaggregated prefill/decode economics. A
    compute-heavy tiny GPT (6 layers, hidden 512 — big enough that
    prefill FLOPs dominate the page bytes, which is exactly the regime
    the split targets) runs the same continuation two ways:

    - SHIP: export paged-KV pages from a prefill engine, pack them over
      the wire format, install on a decode engine, decode one token;
    - RE-PREFILL: a cold engine recomputes the whole prompt.

    Both legs are timed warm (min over post-warmup iters) and asserted
    bit-identical. Acceptance: ship beats re-prefill for prompts >= 4
    pages, int8 transit <= 0.55x the fp32 bytes, and the cost model's
    ``kv_migration_crossover`` prediction rides along for comparison."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.cost_model.comm import (
        kv_migration_crossover, link_model_for,
    )
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.kv_transfer import pack_kv_pages, unpack_kv_pages

    page_len = 16
    cfg = GPTConfig(vocab_size=64, hidden_size=512, num_hidden_layers=6,
                    num_attention_heads=8, max_position_embeddings=256,
                    dtype="float32")
    paddle.seed(0)
    # untrained weights: both legs run the SAME greedy model, so the
    # bit-identity assert and the timings don't need a training loop
    model = GPTForCausalLM(cfg)

    def mk(name):
        eng = serving.GenerationEngine(
            model, serving.GenerationConfig(
                max_slots=2, max_seq_len=128, page_len=page_len,
                num_pages=64, prefill_buckets=(48, 80, 112)),
            name=f"kvmig_{name}")
        eng.start()
        return eng

    rng = np.random.RandomState(0)
    out = {"model": "gpt-6L-512h", "page_len": page_len, "rows": []}
    src, dst, cold = mk("src"), mk("dst"), mk("cold")
    try:
        # warm every prefill bucket on every engine so the timed window
        # measures the steady state, not XLA compiles
        for eng in (src, dst, cold):
            for plen in (33, 64, 96):
                eng.submit(rng.randint(0, 64, size=plen).astype(np.int64),
                           1).result(timeout=600)
        meta = None
        k_st = v_st = None
        for npages in page_counts:
            plen = npages * page_len
            ships, refills = [], []
            for it in range(iters):
                prompt = rng.randint(0, 64, size=plen).astype(np.int64)
                first = src.submit(prompt, 1).result(timeout=600)
                cont = np.append(prompt, int(first[plen])).astype(np.int64)
                t0 = time.perf_counter()
                _n, k_st, v_st = src.export_kv_pages(prompt)
                blob, manifest, meta = pack_kv_pages(k_st, v_st)
                dst.install_kv_pages(prompt, *unpack_kv_pages(blob, manifest))
                r_ship = dst.submit(cont, 1).result(timeout=600)
                ship_ms = (time.perf_counter() - t0) * 1e3
                t0 = time.perf_counter()
                r_cold = cold.submit(cont, 1).result(timeout=600)
                refill_ms = (time.perf_counter() - t0) * 1e3
                assert r_ship.tolist() == r_cold.tolist(), \
                    "shipped-pages continuation diverged from re-prefill"
                if it:  # iter 0 absorbs the export/install compiles
                    ships.append(ship_ms)
                    refills.append(refill_ms)
            row = {"npages": npages, "prompt_tokens": plen,
                   "ship_ms": round(min(ships), 2),
                   "reprefill_ms": round(min(refills), 2),
                   "ship_vs_reprefill": round(min(ships) / min(refills), 3),
                   "wire_bytes": meta["wire_bytes"]}
            out["rows"].append(row)
            # the acceptance gate: migration must pay for itself once the
            # prompt is >= 4 pages (below that, re-prefill may win — that
            # crossover is the point of the recipe)
            if npages >= 4:
                assert row["ship_ms"] < row["reprefill_ms"], row
        # int8 transit leg: same pages, quantized wire format
        _qb, _qm, qmeta = pack_kv_pages(k_st, v_st, quantize=True)
        out["int8_wire_ratio"] = round(
            qmeta["wire_bytes"] / qmeta["fp32_bytes"], 3)
        assert out["int8_wire_ratio"] <= 0.55, out["int8_wire_ratio"]
        out["int8_bytes_saved"] = qmeta["fp32_bytes"] - qmeta["wire_bytes"]
        # what the analytic cost model predicts for this host link
        flops_per_token = 2 * sum(
            int(np.prod(p.shape)) for p in model.parameters())
        bytes_per_page = meta["fp32_bytes"] // out["rows"][-1]["npages"]
        out["cost_model"] = kv_migration_crossover(
            link_model_for("cpu-host"), page_len=page_len,
            bytes_per_page=bytes_per_page,
            flops_per_token=flops_per_token)
    finally:
        for eng in (src, dst, cold):
            eng.close()
    return out


def _measure_sparse_embed(rows=40000, dim=32, batch=256, steps=40,
                          zipf_a=2.0, parity_rows=400):
    """ISSUE-14 recipe: giant streamed embedding tables. A table sized
    4x the configured device-memory cap trains end-to-end through the
    hot-row cache + StreamLane miss streaming; A/B'd against the
    all-resident twin (same math, no streaming) and the serialized-lane
    twin (same bytes, nothing hidden); a small-table parity probe pins
    streamed == resident losses BIT-equal (incl. accumulate(2)); the
    serving leg pins the warmed fixed-shape lookup path at zero retrace/
    zero fresh compiles."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import analysis as A
    from paddle_tpu.serving import BucketSpec, ServingEngine
    from paddle_tpu.sparse import ShardedEmbeddingTable, zipf_ids

    paddle.seed(0)
    # the "device cap" this smoke configures: the hot cache must fit it,
    # the table is 4x bigger — the workload that cannot train resident
    table_bytes = rows * dim * 4
    device_cap_bytes = table_bytes // 4
    cache_rows = device_cap_bytes // (dim * 4)
    # ONE contiguous zipf stream (one hot-row permutation) sliced into
    # batches — the hot set persists across steps, which is the workload
    flat_ids = zipf_ids(batch * steps, rows, a=zipf_a, seed=100)
    ids_stream = [flat_ids[i * batch:(i + 1) * batch]
                  for i in range(steps)]

    def build(n_rows, n_cache, overlap=True, admit=2, seed=7):
        paddle.seed(0)
        table = ShardedEmbeddingTable(
            n_rows, dim, cache_rows=n_cache, n_shards=4, rule="adagrad",
            lr=0.05, seed=seed, admit_threshold=admit, overlap=overlap)
        # the dense tower a real recsys model runs on top of the lookup
        tower = nn.Sequential(nn.Linear(dim, 256), nn.ReLU(),
                              nn.Linear(256, 1))
        from paddle_tpu.optimizer import SGD

        opt = SGD(learning_rate=0.01, parameters=tower.parameters())
        return table, tower, opt

    def one_step(table, tower, opt, ids, nxt=None, update=True):
        out = table.lookup(ids)                      # [batch, dim]
        if nxt is not None:
            table.prefetch(nxt)                      # cross-step fill
        logit = tower(out)
        loss = (logit * logit).mean()
        loss.backward()
        table.flush(update=update)
        if update:
            opt.step()
            opt.clear_grad()
        return float(loss.numpy())

    def run_leg(n_cache, overlap=True, prefetch=True, admit=2):
        table, tower, opt = build(rows, n_cache, overlap=overlap,
                                  admit=admit)
        # warmup: let admission fill the hot set before timing
        warm = max(steps // 3, 5)
        for i in range(warm):
            one_step(table, tower, opt, ids_stream[i % steps],
                     nxt=ids_stream[(i + 1) % steps] if prefetch else None)
        table.lane.reset_stats()
        s0 = table.stats()
        base = {"hit": s0["hit_rows"], "miss": s0["miss_rows"]}
        times = []
        for i in range(steps):
            t0 = time.perf_counter()
            one_step(table, tower, opt, ids_stream[i],
                     nxt=ids_stream[(i + 1) % steps] if prefetch else None)
            times.append(time.perf_counter() - t0)
        # MEDIAN step time: every step fully syncs (loss readback), and
        # on a shared CPU box the mean is scheduler-straggler noise —
        # the median is the steady-state number the A/B compares
        times.sort()
        dt = times[len(times) // 2]
        s = table.stats()
        hit = s["hit_rows"] - base["hit"]
        miss = s["miss_rows"] - base["miss"]
        lane = s["lane"]
        return {
            "step_ms": round(dt * 1e3, 3),
            "hit_rate": round(hit / max(hit + miss, 1), 4),
            "streamed_mb": round(lane["h2d_bytes"] / 1e6, 3),
            "lane_transfer_ms": round(lane["transfer_ms"], 3),
            "lane_stall_ms": round(lane["stall_ms"], 3),
            "lane_hidden_ms": round(lane["hidden_ms"], 3),
            "cache_rows": s["cache_rows"],
            "prefetch_hits": s["prefetch_hits"],
        }

    streamed = run_leg(cache_rows, overlap=True, prefetch=True)
    serialized = run_leg(cache_rows, overlap=False, prefetch=False)
    resident = run_leg(rows, overlap=True, prefetch=False, admit=1)

    # -- parity probe: streamed losses BIT-equal to the all-resident
    # reference, incl. under accumulate(2) ------------------------------------
    def parity_run(n_cache, accum=1):
        table, tower, opt = build(parity_rows, n_cache, seed=11)
        rng = np.random.RandomState(3)
        losses = []
        pstream = [rng.randint(0, parity_rows, (32,)).astype(np.int64)
                   for _ in range(8)]
        for i, ids in enumerate(pstream):
            upd = (i + 1) % accum == 0
            losses.append(one_step(table, tower, opt, ids,
                                   nxt=pstream[(i + 1) % len(pstream)],
                                   update=upd))
        return losses

    bit_equal = (parity_run(parity_rows) == parity_run(parity_rows // 4)
                 and parity_run(parity_rows, accum=2)
                 == parity_run(parity_rows // 4, accum=2))

    # -- serving: warmed fixed-shape lookup, zero retrace/fresh compiles ------
    table, _tower, _opt = build(rows, cache_rows)
    for i in range(3):  # pre-warm the hot set
        table.lookup(ids_stream[i])
        table.clear_pending()
    A.retrace.enable()
    serve = {}
    try:
        eng = ServingEngine(table.serving_target(),
                            buckets=BucketSpec((1, 4), seq_lens=(16,)),
                            input_specs=[((None,), "int64")],
                            name="sparse_embed")
        eng.start()
        warm_fns = len(table._serve_fns)
        # requests slice the SAME zipf stream the table trained/warmed on
        # (same hot-row permutation) — the serving path must exercise the
        # hot cache, not an all-miss disjoint id universe
        futs = [eng.submit([flat_ids[i * 12:(i + 1) * 12]])
                for i in range(16)]
        for f in futs:
            f.result()
        st = eng.stats()
        ts = table.stats()
        serve = {
            "retrace_events": st.get("retrace_events"),
            "fresh_executables_after_warm":
                len(table._serve_fns) - warm_fns,
            "p50_ms": (st.get("latency_ms") or {}).get("p50"),
            "serve_hit_rate": round(ts["serve_hit_rows"] / max(
                ts["serve_hit_rows"] + ts["serve_miss_rows"], 1), 4),
        }
        eng.close()
    finally:
        A.retrace.disable()
        A.retrace.reset()

    return {
        "hit_rate": streamed["hit_rate"],
        "step_ms_streamed": streamed["step_ms"],
        "step_ms_resident": resident["step_ms"],
        "streamed_over_resident": round(
            streamed["step_ms"] / max(resident["step_ms"], 1e-9), 3),
        "overlap_hidden_ms": streamed["lane_hidden_ms"],
        "losses_bit_equal": bool(bit_equal),
        "table_over_cap": round(table_bytes / device_cap_bytes, 2),
        "serve_zero_retrace": serve.get("retrace_events") == 0
        and serve.get("fresh_executables_after_warm") == 0,
        "step_ms_serialized": serialized["step_ms"],
        "streamed_mb_per_step": round(
            streamed["streamed_mb"] / steps, 4),
        "table_bytes": table_bytes,
        "device_cap_bytes": device_cap_bytes,
        "cache_rows": cache_rows,
        "rows": rows,
        "dim": dim,
        "streamed_leg": streamed,
        "serialized_leg": serialized,
        "resident_leg": resident,
        "serving_lookup": serve,
    }


def _configs():
    from paddle_tpu.models import LlamaConfig

    # flagship: 1.16B Llama-recipe model on one v5e chip — d_head=128
    # (full MXU lanes), per-layer remat, flash blocks 1024/1024 (r3 sweep:
    # 49.5% @ 256/512 -> 55.8% @ 1024/1024)
    big = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=20, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=2048, dtype="bfloat16", use_recompute=True)
    # 1.83B with Adafactor's O(n+m) factored state: sized as the biggest
    # RESIDENT model under an older set-up's smaller memory budget; the
    # chip's real budget is memory_stats()['bytes_limit'] (not re-sized)
    big_1p8 = LlamaConfig(
        vocab_size=32000, hidden_size=2560, intermediate_size=6912,
        num_hidden_layers=21, num_attention_heads=20, num_key_value_heads=20,
        max_position_embeddings=2048, dtype="bfloat16", use_recompute=True)
    # long-context: same 1.16B model at 16k tokens — the flash kernel keeps
    # attention memory O(block), so MFU RISES with sequence (61%+ measured)
    long16k = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=20, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=16384, dtype="bfloat16", use_recompute=True)
    # round-over-round comparability: the round-1 374M config
    compat = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=24, num_attention_heads=8, num_key_value_heads=8,
        max_position_embeddings=2048, dtype="bfloat16", use_recompute=True)
    from paddle_tpu.models import LlamaMoEConfig
    from paddle_tpu.models.dit import DiTConfig

    # MoE flagship (BASELINE config 5): DeepSeekMoE-style small-expert
    # recipe — 8 experts/top-2, per-expert FFN smaller than dense, 1.44B
    # total / ~0.55B activated. Adafactor keeps optimizer state O(n+m) so
    # the full expert stack stays resident.
    moe = LlamaMoEConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=2048,
        num_hidden_layers=16, num_attention_heads=12, num_key_value_heads=12,
        max_position_embeddings=2048, dtype="bfloat16", use_recompute=True,
        num_experts=8, top_k=2, capacity_factor=1.25)
    import dataclasses

    moe_cf1 = dataclasses.replace(moe, capacity_factor=1.0)
    # DiT flagship (BASELINE config 4): the published DiT-XL/2 shape at the
    # ImageNet-256 latent (32x32x4, patch 2 -> 256 tokens)
    dit = DiTConfig.dit_xl_2(dtype="bfloat16")
    # streamed-offload capacity demo: 3.08B params
    # (stacked weights + optimizer state in pinned host memory, layerwise
    # streaming; batch 2 keeps the remat boundary activations under the
    # compiler's HBM budget). The resident ceiling is 1.83B and 2.0B OOMs
    # outright; ~3.1B is where the compiler's memory-space assignment runs
    # out of headroom for the grad chains it HBM-places.
    stream_31 = LlamaConfig(
        vocab_size=32000, hidden_size=2816, intermediate_size=7680,
        num_hidden_layers=30, num_attention_heads=22, num_key_value_heads=22,
        max_position_embeddings=2048, dtype="bfloat16", use_recompute=True)
    # segmented-offload capacity: 4.49B params, per-layer host buffers +
    # hand-segmented backward (no stacked grad chain to HBM-place)
    seg_45 = LlamaConfig(
        vocab_size=32000, hidden_size=3328, intermediate_size=8960,
        num_hidden_layers=32, num_attention_heads=26, num_key_value_heads=26,
        max_position_embeddings=2048, dtype="bfloat16", use_recompute=True)
    # BASELINE config 3 shape on ONE chip: the published Llama-2-7B
    # architecture (6.74B params) through the segmented path — per-layer
    # host buffers (~404MB/layer), boundary activations spilled, edge
    # params resident. Capacity evidence, not throughput (host-link bound).
    llama7b = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=4096, dtype="bfloat16", use_recompute=True)
    return {"big": big, "adafactor_1p8b": big_1p8, "long_seq_16k": long16k,
            "compat_374m": compat, "moe": moe, "moe_cf1": moe_cf1,
            "dit": dit,
            "stream_capacity_full": stream_31, "seg_capacity": seg_45,
            "llama7b_seg": llama7b}


def _cpu_smoke():
    """Child-process entry for a CPU backend (tests/CI): every in-process
    leg at tiny size. Counts and control flow only — the row carries no
    ``mfu`` (a CPU timing is never a device metric)."""
    import jax

    from paddle_tpu.models import LlamaConfig

    if jax.devices()[0].platform != "cpu":
        raise RuntimeError("cpu_smoke is the CPU-backend leg; on a chip "
                           "run the recipes")
    detail = _measure(LlamaConfig.tiny(), batch=2, seq=64, iters=2)
    detail.pop("mfu")
    _note_recipe("cpu_smoke", detail)
    for key, fn in (
            ("warm_path", lambda: _measure_warm_path(
                LlamaConfig.tiny(), batch=2, seq=64, iters=3, accum=4)),
            ("stream_capacity", lambda: _measure_stream_ab(
                LlamaConfig.tiny(), batch=2, seq=64, iters=3)),
            ("checkpoint_stall", lambda: _measure_checkpoint_stall(
                LlamaConfig.tiny(), batch=2, seq=64)),
            ("serving", lambda: _measure_serving(clients_sweep=(2, 8),
                                                 per_client=30)),
            ("fused_kernels", _measure_fused_kernels),
            ("sparse_embed", _measure_sparse_embed),
            ("kv_migration", _measure_kv_migration),
            ("online_tune", _measure_online_tune)):
        try:  # the smoke must never sink the bench
            detail[key] = fn()
            _note_recipe(key, detail[key])
        except Exception as e:
            detail[f"{key}_error"] = str(e)[:300]
    return detail


def _run_one(name: str):
    """Child-process entry: one config per process so each gets the whole
    HBM (a prior config's live executables would otherwise OOM the next)."""
    if name == "platform":
        import jax

        dev = jax.devices()[0]
        print("BENCH_RESULT " + json.dumps(
            {"platform": dev.platform, "device_kind": dev.device_kind,
             "count": len(jax.devices())}))
        return
    if name == "cpu_smoke":
        print("BENCH_RESULT " + json.dumps(_cpu_smoke()))
        return
    if name == "resnet_cifar_cpuref":
        # the single-device CPU reference of BASELINE config 1 — pin the
        # backend BEFORE any jax device use
        import jax

        jax.config.update("jax_platforms", "cpu")
        print("BENCH_RESULT " + json.dumps({"losses": _resnet_cifar_losses()}))
        return
    if name in ("resnet_cifar", "bert_finetune"):
        out = (_measure_resnet_cifar() if name == "resnet_cifar"
               else _measure_bert_finetune())
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "serving":
        out = _measure_serving()
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "serving_warmstart":
        out = _measure_serving_warmstart()
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "online_tune":
        out = _measure_online_tune()
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "kv_migration":
        out = _measure_kv_migration()
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "warm_path":
        import jax

        from paddle_tpu.models import LlamaConfig

        if jax.devices()[0].platform == "cpu":
            out = _measure_warm_path(LlamaConfig.tiny(), batch=2, seq=64,
                                     iters=3, accum=4)
        else:
            out = _measure_warm_path(_configs()["big"], batch=4, seq=2048,
                                     iters=4, accum=4)
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "stream_capacity":
        import jax

        from paddle_tpu.models import LlamaConfig

        if jax.devices()[0].platform == "cpu":
            out = _measure_stream_ab(LlamaConfig.tiny(), batch=2, seq=64,
                                     iters=3)
        else:
            out = _measure_stream_ab(_configs()["big"], batch=4, seq=2048,
                                     iters=3)
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "fused_kernels":
        out = _measure_fused_kernels()
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "sparse_embed":
        import jax

        if jax.devices()[0].platform == "cpu":
            out = _measure_sparse_embed()
        else:
            # TPU leg: a bigger table (still host-RAM bound, 4x the
            # configured cap) and a longer timed window
            out = _measure_sparse_embed(rows=400000, dim=64, batch=1024,
                                        steps=40)
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "autoplan":
        # the ranking-fidelity leg runs on the 8-device CPU host mesh (the
        # MULTICHIP dryrun topology) regardless of the parent's platform —
        # pin the backend BEFORE any jax device use, like the cpuref leg
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            " --xla_force_host_platform_device_count=8"
        import jax

        jax.config.update("jax_platforms", "cpu")
        out = _measure_autoplan()
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    if name == "checkpoint_stall":
        import jax

        from paddle_tpu.models import LlamaConfig

        if jax.devices()[0].platform == "cpu":
            out = _measure_checkpoint_stall(LlamaConfig.tiny(), batch=2,
                                            seq=64)
        else:
            out = _measure_checkpoint_stall(_configs()["big"], batch=4,
                                            seq=2048)
        _note_recipe(name, out)
        print("BENCH_RESULT " + json.dumps(out))
        return
    import paddle_tpu.optimizer as opt_mod

    cfg = _configs()[name]
    if name == "big":
        out = _measure(cfg, batch=16, seq=2048, iters=8, device_table=True)
    elif name == "adafactor_1p8b":
        out = _measure(cfg, batch=4, seq=2048, iters=6,
                       optimizer_cls=opt_mod.Adafactor)
    elif name == "long_seq_16k":
        out = _measure(cfg, batch=2, seq=16384, iters=4)
    elif name == "moe":
        out = _measure_moe(cfg, batch=8, seq=2048, iters=6)
        try:
            out["dispatch_probe"] = _moe_dispatch_share(cfg, batch=8,
                                                        seq=2048)
        except Exception as e:  # the probe must never sink the bench
            out["dispatch_probe_error"] = str(e)[:200]
        try:
            # the ISSUE-13 A/B: the same probe through the fused Pallas
            # routing/dispatch kernel (dropless, grouped-matmul FFN)
            from paddle_tpu.framework import flags as flags_mod

            flags_mod.set_flags({"FLAGS_moe_dispatch": "fused"})
            out["dispatch_probe_fused"] = _moe_dispatch_share(
                cfg, batch=8, seq=2048)
            out["dispatch_share_fused"] = \
                out["dispatch_probe_fused"]["dispatch_share"]
            flags_mod.set_flags({"FLAGS_moe_dispatch": "index"})
        except Exception as e:
            out["dispatch_probe_fused_error"] = str(e)[:200]
    elif name == "moe_cf1":
        # tight-capacity variant (dropless-style recipes set cf=1.0): no
        # 25% expert overcompute, so activated == executed MFU. Own process
        # like every config — the one-config-per-process HBM rule
        out = _measure_moe(cfg, batch=8, seq=2048, iters=6)
    elif name == "dit":
        out = _measure_dit(cfg, batch=32, iters=8)
    elif name == "stream_capacity_full":
        out = _measure_stream(cfg, batch=2, seq=2048, iters=3)
    elif name == "seg_capacity":
        out = _measure_segmented(cfg, batch=2, seq=2048, iters=2)
    elif name == "llama7b_seg":
        # batch 1: batch 2 compiles 1.5G over the HBM budget (the latency-
        # hiding scheduler prefetches several layers' params as temps)
        out = _measure_segmented(cfg, batch=1, seq=2048, iters=1)
    else:
        out = _measure(cfg, batch=4, seq=2048, iters=8)
        try:
            out["op_table"] = _op_table(cfg, batch=2, seq=512)
        except Exception as e:  # profiling must never sink the bench
            out["op_table_error"] = str(e)[:200]
    _note_recipe(name, out)
    print("BENCH_RESULT " + json.dumps(out))


_BENCH_ROWS = {}


def _note_recipe(name, out):
    """Satellite contract: every recipe's compact headline also lands in
    the observability registry (the "bench" provider) and the process
    dumps one full ``observability.snapshot()`` next to the BENCH
    artifacts — so BENCH trajectories carry cache/retrace/step-timeline
    context, not just wall clock."""
    try:
        from paddle_tpu import observability as obs

        _BENCH_ROWS[name] = _compact(out) if isinstance(out, dict) else out
        obs.register_provider("bench", lambda: dict(_BENCH_ROWS))
        if name == "autoplan" and isinstance(out, dict):
            # ranking-fidelity provider (ISSUE-10 acceptance: reported in
            # the telemetry dump, not just the headline). Registered HERE
            # so the PARENT process — whose later dumps overwrite a
            # spawned child's telemetry file — carries it too.
            ap = {
                "fidelity": {k: out.get(k) for k in (
                    "top_vs_best_ratio", "beats_median", "rank_corr",
                    "top_config", "candidates_total", "top_measured_ms",
                    "top_predicted_ms", "env_skipped")},
                "measured": out.get("measured") or [],
                "top8": out.get("top8") or [],
            }
            obs.register_provider("autoplan", lambda: ap)
        obs.dump(os.path.join("bench_artifacts", f"telemetry_{name}.json"))
    except Exception:
        pass  # telemetry must never sink the bench


_LIVE_PROCS = set()  # in-flight _spawn children; the watchdog reaps them


def _spawn(name: str, timeout=1200, env=None):
    import subprocess

    # every leg respects the process-wide deadline: never start a child
    # whose own budget would outlive it (the r05 blackout was one recipe
    # eating the whole harness window)
    rem = _remaining_s()
    if rem is not None:
        if rem < 60:
            raise RuntimeError(f"bench budget exhausted before {name}")
        timeout = min(timeout, max(rem - 30, 30))
    child_env = None
    if env:
        child_env = dict(os.environ)
        child_env.update(env)
    p = subprocess.Popen([sys.executable, os.path.abspath(__file__),
                          "--config", name], stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True, env=child_env)
    _LIVE_PROCS.add(p)
    try:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            raise
    finally:
        _LIVE_PROCS.discard(p)
    for line in out.splitlines():
        if line.startswith("BENCH_RESULT "):
            return json.loads(line[len("BENCH_RESULT "):])
    raise RuntimeError(f"bench config {name} failed:\n{err[-2000:]}")


# keys too large for the driver-parsed line (r4's parse failure was an
# oversized single line); they live in the artifact file instead
_HEAVY_KEYS = ("device_op_table", "op_table", "losses_tpu", "losses_cpu",
               "dispatch_probe", "dispatch_probe_fused", "cold", "warm",
               "measured", "top8", "moe_fused", "moe_index", "paged_decode",
               "streamed_leg", "serialized_leg", "resident_leg")

# -- wall-clock contract ------------------------------------------------------
# the r05 blackout was rc=124 with NOTHING on stdout: one leg overran the
# harness window before the first headline printed. Two defenses now:
# a process-wide deadline every leg respects (skip-and-note past it), and
# a headline that is the FIRST line printed and is re-printed as the LAST
# line on ANY exit, SIGTERM included.
_DEADLINE = None          # monotonic seconds; None = no budget
_LAST_HEADLINE = None     # most recent parseable headline line


def _arm_budget():
    global _DEADLINE
    # 1500s default: r05 proved 3000s overruns the harness window (rc 124
    # with a SIGKILL that no handler can catch) — the bench must finish and
    # re-print its headline BEFORE any external timeout lands
    budget = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    if budget > 0:
        _DEADLINE = time.monotonic() + budget
        _start_watchdog(budget)


def _start_watchdog(budget: float):
    """Blackout round-3 defense: the r05 round died rc=124 with
    parsed=null DESPITE the atexit/SIGTERM re-print, because ``timeout
    -k 10``'s follow-up SIGKILL landed before the handler finished — a
    Python signal handler only runs when the MAIN thread surfaces from
    native code, and a main thread pinned inside an XLA compile never
    does. This thread needs no cooperation: it emits the most recent
    headline and exits 0 with margin to spare BEFORE the external
    window closes, headline-last contract intact."""
    import threading

    margin = min(45.0, max(budget * 0.15, 5.0))
    fire_at = _DEADLINE - margin

    def watch():
        while True:
            rem = fire_at - time.monotonic()
            if rem <= 0:
                break
            time.sleep(min(rem, 5.0))
        # deliberate trade-off: a leg still running here has overrun the
        # budget every other leg respected (skip-and-note at rem<90) —
        # truncating it keeps every COMPLETED leg's row (the headline
        # re-emits after each leg) where the external SIGKILL would leave
        # rc=124 and possibly nothing. Exit 0 only when the flagship
        # value actually landed; a stub-only run is still a failure.
        # Reap in-flight recipe children first: os._exit would orphan
        # them to keep burning CPU (and rewriting artifacts) under
        # whatever the harness runs next.
        for p in list(_LIVE_PROCS):
            try:
                p.kill()
            except Exception:
                pass
        if _LAST_HEADLINE is not None:
            # print(), not os.write: this is an ordinary thread, and the
            # TextIOWrapper lock serializes against a main thread caught
            # mid-_emit — a raw fd write could land INSIDE its buffered
            # flush and corrupt the last-line contract (the signal-handler
            # path keeps os.write, where reentrancy is the hazard instead)
            print("\n" + _LAST_HEADLINE, flush=True)
        try:
            ok = json.loads(_LAST_HEADLINE)["value"] is not None
        except Exception:
            ok = False
        os._exit(0 if ok else 1)

    threading.Thread(target=watch, daemon=True,
                     name="pt-bench-watchdog").start()


def _prior_headline():
    """Startup read-back of the on-disk headline (satellite of the same
    blackout): a prior round interrupted hard enough to lose stdout still
    surfaces its last parseable result in THIS round's starting stub."""
    try:
        with open(os.path.join("bench_artifacts", "headline.json")) as f:
            row = json.loads(f.read())
        if isinstance(row, dict) and row.get("value") is not None:
            return {"value": row.get("value"),
                    "vs_baseline": row.get("vs_baseline")}
    except Exception:
        pass
    return None


def _remaining_s():
    if _DEADLINE is None:
        return None
    return _DEADLINE - time.monotonic()


def _emit(line):
    global _LAST_HEADLINE
    _LAST_HEADLINE = line
    # every emission also lands on disk: even a SIGKILL mid-run leaves the
    # most recent parseable headline in bench_artifacts/headline.json
    try:
        os.makedirs("bench_artifacts", exist_ok=True)
        tmp = os.path.join("bench_artifacts", ".headline.tmp")
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, os.path.join("bench_artifacts", "headline.json"))
    except OSError:
        pass  # artifact bookkeeping must never sink the bench
    print(line, flush=True)


def _emit_final(*_sig):
    """Last line of output = the most complete parseable headline (also
    the SIGTERM path: an external timeout still leaves a result)."""
    if _sig:  # signal path: the main thread may be mid-print on the same
        # buffered stdout, where print() would raise a reentrancy error —
        # os.write is signal-safe. Exit before the -k SIGKILL lands.
        if _LAST_HEADLINE is not None:
            os.write(1, ("\n" + _LAST_HEADLINE + "\n").encode())
        os._exit(0 if _LAST_HEADLINE is not None else 1)
    if _LAST_HEADLINE is not None:
        print(_LAST_HEADLINE, flush=True)


def _install_exit_headline():
    import atexit
    import signal

    atexit.register(_emit_final)
    try:
        signal.signal(signal.SIGTERM, _emit_final)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass


def _compact(obj):
    """Strip bulky sub-objects so a printed line stays parseable-small."""
    if isinstance(obj, dict):
        return {k: _compact(v) for k, v in obj.items()
                if k not in _HEAVY_KEYS}
    if isinstance(obj, list):
        return obj if len(obj) <= 16 else obj[:16]
    return obj


# the driver that parses the headline keeps only the LAST ~2000 bytes of
# stdout (the r04 blackout: a detail-laden final line was cut mid-JSON and
# read as parsed=null) — every emitted headline must fit well under that
_HEADLINE_MAX = 1800


def _scalar_row(obj, keep=8):
    """First few numeric entries of one recipe row — the shrunken detail a
    size-capped headline carries (full rows live in bench_progress.json)."""
    if not isinstance(obj, dict):
        return obj if isinstance(obj, (int, float, bool)) else None
    out = {}
    for k, v in obj.items():
        if isinstance(v, (int, float, bool)):
            out[k] = v
            if len(out) >= keep:
                break
    return out


def _headline(big, detail):
    base = {
        "metric": "llama_pretrain_mfu",
        "value": big["mfu"],
        "unit": "%",
        "vs_baseline": (None if big["mfu"] is None
                        else round(big["mfu"] / 38.0, 3)),
    }
    line = json.dumps(dict(base, detail=_compact(detail)))
    if len(line) > _HEADLINE_MAX:
        # shrink every recipe row to its leading scalars
        slim = {k: _scalar_row(v) for k, v in detail.items()}
        slim = {k: v for k, v in slim.items() if v not in (None, {})}
        slim["see"] = "bench_artifacts/bench_progress.json"
        line = json.dumps(dict(base, detail=slim))
    if len(line) > _HEADLINE_MAX:  # belt and braces: pointer-only stub
        line = json.dumps(dict(base, detail={
            "truncated": True,
            "see": "bench_artifacts/bench_progress.json"}))
    return line


def _write_artifact(detail):
    try:
        os.makedirs("bench_artifacts", exist_ok=True)
        tmp = os.path.join("bench_artifacts", ".bench_progress.tmp")
        with open(tmp, "w") as f:
            json.dump(detail, f, indent=1)
        os.replace(tmp, os.path.join("bench_artifacts",
                                     "bench_progress.json"))
    except OSError:
        pass  # artifact bookkeeping must never sink the bench


def main():
    """Driver contract (three rounds of parsed=null taught us this shape):

    - a compact headline is the FIRST line of output (a stub until the
      flagship lands) and is re-printed as the LAST line on every exit
      path, SIGTERM included — an external kill still leaves the most
      complete parseable result on stdout;
    - every recipe runs under the process-wide budget (BENCH_BUDGET_S,
      default 3000s) AND its own leg timeout; a leg that would outlive the
      budget is skipped with a note instead of blacking out the run;
    - after every recipe the headline reprints with the detail so far
      (compact: heavy tables live in bench_artifacts/bench_progress.json);
    - slow capacity/parity legs (10-90 min each) only run with --full or
      BENCH_FULL=1: the default run fits a CI budget.
    """
    # ONE process per chip: this parent never imports jax or paddle_tpu —
    # a parent that has touched JAX holds the chip and every recipe child
    # would then fail to load the TPU library. CPU-vs-chip is decided by a
    # child that reports its platform and exits.
    _arm_budget()
    _install_exit_headline()
    prior = _prior_headline()  # read BEFORE the stub emit overwrites it
    stub = {"status": "starting"}
    if prior:
        stub["prior_round"] = prior
    # FIRST line of output: parseable immediately, value filled in later
    _emit(json.dumps({"metric": "llama_pretrain_mfu", "value": None,
                      "unit": "%", "vs_baseline": None,
                      "detail": stub}))
    full = "--full" in sys.argv or \
        os.environ.get("BENCH_FULL", "") in ("1", "true")
    platform = _spawn("platform", timeout=300)["platform"]
    if platform != "tpu":  # CI smoke on CPU: counts and control flow only
        smoke = _spawn("cpu_smoke", timeout=1200)
        # no CPU number is ever written under the device metric's name:
        # the headline's value stays null ("not measured")
        big = {"mfu": None}
        detail = dict(smoke, platform=platform)
        try:  # own process: the fidelity leg needs the 8-device host mesh
            detail["autoplan"] = _spawn("autoplan", timeout=600)
        except Exception as e:
            detail["autoplan_error"] = str(e)[:300]
        try:
            detail["persistent_cache"] = _warm_start_probe()
        except Exception as e:
            detail["persistent_cache_error"] = str(e)[:300]
        _write_artifact(detail)  # same artifact contract as the TPU path
        _emit(_headline(big, detail))
        return

    big = _spawn("big", timeout=1500)
    detail = dict(big)
    detail["platform"] = "tpu"
    _emit(_headline(big, detail))  # the early headline
    _write_artifact(detail)

    def leg(key, fn):
        rem = _remaining_s()
        if rem is not None and rem < 90:
            detail.setdefault("skipped_over_budget", []).append(key)
            _write_artifact(detail)
            return
        try:
            fn()  # the child noted its own row (_run_one -> _note_recipe):
            # the parent stays off paddle_tpu/jax between spawns
        except Exception as e:
            detail[f"{key}_error"] = str(e)[:300]
        _write_artifact(detail)
        _emit(_headline(big, detail))

    def _adafactor():
        big_model = _spawn("adafactor_1p8b")
        detail["adafactor_1p8b"] = big_model
        detail["hbm_envelope"] = {
            "bytes_limit": big_model.get("bytes_limit"),
            "method": "memory_stats()['bytes_limit'] read in the child",
            "resident_max_params_m": big_model["params_m"]}

    leg("adafactor_1p8b", _adafactor)
    leg("long_seq_16k",
        lambda: detail.__setitem__("long_seq_16k", _spawn("long_seq_16k")))
    leg("compat_374m",
        lambda: detail.__setitem__("compat_374m", _spawn("compat_374m")))

    def _moe():
        detail["moe"] = _spawn("moe")
        try:
            detail["moe"]["cf1_variant"] = _spawn("moe_cf1")
        except Exception as e:
            detail["moe"]["cf1_variant_error"] = str(e)[:300]

    leg("moe", _moe)
    leg("dit", lambda: detail.__setitem__("dit", _spawn("dit")))
    leg("serving", lambda: detail.__setitem__("serving", _spawn("serving")))
    leg("online_tune",
        lambda: detail.__setitem__("online_tune",
                                   _spawn("online_tune", timeout=900)))
    leg("warm_path",
        lambda: detail.__setitem__("warm_path", _spawn("warm_path")))
    leg("autoplan",
        lambda: detail.__setitem__("autoplan", _spawn("autoplan",
                                                      timeout=600)))
    leg("fused_kernels",
        lambda: detail.__setitem__("fused_kernels",
                                   _spawn("fused_kernels", timeout=900)))
    leg("sparse_embed",
        lambda: detail.__setitem__("sparse_embed",
                                   _spawn("sparse_embed", timeout=900)))
    leg("stream_capacity",
        lambda: detail.__setitem__("stream_capacity",
                                   _spawn("stream_capacity")))
    leg("checkpoint_stall",
        lambda: detail.__setitem__("checkpoint_stall",
                                   _spawn("checkpoint_stall")))
    leg("persistent_cache",
        lambda: detail.__setitem__("persistent_cache", _warm_start_probe()))

    if full:
        def _resnet():
            # BASELINE config 1: parity (the child spawns the CPU-ref
            # grandchild, which trains on 1 CPU core — generous budget)
            detail["resnet_cifar"] = _spawn("resnet_cifar", timeout=3600)

        leg("resnet_cifar", _resnet)
        leg("bert_finetune", lambda: detail.__setitem__(
            "bert_finetune", _spawn("bert_finetune", timeout=2400)))

        def _seg():
            detail["seg_capacity"] = _spawn("seg_capacity", timeout=3600)
            detail.setdefault("hbm_envelope", {})["segmented_max_params_b"] \
                = detail["seg_capacity"]["params_b"]

        leg("seg_capacity", _seg)

        def _llama7b():
            # BASELINE config 3 architecture (Llama-2-7B) as a single-chip
            # capacity row — slow by nature (host-link bound), own budget
            detail["llama7b_seg"] = _spawn("llama7b_seg", timeout=5400)
            detail.setdefault("hbm_envelope", {})["segmented_llama7b"] = True

        leg("llama7b_seg", _llama7b)

        def _stream():
            # host-side init + the layerwise-streaming compile are slow by
            # nature; give this capacity demo its own generous budget
            detail["stream_capacity_full"] = _spawn("stream_capacity_full",
                                                    timeout=3000)
            row = detail["stream_capacity_full"]
            detail["hbm_envelope"] = dict(
                detail.get("hbm_envelope", {}),
                streamed_max_params_b=row["params_b"],
                streamed_step_time_s=row["step_time_s"],
                note="resident ceiling 1.83B (2.0B OOMs); streamed "
                     "pinned-host offload trains 3.08B on the same chip; "
                     "larger sizes stop in the compiler's memory-space "
                     "pass, which HBM-places the grad chains (18.7G "
                     "estimate at 4B)")

        leg("stream_capacity_full", _stream)
    else:
        detail["skipped_legs"] = {
            "names": ["resnet_cifar", "bert_finetune", "seg_capacity",
                      "llama7b_seg", "stream_capacity_full"],
            "reason": "slow capacity/parity legs; rerun with --full or "
                      "BENCH_FULL=1 (rows land in bench_artifacts/)"}
        _write_artifact(detail)
        _emit(_headline(big, detail))


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--config":
        _run_one(sys.argv[2])
    else:
        main()
