"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip, in sequence (``python chip_smoke.py``):

- *device*   refuse to run unless JAX's first device is a TPU;
- *kernels*  every registered Pallas kernel at the smoke's real widths
             against its jnp reference, ON the chip;
- *train*    a 1.16B Llama-shaped model at full widths
             (hidden 2048, MLP 5632, 16 heads x 128, vocab 32000, bf16,
             remat, seq 2048, 20 layers) through ``jit.TrainStep`` + AdamW:
             the largest of batch 16/8/4 that fits, finite decreasing
             losses on a fixed batch, the kernels really in the program;
- *serve*    ``GPTForCausalLM(GPTConfig.gpt2_small())`` behind
             ``serving.GenerationEngine`` (paged cache): concurrent
             requests of unequal length streamed to completion, compared
             with ``model.generate`` and the dense forward.

``--chips 4`` runs ONLY the multi-chip phase and its one-chip comparison
(``ShardedTrainStep`` over the four real devices, same Llama widths at
reduced depth). There is no size option: the phases are functions of a
model config so that ``tests/test_chip_smoke.py`` rehearses them at tiny
sizes on the CPU, but ``main()`` always runs the real sizes and refuses to
run without a chip. Any phase that raises makes the exit code non-zero.

The LAST line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Every speed printed before it is a first observation on that device, not a
baseline.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import threading
import time

SEED = 0
# bf16 keeps 8 mantissa bits: a logit of magnitude 2-4 carries ~0.02 of
# rounding, and 12 layers reduced in two different orders (a batched paged
# window vs an unbatched dense forward) compound it. Differences up to this
# are rounding; a wrong cache page or mask is O(1).
LOGIT_TOL = 0.1
# relative band for losses of the SAME seed under another layout (bf16
# partial sums land in another order on a mesh)
LOSS_RTOL = 1e-2


def say(phase: str, **fields) -> None:
    """One ``phase key=value ...`` line on stdout (never the last line)."""
    print(phase + " " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# -- device ------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU chip — jax.devices()[0].platform is "
            f"{devs[0].platform!r}. This script proves the system on the "
            f"chip and has no CPU mode.")
    if len(devs) != chips:
        raise SystemExit(
            f"chip_smoke: expected {chips} chip(s), JAX reports {len(devs)}"
            + (" (pass --chips 4 for the multi-chip phase)"
               if chips == 1 else ""))
    import jaxlib
    from importlib import metadata

    stats = devs[0].memory_stats() or {}
    say("device", platform=devs[0].platform,
        kind=repr(devs[0].device_kind), count=len(devs),
        bytes_limit=stats.get("bytes_limit"), jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=metadata.version("libtpu"))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCacheCounter:
    """JAX's persistent compilation cache, on before the first compile, at
    the repo's one cache directory; counts its hits and misses."""

    def __init__(self):
        import jax
        from paddle_tpu.jit import persistent_cache

        self.dir = persistent_cache.enable_jax_compilation_cache()
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self) -> None:
        say("compile_cache", dir=self.dir, hits=self.hits,
            misses=self.misses)


# -- the kernel table ----------------------------------------------------------

def kernel_calls() -> dict:
    from paddle_tpu.kernels import registry

    return {op: dict(row["calls"])
            for op, row in registry.kernel_table()["ops"].items()}


def check_kernel_table(phase: str, ops, expect_impl: str, before: dict):
    """Every op of ``ops`` was resolved to ``expect_impl`` at least once
    since ``before`` and to nothing else: no interpreter and no jnp
    reference on a path that claims the Pallas kernel."""
    from paddle_tpu.kernels import registry

    table = registry.kernel_table()
    for op in ops:
        row = table["ops"][op]
        delta = {k: row["calls"][k] - before[op][k] for k in registry.IMPLS}
        say(f"{phase}.kernel", op=op, impl=row["impl"],
            calls=json.dumps(delta))
        assert row["impl"] == expect_impl, (op, row["impl"], expect_impl)
        assert delta[expect_impl] > 0, f"{op}: never taken on {phase} path"
        others = {k: v for k, v in delta.items() if k != expect_impl and v}
        assert not others, f"{op}: also resolved to {others}"


# -- kernels: Pallas vs jnp reference, on the device --------------------------

def kernels_phase(impl: str, *, rope_shape, norm_shape, paged, moe) -> None:
    """Run every registered kernel through ``impl`` and through its jnp
    reference on the same inputs; the results must agree to bf16
    rounding. A kernel the compiler accepts is not yet a kernel that is
    right — this is where the repaired ones are shown to be."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.pallas import (moe_dispatch, paged_attention,
                                           rmsnorm, rope)

    keys = iter(jax.random.split(jax.random.key(SEED), 16))

    def close(name, got, ref, tol):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        say("kernels", op=name, impl=impl, max_abs_err=f"{err:.3g}",
            tol=tol)
        assert np.isfinite(err) and err <= tol, (name, err, tol)

    bf16 = jnp.bfloat16
    x = jax.random.normal(next(keys), rope_shape, bf16)
    for off in (0, 7):
        close(f"rope@{off}", rope.rope_apply(x, 1e4, off, impl=impl),
              rope.rope_apply(x, 1e4, off, impl="reference"), 0.05)
    g = jax.grad(lambda z, i: jnp.sum(jnp.sin(
        rope.rope_apply(z, 1e4, 0, impl=i).astype(jnp.float32))),
        argnums=0)
    close("rope.vjp", g(x, impl), g(x, "reference"), 0.05)

    x = jax.random.normal(next(keys), norm_shape, bf16)
    r = jax.random.normal(next(keys), norm_shape, bf16)
    w = 1.0 + 0.1 * jax.random.normal(next(keys), norm_shape[-1:], bf16)
    close("rms_norm", rmsnorm.rms_norm(x, w, 1e-6, impl=impl),
          rmsnorm.rms_norm(x, w, 1e-6, impl="reference"), 0.05)
    yi, si = rmsnorm.rms_norm_residual(x, r, w, 1e-6, impl=impl)
    yc, sc = rmsnorm.rms_norm_residual(x, r, w, 1e-6, impl="reference")
    close("rms_norm_residual.y", yi, yc, 0.05)
    close("rms_norm_residual.s", si, sc, 0.05)

    S, nh, hd, PL, B = paged["slots"], paged["heads"], paged["head_dim"], \
        paged["page_len"], paged["blocks"]
    P = S * B + 1
    ka = jax.random.normal(next(keys), (P, PL, nh, hd), bf16)
    va = jax.random.normal(next(keys), (P, PL, nh, hd), bf16)
    # each slot owns its own pages (page 0 is the scratch page)
    tables = 1 + jnp.arange(S * B, dtype=jnp.int32).reshape(S, B)
    for W in paged["windows"]:
        q = jax.random.normal(next(keys), (S, W, nh, hd), bf16)
        start = jnp.arange(S, dtype=jnp.int32) * 3 + (B * PL) // 2
        pos = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        close(f"paged_attention[W={W}]",
              paged_attention.paged_attention(q, ka, va, tables, pos,
                                              impl=impl),
              paged_attention.paged_attention(q, ka, va, tables, pos,
                                              impl="reference"), 0.05)

    b, s, h, e, inter, k = moe["batch"], moe["seq"], moe["hidden"], \
        moe["experts"], moe["inter"], moe["top_k"]
    x = jax.random.normal(next(keys), (b, s, h), bf16)
    wg = 0.1 * jax.random.normal(next(keys), (h, e), jnp.float32)
    w_gate = 0.02 * jax.random.normal(next(keys), (e, h, inter), bf16)
    w_up = 0.02 * jax.random.normal(next(keys), (e, h, inter), bf16)
    w_down = 0.02 * jax.random.normal(next(keys), (e, inter, h), bf16)
    oi, auxi = moe_dispatch.fused_moe_mlp(x, wg, w_gate, w_up, w_down,
                                          top_k=k, impl=impl)
    oc, auxc = moe_dispatch.fused_moe_mlp(x, wg, w_gate, w_up, w_down,
                                          top_k=k, impl="reference")
    close("moe_dispatch.out", oi, oc, 0.05)
    close("moe_dispatch.aux", auxi, auxc, 1e-3)


# -- train ---------------------------------------------------------------------

def _fixed_batch(vocab: int, batch: int, seq: int):
    import numpy as np

    import paddle_tpu as paddle

    ids = np.random.RandomState(SEED).randint(0, vocab, (batch, seq))
    return paddle.to_tensor(ids.astype("int64"))


def _program_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)


def train_phase(cfg, *, seq: int, batches, steps: int,
                expect_impl: str) -> dict:
    """``steps`` optimizer steps after the compiling one, on a fixed batch:
    losses finite and strictly decreasing. ``batches`` are tried largest
    first; the first whose compiled program fits the device's own
    ``bytes_limit`` is the one that runs."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaForCausalLM, llama_flops_per_token

    before = kernel_calls()
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    paddle.seed(SEED)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          weight_decay=0.1)
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    n_params = sum(p.size for p in model.parameters())
    say("train.model", layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
        mlp=cfg.intermediate_size, heads=cfg.num_attention_heads,
        head_dim=cfg.hidden_size // cfg.num_attention_heads,
        vocab=cfg.vocab_size, dtype=cfg.dtype, remat=cfg.use_recompute,
        seq=seq, params_m=round(n_params / 1e6, 1))

    compiled = ids = None
    for batch in sorted(batches, reverse=True):
        ids = _fixed_batch(cfg.vocab_size, batch, seq)
        t0 = time.perf_counter()
        try:
            compiled = step.lower(ids, ids).compile()
        except jax.errors.JaxRuntimeError as e:
            # the fit probe, not a fallback: only the compiler's own
            # out-of-memory verdict moves on to the next smaller batch
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            say("train.fit", batch=batch, fits=False,
                verdict=repr(str(e).splitlines()[0][:200]))
            compiled = None
            continue
        need = _program_bytes(compiled)
        fits = limit is None or need <= limit
        say("train.fit", batch=batch, fits=fits, program_bytes=need,
            bytes_limit=limit,
            compile_s=round(time.perf_counter() - t0, 1))
        if fits:
            break
        compiled = None
    if compiled is None:
        raise RuntimeError(f"no batch of {tuple(batches)} fits this device")

    if expect_impl == "pallas":  # the kernels are really in the program
        n_calls = compiled.as_text().count("tpu_custom_call")
        say("train.compile_check", tpu_custom_call=n_calls)
        assert n_calls > 0, "no tpu_custom_call in the compiled train step"
    del compiled

    losses, times = [], []
    for _ in range(steps + 1):
        t0 = time.perf_counter()
        loss = step(ids, ids)
        jax.block_until_ready(loss.data)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    say("train.losses", values=json.dumps([round(v, 4) for v in losses]))
    assert all(np.isfinite(losses)), losses
    assert all(b < a for a, b in zip(losses, losses[1:])), \
        f"loss not strictly decreasing on a fixed batch: {losses}"

    step_s = statistics.median(times[1:])
    tokens_per_s = batch * seq / step_s
    out = {"batch": batch, "losses": losses, "step_time_s": step_s,
           "first_call_s": times[0], "tokens_per_s": tokens_per_s}
    stats = dev.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    say("train.step", batch=batch, first_call_s=round(times[0], 2),
        step_time_s=step_s, steps_timed=steps,
        tokens_per_s=round(tokens_per_s, 1),
        peak_bytes_in_use=out["peak_bytes_in_use"], bytes_limit=limit)
    if dev.platform == "tpu":
        from paddle_tpu.cost_model.comm import link_model_for

        mfu = tokens_per_s * llama_flops_per_token(cfg, seq) / \
            link_model_for().peak_flops
        say("train.mfu_first_observation", value=round(100 * mfu, 2),
            unit="%", device=repr(dev.device_kind))
    check_kernel_table("train", ("rms_norm", "rope"), expect_impl, before)
    return out


# -- serve ---------------------------------------------------------------------

def serve_phase(cfg, *, prompt_lens, max_new: int, gen_config: dict,
                expect_impl: str) -> dict:
    """Concurrent ``submit()``s of unequal prompt length, streamed to the
    end, against two references on the same device:

    - ``model.generate`` (greedy): tokens equal up to the first position
      where the reference's own top-2 logit gap is within ``LOGIT_TOL``
      (random weights give near-ties; past a near-tie the two sequences
      legitimately differ and are not compared further);
    - the dense forward over the engine's OWN output: the logprob the
      paged path reported for every token it emitted within ``LOGIT_TOL``
      of the dense log-softmax at that position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models import GPTForCausalLM

    before = kernel_calls()
    paddle.seed(SEED)
    model = GPTForCausalLM(cfg)
    model.eval()
    say("serve.model", layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
        heads=cfg.num_attention_heads, vocab=cfg.vocab_size, dtype=cfg.dtype,
        **gen_config)
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype("int64")
               for n in prompt_lens]

    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        **gen_config))
    t0 = time.perf_counter()
    eng.warmup()
    say("serve.warmup", compile_s=round(time.perf_counter() - t0, 1))
    eng.start()
    stamps = [[] for _ in prompts]
    try:
        t_submit, futs = [], []
        for i, p in enumerate(prompts):
            t_submit.append(time.perf_counter())
            futs.append(eng.submit(
                p, max_new_tokens=max_new, return_logprobs=True,
                on_token=lambda _t, _lp, i=i: stamps[i].append(
                    time.perf_counter())))
        results = [f.result(timeout=600) for f in futs]
    finally:
        eng.close()

    ttft = [s[0] - t for s, t in zip(stamps, t_submit)]
    gaps = [b - a for s in stamps for a, b in zip(s, s[1:])]
    out = {"ttft_s": statistics.median(ttft),
           "inter_token_s": statistics.median(gaps)}
    say("serve.latency", requests=len(prompts),
        prompt_lens=json.dumps(list(prompt_lens)), max_new=max_new,
        ttft_s_median=out["ttft_s"], ttft_s_max=max(ttft),
        inter_token_s_median=out["inter_token_s"], gaps=len(gaps))

    def dense_logits(seq):
        logits = model(paddle.to_tensor(seq[None]))
        return jnp.asarray(logits.data, jnp.float32)[0]   # [T, vocab]

    compared = near_ties = 0
    for p, (full, logprobs) in zip(prompts, results):
        n = len(p)
        full = np.asarray(full)
        assert full.shape == (n + max_new,) and (full[:n] == p).all()
        # (1) the paged path's own logprobs vs the dense forward
        dense_lp = jax.nn.log_softmax(dense_logits(full), axis=-1)
        want = np.asarray(dense_lp[np.arange(n - 1, n + max_new - 1),
                                   full[n:]])
        err = float(np.max(np.abs(np.asarray(logprobs) - want)))
        assert np.isfinite(err) and err <= LOGIT_TOL, \
            f"paged logprobs off the dense forward by {err} (prompt {n})"
        # (2) tokens vs model.generate, up to the first near-tie
        ref = np.asarray(model.generate(paddle.to_tensor(p[None]),
                                        max_new_tokens=max_new,
                                        use_cache=True).numpy())[0]
        for j in range(n, n + max_new):
            if full[j] == ref[j]:
                compared += 1
                continue
            top2 = np.sort(np.asarray(dense_logits(ref)[j - 1]))[-2:]
            gap = float(top2[1] - top2[0])
            assert gap <= LOGIT_TOL, \
                (f"token {j} of prompt {n}: engine {full[j]} != generate "
                 f"{ref[j]} where the reference's top-2 gap is {gap}")
            near_ties += 1
            break
        say("serve.request", prompt_len=n, logprob_max_abs_err=f"{err:.3g}",
            tokens=json.dumps(full[n:].tolist()))
    say("serve.parity", tokens_equal=compared, near_tie_divergences=near_ties,
        tol=LOGIT_TOL)
    assert all(len(s) == max_new for s in stamps), "a stream was cut short"
    check_kernel_table("serve", ("paged_attention",), expect_impl, before)
    return out


# -- four chips ----------------------------------------------------------------

def _shard_census(tensors, devices) -> dict:
    """Bytes each device holds of ``tensors`` (from the arrays' own
    ``addressable_shards``), and how many of them are really partitioned
    (some shard smaller than the whole)."""
    per_dev = {d.id: 0 for d in devices}
    partitioned = 0
    for a in tensors:
        shards = a.addressable_shards
        if any(s.data.size < a.size for s in shards):
            partitioned += 1
        for s in shards:
            per_dev[s.device.id] += s.data.nbytes
    return {"per_device_bytes": per_dev, "partitioned": partitioned}


def sharded_phase(cfg, *, seq: int, batch: int, steps: int, meshes,
                  expect_impl: str) -> dict:
    """``ShardedTrainStep`` over every device under each mesh of ``meshes``
    vs ``jit.TrainStep`` on ONE device, same seed, same fixed batch: losses
    within ``LOSS_RTOL``; parameters laid out over all the devices (code
    that has only seen a virtual mesh may put everything on device 0); the
    expected collectives in the compiled text."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaForCausalLM

    devices = jax.devices()
    loss_fn = lambda m, x, y: m(x, labels=y)  # noqa: E731
    ids = _fixed_batch(cfg.vocab_size, batch, seq)

    def build():
        paddle.seed(SEED)
        model = LlamaForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=3e-4,
                              parameters=model.parameters(),
                              weight_decay=0.1)
        return model, optimizer

    def run(step):
        out = []
        for _ in range(steps):
            loss = step(ids, ids)
            jax.block_until_ready(loss.data)
            out.append(float(loss))
        return out

    got = {}
    for mesh in meshes:
        axes = {k: v for k, v in mesh.items() if k != "level"}
        name = ",".join(f"{k}={v}" for k, v in mesh.items())
        dist.reset_mesh()
        dist.init_mesh(**axes)
        before = kernel_calls()
        model, optimizer = build()
        if "level" in mesh:
            model, optimizer = dist.group_sharded_parallel(
                model, optimizer, level=mesh["level"])
        step = dist.ShardedTrainStep(model, loss_fn, optimizer)
        text = step.lower(ids, ids).compile().as_text()
        coll = {k: text.count(k) for k in
                ("all-reduce", "all-gather", "reduce-scatter",
                 "collective-permute", "all-to-all")}
        say("sharded.collectives", mesh=name, **coll)
        assert coll["all-reduce"] + coll["reduce-scatter"] > 0, coll
        if "level" in mesh:  # ZeRO-3: params gathered before use
            assert coll["all-gather"] > 0, coll
        if expect_impl == "pallas":  # the kernels survive partitioning
            assert "tpu_custom_call" in text, name
        losses = run(step)
        check_kernel_table(f"sharded[{name}]", ("rms_norm", "rope"),
                           expect_impl, before)
        params = [p.data for p in step.train_params]
        states = [leaf for p in step.train_params for leaf in
                  jax.tree_util.tree_leaves(optimizer._accumulators[id(p)])]
        census = _shard_census(params + states, devices)
        per_dev = census["per_device_bytes"]
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        say("sharded.layout", mesh=name, devices=len(devices),
            partitioned_arrays=census["partitioned"],
            shard_bytes_per_device=json.dumps(per_dev),
            bytes_in_use_per_device=json.dumps(in_use))
        assert all(v > 0 for v in per_dev.values()), \
            f"a device holds nothing: {per_dev}"
        assert census["partitioned"] > 0, "nothing is actually sharded"
        assert max(per_dev.values()) <= 1.25 * min(per_dev.values()), per_dev
        if all(v is not None for v in in_use):
            assert max(in_use) <= 1.5 * min(in_use), \
                f"device memory is not balanced: {in_use}"
        say("sharded.losses", mesh=name,
            values=json.dumps([round(v, 4) for v in losses]))
        got[name] = losses
        del step, model, optimizer, params, states
        gc.collect()

    dist.reset_mesh()
    model, optimizer = build()
    ref = run(jit.TrainStep(model, loss_fn, optimizer))
    say("sharded.losses", mesh="one-device", values=json.dumps(
        [round(v, 4) for v in ref]))
    assert all(np.isfinite(ref)) and ref[-1] < ref[0], ref
    for name, losses in got.items():
        np.testing.assert_allclose(losses, ref, rtol=LOSS_RTOL,
                                   err_msg=f"mesh {name} vs one device")
    return {"reference": ref, "meshes": got}


# -- the real sizes ------------------------------------------------------------

def big_llama(layers: int):
    """The 1.16B Llama-shaped model of the bring-up (PR 21)."""
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=layers, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=2048,
        dtype="bfloat16", use_recompute=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: ONLY the multi-chip phase and its one-chip "
                         "comparison (the driver never passes this)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    device = device_phase(args.chips)
    cache = CompileCacheCounter()
    say("compile_cache", dir=cache.dir, state="on")

    if args.chips == 4:
        sharded_phase(
            big_llama(layers=4), seq=2048, batch=4, steps=3,
            meshes=({"sharding": 2, "mp": 2, "level": "p_g_os"},
                    {"dp": 2, "mp": 2}), expect_impl="pallas")
    else:
        from paddle_tpu.models import GPTConfig

        kernels_phase(
            "pallas", rope_shape=(4, 2048, 16, 128), norm_shape=(8192, 2048),
            paged=dict(slots=8, heads=12, head_dim=64, page_len=16,
                       blocks=16, windows=(1, 5, 64)),
            moe=dict(batch=2, seq=2048, hidden=1536, experts=8, inter=2048,
                     top_k=2))
        train_phase(big_llama(layers=20), seq=2048, batches=(16, 8, 4),
                    steps=5, expect_impl="pallas")
        gc.collect()
        serve_phase(
            GPTConfig.gpt2_small(vocab_size=50257),
            prompt_lens=(5, 23, 48, 97), max_new=12,
            gen_config=dict(max_slots=4, max_seq_len=256, page_len=16,
                            prefill_buckets=(32, 64, 128)),
            expect_impl="pallas")
    cache.report()
    say("done", seconds=round(time.perf_counter() - t_start, 1),
        threads=threading.active_count())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
