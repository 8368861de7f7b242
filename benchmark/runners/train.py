"""Train cells: ``models/llama.py`` through ``jit.TrainStep`` (one chip) or
``distributed.ShardedTrainStep`` (a mesh), fed by the packed-documents
iterator while the device trains. Began as a copy of ``chip_smoke.py``'s
``train_phase`` / ``sharded_phase`` (PR 21), which ran on the chip.

Set-up: build the model from the seed, take the reference's loss of the first
batch from the untouched weights, compile and run the first steps. Window:
optimizer steps back to back for ``--seconds``, each ended by
``block_until_ready``, the next batch prepared while the device works. After
the window (outside it): a second comparison with the reference on the
weights as trained, and — in a traced run — a few more steps under the
profiler.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict

import numpy as np

from ..lib import flops, harness, peaks, reference_llama, traffic
from ..lib.harness import say, span

# The system computes in bf16 (8 mantissa bits) and the reference in float32.
# A mean over >= 16 k token losses averages the rounding out: the same step
# on one device and on a mesh, both bf16, differed by 1e-4 in 10.43 (PR 21).
# At the seed's weights every model says "uniform", so a missing layer moves
# the loss by little: the second comparison is made on the weights after the
# window, where the loss has left ln(vocab) and a skipped layer or an 8-bit
# matmul moves it by far more than this. Measured on the chip (PR 23, four
# readings): 2e-5 .. 6e-5 on the seed's weights, 6e-5 .. 1.2e-4 as trained;
# the bounds are about ten times that.
LOSS_ATOL_INIT = 1e-3
LOSS_ATOL_TRAINED = 2e-3

_LAYER_KEYS = {
    "wq": "self_attn__q_proj__weight", "wk": "self_attn__k_proj__weight",
    "wv": "self_attn__v_proj__weight", "wo": "self_attn__o_proj__weight",
    "wg": "mlp__gate_proj__weight", "wu": "mlp__up_proj__weight",
    "wd": "mlp__down_proj__weight", "norm1": "input_layernorm__weight",
    "norm2": "post_attention_layernorm__weight"}
_TOP_KEYS = {"embed": "llama.embed_tokens.weight", "head": "lm_head.weight",
             "final_norm": "llama.norm.weight"}


def _weights_getter(model):
    """The system's own weights, one at a time, on the first device."""
    import jax

    dev0 = jax.devices()[0]
    state = model.state_dict()

    def get(name: str, layer: int):
        if layer < 0:
            return jax.device_put(state[_TOP_KEYS[name]].data, dev0)
        stacked = state["llama.layers." + _LAYER_KEYS[name]].data
        return jax.device_put(stacked[layer], dev0)

    return get


def _llama_config(cfg: Dict, seq: int):
    from paddle_tpu.models import LlamaConfig

    sysc = cfg["system"]
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=max(seq, cfg["max_position_embeddings"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        use_recompute=sysc["use_recompute"], dtype=sysc["dtype"])


def run(ctx) -> Dict:
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaForCausalLM

    spec, seed, seconds = ctx["spec"], ctx["seed"], ctx["seconds"]
    cfg, wl = spec.config, spec.workload
    tr, sysc = wl["traffic"], cfg["system"]
    batch, seq = int(tr["batch"]), int(tr["seq"])
    assert tr["kind"] == "packed_documents", tr["kind"]

    mesh = sysc.get("mesh")
    if mesh:
        dist.reset_mesh()
        dist.init_mesh(**mesh, devices=jax.devices()[:spec.chips])
    paddle.seed(seed % (2 ** 31 - 1))
    lcfg = _llama_config(cfg, seq)
    t = time.perf_counter()
    model = LlamaForCausalLM(lcfg)
    o = sysc["optimizer"]
    optimizer = opt.AdamW(learning_rate=o["learning_rate"],
                          parameters=model.parameters(),
                          weight_decay=o["weight_decay"])
    loss_fn = lambda m, x, y: m(x, labels=y)  # noqa: E731
    step = (dist.ShardedTrainStep if mesh else jit.TrainStep)(
        model, loss_fn, optimizer)
    say("train.model", params_m=round(flops.decoder_param_count(cfg) / 1e6, 1),
        layers=cfg["num_hidden_layers"], batch=batch, seq=seq, mesh=mesh,
        build_s=round(time.perf_counter() - t, 2))

    batches = traffic.packed_documents(tr, cfg["vocab_size"], seed)
    rows = int(wl.get("reference_rows", 1))

    def reference(ids) -> float:
        return reference_llama.causal_lm_loss(_weights_getter(model), cfg,
                                              ids, rows=rows)

    def one_step(ids_np) -> float:
        x = paddle.to_tensor(ids_np)
        loss = step(x, x)
        jax.block_until_ready(loss.data)
        return float(loss)

    # -- set-up: reference on the seed's weights, compile, warm up ----------
    first = next(batches)
    t = time.perf_counter()
    ref_init = reference(first)
    t_ref = time.perf_counter() - t
    t = time.perf_counter()
    loss_init = one_step(first)           # compiles (or loads) the step
    t_first = time.perf_counter() - t
    for _ in range(int(wl.get("warmup_steps", 2))):
        one_step(next(batches))
    say("train.setup", reference_s=round(t_ref, 2),
        first_step_s=round(t_first, 2), loss_init=loss_init,
        ref_init=ref_init, cache_hits=ctx["compiles"].hits,
        cache_misses=ctx["compiles"].misses)
    misses_before = ctx["compiles"].misses

    # -- the window -------------------------------------------------------------
    def loop(until_s: float = 0.0, n_steps: int = 0):
        """Steps back to back until ``until_s`` has passed (or ``n_steps``
        are done); returns losses, per-step walls, seconds in next(batch)."""
        losses, walls, data_wait = [], [], 0.0
        cur = paddle.to_tensor(next(batches))
        t0 = last = time.perf_counter()
        while True:
            with span("step_call"):
                loss = step(cur, cur)
            with span("next_batch"):
                t = time.perf_counter()
                cur = paddle.to_tensor(next(batches))
                data_wait += time.perf_counter() - t
            with span("block"):
                jax.block_until_ready(loss.data)
            now = time.perf_counter()
            losses.append(float(loss))
            walls.append(now - last)
            last = now
            if (n_steps and len(losses) >= n_steps) or \
                    (not n_steps and now - t0 >= until_s):
                return losses, walls, data_wait, now - t0

    setup_s = time.time() - ctx["t_process_start"]
    losses, walls, data_wait, window_s = loop(until_s=seconds)
    compiled_in_window = ctx["compiles"].misses - misses_before
    steps = len(losses)
    tokens_per_s = steps * batch * seq / window_s

    # -- outside the window: the reference on the weights as trained -----------
    check = next(batches)
    ref_end = reference(check)
    loss_end = one_step(check)
    finite = bool(np.all(np.isfinite(losses + [loss_init, loss_end])))
    k = min(5, max(1, steps // 2))
    falling = statistics.fmean(losses[-k:]) < statistics.fmean(losses[:k])
    ok_init = abs(loss_init - ref_init) <= LOSS_ATOL_INIT
    ok_end = abs(loss_end - ref_end) <= LOSS_ATOL_TRAINED
    say("train.correct", finite=finite, falling=falling,
        loss_init=loss_init, ref_init=ref_init, atol_init=LOSS_ATOL_INIT,
        loss_end=loss_end, ref_end=ref_end, atol_end=LOSS_ATOL_TRAINED,
        first_losses=[round(v, 4) for v in losses[:k]],
        last_losses=[round(v, 4) for v in losses[-k:]],
        compiled_in_window=compiled_in_window)
    say("train.window", steps=steps, window_s=window_s,
        tokens_per_s=tokens_per_s,
        step_s_median=statistics.median(walls),
        step_s_max=max(walls), data_wait_s=data_wait, setup_s=setup_s)

    # -- a traced run: a few more steps under the profiler ---------------------
    tracer = harness.Tracer(spec.name, ctx["trace"])
    if ctx["trace"]:
        tracer.start()
        loop(n_steps=int(wl.get("trace_steps", 3)))
        tracer.stop()
        if ctx.get("dump_trace"):
            from ..lib import trace_dump

            trace_dump.dump(tracer, ctx["dump_trace"])

    fpt = flops.decoder_train_flops_per_token(cfg, seq)
    shapes = {"flops_per_token": fpt, "chips": spec.chips, "batch": batch,
              "seq": seq, "kind": "train"}
    if ctx["device"]["platform"] == "tpu":
        shapes["peak_flops_per_s"] = peaks.peaks_for(
            ctx["device"]["kind"])["bf16_flops_per_s"]
    return {
        "correct": finite and falling and ok_init and ok_end
        and compiled_in_window == 0,
        "attempted": steps, "failed": 0 if finite else
        int(np.sum(~np.isfinite(losses))),
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "units": {"train_tokens_per_s": "tokens/s", "setup_s": "s"},
        "counters": {"steps": steps, "tokens": steps * batch * seq,
                     "window_s": window_s, "data_wait_s": data_wait,
                     "tokens_per_s": tokens_per_s},
        "spans": {"step_wall_s": walls},
        "shapes": shapes,
        "trace": tracer.summary,
        "notes": {"steps": steps, "step_s_median": statistics.median(walls),
                  "loss_init_minus_ref": loss_init - ref_init,
                  "loss_end_minus_ref": loss_end - ref_end,
                  "loss_first": losses[0], "loss_last": losses[-1],
                  "cache_misses": ctx["compiles"].misses,
                  "outermost_ops": (tracer.summary or {}).get(
                      "outermost_ops")},
    }


def sweep(ctx, rates) -> None:
    raise SystemExit("--sweep is for serve cells: a train cell offers no rate")
