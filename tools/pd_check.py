#!/usr/bin/env python
"""pd_check — run the paddle_tpu.analysis static passes from the shell.

No TPU required (set JAX_PLATFORMS=cpu); nothing is executed on device
except the tiny retrace demo loop. Examples:

    JAX_PLATFORMS=cpu python tools/pd_check.py            # all passes
    JAX_PLATFORMS=cpu python tools/pd_check.py --self     # repo self-lint
    JAX_PLATFORMS=cpu python tools/pd_check.py --concurrency  # CC lint
    JAX_PLATFORMS=cpu python tools/pd_check.py --json --models llama
    JAX_PLATFORMS=cpu python tools/pd_check.py --passes memory,spmd

Exit code 1 when any ERROR-severity diagnostic is produced (CI gate),
else 0. --strict also fails on warnings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _bootstrap():
    # an 8-device host mesh lets the SPMD pass walk real shard_map programs;
    # must be set before jax initializes its backends
    if "--self" not in sys.argv and "--concurrency" not in sys.argv:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                flags + " --xla_force_host_platform_device_count=8"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _check_llama(A, cfg_kwargs):
    """Whole-train-step capture of the examples/train_llama_tpu.py recipe
    (tiny shape): program summary + memory + spmd over fwd+bwd+update."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [2, 32])
    prog = A.capture(step, ids, ids, label="llama.TrainStep")
    diags = A.run_passes(prog, **cfg_kwargs)
    return prog, diags


def _check_bert(A, cfg_kwargs):
    """Forward capture of the examples/finetune_bert.py model (tiny)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import BertConfig, BertForSequenceClassification

    paddle.seed(0)
    cfg = BertConfig.tiny()
    model = BertForSequenceClassification(cfg, num_classes=2)
    model.eval()
    ids = paddle.randint(0, cfg.vocab_size, [2, 16])
    prog = A.capture(lambda x: model(x), ids, label="bert.forward")
    diags = A.run_passes(prog, **cfg_kwargs)
    return prog, diags


def _check_gpt(A, cfg_kwargs):
    """to_static capture of the examples/generate_gpt.py model (tiny)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny())
    model.eval()
    ids = paddle.randint(0, 256, [1, 16])
    prog = A.capture(lambda x: model(x), ids, label="gpt.forward")
    diags = A.run_passes(prog, **cfg_kwargs)
    return prog, diags


def _check_pipeline(A, cfg_kwargs):
    """ppermute-pipeline program over a pp=2 host mesh (the
    examples/distributed_data_parallel.py-family program shape): the spmd
    pass walks the real stage-handoff collectives."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.meta_parallel.pipeline import (
        ppermute_pipeline)
    from jax.sharding import PartitionSpec as P

    dist.reset_mesh()
    import jax as _jax

    env = dist.init_mesh(pp=2, dp=len(_jax.devices()) // 2)

    def stage(h):
        return jnp.tanh(h) * 1.1

    def piped(x_mb):
        def local(x_local):
            return ppermute_pipeline(stage, x_local, 2, remat=False)

        return jax.shard_map(local, mesh=env.mesh, in_specs=P(),
                             out_specs=P(), axis_names={"pp"},
                             check_vma=False)(x_mb)

    x = jnp.ones((4, 2, 8), jnp.float32)  # [M, mb, d]
    prog = A.capture(piped, x, label="pipeline.ppermute")
    diags = A.run_passes(prog, **cfg_kwargs)
    dist.reset_mesh()
    return prog, diags


def _retrace_demo(A):
    """Enable the auditor, run a toy loop with an induced dtype drift, and
    report the attributed recompiles — the end-to-end retrace pass."""
    import paddle_tpu as paddle

    A.retrace.reset()
    A.retrace.enable()
    try:
        a = paddle.ones([4, 4])
        _ = (a + a) * 2.0                     # baseline compiles
        b = paddle.ones([4, 4], dtype="int32")
        _ = (b + b) * 2                       # induced dtype drift
        c = paddle.ones([8, 4])
        _ = (c + c) * 2.0                     # induced shape drift
    finally:
        A.retrace.disable()
    return A.retrace.report()


MODEL_CHECKS = {
    "llama": _check_llama,
    "bert": _check_bert,
    "gpt": _check_gpt,
    "pipeline": _check_pipeline,
}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="pd_check", description=__doc__)
    ap.add_argument("--self", action="store_true", dest="self_lint",
                    help="run the repo self-lint (AST footgun pass) only")
    ap.add_argument("--concurrency", action="store_true",
                    dest="concurrency_lint",
                    help="run the repo concurrency lint (CC codes: "
                         "blocking-under-lock, signal-handler locks, "
                         "thread/daemon audit, lock-order conflicts) only")
    ap.add_argument("--root", default=None,
                    help="lint root (default: the paddle_tpu package)")
    ap.add_argument("--json", action="store_true", help="JSON output")
    ap.add_argument("--models", default="llama,bert,gpt,pipeline",
                    help=f"comma list from {sorted(MODEL_CHECKS)}")
    ap.add_argument("--passes", default=None,
                    help="comma list of jaxpr passes (default: all)")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="the TARGET chip's HBM for the memory/spmd passes "
                         "(default: a v5e's published 16 GB; a live chip "
                         "reports its own as memory_stats()['bytes_limit'])")
    ap.add_argument("--frac", type=float, default=0.5,
                    help="fat-intermediate threshold as a fraction of HBM")
    ap.add_argument("--no-retrace-demo", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on warnings too")
    args = ap.parse_args(argv)

    import paddle_tpu.analysis as A

    all_diags = []
    blocks = []

    if args.self_lint or args.concurrency_lint:
        if args.self_lint:
            diags = A.selfcheck.run_selfcheck(args.root)
            all_diags += diags
            blocks.append(("selfcheck", None, diags))
        if args.concurrency_lint:
            diags = A.concurrency.run_concurrency(args.root)
            all_diags += diags
            blocks.append(("concurrency", None, diags))
    else:
        cfg = {"hbm_bytes": int(args.hbm_gb * 1e9), "hbm_frac": args.frac}
        if args.passes:
            cfg["passes"] = [p.strip() for p in args.passes.split(",")]
        for name in [m.strip() for m in args.models.split(",") if m.strip()]:
            if name not in MODEL_CHECKS:
                ap.error(f"unknown model {name!r}; "
                         f"choose from {sorted(MODEL_CHECKS)}")
            try:
                prog, diags = MODEL_CHECKS[name](A, cfg)
                blocks.append((name, prog.summary(), diags))
                all_diags += diags
            except NotImplementedError as e:  # old-jax shard_map gaps
                blocks.append((name, {"skipped": str(e)[:160]}, []))
        if not args.no_retrace_demo:
            # the demo INDUCES drift to prove the auditor works — its
            # warnings are expected output, not repo findings, so they are
            # shown but excluded from the exit-code gate
            blocks.append(("retrace-demo", None, _retrace_demo(A)))
        diags = A.selfcheck.run_selfcheck(args.root)
        blocks.append(("selfcheck", None, diags))
        all_diags += diags
        diags = A.concurrency.run_concurrency(args.root)
        blocks.append(("concurrency", None, diags))
        all_diags += diags

    if args.json:
        print(json.dumps({
            "blocks": [{"name": n, "summary": s,
                        "diagnostics": [d.to_dict() for d in ds]}
                       for n, s, ds in blocks],
            "max_severity": A.max_severity(all_diags),
        }, default=str))
    else:
        for name, summary, diags in blocks:
            header = f"== {name} =="
            if summary:
                header += f"  {json.dumps(summary, default=str)[:200]}"
            print(A.render(diags, header=header))
            print()
        worst = A.max_severity(all_diags)
        print(f"pd_check: {len(all_diags)} finding(s), "
              f"max severity: {worst or 'none'}")

    failing = ("error", "warning") if args.strict else ("error",)
    return 1 if any(d.severity in failing for d in all_diags) else 0


if __name__ == "__main__":
    _bootstrap()
    sys.exit(main())
