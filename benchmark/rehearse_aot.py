"""The third rehearsal (on-chip-measurement guide, section 2): compile each
cell's main program at its REAL shapes for a ``v5e:2x2`` that is described,
not attached, and print ``memory_analysis()``. What the chip's compiler
would refuse (VMEM, tiling, a program over ``bytes_limit``) is refused
here, at no chip time. Nothing runs, so nothing here is a measurement.

    python3 benchmark/rehearse_aot.py [<cell> ...]      (default: every cell
                                                         with a workload file)

- serve cells: the engine's window step (``serving/generation.py:
  _build_window_step``) at W = 1 and each prefill bucket, at the
  configuration's slots x context, with the Pallas paged-attention path on;
- one-chip train cells: the whole ``jit.TrainStep`` program (forward,
  backward, AdamW) at the cell's batch x sequence, kernels on;
- mesh train cells: not compiled whole here (``ShardedTrainStep`` places its
  parameters on the devices of its mesh, and a described device holds no
  array); their per-shard kernel shapes are compiled instead.

A script run by hand, not a test: a second test file that describes the
topology can take libtpu's lock from ``tests/test_chip_compile.py`` on
another worker. This process's backend is the CPU, so the program's
``jax.default_backend()`` branches are steered to their TPU side HERE, in
the script (the program has no option for it).
"""
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

BYTES_LIMIT = 16_909_336_064  # memory_stats()["bytes_limit"] of the v5e, PR 21


def steer_to_tpu():
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import registry
    from paddle_tpu.nn.functional import attention

    registry._backend = lambda: "tpu"
    fa._interpret = lambda: False
    real = attention.attention_backend
    attention.attention_backend = \
        lambda sq, sk, hd, platform=None: real(sq, sk, hd, "tpu")


def report(tag, compiled):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes
             + ma.generated_code_size_in_bytes)
    n_kernels = compiled.as_text().count("tpu_custom_call")
    print(f"aot {tag} args_gb={ma.argument_size_in_bytes / 1e9:.2f} "
          f"temp_gb={ma.temp_size_in_bytes / 1e9:.2f} "
          f"out_gb={ma.output_size_in_bytes / 1e9:.2f} "
          f"alias_gb={ma.alias_size_in_bytes / 1e9:.2f} "
          f"program_gb={total / 1e9:.2f} "
          f"fits={total <= BYTES_LIMIT} tpu_custom_call={n_kernels}",
          flush=True)


def structs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def serve_cell(spec, one_chip):
    import paddle_tpu as paddle
    from paddle_tpu.jit import lowerable
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import generation as gen

    cfg, e = spec.config, spec.config["system"]["engine"]
    paddle.seed(0)
    mcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_hidden_layers=cfg["n_layer"], num_attention_heads=cfg["n_head"],
        intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
        max_position_embeddings=cfg["n_positions"],
        dtype=spec.config["system"]["dtype"])
    model = GPTForCausalLM(mcfg)
    params = structs(gen._extract_gpt_params(model), one_chip)
    S, PL = e["max_slots"], e["page_len"]
    B = -(-e["max_seq_len"] // PL)
    P = S * B + 2 * B + 1   # the engine's default pool
    nh, hd = mcfg.num_attention_heads, mcfg.hidden_size // \
        mcfg.num_attention_heads
    arena = [jax.ShapeDtypeStruct((P, PL, nh, hd), jnp.bfloat16,
                                  sharding=one_chip)
             for _ in range(mcfg.num_hidden_layers)]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    for W in [1] + list(e["prefill_buckets"]):
        t = time.perf_counter()
        step = gen._build_window_step(mcfg, S, B, PL, W, True,
                                      label=f"aot:window{W}", fused=True)
        compiled = lowerable(step).lower(
            params, arena, arena, i32(S, B), i32(S, W), i32(S)).compile()
        report(f"{spec.name} window W={W} slots={S} ctx={B * PL} "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)


def train_cell(spec, one_chip):
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaForCausalLM

    from benchmark.runners.train import _llama_config

    cfg, tr = spec.config, spec.workload["traffic"]
    if cfg["system"].get("mesh"):
        print(f"aot {spec.name}: a mesh cell; the whole step is not compiled "
              f"here (see the module docstring)", flush=True)
        return
    paddle.seed(0)
    model = LlamaForCausalLM(_llama_config(cfg, tr["seq"]))
    o = cfg["system"]["optimizer"]
    optimizer = opt.AdamW(learning_rate=o["learning_rate"],
                          parameters=model.parameters(),
                          weight_decay=o["weight_decay"])
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    step._ensure_built()
    for batch in (tr["batch"], tr["batch"] // 2):
        ids = jnp.asarray(np.zeros((batch, tr["seq"]), np.int32))
        args = structs(jit.step_args(step, (ids, ids), jax.random.key(0)),
                       one_chip)
        t = time.perf_counter()
        compiled = jit.lowerable(step._jitted).lower(*args).compile()
        report(f"{spec.name} train_step batch={batch} seq={tr['seq']} "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)


def main(argv):
    from jax.experimental import topologies

    from benchmark.lib import harness

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    steer_to_tpu()
    cells = argv or sorted(f[:-5] for f in os.listdir(
        os.path.join(harness.BENCH_DIR, "workloads")) if f.endswith(".json"))
    for cell in cells:
        spec = harness.Spec(cell)
        (serve_cell if spec.kind == "serve" else train_cell)(spec, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
