"""The third rehearsal for the cells whose runner is ``serve_recurrent``
(``benchmark/rehearse_aot.py`` knows the GPT-2 window step by name and is not
this PR's to edit): compile the engine's decode program, each one-row prefill
program and the state install at the configuration's REAL shapes for a
``v5e:2x2`` that is described, not attached, and print ``memory_analysis()``
plus what the compiled decode program does with the state arenas (a copy of a
whole arena is named). Nothing runs, so nothing here is a measurement.

    python3 benchmark/rehearse_aot_recurrent.py [<cell> ...] [--slots N]
"""
import os
import re
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark.rehearse_aot import report, steer_to_tpu, structs  # noqa: E402


def cell(spec, one_chip, slots=None):
    from paddle_tpu.jit import lowerable
    from paddle_tpu.serving import generation as gen

    from benchmark.runners.serve_recurrent import model_config

    e = spec.config["system"]["engine"]
    sm = model_config(spec.config).served_model()
    params = structs(sm.param_shapes(), one_chip)
    S, PL = slots or e["max_slots"], e["page_len"]
    B = -(-e["max_seq_len"] // PL)
    P = S * B + 2 * B + 1   # the engine's default pool
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    arena = [sd((P, PL, sm.num_kv_heads, sm.head_dim), jnp.bfloat16)
             for _ in range(sm.num_layers)]
    state = [{k: sd((S,) + tuple(shape), dt)
              for k, (shape, dt) in sm.state_spec.items()}
             for _ in range(sm.num_layers)]
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    gb = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)) / 1e9
    print(f"aot {spec.name} weights_gb={gb(params):.2f} "
          f"state_gb={gb(state):.2f} kv_gb={2 * gb(arena):.2f}", flush=True)
    t = time.perf_counter()
    step = gen._build_window_step(sm, S, B, PL, 1, True,
                                  label="aot:decode", fused=True)
    compiled = lowerable(step).lower(
        params, arena, arena, i32(S, B), i32(S, 1), i32(S), i32(S),
        state).compile()
    report(f"{spec.name} decode slots={S} ctx={B * PL} "
           f"compile_s={time.perf_counter() - t:.0f}", compiled)
    txt = compiled.as_text()
    shape = ",".join(str(d) for d in (S,) + tuple(sm.state_spec["ssm"][0]))
    copies = [ln.strip()[:160] for ln in txt.splitlines()
              if re.search(r"= f32\[" + shape + r"\]\S* copy\(", ln)]
    print(f"aot {spec.name} decode: mentions of pt_ssm_step="
          f"{txt.count('pt_ssm_step')} "
          f"whole-state-arena copies={len(copies)}", flush=True)
    for ln in copies[:4]:
        print("   ", ln, flush=True)
    for W in e["prefill_buckets"]:
        t = time.perf_counter()
        step = gen._build_window_step(sm, 1, B, PL, W, True,
                                      label=f"aot:prefill{W}", fused=True,
                                      prefill=True)
        compiled = lowerable(step).lower(
            params, arena, arena, i32(1, B), i32(1, W), i32(1), i32(1),
            None).compile()
        report(f"{spec.name} prefill W={W} rows=1 "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)


def main(argv):
    from jax.experimental import topologies

    from benchmark.lib import harness

    slots = None
    if "--slots" in argv:
        i = argv.index("--slots")
        slots = int(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    steer_to_tpu()
    cells = argv or sorted(
        f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                "workloads"))
        if f.endswith(".json")
        and harness.Spec(f[:-5]).kind == "serve_recurrent")
    for name in cells:
        cell(harness.Spec(name), one_chip, slots)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
