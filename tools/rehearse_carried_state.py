"""The rehearsal of the CARRIED STEP of a model that keeps recurrent state
(``ServedModel.carries_rounds`` with a ``state_spec``: Nemotron-H):
compile the largest bucket's prefill program in BOTH its forms — from zero,
and from the state the prompt's previous chunk left — each carrying the
``max_slots`` rows of a decode round and taking the (row, arenas) state pair,
at the configuration's REAL shapes for a ``v5e:2x2`` that is described, not
attached. Prints ``memory_analysis()``, the program's Mosaic calls by name,
whether it copies a whole arena of either kind, and whether a stack of expert
matrices is laid out anew in front of a grouped matmul. Nothing runs, so
nothing here is a measurement. ``benchmark/rehearse_aot_hybrid.py`` (a
``benchmark`` PR's to edit) compiles the same cell's programs WITHOUT a carry:
the decode round and the smaller buckets, which this leaves as they were.

    python3 tools/rehearse_carried_state.py [<cell> ...]
"""
import math
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
from benchmark.rehearse_aot_hybrid import _CALL, _copies  # noqa: E402


def cell(spec, one_chip):
    from paddle_tpu.jit import lowerable
    from paddle_tpu.kernels import grouped_matmul
    from paddle_tpu.serving import generation as gen

    from benchmark.runners.serve_recurrent import model_config

    grouped_matmul._on_tpu = lambda: True
    e = spec.config["system"]["engine"]
    sm = model_config(spec.config).served_model()
    if not (sm.carries_rounds and sm.state_spec):
        print(f"aot {spec.name}: carries no round over a state", flush=True)
        return
    params = structs(sm.param_shapes(), one_chip)
    S, PL, P = e["max_slots"], e["page_len"], e["num_pages"]
    B, W = -(-e["max_seq_len"] // PL), e["prefill_buckets"][-1]
    kinds = sm.cache_spec["layers"]
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    page = (P, sm.num_kv_heads, PL, sm.head_dim)
    arena = [sd(page, jnp.bfloat16) for k in kinds if k == "full"]
    state = lambda rows: [  # noqa: E731
        {k: sd((rows,) + tuple(shape), dt)
         for k, (shape, dt) in sm.state_spec.items()}
        for k in kinds if k == "state"]
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    pair = lambda f: (f(1), f(S))  # noqa: E731
    big = (S,) + tuple(sm.state_spec["ssm"][0])
    # a stack of expert matrices [experts, in, out]: a ``copy`` of one is the
    # re-layout PR 57 found at 1856 columns (1.28 GB a layer a call)
    stacks = {tuple(a.shape) for layer in params["layers"]
              for a in layer.values() if len(a.shape) == 3}
    step = gen._build_window_step(sm, 1, B, PL, W, True,
                                  label=f"aot:prefill{W}", prefill=True,
                                  carry=S)
    for tag, row in (("from zero", None), ("resumed", state(1))):
        t = time.perf_counter()
        compiled = lowerable(step).lower(
            params, arena, arena, pair(lambda r: i32(1, r, B)),
            (i32(1, W), i32(S, 1)), pair(i32), pair(i32),
            (row, state(S))).compile()
        report(f"{spec.name} prefill{W}+{S} {tag} "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)
        txt = compiled.as_text()
        names = {}
        for name in _CALL.findall(txt):
            names[name] = names.get(name, 0) + 1
        relaid = [ln.strip()[:160] for shape in stacks
                  for ln in _copies(txt, "bf16", shape)]
        print(f"aot {spec.name} prefill{W}+{S} {tag}: kernels={names} "
              f"whole-state-arena copies={len(_copies(txt, 'f32', big))} "
              f"whole-kv-arena copies={len(_copies(txt, 'bf16', page))} "
              f"expert-stack copies={len(relaid)}", flush=True)
        for ln in relaid:
            print("   ", ln, flush=True)
        # an array the chip holds with another axis last (PR 57: ``up`` at
        # 1856 columns was ``{1,2,0}``): named where it is 64 MB or more
        turned = {}
        for dt, dims in re.findall(r"(bf16|f32)\[([\d,]+)\]\{1,2,0", txt):
            n = (2 if dt == "bf16" else 4) * math.prod(
                map(int, dims.split(",")))
            if n >= 64e6:
                turned[f"{dt}[{dims}]"] = round(n / 1e6)
        print(f"aot {spec.name} prefill{W}+{S} {tag}: arrays of 64 MB or "
              f"more laid out {{1,2,0}}: {turned or 'none'}", flush=True)


def main(argv):
    from jax.experimental import topologies

    from benchmark.lib import harness

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    steer_to_tpu()
    cells = argv or sorted(
        f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                "workloads"))
        if f.endswith(".json")
        and harness.Spec(f[:-5]).kind == "serve_hybrid")
    for name in cells:
        cell(harness.Spec(name), one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
