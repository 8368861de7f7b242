"""The rehearsal for the cells whose runner is ``serve_retention`` (a model
with NOTHING paged; ``benchmark/rehearse_aot_recurrent.py`` builds K/V arenas
and page tables and names Falcon-H1's state, and is not this PR's to edit):
compile the engine's decode program, each one-row prefill program in BOTH its
forms (from zero, and from the state the prompt's previous chunk left) and the
state install at the configuration's REAL shapes for a ``v5e:2x2`` that is
described, not attached, and print ``memory_analysis()`` plus what each
compiled program does with the state arenas: a copy of a whole arena, or of a
row's whole state, is named (5.8 GB of arenas have no room for one). Nothing
runs, so nothing here is a measurement.

    python3 benchmark/rehearse_aot_retention.py [<cell> ...] [--slots N]
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


def _state_copies(txt: str, shape) -> list:
    dims = ",".join(str(d) for d in shape)
    return [ln.strip()[:160] for ln in txt.splitlines()
            if re.search(r"= f32\[" + dims + r"\]\S* copy\(", ln)]


def cell(spec, one_chip, slots=None):
    from paddle_tpu.jit import lowerable
    from paddle_tpu.serving import generation as gen

    from benchmark.runners.serve_recurrent import model_config

    e = spec.config["system"]["engine"]
    sm = model_config(spec.config).served_model()
    params = structs(sm.param_shapes(), one_chip)
    S = slots or e["max_slots"]
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    state = lambda rows: [  # noqa: E731
        {k: sd((rows,) + tuple(shape), dt)
         for k, (shape, dt) in sm.state_spec.items()}
        for _ in range(sm.num_layers)]
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    gb = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)) / 1e9
    print(f"aot {spec.name} weights_gb={gb(params):.2f} "
          f"state_gb={gb(state(S)):.2f} a_slot_gb={gb(state(1)):.3f}",
          flush=True)
    big = tuple(sm.state_spec["S"][0])

    def build(tag, rows, W, prefill, st):
        t = time.perf_counter()
        step = gen._build_window_step(sm, rows, 0, e["page_len"], W, True,
                                      label=f"aot:{tag}", prefill=prefill)
        compiled = lowerable(step).lower(
            params, [], [], None, i32(rows, W), i32(rows), i32(rows),
            st).compile()
        report(f"{spec.name} {tag} rows={rows} "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)
        txt = compiled.as_text()
        arena = _state_copies(txt, (S,) + big)
        row = _state_copies(txt, (1,) + big) if rows == 1 else []
        print(f"aot {spec.name} {tag}: pt_retention_step="
              f"{txt.count('pt_retention_step')} pt_retention_chunk="
              f"{txt.count('pt_retention_chunk')} whole-arena copies="
              f"{len(arena)} whole-row copies={len(row)}", flush=True)
        for ln in (arena + row)[:4]:
            print("   ", ln, flush=True)

    build(f"decode slots={S}", S, 1, False, state(S))
    for W in e["prefill_buckets"]:
        build(f"prefill{W}", 1, W, True, None)
        build(f"prefill{W}:resume", 1, W, True, state(1))

    def install(arenas, rows, slot):
        return jax.tree_util.tree_map(
            lambda a, r: jax.lax.dynamic_update_slice(
                a, r.astype(a.dtype), (slot,) + (0,) * (a.ndim - 1)),
            arenas, rows)

    compiled = jax.jit(install, donate_argnums=(0,)).lower(
        state(S), state(1), i32()).compile()
    report(f"{spec.name} state_install", compiled)
    print(f"aot {spec.name} state_install: whole-arena copies="
          f"{len(_state_copies(compiled.as_text(), (S,) + big))}", flush=True)


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
        and harness.Spec(f[:-5]).kind == "serve_retention")
    for name in cells:
        cell(harness.Spec(name), one_chip, slots)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
