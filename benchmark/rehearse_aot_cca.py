"""The rehearsal for the cells whose runner is ``serve_cca`` (a model whose
every layer keeps memory of TWO kinds: K/V pages and a conv tail by slot —
``cache_spec["layers"]`` ``"full+state"``; the other ``rehearse_aot*.py``
build one kind of memory a layer and are not this PR's to edit): compile the
engine's decode program, each one-row prefill program in BOTH its forms (from
zero, and from the tail the prompt's previous chunk left) — the largest
bucket's CARRYING a round of ``max_slots`` rows, as the engine builds it — and
the state install at the configuration's REAL shapes for a ``v5e:2x2`` that is
described, not attached, and print ``memory_analysis()``, each program's
Mosaic calls by name and whether it copies a whole arena of either kind.
Nothing runs, so nothing here is a measurement.

    python3 benchmark/rehearse_aot_cca.py [<cell> ...] [--slots N] [--pages N]
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

from benchmark.rehearse_aot import report, steer_to_tpu, structs  # noqa: E402
from benchmark.rehearse_aot_hybrid import _CALL, _copies  # noqa: E402


def cell(spec, one_chip, slots=None, pages=None):
    from paddle_tpu.jit import lowerable
    from paddle_tpu.kernels import grouped_matmul
    from paddle_tpu.serving import generation as gen

    from benchmark.runners.serve_recurrent import model_config

    # the grouped matmuls ask the backend themselves
    grouped_matmul._on_tpu = lambda: True
    e = spec.config["system"]["engine"]
    sm = model_config(spec.config).served_model()
    params = structs(sm.param_shapes(), one_chip)
    S, PL = slots or e["max_slots"], e["page_len"]
    P = pages or e["num_pages"]
    B = -(-e["max_seq_len"] // PL)
    L = sm.num_layers
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    page = (P, sm.num_kv_heads, PL, sm.head_dim)
    tail = (S,) + tuple(sm.state_spec["tail"][0])
    arena = [sd(page, jnp.bfloat16) for _ in range(L)]
    state = lambda rows: [  # noqa: E731
        {k: sd((rows,) + tuple(shape), dt)
         for k, (shape, dt) in sm.state_spec.items()} for _ in range(L)]
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    gb = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)) / 1e9
    print(f"aot {spec.name} weights_gb={gb(params):.3f} "
          f"kv_gb={2 * gb(arena):.3f} tails_gb={gb(state(S)):.4f} "
          f"a_slot_mb={gb(state(1)) * 1e3:.3f} page_len={PL} pages={P} "
          f"tokens={(P - 1) * PL} "
          f"bytes_a_token={2 * 2 * page[1] * page[3] * L} "
          f"layers={L} x full+state slots={S}", flush=True)
    attends = {}

    def show(tag, compile_):
        t = time.perf_counter()
        compiled = compile_()
        report(f"{spec.name} {tag} compile_s={time.perf_counter() - t:.0f}",
               compiled)
        txt = compiled.as_text()
        names = {}
        for name in _CALL.findall(txt):
            names[name] = names.get(name, 0) + 1
        print(f"aot {spec.name} {tag}: kernels={names} "
              f"whole-tail-arena copies={len(_copies(txt, 'f32', tail))} "
              f"whole-kv-arena copies={len(_copies(txt, 'bf16', page))}",
              flush=True)

    def window(tag, rows, W, prefill, carry, st):
        step = gen._build_window_step(sm, rows, B, PL, W, True,
                                      label=f"aot:{tag}", prefill=prefill,
                                      carry=carry, attends=attends)
        ops = (i32(1, rows, B), i32(rows, W), i32(rows), i32(rows))
        if carry:   # every operand a pair: the prompt's, then the round's
            ops = tuple(zip(ops, (i32(1, S, B), i32(S, 1), i32(S), i32(S))))
            st = (st, state(S))
        show(tag, lambda: lowerable(step).lower(params, arena, arena, *ops,
                                                st).compile())

    window(f"decode slots={S}", S, 1, False, 0, state(S))
    for W in e["prefill_buckets"]:
        # the largest bucket's program carries a round of every slot
        carry = S if W == e["prefill_buckets"][-1] else 0
        tag = f"prefill{W}" + (f"+round{carry}" if carry else "")
        window(tag, 1, W, True, carry, None)
        window(tag + ":resume", 1, W, True, carry, state(1))

    def install(arenas, rows, slot):
        return jax.tree_util.tree_map(
            lambda a, r: jax.lax.dynamic_update_slice(
                a, r.astype(a.dtype), (slot,) + (0,) * (a.ndim - 1)),
            arenas, rows)

    show("state_install", lambda: jax.jit(install, donate_argnums=(0,)).lower(
        state(S), state(1), i32()).compile())


def main(argv):
    from jax.experimental import topologies

    from benchmark.lib import harness

    opts = {}
    for flag in ("--slots", "--pages"):
        if flag in argv:
            i = argv.index(flag)
            opts[flag[2:]] = int(argv[i + 1])
            argv = argv[:i] + argv[i + 2:]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    steer_to_tpu()
    cells = argv or sorted(
        f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                "workloads"))
        if f.endswith(".json") and harness.Spec(f[:-5]).kind == "serve_cca")
    for name in cells:
        cell(harness.Spec(name), one_chip, **opts)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
