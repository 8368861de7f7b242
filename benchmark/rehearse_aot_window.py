"""The fourth rehearsal, for the cells whose runner is ``serve_window``
(``rehearse_aot.py`` knows the GPT-2 window step by name,
``rehearse_aot_recurrent.py`` the state arenas, ``rehearse_aot_latent.py``
the latent arena; none is this PR's to edit): compile the engine's decode
program and each one-row prefill chunk at the configuration's REAL shapes for
a ``v5e:2x2`` that is described, not attached, and print the table PERF.md
section 4 quotes — the weights' and both pools' bytes, each program's
``memory_analysis()``, its Mosaic calls by name, and whether it copies a
whole arena of either kind. Nothing runs, so nothing here is a measurement.

    python3 benchmark/rehearse_aot_window.py [<cell> ...] [--slots N]
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

_CALL = re.compile(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"")


def cell(spec, one_chip, slots=None):
    from paddle_tpu.jit import lowerable
    from paddle_tpu.serving import generation as gen
    from paddle_tpu.serving.paged_kv import window_page_bound

    from benchmark.runners.serve_recurrent import model_config

    e = spec.config["system"]["engine"]
    sm = model_config(spec.config).served_model()
    params = structs(sm.param_shapes(), one_chip)
    S, PL = slots or e["max_slots"], e["page_len"]
    B = -(-e["max_seq_len"] // PL)
    pages = {"full": e["num_pages"], "window": e["window_pages"]}
    kinds = sm.cache_spec["layers"]
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    arena = [sd((pages[k], sm.num_kv_heads, PL, sm.head_dim), jnp.bfloat16)
             for k in kinds]
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    gb = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)) / 1e9
    by_kind = {k: 2 * gb([a for a, kk in zip(arena, kinds) if kk == k])
               for k in pages}
    win = sm.cache_spec["window"]
    print(f"aot {spec.name} weights_gb={gb(params):.3f} "
          f"full_cache_gb={by_kind['full']:.3f} "
          f"window_cache_gb={by_kind['window']:.3f} page_len={PL} "
          f"pages={pages} layers={','.join(kinds)} "
          f"full_tokens={(pages['full'] - 1) * PL} "
          f"window_pages_a_slot={window_page_bound(win, 1, PL)} "
          f"window_pages_a_chunk="
          f"{window_page_bound(win, e['prefill_buckets'][-1], PL)}",
          flush=True)
    programs = [(S, 1, False)] + [(1, W, True) for W in e["prefill_buckets"]]
    for rows, W, prefill in programs:
        t = time.perf_counter()
        step = gen._build_window_step(sm, rows, B, PL, W, True,
                                      label=f"aot:window:{rows}x{W}",
                                      fused=True, prefill=prefill)
        compiled = lowerable(step).lower(
            params, arena, arena, i32(2, rows, B), i32(rows, W), i32(rows),
            i32(rows), None).compile()
        report(f"{spec.name} {'prefill' if prefill else 'decode'} "
               f"rows={rows} W={W} ctx={B * PL} "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)
        txt = compiled.as_text()
        names = sorted(re.sub(r"\.\d+$", "", c) for c in _CALL.findall(txt))
        calls = {n: names.count(n) for n in dict.fromkeys(names)}
        shapes = "|".join(f"{p},{sm.num_kv_heads},{PL},{sm.head_dim}"
                          for p in sorted(set(pages.values())))
        copies = [ln.strip()[:160] for ln in txt.splitlines()
                  if re.search(rf"= bf16\[({shapes})\]\S* copy\(", ln)]
        print(f"aot {spec.name} rows={rows} W={W}: mosaic calls {calls} "
              f"whole-arena copies={len(copies)}", flush=True)
        for ln in copies[:4]:
            print("   ", ln, flush=True)


def main(argv):
    from jax.experimental import topologies

    from benchmark.lib import harness
    from paddle_tpu.kernels import grouped_matmul

    opts = {}
    if "--slots" in argv:
        i = argv.index("--slots")
        opts["slots"] = int(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    steer_to_tpu()
    grouped_matmul._on_tpu = lambda: True   # megablox, as on the chip
    cells = argv or sorted(
        f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                "workloads"))
        if f.endswith(".json")
        and harness.Spec(f[:-5]).kind == "serve_window")
    for name in cells:
        cell(harness.Spec(name), one_chip, **opts)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
