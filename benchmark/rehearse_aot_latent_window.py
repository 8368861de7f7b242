"""The rehearsal for the cells whose runner is ``serve_latent_window`` (a
latent cache of two layer kinds: dots3-note's sparse-selected full layers and
window layers with wider rows), as ``rehearse_aot_sparse.py`` is for
``serve_sparse``: compile the engine's decode program, each tail-bucket
prefill and the program of the largest bucket that CARRIES a decode round (and
the check's program that names its selection), at the configuration's REAL
shapes for a ``v5e:2x2`` that is described, not attached, and print
``memory_analysis()``, the Mosaic calls by name and whether a compiled program
copies a whole arena of any of the three shapes (there must be none). Nothing
runs, so nothing here is a measurement.

    python3 benchmark/rehearse_aot_latent_window.py [<cell> ...] [--slots N] [--pages N] [--window-pages N]
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


def cell(spec, one_chip, slots=None, pages=None, window_pages=None):
    from paddle_tpu.jit import lowerable
    from paddle_tpu.serving import generation as gen
    from paddle_tpu.serving.paged_kv import latent_width

    from benchmark.runners.serve_recurrent import model_config

    e = spec.config["system"]["engine"]
    sm = model_config(spec.config).served_model()
    params = structs(sm.param_shapes(), one_chip)
    S, PL = slots or e["max_slots"], e["page_len"]
    B = -(-e["max_seq_len"] // PL)
    P = pages or e.get("num_pages") or S * B + 2 * B + 1
    sd = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    WP = window_pages or e["window_pages"]
    spec_, index = sm.cache_spec, sm.cache_spec["index"]
    width, iw = latent_width(spec_["dim"]), int(index["dim"])
    wide = latent_width(spec_["window_row"]["dim"])
    arena = [sd((WP, PL, wide) if kind == "window" else (P, PL, width),
                jnp.bfloat16) for kind in spec_["layers"]]
    keys = [sd((P, PL, iw), jnp.bfloat16)
            for kind in index["layers"] if kind == "full"]
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    gb = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)) / 1e9
    full = [a for a in arena if a.shape[-1] == width]
    print(f"aot {spec.name} weights_gb={gb(params):.2f} "
          f"latent_full_gb={gb(full):.2f} index_gb={gb(keys):.2f} "
          f"latent_window_gb={gb(arena) - gb(full):.2f} "
          f"page_len={PL} pages={P} window_pages={WP} "
          f"tokens={(P - 1) * PL} "
          f"bytes_a_token_full={2 * (width * len(full) + iw * len(keys))} "
          f"bytes_a_token_window={2 * wide * (len(arena) - len(full))}",
          flush=True)
    buckets = e["prefill_buckets"]
    # the last: the check's program that names what it selected
    # (``GenerationEngine.selected_keys``), run beside the arenas too
    programs = [(S, 1, False, 0, False)] + \
        [(1, W, True, S if W == buckets[-1] else 0, False) for W in buckets] \
        + [(1, buckets[-1], True, 0, True)]
    attends = {}
    for rows, W, prefill, carry, selection in programs:
        t = time.perf_counter()
        step = gen._build_window_step(
            sm, rows, B, PL, W, True, label=f"aot:latent_window:{rows}x{W}",
            fused=True, prefill=prefill, carry=carry, attends=attends,
            selection=selection)
        # the tables: the full layers', then the window layers'
        ops = (i32(2, rows, B), i32(rows, W), i32(rows), i32(rows))
        if carry:   # every operand a pair: the prompt's, then the round's
            ops = tuple(zip(ops, (i32(2, S, B), i32(S, 1), i32(S), i32(S))))
        compiled = lowerable(step).lower(params, arena, keys, *ops,
                                         None).compile()
        report(f"{spec.name} {'prefill' if prefill else 'decode'} "
               f"rows={rows} W={W} carries={carry} "
               f"{'selection ' * selection}ctx={B * PL} "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)
        txt = compiled.as_text()
        names = sorted(re.sub(r"\.\d+$", "", c) for c in _CALL.findall(txt))
        calls = {n: names.count(n) for n in dict.fromkeys(names)}
        copies = [ln.strip()[:160] for ln in txt.splitlines()
                  if re.search(rf"= bf16\[({P},{PL},({width}|{iw})|"
                               rf"{WP},{PL},{wide})\]\S* copy\(", ln)]
        print(f"aot {spec.name} rows={rows} W={W} carries={carry}: mosaic "
              f"calls {calls} whole-arena copies={len(copies)}", flush=True)
        for ln in copies[:4]:
            print("   ", ln, flush=True)


def main(argv):
    from jax.experimental import topologies

    from benchmark.lib import harness
    from paddle_tpu.kernels import grouped_matmul

    opts = {}
    for flag in ("--slots", "--pages", "--window-pages"):
        if flag in argv:
            i = argv.index(flag)
            opts[flag[2:].replace("-", "_")] = int(argv[i + 1])
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
        and harness.Spec(f[:-5]).kind == "serve_latent_window")
    for name in cells:
        cell(harness.Spec(name), one_chip, **opts)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
