"""The rehearsal for the cells whose runner is ``serve_hyper`` (a model whose
residual path is several streams, every expert and the whole vocabulary on
the chip): compile every window program of the engine — the decode round,
each one-row prefill chunk and the carried step of the largest bucket — at
the configuration's REAL shapes for a ``v5e:2x2`` that is described, not
attached, with the page pool the configuration names, and print
``memory_analysis()`` (the peak must stay under the chip's 15.75 GB with room:
15.2), the Mosaic calls by name (``pt_mhc_pre`` / ``pt_mhc_post`` ten times a
program) and whether a compiled program copies a whole latent arena. Nothing
runs, so nothing here is a measurement.

    python3 benchmark/rehearse_aot_hyper.py [<cell> ...] [--slots N] [--pages N]
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


def cell(spec, one_chip, slots=None, pages=None):
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
    width = latent_width(sm.cache_spec["dim"])
    arena = [sd((P, PL, width), jnp.bfloat16) for _ in range(sm.num_layers)]
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    gb = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)) / 1e9
    print(f"aot {spec.name} weights_gb={gb(params):.2f} "
          f"latent_cache_gb={gb(arena):.2f} page_len={PL} pages={P} "
          f"tokens={(P - 1) * PL} bytes_a_token={2 * width * sm.num_layers}",
          flush=True)
    buckets = e["prefill_buckets"]
    programs = [(S, 1, False, 0)] + \
        [(1, W, True, S if W == buckets[-1] else 0) for W in buckets]
    attends = {}
    for rows, W, prefill, carry in programs:
        t = time.perf_counter()
        step = gen._build_window_step(
            sm, rows, B, PL, W, True, label=f"aot:hyper:{rows}x{W}",
            fused=True, prefill=prefill, carry=carry, attends=attends)
        ops = (i32(rows, B), i32(rows, W), i32(rows), i32(rows))
        if carry:   # every operand a pair: the prompt's, then the round's
            ops = tuple(zip(ops, (i32(S, B), i32(S, 1), i32(S), i32(S))))
        compiled = lowerable(step).lower(params, arena, [], *ops,
                                         None).compile()
        report(f"{spec.name} {'prefill' if prefill else 'decode'} "
               f"rows={rows} W={W} carries={carry} ctx={B * PL} "
               f"compile_s={time.perf_counter() - t:.0f}", compiled)
        txt = compiled.as_text()
        names = sorted(re.sub(r"\.\d+$", "", c) for c in _CALL.findall(txt))
        calls = {n: names.count(n) for n in dict.fromkeys(names)}
        copies = [ln.strip()[:160] for ln in txt.splitlines()
                  if re.search(rf"= bf16\[{P},{PL},{width}\]\S* copy\(", ln)]
        stream = [ln.strip()[:160] for ln in txt.splitlines()
                  if re.search(rf"= f32\[\d+,{sm.cfg.stream_dim}\]\S* copy\(",
                               ln)]
        print(f"aot {spec.name} rows={rows} W={W} carries={carry}: mosaic "
              f"calls {calls} whole-arena copies={len(copies)} "
              f"stream copies={len(stream)}", flush=True)
        for ln in (copies + stream)[:6]:
            print("   ", ln, flush=True)


def main(argv):
    from jax.experimental import topologies

    from benchmark.lib import harness
    from paddle_tpu.kernels import grouped_matmul

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
    grouped_matmul._on_tpu = lambda: True   # megablox, as on the chip
    cells = argv or sorted(
        f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                "workloads"))
        if f.endswith(".json")
        and harness.Spec(f[:-5]).kind == "serve_hyper")
    for name in cells:
        cell(harness.Spec(name), one_chip, **opts)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
