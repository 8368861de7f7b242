"""Where a program's device time goes — a served window program's or a train
step's — by the part of the model that asked for it (the twin of
``tools/warmup_spans.py``, for the device side):

    python3 tools/program_parts.py <trace dir or .xplane.pb> [--top N] [--json]

Reads a ``jax.profiler`` trace — the benchmark's own
(``.cache/bench_trace/<cell>/``, a ``--trace 1`` run), a
``capture_steps(logdir=...)`` capture, any other — through
``observability.trace.xplane.correlate().by_part`` and prints, per program
(``jit_pt_window1``, ``jit_pt_prefill2048_carry`` ...: the engine names each
window program): its calls in the trace, device ms a call, ms a call and
share by part (``observability.trace.parts``: the innermost ``pt.<part>`` of
an op's name stack; an op the compiler put in with no name goes with the
next op of its run that has one), and the widest ops of each part by short
name and shape. A train step (``jit_step``: ``models/llama.py`` under
``jit.TrainStep`` / ``ShardedTrainStep``) prints part x PHASE besides —
forward, recompute, backward (``parts.phase_of``: the ``jvp(`` /
``transpose(`` / ``rematted_computation`` of the same name stack) and
``none`` for what lies outside the gradient, the optimizer first — in ms a
call, summed over the devices, and each wide op's phase. ``--json`` prints
``by_part`` as it is. Needs no chip: a trace is a file.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def find(path: str) -> str:
    """The file itself, or the newest trace a profiler left under a log
    directory (``<dir>/plugins/profile/<time>/*.xplane.pb``)."""
    from paddle_tpu.observability.trace import xplane

    hit = path if os.path.isfile(path) else xplane.find_xplane(path)
    if hit is None:
        raise SystemExit(f"no plugins/profile/*/*.xplane.pb under {path!r}")
    return hit


def render(by_part, top: int = 5) -> str:
    from paddle_tpu.observability.trace import parts, xplane

    columns = parts.PHASES + (xplane.NO_PHASE,)
    lines = []
    total = by_part["device_us"] or 1.0
    lines.append("all programs: %.1f ms of device time; " % (total / 1e3)
                 + ", ".join(f"{p} {100 * us / total:.1f} %"
                             for p, us in by_part["parts"].items()))
    for name, row in sorted(by_part["programs"].items(),
                            key=lambda kv: -kv[1]["device_us"]):
        calls, dev = row["calls"], row["device_us"] or 1.0
        lines.append("")
        lines.append(f"{name}: {calls} calls, {dev / calls / 1e3:.3f} ms a "
                     f"call, {100 * dev / total:.1f} % of the device time")
        phases = row.get("phases")
        if phases:      # a train step: part x phase, ms a call
            table = [(part, phases[part]) for part in row["parts"]]
            table.append(("all", {ph: sum(by.get(ph, 0.0) for _p, by in table)
                                  for ph in columns}))
            lines.append("  %-12s %s %10s" % ("ms a call", " ".join(
                f"{ph:>10}" for ph in columns), "all"))
            for part, by in table:
                lines.append("  %-12s %s %10.3f" % (part, " ".join(
                    f"{by.get(ph, 0.0) / calls / 1e3:10.3f}"
                    for ph in columns), sum(by.values()) / calls / 1e3))
        for part, us in row["parts"].items():
            lines.append(f"  {part:<12} {us / calls / 1e3:8.3f} ms a call "
                         f"{100 * us / dev:5.1f} %")
            for op in row["top_ops"].get(part, [])[:top]:
                lines.append(
                    f"      {op['us'] / calls / 1e3:8.3f} ms  "
                    f"x{op['calls'] / calls:<4g} {op['op']}  {op['shape']}"
                    + (f"  [{op['phase']}]" if "phase" in op else ""))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--top", type=int, default=5,
                    help="widest ops shown a part (default 5)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from paddle_tpu.observability.trace import xplane

    path = find(args.trace)
    cor = xplane.correlate(xplane.read_xplane(path), source=path,
                           top_ops=args.top)
    if cor.by_part is None:
        raise SystemExit(f"{path}: no device ops line (a CPU trace?)")
    print(json.dumps(cor.by_part) if args.json
          else render(cor.by_part, args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
