"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` names ``benchmark/workloads/<cell>.json``, which names its
configuration (``benchmark/configs/<config>.json``), its kind (``train`` or
``serve`` -> ``benchmark/runners/<kind>.py``), its chips and its traffic.
The last line of stdout is the result; nothing is printed when there is no
TPU. ``--sweep`` (serve cells; not in BENCHMARK.json) prints the table of
offered rate against what the server sustains. ``--rehearsal`` runs the same
files and code at the tiny sizes their ``rehearsal`` groups give, on the
CPU, and marks its line so.

This process stays off JAX until here, and is the one process that holds
the chips.
"""
import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="serve cells: comma-separated rates (requests/s), "
                         "--seconds each; prints the knee table")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--dump-trace", default=None,
                    help="directory to copy the raw trace outline into")
    args = ap.parse_args(argv)

    from benchmark.lib import harness

    spec = harness.Spec(args.workload, rehearsal=args.rehearsal)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={spec.chips}")
    device = harness.require_devices(spec.chips, args.rehearsal)
    cache_dir = harness.enable_compile_cache()
    harness.say("bench.start", workload=spec.name, kind=spec.kind,
                seed=args.seed, seconds=args.seconds, trace=args.trace,
                device=repr(device["kind"]), count=device["count"],
                compile_cache=cache_dir)
    runner = importlib.import_module(f"benchmark.runners.{spec.kind}")
    ctx = {"spec": spec, "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "t_process_start": T_PROCESS_START,
           "device": device, "dump_trace": args.dump_trace,
           "compiles": harness.CompileCounter()}
    if args.sweep:
        runner.sweep(ctx, [float(r) for r in args.sweep.split(",")])
        return 0
    out = runner.run(ctx)

    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"]}
    if args.trace:
        line["metrics"] = harness.layer_metrics(spec, out)
        tr = out.get("trace")
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
    else:
        line["metrics"] = harness.end_to_end_metrics(spec, out)
    line["device"] = device
    if args.rehearsal:
        line["rehearsal"] = True  # CPU, tiny sizes: no device number here
    line["notes"] = out.get("notes", {})
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
