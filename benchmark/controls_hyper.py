"""The three controls of the ``serve_hyper`` cells' check (PERF.md section 6,
PR 53): the cell's own command with the plain reference made WRONG in one way,
so that the run must come out not ``correct`` — each by at least twice a
limit of the check. Not in ``BENCHMARK.json``: the builder of a PR that
touches the check, the residual path or the draw of its parameters runs them
by hand on the chip.

    python3 benchmark/controls_hyper.py low_precision --workload <cell> --seed <n> --seconds 30 --trace 0
    python3 benchmark/controls_hyper.py one_iteration --workload <cell> ...
    python3 benchmark/controls_hyper.py no_mixing --workload <cell> ...

``low_precision`` (i): every matmul operand of the reference's sublayers and
the would-be cache rows rounded to 3 mantissa bits (``lax.reduce_precision(x,
8, 3)``: what a scaled fp8 matmul keeps, the nearest precision below the
bfloat16 the configuration states). ``one_iteration`` (ii): the reference's
``H_res`` from ONE Sinkhorn iteration where the configuration says 20 — the
way a served path would be wrong if its loop ran short. ``no_mixing`` (iii):
the reference's ``H_res = I`` — the streams never mixed, the way a plain
residual path would be wrong for this model."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    which, rest = argv[0], argv[1:]
    import jax

    from benchmark import run
    from benchmark.lib import reference_xing4 as reference

    if which == "low_precision":
        reference.ROUND = lambda x: jax.lax.reduce_precision(x, 8, 3)
    elif which == "one_iteration":
        reference.SINKHORN_ITERS = 1
    elif which == "no_mixing":
        reference.H_RES_IDENTITY = True
    else:
        raise SystemExit(f"unknown control {which!r}: low_precision | "
                         "one_iteration | no_mixing")
    print(f"control {which}: the reference is wrong on purpose; the run must "
          "NOT be correct", flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
