"""The two controls of the ``serve_sparse`` cells' check (PERF.md section 6, PR
44): the cell's own command with the plain reference made WRONG in one way,
so that the run must come out not ``correct`` by at least one of the check's
limits. Not in ``BENCHMARK.json``: the builder of a PR that touches the check
runs it by hand on the chip.

    python3 benchmark/controls_sparse.py low_precision --workload <cell> --seed <n> --seconds 30 --trace 0
    python3 benchmark/controls_sparse.py recent --workload <cell> --seed <n> --seconds 30 --trace 0

``low_precision``: every matmul operand of the reference and both would-be
cache rows rounded to 3 mantissa bits (``lax.reduce_precision(x, 8, 3)``: what
a scaled fp8 matmul keeps, the nearest precision below the bfloat16 the
configuration states). ``recent``: the reference attends the ``index_topk``
MOST RECENT tokens instead of the set the indexer selects — a selection that is
wrong in the most plausible way."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    which, rest = argv[0], argv[1:]
    import jax

    from benchmark import run
    from benchmark.lib import reference_glm_moe_dsa as reference

    if which == "low_precision":
        reference.ROUND = lambda x: jax.lax.reduce_precision(x, 8, 3)
    elif which == "recent":
        reference.RECENT = True
    else:
        raise SystemExit(f"unknown control {which!r}: low_precision | recent")
    print(f"control {which}: the reference is wrong on purpose; the run must "
          "NOT be correct", flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
