"""The two controls of the ``serve_latent_window`` cells' check (PERF.md
section 6, PR 50): the cell's own command with the plain reference made WRONG in one way,
so that the run must come out not ``correct`` by at least one of the check's
limits. Not in ``BENCHMARK.json``: the builder of a PR that touches the check
runs it by hand on the chip.

    python3 benchmark/controls_latent_window.py low_precision --workload <cell> --seed <n> --seconds 30 --trace 0
    python3 benchmark/controls_latent_window.py no_window --workload <cell> --seed <n> --seconds 30 --trace 0

``low_precision``: every matmul operand of the reference and the would-be
cache rows of both layer kinds (and the index keys) rounded to 3 mantissa bits
(``lax.reduce_precision(x, 8, 3)``: what a scaled fp8 matmul keeps, the
nearest precision below the bfloat16 the configuration states). ``no_window``:
the reference's window layers see EVERY earlier key — the window left out, the
way a served window layer would be wrong if its pages were never given back or
its mask were missing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    which, rest = argv[0], argv[1:]
    import jax

    from benchmark import run
    from benchmark.lib import reference_dots3_note as reference

    if which == "low_precision":
        reference.ROUND = lambda x: jax.lax.reduce_precision(x, 8, 3)
    elif which == "no_window":
        reference.NO_WINDOW = True
    else:
        raise SystemExit(
            f"unknown control {which!r}: low_precision | no_window")
    print(f"control {which}: the reference is wrong on purpose; the run must "
          "NOT be correct", flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
