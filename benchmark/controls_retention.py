"""The two controls of the ``serve_retention`` cells' check (PERF.md section
6, PR 46): the cell's own command with one side made WRONG in one way, so that
the run must come out not ``correct`` by at least one of the check's limits.
Not in ``BENCHMARK.json``: the builder of a PR that touches the check runs it
by hand on the chip.

    python3 benchmark/controls_retention.py low_precision --workload <cell> --seed <n> --seconds 30 --trace 0
    python3 benchmark/controls_retention.py bfloat16_state --workload <cell> --seed <n> --seconds 30 --trace 0

``low_precision``: every matmul operand of the REFERENCE rounded to 3 mantissa
bits (``lax.reduce_precision(x, 8, 3)``: what a scaled fp8 matmul keeps, the
nearest precision below the bfloat16 the configuration states): the logprob
limits must refuse it. ``bfloat16_state``: the ENGINE's two retention ops hand
back their state rounded to bfloat16 at every write (float32 buffers still, so
that the dtype check passes): what an arena kept in bfloat16 would hold. The
logprobs cannot see it; the long-memory state limit must."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    which, rest = argv[0], argv[1:]
    import jax

    from benchmark import run

    if which == "low_precision":
        from benchmark.lib import reference_brumby as reference

        reference.ROUND = lambda x: jax.lax.reduce_precision(x, 8, 3)
    elif which == "bfloat16_state":
        from paddle_tpu.kernels.pallas import power_retention as pr

        def rounding(op):
            def rounded(*args, **kw):
                S, Z, y = op(*args, **kw)
                lower = lambda x: jax.lax.reduce_precision(x, 8, 7)  # noqa
                return lower(S), lower(Z), y

            return rounded

        pr.retention_step = rounding(pr.retention_step)
        pr.retention_chunk = rounding(pr.retention_chunk)
    else:
        raise SystemExit(f"unknown control {which!r}: low_precision | "
                         "bfloat16_state")
    print(f"control {which}: one side is wrong on purpose; the run must NOT "
          "be correct", flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
