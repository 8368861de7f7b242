"""The two controls of the ``serve_hybrid`` cells' check (PERF.md section 6,
PR 57): the cell's own command with one side made WRONG in one way, so that
the run must come out not ``correct`` by at least one of the check's limits.
Not in ``BENCHMARK.json``: the builder of a PR that touches the check, the
scan or the state arenas runs them by hand on the chip.

    python3 benchmark/controls_hybrid.py low_precision --workload <cell> --seed <n> --seconds 30 --trace 0
    python3 benchmark/controls_hybrid.py bfloat16_state --workload <cell> --seed <n> --seconds 30 --trace 0

``low_precision``: every matmul operand of the REFERENCE rounded to 3 mantissa
bits (``lax.reduce_precision(x, 8, 3)``: what a scaled fp8 matmul keeps, the
nearest precision below the bfloat16 the configuration states): the router
agreement and the logprob limits must refuse it. ``bfloat16_state``: the
ENGINE's scan and step hand back their SSM state rounded to bfloat16 at every
write (float32 buffers still, so that the dtype check passes): what an arena
kept in bfloat16 would hold. The logprobs cannot see it; the limit on the
first Mamba-2 layer's state against one pass of the served blocks must."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    which, rest = argv[0], argv[1:]
    import jax

    from benchmark import run

    if which == "low_precision":
        from benchmark.lib import reference_nemotron_h as reference

        reference.ROUND = lambda x: jax.lax.reduce_precision(x, 8, 3)
    elif which == "bfloat16_state":
        from paddle_tpu.models import nemotron_h

        scan = nemotron_h.mamba_scan

        def rounded(*args, **kw):
            y, ssm = scan(*args, **kw)
            return y, jax.lax.reduce_precision(ssm, 8, 7)

        nemotron_h.mamba_scan = rounded
    else:
        raise SystemExit(f"unknown control {which!r}: low_precision | "
                         "bfloat16_state")
    print(f"control {which}: one side is wrong on purpose; the run must NOT "
          "be correct", flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
