"""The controls of the ``serve_cca`` cells' check (PERF.md section 6, PR 59):
the cell's own command with one side made WRONG in one way, so that the run
must come out not ``correct`` by at least one of the check's limits (one of
them, ``bfloat16_stream``, does not: below). Not in
``BENCHMARK.json``: the builder of a PR that touches the check, the convs or
the tail runs them by hand on the chip.

    python3 benchmark/controls_cca.py low_precision --workload <cell> --seed <n> --seconds 30 --trace 0
    python3 benchmark/controls_cca.py drop:<mechanism> --workload <cell> ...
    python3 benchmark/controls_cca.py bfloat16_tail --workload <cell> ...
    python3 benchmark/controls_cca.py bfloat16_router --workload <cell> ...
    python3 benchmark/controls_cca.py bfloat16_stream --workload <cell> ...

``low_precision``: every matmul operand of the REFERENCE rounded to 3 mantissa
bits (``lax.reduce_precision(x, 8, 3)``: what a scaled fp8 matmul keeps, the
nearest precision below the bfloat16 the configuration states).
``drop:<mechanism>``: the reference without one mechanism (``qk_mean``,
``value_shift``, ``tau``, ``eda``, ``bias``, ``scales``): what a system that
left it out would be compared against. ``bfloat16_tail``: the ENGINE's convs
hand back their tail rounded to bfloat16 at every write (float32 buffers
still, so that the dtype check passes): the limit on the first layer's tail
against one pass of the served blocks must refuse it. ``bfloat16_router`` /
``bfloat16_stream``: the reference with every operand of the router's four
matmuls, or the residual stream after every sublayer, rounded to bfloat16's
7 mantissa bits — the two precisions the configuration's ``assumed`` states
float32 for, one step down each. The first must come out not ``correct`` (by
``ROUTER_ALONE``, the router by itself on the reference's stream); the second
is the one control that comes out ``correct``: what it reads beside a run as
configured says how far the limits are from seeing a bfloat16 stream
(``serve_cca.py``, the comment above the limits)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    which, rest = argv[0], argv[1:]
    import jax

    from benchmark import run
    from benchmark.lib import reference_zaya1 as reference

    if which == "low_precision":
        reference.ROUND = lambda x: jax.lax.reduce_precision(x, 8, 3)
    elif which in ("bfloat16_router", "bfloat16_stream"):
        setattr(reference, "ROUND_" + which[9:].upper(),
                lambda x: jax.lax.reduce_precision(x, 8, 7))
    elif which.startswith("drop:"):
        reference.DROPPED = frozenset(which[5:].split(","))
    elif which == "bfloat16_tail":
        from paddle_tpu.models import zaya1

        recur = zaya1.recur

        def rounded(run_, state, step, *seqs):
            def low(*args):
                y, st = run_(*args)
                return y, {"tail": jax.lax.reduce_precision(st["tail"], 8, 7)}

            return recur(low, state, step, *seqs)

        zaya1.recur = rounded
    else:
        raise SystemExit(f"unknown control {which!r}: low_precision | "
                         "drop:<mechanism> | bfloat16_tail | "
                         "bfloat16_router | bfloat16_stream")
    print(f"control {which}: one side is wrong on purpose; the run " + (
        "is known to come out correct all the same: read its numbers"
        if which == "bfloat16_stream" else "must NOT be correct"), flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
