"""What one ``pt_ssm_step`` call (``paddle_tpu/kernels/pallas/ssm_step.py``)
has to move and compute, from its shapes alone — the benchmark's own
arithmetic, kept apart from the program's.

One call advances ``rows`` slots x ``heads`` heads one step of the Mamba-2
recurrence: for each (row, head) it reads the float32 state ``S`` [d_head,
d_state], forms ``exp(dt A) S + dt x (outer) B``, writes it back in place and
emits ``S C + D x``. Every row of the arena the call covers is read and
written, whether its slot holds a sequence or not.

- bytes: 2 x rows x heads x d_head x d_state x 4 (the state, in and out) +
  the small operands in float32: ``x`` in and ``y`` out (rows x heads x
  d_head each), ``B`` and ``C`` (rows x groups x d_state each), ``dt`` and
  ``exp(dt A)`` (rows x heads each);
- operations: per state element a multiply and a multiply-add for the update
  and a multiply-add for the contraction: 5 a state element.

The call is bound by its bytes: at 64 x 32 x 128 x 256 the state alone is
2 x 268 MB = 0.66 ms at 819 GB/s against 1.34 GFLOP = 0.007 ms of the chip's
197 TFLOP/s (and the VPU, not the MXU, does them).
"""
from typing import Dict


def ssm_step_bytes(rows: int, heads: int, d_head: int, d_state: int,
                   groups: int) -> int:
    state = rows * heads * d_head * d_state * 4
    small = 4 * (2 * rows * heads * d_head + 2 * rows * groups * d_state
                 + 2 * rows * heads)
    return 2 * state + small


def ssm_step_flops(rows: int, heads: int, d_head: int, d_state: int,
                   groups: int) -> int:
    return 5 * rows * heads * d_head * d_state


def floor_seconds(shape: Dict, peaks: Dict) -> Dict:
    """The least time one call can take on a chip of ``peaks`` and which of
    the two bounds it."""
    args = (shape["rows"], shape["heads"], shape["d_head"], shape["d_state"],
            shape["groups"])
    t_bytes = ssm_step_bytes(*args) / peaks["hbm_bytes_per_s"]
    t_flops = ssm_step_flops(*args) / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "operations"}
