"""What one ``pt_retention_step`` and one ``pt_retention_chunk`` call
(``paddle_tpu/kernels/pallas/power_retention.py``) have to move and compute,
from the MATHEMATICS alone — the benchmark's own arithmetic, kept apart from
the program's: ``phi`` is the minimal symmetric square, ``D = d (d + 1) / 2``
(8256 at 128), the state and the normaliser ``z`` [D] are float32, whatever
layout a kernel tiles them to, and the rows are those the call was asked to
advance — so that a later kernel reads against the same work.

One STEP advances ``rows`` slots x ``kv_heads`` heads one token: for each
(row, head) it reads ``S`` [D, d] and ``z`` [D], forms ``e^lambda S + phi(k)
v^T``, writes both back and emits, for each of the head's ``group`` query
heads, ``phi(q)^T S / (phi(q) . z + eps)``.

- bytes: 2 x rows x kv_heads x (D x d + D) x 4 (the state, in and out) + the
  small operands in float32: ``q`` in and ``y`` out (rows x heads x d each),
  ``k`` and ``v`` (rows x kv_heads x d each), the gate (rows x kv_heads);
- operations: per state element a multiply and a multiply-add for the update
  (3) and a multiply-add for each query head's contraction (2 x group), plus
  building ``phi`` (D x (1 + group) multiplies).

One CHUNK call advances ``rows`` rows x ``kv_heads`` heads over ``tokens``
positions in inner chunks of ``c``: per token and K/V head the ``group``
contractions ``phi(q)^T S`` and the update ``phi(k) v^T`` (2 x D x d each),
the normaliser's (2 x D each), and inside a chunk the causal half of the
product ``q . k`` and of the weighted sum of ``v`` (2 x d each a (query, key)
pair, c / 2 keys a query on average).

- operations: tokens x kv_heads x (2 D (d + 1) (group + 1) + group x c x 2 d);
- bytes: the state of every (row, head) in and out once, and ``q, k, v`` in
  and ``y`` out in float32.
"""
from typing import Dict


def phi_dim(d: int) -> int:
    return d * (d + 1) // 2


def step_bytes(rows: int, heads: int, kv_heads: int, d: int) -> int:
    state = rows * kv_heads * (phi_dim(d) * d + phi_dim(d)) * 4
    small = 4 * (2 * rows * heads * d + 2 * rows * kv_heads * d
                 + rows * kv_heads)
    return 2 * state + small


def step_flops(rows: int, heads: int, kv_heads: int, d: int) -> int:
    group = heads // kv_heads
    D = phi_dim(d)
    return rows * kv_heads * (D * (d + 1) * (3 + 2 * group)
                              + D * (1 + group))


def chunk_flops(rows: int, tokens: int, heads: int, kv_heads: int, d: int,
                c: int) -> int:
    group = heads // kv_heads
    D = phi_dim(d)
    return rows * tokens * kv_heads * (2 * D * (d + 1) * (group + 1)
                                       + group * c * 2 * d)


def _floor(n_bytes: float, flops: float, peaks: Dict) -> Dict:
    t_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "operations"}


def step_floor_seconds(shape: Dict, rows: int, peaks: Dict) -> Dict:
    """The least time the chip could take to advance ``rows`` (row, layer)
    pairs one token — one call's rows, or a stretch's calls' summed: both
    bounds are linear in the rows."""
    args = (rows, shape["heads"], shape["kv_heads"], shape["d"])
    return _floor(step_bytes(*args), step_flops(*args), peaks)


def chunk_floor_seconds(shape: Dict, tokens: int, calls: int,
                        peaks: Dict) -> Dict:
    """The least time the chip could take for ``calls`` one-row chunk calls
    that advanced ``tokens`` valid positions in all: the larger of their
    operations and their bytes (each call moves one row's state in and
    out)."""
    h, kvh, d = shape["heads"], shape["kv_heads"], shape["d"]
    state = 2 * kvh * (phi_dim(d) * d + phi_dim(d)) * 4
    return _floor(calls * state + 4 * tokens * d * (2 * h + 2 * kvh),
                  chunk_flops(1, tokens, h, kvh, d, shape["c"]), peaks)
