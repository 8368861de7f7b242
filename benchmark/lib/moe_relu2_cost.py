"""What the grouped matmuls of an UNGATED held-experts layer
(``paddle_tpu/nn/layer/moe.py:moe_held_experts_mlp`` with no gate matrix ->
``kernels/grouped_matmul.py``: TWO megablox ``gmm`` calls, up and down, and
``down(relu(up(x))^2)`` between them) have to move and compute over a stretch
of serving, from the counts the window programs hand back — the benchmark's
own arithmetic, kept apart from the program's. (``moe_cost.gmm_cost`` reckons
the three matrices of a gated expert: read for this layer it would put the
floor 1.5 x too high.)

``rows`` is the routed (token, choice) pairs that met a held expert
(``moe_held_pairs_total``), ``experts_hit`` the held experts that got at least
one row, summed over layers and programs (``moe_experts_hit_total``): an expert
with no row streams nothing.

- bytes: an expert that got a row streams its two matrices once: 2 x hidden x
  width x 2 B; a row is read at ``hidden`` by up, written float32 and read
  bfloat16 at ``width`` between the two (the square is taken on the float32)
  and written at ``hidden``;
- operations: 2 x rows x 2 x hidden x width.

At 2688 x 1856 an expert's two matrices are 19.96 MB (24.4 us at 819 GB/s) and
a row is 20 MFLOP: an expert needs 241 rows a call before its operations
outweigh its weights — a decode round of 128 slots gives it 6, a 2048-token
chunk 96.
"""
from typing import Dict

from .mla_cost import floor_seconds  # noqa: F401  (the same two bounds)


def gmm_cost(rows: int, experts_hit: int, shape: Dict) -> Dict:
    h, w, item = shape["hidden"], shape["width"], shape["itemsize"]
    weights = experts_hit * 2 * h * w * item
    acts = rows * (h * item + w * 4 + w * item + h * item)
    return {"bytes": weights + acts, "flops": 2 * rows * 2 * h * w}
