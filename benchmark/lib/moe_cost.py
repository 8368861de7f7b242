"""What the grouped matmuls of a held-experts layer
(``paddle_tpu/nn/layer/moe.py:moe_held_experts_mlp`` ->
``kernels/grouped_matmul.py``: three megablox ``gmm`` calls, gate, up and
down) have to move and compute over a stretch of serving, from the counts the
window programs hand back — the benchmark's own arithmetic, kept apart from
the program's.

``rows`` is the routed (token, choice) pairs that met a held expert
(``moe_held_pairs_total``), ``experts_hit`` the held experts that got at least
one row, summed over layers and programs (``moe_experts_hit_total``): an expert
with no row streams nothing.

- bytes: an expert that got a row streams its three matrices once: 3 x hidden
  x width x 2 each; a row is read at ``hidden`` by gate and by up, written and
  read at ``width`` three times between them (float32 out of gate and up,
  bfloat16 into down) and written at ``hidden``;
- operations: 2 x rows x 3 x hidden x width.

At 7680 x 2048 an expert's weights are 94.4 MB (0.115 ms at 819 GB/s) and a
row is 94 MFLOP: a held expert needs 227 rows a call before its operations
outweigh its weights — a decode round of 128 slots gives it 4, a 512-token
chunk 16.
"""
from typing import Dict

from .mla_cost import floor_seconds  # noqa: F401  (the same two bounds)


def gmm_cost(rows: int, experts_hit: int, shape: Dict) -> Dict:
    h, w, item = shape["hidden"], shape["width"], shape["itemsize"]
    weights = experts_hit * 3 * h * w * item
    acts = rows * (2 * h * item + 2 * w * 4 + w * item + h * item)
    return {"bytes": weights + acts, "flops": 2 * rows * 3 * h * w}
