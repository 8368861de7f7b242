"""What the ``pt_mla_paged_attention`` calls
(``paddle_tpu/kernels/pallas/mla_paged_attention.py``) of a stretch of serving
have to move and compute, from their shapes and the cached lengths they met —
the benchmark's own arithmetic, kept apart from the program's.

One call attends ``heads`` query heads of each of its query tokens against
the cached rows that token may see. A cached row is ``row_width`` values in
the arena (576 = 512 ``c_kv`` + 64 ``k_r``, laid out at 640: whole 128-lane
tiles, which is what a page's DMA moves); a query token is a ``[heads,
row_width]`` slab in and a ``[heads, value_dim]`` slab out. With ``keys`` the
cached positions the call's query tokens see, SUMMED over the tokens (a
decode row of length n sees n + 1; token w of a chunk that starts at ``lo``
sees lo + w + 1):

- operations: each (query token, head, visible row) is one score (a dot of
  ``latent_dim`` = 576) and one weighted sum (``value_dim`` = 512): 2 x heads
  x keys x (latent_dim + value_dim);
- bytes: a decode round must read each row's cached rows once: keys x
  row_width x 2. A prefill chunk's tokens share their rows: the chunk's
  visible rows once, (lo + n) x row_width x 2 — its ``keys`` grow as n x lo +
  n^2 / 2 while its rows grow as lo + n, which is why a chunk is bound by its
  operations. Both also move the queries in and the contexts out.

A decode round at 128 heads: 2 x 128 x 1088 / 1280 = 218 FLOP a cached byte
against the chip's 197 TFLOP/s / 819 GB/s = 240: just under the ridge, on the
bytes' side.
"""
from typing import Dict


def decode_cost(keys: int, rows: int, shape: Dict) -> Dict:
    """All decode-round calls of a stretch: ``keys`` cached positions seen,
    summed over their ``rows`` live rows."""
    h, width = shape["heads"], shape["row_width"]
    item = shape["itemsize"]
    return {"bytes": keys * width * item
            + rows * h * (width + shape["value_dim"]) * item,
            "flops": 2 * h * keys * (shape["latent_dim"]
                                     + shape["value_dim"])}


def prefill_cost(keys: int, shape: Dict) -> Dict:
    """All prefill-chunk calls of a stretch, their query tokens seeing
    ``keys`` cached positions in all. Bound by operations (a chunk of n
    tokens at ``lo`` computes on n x lo + n^2 / 2 positions and reads lo + n
    rows), so the bytes are left at 0: a floor that can only be too low."""
    return {"bytes": 0,
            "flops": 2 * shape["heads"] * keys * (shape["latent_dim"]
                                                  + shape["value_dim"])}


def traced_floor_seconds(shape: Dict, peaks: Dict) -> float:
    """The least time the chip could take for every call of the traced
    stretch ``shape["traced"]`` describes (decode rounds and prefill chunks
    apart, each by its larger bound), all layers."""
    t = shape["traced"]
    dec = floor_seconds(decode_cost(t["keys_decode"], t["rows_decode"],
                                    shape), peaks)
    pre = floor_seconds(prefill_cost(t["keys_prefill"], shape), peaks)
    return shape["layers"] * (dec["seconds"] + pre["seconds"])


def floor_seconds(cost: Dict, peaks: Dict) -> Dict:
    """The least time a chip of ``peaks`` could take for ``cost``, and which
    of the two bounds it."""
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "operations"}
