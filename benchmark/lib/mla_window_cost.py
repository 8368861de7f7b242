"""What the WINDOW layers' latent attention of a stretch of serving has to
move and compute (dots3-note's ``sliding_attention`` layers: absorbed MLA over
the last ``window`` positions), from the widths and from what the engine
counted for the stretch — the benchmark's own arithmetic, kept apart from the
program's. A ROOFLINE READS THE WORK, WHATEVER IMPLEMENTS IT: the floor is
what the window REQUIRES — ``min(t + 1, window)`` keys a query — not what the
kernel that does it happens to walk (``pt_mla_window_attention`` reads whole
blocks from the one that holds a tile's first visible key and masks what lies
behind the window: ``serve.mla_window_walk_pct`` says how much more that is),
so the share cannot pass 100 % by the walk reading more.

One call attends ``heads`` query heads of each of its query tokens against the
cached rows inside that token's window. A cached row is ``row_width`` values in
the arena (1088 = 1024 ``c_kv`` + 64 ``k_r``, laid out at 1152: whole 128-lane
tiles, which is what a page's DMA moves); a query token is a ``[heads,
row_width]`` slab in and a ``[heads, value_dim]`` slab out. With ``keys`` the
positions inside the queries' windows, SUMMED over the tokens and over the
window layers (``attn_keys_window_*_total``):

- operations: each (query token, head, key in window) is one score (a dot of
  ``latent_dim`` = 1088) and one weighted sum (``value_dim`` = 1024): 2 x
  heads x keys x (latent_dim + value_dim);
- bytes: a decode row must read its window's rows once: keys x row_width x 2,
  and its query slab in and context out. A prefill chunk's neighbouring tokens
  share their windows' rows (a tile reads them once for all its queries), so
  a chunk is floored by its operations alone: bytes left at 0, a floor that
  can only be too low.

A decode row at 64 heads: 2 x 64 x 2112 / 2304 = 117 operations a cached byte
against the chip's 197 TFLOP/s / 819 GB/s = 240: on the bytes' side.
"""
from typing import Dict

from .mla_cost import floor_seconds


def decode_cost(keys: int, rows: int, shape: Dict) -> Dict:
    """All decode-round calls of a stretch: ``keys`` positions inside the
    rows' windows and ``rows`` live rows, both summed over the window
    layers."""
    h, width, item = shape["heads"], shape["row_width"], shape["itemsize"]
    return {"bytes": keys * width * item
            + rows * h * (width + shape["value_dim"]) * item,
            "flops": 2 * h * keys * (shape["latent_dim"]
                                     + shape["value_dim"])}


def prefill_cost(keys: int, shape: Dict) -> Dict:
    """All prefill-chunk calls of a stretch, their query tokens seeing
    ``keys`` positions inside their windows in all (summed over the window
    layers). By operations alone."""
    return {"bytes": 0,
            "flops": 2 * shape["heads"] * keys * (shape["latent_dim"]
                                                  + shape["value_dim"])}


def traced_floor_seconds(shape: Dict, peaks: Dict) -> float:
    """The least time the chip could take for every window-layer call of the
    traced stretch ``shape["traced"]`` describes (decode rounds and prefill
    chunks apart, each by its larger bound)."""
    t = shape["traced"]
    return floor_seconds(decode_cost(t["keys_decode"], t["rows_decode"],
                                     shape), peaks)["seconds"] + \
        floor_seconds(prefill_cost(t["keys_prefill"], shape),
                      peaks)["seconds"]
