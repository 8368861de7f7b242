"""What the learned sparse attention of a stretch of serving has to move and
compute — the index scores (``pt_dsa_index_scores``) and the selected latent
attention (``pt_mla_sparse_attention``) — from the widths and from what the
engine counted for the stretch: the benchmark's own arithmetic, kept apart from
the program's. A ROOFLINE READS THE WORK, WHATEVER IMPLEMENTS IT: the floor is
what the selection leaves to do, not what the kernel that does it happens to
read.

*Index scores.* A query token scores every cached position it may see with
``index_heads`` heads of ``index_dim``: ``scored`` (positions seen, summed over
the tokens and over the layers that own an indexer) x ``2 x index_heads x
index_dim`` operations. A decode row's index keys are its own: ``scored x
index_dim x itemsize`` bytes; a chunk's tokens share theirs and are bound by
operations (bytes left at 0: a floor that can only be too low).

*Selected attention.* A query token attends the ``selected`` keys it chose
(``min(t + 1, topk)`` a token a layer, summed) with ``heads`` heads against
rows of ``latent_dim`` in, ``value_dim`` out: ``2 x heads x selected x
(latent_dim + value_dim)`` operations. A decode row reads the rows it selected,
once: ``selected x row_width x itemsize`` bytes (``row_width``: the row as the
arena lays it out, 640 for 576) plus the query slab in and the context out. A
chunk's neighbouring queries may share a selected row, so a kernel that reads
it once for several must not read above 100 %: chunks are floored by their
operations alone.

At 64 heads a selected row is 2 x 64 x 1088 / 1280 = 109 operations a byte
against the chip's 197 TFLOP/s / 819 GB/s = 240: a decode row is on the bytes'
side of both floors.
"""
from typing import Dict

from .mla_cost import floor_seconds


def index_decode_cost(scored: int, shape: Dict) -> Dict:
    return {"bytes": scored * shape["index_dim"] * shape["itemsize"],
            "flops": 2 * shape["index_heads"] * shape["index_dim"] * scored}


def index_prefill_cost(scored: int, shape: Dict) -> Dict:
    return {"bytes": 0,
            "flops": 2 * shape["index_heads"] * shape["index_dim"] * scored}


def attend_decode_cost(selected: int, rows: int, shape: Dict) -> Dict:
    """``selected`` keys attended by the decode rows of a stretch, summed
    over rows and layers; ``rows`` live rows (a layer's)."""
    h, width, item = shape["heads"], shape["row_width"], shape["itemsize"]
    return {"bytes": selected * width * item
            + rows * shape["layers"] * h * (width + shape["value_dim"]) * item,
            "flops": 2 * h * selected * (shape["latent_dim"]
                                         + shape["value_dim"])}


def attend_prefill_cost(selected: int, shape: Dict) -> Dict:
    return {"bytes": 0,
            "flops": 2 * shape["heads"] * selected * (shape["latent_dim"]
                                                      + shape["value_dim"])}


def index_floor_seconds(shape: Dict, peaks: Dict) -> float:
    """The least time the chip could take for the index scores of the traced
    stretch ``shape["traced"]`` describes, all layers that own an indexer."""
    t = shape["traced"]
    return floor_seconds(index_decode_cost(t["scored_decode"], shape),
                         peaks)["seconds"] + \
        floor_seconds(index_prefill_cost(t["scored_prefill"], shape),
                      peaks)["seconds"]


def attend_floor_seconds(shape: Dict, peaks: Dict) -> float:
    """The same for the selected attention, all layers."""
    t = shape["traced"]
    return floor_seconds(attend_decode_cost(
        t["selected_decode"], t["rows_decode"], shape), peaks)["seconds"] + \
        floor_seconds(attend_prefill_cost(t["selected_prefill"], shape),
                      peaks)["seconds"]
