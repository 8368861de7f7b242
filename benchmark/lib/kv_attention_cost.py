"""What the ``pt_ranged_attention_*`` calls
(``paddle_tpu/kernels/pallas/ranged_paged_attention.py``) of a stretch of
serving have to move and compute, from the keys in range the engine counted
for them — the benchmark's own arithmetic, kept apart from the program's.

One call attends the ``heads`` query heads of each of its query tokens
against the cached keys that token may see: in a full layer every earlier
key, in a window layer the last ``window`` (the engine counts both, summed
over the tokens and over a kind's layers: ``attn_keys_full_total``,
``attn_keys_window_total``). A cached key is a key and a value of ``kv_heads
x head_dim`` each: 2 x 8 x 128 x 2 B = 4096 B a token a layer.

- operations: each (query token, head, key in range) is one score and one
  weighted sum over ``head_dim``: 4 x head_dim x heads x keys;
- bytes: a decode round must read each row's keys in range once: keys x 4096
  B, plus its queries in and contexts out. A prefill chunk's tokens share
  their keys (a chunk of n tokens at ``lo`` computes on n x lo + n^2 / 2 keys
  and reads lo + n), which is why a chunk is bound by its operations: its
  bytes are left at 0, a floor that can only be too low.

A decode round at 64 heads: 4 x 128 x 64 / 4096 = 8 FLOP a cached byte
against the chip's 197 TFLOP/s / 819 GB/s = 240: far on the bytes' side.
"""
from typing import Dict

from .mla_cost import floor_seconds


def key_bytes(shape: Dict) -> int:
    return 2 * shape["kv_heads"] * shape["head_dim"] * shape["itemsize"]


def decode_cost(keys: int, query_heads: int, shape: Dict) -> Dict:
    """Decode-round calls of one kind of layer: ``keys`` keys in range,
    summed over their rows and the kind's layers; ``query_heads`` (row,
    head) pairs they served."""
    d = shape["head_dim"]
    # each key meets the heads of its own layer: ``keys`` is summed over the
    # kind's layers and ``heads`` is the kind's total, so a layer's heads are
    # heads / count
    return {"bytes": keys * key_bytes(shape)
            + 2 * query_heads * d * shape["itemsize"],
            "flops": 4 * d * keys * shape["heads_a_layer"]}


def prefill_cost(keys: int, shape: Dict) -> Dict:
    return {"bytes": 0,
            "flops": 4 * shape["head_dim"] * keys * shape["heads_a_layer"]}


def traced_floor_seconds(shape: Dict, peaks: Dict) -> float:
    """The least time the chip could take for every ranged-attention call of
    the traced stretch ``shape["traced"]`` describes: each kind's decode
    rounds by the larger of bytes and operations, its prefill chunks by
    operations."""
    t, total = shape["traced"], 0.0
    for kind, layers in shape["layers"].items():
        if not layers["count"]:
            continue
        one = dict(shape, heads_a_layer=layers["heads"] / layers["count"])
        dec = decode_cost(t[kind]["keys_decode"],
                          t["rows_decode"] * layers["heads"], one)
        pre = prefill_cost(t[kind]["keys_prefill"], one)
        total += floor_seconds(dec, peaks)["seconds"] + \
            floor_seconds(pre, peaks)["seconds"]
    return total
