"""The ranged attention kernel's tiles
(``kernels/pallas/ranged_paged_attention.py``): ``walk_cost`` reckons what a
call walks under a tiling from shapes and lengths alone, ``choose_tiles``
picks the tiling from the call's shape, and the engine counts the pages its
calls walk with the same arithmetic."""
import numpy as np
import pytest

from paddle_tpu.kernels.pallas import ranged_paged_attention as kr

# Laguna-XS.2: 8 K/V heads of 128, pages of 128 tokens; a full layer has 48
# query heads (6 a K/V head), a window layer 64 (8) and a window of 512
LAGUNA = dict(G=8, PL=128, d=128)
PAGE = 2 * 8 * 128 * 128 * 2          # a page's K and V, bytes


def _cost(S, W, Hg, window, keys, tiling, live=None, **shape):
    return kr.walk_cost(S, W, Hg, window=window, keys=keys, tiling=tiling,
                        live=live, **{**LAGUNA, **shape})


# -- walk_cost against a hand count --------------------------------------------

def test_a_chunk_of_64_tiles_walks_its_context_once_a_tile():
    """The tiling every call had before the chooser: a 2048-token chunk in
    64 tiles of 32 tokens, blocks of 4 pages (512 keys)."""
    c = _cost(1, 2048, 6, None, 0, (32, 4))
    # tile t ends at key 32 t + 31, in block t // 16: 16 tiles walk 1 block,
    # 16 walk 2, 16 walk 3, 16 walk 4
    assert c["steps"] == 64 and c["iterations"] == 16 * (1 + 2 + 3 + 4)
    assert c["pages"] == 640 and c["bytes"] == 640 * PAGE
    assert c["pages_in_range"] == 16          # 2048 keys / 128
    # behind 8192 cached tokens every tile walks the 16 blocks in front too
    c = _cost(1, 2048, 6, None, 8192, (32, 4))
    assert c["iterations"] == 64 * 16 + 160 == 1184
    assert c["pages"] == 4736 and c["bytes"] == 4736 * PAGE   # 2.48 GB
    assert c["pages_in_range"] == 80          # (8192 + 2048) / 128
    # twice the tokens a tile: half the tiles, each to the same last block
    twice = _cost(1, 2048, 6, None, 8192, (64, 4))
    assert twice["steps"] == 32 and twice["iterations"] == 32 * 16 + 80
    # blocks of 8 pages: half the iterations and the odd block's other half
    # DMA'd besides
    wide = _cost(1, 2048, 6, None, 8192, (64, 8))
    assert wide["iterations"] == 32 * 8 + 16 * (1 + 2)
    assert wide["pages"] == 8 * wide["iterations"] > twice["pages"]


def test_a_round_walks_each_rows_range_once():
    c = _cost(128, 1, 6, None, 1000, (1, 4))
    # key 1000 lies in block 1 of 512: two blocks, the 8 pages that hold a key
    assert c["steps"] == 128 and c["iterations"] == 256
    assert c["pages"] == c["pages_in_range"] == 128 * 8
    # one length a row; a sequence with nothing cached yet walks the block
    # its own key lies in
    lengths = np.array([0, 511, 512, 5000])
    c = _cost(4, 1, 6, None, lengths, (1, 4))
    assert c["iterations"] == 1 + 1 + 2 + 10
    assert c["pages_in_range"] == 1 + 4 + 5 + 40
    assert (c["skipped"], c["prefetched"]) == (0, 3)


def test_an_idle_row_starts_no_walk():
    """A hand-listed round: rows 0, 2 and 5 hold no sequence. They add no
    iteration, page or byte; a live step finds its first block in flight
    where the step before it was live (row 4 behind row 3), and starts its
    own behind an idle one or at the call's start."""
    lengths = np.array([0, 511, 0, 512, 5000, 0])
    c = _cost(6, 1, 6, None, lengths, (1, 4), live=lengths > 0)
    assert (c["steps"], c["skipped"], c["prefetched"]) == (6, 3, 1)
    assert c["iterations"] == 1 + 2 + 10
    assert c["pages"] == 4 * 13 and c["bytes"] == 4 * 13 * PAGE
    assert c["pages_in_range"] == 4 + 5 + 40
    # the same rows alone: the same walk, but the first live row is the
    # call's first step now
    alone = _cost(3, 1, 6, None, lengths[lengths > 0], (1, 4))
    assert {k: c[k] for k in ("iterations", "pages", "bytes",
                              "pages_in_range")} == \
        {k: alone[k] for k in ("iterations", "pages", "bytes",
                               "pages_in_range")}
    assert (alone["steps"], alone["skipped"], alone["prefetched"]) == (3, 0, 2)
    # a window layer's idle rows: the five pages of each live row, no more
    c = _cost(4, 1, 8, 512, [1000, 0, 0, 1000], (1, 1),
              live=[True, False, False, True])
    assert c["pages"] == c["pages_in_range"] == 10
    assert (c["skipped"], c["prefetched"]) == (2, 0)
    # W = k + 1 = 4 tokens a row in tiles of one: an idle row is 4 steps, a
    # live row's later tiles find their block in flight
    c = _cost(3, 4, 6, None, [0, 600, 0], (1, 4), live=[False, True, False])
    assert (c["steps"], c["skipped"], c["prefetched"]) == (12, 8, 3)
    assert c["iterations"] == 4 * 2
    # nothing live: nothing walked
    c = _cost(5, 1, 6, None, 0, (1, 4), live=np.zeros(5, bool))
    assert (c["skipped"], c["prefetched"], c["iterations"], c["pages"],
            c["bytes"], c["pages_in_range"]) == (5, 0, 0, 0, 0, 0)
    # a chunk never has an idle tile: every tile but the first is prefetched
    c = _cost(1, 2048, 6, None, 8192, (128, 8))
    assert (c["steps"], c["skipped"], c["prefetched"]) == (16, 0, 15)


def test_a_window_layer_walks_six_pages_for_five():
    """A decode row behind 1000 tokens sees keys 489..1000: pages 3..7, and
    blocks 1..3 of 2 pages."""
    c = _cost(1, 1, 8, 512, 1000, (1, 2))
    assert c["iterations"] == 3 and c["pages"] == 6
    assert c["pages_in_range"] == 5
    # blocks of 4 pages would DMA 8 for the same 5
    assert _cost(1, 1, 8, 512, 1000, (1, 4))["pages"] == 8
    # a chunk's tile sees its window and its own tokens: 32 tokens behind
    # 8192 see keys 7681..8223, blocks 30..32 of 256
    c = _cost(1, 32, 8, 512, 8192, (32, 2))
    assert c["steps"] == 1 and c["iterations"] == 3


def test_vmem_counts_every_buffer():
    c = _cost(1, 2048, 6, None, 0, (32, 4))
    rows = 192                                 # 6 heads x 32 tokens
    want = 2 * 2 * 8 * 512 * 128 * 2           # K and V double buffers
    want += 2 * 2 * 8 * rows * 128 * 2         # q and o, two blocks each
    want += 8 * rows * 128 * 4                 # acc
    want += 2 * 8 * rows * 128 * 4             # m and l: a padded lane each
    want += kr._SCORE_TILES * rows * 512 * 4   # scores in flight
    assert c["vmem"] == want
    # 6 query rows of a decode round pad to a (16, 128) tile
    assert kr._padded_rows(6, 1) == 16 and kr._padded_rows(8, 64) == 512


# -- choose_tiles ---------------------------------------------------------------

# (W, Hg, G, PL, d, window) -> (TW, KP): the five published shapes of Laguna
# (the 128-row round in both kinds of layer, one-row chunks of 2048 and 256),
# the two 512-token calls between them, and the later users' (ROADMAP S2:
# GPT-2-large's 20 heads of 64 and Falcon-H1's 5 query heads a K/V head of
# 128, both on pages of 16 tokens), which nothing wires yet
CHOSEN = {
    "laguna-round-full": ((1, 6, 8, 128, 128, None), (1, 4)),
    "laguna-round-window": ((1, 8, 8, 128, 128, 512), (1, 1)),
    "laguna-chunk2048-full": ((2048, 6, 8, 128, 128, None), (128, 8)),
    "laguna-chunk2048-window": ((2048, 8, 8, 128, 128, 512), (128, 2)),
    "laguna-chunk256-window": ((256, 8, 8, 128, 128, 512), (128, 2)),
    "laguna-chunk256-full": ((256, 6, 8, 128, 128, None), (128, 8)),
    "laguna-chunk512-full": ((512, 6, 8, 128, 128, None), (128, 8)),
    "laguna-chunk512-window": ((512, 8, 8, 128, 128, 512), (128, 2)),
    "gpt2-large-round": ((1, 1, 20, 16, 64, None), (1, 32)),
    "gpt2-large-chunk256": ((256, 1, 20, 16, 64, None), (256, 64)),
    "falcon-h1-round": ((1, 5, 4, 16, 128, None), (1, 64)),
    "falcon-h1-chunk256": ((256, 5, 4, 16, 128, None), (128, 64)),
    # 2 K/V heads of 128 on pages of 128 (PR 62: an iteration's fixed part is
    # paid once whatever the heads, so fewer heads want longer blocks): ZAYA1
    # (4 query heads a K/V head, 256 rows) and Nemotron-H (16, 128 rows);
    # their chunks as before
    "zaya1-round": ((1, 4, 2, 128, 128, None), (1, 8)),
    "nemotron-h-round": ((1, 16, 2, 128, 128, None), (1, 8)),
    "zaya1-chunk256": ((256, 4, 2, 128, 128, None), (256, 8)),
    "zaya1-chunk2048": ((2048, 4, 2, 128, 128, None), (256, 8)),
    "nemotron-h-chunk2048": ((2048, 16, 2, 128, 128, None), (64, 8)),
    # the parity tests' pages of 8 tokens, windows of 8 and 24
    "tiny-round": ((1, 6, 2, 8, 128, None), (1, 128)),
    "tiny-chunk-window": ((16, 8, 2, 8, 128, 8), (16, 1)),
    "tiny-chunk64-window": ((64, 8, 2, 8, 128, 24), (8, 1)),
}


def _candidates(W, PL):
    tws = [1 << n for n in range(W.bit_length()) if W % (1 << n) == 0]
    kps = [1 << n for n in range(12) if (PL << n) <= 2048]
    return [(tw, kp) for tw in tws for kp in kps]


@pytest.mark.parametrize("case", list(CHOSEN))
def test_tiles_follow_from_the_calls_shape(case):
    (W, Hg, G, PL, d, window), want = CHOSEN[case]
    TW, KP = kr.choose_tiles(W, Hg, G, PL, d, window, 2)
    assert (TW, KP) == want
    # legal: a power of two that divides the window's tokens; a block is a
    # whole number of pages, never a fraction of one, reckoned in keys
    assert W % TW == 0 and TW & (TW - 1) == 0
    assert isinstance(KP, int) and KP >= 1 and KP * PL <= 2048
    ref = kr._REFERENCE_KEYS

    def cost(t):
        return kr.walk_cost(len(ref), W, Hg, G, PL, d, window, ref, t, 2)

    mine = cost((TW, KP))
    # within the budget `walk_cost` reports, which is well inside the chip's
    assert mine["vmem"] <= kr.VMEM_BUDGET < 128 * 2 ** 20
    if window is not None and KP > 1:
        # the window's worst walk: the blocks a window that starts on a
        # block's last key touches (one page a block is the least there is)
        walked = (-(-(window - 1) // (KP * PL)) + 1) * KP * PL
        assert walked <= kr._WINDOW_WALK * window
    # and the least work of every candidate that is as legal
    for t in _candidates(W, PL):
        c = cost(t)
        KB = t[1] * PL
        bound = window is None or t[1] == 1 or \
            (-(-(window - 1) // KB) + 1) * KB <= kr._WINDOW_WALK * window
        if c["vmem"] <= kr.VMEM_BUDGET and bound:
            assert mine["work"] <= c["work"], t


def test_a_block_is_reckoned_in_keys_whatever_the_page():
    """Pages of 16 tokens get the blocks pages of 128 get, in keys: no second
    rule for a short page."""
    for Hg, window in ((6, None), (8, 512)):
        for W in (1, 2048):
            keys = {PL: kr.choose_tiles(W, Hg, 8, PL, 128, window, 2)[1] * PL
                    for PL in (16, 32, 64, 128)}
            assert len(set(keys.values())) == 1, keys


def test_a_windows_block_keeps_its_bound():
    """Laguna's window of 512: a block of 256 keys walks at most 3 blocks (6
    pages of 128 for the 5 that hold a visible key: the bound it had), one
    of 512 would walk 8 for 5 and is never chosen — whatever it would save."""
    for W in (1, 32, 256, 2048):
        _TW, KP = kr.choose_tiles(W, 8, 8, 128, 128, 512, 2)
        assert KP * 128 <= 256
    # a round's row shares its blocks with nobody: ONE page a block, so the
    # five pages that hold a visible key and no sixth
    c = _cost(1, 1, 8, 512, 1000, kr.choose_tiles(1, 8, 8, 128, 128, 512, 2))
    assert c["pages"] == c["pages_in_range"] == 5


def test_the_chooser_reads_the_shape_alone(monkeypatch):
    """No flag, environment variable or model's name: the same arguments give
    the same tiles with every ``PT_*`` variable set to anything."""
    import os

    want = kr.choose_tiles(2048, 6, 8, 128, 128, None, 2)
    for name in list(os.environ) + ["PT_RANGED_ROWS", "PT_RANGED_PAGES"]:
        if name.startswith("PT_"):
            monkeypatch.setenv(name, "7")
    assert kr.choose_tiles(2048, 6, 8, 128, 128, None, 2) == want
    for gone in ("_ROWS", "_PAGES_FULL", "_PAGES_WINDOW", "_tile_tokens"):
        assert not hasattr(kr, gone)
