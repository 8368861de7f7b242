"""The served grouped matmuls' tiles (``kernels/grouped_matmul.py``):
``choose_tiling`` picks them from shapes by the bytes they move, the kernel
hands back the rows' dtype rounded once, and ``moe_held_experts_mlp`` counts
how often an expert's weights are streamed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.nn.layer.moe import moe_held_experts_mlp

# (m routed pairs, k, n, held experts, router outputs) -> the tile: the
# three served configurations' expert layers at the programs their cells run
SERVED = {
    # Laguna, 256 of 256 held, experts of 2048 x 512: the carrying call
    # (2048 + 128 tokens x 8), a decode round, the 256- and 512-token calls
    "laguna-carry-in": ((17408, 2048, 512, 256, 256), (128, 2048, 512)),
    "laguna-carry-out": ((17408, 512, 2048, 256, 256), (128, 512, 2048)),
    "laguna-round-in": ((1024, 2048, 512, 256, 256), (128, 2048, 512)),
    "laguna-round-out": ((1024, 512, 2048, 256, 256), (128, 512, 2048)),
    "laguna-256-in": ((2048, 2048, 512, 256, 256), (128, 2048, 512)),
    "laguna-256-out": ((2048, 512, 2048, 256, 256), (128, 512, 2048)),
    "laguna-512-in": ((4096, 2048, 512, 256, 256), (128, 2048, 512)),
    "laguna-512-out": ((4096, 512, 2048, 256, 256), (128, 512, 2048)),
    # openPangu, 16 of 256 held, 7680 x 2048: 512 + 128 tokens, a round
    "openpangu-carry-in": ((5120, 7680, 2048, 16, 256), (128, 7680, 256)),
    "openpangu-carry-out": ((5120, 2048, 7680, 16, 256), (128, 2048, 768)),
    "openpangu-round-in": ((1024, 7680, 2048, 16, 256), (128, 7680, 256)),
    # GLM-5.2, 16 of 256 held, 6144 x 2048: 2048 + 32 tokens
    "glm-carry-in": ((16640, 6144, 2048, 16, 256), (128, 6144, 256)),
    "glm-carry-out": ((16640, 2048, 6144, 16, 256), (128, 2048, 1024)),
    # no whole-k weight tile fits at any n tile: k stays tiled
    "wide-k": ((1024, 32768, 512, 16, 256), None),
    # fewer rows than a row tile: the row tile is the operand
    "few-rows": ((64, 2048, 512, 16, 256), (64, 2048, 512)),
}


def _costs(m, k, n, held, outputs, tm):
    return {(tk, tn): gm.tiling_cost(m, k, n, (tm, tk, tn), groups=held,
                                     rows_per_group=m / outputs)
            for tk in gm._tile_sizes(k) for tn in gm._tile_sizes(n)}


@pytest.mark.parametrize("case", list(SERVED))
def test_tiles_follow_from_the_shapes(case):
    (m, k, n, held, outputs), want = SERVED[case]
    tm, tk, tn = gm.choose_tiling(m, k, n, groups=held,
                                  rows_per_group=m / outputs)
    if want is not None:
        assert (tm, tk, tn) == want
    # what megablox asks of a tile: rows in eights (or the operand), k and n
    # tiles whole lanes that divide the operand
    assert tm == min(128, m) and (tm % 8 == 0 or tm == m)
    assert tk % 128 == 0 and k % tk == 0 and tn % 128 == 0 and n % tn == 0
    costs = _costs(m, k, n, held, outputs, tm)
    fits = {t: c for t, c in costs.items() if c["vmem"] <= gm.VMEM_BUDGET}
    mine = fits[(tk, tn)]
    assert mine["vmem"] <= gm.VMEM_BUDGET < 16 * 2 ** 20
    assert mine["bytes"] == min(c["bytes"] for c in fits.values())
    # the contraction is ONE tile exactly where the reckoning says a tiled
    # k's second stream of the weights outweighs the rows a whole-k tile's
    # narrower n tile reads again
    whole = [c["bytes"] for (a, _), c in fits.items() if a == k]
    tiled = [c["bytes"] for (a, _), c in fits.items() if a < k]
    assert (tk == k) == bool(whole and (not tiled or min(whole) <= min(tiled)))
    hit = min(held, max(1, round(held * m / outputs)))  # experts with rows
    assert mine["weights"] == (hit if tk == k else mine["visits"]) * k * n * 2


def test_a_tiled_k_streams_the_weights_once_a_visit():
    """The reckoning itself: Laguna's carrying call under its old tile
    (128, 512, 512) against the chosen one — 391 visits of 256 experts."""
    old = gm.tiling_cost(17408, 2048, 512, (128, 512, 512), groups=256,
                         rows_per_group=68, out_item=4)
    new = gm.tiling_cost(17408, 2048, 512, (128, 2048, 512), groups=256,
                         rows_per_group=68)
    assert old["visits"] == new["visits"] == 136 + 256 - 1
    assert old["weights"] == 391 * 2048 * 512 * 2
    assert new["weights"] == 256 * 2048 * 512 * 2
    assert old["rows"] == 391 * 128 * 2048 * 2       # a visit re-reads them
    assert new["rows"] == 136 * 128 * 2048 * 2       # a row tile, once
    assert old["steps"] == 4 * new["steps"]


# ragged groups that straddle the 128-row tiles: [0, 100) [100, 160) — one
# tile edge inside — an empty group, [160, 256)
_SIZES = (100, 60, 0, 96)


@pytest.mark.parametrize("tiling", [(128, 256, 128), (128, 128, 128)],
                         ids=["one-k-tile", "two-k-tiles"])
def test_the_kernel_rounds_its_accumulator_once(tiling):
    """megablox ``gmm`` with ``preferred_element_type=bfloat16`` is, bit for
    bit, its float32 result cast to bfloat16 — also where a row tile is
    shared by groups and its earlier rows are read back as bfloat16."""
    from jax.experimental.pallas.ops.tpu import megablox as mb

    k1, k2 = jax.random.split(jax.random.PRNGKey(45))
    lhs = jax.random.normal(k1, (256, 256), jnp.bfloat16)
    rhs = jax.random.normal(k2, (4, 256, 256), jnp.bfloat16)
    sizes = jnp.asarray(_SIZES, jnp.int32)
    f32 = mb.gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
                 tiling=tiling, interpret=True)
    b16 = mb.gmm(lhs, rhs, sizes, preferred_element_type=jnp.bfloat16,
                 tiling=tiling, interpret=True)
    assert b16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(f32.astype(jnp.bfloat16)).view(np.uint16),
        np.asarray(b16).view(np.uint16))
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(f32), np.asarray(want), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("one_k_tile", [True, False])
def test_weight_streams_against_a_hand_count(monkeypatch, one_k_tile):
    """320 tokens, top 1, four experts of 256 x 128 that get 100, 60, 0 and
    160 rows: three experts are hit; on 128-row tiles their rows span 1 + 2
    + 2 tiles, which is what a tiled k streams."""
    if not one_k_tile:  # room for a (128, 128, 128) tile of float32, no more
        monkeypatch.setattr(gm, "VMEM_BUDGET", gm.tiling_cost(
            320, 256, 128, (128, 128, 128), groups=4, rows_per_group=80,
            lhs_item=4, rhs_item=4, out_item=4)["vmem"])
    to = np.repeat([0, 1, 3], [100, 60, 160])
    x = np.full((320, 256), 0.01, np.float32)
    x[np.arange(320), to] = 4.0
    wr = np.zeros((256, 4), np.float32)
    wr[np.arange(4), np.arange(4)] = 1.0
    rng = np.random.default_rng(0)
    w_in = rng.standard_normal((4, 256, 128)).astype(np.float32) * 0.05
    w_out = rng.standard_normal((4, 128, 256)).astype(np.float32) * 0.05
    y, stats = jax.jit(lambda *a: moe_held_experts_mlp(
        *a, top_k=1, first=0))(x, wr, w_in, w_in, w_out)
    assert int(stats["held"]) == int(stats["pairs"]) == 320
    assert int(stats["experts_hit"]) == 3
    assert int(stats["weight_streams"]) == (3 if one_k_tile else 5)
    assert np.isfinite(np.asarray(y)).all()
