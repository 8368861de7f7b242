"""Laguna-XS.2 (window and full attention layers mixed, a head count and a
RoPE by layer kind, one gate a head, all experts held) at
``LagunaConfig.tiny()`` on seeded weights: the model, the engine's paged
cache of two layer kinds, chunked prefill across the window, the whole expert
layer and the refusals, against the plain reference
(``paddle_tpu/models/reference/laguna.py``)."""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import LagunaConfig, LagunaForCausalLM
from paddle_tpu.models import laguna
from paddle_tpu.models.reference import laguna as ref
from paddle_tpu.nn.layer import moe
from paddle_tpu.serving.paged_kv import (CacheLayout, PageDemand, PagedKVPool,
                                         PoolExhausted, window_page_bound)


def _build(cfg, seed=3):
    paddle.seed(seed)
    model = LagunaForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer=-1):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return model, params, get


@pytest.fixture(scope="module")
def tiny():
    cfg = LagunaConfig.tiny()
    return (cfg,) + _build(cfg)


def _engine(model, **over):
    kw = dict(max_slots=3, max_seq_len=128, page_len=4,
              prefill_buckets=(4, 8, 16), prefix_cache=False, num_pages=120)
    kw.update(over)
    return serving.GenerationEngine(model, serving.GenerationConfig(**kw))


def _serve(eng, prompts, max_new):
    with eng:
        futs = [eng.submit(p, max_new_tokens=n, return_logprobs=True)
                for p, n in zip(prompts, max_new)]
        return [f.result(timeout=300) for f in futs]


_JITTED = (ref._project, ref._attend, ref._swiglu, ref._experts,
           ref._head_slice)


# -- (a) the model against the reference ---------------------------------------

def test_forward_matches_the_reference_with_both_kinds_of_layer(tiny):
    """Window 8 over 40 tokens, 4 and 6 query heads over 2 K/V heads, YaRN
    over half a head and plain RoPE over a whole one, the gate, the dense
    layer and four sparse ones: float32, exact to rounding."""
    cfg, model, _params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 40))
    got = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        want = np.asarray(ref.logits(get, laguna.as_dict(cfg), ids[b]))
        np.testing.assert_allclose(got[b], want, atol=2e-4)


@pytest.mark.parametrize("control", ["NO_WINDOW", "ROUND"])
def test_the_references_controls_move_the_logits(tiny, control, monkeypatch):
    """What the benchmark's two controls switch: the window left out of the
    sliding layers, and every matmul operand at 3 mantissa bits. Each must
    move the logits of positions past the window (the jitted pieces are
    traced anew under the switch)."""
    cfg, _model, _params, get = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 24)
    want = np.asarray(ref.logits(get, laguna.as_dict(cfg), ids))
    for fn in _JITTED:
        fn.clear_cache()
    monkeypatch.setattr(ref, control, True if control == "NO_WINDOW" else
                        (lambda x: jax.lax.reduce_precision(x, 8, 3)))
    try:
        got = np.asarray(ref.logits(get, laguna.as_dict(cfg), ids))
    finally:
        for fn in _JITTED:
            fn.clear_cache()
    assert np.abs(got - want)[cfg.sliding_window:].max() > 1e-2
    if control == "NO_WINDOW":   # the first 8 positions see the same keys
        np.testing.assert_allclose(got[:cfg.sliding_window],
                                   want[:cfg.sliding_window], atol=2e-4)


def test_the_reference_in_blocks_is_the_reference_whole(tiny, monkeypatch):
    """On the chip the reference takes 2048 positions at a time and computes
    only the blocks a request reaches: the same numbers as one block."""
    cfg, _model, _params, get = tiny
    ids = np.random.default_rng(6).integers(0, cfg.vocab_size, 45)
    whole, n = ref.next_token_logprobs(get, laguna.as_dict(cfg), ids, 64,
                                       vocab_slices=2, with_pairs=True)
    monkeypatch.setattr(ref, "BLOCK", 16)
    blocks, m = ref.next_token_logprobs(get, laguna.as_dict(cfg), ids, 64,
                                        vocab_slices=3, with_pairs=True)
    np.testing.assert_allclose(blocks, whole, atol=2e-5)
    assert n == m == 44 * 2 * 4
    got = np.asarray(ref.logits(get, laguna.as_dict(cfg), ids[:32]))
    monkeypatch.setattr(ref, "BLOCK", 2048)
    np.testing.assert_allclose(
        got, np.asarray(ref.logits(get, laguna.as_dict(cfg), ids[:32])),
        atol=2e-4)


def test_yarn_inverse_frequencies_follow_the_written_formula():
    """The published full-attention RoPE: theta 5e5 over 64 rotated dims,
    factor 64 over 4096 positions, beta_fast 64, beta_slow 1."""
    rope = laguna._default_rope()[laguna.FULL]
    inv = ref.yarn_inv_freq(rope, 64)
    base = 500000.0 ** (np.arange(0, 64, 2) / 64.0)

    def corr(rot):
        return 64 * math.log(4096 / (rot * 2 * math.pi)) / \
            (2 * math.log(500000.0))

    low, high = math.floor(corr(64)), math.ceil(corr(1))
    assert (low, high) == (5, 16)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = (1 / (64 * base)) * ramp + (1 / base) * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    # fast dims keep their frequency, slow dims are stretched 64 x
    np.testing.assert_allclose(inv[:5], 1 / base[:5], rtol=1e-12)
    np.testing.assert_allclose(inv[16:], 1 / (64 * base[16:]), rtol=1e-12)
    cfg = laguna.as_dict(LagunaConfig())
    _inv, dim, fac = ref.rope_of(cfg, laguna.FULL)
    assert (dim, round(fac, 5)) == (64, 1.41589)
    inv_s, dim_s, fac_s = ref.rope_of(cfg, laguna.SLIDING)
    assert (dim_s, fac_s) == (128, 1.0)
    np.testing.assert_allclose(
        inv_s, 1 / 10000.0 ** (np.arange(0, 128, 2) / 128.0), rtol=1e-12)


def test_the_routers_top_k_weights(tiny):
    """Sigmoid scores, the top 2 of 8 normalised, then the factor 2.5."""
    cfg, _model, params, _get = tiny
    u = jnp.asarray(np.random.default_rng(2).normal(size=(7, 64)),
                    jnp.float32)
    wr = params["layers"][1]["router"]
    gates = np.asarray(ref._route(u, wr, top_k=2, scale=2.5))
    s = 1 / (1 + np.exp(-np.asarray(u, np.float64) @ np.asarray(wr)))
    for t in range(7):
        top = np.argsort(-s[t])[:2]
        assert set(np.nonzero(gates[t])[0]) == set(top)
        np.testing.assert_allclose(gates[t][top],
                                   2.5 * s[t][top] / s[t][top].sum(),
                                   rtol=1e-5)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-5)


def test_a_config_cut_in_depth_keeps_the_first_layers():
    cfg = LagunaConfig(num_hidden_layers=5)
    assert cfg.layer_types == [laguna.FULL] + [laguna.SLIDING] * 3 + \
        [laguna.FULL]
    assert cfg.num_attention_heads_per_layer == [48, 64, 64, 64, 48]
    assert cfg.mlp_layer_types == ["dense"] + ["sparse"] * 4
    assert cfg.served_model().cache_spec == {
        "kind": "kv_by_layer", "window": 512,
        "layers": ["full", "window", "window", "window", "full"]}
    # the issue's arithmetic: 3.870 B parameters in layers 0-4
    shapes = cfg.served_model().param_shapes()
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 3_869_857_792 and round(n / 1e9, 3) == 3.870
    with pytest.raises(ValueError, match="entries for"):
        LagunaConfig(num_hidden_layers=5, layer_types=[laguna.FULL] * 3)


# -- (b) the engine against the reference --------------------------------------

def test_chunked_prefill_across_the_window_then_decode_past_three_windows(
        tiny):
    """Short and long requests together in one batch: prompts of 1 to 4
    chunks (buckets 4 / 8 / 16) that cross the window of 8, then decode for
    up to 60 tokens (more than seven windows), so that window pages go back
    and are taken again; against the reference's ONE full forward over the
    engine's own output."""
    cfg, model, _params, get = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 37, 50, 11, 3)]
    new = [40, 30, 12, 60, 5]
    eng = _engine(model)
    outs = _serve(eng, prompts, new)
    pairs = 0
    for p, (full, lps) in zip(prompts, outs):
        want, n = ref.next_token_logprobs(get, laguna.as_dict(cfg), full, 128,
                                          vocab_slices=2, with_pairs=True)
        pairs += n
        np.testing.assert_allclose(lps, want[len(p) - 1:], atol=2e-4)
    st = eng.stats()
    c = st["counters"]
    # (e) every expert is held: every routed pair met one, idle rows and a
    # bucket's padding route nowhere
    consumed = sum(len(p) for p in prompts) + sum(new) - len(new)
    assert c["moe_pairs_total"] == consumed * 2 * 4 == pairs
    assert c["moe_held_pairs_total"] == c["moe_pairs_total"]
    # tiny widths hold the contraction in one tile: an expert's weights are
    # streamed once a call it gets a row in
    assert c["moe_weight_streams_total"] == c["moe_experts_hit_total"] > 0
    assert st["moe_weight_streams_per_expert"] == 1.0
    # pages of the window layers went back while their slots ran on
    assert c["window_pages_released_total"] > 40
    w = st["kv_pages"]["window"]
    assert w["pages_live"] == 0 and st["kv_pages"]["pages_live"] == 0
    # the two gauges of a cache of two layer kinds (docs/serving.md)
    assert st["kv_pages_live_by_kind"] == {"full": 0, "window": 0}
    by_kind = st["kv_pool_bytes_by_kind"]
    assert by_kind["full"] > by_kind["window"] > 0
    assert by_kind["full"] + by_kind["window"] == st["kv_pool_bytes"]
    assert w["alloc_total"] == w["free_total"]
    # a slot never held more than its chunk's bound: 3 slots at a time
    assert w["pages_peak"] <= 3 * window_page_bound(8, 16, 4)
    # keys in range: the window layers scored far fewer than the full ones
    assert c["attn_keys_window_total"] * 2 < c["attn_keys_full_total"] * 3 / 2
    assert c["attn_keys_full_total"] == 2 * (c["attn_keys_decode_total"]
                                            + c["attn_keys_prefill_total"])
    assert 0 < c["attn_keys_window_decode_total"] < \
        c["attn_keys_window_total"]


def test_the_engine_counts_the_pages_its_attention_calls_walk(tiny):
    """``attn_pages_walked_<kind>_total`` / ``attn_pages_in_range_<kind>_total``
    and ``stats()["attn_walk_amplification"]``: ``walk_cost`` of the tiles
    ``choose_tiles`` gives each traced shape, over the calls dispatched — a prompt of three
    chunks (behind 0, 16 and 32 tokens) and the rounds that follow, whose
    idle rows walk nothing: they are counted as skipped, and every live
    grid step but a call's first as one whose first block was in flight."""
    from paddle_tpu.kernels.pallas.ranged_paged_attention import \
        choose_tiles, walk_cost

    cfg, model, _params, _get = tiny
    eng = _engine(model)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 37)
    _serve(eng, [prompt], [4])
    st = eng.stats()
    c = st["counters"]
    G, PL, d = cfg.num_key_value_heads, 4, cfg.head_dim
    layers = {"full": 2, "window": 3}
    pipeline = {"skipped": 0, "prefetched": 0}
    for kind, window in (("full", None), ("window", 8)):
        walks = eng._attends[kind].walks
        assert {1, 8, 16} <= set(walks)     # a round, the buckets called
        want = {"pages": 0, "pages_in_range": 0}
        # chunks of 16, 16 and 5 tokens (the last in the bucket of 8), then
        # the three rounds that emit tokens 2..4: the prompt's slot at 37,
        # 38, 39 and two idle rows, which walk nothing
        calls = [(16, [0], None), (16, [16], None), (8, [32], None)] + \
            [(1, [n, 0, 0], [True, False, False]) for n in (37, 38, 39)]
        Hg = cfg.num_attention_heads_per_layer[0 if kind == "full" else 1] // G
        for W, keys, live in calls:
            assert walks[W] == Hg
            cost = walk_cost(len(keys), W, Hg, G, PL, d, window, keys,
                             choose_tiles(W, Hg, G, PL, d, window, 4), 4,
                             live)
            for k in want:
                want[k] += cost[k] * layers[kind]
            for k in pipeline:
                pipeline[k] += cost[k] * layers[kind]
        walked = c[f"attn_pages_walked_{kind}_total"]
        held = c[f"attn_pages_in_range_{kind}_total"]
        assert (walked, held) == (want["pages"], want["pages_in_range"])
        # the rounds' part apart: three rounds of one live row of three
        dec = {"pages": 0, "pages_in_range": 0}
        for W, keys, live in calls[3:]:
            cost = walk_cost(3, 1, Hg, G, PL, d, window, keys,
                             choose_tiles(1, Hg, G, PL, d, window, 4), 4,
                             live)
            assert (cost["skipped"], cost["prefetched"]) == (2, 0)
            for k in dec:
                dec[k] += cost[k] * layers[kind]
        assert c[f"attn_pages_walked_{kind}_decode_total"] == dec["pages"]
        assert c[f"attn_pages_in_range_{kind}_decode_total"] == \
            dec["pages_in_range"]
        assert st["attn_walk_amplification"][kind] == {
            "prefill": round((walked - dec["pages"])
                             / (held - dec["pages_in_range"]), 3),
            "decode": round(dec["pages"] / dec["pages_in_range"], 3)}
        assert walked >= held > 0
    # two idle rows a round, three rounds, five layers; a round's one live
    # step is its call's first and every chunk here is one tile: no step
    # found its first block in flight (``test_ranged_attention_tiles.py``
    # lists streams that do)
    assert c["attn_rows_idle_skipped_total"] == pipeline["skipped"] == 30
    assert c["attn_steps_prefetched_total"] == pipeline["prefetched"] == 0


def test_keys_in_window_arithmetic():
    f = serving.GenerationEngine._keys_in_window
    for lo, hi, w in [(0, 3, 2), (0, 20, 8), (5, 9, 8), (7, 8, 8), (30, 46, 8)]:
        assert f(lo, hi, w) == sum(min(i + 1, w) for i in range(lo, hi))


# -- (d) the cache's accounting -------------------------------------------------

def test_a_slots_window_pages_never_pass_the_bound(tiny):
    """Drive the pool's ``slide`` as a prompt's chunks and then its decode
    rounds do: the slot never holds more than the bound of the program in
    flight, what is behind the window has gone back, and the slide says what
    changed hands."""
    _cfg, model, _params, _get = tiny
    eng = _engine(model, max_slots=2)
    pool = eng._pool
    s, wa = eng._slots[0].pages, pool.window_allocator
    held = lambda: s.whi - s.wlo  # noqa: E731
    for lo in range(0, 64, 16):                       # four 16-token chunks
        before = held()
        released, taken = pool.slide(s, lo, lo + 15)
        assert held() == before - released + taken
        assert held() <= window_page_bound(8, 16, 4) == 7
        assert wa.live_pages == held()
        first_visible = max(lo - 7, 0) // 4
        assert s.wlo == first_visible and s.whi == (lo + 15) // 4 + 1
        assert (s.wtable[:s.wlo] == 0).all() and (s.wtable[s.wlo:s.whi] > 0).all()
    for pos in range(64, 100):                        # decode rounds
        pool.slide(s, pos, pos)
        assert held() <= window_page_bound(8, 1, 4) == 4 == eng._wbound
    pool.release(s)
    assert wa.live_pages == 0 and (s.wtable == 0).all()
    wa.check()
    eng.close()


def test_admission_counts_both_kinds_and_requeues_when_either_is_short(tiny):
    """``can_allocate`` holds a request back while the window pool cannot
    give its widest chunk beside what the running slots are promised; the
    engine serves both requests all the same, one after the other."""
    cfg, model, _params, _get = tiny
    pool = PagedKVPool(model.served_model().cache_layout(4), 40, jnp.float32,
                       prefix_cache=False, max_slots=1, n_blocks=16,
                       window_pages=12, chunk=16)
    # a running slot holds one window page and is promised its bound, 4: of
    # the 11 usable, 10 are free and 7 left for a joining prompt's widest call
    running = pool.slot_pages()
    assert pool.join(running, pool.demand(np.arange(1), 3)) == (0, 1)
    long = pool.demand(np.arange(20), 20)
    assert long == PageDemand([tuple(range(i, i + 4)) for i in range(0, 20, 4)],
                              10, 7, 20)
    assert pool.can_allocate(long)
    (taken,) = pool.window_allocator.alloc(1)
    assert not pool.can_allocate(long)
    pool.window_allocator.release(taken)
    # the full pool is short
    assert not pool.can_allocate(PageDemand([], 40, 4, 1))
    # a join that cannot have its window pages leaves the pool as it was
    joining, held = pool.slot_pages(), pool.window_allocator.alloc(9)
    free = pool.allocator.free_pages
    with pytest.raises(PoolExhausted):
        pool.join(joining, long)
    assert pool.allocator.free_pages == free and joining.blocks == 0 \
        and not joining.table.any()
    for page in held:
        pool.window_allocator.release(page)
    pool.release(running)
    assert pool.allocator.live_pages == 0 == \
        pool.window_allocator.live_pages
    assert [a.shape for a in pool.k] == [
        (40, 2, 4, 16), (12, 2, 4, 16), (12, 2, 4, 16), (12, 2, 4, 16),
        (40, 2, 4, 16)]
    assert pool.bytes_by_kind() == {"full": 2 * 2 * 40 * 512,
                                    "window": 2 * 3 * 12 * 512}
    with pytest.raises(PoolExhausted):
        pool.window_allocator.alloc(12)
    # the smallest window pool two slots admit: 2 x 4 + 7 + scratch
    with pytest.raises(ValueError, match="window_pages 15"):
        _engine(model, max_slots=2, window_pages=15)
    eng = _engine(model, max_slots=2, window_pages=16)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 40) for _ in range(3)]
    outs = _serve(eng, prompts, [6, 6, 6])
    assert all(len(full) == 46 for full, _lps in outs)
    assert eng.stats()["kv_pages"]["window"]["pages_live"] == 0


# -- (e) the whole expert layer -------------------------------------------------

def test_an_expert_layer_that_holds_every_expert_is_the_whole_layer(tiny):
    cfg, _model, params, get = tiny
    p = params["layers"][2]
    u = jnp.asarray(np.random.default_rng(5).normal(size=(9, 64)),
                    jnp.float32)
    valid = jnp.arange(9) < 7
    got, stats = moe.moe_held_experts_mlp(
        u, p["router"], p["experts_gate"], p["experts_up"],
        p["experts_down"], top_k=2, first=0, scale=2.5, valid=valid,
        x_route=u)
    gates = ref._route(u, p["router"], top_k=2, scale=2.5)
    want = ref._experts(u, gates, p["experts_gate"], p["experts_up"],
                        p["experts_down"])
    np.testing.assert_allclose(np.asarray(got)[:7], np.asarray(want)[:7],
                               atol=2e-5)
    assert np.abs(np.asarray(got)[7:]).max() == 0    # padding routes nowhere
    assert int(stats["pairs"]) == int(stats["held"]) == 14


# -- (f) what a windowed cache refuses, in words --------------------------------

def test_what_cannot_serve_a_windowed_cache_is_refused_in_words(tiny):
    cfg, model, _params, _get = tiny
    with pytest.raises(ValueError, match="prefix cache cannot serve it"):
        _engine(model, prefix_cache=True)
    with pytest.raises(ValueError, match="speculative decoding is refused"):
        _engine(model, draft_model=model)
    with pytest.raises(ValueError, match="warm tier spills and restores"):
        _engine(model, warm_pool_bytes=1 << 20)
    eng = _engine(model)
    prompt = np.arange(8)
    with pytest.raises(RuntimeError, match="cannot be read out or installed"):
        eng.export_kv_pages(prompt)
    with pytest.raises(RuntimeError, match="cannot be read out or installed"):
        eng.install_kv_pages(prompt, [], [])
    eng.close()
    with pytest.raises(ValueError, match="unknown cache kind 'ring'"):
        CacheLayout.parse({"kind": "ring"}, None, 1, 4, 2, 16)
    with pytest.raises(ValueError, match="no prefix cache and no warm tier"):
        PagedKVPool(model.served_model().cache_layout(4), 8, jnp.float32,
                    prefix_cache=True, window_pages=8)


def test_the_benchmarks_reference_is_the_repos():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "paddle_tpu", "models", "reference",
                           "laguna.py")) as f, \
            open(os.path.join(repo, "benchmark", "lib",
                              "reference_laguna.py")) as g:
        assert f.read() == g.read()
