"""dots3-note (``dots3_note``: latent attention of two kinds in one cache —
full layers under a lightning indexer, window layers with their own ranks,
heads and row width — a gate a head, a share of sparse experts) at
``Dots3NoteConfig.tiny()`` on seeded weights: the model, the engine's latent
cache by layer kind (two tables, two allocators, three arena shapes), chunked
prefill with the carried step, the window kernel and the held-experts share
against the plain reference (``paddle_tpu/models/reference/dots3_note.py``)."""
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.kernels.pallas import mla_paged_attention as kmla
from paddle_tpu.models import Dots3NoteConfig, Dots3NoteForCausalLM
from paddle_tpu.models.reference import dots3_note as ref
from paddle_tpu.nn.layer import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(cfg, seed=3):
    paddle.seed(seed)
    model = Dots3NoteForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return model, params, get


@pytest.fixture(scope="module")
def tiny():
    """Published layers 0-4 (dense full, full, window x 3), 8 experts all
    held, 6 index keys a query, a window of 9."""
    cfg = Dots3NoteConfig.tiny()
    return (cfg,) + _build(cfg)


@pytest.fixture(scope="module")
def share():
    """A share of it: experts 2..5 of a router of 8."""
    cfg = Dots3NoteConfig.tiny(n_routed_experts=4, router_experts=8,
                               held_experts_first=2)
    return (cfg,) + _build(cfg)


def _engine(model, **over):
    kw = dict(max_slots=4, max_seq_len=128, page_len=8,
              prefill_buckets=(8, 16), prefix_cache=False)
    kw.update(over)
    return serving.GenerationEngine(model, serving.GenerationConfig(**kw))


def _serve(eng, prompts, max_new):
    with eng:
        futs = [eng.submit(p, max_new_tokens=n, return_logprobs=True)
                for p, n in zip(prompts, max_new)]
        return [f.result(timeout=600) for f in futs]


def _selected_keys(lengths, topk, layers):
    return layers * sum(min(t + 1, topk) for n in lengths for t in range(n))


def _window_keys(lengths, window, layers):
    return layers * sum(min(t + 1, window) for n in lengths for t in range(n))


def test_the_configuration_is_the_published_one():
    """The defaults are the catalog row: 46 layers of which 13 full, the two
    kinds' sizes, and what a kind's layer derives from them."""
    cfg = Dots3NoteConfig()
    kinds = cfg.layer_kinds()
    assert (len(kinds), kinds.count("full")) == (46, 13)
    assert [i for i, k in enumerate(kinds) if k == "full"][:4] == [0, 1, 5, 9]
    assert kinds[-1] == "full"
    full, win = cfg.dims("full"), cfg.dims("window")
    assert (full["heads"], full["dc"], full["dn"], full["theta"]) == \
        (128, 512, 128, 8e7)
    assert (win["heads"], win["dc"], win["dn"], win["theta"]) == \
        (64, 1024, 192, 5e4)
    np.testing.assert_allclose(full["rescale"], (5 ** 0.5, 10 ** 0.5))
    np.testing.assert_allclose(win["rescale"], (5 ** 0.5, 5 ** 0.5))
    spec = cfg.served_model().cache_spec
    assert (spec["dim"], spec["value_dim"], spec["window"]) == (576, 512, 513)
    assert spec["window_row"] == {"dim": 1088, "value_dim": 1024,
                                  "scale": 256 ** -0.5, "heads": 64}
    assert spec["index"]["layers"][:6] == ["full", "full", None, None, None,
                                           "full"]
    assert cfg.served_model().carries_rounds


def test_forward_matches_the_reference(tiny):
    """The model's forward (absorbed attention, the exact top-k, the window
    mask, the gate, the rescale) against the reference (non-absorbed, dense
    under masks), and each full layer selects the reference's sets."""
    cfg, model, params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 40))
    got = np.asarray(model(paddle.to_tensor(ids)).numpy())
    conf = dataclasses.asdict(cfg)
    for row in range(2):
        selected = []
        want = ref.logits(get, conf, ids[row], selected=selected)
        np.testing.assert_allclose(got[row], np.asarray(want), atol=3e-4)
        assert len(selected) == 2 and selected[0].shape == (40, 6)


def test_the_rescale_and_the_gate_are_in_the_result(tiny):
    """Both are arguments of shared helpers: without either the logits move."""
    cfg, model, _params, get = tiny
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, 24)
    conf = dataclasses.asdict(cfg)
    base = np.asarray(ref.logits(get, conf, ids))
    off = np.asarray(ref.logits(
        get, dict(conf, apply_mla_qkv_lora_rescale=False), ids))
    assert np.abs(off - base).max() > 1e-2

    def ungated(name, layer):
        w = get(name, layer)
        return jnp.zeros_like(w) if name == "g" else w   # sigmoid(0): a half

    half = np.asarray(ref.logits(ungated, conf, ids))
    assert np.abs(half - base).max() > 1e-2


@pytest.mark.parametrize("which", ["tiny", "share"])
def test_chunked_prefill_then_decode_through_the_caches_match_the_reference(
        which, request):
    """Prompts of 1 to 4 chunks (buckets 8 / 16; contexts to six windows and
    nine times ``index_topk``) go together through the engine: chunked prefill
    — the largest bucket's program carrying the running rows' decode step —
    then decode, through the full layers' latent and index arenas and the
    window layers' wider rows, window pages given back on the way, against the
    reference's ONE full forward over the engine's own output: the logprobs,
    the held routed pairs, the keys attended by kind and the pools'
    accounts."""
    cfg, model, _params, get = request.getfixturevalue(which)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 40, 23, 50)]
    new = [6, 4, 7, 5]
    eng = _engine(model)
    outs = _serve(eng, prompts, new)
    held = 0
    for p, (full, lps) in zip(prompts, outs):
        want, n = ref.next_token_logprobs(get, dataclasses.asdict(cfg), full,
                                          64, with_pairs=True)
        held += n
        np.testing.assert_allclose(lps, want[len(p) - 1:], atol=2e-4)
    st = eng.stats()
    c = st["counters"]
    assert c["prefill_chunks_total"] == 1 + 3 + 2 + 4
    assert c["rounds_carried_total"] > 0          # the carried step ran
    consumed = [len(p) + n - 1 for p, n in zip(prompts, new)]
    assert c["moe_pairs_total"] == sum(consumed) * cfg.num_experts_per_tok * 4
    assert c["moe_held_pairs_total"] == held
    # the full layers attend what they select, the window layers their window
    assert c["attn_keys_selected_prefill_total"] + \
        c["attn_keys_selected_decode_total"] == _selected_keys(
            consumed, cfg.index_topk, 2)
    assert c["index_keys_scored_prefill_total"] == \
        2 * c["attn_keys_prefill_total"]
    assert c["attn_keys_window_prefill_total"] == _window_keys(
        [len(p) for p in prompts], cfg.sliding_window_size, 3)
    assert c["attn_keys_window_total"] == _window_keys(
        consumed, cfg.sliding_window_size, 3) == \
        c["attn_keys_window_prefill_total"] + \
        c["attn_keys_window_decode_total"]
    assert c["attn_keys_full_total"] == 2 * (
        c["attn_keys_prefill_total"] + c["attn_keys_decode_total"])
    # the window kernel walks whole blocks: never fewer rows than its windows
    assert c["attn_rows_walked_window_total"] >= \
        c["attn_rows_in_window_total"] > 0
    # window pages went back behind the window, all of them by the end
    assert c["window_pages_released_total"] > 0
    assert c["window_pages_taken_total"] > c["window_pages_released_total"]
    pool = eng._pool
    pool.allocator.check()
    pool.window_allocator.check()
    assert pool.allocator.live_pages == pool.window_allocator.live_pages == 0
    assert pool.window_allocator.alloc_total == c["window_pages_taken_total"]
    # three arena shapes: full latent rows, index keys, wider window rows
    assert len(pool.k) == 5 and len(pool.v) == 2
    wp = pool.window_allocator.num_pages
    assert [a.shape for a in pool.k] == \
        [(pool.num_pages, 8, 128)] * 2 + [(wp, 8, 128)] * 3
    assert pool.v[0].shape == (pool.num_pages, 8, cfg.index_head_dim)
    by_kind = pool.bytes_by_kind()
    assert by_kind == {"latent_full": 2 * pool.num_pages * 8 * 128 * 4,
                       "latent_window": 3 * wp * 8 * 128 * 4,
                       "index": 2 * pool.num_pages * 8 * 8 * 4}
    assert st["kv_pool_bytes"] == sum(by_kind.values())
    assert st["kv_pages"]["window"]["pool_bytes"] == by_kind["latent_window"]


def test_wider_window_rows_are_recycled_between_requests(tiny):
    """A window pool too small for two long requests at once serves them one
    after the other, the second through the first's pages: the accounts hold
    (``PageAllocator.check``) and the logprobs are the reference's."""
    cfg, model, _params, get = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (60, 70, 33)]
    # 2 slots: each needs window_page_bound(9, 1, 8) = 3 decoding, a chunk 5
    eng = _engine(model, max_slots=2, window_pages=2 * 3 + 5 + 1)
    outs = _serve(eng, prompts, [5, 5, 5])
    for p, (full, lps) in zip(prompts, outs):
        want = ref.next_token_logprobs(get, dataclasses.asdict(cfg), full, 128)
        np.testing.assert_allclose(lps, want[len(p) - 1:], atol=2e-4)
    w = eng._pool.window_allocator
    w.check()
    eng._pool.allocator.check()
    assert w.live_pages == 0 and w.alloc_total > 3 * w.usable_pages / 2
    assert w.peak_live <= w.usable_pages
    with pytest.raises(ValueError, match="window_pages"):
        _engine(model, max_slots=2, window_pages=8)


def test_the_engines_own_programs_select_the_references_sets(tiny):
    """``GenerationEngine.selected_keys`` through BOTH tables (the window
    layers' pages round their small pool): exactly the reference's ``S_t`` in
    both full layers, and the reference GIVEN that selection computes what it
    computes by its own."""
    cfg, model, _params, get = tiny
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, 55)
    eng = _engine(model)
    eng.close()
    given = eng.selected_keys(ids[:-1])
    assert [g.shape for g in given] == [(54, 512 // 8)] * 2
    want, agreement = [], []
    conf = dataclasses.asdict(cfg)
    ref.logits(get, conf, np.pad(ids[:-1], (0, 10)), selected=want)
    for bits, idx in zip(given, want):
        mask = np.unpackbits(bits, axis=1, bitorder="little").astype(bool)
        for t in range(54):
            assert set(np.nonzero(mask[t])[0].tolist()) == \
                {int(s) for s in idx[t] if s <= t}
    lp = ref.next_token_logprobs(get, conf, ids, 64, given=given,
                                 agreement=agreement)
    np.testing.assert_array_equal(lp, ref.next_token_logprobs(get, conf, ids,
                                                              64))
    n = np.minimum(np.arange(54) + 1, cfg.index_topk)
    assert len(agreement) == 2
    for shared, selected, _lead_shared, _lead in agreement:
        np.testing.assert_array_equal(selected, n)
        np.testing.assert_array_equal(shared, n)


@pytest.mark.parametrize("what", ["prefix_cache", "draft_model",
                                  "warm_pool_bytes", "export_kv_pages",
                                  "install_kv_pages"])
def test_what_a_windowed_latent_cache_cannot_take_is_refused_in_words(
        tiny, what):
    cfg, model, _params, _get = tiny
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="sliding window.*prefix cache"):
            _engine(model, prefix_cache=True)
    elif what == "draft_model":
        with pytest.raises(ValueError, match="sliding window.*speculative"):
            _engine(model, draft_model=model)
    elif what == "warm_pool_bytes":
        with pytest.raises(ValueError, match="sliding window.*warm tier"):
            _engine(model, warm_pool_bytes=1 << 20)
    else:
        eng = _engine(model)
        with pytest.raises(RuntimeError, match="sliding window"):
            if what == "export_kv_pages":
                eng.export_kv_pages(np.arange(16))
            else:
                eng.install_kv_pages(np.arange(16), [], [])
        eng.close()


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer():
    """THE SHARE TEST at the cell's arithmetic: the routed result of each of
    the sixteen 16-expert shares of one 256-expert layer (top 8 of ``s + b``,
    ``routed_scaling_factor`` 1), with what every chip computes alike — the
    shared expert — counted once, add up to what the uncut reference gives
    for the whole layer."""
    cfg = Dots3NoteConfig.tiny(n_routed_experts=256, num_experts_per_tok=8,
                               num_hidden_layers=2)
    _model, _params, get = _build(cfg, seed=11)
    whole = dataclasses.asdict(cfg)
    layer, n = 1, 24
    keys = ref.SHARED_KEYS + ref.EXPERT_KEYS + ("router", "router_bias")
    w = {k: get(k, layer) for k in keys}
    u = jnp.asarray(np.random.default_rng(5).normal(size=(n, cfg.hidden_size)),
                    jnp.float32)
    want, pairs = ref.mlp_branch(u, w.__getitem__, whole, False)
    assert pairs == n * 8
    shared = ref._swiglu(u, *(w[k] for k in ref.SHARED_KEYS))
    program, reference, held = shared, shared, 0
    for first in range(0, 256, 16):
        mine = {k: w[k][first:first + 16] for k in ref.EXPERT_KEYS}
        y, stats = moe.moe_held_experts_mlp(
            u, w["router"], mine["experts_gate"], mine["experts_up"],
            mine["experts_down"], top_k=8, first=first, score="sigmoid",
            norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
            bias=w["router_bias"])
        program = program + y
        held += int(stats["held"])
        part, n_held = ref.mlp_branch(
            u, {**w, **mine}.__getitem__,
            dict(whole, n_routed_experts=16, router_experts=256,
                 held_experts_first=first), False)
        assert n_held == int(stats["held"])
        reference = reference + (part - shared)
    assert held == pairs
    np.testing.assert_allclose(np.asarray(program), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(reference), np.asarray(want),
                               atol=2e-5)


# -- the window kernel -----------------------------------------------------------

def _dense_window(q, arena, tables, start, dv, scale, window):
    """A dense masked softmax in numpy float64: keys ``i - window < j <= i``."""
    S, W, H, dl = q.shape
    PL = arena.shape[1]
    out = np.zeros((S, W, H, dv))
    a, q = np.asarray(arena, np.float64), np.asarray(q, np.float64)
    for s in range(S):
        kv = a[np.asarray(tables[s])].reshape(-1, dl)
        for w in range(W):
            i = int(start[s]) + w
            j = np.arange(max(i - window + 1, 0), i + 1)
            sc = q[s, w] @ kv[j].T * scale                     # [H, keys]
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[s, w] = (p / p.sum(-1, keepdims=True)) @ kv[j, :dv]
    return out


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("S,W,starts", [(4, 1, (0, 13, 41, 70)),
                                        (1, 16, (24,)), (2, 8, (0, 48))])
def test_window_kernel_matches_a_dense_masked_softmax(monkeypatch, impl, S, W,
                                                      starts):
    """The jnp path and the Pallas path (interpret mode; blocks of 2 pages so
    that the walk starts past block 0) against a dense softmax over exactly
    the window's keys — at a value width of its own and with the pages behind
    the window given back (table entry 0) — and a window one key shorter or
    longer is a different result."""
    monkeypatch.setattr(kmla, "_BLOCK_TOKENS", 16)
    rng = np.random.default_rng(S * 100 + W)
    PL, B, H, dl, dv, window = 8, 12, 2, 256, 128, 19
    arena = jnp.asarray(rng.standard_normal((S * B + 1, PL, dl)), jnp.float32)
    tables = 1 + rng.permutation(S * B).reshape(S, B)
    start = np.asarray(starts, np.int32)
    for s in range(S):     # what lies wholly behind the window is given back
        tables[s, :max(int(start[s]) - (window - 1), 0) // PL] = 0
    q = jnp.asarray(rng.standard_normal((S, W, H, dl)), jnp.float32)

    def run(n):
        return np.asarray(kmla.mla_paged_attention(
            q, arena, jnp.asarray(tables, jnp.int32), jnp.asarray(start),
            dv=dv, scale=0.07, window=n, impl=impl))

    want = _dense_window(q, arena, tables, start, dv, 0.07, window)
    got = run(window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    live = want[start.argmax()]           # a row with a whole window behind it
    for off in (window - 1, window + 1):
        other = run(off)[start.argmax()]
        if start.max() + W > window:
            assert np.abs(other - live).max() > 1e-3, off
    # no window: every earlier key (the kernel as it was), another result
    if start.max() + W > window:
        tables_full = 1 + rng.permutation(S * B).reshape(S, B)
        dense = np.asarray(kmla.mla_paged_attention(
            q, arena, jnp.asarray(tables_full, jnp.int32), jnp.asarray(start),
            dv=dv, scale=0.07, impl=impl))
        assert dense.shape == got.shape


def test_window_walk_counts_blocks_against_windows():
    """8 tokens a tile at 64 heads, blocks of 512 rows, a window of 513: a
    tile deep in a sequence walks 2 or 3 blocks for the 520 rows its queries
    see; a decode row 2 for 513."""
    walked, inside = kmla.window_walk(2048, 64, 128, 513, [4096])
    assert inside == 256 * 520
    assert 2 * 512 * 256 <= walked <= 3 * 512 * 256
    assert kmla.window_walk(1, 64, 128, 513, [4096, 100]) == \
        (2 * 512 + 512, 513 + 101)
    # nothing masked, nothing walked in vain: a window of whole blocks
    assert kmla.window_walk(512, 1, 128, 1, [512]) == (512, 512)
