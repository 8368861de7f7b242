"""GLM-5 (``glm_moe_dsa``: latent attention under a learned sparse attention —
lightning indexer, exact top-k, IndexShare — and a share of sparse experts with
a selection bias) at ``GlmMoeDsaConfig.tiny()`` on seeded weights: the model,
the engine's two paged caches (latent rows and index keys on one page table),
chunked prefill with the carried step, the three new kernels and the
held-experts share against the plain reference
(``paddle_tpu/models/reference/glm_moe_dsa.py``)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.kernels import registry
from paddle_tpu.kernels.pallas import dsa_index
from paddle_tpu.kernels.pallas import mla_sparse_attention as ksp
from paddle_tpu.models import (GlmMoeDsaConfig, GlmMoeDsaForCausalLM,
                               glm_moe_dsa)
from paddle_tpu.models.reference import glm_moe_dsa as ref
from paddle_tpu.nn.layer import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(cfg, seed=3):
    paddle.seed(seed)
    model = GlmMoeDsaForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return model, params, get


@pytest.fixture(scope="module")
def tiny():
    """Published layers 2-6 of the pattern (dense + full, then shared x 3,
    full), 8 experts all held, 6 index keys a query."""
    cfg = GlmMoeDsaConfig.tiny()
    return (cfg,) + _build(cfg)


@pytest.fixture(scope="module")
def share():
    """A share of it: experts 2..5 of a router of 8."""
    cfg = GlmMoeDsaConfig.tiny(n_routed_experts=4, router_experts=8,
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


def test_selected_absorbed_forward_matches_the_reference(tiny):
    """The ``nn.Layer`` forward (absorbed, an additive mask from the exact
    top-k of the index scores) against the reference (non-absorbed, a mask
    from ``lax.top_k``): logits, with contexts four times ``index_topk``."""
    cfg, model, _params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 24))
    got = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        want = np.asarray(ref.logits(get, dataclasses.asdict(cfg), ids[b]))
        np.testing.assert_allclose(got[b], want, atol=2e-4)


def test_the_selected_sets_are_the_references_and_shared_layers_reuse_them(
        tiny):
    """In float32 each ``full`` layer selects exactly the reference's
    ``S_t`` — and a ``shared`` layer attends the very selection of the
    ``full`` layer before it (the same object), a later ``full`` layer its
    own."""
    cfg, _model, params, get = tiny
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, 32)
    seen = []

    class Spy(glm_moe_dsa._DenseAttend):
        def __call__(self, *args, **kw):
            out = super().__call__(*args, **kw)
            seen.append(self.bias)
            return out

    x = params["embed"][jnp.asarray(ids)][None].astype(jnp.float32)
    pos = jnp.arange(32, dtype=jnp.int32)[None]
    attend = Spy(glm_moe_dsa.attn_scale(cfg), cfg.index_topk)
    for p in params["layers"]:
        x, _stats = glm_moe_dsa.block_fn(cfg, p, x, pos, attend, None)
    assert cfg.layer_kinds() == ["full", "shared", "shared", "shared", "full"]
    assert seen[1] is seen[0] and seen[2] is seen[0] and seen[3] is seen[0]
    assert seen[4] is not seen[0]
    want = []
    ref.logits(get, dataclasses.asdict(cfg), ids, selected=want)
    assert len(want) == len(attend.selected) == 2
    differ = 0
    for mask, idx in zip(attend.selected, want):
        mask = np.asarray(mask[0])
        for t in range(32):
            mine = set(np.nonzero(mask[t])[0].tolist())
            theirs = {int(s) for s in idx[t] if s <= t}
            assert len(mine) == min(t + 1, cfg.index_topk)
            differ += mine != theirs
    assert differ == 0
    assert not np.array_equal(np.asarray(seen[0]), np.asarray(seen[4]))


@pytest.mark.parametrize("which", ["tiny", "share"])
def test_chunked_prefill_then_decode_through_both_caches_match_the_reference(
        which, request):
    """Prompts of 1 to 4 chunks (buckets 8 / 16; contexts to nine times
    ``index_topk``) go together through the engine: chunked prefill — the
    largest bucket's program carrying the running rows' decode step — then
    decode, through the latent arenas and the index arenas, against the
    reference's ONE full forward over the engine's own output: the logprobs,
    the held routed pairs and the keys attended, exactly."""
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
    # tiny widths hold the contraction in one tile: an expert's weights are
    # streamed once a call it gets a row in
    assert c["moe_weight_streams_total"] == c["moe_experts_hit_total"] > 0
    assert st["moe_weight_streams_per_expert"] == 1.0
    # the keys attended: min(t + 1, topk) a position a layer, counted on the
    # device from the selection itself, prefill and decode apart
    assert c["attn_keys_selected_prefill_total"] == _selected_keys(
        [len(p) for p in prompts], cfg.index_topk, 5)
    assert c["attn_keys_selected_prefill_total"] + \
        c["attn_keys_selected_decode_total"] == _selected_keys(
            consumed, cfg.index_topk, 5)
    # what the two indexers scored: every visible position, once a full layer
    assert c["index_keys_scored_prefill_total"] == \
        2 * c["attn_keys_prefill_total"]
    assert c["index_keys_scored_decode_total"] == \
        2 * c["attn_keys_decode_total"]
    pool = eng._pool
    assert len(pool.k) == 5 and len(pool.v) == 2
    assert pool.v[0].shape == (pool.num_pages, 8, cfg.index_head_dim)
    by_kind = pool.bytes_by_kind()
    assert by_kind == {"latent": 5 * pool.num_pages * 8 * 128 * 4,
                       "index": 2 * pool.num_pages * 8 * 8 * 4}
    assert st["kv_pool_bytes"] == sum(by_kind.values())


def test_the_prefix_trie_shares_index_rows_with_latent_pages(tiny):
    """A page holds its tokens' latent rows AND their index keys (one page
    table for both arenas), so a prompt whose leading blocks the trie serves
    scores its suffix's queries against index keys another request wrote."""
    cfg, model, _params, get = tiny
    prompt = np.random.default_rng(7).integers(0, cfg.vocab_size, 44)
    eng = _engine(model, prefix_cache=True)
    with eng:
        first = eng.submit(prompt, max_new_tokens=4,
                           return_logprobs=True).result(timeout=600)
        again = eng.submit(prompt, max_new_tokens=4,
                           return_logprobs=True).result(timeout=600)
    assert eng.stats()["kv_pages"]["prefix"]["hits"] > 0
    np.testing.assert_array_equal(first[0], again[0])
    want = ref.next_token_logprobs(get, dataclasses.asdict(cfg), again[0], 64)
    np.testing.assert_allclose(again[1], want[len(prompt) - 1:], atol=2e-4)


def test_the_engines_own_programs_select_the_references_sets(tiny):
    """``GenerationEngine.selected_keys`` names the keys each position
    attends, by the largest bucket's chunk program over the paged caches (3
    chunks and a tail here, contexts to nine times ``index_topk``): in
    float32 exactly the reference's ``S_t`` in both ``full`` layers; the
    reference GIVEN that selection computes what it computes by its own, and
    says how far the two agree — of a selection that is wrong (every
    position's most recent keys) too."""
    cfg, model, _params, get = tiny
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, 55)
    eng = _engine(model)
    with pytest.raises(RuntimeError, match="close"):
        eng.selected_keys(ids)
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
    for shared, selected, lead_shared, lead in agreement:
        np.testing.assert_array_equal(selected, n)
        np.testing.assert_array_equal(shared, n)
        np.testing.assert_array_equal(lead, np.minimum(n, cfg.index_topk // 2))
        np.testing.assert_array_equal(lead_shared, lead)
    recent = np.zeros((54, 64), bool)
    for t in range(54):
        recent[t, max(0, t + 1 - cfg.index_topk):t + 1] = True
    recent, agreement = [np.packbits(recent, axis=1, bitorder="little")] * 2, []
    off = ref.next_token_logprobs(get, conf, ids, 64, given=recent,
                                  agreement=agreement)
    assert np.abs(off - lp).max() > 1e-3
    assert all(a[0].sum() < 0.7 * n.sum() and a[2].sum() < 0.7 * a[3].sum()
               for a in agreement)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.selected_keys(np.zeros(200, np.int64))
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    plain = serving.GenerationEngine(
        GPTForCausalLM(GPTConfig.tiny()), serving.GenerationConfig(
            max_slots=2, max_seq_len=32, prefill_buckets=(8,)))
    plain.close()
    with pytest.raises(ValueError, match="no index row"):
        plain.selected_keys(np.arange(4))


@pytest.mark.parametrize("what", ["draft_model", "warm_pool_bytes",
                                  "export_kv_pages"])
def test_what_an_index_cache_cannot_take_yet_is_refused_in_words(tiny, what):
    cfg, model, _params, _get = tiny
    if what == "draft_model":
        with pytest.raises(ValueError, match="indexer selects"):
            _engine(model, draft_model=model)
    elif what == "warm_pool_bytes":
        with pytest.raises(ValueError, match="latent row"):
            _engine(model, prefix_cache=True, warm_pool_bytes=1 << 20)
    else:
        eng = _engine(model)
        with pytest.raises(RuntimeError, match="latent row"):
            eng.export_kv_pages(np.arange(16))
        eng.close()


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(tiny):
    """THE SHARE TEST, with the selection bias in it: the routed result of
    each of the 4 shares of a layer (2 of its 8 experts each), with what
    every chip computes alike — the shared expert — counted once, add up to
    what the uncut reference gives for the whole layer."""
    cfg, _model, _params, get = tiny
    whole = dataclasses.asdict(cfg)
    layer, n = 1, 24
    keys = ref.SHARED_KEYS + ref.EXPERT_KEYS + ("router", "router_bias")
    w = {k: get(k, layer) for k in keys}
    u = jnp.asarray(np.random.default_rng(5).normal(size=(n, cfg.hidden_size)),
                    jnp.float32)
    want, pairs = ref.mlp_branch(u, w.__getitem__, whole, False)
    assert pairs == n * cfg.num_experts_per_tok
    shared = ref._swiglu(u, *(w[k] for k in ref.SHARED_KEYS))
    program, reference, held = shared, shared, 0
    for first in range(0, 8, 2):
        mine = {k: w[k][first:first + 2] for k in ref.EXPERT_KEYS}
        y, stats = moe.moe_held_experts_mlp(
            u, w["router"], mine["experts_gate"], mine["experts_up"],
            mine["experts_down"], top_k=cfg.num_experts_per_tok, first=first,
            score="sigmoid", norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, bias=w["router_bias"])
        program = program + y
        held += int(stats["held"])
        part, n_held = ref.mlp_branch(
            u, {**w, **mine}.__getitem__,
            dict(whole, n_routed_experts=2, router_experts=8,
                 held_experts_first=first), False)
        assert n_held == int(stats["held"])
        reference = reference + (part - shared)
    assert held == pairs
    np.testing.assert_allclose(np.asarray(program), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(reference), np.asarray(want),
                               atol=2e-5)


def test_the_selection_bias_moves_choices_and_no_gate():
    """``noaux_tc``: the top-k is of ``score + bias``, a chosen expert's gate
    its own score; ``bias=None`` is the router as it was."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(16, 8)) / 4, jnp.float32)
    plain = moe._route(x, wr, 2, score="sigmoid", norm_topk=False)
    none = moe._route(x, wr, 2, score="sigmoid", norm_topk=False, bias=None)
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(none[1]))
    bias = jnp.asarray(rng.normal(size=8) * 0.3, jnp.float32)
    gate_v, gate_i, _aux = moe._route(x, wr, 2, score="sigmoid",
                                      norm_topk=False, bias=bias)
    assert (np.asarray(gate_i) != np.asarray(plain[1])).any()
    s = np.asarray(jax.nn.sigmoid(x @ wr))
    want_i = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(gate_i), -1),
                                  np.sort(want_i, -1))
    np.testing.assert_allclose(
        np.asarray(gate_v), np.take_along_axis(s, np.asarray(gate_i), -1),
        rtol=1e-6)


def test_interleaved_rope_is_a_rotation_of_neighbouring_pairs():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 5, 3, 8)).astype(np.float32)
    pos = np.array([[0, 1, 2, 3, 4], [100, 101, 5000, 5001, 40000]])
    got = np.asarray(glm_moe_dsa.rope_pairs(jnp.asarray(x), jnp.asarray(pos),
                                            8e6))
    theirs = np.asarray(ref._rope(jnp.asarray(x[0]), 0, 8e6))
    np.testing.assert_allclose(got[0], theirs, atol=1e-6)
    want = np.zeros_like(x)
    for r in range(2):
        for w in range(5):
            for i in range(4):
                a = pos[r, w] * 8e6 ** (-2 * i / 8)
                c, s = np.cos(a), np.sin(a)
                want[r, w, :, 2 * i] = x[r, w, :, 2 * i] * c \
                    - x[r, w, :, 2 * i + 1] * s
                want[r, w, :, 2 * i + 1] = x[r, w, :, 2 * i + 1] * c \
                    + x[r, w, :, 2 * i] * s
    np.testing.assert_allclose(got, want, atol=2e-4)
    # a rotation: dot products of a query and a key depend on the distance
    q, k = x[0, 0, 0], x[0, 1, 0]
    def at(p):
        r = glm_moe_dsa.rope_pairs(
            jnp.asarray(np.stack([q, k])[None, :, None, :]),
            jnp.asarray([[p + 7, p]]), 8e6)
        return float(jnp.dot(r[0, 0, 0], r[0, 1, 0]))
    assert abs(at(3) - at(900)) < 1e-3


# -- the kernels ----------------------------------------------------------------

def _paged(rng, S, B, PL, width, dtype=jnp.float32):
    arena = jnp.asarray(rng.standard_normal((S * B + 1, PL, width)), dtype)
    tables = jnp.asarray(1 + rng.permutation(S * B).reshape(S, B), jnp.int32)
    return arena, tables


@pytest.mark.parametrize("S,W,starts", [(4, 1, (0, 13, 41, 7)),
                                        (1, 16, (24,)), (2, 12, (3, 70))])
def test_index_scores_kernel_matches_its_reference_at_ragged_lengths(
        S, W, starts):
    rng = np.random.default_rng(11)
    arena, tables = _paged(rng, S, 12, 8, 16)
    start = jnp.asarray(starts, jnp.int32)
    qi = jnp.asarray(rng.standard_normal((S, W, 4, 16)), jnp.float32)
    wi = jnp.asarray(rng.standard_normal((S, W, 4)), jnp.float32)
    want = np.asarray(dsa_index.dsa_index_scores(qi, wi, arena, tables, start,
                                                 impl="reference"))
    got = np.asarray(dsa_index.dsa_index_scores(qi, wi, arena, tables, start,
                                                impl="interpret"))
    assert got.shape == want.shape == (S, -(-W // 8) * 8,
                                       dsa_index.padded_context(12, 8))
    seen = np.isfinite(want)
    assert (np.isfinite(got) == seen).all()
    for s in range(S):       # token w of row s scores start + w + 1 keys
        assert (seen[s].sum(-1)[:W] == starts[s] + np.arange(W) + 1).all()
        assert not seen[s, W:].any()
    np.testing.assert_allclose(got[seen], want[seen], atol=2e-5)


@pytest.mark.parametrize("S,W,starts", [(4, 1, (0, 13, 41, 7)),
                                        (1, 16, (24,)), (2, 12, (3, 70))])
def test_selected_attention_kernel_matches_its_reference(S, W, starts):
    rng = np.random.default_rng(12)
    arena, tables = _paged(rng, S, 12, 8, 128)
    start = jnp.asarray(starts, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, W, 4, 128)) / 4, jnp.float32)
    Wp, Lp = -(-W // 8) * 8, dsa_index.padded_context(12, 8)
    scores = jnp.asarray(rng.standard_normal((S, Wp, Lp)), jnp.float32)
    pos = start[:, None] + jnp.arange(Wp)
    scores = jnp.where((jnp.arange(Lp)[None, None] <= pos[..., None])
                       & (jnp.arange(Wp) < W)[None, :, None], scores,
                       -jnp.inf)
    bias, n = dsa_index.exact_topk_bias(scores, 6)
    assert (np.asarray(n)[:, :W] == np.minimum(np.asarray(pos)[:, :W] + 1,
                                               6)).all()
    want = ksp.mla_sparse_attention(q, arena, tables, start, bias, dv=64,
                                    scale=0.3, impl="reference")
    got = ksp.mla_sparse_attention(q, arena, tables, start, bias, dv=64,
                                   scale=0.3, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and it IS a selection: the dense kernel's answer differs
    dense = ksp.mla_sparse_attention(q, arena, tables, start,
                                     jnp.zeros_like(bias), dv=64, scale=0.3,
                                     impl="reference")
    assert float(jnp.abs(dense - want).max()) > 1e-2
    with pytest.raises(ValueError, match="bias is"):
        ksp.mla_sparse_attention(q, arena, tables, start, bias[:, :, :8],
                                 dv=64, scale=0.3, impl="reference")


@pytest.mark.parametrize("n,k", [(300, 17), (1024, 128), (40, 64)])
def test_exact_topk_is_lax_top_k_ties_and_all(n, k):
    """Scores on a coarse grid tie at the threshold in most rows: exactly
    ``k`` keys, the earlier position first, as ``lax.top_k`` chooses; rows
    with fewer than ``k`` finite scores select them all; the bucketed form
    (``longest``) selects the same."""
    rng = np.random.default_rng(13)
    sc = np.round(rng.standard_normal((6, n)) * 2) / 2
    sc[1, n // 3:] = -np.inf
    sc[2, 3:] = -np.inf
    sc = jnp.asarray(sc, jnp.float32)
    bias, cnt = dsa_index.exact_topk_bias(sc, k)
    finite = np.isfinite(np.asarray(sc)).sum(-1)
    assert (np.asarray(cnt) == np.minimum(finite, k)).all()
    for r in range(6):
        _v, idx = jax.lax.top_k(sc[r], min(k, n))
        want = {int(i) for i in np.asarray(idx) if np.isfinite(sc[r, i])}
        assert want == set(np.nonzero(np.asarray(bias[r]) == 0)[0].tolist())
    assert set(np.unique(np.asarray(bias))) <= {0.0, np.float32(-1e30)}
    for longest in (1, n // 2, n):
        masked = jnp.where(jnp.arange(n) < longest, sc, -jnp.inf)
        a = dsa_index.exact_topk_bias(masked, k)
        b = dsa_index.exact_topk_bias(masked, k, jnp.int32(longest))
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_the_bucketed_top_k_runs_over_the_narrowest_width_that_covers():
    sc = jnp.asarray(np.random.default_rng(14).standard_normal((2, 8192)),
                     jnp.float32)
    text = jax.jit(lambda s, n: dsa_index.exact_topk_bias(s, 64, n)) \
        .lower(sc, jnp.int32(5)).as_text()
    for width in (1024, 2048, 4096, 8192):      # one branch a width
        assert f"tensor<2x{width}xui32>" in text or \
            f"tensor<2x{width}xi1>" in text, width


def test_new_kernels_resolve_through_the_registry(monkeypatch):
    for name in ("dsa_index_scores", "mla_sparse_attention"):
        monkeypatch.delenv("PT_PALLAS_INTERPRET", raising=False)
        assert registry.resolve(name) == "reference"       # the CPU
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        assert registry.resolve(name) == "interpret"
        assert registry.kernel_table()["ops"][name]["doc"]


def test_the_engine_runs_the_pallas_kernels_under_the_interpreter(
        tiny, monkeypatch):
    """The whole served path through the Pallas kernels' own code
    (``PT_PALLAS_INTERPRET``): a chunked prompt and its decode against the
    reference."""
    cfg, model, _params, get = tiny
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    prompt = np.random.default_rng(9).integers(0, cfg.vocab_size, 21)
    (full, lps), = _serve(_engine(model, max_slots=2), [prompt], [3])
    want = ref.next_token_logprobs(get, dataclasses.asdict(cfg), full, 64)
    np.testing.assert_allclose(lps, want[len(prompt) - 1:], atol=2e-4)
    calls = registry.kernel_table()["ops"]
    assert calls["dsa_index_scores"]["calls"]["interpret"] > 0
    assert calls["mla_sparse_attention"]["calls"]["interpret"] > 0


def test_config_says_what_it_cannot_do():
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        GlmMoeDsaConfig.tiny(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="must own an indexer"):
        GlmMoeDsaConfig.tiny(layer_offset=3, num_hidden_layers=4)
    with pytest.raises(ValueError, match="outside the router"):
        GlmMoeDsaConfig.tiny(n_routed_experts=4, router_experts=8,
                             held_experts_first=6)
    cfg = GlmMoeDsaConfig(num_nextn_predict_layers=0)     # GLM-5.2 itself
    assert cfg.layer_kinds().count("full") == 21
    assert [cfg.is_dense(i) for i in range(4)] == [True, True, True, False]
    spec = GlmMoeDsaConfig.tiny().served_model().cache_spec
    assert spec["kind"] == "latent" and spec["index"]["layers"] == \
        ["full", "shared", "shared", "shared", "full"]
    assert GlmMoeDsaConfig.tiny().served_model().carries_rounds


def test_the_benchmarks_reference_is_the_repos():
    with open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                           "glm_moe_dsa.py")) as f, \
            open(os.path.join(REPO, "benchmark", "lib",
                              "reference_glm_moe_dsa.py")) as g:
        assert f.read() == g.read()
