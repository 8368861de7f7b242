"""openPangu-Ultra-MoE (latent attention + a share of sparse experts beside a
shared one) at ``OpenPanguMoEConfig.tiny()`` on seeded weights: the model, the
engine's latent paged cache, chunked prefill, the held-experts share and the
latent-attention kernel against the plain reference
(``paddle_tpu/models/reference/openpangu_moe.py``)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.kernels.pallas import mla_paged_attention as mla
from paddle_tpu.models import OpenPanguMoEConfig, OpenPanguMoEForCausalLM
from paddle_tpu.models.reference import openpangu_moe as ref
from paddle_tpu.nn.layer import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(cfg, seed=3):
    paddle.seed(seed)
    model = OpenPanguMoEForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return model, params, get


@pytest.fixture(scope="module")
def tiny():
    """The whole tiny model: 8 experts, all held."""
    cfg = OpenPanguMoEConfig.tiny()
    return (cfg,) + _build(cfg)


@pytest.fixture(scope="module")
def share():
    """A share of it: experts 2..5 of a router of 8."""
    cfg = OpenPanguMoEConfig.tiny(n_routed_experts=4, router_experts=8,
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
        return [f.result(timeout=300) for f in futs]


def test_a_prefill_calls_counts_land_with_the_call(tiny):
    """A prompt of four chunks: what each call counted on the device is
    added to the metrics when THAT call is read — four reads of one
    program's scalars, each before the prompt's first token, not one read of
    four at its end — so a stretch of the counters (the benchmark's traced
    second) holds the programs that ran in it."""
    cfg, model, _params, _get = tiny
    eng = _engine(model)
    seen, count = [], eng._count_programs

    def recording(counted):  # (programs read, pairs counted before them)
        seen.append((len(counted),
                     eng.stats()["counters"].get("moe_pairs_total", 0)))
        count(counted)

    eng._count_programs = recording
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 50)
    _serve(eng, [prompt], [2])
    reads = [(n, pairs) for n, pairs in seen if n][:4]
    layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    # 50 tokens are 16 + 16 + 16 + 8: before each read the metrics hold the
    # chunks read so far
    assert reads == [(1, k * cfg.num_experts_per_tok * layers)
                     for k in (0, 16, 32, 48)]


def test_absorbed_forward_matches_the_non_absorbed_reference(tiny):
    """The ``nn.Layer`` forward scores heads against ``[c_kv | k_r]`` rows
    (absorbed); the reference up-projects keys and values per head."""
    cfg, model, _params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 24))
    got = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        want = np.asarray(ref.logits(get, dataclasses.asdict(cfg), ids[b]))
        np.testing.assert_allclose(got[b], want, atol=2e-4)


@pytest.mark.parametrize("which", ["tiny", "share"])
def test_chunked_prefill_then_latent_decode_match_the_reference(
        which, request):
    """Prompts of 1 to 4 chunks (buckets 8 / 16) go together through the
    engine: chunked prefill, then decode through the latent cache, against
    the reference's ONE full forward over the engine's own output — the
    logprobs, and the routed pairs that met a held expert, exactly."""
    cfg, model, _params, get = request.getfixturevalue(which)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 40, 23, 50)]
    eng = _engine(model)
    outs = _serve(eng, prompts, [6, 4, 7, 5])
    held = 0
    for p, (full, lps) in zip(prompts, outs):
        want, n = ref.next_token_logprobs(get, dataclasses.asdict(cfg), full,
                                          64, with_pairs=True)
        held += n
        np.testing.assert_allclose(lps, want[len(p) - 1:], atol=2e-4)
    st = eng.stats()
    c = st["counters"]
    # 40 tokens are 16 + 16 + 8, 50 are 16 + 16 + 16 + 8 (the bucket of 2)
    assert c["prefill_chunks_total"] == 1 + 3 + 2 + 4
    # every chunk but a prompt's first went out behind the one before it
    assert c["programs_run_ahead_total"] >= 2 + 1 + 3
    consumed = sum(len(p) for p in prompts) + (6 + 4 + 7 + 5) - 4
    experts_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    # idle decode rows and a bucket's padding route nowhere
    assert c["moe_pairs_total"] == \
        consumed * cfg.num_experts_per_tok * experts_layers
    assert c["moe_held_pairs_total"] == held
    assert st["moe_held_share"] == round(held / c["moe_pairs_total"], 5)
    # tiny widths hold the contraction in one tile: an expert's weights are
    # streamed once a call it gets a row in
    assert c["moe_weight_streams_total"] == c["moe_experts_hit_total"] > 0
    assert st["moe_weight_streams_per_expert"] == 1.0
    if which == "tiny":
        assert held == c["moe_pairs_total"]  # every expert is held
    assert st["kv_pool_bytes"] == eng._kv_pool_bytes() == \
        cfg.num_hidden_layers * eng._pool.num_pages * 8 * 128 * 4


@pytest.mark.parametrize("rows,W,lens", [(4, 1, (0, 13, 41, 7)),
                                         (1, 8, (11,)), (1, 16, (0,))])
def test_mla_kernel_matches_its_reference_at_ragged_lengths(
        monkeypatch, rows, W, lens):
    """The Pallas kernel through the interpreter against the jnp reference:
    a decode round with an idle row (length 0, table all scratch) and rows
    of 1 to 6 pages, and one-row prefill chunks at an offset and at 0; tiles
    and blocks small enough that every row walks more than one block."""
    monkeypatch.setattr(mla, "_ROWS", 16)
    monkeypatch.setattr(mla, "_BLOCK_TOKENS", 16)
    rng = np.random.default_rng(0)
    H, dl, dv, PL, B = 8, 128, 96, 8, 7
    P = 1 + rows * B
    arena = jnp.asarray(rng.normal(size=(P, PL, dl)), jnp.float32)
    tables = np.zeros((rows, B), np.int32)
    for s, n in enumerate(lens):
        if rows == 1 or n:  # an idle decode row keeps the scratch page
            used = -(-(n + W) // PL)
            tables[s, :used] = 1 + rng.permutation(P - 1)[:used]
    q = jnp.asarray(rng.normal(size=(rows, W, H, dl)), jnp.float32)
    args = (q, arena, jnp.asarray(tables), jnp.asarray(lens, jnp.int32))
    want = mla.mla_paged_attention(*args, dv=dv, scale=0.2, impl="reference")
    got = mla.mla_paged_attention(*args, dv=dv, scale=0.2, impl="interpret")
    assert got.shape == (rows, W, H, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_mla_kernel_resolves_through_the_registry(monkeypatch):
    from paddle_tpu.kernels import registry

    assert "mla_paged_attention" in registry.registry()
    assert registry.resolve("mla_paged_attention") == "reference"
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    assert registry.resolve("mla_paged_attention") == "interpret"


def _layer_weights(get, layer):
    return {k: get(k, layer) for k in
            ref.SHARED_KEYS + ref.EXPERT_KEYS + ("router",)}


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(tiny):
    """THE SHARE TEST. The routed result of each of the 4 shares of a layer
    (2 of its 8 experts each: the program's ``moe_held_experts_mlp`` and the
    reference told the same share), with what every chip computes alike —
    the shared expert — counted once, add up to what the uncut reference
    gives for the whole layer; and the shares' held pairs to all pairs."""
    cfg, _model, _params, get = tiny
    whole = dataclasses.asdict(cfg)
    layer, n = 1, 24
    w = _layer_weights(get, layer)
    u = jnp.asarray(np.random.default_rng(5).normal(size=(n, cfg.hidden_size)),
                    jnp.float32)
    want, pairs = ref.mlp_branch(u, w.__getitem__, whole, layer)
    assert pairs == n * cfg.num_experts_per_tok
    shared = ref._swiglu(u, *(w[k] for k in ref.SHARED_KEYS))
    program, reference, held = shared, shared, 0
    for first in range(0, 8, 2):
        mine = {k: w[k][first:first + 2] for k in ref.EXPERT_KEYS}
        y, stats = moe.moe_held_experts_mlp(
            u, w["router"], mine["experts_gate"], mine["experts_up"],
            mine["experts_down"], top_k=cfg.num_experts_per_tok, first=first,
            score="sigmoid", norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor)
        program = program + y
        held += int(stats["held"])
        assert int(stats["pairs"]) == pairs
        part, n_held = ref.mlp_branch(
            u, {**w, **mine}.__getitem__,
            dict(whole, n_routed_experts=2, router_experts=8,
                 held_experts_first=first), layer)
        assert n_held == int(stats["held"])
        reference = reference + (part - shared)
    assert held == pairs
    np.testing.assert_allclose(np.asarray(program), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(reference), np.asarray(want),
                               atol=2e-5)


def test_rows_that_hold_no_token_route_nowhere(tiny):
    cfg, _model, _params, get = tiny
    w = _layer_weights(get, 1)
    u = jnp.asarray(np.random.default_rng(6).normal(size=(6, cfg.hidden_size)),
                    jnp.float32)
    valid = jnp.asarray([True, False, True, True, False, False])
    y, stats = moe.moe_held_experts_mlp(
        u, w["router"], w["experts_gate"], w["experts_up"],
        w["experts_down"], top_k=2, first=0, valid=valid)
    assert int(stats["pairs"]) == int(stats["held"]) == 3 * 2
    assert not np.asarray(y)[~np.asarray(valid)].any()
    assert np.asarray(y)[np.asarray(valid)].any(axis=-1).all()


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_one_router_two_scores(score):
    """``_route`` is one router with an argument: top-k of the scores over
    ALL outputs, renormalised over the chosen, times the scale."""
    rng = np.random.default_rng(2)
    x, wg = rng.normal(size=(5, 16)), rng.normal(size=(16, 8))
    v, i, _aux = moe._route(jnp.asarray(x, jnp.float32),
                            jnp.asarray(wg, jnp.float32), 3, score=score,
                            scale=2.5)
    logits = x @ wg
    s = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True) \
        if score == "softmax" else 1 / (1 + np.exp(-logits))
    idx = np.argsort(-s, axis=-1)[:, :3]
    top = np.take_along_axis(s, idx, -1)
    np.testing.assert_array_equal(np.asarray(i), idx)
    np.testing.assert_allclose(np.asarray(v),
                               2.5 * top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="softmax.*sigmoid"):
        moe._route(jnp.zeros((1, 16)), jnp.zeros((16, 8)), 3, score="tanh")


def test_a_gpt2_prompt_past_the_largest_bucket_equals_its_one_shot_forward():
    """A stateless K/V model takes the same chunked path: 40 tokens through
    buckets of 8 / 16 (three window calls) give the tokens and logprobs of
    one 64-token prefill."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128, dtype="float32"))
    prompt = np.random.default_rng(4).integers(0, 64, 40)
    chunked = _engine(model)
    (full_c, lp_c), = _serve(chunked, [prompt], [6])
    one = _engine(model, prefill_buckets=(64,))
    (full_1, lp_1), = _serve(one, [prompt], [6])
    np.testing.assert_array_equal(full_c, full_1)
    np.testing.assert_allclose(lp_c, lp_1, atol=1e-5)
    assert chunked.stats()["counters"]["prefill_chunks_total"] == 3
    assert one.stats()["counters"]["prefill_chunks_total"] == 1
    # the filled share of the prefill programs' token-rows
    assert chunked.stats()["prefill_fill_rate"] == 1.0
    assert one.stats()["prefill_fill_rate"] == round(40 / 64, 4)


def test_a_trie_hit_then_a_chunked_suffix_equals_a_cold_one_shot_prefill():
    """The path a shared-prefix request takes through ``_admit``, with the
    chunk loop behind it: the trie serves the first 16 tokens' pages, the 40
    that follow are prefilled from ``start = 16`` as chunks of 16 / 16 / 8
    against them — to the tokens and logprobs of a cold engine's one 64-token
    prefill."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(1)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128, dtype="float32"))
    rng = np.random.default_rng(6)
    a = rng.integers(0, 64, 21)
    b = np.concatenate([a[:16], rng.integers(0, 64, 40)])
    warm = _engine(model, prefix_cache=True, max_slots=1)
    (_fa, _la), (fb, lb) = _serve(warm, [a, b], [2, 6])
    c = warm.stats()["counters"]
    assert c["prefix_hit_tokens"] == 16
    assert c["prefill_chunks_total"] == 2 + 3  # a: 16 + 5; b: its suffix
    (fc, lc), = _serve(_engine(model, prefill_buckets=(64,)), [b], [6])
    np.testing.assert_array_equal(fb, fc)
    np.testing.assert_allclose(lb, lc, atol=1e-5)


def test_submit_refuses_only_what_max_seq_len_cannot_hold(tiny):
    _cfg, model, _params, _get = tiny
    eng = _engine(model, max_seq_len=64)
    with eng:
        ok = eng.submit(np.arange(60) % 7, max_new_tokens=3)
        assert len(ok.result(timeout=120)) == 63
        bad = eng.submit(np.arange(62) % 7, max_new_tokens=3)
        with pytest.raises(serving.BadRequest, match="max_seq_len"):
            bad.result(timeout=5)


def test_a_recurrent_state_still_refuses_a_prompt_past_its_buckets():
    from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM

    paddle.seed(3)
    model = FalconH1ForCausalLM(FalconH1Config.tiny())
    eng = _engine(model, max_seq_len=64)
    fut = eng.submit(np.arange(20), max_new_tokens=2)
    with pytest.raises(serving.BadRequest, match="largest prefill bucket"):
        fut.result(timeout=5)
    eng.close()


@pytest.mark.parametrize("what", ["warm_pool_bytes", "export_kv_pages",
                                  "install_kv_pages"])
def test_what_a_latent_cache_cannot_take_yet_is_refused_in_words(tiny, what):
    _cfg, model, _params, _get = tiny
    if what == "warm_pool_bytes":
        with pytest.raises(ValueError, match="latent row.*warm tier"):
            _engine(model, prefix_cache=True, warm_pool_bytes=1 << 20)
        return
    eng = _engine(model, prefix_cache=True)  # the trie holds page ids: fine
    pages = [np.zeros((1, 8, 4, 12), np.float32)] * 3
    args = (np.arange(8),) if what == "export_kv_pages" \
        else (np.arange(8), pages, pages)
    with pytest.raises(RuntimeError, match="latent row.*wire format"):
        getattr(eng, what)(*args)
    eng.close()


def test_the_prefix_trie_serves_latent_pages(tiny):
    """The trie holds page ids and needs no change: a second prompt with the
    first's 16-token prefix prefills its suffix alone, to the same logprobs
    a cold engine gives."""
    cfg, model, _params, _get = tiny
    rng = np.random.default_rng(8)
    a = rng.integers(0, cfg.vocab_size, 21)
    b = np.concatenate([a[:16], rng.integers(0, cfg.vocab_size, 9)])
    warm = _engine(model, prefix_cache=True, max_slots=1)
    (_fa, _la), (fb, lb) = _serve(warm, [a, b], [2, 4])
    assert warm.stats()["counters"]["prefix_hit_tokens"] == 16
    (fc, lc), = _serve(_engine(model), [b], [4])
    np.testing.assert_array_equal(fb, fc)
    np.testing.assert_allclose(lb, lc, atol=1e-5)


def test_prefill_chunk_spans_sit_inside_admit(tiny):
    from paddle_tpu.observability.trace.request_trace import tracer

    _cfg, model, _params, _get = tiny
    eng = _engine(model)
    _serve(eng, [np.arange(1, 41) % 9], [2])
    rows = [r for r in tracer().worker_spans()]
    by_id = {r["id"]: r for r in rows}

    def under_admit(r):
        while r["parent"] in by_id:
            r = by_id[r["parent"]]
            if r["name"] == "pt.serve.admit":
                return True
        return False

    chunks = [r for r in rows if r["name"] == "pt.serve.prefill_chunk"]
    mine = [r for r in chunks if r["args"].get("W") in (8, 16)][-3:]
    assert [(r["args"]["start"], r["args"]["W"]) for r in mine] == \
        [(0, 16), (16, 16), (32, 8)]
    assert all(under_admit(r) for r in mine)


def test_the_next_program_goes_out_before_the_last_is_read(tiny):
    """Two short prompts decode; from the worker's own thread, between two
    of their rounds, a prompt of three chunks and two short ones arrive. Of
    the long prompt's calls the second and third go out behind the one
    before (a chunk needs nothing of the last on the host) and the two of the
    largest bucket each carry the running sequences' round, so nobody is owed
    one: the next waiting prompt's call goes out behind its last (a slot is
    free), and with every slot taken a round behind a prefill (the prompt's
    first token reaches that round on the device) or a round — until a
    request's budget frees a slot, where the worker reads before it decides.
    Logprobs are the reference's and every routed pair is counted once."""
    from paddle_tpu.observability.trace.request_trace import tracer

    cfg, model, _params, get = tiny
    rng = np.random.default_rng(7)
    lens, outs = (5, 7, 40, 5, 6), (12, 12, 3, 3, 3)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
    eng = _engine(model)
    late, seen = [], []

    def the_rest_arrive(_tok, _lp):
        seen.append(1)
        if len(seen) == 2:  # in the emit of the first round
            late.extend(eng.submit(p, max_new_tokens=n, return_logprobs=True)
                        for p, n in zip(prompts[2:], outs[2:]))

    eng.start = lambda: eng  # both queued before the worker's first turn
    futs = [eng.submit(p, max_new_tokens=n, return_logprobs=True,
                       on_token=None if i else the_rest_arrive)
            for i, (p, n) in enumerate(zip(prompts[:2], outs[:2]))]
    del eng.start
    with eng:
        done = [f.result(timeout=300) for f in futs]
        done += [f.result(timeout=300) for f in late]
        stats = eng.stats()
    for p, (full, lps) in zip(prompts, done):
        want = ref.next_token_logprobs(get, dataclasses.asdict(cfg), full, 64)
        np.testing.assert_allclose(lps, want[len(p) - 1:], atol=2e-4)
    c = stats["counters"]
    assert c["prefill_chunks_total"] == 1 + 1 + 3 + 1 + 1
    # behind a read: the second prompt's call (it waited, a slot was free);
    # chunks two and three; the fourth prompt's call; the round behind it
    # (no slot free) and the round behind that
    assert c["programs_run_ahead_total"] == 1 + 2 + 1 + 1 + 1
    rows = [r for r in tracer().worker_spans()
            if r["thread"].endswith(eng.name)]
    chunks = [r["args"] for r in rows if r["name"] == "pt.serve.prefill_chunk"]
    assert [(a["start"], a["ahead"]) for a in chunks] == [
        (0, 0), (0, 1), (0, 0), (16, 1), (32, 1), (0, 1), (0, 0)]
    # the long prompt's two 16-token calls carried the two running rows
    assert [a["carried"] for a in chunks] == [0, 0, 2, 2, 0, 0, 0]
    assert c["rounds_carried_total"] == 2
    assert stats["carried_round_rate"] == round(2 / c["decode_steps"], 4)
    rounds = [r["args"]["ahead"] for r in rows
              if r["name"] == "pt.serve.decode_round"]
    assert rounds == [0, 1, 1] + [0] * (c["decode_steps"] - 2 - 3)
    consumed = sum(lens) + sum(outs) - len(lens)
    experts_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    assert c["moe_pairs_total"] == c["moe_held_pairs_total"] == \
        consumed * cfg.num_experts_per_tok * experts_layers


def test_config_says_what_it_cannot_do():
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        OpenPanguMoEConfig()   # the published MTP module is not served
    with pytest.raises(ValueError, match="outside the router"):
        OpenPanguMoEConfig.tiny(n_routed_experts=4, router_experts=8,
                                held_experts_first=6)
    cfg = OpenPanguMoEConfig(num_nextn_predict_layers=0)
    assert (cfg.latent_dim, cfg.router_experts) == (576, 256)
    sm = cfg.served_model()
    assert sm.cache_spec == {"kind": "latent", "dim": 576, "value_dim": 512}
    shapes = sm.param_shapes()
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert abs(n / 1e9 - 719.09) < 0.01  # published: 718 B (MTP apart)


def test_the_benchmarks_reference_is_the_repos():
    with open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                           "openpangu_moe.py")) as f, \
            open(os.path.join(REPO, "benchmark", "lib",
                              "reference_openpangu_moe.py")) as g:
        assert f.read() == g.read()


@pytest.mark.parametrize("prompt_len,live", [
    (40, [0, 0, 0, 1, 1, 0, 2, 2, 0]), (12, [0, 1, 2]), (6, [0, 0, 0])])
def test_a_chunked_admission_holds_nobody(tiny, prompt_len, live):
    """Three prompts wait when the worker starts and are admitted back to
    back, chunked or not: a prompt of three chunks owes the running sequences
    no round, because its two calls of the largest bucket CARRIED one each
    (the second prompt's the first prompt's row, the third's both; the first
    prompt found nobody running and its calls carried no live row). A prompt
    that fits the largest bucket is one such call; prompts that fit a smaller
    bucket carry nothing, as ever."""
    from paddle_tpu.observability.trace.request_trace import tracer

    cfg, model, _params, _get = tiny
    eng = _engine(model)
    rng = np.random.default_rng(9)
    futs = [eng.submit(rng.integers(0, cfg.vocab_size, prompt_len),
                       max_new_tokens=6) for _ in range(3)]
    with eng:  # the worker starts with all three queued
        for f in futs:
            f.result(timeout=300)
        c = eng.stats()["counters"]
    rows = sorted((r for r in tracer().worker_spans()
                   if r["thread"].endswith(eng.name)), key=lambda r: r["t0"])
    names = [r["name"].rsplit(".", 1)[-1] for r in rows
             if r["name"] in ("pt.serve.admit", "pt.serve.decode_round")]
    admits = [i for i, n in enumerate(names) if n == "admit"]
    assert len(admits) == 3
    assert names[admits[0]:admits[2] + 1] == ["admit", "admit", "admit"]
    assert [r["args"]["carried"] for r in rows
            if r["name"] == "pt.serve.prefill_chunk"] == live
    assert c.get("rounds_carried_total", 0) == sum(1 for n in live if n)
    # every token but a request's first came from a round, carried or not
    assert c["tokens_total"] == c["slot_rounds"] == 3 * (6 - 1)
