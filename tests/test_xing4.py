"""Xing4.0 (latent attention and 64-of-64 routed experts on a residual path of
four streams: manifold-constrained hyper-connections, Sinkhorn-mixed) at
``Xing4Config.tiny()`` on seeded weights: the model, the engine's latent
paged cache with chunked prefill and the carried step, the residual path's
kernel pair and what a wrong residual path would read, against the plain
reference (``paddle_tpu/models/reference/xing4.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.kernels.pallas import mhc
from paddle_tpu.models import Xing4Config, Xing4ForCausalLM
from paddle_tpu.models import xing4
from paddle_tpu.models.reference import xing4 as ref

PARITY = 2e-4      # the tolerance of every comparison with the reference


def _build(cfg, seed=3):
    paddle.seed(seed)
    model = Xing4ForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return model, params, get


@pytest.fixture(scope="module")
def tiny():
    cfg = Xing4Config.tiny()
    return (cfg,) + _build(cfg)


@pytest.fixture(scope="module")
def ids(tiny):
    return np.random.default_rng(0).integers(0, tiny[0].vocab_size, (2, 40))


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


def test_whole_sequence_forward_matches_the_reference(tiny, ids):
    """Absorbed MLA, the grouped-matmul experts and the packed maps against
    the non-absorbed reference with its Python-loop Sinkhorn; and the served
    blocks choose the reference's experts."""
    cfg, model, params, get = tiny
    out = np.asarray(model(paddle.to_tensor(ids)).numpy())
    for b in range(2):
        want = np.asarray(ref.logits(get, xing4.as_dict(cfg), ids[b]))
        assert np.abs(want).max() > 3          # logits spread over units
        np.testing.assert_allclose(out[b], want, atol=PARITY)
    _y, chosen = ref.final_hidden(get, xing4.as_dict(cfg), ids[0])
    mine = np.asarray(xing4.routed_experts(cfg, params, ids[0], block=8))
    assert chosen.shape == mine.shape == (2, 40, cfg.num_experts_per_tok)
    assert (np.sort(mine, -1) == np.sort(chosen, -1)).all()


@pytest.mark.parametrize("control", ["sinkhorn_iters_1", "h_res_identity"])
def test_a_wrong_residual_path_is_not_the_model(tiny, ids, control,
                                                monkeypatch):
    """The test-suite twin of the cell's controls (ii) and (iii): the
    reference with ONE Sinkhorn iteration, and with ``H_res = I``, each
    differ from the model by far more than the parity tolerance — the draw
    of the maps' parameters makes the path visible."""
    cfg, model, _params, get = tiny
    out = np.asarray(model(paddle.to_tensor(ids[:1])).numpy())[0]
    if control == "sinkhorn_iters_1":
        monkeypatch.setattr(ref, "SINKHORN_ITERS", 1)
    else:
        monkeypatch.setattr(ref, "H_RES_IDENTITY", True)
    wrong = np.asarray(ref.logits(get, xing4.as_dict(cfg), ids[0]))
    assert np.abs(out - wrong).max() > 1000 * PARITY


def test_chunked_prefill_the_carried_step_and_decode_match_the_reference(
        tiny):
    """Prompts of 1 to 4 chunks (buckets 8 / 16) go together through the
    engine: chunked prefill, rounds carried by the largest bucket's calls,
    decode through the latent cache — against the reference's ONE full
    forward over the engine's own output (logprobs, not tokens); the host's
    count of the residual path's mixes is what was served."""
    cfg, model, _params, get = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 40, 23, 50)]
    eng = _engine(model)
    outs = _serve(eng, prompts, [6, 4, 7, 5])
    for p, (full, lps) in zip(prompts, outs):
        want = ref.next_token_logprobs(get, xing4.as_dict(cfg), full, 64)
        np.testing.assert_allclose(lps, want[len(p) - 1:], atol=PARITY)
    st = eng.stats()
    c = st["counters"]
    assert c["prefill_chunks_total"] == 1 + 3 + 2 + 4
    assert c["rounds_carried_total"] > 0      # the carried step ran
    consumed = sum(len(p) for p in prompts) + (6 + 4 + 7 + 5) - 4
    experts_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    assert c["moe_pairs_total"] == c["moe_held_pairs_total"] == \
        consumed * cfg.num_experts_per_tok * experts_layers
    # two sublayers a layer mixed every consumed token once: padding and
    # idle rows are not counted
    assert c["mhc_mix_tokens_total"] == consumed * 2 * cfg.num_hidden_layers
    assert st["mhc_mix_tokens_per_s"] > 0
    assert st["kv_pool_bytes"] == eng._kv_pool_bytes() == \
        cfg.num_hidden_layers * eng._pool.num_pages * 8 * 128 * 4


def test_the_engine_sees_one_trailing_axis(tiny):
    """The seam: ``embed`` hands the engine ``[rows, W, hc_mult x hidden]``,
    ``head`` folds it; ``cache_spec`` is the plain latent one."""
    cfg, _model, params, _get = tiny
    sm = cfg.served_model()
    assert sm.cache_spec == {"kind": "latent", "dim": cfg.latent_dim,
                             "value_dim": cfg.kv_lora_rank}
    assert sm.carries_rounds
    x = sm.embed(params, jnp.zeros((2, 3), jnp.int32), None)
    assert x.shape == (2, 3, cfg.hc_mult * cfg.hidden_size)
    assert x.dtype == jnp.float32
    rows = np.asarray(x).reshape(2, 3, cfg.hc_mult, cfg.hidden_size)
    assert (rows == rows[:, :, :1]).all()      # replicate in
    assert sm.head(params, x).shape == (2, 3, cfg.vocab_size)
    assert sm.token_counters == {
        "mhc_mix_tokens_total": 2 * cfg.num_hidden_layers}


def _drawn(n, c, t, seed=0, a_res=xing4.A_RES):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (t, n * c), jnp.float32)
    g = 1 + 0.1 * jax.random.normal(k[1], (n * c,))
    phi = jax.random.normal(k[2], (n * c, 2 * n + n * n)) / np.sqrt(n * c)
    b = jnp.concatenate([
        xing4.B_GATES * jax.random.normal(k[4], (2 * n,)),
        (xing4.B_DIAG * jnp.eye(n) + xing4.B_STD
         * jax.random.normal(k[3], (n, n))).reshape(-1)])
    return x, g, phi, b, jnp.asarray([1.0, 0.7, a_res])


_KW = dict(n=4, iters=20, eps=1e-6, lo=-30.0, hi=30.0)


def test_h_res_is_doubly_stochastic_and_the_clamp_leaves_it(tiny):
    """For drawn inputs at the model's draw of the maps' parameters: rows and
    columns of ``H_res`` sum to 1 within 1e-4 after the 20 iterations (98
    tokens in 100; near a permutation the iteration is slow, and every token
    is within 5e-2), ONE iteration leaves the columns visibly off, and a
    clamp of +-30 changes nothing while one of +-1 does."""
    x, g, phi, b, a = _drawn(4, 64, 400)
    proj, bias = mhc.pack_params(g, phi, b, a, 4)
    _u, maps = mhc.mhc_pre(x, proj, bias, impl="reference", **_KW)
    h_pre, h_post, h_res = (np.asarray(m) for m in mhc.unpack_maps(maps, 4))
    assert np.abs(h_res.sum(-1) - 1).max() < 1e-4       # rows came last
    cols = np.abs(h_res.sum(-2) - 1).max(-1)
    assert np.quantile(cols, 0.98) < 1e-4 and cols.max() < 5e-2
    assert (h_res > 0).all() and ((0 < h_pre) & (h_pre < 1)).all()
    assert ((0 < h_post) & (h_post < 2)).all()
    assert h_res.std(0).min() > 1e-3          # token-dependent, every entry
    _u, once = mhc.mhc_pre(x, proj, bias, impl="reference",
                           **dict(_KW, iters=1))
    off = np.asarray(mhc.unpack_maps(once, 4)[2]).sum(-2)
    assert np.median(np.abs(off - 1).max(-1)) > 0.05
    _u, wide = mhc.mhc_pre(x, proj, bias, impl="reference",
                           **dict(_KW, lo=-1e9, hi=1e9))
    np.testing.assert_array_equal(np.asarray(wide), np.asarray(maps))
    _u, tight = mhc.mhc_pre(x, proj, bias, impl="reference",
                            **dict(_KW, lo=-1.0, hi=1.0))
    assert np.abs(np.asarray(tight) - np.asarray(maps)).max() > 0.05


@pytest.mark.parametrize("t", [24, 128, 131])
def test_the_kernel_pair_matches_its_jnp_reference(t):
    """``pt_mhc_pre`` / ``pt_mhc_post`` through the Pallas interpreter
    against the jnp reference AND against the equations written out, at
    whole tiles, part tiles and rows padded to a tile — a row of zeros (an
    idle decode row) included."""
    n, c = 4, 128
    x, g, phi, b, a = _drawn(n, c, t, seed=t, a_res=1.5)
    x = x.at[3].set(0.0)
    proj, bias = mhc.pack_params(g, phi, b, a, n)
    u0, m0 = mhc.mhc_pre(x, proj, bias, impl="reference", **_KW)
    u1, m1 = mhc.mhc_pre(x, proj, bias, impl="interpret", **_KW)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m0), atol=2e-5)
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u0), atol=5e-5)
    assert np.isfinite(np.asarray(m1)).all()
    # the equations, from the parameters as the reference file stores them
    with jax.default_matmul_precision("highest"):
        xh = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g
        z = xh @ phi
        h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
        h_post = 2 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
        m = ref.sinkhorn(jnp.exp(a[2] * z[:, 2 * n:].reshape(t, n, n)
                                 + b[2 * n:].reshape(n, n)), 20, 1e-6)
    got = mhc.unpack_maps(m1, n)
    for mine, want in zip(got, (h_pre, h_post, m)):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(want),
                                   atol=5e-5)
    y = jax.random.normal(jax.random.PRNGKey(9), (t, c))
    o0 = mhc.mhc_post(x, y, m0, n=n, impl="reference")
    o1 = mhc.mhc_post(x, y, m0, n=n, impl="interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), atol=2e-5)
    want = jnp.einsum("tij,tjc->tic", m, x.reshape(t, n, c)) \
        + h_post[:, :, None] * y[:, None, :]
    np.testing.assert_allclose(np.asarray(o1).reshape(t, n, c),
                               np.asarray(want), atol=2e-4)


def test_the_pair_resolves_through_the_registry(monkeypatch):
    from paddle_tpu.kernels import registry

    assert registry.resolve("mhc_pre") == registry.resolve("mhc_post") \
        == "reference"                                    # the CPU
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    assert registry.resolve("mhc_pre") == registry.resolve("mhc_post") \
        == "interpret"
    with pytest.raises(ValueError, match="power of two"):
        mhc.pack_params(jnp.ones(6), jnp.ones((6, 15)), jnp.ones(15),
                        jnp.ones(3), 3)


def test_the_model_runs_its_kernels_under_the_interpreter(monkeypatch):
    """The whole block with the REAL kernels (interpreted): the model's
    forward through ``resolve`` equals the reference too."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    cfg = Xing4Config.tiny(num_hidden_layers=2)
    model, _params, get = _build(cfg, seed=5)
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, (1, 16))
    out = np.asarray(model(paddle.to_tensor(ids)).numpy())[0]
    want = np.asarray(ref.logits(get, xing4.as_dict(cfg), ids[0]))
    np.testing.assert_allclose(out, want, atol=PARITY)


def test_config_says_what_it_cannot_do():
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        Xing4Config()   # the published MTP module is not served
    with pytest.raises(ValueError, match="yarn"):
        Xing4Config(num_nextn_predict_layers=0, rope_scaling={"type": "x"})
    cfg = Xing4Config(num_nextn_predict_layers=0)
    assert (cfg.latent_dim, cfg.stream_dim) == (576, 14336)
    assert abs(xing4.attn_scale(cfg) - 192 ** -0.5 * 1.41589 ** 2) < 1e-6
    sm = cfg.served_model()
    assert sm.cache_spec == {"kind": "latent", "dim": 576, "value_dim": 512}
    per_layer = [sum(int(np.prod(s)) for k, (s, _d)
                     in xing4.param_shapes(cfg, i).items())
                 for i in (0, 2)]
    # the issue's count: a dense layer 127.50 M + 0.72 M of maps, an expert
    # layer 744.29 M + 0.72 M
    assert abs(per_layer[0] / 1e6 - 128.22) < 0.02
    assert abs(per_layer[1] / 1e6 - 745.01) < 0.02
    total = 2 * per_layer[0] + 38 * per_layer[1] + 2 * 131072 * 3584 + 3584
    assert abs(total / 1e9 - 29.51) < 0.02    # published: 29 B (MTP apart)


def test_yarn_frequencies_are_lagunas_and_the_references_agree():
    """The model imports Laguna's YaRN; the reference computes its own: the
    two tables are one."""
    cfg = Xing4Config(num_nextn_predict_layers=0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 300, 2, 64))
    pos = (jnp.arange(300, dtype=jnp.int32) * 800)[None]   # to 240 k
    mine = xing4._rope_of(cfg)(x, pos)
    cos, sin = ref.rope_tables(xing4.as_dict(cfg), 300 * 800)
    want = ref._rope(x[0], cos[::800], sin[::800])
    np.testing.assert_allclose(np.asarray(mine[0]), np.asarray(want),
                               atol=2e-3)
    assert int(pos[0, -1]) > \
        cfg.rope_scaling["original_max_position_embeddings"]
