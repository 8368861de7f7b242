"""Brumby (power retention, degree 2): the three forms of one layer agree —
the reference's attention form (``models/reference/brumby.py``), the chunked
form (``retention_chunk``) and the recurrent form (``retention_step``) — on
seeded weights; the model through ``GenerationEngine`` with NOTHING paged,
prompts prefilled in chunks that resume the state, rounds between them; the
two kernels against their ``jnp`` forms under the Pallas interpreter; and
what the engine refuses for such a model, in words.

Tolerances. Everything here is float32 on the CPU, where the references'
matmuls are exact to rounding: two forms of one sum differ by its rounding
alone, ~1e-6 relative on sums of tens of terms, and the limits are 10-50 x
that (2e-5 .. 1e-4). A state kept in bfloat16 moves it by 4e-3 a write and a
dropped normaliser by O(1): both fail every comparison below by orders of
magnitude (``test_a_bfloat16_state_or_no_normaliser_fails``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.kernels.pallas import power_retention as pr
from paddle_tpu.models import (BrumbyConfig, BrumbyForCausalLM,
                               FalconH1Config, FalconH1ForCausalLM)
from paddle_tpu.models.reference import brumby as ref

F32 = jnp.float32
TOL = 5e-5


def _qkv(seed, W, H=4, Hk=2, d=16, rows=1):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s), F32)       # noqa: E731
    lg = jnp.log(jnp.asarray(rng.uniform(0.8, 0.999, (rows, W, Hk)), F32))
    return n(rows, W, H, d), n(rows, W, Hk, d), n(rows, W, Hk, d), lg


def _attention_form(Q, K, V, lg):
    """One row, float64: the definition."""
    Q, K, V, lg = (np.asarray(t, np.float64) for t in (Q, K, V, lg))
    W, H, d = Q.shape
    G = H // K.shape[1]
    cum = np.cumsum(lg, 0)
    out = np.zeros((W, H, V.shape[-1]))
    for h in range(H):
        kk = h // G
        w = (Q[:, h] @ K[:, kk].T / np.sqrt(d)) ** 2 * np.tril(
            np.exp(cum[:, None, kk] - cum[None, :, kk]))
        out[:, h] = w @ V[:, kk] / (w.sum(1, keepdims=True) + pr.EPS)
    return out


def _zero(rows, Hk, d):
    return (jnp.zeros((rows, Hk, pr.phi_dim(d), d), F32),
            jnp.zeros((rows, Hk, d, d), F32))


# -- phi -----------------------------------------------------------------------

@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_of_a_dot_phi_of_b_is_the_dot_squared(d):
    rng = np.random.default_rng(d)
    a, b = (jnp.asarray(rng.normal(size=d), F32) for _ in range(2))
    want = float(np.dot(a, b)) ** 2
    for phi in (pr.phi, ref.phi):
        got = float(jnp.dot(phi(a), phi(b), precision="highest"))
        assert abs(got - want) <= 1e-4 * max(1.0, want)
    assert pr.phi(a).shape == (pr.phi_dim(d),)
    assert ref.phi(a).shape == (d * (d + 1) // 2,)


def test_the_tiled_phi_is_five_percent_over_the_minimal_at_128():
    assert pr.phi_dim(128) == 8704 and 128 * 129 // 2 == 8256
    with pytest.raises(ValueError, match="multiple of 8"):
        pr.phi_dim(12)


def test_the_canonical_state_is_the_references_phi_of_the_same_sum():
    """``canonical_state`` maps the tiled ``S`` and the dense ``Z`` onto the
    minimal symmetric square: phi_min(k) v^T and phi_min(k) exactly."""
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.normal(size=16), F32) for _ in range(2))
    S, z = pr.canonical_state(pr.phi(k)[:, None] * v[None, :],
                              k[:, None] * k[None, :])
    np.testing.assert_allclose(S, ref.phi(k)[:, None] * v[None, :],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(z, ref.phi(k), rtol=1e-6, atol=1e-6)


# -- the three forms -----------------------------------------------------------

@pytest.mark.parametrize("c", [8, 16, 64])
def test_the_chunked_form_is_the_attention_form(c):
    """A window of 37 tokens — not a multiple of any inner chunk."""
    Q, K, V, lg = _qkv(1, 37)
    _S, _Z, Y = pr.retention_chunk(*_zero(1, 2, 16), Q, K, V, lg,
                                   jnp.ones((1, 37), bool), chunk=c,
                                   impl="reference")
    np.testing.assert_allclose(Y[0], _attention_form(Q[0], K[0], V[0], lg[0]),
                               rtol=TOL, atol=TOL)


def test_the_recurrent_form_is_the_chunked_form_outputs_and_state():
    Q, K, V, lg = _qkv(2, 29)
    S1, Z1, Y = pr.retention_chunk(*_zero(1, 2, 16), Q, K, V, lg,
                                   jnp.ones((1, 29), bool), chunk=8,
                                   impl="reference")
    S, Z = _zero(1, 2, 16)
    ys = []
    for t in range(29):
        S, Z, y = pr.retention_step(S, Z, Q[:, t], K[:, t], V[:, t], lg[:, t],
                                    jnp.ones((1,), bool), impl="reference")
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), Y, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(S, S1, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(Z, Z1, rtol=TOL, atol=TOL)


@pytest.mark.parametrize("cut", [8, 13, 20])
def test_a_window_in_two_calls_that_resume_is_the_window_in_one(cut):
    Q, K, V, lg = _qkv(3, 33)
    ones = jnp.ones((1, 33), bool)
    S1, Z1, Y = pr.retention_chunk(*_zero(1, 2, 16), Q, K, V, lg, ones,
                                   chunk=8, impl="reference")
    Sa, Za, Ya = pr.retention_chunk(
        *_zero(1, 2, 16), Q[:, :cut], K[:, :cut], V[:, :cut], lg[:, :cut],
        ones[:, :cut], chunk=8, impl="reference")
    Sb, Zb, Yb = pr.retention_chunk(
        Sa, Za, Q[:, cut:], K[:, cut:], V[:, cut:], lg[:, cut:],
        ones[:, cut:], chunk=8, impl="reference")
    np.testing.assert_allclose(jnp.concatenate([Ya, Yb], 1), Y, rtol=TOL,
                               atol=TOL)
    np.testing.assert_allclose(Sb, S1, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(Zb, Z1, rtol=TOL, atol=TOL)


def test_padded_positions_and_idle_rows_do_not_advance_the_state():
    Q, K, V, lg = _qkv(4, 24, rows=2)
    valid = jnp.arange(24)[None, :] < jnp.asarray([[17], [24]])
    S1, Z1, Y = pr.retention_chunk(*_zero(2, 2, 16), Q, K, V, lg, valid,
                                   chunk=8, impl="reference")
    Sa, Za, Ya = pr.retention_chunk(
        *_zero(1, 2, 16), Q[:1, :17], K[:1, :17], V[:1, :17], lg[:1, :17],
        jnp.ones((1, 17), bool), chunk=8, impl="reference")
    np.testing.assert_allclose(S1[:1], Sa, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(Z1[:1], Za, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(Y[:1, :17], Ya, rtol=TOL, atol=TOL)
    # a round: the idle row keeps its bytes and reads 0
    S2, Z2, y = pr.retention_step(S1, Z1, Q[:, 0], K[:, 0], V[:, 0], lg[:, 0],
                                  jnp.asarray([False, True]),
                                  impl="reference")
    assert bool(jnp.all(S2[0] == S1[0])) and bool(jnp.all(Z2[0] == Z1[0]))
    assert not bool(jnp.all(S2[1] == S1[1]))
    assert float(jnp.abs(y[0]).max()) == 0.0


# -- the kernels under the interpreter -----------------------------------------

@pytest.mark.parametrize("valid", [
    [True, False, True, True, False], [False, True, False, False, True],
    [False, False, True, False, False], [True] * 5, [False] * 5])
def test_the_step_kernel_is_its_reference_under_the_interpreter(valid):
    """Whatever rows a round advances — an idle row rides a neighbour's
    blocks and keeps its own bytes bit for bit, also the leading ones, also
    when no row is valid at all."""
    Q, K, V, lg = _qkv(5, 6, rows=5)
    S, Z, _Y = pr.retention_chunk(*_zero(5, 2, 16), Q, K, V, lg,
                                  jnp.ones((5, 6), bool), impl="reference")
    valid = jnp.asarray(valid)
    want = pr.retention_step(S, Z, Q[:, 0], K[:, 0], V[:, 0], lg[:, 0], valid,
                             impl="reference")
    got = pr.retention_step(S, Z, Q[:, 0], K[:, 0], V[:, 0], lg[:, 0], valid,
                            impl="interpret")
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=TOL, atol=TOL)
    idle = np.flatnonzero(~np.asarray(valid))
    assert bool(jnp.all(got[0][idle] == S[idle]))
    assert bool(jnp.all(got[1][idle] == Z[idle]))
    assert float(jnp.abs(got[2][idle]).max(initial=0.0)) == 0.0


def test_the_chunk_kernel_is_its_reference_under_the_interpreter():
    """From a given state, a padded tail, a window that is not a multiple of
    the inner chunk. (On the chip the MXU multiplies in bfloat16 and the
    outputs differ from the reference by 0.3 %: PERF.md section 6, PR 46.)"""
    Q, K, V, lg = _qkv(6, 37)
    S0, Z0, _Y = pr.retention_chunk(*_zero(1, 2, 16), *_qkv(7, 9),
                                    jnp.ones((1, 9), bool), impl="reference")
    valid = jnp.arange(37)[None, :] < 30
    want = pr.retention_chunk(S0, Z0, Q, K, V, lg, valid, chunk=16,
                              impl="reference")
    got = pr.retention_chunk(S0, Z0, Q, K, V, lg, valid, chunk=16,
                             impl="interpret")
    np.testing.assert_allclose(got[0], want[0], rtol=TOL, atol=TOL)
    np.testing.assert_allclose(got[1], want[1], rtol=TOL, atol=TOL)
    np.testing.assert_allclose(got[2][:, :30], want[2][:, :30], rtol=TOL,
                               atol=TOL)


def test_the_ops_refuse_a_state_that_is_not_float32():
    Q, K, V, lg = _qkv(8, 4)
    S, Z = _zero(1, 2, 16)
    with pytest.raises(ValueError, match="float32"):
        pr.retention_chunk(S.astype(jnp.bfloat16), Z, Q, K, V, lg,
                           jnp.ones((1, 4), bool))
    with pytest.raises(ValueError, match="float32"):
        pr.retention_step(S, Z.astype(jnp.bfloat16), Q[:, 0], K[:, 0],
                          V[:, 0], lg[:, 0], jnp.ones((1,), bool))


# -- the model -----------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = BrumbyConfig.tiny()
    model = BrumbyForCausalLM(cfg)
    model.eval()
    sm = model.served_model()
    params = sm.params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return cfg, model, sm, params, get, dataclasses.asdict(cfg)


def test_the_config_carries_the_published_keys_and_what_is_assumed():
    cfg = BrumbyConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim,
            cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.intermediate_size, cfg.vocab_size) == (
        40, 5120, 128, 40, 8, 17408, 151936)
    assert (cfg.retention_power, cfg.retention_eps) == (2, 1e-6)
    with pytest.raises(ValueError, match="degree 2"):
        BrumbyConfig.tiny(retention_power=4)
    sm = cfg.served_model()
    assert sm.cache_spec == {"kind": "none"} and sm.resumes_state
    assert not sm.carries_rounds
    assert sm.state_spec["S"][0] == (8, 8704, 128)
    assert sm.state_spec["z"][0] == (8, 128, 128)


def test_the_gates_draw_spans_a_memory_of_ten_to_a_thousand_tokens(tiny):
    """``e^lambda = sigmoid(gate_shift + gate_std n)``: most of it between 0.9
    and 0.999 on unit-scale inputs."""
    cfg, _model, _sm, params, _get, _c = tiny
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(4096, cfg.hidden_size)), F32)
    g = jax.nn.sigmoid(u @ params["layers"][0]["g_w"].astype(F32)
                       + cfg.gate_shift)
    lo, hi = np.quantile(np.asarray(g), [0.05, 0.95])
    assert 0.85 < lo < 0.95 and 0.995 < hi < 0.9999


def test_the_forward_is_the_references_logits(tiny):
    cfg, model, _sm, _params, get, c = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 21))
    out = np.asarray(model(paddle.to_tensor(ids)).data)
    for row in range(2):
        want = np.asarray(ref.logits(get, c, ids[row]))
        np.testing.assert_allclose(out[row], want, rtol=1e-4, atol=1e-4)


def _prefill(sm, params, ids, cuts):
    """The served blocks over ``ids`` in calls that end at ``cuts``, each
    handed the state the one before it left; returns the last position's
    hidden state and every layer's final state."""
    state, lo, x = None, 0, None
    for hi in cuts:
        n = hi - lo
        W = -(-n // 8) * 8                    # a padded bucket
        tokens = np.zeros((1, W), np.int32)
        tokens[0, :n] = ids[lo:hi]
        pos = lo + jnp.arange(W)[None, :]
        valid = jnp.arange(W)[None, :] < n
        x = sm.embed(params, jnp.asarray(tokens), pos)
        out = []
        for li, p in enumerate(params["layers"]):
            x, st, _counted = sm.block(
                p, x, pos, None, None if state is None else state[li], valid,
                step=False)
            out.append(st)
        state, lo, x = out, hi, x[:, n - 1]
    return x, state


def test_prefill_in_chunks_that_resume_is_prefill_in_one_call(tiny):
    cfg, _model, sm, params, get, c = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 45)
    x1, s1 = _prefill(sm, params, ids, [45])
    x3, s3 = _prefill(sm, params, ids, [16, 29, 45])
    np.testing.assert_allclose(x3, x1, rtol=1e-4, atol=1e-4)
    _y, states = ref.final_hidden(get, c, ids)
    for got1, got3, want in zip(s1, s3, states):
        for got in (got1, got3):
            canon = sm.reference_state({k: v[0] for k, v in got.items()})
            for k in ("S", "z"):
                scale = float(jnp.abs(want[k]).max())
                np.testing.assert_allclose(canon[k], want[k], rtol=1e-4,
                                           atol=1e-4 * scale)


def test_a_bfloat16_state_or_no_normaliser_fails(tiny):
    """The size of the two faults the limits above are set against: a state
    rounded to bfloat16 between two chunks, and the normaliser dropped."""
    cfg, _model, sm, params, _get, _c = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 45)
    x1, s1 = _prefill(sm, params, ids, [45])
    rounded = [{k: v.astype(jnp.bfloat16).astype(F32) for k, v in st.items()}
               for st in s1]
    err = max(float(jnp.abs(r[k] - s[k]).max() / jnp.abs(s[k]).max())
              for r, s in zip(rounded, s1) for k in r)
    assert err > 10 * 1e-4          # ten times the limits above
    Q, K, V, lg = _qkv(9, 12)
    _S, _Z, Y = pr.retention_chunk(*_zero(1, 2, 16), Q, K, V, lg,
                                   jnp.ones((1, 12), bool), impl="reference")
    Q, K, V, lg = (np.asarray(t[0], np.float64) for t in (Q, K, V, lg))
    w = (Q[:, 0] @ K[:, 0].T / 4.0) ** 2 * np.tril(
        np.exp(np.cumsum(lg, 0)[:, None, 0] - np.cumsum(lg, 0)[None, :, 0]))
    unnormed = w @ V[:, 0]
    assert np.abs(unnormed - np.asarray(Y[0, :, 0])).max() > 0.1


# -- through the engine --------------------------------------------------------

@pytest.fixture(scope="module")
def served(tiny):
    cfg, model, _sm, _params, get, c = tiny
    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=3, max_seq_len=128, prefill_buckets=(8, 16),
        prefix_cache=False, max_queue=16))
    eng.warmup()
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 37, 16, 50, 70)]
    futs = [eng.submit(p, max_new_tokens=6 + i, return_logprobs=True)
            for i, p in enumerate(prompts)]
    results = [f.result(timeout=300) for f in futs]
    stats = eng.stats()
    eng.close()
    return eng, prompts, results, stats


def test_prefill_then_decode_through_the_engine_is_the_references_forward(
        tiny, served):
    _cfg, _model, _sm, _params, get, c = tiny
    _eng, prompts, results, _stats = served
    for (full, lps), p in zip(results, prompts):
        assert len(full) == len(p) + len(lps)
        want = ref.next_token_logprobs(get, c, np.asarray(full), 256)
        np.testing.assert_allclose(lps, want[len(p) - 1:], rtol=0, atol=1e-4)


def test_the_engine_pages_nothing_and_counts_what_it_resumed(served):
    eng, prompts, results, stats = served
    c = stats["counters"]
    assert stats["kv_pages"]["cache"] == "none"
    assert stats["kv_pages"]["pool_bytes"] == 0
    assert stats["kv_pages"]["pages_live"] == stats["kv_pages"][
        "pages_peak"] == 0
    assert c["kv_pages_written_total"] == c["kv_rows_written_total"] == 0
    assert eng._pool.k == [] and eng._pool.v == []
    # 37 -> 3 calls, 50 -> 4, 70 -> 5 (buckets of 16), the others one
    assert c["prefill_chunks_total"] == 1 + 3 + 1 + 4 + 5
    assert c["state_resumes_total"] == 2 + 3 + 4
    assert c["state_installs_total"] == c["prefills_total"] == 5
    layers = 2
    assert c["retention_chunk_tokens_total"] == \
        layers * sum(len(p) for p in prompts)
    assert c["retention_steps_total"] == \
        layers * sum(len(lps) - 1 for _full, lps in results)
    assert c.get("rounds_carried_total", 0) == 0
    assert stats["kv_pages"]["state_bytes"] == eng._state_pool_bytes() > 0


def test_a_round_between_two_chunks_leaves_the_joining_slots_state_alone(tiny):
    """One sequence decodes while a long prompt joins: rounds go out between
    the prompt's chunks (``decode_steps`` moves while it is admitted) and
    both sequences come out as the reference says — a round that advanced the
    joining slot's row, or a chunk that did not resume, would show."""
    cfg, model, _sm, _params, get, c = tiny
    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=2, max_seq_len=160, prefill_buckets=(8,),
        prefix_cache=False, max_queue=16))
    eng.start()
    rng = np.random.default_rng(5)
    first = eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new_tokens=60,
                       return_logprobs=True)
    while eng.stats()["counters"].get("decode_steps", 0) < 2:
        pass
    before = eng.stats()["counters"]["decode_steps"]
    long = rng.integers(0, cfg.vocab_size, 64)           # 8 chunks of 8
    second = eng.submit(long, max_new_tokens=4, return_logprobs=True)
    out2, lp2 = second.result(timeout=300)
    out1, lp1 = first.result(timeout=300)
    counters = eng.stats()["counters"]
    eng.close()
    assert counters["state_resumes_total"] == 7
    # 7 rounds at least went between the 8 chunks while the first decoded
    assert counters["decode_steps"] - before >= 7
    for full, lps, n in ((out1, lp1, 6), (out2, lp2, 64)):
        want = ref.next_token_logprobs(get, c, np.asarray(full), 256)
        np.testing.assert_allclose(lps, want[n - 1:], rtol=0, atol=1e-4)


def test_the_final_state_in_the_slot_is_the_references(tiny):
    cfg, model, sm, _params, get, c = tiny
    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=1, max_seq_len=128, prefill_buckets=(8, 16),
        prefix_cache=False))
    eng.start()
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 41)
    full = eng.submit(prompt, max_new_tokens=5).result(timeout=300)
    eng.close()
    _lp, states = ref.next_token_logprobs(get, c, np.asarray(full), 256,
                                          with_state=True)
    for held, want in zip(eng.slot_state(0), states):
        got = sm.reference_state(held)
        for k in ("S", "z"):
            scale = float(jnp.abs(want[k]).max())
            np.testing.assert_allclose(got[k], want[k], rtol=1e-4,
                                       atol=1e-4 * scale)


# -- refusals, in words --------------------------------------------------------

def _engine(model, **kw):
    kw.setdefault("prefix_cache", False)
    return serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=2, max_seq_len=64, prefill_buckets=(8, 16), **kw))


def test_a_prefix_cache_is_refused(tiny):
    with pytest.raises(ValueError, match="no state to resume from"):
        _engine(tiny[1], prefix_cache=True)


def test_a_draft_model_is_refused(tiny):
    with pytest.raises(ValueError, match="cannot be rolled back"):
        _engine(tiny[1], draft_model=tiny[1])


def test_the_warm_tier_is_refused(tiny):
    with pytest.raises(ValueError, match="keeps no K/V pages at all"):
        _engine(tiny[1], warm_pool_bytes=1 << 20)


@pytest.mark.parametrize("call", ["export_kv_pages", "install_kv_pages"])
def test_page_export_and_install_are_refused(tiny, call):
    eng = _engine(tiny[1])
    args = (np.arange(8),) if call == "export_kv_pages" else \
        (np.arange(8), [], [])
    with pytest.raises(RuntimeError, match="keeps no K/V pages at all"):
        getattr(eng, call)(*args)
    eng.close()


def test_max_seq_len_past_the_position_table_is_refused(tiny):
    with pytest.raises(ValueError, match="exceeds the model's position"):
        serving.GenerationEngine(tiny[1], serving.GenerationConfig(
            max_slots=2, max_seq_len=512, prefill_buckets=(8,),
            prefix_cache=False))


def test_max_seq_len_bounds_positions_only(tiny):
    """No memory grows with ``max_seq_len`` where nothing is paged."""
    sizes = []
    for n in (32, 256):
        eng = serving.GenerationEngine(tiny[1], serving.GenerationConfig(
            max_slots=2, max_seq_len=n, prefill_buckets=(8,),
            prefix_cache=False))
        sizes.append((eng._kv_pool_bytes(), eng._state_pool_bytes()))
        eng.close()
    assert sizes[0] == sizes[1] and sizes[0][0] == 0
    eng = _engine(tiny[1])
    fut = eng.submit(np.arange(60), max_new_tokens=10)
    with pytest.raises(Exception, match="exceeds max_seq_len 64"):
        fut.result(timeout=5)
    eng.close()


def test_a_long_prompt_on_falcon_h1_is_still_refused_with_todays_words():
    paddle.seed(3)
    model = FalconH1ForCausalLM(FalconH1Config.tiny())
    model.eval()
    assert not model.served_model().resumes_state
    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=2, max_seq_len=64, page_len=4, prefill_buckets=(8, 16),
        prefix_cache=False))
    fut = eng.submit(np.arange(17) % 7, max_new_tokens=2)
    with pytest.raises(Exception, match="prompt length 17 exceeds the "
                       "largest prefill bucket 16"):
        fut.result(timeout=5)
    eng.close()


def test_a_cache_of_kind_none_needs_a_state():
    from paddle_tpu.serving.paged_kv import CacheLayout, PagedKVPool

    with pytest.raises(ValueError, match="would remember nothing"):
        CacheLayout.parse({"kind": "none"}, None, 2, 16, 2, 8)
    with pytest.raises(ValueError, match="no prefix cache and no warm tier"):
        PagedKVPool(CacheLayout.parse(
            {"kind": "none"}, {"S": ((2, 64, 8), F32)}, 2, 16, 2, 8),
            2, F32, prefix_cache=True, max_slots=2)


def test_the_two_reference_files_are_one():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "paddle_tpu", "models", "reference",
                           "brumby.py")) as f, \
            open(os.path.join(root, "benchmark", "lib",
                              "reference_brumby.py")) as g:
        assert f.read() == g.read()
