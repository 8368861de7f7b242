"""Falcon-H1 (parallel Mamba-2 + attention block) at ``FalconH1Config.tiny()``
on seeded weights: the model, the engine's two caches and the one-step kernel
against the plain reference (``paddle_tpu/models/reference/falcon_h1.py``),
and GPT-2 through the served-model seam against the parent's hand-written
window step."""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
from paddle_tpu.models import falcon_h1 as fh
from paddle_tpu.models.reference import falcon_h1 as ref
from paddle_tpu.serving import generation as gen

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg_dict(cfg):
    return {k: list(v) if isinstance(v, tuple) else v
            for k, v in dataclasses.asdict(cfg).items()}


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = FalconH1Config.tiny()
    model = FalconH1ForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return cfg, model, params, get


def _engine(model, **over):
    kw = dict(max_slots=3, max_seq_len=64, page_len=8,
              prefill_buckets=(8, 16, 32), prefix_cache=False)
    kw.update(over)
    return serving.GenerationEngine(model, serving.GenerationConfig(**kw))


def _serve(eng, prompts, max_new):
    with eng:
        futs = [eng.submit(p, max_new_tokens=n, return_logprobs=True)
                for p, n in zip(prompts, max_new)]
        return [f.result(timeout=300) for f in futs]


def test_model_forward_matches_the_reference(tiny):
    cfg, model, _params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 21))
    out = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        want = np.asarray(ref.logits(get, _cfg_dict(cfg), ids[b]))
        np.testing.assert_allclose(out[b], want, atol=5e-5)
    assert np.std(want) > 1.0  # logits spread: an error would show


def test_engine_prefill_and_decode_match_the_reference(tiny):
    """Prefill (chunked scan, state install), then decode through both
    caches, against the reference's full forward: logprobs, not tokens."""
    cfg, model, _params, get = tiny
    rng = np.random.default_rng(1)
    lens, outs = (5, 13, 20, 8, 31), (9, 6, 12, 7, 5)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
    eng = _engine(model)
    for (full, lps), p, o in zip(_serve(eng, prompts, outs), lens, outs):
        assert len(full) == p + o and len(lps) == o
        want = ref.next_token_logprobs(get, _cfg_dict(cfg), full, 64,
                                       vocab_slices=3)
        np.testing.assert_allclose(lps, want[p - 1:], atol=2e-5)
    c = eng.stats()["counters"]
    assert c["state_installs_total"] == c["state_resets_total"] == 5
    assert eng.stats()["kv_pages"]["state_bytes"] == eng._state_pool_bytes() \
        == 2 * 3 * (4 * 8 * 16 * 4 + 3 * cfg.conv_dim * 4)


def test_a_slot_holds_the_references_final_state(tiny):
    """What a request leaves in its slot's row of both state arenas — the
    prefill's chunked scan installed, then one ``ssm_step`` a round — is the
    reference recurrence's state after every token but the last emitted;
    ``slot_state`` reads it from the closed engine (three requests, three
    slots: nobody's row is reused)."""
    cfg, model, _params, get = tiny
    rng = np.random.default_rng(5)
    lens, outs = (6, 19, 30), (11, 4, 7)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
    eng = _engine(model)
    done = _serve(eng, prompts, outs)
    for slot, (full, lps) in enumerate(done):
        _want, states = ref.next_token_logprobs(
            get, _cfg_dict(cfg), full, 64, vocab_slices=3, with_state=True)
        for got, want in zip(eng.slot_state(slot), states):
            assert float(jnp.abs(want["ssm"]).max()) > 1e-3
            np.testing.assert_allclose(got["ssm"], want["ssm"], atol=2e-5)
            np.testing.assert_allclose(got["conv"], want["conv"], atol=2e-5)
    with pytest.raises(ValueError, match="no recurrent state"):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        serving.GenerationEngine(GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=1,
            num_attention_heads=4, max_position_embeddings=64)),
            serving.GenerationConfig(max_slots=1, max_seq_len=32,
                                     page_len=8, prefill_buckets=(8,))
        ).slot_state(0)


def test_a_prefill_run_ahead_leaves_its_state_for_the_round_behind_it(tiny):
    """Three prompts wait for three slots. The second and third prompt's
    prefill calls go out before the one in front is read, each with the
    install of its final state dispatched behind it; the first round goes
    out behind the third call unread — the slot's row of both state arenas
    is the prefill's by the time the round steps it, on the device's own
    order — and two more rounds behind that, until a budget ends. Logprobs
    and the slots' final state are the reference's."""
    cfg, model, _params, get = tiny
    rng = np.random.default_rng(11)
    lens, outs = (6, 19, 30), (5, 4, 6)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
    eng = _engine(model)
    eng.start = lambda: eng  # all three queued before the worker's first turn
    futs = [eng.submit(p, max_new_tokens=n, return_logprobs=True)
            for p, n in zip(prompts, outs)]
    del eng.start
    with eng:
        done = [f.result(timeout=300) for f in futs]
    for slot, (p, (full, lps)) in enumerate(zip(lens, done)):
        want, states = ref.next_token_logprobs(
            get, _cfg_dict(cfg), full, 64, vocab_slices=3, with_state=True)
        np.testing.assert_allclose(lps, want[p - 1:], atol=2e-5)
        for got, st in zip(eng.slot_state(slot), states):
            np.testing.assert_allclose(got["ssm"], st["ssm"], atol=2e-5)
            np.testing.assert_allclose(got["conv"], st["conv"], atol=2e-5)
    c = eng.stats()["counters"]
    assert c["state_installs_total"] == 3
    # two prefill calls and three rounds went out behind an unread program;
    # the second request's budget ends with round three, so the worker reads
    # that before it decides
    assert c["programs_run_ahead_total"] == 2 + 3
    assert c["decode_steps"] == 5 and c["slot_rounds"] == 4 + 3 + 5


def test_rounds_ahead_of_a_bound_pool_leave_an_idle_rows_state_alone(tiny):
    """Four slots, a pool of six usable pages, four requests of three pages
    each, queued together: two run, two wait, two slots are free — the
    rounds go out ahead because the pool holds neither of those that wait,
    with idle rows for the free slots and, later, for the slots of requests
    that have ended. Every request is served in a slot of its own (the slot
    longest free goes first), so each one's final state can be read once
    the engine is closed: had a round sent ahead stepped an idle row, the
    rows of the two that ended first would have moved on from the
    reference's. Logprobs are the reference's too."""
    cfg, model, _params, get = tiny
    rng = np.random.default_rng(23)
    lens, outs = (6, 13, 10, 5), (14, 9, 12, 17)   # 3 pages of 8 each
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
    eng = _engine(model, max_slots=4, num_pages=7)
    eng.start = lambda: eng
    futs = [eng.submit(p, max_new_tokens=n, return_logprobs=True)
            for p, n in zip(prompts, outs)]
    del eng.start
    with eng:
        done = [f.result(timeout=300) for f in futs]
    assert [s for s, _t0, _t1, _n in sorted(eng._slot_hist,
                                            key=lambda h: h[1])] == \
        [0, 1, 2, 3]
    for slot, (p, (full, lps)) in enumerate(zip(lens, done)):
        want, states = ref.next_token_logprobs(
            get, _cfg_dict(cfg), full, 64, vocab_slices=3, with_state=True)
        np.testing.assert_allclose(lps, want[p - 1:], atol=2e-5)
        for got, st in zip(eng.slot_state(slot), states):
            np.testing.assert_allclose(got["ssm"], st["ssm"], atol=2e-5)
            np.testing.assert_allclose(got["conv"], st["conv"], atol=2e-5)
    c = eng.stats()["counters"]
    assert c["state_installs_total"] == 4
    # rounds 1-8 behind the second call and each other, until the second
    # request's budget ends; the third joins with that read, and rounds
    # 9-13 go out ahead of the fourth, until the first one's ends. Then
    # nobody waits and nothing goes out ahead
    assert c["rounds_ahead_pool_bound_total"] == 8 + 5
    assert c["programs_run_ahead_total"] == \
        1 + c["rounds_ahead_pool_bound_total"]


def _prefill(sm, params, prompt, W, B=8, PL=8):
    P = B + 1
    arena = [jnp.zeros((P, PL, sm.num_kv_heads, sm.head_dim), jnp.float32)
             for _ in range(sm.num_layers)]
    step = gen._build_window_step(sm, 1, B, PL, W, donate=False,
                                  label=f"t28:prefill{W}", prefill=True)
    tokens = np.zeros((1, W), np.int32)
    tokens[0, :len(prompt)] = prompt
    table = np.arange(1, B + 1, dtype=np.int32)[None]
    return step(params, arena, arena, jnp.asarray(table), jnp.asarray(tokens),
                jnp.zeros(1, jnp.int32),
                jnp.asarray([len(prompt)], jnp.int32))


def test_padded_bucket_gives_the_unpadded_state_and_logits(tiny):
    """A prompt in a larger bucket: the padding must not advance the
    recurrence nor reach the conv tail."""
    cfg, model, params, _get = tiny
    sm = model.served_model()
    prompt = np.random.default_rng(2).integers(1, cfg.vocab_size, 11)
    nxt_a, lp_a, _k, _v, st_a = _prefill(sm, params, prompt, 16)
    nxt_b, lp_b, _k, _v, st_b = _prefill(sm, params, prompt, 32)
    assert np.asarray(nxt_a).shape == (1, 1)  # the last real position only
    assert int(nxt_a[0, 0]) == int(nxt_b[0, 0])
    np.testing.assert_allclose(lp_a, lp_b, atol=1e-5)
    for la, lb in zip(st_a, st_b):
        np.testing.assert_allclose(la["ssm"], lb["ssm"], atol=1e-5)
        np.testing.assert_allclose(la["conv"], lb["conv"], atol=1e-5)
        assert float(jnp.abs(la["ssm"]).max()) > 0


def test_a_reused_slot_starts_from_zero_state(tiny):
    """One slot, two tenants: the second sees what it would on a fresh
    engine — nothing of the first's state."""
    cfg, model, _params, _get = tiny
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, cfg.vocab_size, 19), \
        rng.integers(0, cfg.vocab_size, 7)
    both = _serve(_engine(model, max_slots=1), [a, b], [6, 9])
    alone = _serve(_engine(model, max_slots=1), [b], [9])
    np.testing.assert_array_equal(both[1][0], alone[0][0])
    np.testing.assert_array_equal(both[1][1], alone[0][1])


def test_slots_admitted_in_different_rounds_keep_their_own_state(tiny):
    cfg, model, _params, _get = tiny
    rng = np.random.default_rng(4)
    a, b = rng.integers(0, cfg.vocab_size, 12), \
        rng.integers(0, cfg.vocab_size, 25)
    eng = _engine(model)
    with eng:
        seen = []
        fa = eng.submit(a, max_new_tokens=24, return_logprobs=True,
                        on_token=lambda *t: seen.append(t))
        while len(seen) < 5:  # a is decoding: b joins a later round
            pass
        fb = eng.submit(b, max_new_tokens=10, return_logprobs=True)
        ra, rb = fa.result(timeout=300), fb.result(timeout=300)
    sa, = _serve(_engine(model), [a], [24])
    sb, = _serve(_engine(model), [b], [10])
    for got, want in ((ra, sa), (rb, sb)):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_allclose(got[1], want[1], atol=1e-5)


def _recurrence_inputs(key, T, R, H, P, N, G):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (T, R, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, R, H)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    b = jax.random.normal(ks[3], (T, R, G, N))
    c = jax.random.normal(ks[4], (T, R, G, N))
    d = jax.random.normal(ks[5], (H,))
    return x, dt, a, b, c, d


def test_ssm_step_kernel_twin_and_reference_recurrence():
    """``pt_ssm_step`` through the Pallas interpreter, its jnp reference,
    and T steps of the reference's plain recurrence; a row whose ``dt`` is
    0 keeps its state bit for bit."""
    from paddle_tpu.kernels.pallas.ssm_step import ssm_step

    T, R, H, P, N, G = 5, 3, 4, 8, 16, 2
    x, dt, a, b, c, d = _recurrence_inputs(jax.random.key(28), T, R, H, P,
                                           N, G)
    dt = dt.at[:, 1].set(0.0)  # row 1 idles throughout
    s0 = jax.random.normal(jax.random.key(29), (R, H, P, N))
    s_i = s_c = s0
    for t in range(T):
        s_i, y_i = ssm_step(s_i, x[t], dt[t], a, b[t], c[t], d,
                            impl="interpret")
        s_c, y_c = ssm_step(s_c, x[t], dt[t], a, b[t], c[t], d,
                            impl="reference")
        np.testing.assert_allclose(y_i, y_c, atol=2e-5)
    np.testing.assert_allclose(s_i, s_c, atol=2e-5)
    np.testing.assert_array_equal(s_i[1], s0[1])
    # from a zero state, the reference's lax.scan over time
    s = jnp.zeros((R, H, P, N))
    ys = []
    for t in range(T):
        s, y = ssm_step(s, x[t], dt[t], a, b[t], c[t], d, impl="interpret")
        ys.append(y)
    rep = lambda m: jnp.repeat(m, H // G, axis=1)  # noqa: E731
    for r in range(R):
        y_ref, s_ref = ref.ssm_recurrence(x[:, r], dt[:, r], a,
                                          rep(b[:, r]), rep(c[:, r]), d)
        np.testing.assert_allclose(jnp.stack(ys)[:, r], y_ref, atol=2e-5)
        np.testing.assert_allclose(s[r], s_ref, atol=2e-5)


@pytest.mark.parametrize("n_valid", [5, 16, 19])
def test_chunked_scan_is_the_plain_recurrence(n_valid):
    """``ssd_chunked`` (chunks of 8, a window of 19: padded to 24) against
    the step-by-step recurrence, with the tail past ``n_valid`` masked by
    ``dt`` = 0: same outputs on the real positions, same FINAL state."""
    T, H, P, N, G = 19, 4, 8, 16, 2
    x, dt, a, b, c, d = _recurrence_inputs(jax.random.key(7), T, 1, H, P,
                                           N, G)
    dt = jnp.where(jnp.arange(T)[:, None, None] < n_valid, dt, 0.0)
    rep = lambda m: jnp.repeat(m, H // G, axis=2)  # noqa: E731
    tm = lambda m: jnp.swapaxes(m, 0, 1)           # noqa: E731
    y, s = fh.ssd_chunked(tm(x), tm(dt), a, tm(rep(b)), tm(rep(c)), 8)
    y_ref, _ = ref.ssm_recurrence(x[:, 0], dt[:, 0], a, rep(b)[:, 0],
                                  rep(c)[:, 0], jnp.zeros(H))
    _, s_ref = ref.ssm_recurrence(x[:n_valid, 0], dt[:n_valid, 0], a,
                                  rep(b)[:n_valid, 0], rep(c)[:n_valid, 0],
                                  jnp.zeros(H))
    np.testing.assert_allclose(y[0, :n_valid], y_ref[:n_valid], atol=5e-5)
    np.testing.assert_allclose(s[0], s_ref, atol=5e-5)


@pytest.mark.parametrize("what", ["prefix_cache", "draft_model",
                                  "export_kv_pages", "install_kv_pages"])
def test_what_a_recurrent_state_makes_wrong_is_refused_in_words(tiny, what):
    cfg, model, _params, _get = tiny
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="recurrent state.*prefix"):
            serving.GenerationEngine(model, serving.GenerationConfig(
                max_slots=2, max_seq_len=32, page_len=8,
                prefill_buckets=(8,)))  # the default asks for the trie
    elif what == "draft_model":
        with pytest.raises(ValueError, match="recurrent state.*speculative"):
            _engine(model, draft_model=model)
    else:
        eng = _engine(model)
        pages = [np.zeros((1, 8, 2, 8), np.float32)] * 2
        args = (np.arange(8),) if what == "export_kv_pages" \
            else (np.arange(8), pages, pages)
        with pytest.raises(RuntimeError, match="recurrent state"):
            getattr(eng, what)(*args)


def test_swap_weights_streams_the_seams_flat_names(tiny):
    cfg, model, params, _get = tiny
    flat = serving.served_model.flatten_params(params)
    assert "layers.1.in_w" in flat and "head" in flat
    assert set(k.split(".", 2)[-1] for k in flat if k.startswith("layers.")) \
        == set(fh.BLOCK_KEYS)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 9)
    eng = _engine(model)
    with eng:
        before = eng.submit(prompt, max_new_tokens=4,
                            return_logprobs=True).result(timeout=300)
        other = dict(flat)
        other["head"] = np.asarray(flat["head"])[:, ::-1].copy()
        assert eng.swap_weights(other) == 1
        after = eng.submit(prompt, max_new_tokens=4,
                           return_logprobs=True).result(timeout=300)
        with pytest.raises(ValueError, match="missing param"):
            eng.swap_weights({k: v for k, v in flat.items()
                              if k != "layers.0.A_log"})
    # the reversed head names the mirrored token with the same logprob
    assert int(after[0][9]) == cfg.vocab_size - 1 - int(before[0][9])
    np.testing.assert_allclose(after[1][0], before[1][0], atol=1e-6)


def test_state_install_span_sits_inside_admit(tiny):
    from paddle_tpu.observability.trace.request_trace import tracer

    cfg, model, _params, _get = tiny
    eng = _engine(model)
    _serve(eng, [np.arange(1, 10)], [3])
    rows = [r for r in tracer().worker_spans()
            if r["thread"].endswith(eng.name)]
    installs = [r for r in rows if r["name"] == "pt.serve.state_install"]
    assert installs
    by_id = {r["id"]: r for r in rows}

    def ancestors(r):
        while r["parent"] in by_id:
            r = by_id[r["parent"]]
            yield r["name"]

    # dispatched behind the prompt's last prefill call, inside that call's
    # span: a round that goes out ahead of the read finds the state there
    assert all(list(ancestors(r))[:3] == [
        "pt.serve.prefill_chunk", "pt.serve.prefill_dispatch",
        "pt.serve.admit"] for r in installs if r["parent"] in by_id)
    assert eng.stats()["state_pool_bytes"] == eng._state_pool_bytes() > 0


def test_the_benchmarks_reference_is_the_repos():
    with open(os.path.join(REPO, "paddle_tpu", "models", "reference",
                           "falcon_h1.py")) as f, \
            open(os.path.join(REPO, "benchmark", "lib",
                              "reference_falcon_h1.py")) as g:
        assert f.read() == g.read()


# -- GPT-2 through the seam against the parent's hand-written step -------------

def _parent_window_step(cfg, S, B, W, PL, gather):
    """``_build_window_step`` as it stood before the seam (PR 26), kept here
    as the oracle: GPT-2's block written out inside the engine."""
    from paddle_tpu.kernels.pallas.paged_attention import paged_attention

    nh = cfg.num_attention_heads
    hd = cfg.hidden_size // nh
    eps, scale, L = cfg.layer_norm_epsilon, 1.0 / math.sqrt(hd), B * PL

    def ln(x, w, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) * w + b

    def step(params, k_arenas, v_arenas, tables, tokens, lengths):
        P = k_arenas[0].shape[0]
        pos = lengths[:, None] + jnp.arange(W)
        pos_idx = jnp.minimum(pos, params["pos"].shape[0] - 1)
        x = params["embed"][tokens] + params["pos"][pos_idx]
        mask = jnp.arange(L)[None, None, :] <= pos[:, :, None]
        blk = pos // PL
        pidx = jnp.take_along_axis(tables, jnp.minimum(blk, B - 1), axis=1)
        pidx = jnp.where(blk < B, pidx, 0)
        flat = (pidx * PL + pos % PL).reshape(-1)
        new_k, new_v = [], []
        for p, kc, vc in zip(params["layers"], k_arenas, v_arenas):
            h1 = ln(x, p["ln1_w"], p["ln1_b"])
            qkv = (h1 @ p["qkv_w"] + p["qkv_b"]).reshape(S, W, 3, nh, hd)
            q, k1, v1 = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            kc = kc.reshape(P * PL, nh, hd).at[flat].set(
                k1.reshape(S * W, nh, hd)).reshape(P, PL, nh, hd)
            vc = vc.reshape(P * PL, nh, hd).at[flat].set(
                v1.reshape(S * W, nh, hd)).reshape(P, PL, nh, hd)
            if not gather:
                ctx = paged_attention(q, kc, vc, tables, pos, scale=scale)
            else:
                kk = kc[tables].reshape(S, L, nh, hd)
                vv = vc[tables].reshape(S, L, nh, hd)
                logits = jnp.einsum("swhd,sLhd->swhL", q, kk)
                logits = logits.astype(jnp.float32) * scale
                logits = jnp.where(mask[:, :, None, :], logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
                ctx = jnp.einsum("swhL,sLhd->swhd", probs, vv)
            x = x + (ctx.reshape(S, W, nh * hd) @ p["out_w"] + p["out_b"])
            h2 = ln(x, p["ln2_w"], p["ln2_b"])
            m = jax.nn.gelu(h2 @ p["fc_in_w"] + p["fc_in_b"],
                            approximate=True)
            x = x + (m @ p["fc_out_w"] + p["fc_out_b"])
            new_k.append(kc)
            new_v.append(vc)
        xf = ln(x, params["lnf_w"], params["lnf_b"])
        logits = xf @ params["embed"].T
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lf = logits.astype(jnp.float32)
        logp = jnp.max(lf, axis=-1) - jax.scipy.special.logsumexp(lf, axis=-1)
        return nxt, logp, new_k, new_v

    return step


@pytest.mark.parametrize("oracle", ["gather", "paged_attention"])
@pytest.mark.parametrize("rows,W", [(3, 1), (1, 16), (3, 4)])
def test_gpt2_through_the_seam_is_the_parents_program(rows, W, oracle):
    """Decode, one-row prefill and verify: bit-equal outputs to the parent's
    hand-written step, with its attention written out as gather-then-attend
    and through ``paged_attention``. The one difference in the program: the
    attention is one called function (XLA inlines it: the compiled program
    has the parent's instructions)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    dtype="float32")
    model = GPTForCausalLM(cfg)
    params = model.served_model().params(model)
    B, PL = 4, 8
    P = 3 * B + 1
    keys = jax.random.split(jax.random.key(1), 4)
    k0 = [jax.random.normal(k, (P, PL, 4, 8)) * 0.1 for k in keys[:2]]
    v0 = [jax.random.normal(k, (P, PL, 4, 8)) * 0.1 for k in keys[2:]]
    rng = np.random.default_rng(0)
    tables = rng.permutation(np.arange(1, P))[:rows * B].reshape(rows, B)
    args = (params, k0, v0, jnp.asarray(tables, jnp.int32),
            jnp.asarray(rng.integers(0, 64, (rows, W)), jnp.int32),
            jnp.asarray(rng.integers(0, 12, rows), jnp.int32))
    ours = gen._build_window_step(model.served_model(), rows, B, PL, W,
                                  donate=False, label=f"t28:seam:{oracle}")
    theirs = jax.jit(_parent_window_step(cfg, rows, B, W, PL,
                                         gather=oracle == "gather"))
    from paddle_tpu.jit import lowerable

    # ONE function the program calls once a layer (traced and lowered
    # once), not a copy a layer
    ours_text = lowerable(ours).lower(*args).as_text()
    assert ours_text.count("call @paged_attend") == cfg.num_hidden_layers
    assert len(re.findall(r"func\.func private @paged_attend\w*\(",
                          ours_text)) == 1
    for a, b in zip(jax.tree_util.tree_leaves(ours(*args)),
                    jax.tree_util.tree_leaves(theirs(*args))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# sha256 (first 16 hex) of the window programs' lowered text at the PARENT of
# PR 32 (commit 795d4c1, jax 0.9.0; regenerate with the body of the test from
# a checkout of the commit a later PR is held to)
_PARENT_TEXT = {
    ("gpt2", 3, 1, False): "29433d40c5de7d35",
    ("gpt2", 1, 8, True): "91dab69840d3029b",
    ("gpt2", 3, 3, False): "089ae77d6a015f59",
    ("falcon_h1", 3, 1, False): "eac7efc3b21685f6",
    ("falcon_h1", 1, 8, True): "f99df1e2fa730513",
}


@pytest.mark.parametrize("arch,rows,W,prefill", sorted(_PARENT_TEXT))
def test_window_programs_lower_to_the_parents_text(arch, rows, W, prefill):
    """PR 32 widened the seam (a latent cache, program counters, chunked
    prefill): GPT-2's and Falcon-H1's decode, verify and one-row prefill
    programs still lower to the parent's text letter for letter, so a
    compile-cache entry the parent wrote is still theirs."""
    import hashlib

    from paddle_tpu.jit import lowerable

    if arch == "gpt2":
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=64,
            dtype="float32"))
        kvh = 4
    else:
        paddle.seed(3)
        model = FalconH1ForCausalLM(FalconH1Config.tiny())
        kvh = 2
    sm = model.served_model()
    B, PL = 4, 8
    arenas = [jnp.zeros((3 * B + 1, PL, kvh, 8))] * 2
    state = None if prefill or sm.state_spec is None else [
        {name: jnp.zeros((rows,) + tuple(shape), dt)
         for name, (shape, dt) in sm.state_spec.items()} for _ in range(2)]
    args = (sm.params(model), arenas, arenas,
            jnp.zeros((rows, B), jnp.int32), jnp.zeros((rows, W), jnp.int32),
            jnp.zeros(rows, jnp.int32), jnp.ones(rows, jnp.int32), state)
    step = gen._build_window_step(sm, rows, B, PL, W, donate=False,
                                  label="t", prefill=prefill)
    text = lowerable(step).lower(*args).as_text()
    # since PR 37 a window program is named from its label (``jit_pt_t``
    # here) where every one was ``jit_step``: the name apart, the text is
    # still that parent's
    assert "module @jit_pt_t " in text
    text = text.replace("module @jit_pt_t ", "module @jit_step ", 1)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        _PARENT_TEXT[(arch, rows, W, prefill)]
