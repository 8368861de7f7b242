"""ZAYA1 (compressed convolutional attention — latent queries and keys mixed
over the sequence by two causal convolutions, a shifted value head — and a
top-1 expert sublayer routed by an MLP fed by the previous layer's router,
both under residual scaling) at ``Zaya1Config.tiny()`` on seeded weights: the
model, the engine's layers of TWO memories (K/V pages and a conv tail by
slot) with chunks that resume the tail and, in the largest bucket, CARRY the
running sequences' round, and the top-1 layer's held share, against the plain
reference (``paddle_tpu/models/reference/zaya1.py``: the convs as explicit
shifts)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import Zaya1Config, Zaya1ForCausalLM, zaya1
from paddle_tpu.models.reference import zaya1 as ref
from paddle_tpu.nn.layer import moe
from paddle_tpu.serving.served_model import Carried
from test_carried_step import _spans

PARITY = 2e-4      # the tolerance of every comparison with the reference


def _build(cfg, seed=3):
    paddle.seed(seed)
    model = Zaya1ForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return model, params, get


@pytest.fixture(scope="module")
def tiny():
    cfg = Zaya1Config.tiny()
    return (cfg, zaya1.as_dict(cfg)) + _build(cfg)


def _engine(model, carry=True, **over):
    kw = dict(max_slots=4, max_seq_len=128, page_len=4,
              prefill_buckets=(8, 12), prefix_cache=False)
    kw.update(over)
    eng = serving.GenerationEngine(model, serving.GenerationConfig(**kw))
    if not carry:
        # switched off at the call (no user-facing flag): every prefill
        # program is built row-only and a round goes out BETWEEN two chunks
        eng._carried_rows = lambda W: 0
    return eng


def _tail_close(got, want):
    scale = max(float(jnp.abs(want["tail"]).max()), 1e-6)
    np.testing.assert_allclose(np.asarray(got["tail"]),
                               np.asarray(want["tail"]), rtol=0,
                               atol=PARITY * scale)


# -- the model against the reference -------------------------------------------

def test_whole_sequence_forward_matches_the_reference(tiny):
    """The convs behind their tail, the grouped top-1 experts behind the
    router MLP and the dense attention against the reference's explicit
    shifts and its loop over experts: logits and the final tails (the
    routers' choices in one dense pass: ``tests/bench/test_cca_cells.py``)."""
    cfg, c, model, params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 43))
    out = np.asarray(model(paddle.to_tensor(ids)).numpy())
    for b in range(2):
        want = np.asarray(ref.logits(get, c, ids[b]))
        assert np.abs(want).max() > 3          # logits spread over units
        np.testing.assert_allclose(out[b], want, atol=PARITY)
    _y, chosen, tails = ref.final_hidden(get, c, ids[0])
    assert chosen.shape == (cfg.num_hidden_layers, 43, 1)
    # top-1 of 8 with no second choice: the draw spreads the tokens
    assert len(np.unique(chosen)) >= 6
    _logits, held = zaya1.forward_fn(
        cfg, params, params["embed"][jnp.asarray(ids[:1])], block=8)
    assert len(held) == len(tails) == cfg.num_hidden_layers
    for got, want in zip(held, tails):
        assert got["tail"].shape == (1, cfg.tail_dim)
        _tail_close({"tail": got["tail"][0]}, want)


@pytest.mark.parametrize("mechanism", ["qk_mean", "value_shift", "tau", "eda",
                                       "bias", "scales"])
def test_a_dropped_mechanism_moves_the_logits_beyond_the_tolerance(
        tiny, mechanism, monkeypatch):
    """Each mechanism in turn at its neutral value in the REFERENCE (the q-k
    mean 0, the second value head unshifted, tau 1, the depth averaging 0,
    the selection bias 0, the residual scales (1, 0, 1, 0)): the model's
    logits are then off by far more than the tolerance — the seeded draw
    leaves none of them where a check could not see it."""
    cfg, c, model, _params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 43)
    out = np.asarray(model(paddle.to_tensor(ids[None])).numpy())[0]
    monkeypatch.setattr(ref, "DROPPED", frozenset([mechanism]))
    without = np.asarray(ref.logits(get, c, ids))
    assert np.abs(out - without).max() > 100 * PARITY, mechanism


def test_the_selection_bias_is_drawn_wide_enough_to_be_seen():
    """At the published router widths (a softmax over 16 behind a 256-wide
    MLP, top-1) the drawn bias changes at least one choice in ten and no
    gate: a chosen expert's gate is its own probability."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    hid = jax.nn.gelu(jax.random.normal(k[0], (2048, 256)),
                      approximate=False)
    w3 = jax.random.normal(k[1], (256, 16)) / 16.0
    bias = zaya1.ROUTER_BIAS_STD * jax.random.normal(k[2], (16,))
    kw = dict(score="softmax", norm_topk=False)
    _v0, i0, _ = moe._route(hid, w3, 1, **kw)
    v1, i1, _ = moe._route(hid, w3, 1, bias=bias, **kw)
    same = float(jnp.mean(i0 == i1))
    assert 0.3 < same <= 0.9, same
    p = jax.nn.softmax(hid @ w3, -1)
    np.testing.assert_allclose(v1, jnp.take_along_axis(p, i1, -1), rtol=1e-5)


# -- the top-1 layer's held share ----------------------------------------------

def test_two_held_shares_of_the_top1_layer_add_up_to_the_whole():
    """The test the ``model-configs`` guide asks of a held share: two chips'
    shares of a 16-expert top-1 layer (``first`` 0 and 8) add up to the
    whole layer and to what the uncut reference gives — with ONE choice a
    token every pair is held by exactly one of the two."""
    cfg = Zaya1Config.tiny(num_experts=16)
    _model, params, _get = _build(cfg)
    c, p = zaya1.as_dict(cfg), params["layers"][1]
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(k[0], (1, 24, cfg.hidden_size))
    r_prev = jax.random.normal(k[1], (1, 24, cfg.router_hidden_size))
    hid, r = zaya1.router_hidden(cfg, p, x, r_prev)
    u = zaya1._rms(x, p["norm2"], cfg.rms_norm_eps)
    whole, stats = zaya1.routed_share(cfg, p, u, hid, None)
    assert int(stats["pairs"]) == int(stats["held"]) == 24
    parts, held = [], 0
    for first in (0, 8):
        share = {**p, **{name: p[name][first:first + 8] for name in
                         ("experts_gate", "experts_up", "experts_down")}}
        y, stats = zaya1.routed_share(cfg, share, u, hid, None, first=first)
        parts.append(y)
        held += int(stats["held"])
        assert int(stats["pairs"]) == 24
    assert held == 24                                # every pair held once
    assert float(jnp.abs(parts[0]).max()) > 0 < float(jnp.abs(parts[1]).max())
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-5)
    want, _chosen, want_r = ref.experts(x[0], r_prev[0],
                                        lambda name: p[name], c)
    np.testing.assert_allclose(
        zaya1._scaled(p["scale2"], x, parts[0] + parts[1])[0], want,
        atol=PARITY)
    np.testing.assert_allclose(r[0], want_r, atol=PARITY)
    # and the reference's own shares add up alike (what the scales add to
    # the stream itself counted once)
    halves = [ref.experts(x[0], r_prev[0], lambda name, lo=lo:
                          p[name][lo:lo + 8] if name.startswith("experts_")
                          else p[name], c, first=lo, count=8)[0]
              for lo in (0, 8)]
    s = p["scale2"]
    np.testing.assert_allclose(
        halves[0] + halves[1] - (s[0] * x[0] + s[1] + s[3]), want,
        atol=PARITY)


# -- the tail under ``valid`` and under a carry ---------------------------------

def test_valid_holds_the_tail_at_a_rows_last_real_token(tiny):
    """``_mix`` over a padded bucket leaves the tail of the row's LAST REAL
    token (not of the padding), a row with no real token (an idle slot of a
    round) keeps the tail it had, and a ``Carried`` pair is the chunk from
    its row and the round through the arenas, each as it would be alone."""
    cfg, _c, _model, params, _get = tiny
    p = params["layers"][0]
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    z = jax.random.normal(k[0], (1, 12, cfg.mix_dim))
    vv = jax.random.normal(k[1], (1, 12, 2 * cfg.head_dim))
    pos = jnp.arange(12)[None]
    real = (jnp.arange(12) < 7)[None]
    (q, kk, v), st = zaya1._mix(cfg, p, z, vv, pos, None, real, False)
    (q7, k7, v7), st7 = zaya1._mix(cfg, p, z[:, :7], vv[:, :7], pos[:, :7],
                                   None, real[:, :7], False)
    np.testing.assert_array_equal(st["tail"], st7["tail"])
    np.testing.assert_allclose(q[:, :7], q7, atol=1e-6)
    C, d = cfg.mix_dim, cfg.head_dim
    np.testing.assert_array_equal(st["tail"][0, :C], z[0, 6])
    np.testing.assert_array_equal(st["tail"][0, 2 * C:], vv[0, 6, d:])
    # the shifted head reads the previous token's projection
    np.testing.assert_array_equal(v[0, 1:, 1], vv[0, :-1, d:])
    np.testing.assert_array_equal(v[0, :, 0], vv[0, :, :d])
    # a round over arenas of 3 slots, the middle one idle
    arenas = {"tail": jax.random.normal(k[2], (3, cfg.tail_dim))}
    rz = jax.random.normal(k[3], (3, 1, cfg.mix_dim))
    rv = jax.random.normal(k[0], (3, 1, 2 * d))
    live = jnp.asarray([[True], [False], [True]])
    rpos = jnp.asarray([[9], [0], [30]])
    (rq, rk, rvv), after = zaya1._mix(cfg, p, rz, rv, rpos, arenas, live,
                                      True)
    np.testing.assert_array_equal(after["tail"][1], arenas["tail"][1])
    assert np.abs(after["tail"][0] - arenas["tail"][0]).max() > 0.1
    np.testing.assert_array_equal(rvv[:, 0, 1], arenas["tail"][:, 2 * C:])
    # the pair: one row of 12 + 3 tokens
    pair = Carried(st7, arenas, 12)
    both = lambda a, b: jnp.concatenate([a, jnp.swapaxes(b, 0, 1)], 1)
    (cq, ck, cv), (row, arenas2) = zaya1._mix(
        cfg, p, both(z, rz), both(vv, rv), both(pos, rpos), pair,
        both(real, live), False)
    (q2, k2, v2), st2 = zaya1._mix(cfg, p, z, vv, pos, st7, real, False)
    for got, alone, rnd in ((cq, q2, rq), (ck, k2, rk), (cv, v2, rvv)):
        np.testing.assert_allclose(got[:, :12], alone, atol=1e-6)
        np.testing.assert_allclose(got[0, 12:], rnd[:, 0], atol=1e-6)
    np.testing.assert_array_equal(row["tail"], st2["tail"])
    np.testing.assert_array_equal(arenas2["tail"], after["tail"])


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served(tiny):
    cfg, _c, model, _params, _get = tiny
    eng = _engine(model)
    eng.warmup()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n)
               for n in (5, 40, 23, 49, 17, 33)]
    # all six are queued before the worker's first turn: what rides which
    # call is then the schedule's and not the clock's
    eng.start = lambda: eng
    futs = [eng.submit(p, max_new_tokens=4 + i, return_logprobs=True)
            for i, p in enumerate(prompts)]
    del eng.start
    eng.start()
    results = [f.result(timeout=300) for f in futs]
    stats = eng.stats()
    eng.close()
    return eng, prompts, results, stats, \
        _spans(eng, "pt.serve.prefill_chunk"), \
        _spans(eng, "pt.serve.decode_round")


def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        tiny, served):
    _cfg, c, _model, _params, get = tiny
    _eng, prompts, results, _stats, _chunks, _rounds = served
    for (full, lps), p in zip(results, prompts):
        assert len(full) == len(p) + len(lps)
        want, _chosen, _st = ref.next_token_logprobs(get, c, np.asarray(full),
                                                     64)
        np.testing.assert_allclose(lps, want[len(p) - 1:], rtol=0,
                                   atol=PARITY)


def test_every_layer_keeps_pages_and_a_tail(tiny, served):
    """Three ``"full+state"`` layers: K/V arenas AND a state arena in each,
    counted under both kinds; admission counts pages as a full layer's; the
    served model carries rounds by the existing rule."""
    cfg, _c, _model, _params, _get = tiny
    eng, prompts, results, stats, chunks, rounds = served
    pool, kv = eng._pool, stats["kv_pages"]
    L = cfg.num_hidden_layers
    assert pool.layer_kinds == ["full+state"] * L
    assert eng._sm.carries_rounds and eng._sm.resumes_state
    assert kv["cache"] == "kv_by_layer"
    assert kv["layers_by_kind"] == {"full": L, "state": L}
    assert kv["arenas"] == {"kv": L, "state": L}
    assert pool.window_allocator is None
    one = pool.num_pages * cfg.num_key_value_heads * 4 * cfg.head_dim * 4
    assert kv["pool_bytes"] == eng._kv_pool_bytes() == 2 * one * L
    per_slot = 4 * cfg.tail_dim
    assert kv["state_bytes"] == eng._state_pool_bytes() == L * 4 * per_slot
    assert pool.bytes_by_kind() == {"full": 2 * one * L,
                                    "state": L * 4 * per_slot}
    assert pool.state[0]["tail"].shape == (4, cfg.tail_dim)
    need = sorted(-(-(len(p) + 4 + i) // 4) for i, p in enumerate(prompts))
    assert sum(need[:4]) <= kv["pages_peak"] <= sum(need[-4:])
    assert kv["alloc_total"] == sum(need)
    c = stats["counters"]
    # buckets of 8 and 12: 40 -> 4 calls, 23 -> 2, 49 -> 5, 17 -> 2, 33 -> 3
    assert c["prefill_chunks_total"] == 1 + 4 + 2 + 5 + 2 + 3
    assert c["state_resumes_total"] == 3 + 1 + 4 + 1 + 2
    assert c["state_installs_total"] == c["prefills_total"] == 6
    assert [a["W"] for a in chunks].count(12) == 3 + 2 + 4 + 1 + 3
    assert all(a["carried"] == 0 for a in chunks if a["W"] == 8)
    carried = [a["carried"] for a in chunks if a["carried"]]
    assert c["rounds_carried_total"] == len(carried) == 13
    assert c["decode_steps"] == len(carried) + len(rounds)
    assert c["tokens_total"] == c["slot_rounds"] == \
        sum(len(lps) - 1 for _full, lps in results)
    consumed = sum(len(p) for p in prompts) + \
        sum(len(lps) - 1 for _full, lps in results)
    # top-1, every expert held: one pair a (token, layer)
    assert c["moe_pairs_total"] == c["moe_held_pairs_total"] == consumed * L
    # every layer pages: each scored every cached key once
    assert c["attn_keys_full_total"] == L * (
        c["attn_keys_prefill_total"] + c["attn_keys_decode_total"])
    assert stats["kv_pool_bytes_by_kind"] == pool.bytes_by_kind()


def _one(model, prompt, new, **over):
    eng = _engine(model, **{"max_slots": 1, **over})
    with eng:
        full, lps = eng.submit(prompt, max_new_tokens=new,
                               return_logprobs=True).result(timeout=300)
    return eng, np.asarray(full), np.asarray(lps)


@pytest.mark.parametrize("carry", [True, False])
@pytest.mark.parametrize("bucket", [12, 1])
def test_a_prompt_in_chunks_is_the_prompt_whole(tiny, carry, bucket):
    """37 tokens through buckets of 12 (chunk edges at 12, 24, 36: the last
    call ONE token) and through buckets of ONE token (a boundary at every
    position: each conv and the value shift read the tail at every token)
    against the same prompt in one 64-token call: the same tokens, logprobs
    and final tail to float32 rounding, and both the reference's. ``carry``:
    every chunk is the CARRYING program's (idle slots' rows ride it) — or the
    row-only one's."""
    cfg, c, model, _params, get = tiny
    n = 37 if bucket == 12 else 9
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, n)
    chunked, full_c, lp_c = _one(model, prompt, 5, prefill_buckets=(bucket,),
                                 max_slots=3, carry=carry)
    assert chunked._carried_rows(bucket) == (3 if carry else 0)
    whole, full_w, lp_w = _one(model, prompt, 5, prefill_buckets=(64,),
                               max_seq_len=128, carry=False)
    cc, cw = (e.stats()["counters"] for e in (chunked, whole))
    calls = -(-n // bucket)
    assert cc["prefill_chunks_total"] == calls and \
        cc["state_resumes_total"] == calls - 1
    assert cw["prefill_chunks_total"] == 1 and \
        cw.get("state_resumes_total", 0) == 0
    assert (full_c == full_w).all()
    np.testing.assert_allclose(lp_c, lp_w, rtol=0, atol=2e-5)
    want, _chosen, tails = ref.next_token_logprobs(get, c, full_c, 64)
    np.testing.assert_allclose(lp_c, want[n - 1:], rtol=0, atol=PARITY)
    for a, b, r in zip(chunked.slot_state(0), whole.slot_state(0), tails):
        _tail_close(a, b)
        _tail_close(a, r)


@pytest.mark.parametrize("carry", [True, False])
def test_a_carried_round_is_a_round_of_its_own(tiny, carry):
    """One sequence decodes while a long prompt joins a slot whose last
    tenant left a tail behind: the running sequence's rounds RIDE the
    prompt's chunks (``carry``) or go out between them. Both come out as the
    reference says — a round that stepped the joining slot's tail, a chunk
    that started from zeros, or a round sent twice would show — and the
    joining slot's row of every arena is, at its install, bit for bit what
    it was at its join."""
    cfg, c, model, _params, get = tiny
    eng = _engine(model, carry=carry, max_slots=2, max_seq_len=160,
                  prefill_buckets=(8,), max_queue=16)
    assert eng._carried_rows(8) == (2 if carry else 0)
    rows, join, install = {}, eng._join, eng._install_state

    def row_of(slot_no):
        return [np.asarray(layer["tail"][slot_no])
                for layer in eng._pool.state]

    def joined(adm):
        join(adm)
        rows[adm.req] = [row_of(adm.slot_no)]

    def installed(slot_no, row):
        rows[eng._slots[slot_no].req].append(row_of(slot_no))
        install(slot_no, row)

    eng._join, eng._install_state = joined, installed
    eng.start()
    rng = np.random.default_rng(5)
    for f in [eng.submit(rng.integers(0, cfg.vocab_size, 9),
                         max_new_tokens=3) for _ in range(2)]:
        f.result(timeout=300)
    first = eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new_tokens=60,
                       return_logprobs=True)
    while eng.stats()["counters"].get("decode_steps", 0) < 6:
        pass
    long = rng.integers(0, cfg.vocab_size, 61)     # 8 chunks, the last of 5
    second = eng.submit(long, max_new_tokens=4, return_logprobs=True)
    out2, lp2 = second.result(timeout=300)
    out1, lp1 = first.result(timeout=300)
    counters = eng.stats()["counters"]
    eng.close()
    chunks = _spans(eng, "pt.serve.prefill_chunk")
    assert counters["state_resumes_total"] == 7 + 2     # (9 tokens: 2 calls)
    for full, lps, n in ((out1, lp1, 6), (out2, lp2, 61)):
        want, _ch, _st = ref.next_token_logprobs(get, c, np.asarray(full),
                                                 128)
        np.testing.assert_allclose(lps, want[n - 1:], rtol=0, atol=PARITY)
    at_join, at_install = next(v for req, v in rows.items()
                               if len(req.prompt) == 61)
    assert len(at_join) == cfg.num_hidden_layers
    for was, then in zip(at_join, at_install):
        assert np.abs(was).max() > 0
        np.testing.assert_array_equal(then, was)
    carried = [a["carried"] for a in chunks]
    assert carried[-8:] == ([1] * 8 if carry else [0] * 8)  # the long one's
    assert counters.get("rounds_carried_total", 0) == \
        sum(n > 0 for n in carried)
    assert counters["tokens_total"] == counters["slot_rounds"] == \
        2 + 2 + 59 + 3


def test_a_round_with_idle_slots_is_the_round_without_them(tiny):
    """One sequence in an engine of four slots: three rows of every round
    hold nothing. The attention kernel starts no walk for them (their own
    key's page is the scratch page) and hands back zeros, which nothing
    reads: the sequence's tokens and logprobs are those of an engine of ONE
    slot, and the reference's; the pages walked are the live row's alone."""
    cfg, c, model, _params, get = tiny
    prompt = np.random.default_rng(4).integers(0, cfg.vocab_size, 21)
    alone, full_a, lp_a = _one(model, prompt, 6, prefill_buckets=(64,),
                               carry=False)
    among, full_b, lp_b = _one(model, prompt, 6, prefill_buckets=(64,),
                               max_slots=4, carry=False)
    assert (full_a == full_b).all()
    np.testing.assert_allclose(lp_b, lp_a, rtol=0, atol=2e-5)
    want, _chosen, _st = ref.next_token_logprobs(get, c, full_b, 64)
    np.testing.assert_allclose(lp_b, want[len(prompt) - 1:], rtol=0,
                               atol=PARITY)
    ca, cb = (e.stats()["counters"] for e in (alone, among))
    rounds = cb["decode_steps"]
    assert rounds == ca["decode_steps"] == 5
    assert ca.get("attn_rows_idle_skipped_total", 0) == 0
    assert cb["attn_rows_idle_skipped_total"] == \
        3 * rounds * cfg.num_hidden_layers
    for name in ("attn_pages_walked_full_decode_total",
                 "attn_pages_in_range_full_decode_total"):
        assert cb[name] == ca[name] > 0


# -- refusals, in words ---------------------------------------------------------

def test_what_assumes_pages_of_kv_is_refused_in_words(tiny):
    """A layer of both memories is refused what a state layer is: a prefix's
    pages hold no tail."""
    model = tiny[2]
    with pytest.raises(ValueError, match="no state to resume from"):
        _engine(model, prefix_cache=True)
    with pytest.raises(ValueError, match="cannot be rolled back"):
        _engine(model, draft_model=model)
    with pytest.raises(ValueError, match="a prefix's state is in none"):
        _engine(model, warm_pool_bytes=1 << 20)
    eng = _engine(model)
    for call, args in (("export_kv_pages", (np.arange(8),)),
                       ("install_kv_pages", (np.arange(8), [], []))):
        with pytest.raises(RuntimeError, match="carries recurrent state"):
            getattr(eng, call)(*args)
    eng.close()


def test_a_layer_of_both_memories_is_a_declared_kind():
    from paddle_tpu.serving.paged_kv import (LAYER_KEEPS, CacheLayout,
                                             PagedKVPool)

    assert LAYER_KEEPS["full+state"] == ("full", True)
    spec = {"tail": ((10,), jnp.float32)}
    with pytest.raises(ValueError, match="exactly where the model declares"):
        CacheLayout.parse({"kind": "kv_by_layer",
                           "layers": ["full", "full+state"]}, None, 2, 4, 2, 8)
    pool = PagedKVPool(CacheLayout.parse(
        {"kind": "kv_by_layer",
         "layers": ["full+state", "none", "state", "full"]}, spec, 4, 4, 2, 8),
        8, jnp.float32, prefix_cache=False, max_slots=2)
    assert len(pool.k) == len(pool.v) == len(pool.state) == 2
    assert pool.state[0]["tail"].shape == (2, 10)
    assert pool.layers_by_kind() == {"full": 2, "state": 2, "none": 1}
    assert set(pool.bytes_by_kind()) == {"full", "state"}
