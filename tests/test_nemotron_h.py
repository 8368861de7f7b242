"""Nemotron-H (a stack whose layers are ONE mixer each: Mamba-2, ungated
relu^2 experts beside a shared one, or a grouped-query attention with no
rotary embedding) at ``NemotronHConfig.tiny()`` on seeded weights: the model,
the engine's memory by layer kind with chunks that resume the state-space
state and, in the largest bucket, CARRY the running sequences' round, and the
ungated expert layer's held share, against the plain reference
(``paddle_tpu/models/reference/nemotron_h.py``: the recurrence a token at a
time)."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
from paddle_tpu.models import falcon_h1, nemotron_h
from paddle_tpu.models.reference import nemotron_h as ref
from paddle_tpu.nn.layer import moe
from test_carried_step import _spans

PARITY = 2e-4      # the tolerance of every comparison with the reference


def _build(cfg, seed=3):
    paddle.seed(seed)
    model = NemotronHForCausalLM(cfg)
    model.eval()
    params = model.served_model().params(model)

    def get(name, layer):
        return params[name] if layer < 0 else params["layers"][layer][name]

    return model, params, get


@pytest.fixture(scope="module")
def tiny():
    cfg = NemotronHConfig.tiny()
    return (cfg, nemotron_h.as_dict(cfg)) + _build(cfg)


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




def _state_close(got, want):
    for k in ("ssm", "conv"):
        scale = max(float(jnp.abs(want[k]).max()), 1e-6)
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=0, atol=PARITY * scale)


# -- the model against the reference -------------------------------------------

def test_whole_sequence_forward_matches_the_reference(tiny):
    """The chunked scan, the two-matrix grouped experts and the dense
    attention against the reference's recurrence a token at a time and its
    loop over experts: logits, the routers' choices, the final states."""
    cfg, c, model, params, get = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 43))
    out = np.asarray(model(paddle.to_tensor(ids)).numpy())
    for b in range(2):
        want = np.asarray(ref.logits(get, c, ids[b]))
        assert np.abs(want).max() > 3          # logits spread over units
        np.testing.assert_allclose(out[b], want, atol=PARITY)
    _y, chosen, states = ref.final_hidden(get, c, ids[0])
    mine, held = nemotron_h.routed_experts(cfg, params, ids[0], block=8)
    assert chosen.shape == mine.shape == (2, 43, cfg.num_experts_per_tok)
    assert (np.sort(np.asarray(mine), -1) == np.sort(chosen, -1)).all()
    assert len(held) == len(states) == 3
    for got, want in zip(held, states):
        _state_close({k: v[0] for k, v in got.items()}, want)


def test_the_selection_bias_is_drawn_wide_enough_to_be_seen(tiny):
    """The draw exercises the mechanism: at the published router width (128
    experts, 6 a token) the bias changes at least one choice in ten, and it
    changes no gate (a chosen expert's gate is its own score) — and the tiny
    model's own routers choose differently without it."""
    cfg, c, _model, _params, get = tiny
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    u = jax.random.normal(k[0], (512, 64))
    wr = jax.random.normal(k[1], (64, 128)) / 8.0
    bias = nemotron_h.ROUTER_BIAS_STD * jax.random.normal(k[2], (128,))
    kw = dict(score="sigmoid", norm_topk=False)
    v0, i0, _ = moe._route(u, wr, 6, **kw)
    v1, i1, _ = moe._route(u, wr, 6, bias=bias, **kw)
    same = np.mean([len(set(a) & set(b)) for a, b in zip(
        np.asarray(i0).tolist(), np.asarray(i1).tolist())]) / 6
    assert 0.5 < same <= 0.9, same
    s = jax.nn.sigmoid(u @ wr)
    np.testing.assert_allclose(v1, jnp.take_along_axis(s, i1, -1), rtol=1e-5)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 43)
    _y, chosen, _st = ref.final_hidden(get, c, ids)

    def unbiased(name, layer):
        w = get(name, layer)
        return jnp.zeros_like(w) if name == "router_bias" else w

    _y, plain, _st = ref.final_hidden(unbiased, c, ids)
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any(-1).mean() > 0.1


# -- the ungated expert layer's held share -------------------------------------

def test_two_held_shares_of_the_ungated_layer_add_up_to_the_whole(tiny):
    """The test the ``model-configs`` guide asks of a held share: two chips'
    shares of the relu^2 layer (experts 0-3 and 4-7 of 8), with what every
    chip computes alike — the shared expert — counted once, add up to what
    the uncut reference gives for the whole layer."""
    cfg, c, _model, params, _get = tiny
    p = params["layers"][1]
    assert "router" in p
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.hidden_size))
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg.layer_norm_epsilon)
    parts, pairs = [], 0
    for first in (0, 4):
        y, stats = moe.moe_held_experts_mlp(
            u, p["router"], None, p["experts_up"][first:first + 4],
            p["experts_down"][first:first + 4],
            top_k=cfg.num_experts_per_tok, first=first, score="sigmoid",
            norm_topk=True, scale=cfg.routed_scaling_factor,
            bias=p["router_bias"])
        parts.append(y)
        pairs += int(stats["held"])
        assert int(stats["pairs"]) == 24 * cfg.num_experts_per_tok
    assert pairs == 24 * cfg.num_experts_per_tok    # every pair held once
    assert float(jnp.abs(parts[0]).max()) > 0 < float(jnp.abs(parts[1]).max())
    shared = nemotron_h._relu2(u, p["shared_up"], p["shared_down"])
    want, _chosen = ref.experts(x, lambda name: p[name], c)
    np.testing.assert_allclose(x + parts[0] + parts[1] + shared, want,
                               atol=PARITY)
    # and the reference's own shares add up alike
    halves = [ref.experts(x, lambda name, lo=lo: p[name][lo:lo + 4]
                          if name.startswith("experts_") else p[name], c,
                          first=lo, count=4, shared=lo == 0)[0] - x
              for lo in (0, 4)]
    np.testing.assert_allclose(x + halves[0] + halves[1], want, atol=PARITY)


def _digest(fn, *args):
    return hashlib.sha256(jax.jit(fn).lower(*args).as_text().encode()) \
        .hexdigest()[:16]


def test_the_gated_callers_program_is_the_parents():
    """An expert WITH a gate matrix runs what it ran: the lowered text of a
    gated call is the one this PR's parent lowered (d812f63, jax 0.9.0), and
    its result is ``silu(gate) x up`` through ``down``."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(k[0], (16, 32))
    wr = jax.random.normal(k[1], (32, 8))
    wg, wu = (jax.random.normal(kk, (8, 32, 16)) / 6 for kk in k[2:4])
    wd = jax.random.normal(k[4], (8, 16, 32)) / 4

    def gated(x, wr, wg, wu, wd):
        return moe.moe_held_experts_mlp(x, wr, wg, wu, wd, top_k=2, first=0)

    assert _digest(gated, x, wr, wg, wu, wd) == "e91df7e023cdfaf9"
    y, _stats = gated(x, wr, wg, wu, wd)
    gates, idx, _aux = moe._route(x, wr, 2, score="sigmoid",
                                  precision=jax.lax.Precision.HIGHEST)
    want = sum(gates[:, j, None] * jnp.einsum(
        "ti,tih->th", jax.nn.silu(jnp.einsum("th,thi->ti", x, wg[idx[:, j]]))
        * jnp.einsum("th,thi->ti", x, wu[idx[:, j]]), wd[idx[:, j]])
        for j in range(2))
    np.testing.assert_allclose(y, want, atol=1e-4)


def test_the_chunked_scan_from_none_is_the_scan_from_zero():
    """``ssd_chunked`` from a given state: a window split in two at an edge
    off the chunk size equals the window whole, and ``initial=None`` lowers
    to the text it lowered to without the argument (Falcon-H1's programs are
    pinned by hash in ``tests/test_falcon_h1.py``)."""
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    R, W, H, P, N = 2, 29, 4, 8, 16
    x = jax.random.normal(k[0], (R, W, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (R, W, H)))
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b, c = (jax.random.normal(kk, (R, W, H, N)) for kk in k[3:])
    y, s = falcon_h1.ssd_chunked(x, dt, a, b, c, 8)
    cut = 13
    y1, s1 = falcon_h1.ssd_chunked(x[:, :cut], dt[:, :cut], a, b[:, :cut],
                                   c[:, :cut], 8)
    y2, s2 = falcon_h1.ssd_chunked(x[:, cut:], dt[:, cut:], a, b[:, cut:],
                                   c[:, cut:], 8, s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=1e-4)
    np.testing.assert_allclose(s2, s, atol=1e-4)
    zero = jnp.zeros_like(s)
    y0, s0 = falcon_h1.ssd_chunked(x, dt, a, b, c, 8, zero)
    np.testing.assert_allclose(y0, y, atol=1e-5)
    np.testing.assert_allclose(s0, s, atol=1e-5)


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


def test_prefill_then_decode_through_the_by_layer_pool_is_the_references_forward(
        tiny, served):
    _cfg, c, _model, _params, get = tiny
    _eng, prompts, results, _stats, _chunks, _rounds = served
    for (full, lps), p in zip(results, prompts):
        assert len(full) == len(p) + len(lps)
        want, _chosen, _st = ref.next_token_logprobs(get, c, np.asarray(full),
                                                     64)
        np.testing.assert_allclose(lps, want[len(p) - 1:], rtol=0,
                                   atol=PARITY)


def test_the_pool_keeps_memory_by_layer_kind(tiny, served):
    """``M E M * E M``: K/V arenas on the one attention layer, state arenas
    on the three Mamba-2 layers, nothing on the expert layers; admission
    counts pages for the attention layer alone."""
    cfg, _c, _model, _params, _get = tiny
    eng, prompts, results, stats, chunks, rounds = served
    pool, kv = eng._pool, stats["kv_pages"]
    assert pool.layer_kinds == ["state", "none", "state", "full", "none",
                                "state"]
    assert kv["cache"] == "kv_by_layer"
    assert kv["layers_by_kind"] == {"state": 3, "none": 2, "full": 1}
    assert kv["arenas"] == {"kv": 1, "state": 3}
    assert pool.window_allocator is None
    # one layer's K and V: [pages, kv heads, page_len, head_dim] float32
    one = pool.num_pages * cfg.num_key_value_heads * 4 * cfg.head_dim * 4
    assert kv["pool_bytes"] == eng._kv_pool_bytes() == 2 * one
    per_slot = 4 * (cfg.mamba_num_heads * cfg.mamba_head_dim
                    * cfg.ssm_state_size
                    + (cfg.conv_kernel - 1) * cfg.conv_dim)
    assert kv["state_bytes"] == eng._state_pool_bytes() == 3 * 4 * per_slot
    assert pool.bytes_by_kind() == {"full": 2 * one, "state": 12 * per_slot}
    assert pool.live_pages_by_kind() == {"full": 0}
    # a request holds ceil((prompt + new) / page_len) pages, whatever the
    # number of layers: 4 slots' worth at the peak
    need = sorted(-(-(len(p) + 4 + i) // 4) for i, p in enumerate(prompts))
    assert sum(need[:4]) <= kv["pages_peak"] <= sum(need[-4:])
    assert kv["alloc_total"] == sum(need)
    c = stats["counters"]
    # buckets of 8 and 12: 40 -> 4 calls, 23 -> 2, 49 -> 5, 17 -> 2, 33 -> 3
    assert c["prefill_chunks_total"] == 1 + 4 + 2 + 5 + 2 + 3
    assert c["state_resumes_total"] == 3 + 1 + 4 + 1 + 2
    assert c["state_installs_total"] == c["prefills_total"] == 6
    # every call of the largest bucket is the carrying program's — 40 -> 3,
    # 23 -> 2 (its remainder of 11 too), 49 -> 4, 17 -> 1, 33 -> 3 — and with
    # the six queued behind one another each found a sequence running: a
    # round rode it, and no round went out between two chunks
    assert [a["W"] for a in chunks].count(12) == 3 + 2 + 4 + 1 + 3
    assert all(a["carried"] == 0 for a in chunks if a["W"] == 8)
    carried = [a["carried"] for a in chunks if a["carried"]]
    assert c["rounds_carried_total"] == len(carried) == 13
    assert c["decode_steps"] == len(carried) + len(rounds)
    assert c["slot_rounds"] == sum(carried) + sum(a["n_active"]
                                                  for a in rounds)
    assert c["tokens_total"] == c["slot_rounds"] == \
        sum(len(lps) - 1 for _full, lps in results)
    consumed = sum(len(p) for p in prompts) + \
        sum(len(lps) - 1 for _full, lps in results)
    assert c["moe_pairs_total"] == c["moe_held_pairs_total"] == \
        consumed * cfg.num_experts_per_tok * 2
    # the one full layer scored every cached key once
    assert c["attn_keys_full_total"] == \
        c["attn_keys_prefill_total"] + c["attn_keys_decode_total"]
    assert "attn_keys_window_total" not in c
    # the two gauges of a cache that declares its layers' kinds
    assert stats["kv_pool_bytes_by_kind"] == pool.bytes_by_kind()
    assert stats["kv_pages_live_by_kind"] == {"full": 0}


def _one(model, prompt, new, **over):
    eng = _engine(model, **{"max_slots": 1, **over})
    with eng:
        full, lps = eng.submit(prompt, max_new_tokens=new,
                               return_logprobs=True).result(timeout=300)
    return eng, np.asarray(full), np.asarray(lps)


@pytest.mark.parametrize("carry", [True, False])
def test_a_prompt_in_chunks_is_the_prompt_whole(tiny, carry):
    """37 tokens through buckets of 12 — chunk edges at 12, 24, 36: off the
    scan's chunks of 8, the last call ONE token — against the same prompt in
    one 64-token call: the same tokens, logprobs and final state (SSM state
    and conv tail) to float32 rounding, and both the reference's. ``carry``:
    every chunk is the CARRYING program's (two idle slots' rows ride it, and
    the joining slot's own has no live row) — or the row-only one's."""
    cfg, c, model, _params, get = tiny
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 37)
    chunked, full_c, lp_c = _one(model, prompt, 5, prefill_buckets=(12,),
                                 max_slots=3, carry=carry)
    assert chunked._carried_rows(12) == (3 if carry else 0)
    assert chunked._sm.carries_rounds
    whole, full_w, lp_w = _one(model, prompt, 5, prefill_buckets=(64,),
                               max_seq_len=128, carry=False)
    cc, cw = (e.stats()["counters"] for e in (chunked, whole))
    assert cc["prefill_chunks_total"] == 4 and cc["state_resumes_total"] == 3
    assert cw["prefill_chunks_total"] == 1 and \
        cw.get("state_resumes_total", 0) == 0
    assert (full_c == full_w).all()
    np.testing.assert_allclose(lp_c, lp_w, rtol=0, atol=2e-5)
    want, _chosen, states = ref.next_token_logprobs(get, c, full_c, 64)
    np.testing.assert_allclose(lp_c, want[36:], rtol=0, atol=PARITY)
    for a, b, r in zip(chunked.slot_state(0), whole.slot_state(0), states):
        _state_close(a, b)
        _state_close(a, r)


@pytest.mark.parametrize("carry", [True, False])
def test_a_round_between_two_chunks_leaves_the_joining_slots_state_alone(
        tiny, carry):
    """One sequence decodes while a long prompt joins a slot whose last
    tenant left a state behind: the running sequence's rounds RIDE the
    prompt's chunks (``carry``: the one bucket is the largest) or go out
    between them (the carry switched off at the call: what a state model
    that does not qualify gets). Both come out as the reference says — a
    round that advanced the joining slot's row or tail, a chunk that started
    from zero, or a round sent twice would show — and the joining slot's row
    of every arena is, at its install, bit for bit what it was at its join;
    every decode step is counted once, carried or not."""
    cfg, c, model, _params, get = tiny
    eng = _engine(model, carry=carry, max_slots=2, max_seq_len=160,
                  prefill_buckets=(8,), max_queue=16)
    assert eng._carried_rows(8) == (2 if carry else 0)
    rows, join, install = {}, eng._join, eng._install_state

    def row_of(slot_no):
        return [{k: np.asarray(a[slot_no]) for k, a in layer.items()}
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
    # both slots' rows hold a tenant's state before anybody joins them
    for f in [eng.submit(rng.integers(0, cfg.vocab_size, 9),
                         max_new_tokens=3) for _ in range(2)]:
        f.result(timeout=300)
    first = eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new_tokens=60,
                       return_logprobs=True)
    while eng.stats()["counters"].get("decode_steps", 0) < 6:
        pass
    before = eng.stats()["counters"]
    long = rng.integers(0, cfg.vocab_size, 61)     # 8 chunks, the last of 5
    second = eng.submit(long, max_new_tokens=4, return_logprobs=True)
    out2, lp2 = second.result(timeout=300)
    out1, lp1 = first.result(timeout=300)
    counters = eng.stats()["counters"]
    eng.close()
    chunks = _spans(eng, "pt.serve.prefill_chunk")
    rounds = _spans(eng, "pt.serve.decode_round")
    assert counters["state_resumes_total"] == 7 + 2     # (9 tokens: 2 calls)
    assert counters["decode_steps"] - before["decode_steps"] >= 7
    for full, lps, n in ((out1, lp1, 6), (out2, lp2, 61)):
        want, _ch, _st = ref.next_token_logprobs(get, c, np.asarray(full),
                                                 128)
        np.testing.assert_allclose(lps, want[n - 1:], rtol=0, atol=PARITY)
    # the long prompt's slot: its last tenant's state, untouched by the seven
    # or eight rounds that went by, until the install wrote the prompt's own
    at_join, at_install = next(v for req, v in rows.items()
                               if len(req.prompt) == 61)
    assert len(at_join) == 3                  # the three Mamba-2 layers
    for was, then in zip(at_join, at_install):
        for k in ("ssm", "conv"):
            assert np.abs(was[k]).max() > 0
            np.testing.assert_array_equal(then[k], was[k])
    # a call carries at most one round, and a round is one step: counted once
    carried = [a["carried"] for a in chunks]
    assert carried[-8:] == ([1] * 8 if carry else [0] * 8)  # the long one's
    assert counters.get("rounds_carried_total", 0) == \
        sum(n > 0 for n in carried)
    # (a round between two chunks is read outside any round's span: the
    # long prompt's seven gaps had one each, a carrying engine's none)
    between = counters["decode_steps"] - len(rounds) - \
        counters.get("rounds_carried_total", 0)
    assert between == 0 if carry else between >= 7
    assert counters["tokens_total"] == counters["slot_rounds"] == \
        2 + 2 + 59 + 3


# -- refusals, in words ---------------------------------------------------------

def test_what_assumes_pages_of_kv_is_refused_in_words(tiny):
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


def test_a_cache_spec_must_say_what_its_layers_keep():
    from paddle_tpu.serving.paged_kv import (CacheLayout, PageDemand,
                                             PagedKVPool)

    spec = {"ssm": ((2, 4, 8), jnp.float32)}

    def layout(layers, state_spec):
        return CacheLayout.parse({"kind": "kv_by_layer", "layers": layers},
                                 state_spec, len(layers), 4, 2, 8)

    with pytest.raises(ValueError, match="'state' or 'none'"):
        layout(["full", "ring"], spec)
    with pytest.raises(ValueError, match="exactly where the model declares"):
        layout(["full", "state"], None)
    with pytest.raises(ValueError, match="pages nothing"):
        layout(["state", "none"], spec)
    pool = PagedKVPool(layout(["state", "full", "none"], spec), 8,
                       jnp.float32, prefix_cache=False, max_slots=2)
    assert len(pool.k) == len(pool.v) == len(pool.state) == 1
    assert pool.state[0]["ssm"].shape == (2, 2, 4, 8)
    assert pool.layers_by_kind() == {"state": 1, "full": 1, "none": 1}
    assert pool.can_allocate(PageDemand([], 7, 0, 1)) and \
        not pool.can_allocate(PageDemand([], 8, 0, 1))
