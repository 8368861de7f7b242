"""ISSUE 12: the production serving tier — paged KV cache with prefix
reuse, speculative decoding, and the multi-replica router.

Covers the acceptance surface: allocator/trie invariants (alloc, free,
ref-count, COW, fragmentation under churn, leaf-only LRU eviction),
prefix-hit parity (a shared-prefix request produces the same greedy
tokens as a cold prefill — its K/V pages ARE the cold request's pages),
speculative greedy parity vs ``model.generate``, rejection-sampling
distribution preservation, deadline-aware (EDF) slot joining with
queued-expiry shedding, paged admission bounds (pool capacity, not slot
length), router quota/backpressure/fault behavior, and the zero-retrace
steady-state contract for the paged decode path.
"""
import os
import time
from concurrent.futures import Future
from concurrent.futures import wait as fwait

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.serving.paged_kv import (
    CacheLayout, PageAllocator, PagedKVPool, PoolExhausted, PrefixCache,
    token_blocks,
)


# -- allocator ----------------------------------------------------------------

def test_allocator_alloc_free_refcount_invariants():
    a = PageAllocator(8)                    # 1 scratch + 7 usable
    p = a.alloc(3)
    assert len(set(p)) == 3 and 0 not in p
    assert a.free_pages == 4 and a.live_pages == 3
    a.retain(p[0])
    assert a.ref(p[0]) == 2
    a.release(p[0])                         # still held once
    assert a.ref(p[0]) == 1 and a.free_pages == 4
    a.release(p[0])                         # now freed
    assert a.ref(p[0]) == 0 and a.free_pages == 5
    with pytest.raises(RuntimeError, match="double free"):
        a.release(p[0])
    with pytest.raises(RuntimeError, match="retain of free"):
        a.retain(p[0])
    with pytest.raises(PoolExhausted):
        a.alloc(8)
    assert a.free_pages == 5                # all-or-nothing: no leak
    a.check()


def test_allocator_cow_semantics():
    a = PageAllocator(4)
    (p,) = a.alloc(1)
    same, copied = a.cow(p)
    assert same == p and not copied         # exclusive: write in place
    a.retain(p)                             # now shared
    new, copied = a.cow(p)
    assert copied and new != p
    assert a.ref(new) == 1 and a.ref(p) == 1   # writer moved off the share
    assert a.cow_total == 1
    a.check()


def test_allocator_fragmentation_churn():
    """Random alloc/free churn: the free list and refcounts stay coherent
    (no double allocation, no lost pages) at every step."""
    rng = np.random.RandomState(0)
    a = PageAllocator(32)
    live = []
    for _ in range(400):
        if live and (rng.rand() < 0.5 or a.free_pages == 0):
            pages = live.pop(rng.randint(len(live)))
            for p in pages:
                a.release(p)
        else:
            n = rng.randint(1, 5)
            if n <= a.free_pages:
                live.append(a.alloc(n))
        a.check()
        held = [p for pages in live for p in pages]
        assert len(held) == len(set(held)), "page handed out twice"
        assert a.live_pages == len(held)
    for pages in live:
        for p in pages:
            a.release(p)
    a.check()
    assert a.free_pages == 31


# -- prefix trie --------------------------------------------------------------

def _chain(*blocks):
    return [tuple(b) for b in blocks]


def test_prefix_trie_match_insert_and_context_separation():
    a = PageAllocator(16)
    t = PrefixCache()
    pages = a.alloc(3)
    blocks = _chain([1, 2], [3, 4], [5, 6])
    assert t.insert(blocks, pages, a) == 3
    assert all(a.ref(p) == 2 for p in pages)       # ours + the trie's
    got = t.match(blocks, 2, a)
    assert got == pages
    assert all(a.ref(p) == 3 for p in pages)       # match retained for us
    # partial chains match their prefix only
    assert t.match(_chain([1, 2], [9, 9]), 2) == pages[:1]
    # the SAME block under a different prefix is a different node
    assert t.match(_chain([3, 4]), 2) == []
    assert t.match_len(blocks) == 3
    assert t.stats()["hit_tokens"] > 0


def test_prefix_trie_eviction_is_lru_leaf_only():
    a = PageAllocator(16)
    t = PrefixCache()
    p_ab = a.alloc(2)
    t.insert(_chain([1], [2]), p_ab, a)
    p_c = a.alloc(1)
    t.insert(_chain([3]), p_c, a)
    for p in p_ab + p_c:
        a.release(p)                       # trie is now the only holder
    t.match(_chain([3]), 1)                # bump [3]: chain a-b is LRU
    # evicting ONE page must take the a-b chain's LEAF, never its root
    assert t.evict(1, a) == 1
    assert t.match_len(_chain([1], [2])) == 1      # root [1] survives
    assert t.match_len(_chain([3])) == 1
    # a held page is never evicted: retain [3]'s page, ask for everything
    a.retain(p_c[0])
    freed = t.evict(10, a)
    assert freed == 1                      # [1] goes; held [3] survives
    assert t.match_len(_chain([3])) == 1 and len(t) == 1
    a.release(p_c[0])
    assert t.evict(10, a) == 1 and len(t) == 0
    a.check()
    assert a.free_pages == 15


def test_token_blocks_full_blocks_only():
    assert token_blocks(np.arange(10), 4) == [(0, 1, 2, 3), (4, 5, 6, 7)]
    assert token_blocks(np.arange(10), 4, limit=1) == [(0, 1, 2, 3)]
    assert token_blocks(np.arange(3), 4) == []


def test_pool_cow_copies_device_contents():
    pool = PagedKVPool(CacheLayout.parse(None, None, 1, 2, 1, 2), 4,
                       "float32")
    (p,) = pool.allocate(1)
    pool.k[0] = pool.k[0].at[p].set(1.5)
    pool.allocator.retain(p)               # shared: a writer must COW
    new, copied = pool.ensure_writable(p)
    assert copied and new != p
    np.testing.assert_array_equal(np.asarray(pool.k[0][new]),
                                  np.asarray(pool.k[0][p]))


# -- rejection sampling (sampled speculative correctness) ---------------------

def test_rejection_sample_preserves_target_distribution():
    """Empirical check of the published property: whatever the draft
    proposes, the FIRST emitted token is distributed as the target."""
    rng = np.random.RandomState(0)
    V, k, n = 4, 1, 20000
    draft = np.array([[0.7, 0.1, 0.1, 0.1]])
    target = np.array([[0.1, 0.4, 0.3, 0.2], [0.25, 0.25, 0.25, 0.25]])
    counts = np.zeros(V)
    for _ in range(n):
        d_tok = np.array([rng.choice(V, p=draft[0])])
        out, acc = serving.rejection_sample(draft, target, d_tok, rng)
        assert len(out) == acc + 1
        counts[out[0]] += 1
    emp = counts / n
    np.testing.assert_allclose(emp, target[0], atol=0.015)


def test_rejection_sample_identical_distributions_accept_all():
    rng = np.random.RandomState(1)
    probs = np.array([[0.5, 0.5], [0.5, 0.5], [0.5, 0.5]])
    for _ in range(50):
        d = np.array([rng.choice(2, p=probs[0]), rng.choice(2, p=probs[1])])
        out, acc = serving.rejection_sample(probs[:2], probs, d, rng)
        assert acc == 2 and list(out[:2]) == list(d)
    assert serving.greedy_accept([3, 5, 7], [3, 5, 9]) == 2
    assert serving.greedy_accept([4], [4]) == 1
    assert serving.greedy_accept([1], [2]) == 0


# -- engine: paged decode, prefix reuse, deadlines ----------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    """1-layer GPT trained to continue the repeating 0..7 pattern:
    confident logits make greedy decode stable (the serving recipe)."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=64,
                    dtype="float32")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    pattern = np.tile(np.arange(8), 8)[None, :]
    ids = paddle.to_tensor(pattern.astype("int64"))
    for _ in range(80):
        loss = step(ids, ids)
    assert float(loss) < 0.1
    return model, pattern[0]


@pytest.fixture(scope="module")
def paged_engine(tiny_lm):
    """ONE shared paged engine (compiles are the expensive part); tests
    assert on counter DELTAS so they stay order-independent."""
    model, pattern = tiny_lm
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                        page_len=8,
                                        prefill_buckets=(8, 16, 24)))
    eng.start()
    yield eng, model, pattern
    eng.close()


def _counters(eng):
    snap = eng.metrics.snapshot()["counters"]
    return lambda name: snap.get(name, 0)


def test_prefix_hit_parity_with_cold_prefill(paged_engine):
    """A request sharing a cached prefix must produce the SAME tokens as
    the cold path — its prefix K/V pages ARE the cold request's pages, so
    the logits feeding every argmax are bit-identical by construction."""
    eng, model, pattern = paged_engine
    before = _counters(eng)
    prompt = pattern[:19].astype("int64")          # two full 8-blocks
    ref = np.asarray(model.generate(paddle.to_tensor(prompt[None]),
                                    max_new_tokens=6,
                                    use_cache=True).numpy())[0]
    cold = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
    warm = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
    assert cold.tolist() == ref.tolist()
    assert warm.tolist() == ref.tolist()
    after = _counters(eng)
    assert after("prefix_hits") - before("prefix_hits") >= 1
    assert after("prefix_hit_tokens") - before("prefix_hit_tokens") >= 16
    assert eng.prefix_match_tokens(prompt) == 16
    pool = eng.stats()["kv_pages"]
    assert pool["prefix"]["nodes"] >= 2
    assert pool["pages_free"] > 0


def test_pages_release_on_completion(paged_engine):
    """Finished requests return their private pages; only trie-adopted
    prefix pages stay live."""
    eng, _model, pattern = paged_engine
    eng.submit(pattern[:9].astype("int64"), max_new_tokens=3).result(
        timeout=300)
    t0 = time.monotonic()
    while eng.stats()["active_slots"] and time.monotonic() - t0 < 30:
        time.sleep(0.01)
    a = eng._pool.allocator
    trie_pages = len(eng._pool.trie)
    assert a.live_pages == trie_pages, (a.live_pages, trie_pages)


def test_deadline_edf_join_order_and_shedding(paged_engine):
    """Queued requests join freed slots earliest-deadline-first, and a
    request whose deadline expires while queued is shed before prefill."""
    from paddle_tpu.observability.trace import tracer

    eng, _model, pattern = paged_engine
    # occupy BOTH slots with long decodes so submissions below queue up
    # (must be in-slot, not queued: EDF would sort the doomed request
    # ahead of queued work and admit it before its deadline passes)
    busy = [eng.submit(pattern[:12].astype("int64"), max_new_tokens=20)
            for _ in range(2)]
    t0 = time.monotonic()
    while len(eng._active()) < 2 and time.monotonic() - t0 < 60:
        time.sleep(0.0005)
    assert len(eng._active()) == 2
    # distinct prompt lengths tag each request's trace
    no_dl = eng.submit(pattern[:10].astype("int64"), max_new_tokens=2)
    late = eng.submit(pattern[:11].astype("int64"), max_new_tokens=2,
                      deadline_ms=60_000)
    soon = eng.submit(pattern[:13].astype("int64"), max_new_tokens=2,
                      deadline_ms=30_000)
    doomed = eng.submit(pattern[:14].astype("int64"), max_new_tokens=2,
                        deadline_ms=0.5)
    with pytest.raises(serving.DeadlineExceeded):
        doomed.result(timeout=60)
    for f in busy + [no_dl, late, soon]:
        f.result(timeout=300)
    assert eng.metrics.counter("shed_total") >= 1
    # EDF: prefill order soon < late < no-deadline (from the trace spans)
    t_pf = {}
    for t in tracer().traces(engine=eng.name):
        pl = t["meta"].get("prompt_len")
        pf = next((s for s in t["spans"] if s["name"] == "prefill"), None)
        if pf is not None and t["ok"] and pl in (10, 11, 13):
            t_pf[pl] = pf["t0"]
    assert t_pf[13] < t_pf[11] < t_pf[10]


def test_paged_admission_pool_capacity_bounds(tiny_lm):
    """Under paged KV the admission bound is POOL capacity: a request that
    can never hold enough pages is a clean BadRequest; one that merely
    oversubscribes the pool queues and completes. The position table stays
    its own (max_seq_len) bound."""
    model, pattern = tiny_lm
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                        page_len=8, num_pages=4,
                                        prefill_buckets=(8, 16)),
        name="tinypool")
    with eng:
        p = pattern[:9].astype("int64")
        with pytest.raises(serving.BadRequest, match="max_seq_len"):
            eng.submit(p, max_new_tokens=32).result(timeout=60)
        # needs ceil(25/8)=4 pages > the pool's 3 usable: impossible at
        # ANY load -> clean BadRequest
        with pytest.raises(serving.BadRequest, match="KV pages"):
            eng.submit(p, max_new_tokens=16).result(timeout=60)
        # two 2-page requests oversubscribe the 3-page pool: the second
        # WAITS for pages instead of failing
        a = eng.submit(p, max_new_tokens=7)
        b = eng.submit(p, max_new_tokens=7)
        for f in (a, b):
            out = f.result(timeout=300)
            assert out[9:].tolist() == [(9 + i) % 8
                                        for i in range(len(out) - 9)]
        alloc = eng._pool.allocator
        assert alloc.live_pages == len(eng._pool.trie)  # only trie-held
        alloc.check()


# -- the one-row prefill (ISSUE 26) ---------------------------------------------

@pytest.mark.parametrize("attend", ["reference", "interpret"])
@pytest.mark.parametrize("start", [0, 8], ids=["cold", "prefix_suffix"])
def test_one_row_prefill_matches_all_slots_program(start, attend,
                                                   monkeypatch):
    """A prompt prefilled through the ONE-ROW window program gives the
    same first token, the same logprob and the same page contents as the
    all-slots program with the other rows zero — for a cold prompt and for
    a prefix-cache suffix (``start > 0``: the first block is somebody's
    cached page), through the jnp reference attention and through the
    Pallas paged kernel itself (interpreted) at S = 1. Pages that are not in the
    request's table are bit-identical before and after."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.generation import (_build_window_step,
                                               _extract_gpt_params)

    if attend == "interpret":
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    dtype="float32")
    params = _extract_gpt_params(GPTForCausalLM(cfg))
    S, B, PL, W, slot = 3, 4, 8, 16, 1
    P, nh, hd = S * B + 1, 4, cfg.hidden_size // 4
    keys = jax.random.split(jax.random.key(26), 2 * cfg.num_hidden_layers)
    arenas = [jax.random.normal(k, (P, PL, nh, hd), jnp.float32) * 0.1
              for k in keys]
    k0, v0 = arenas[:2], arenas[2:]       # other requests' live context
    table = np.array([7, 3, 11, 0], np.int32)   # 3 blocks held, 1 not
    n = 11                                       # real suffix tokens < W
    suffix = np.arange(5, 5 + n, dtype=np.int32)
    tokens = np.zeros((S, W), np.int32)
    tokens[slot, :n] = suffix
    lengths = np.zeros(S, np.int32)
    lengths[slot] = start
    tables = np.zeros((S, B), np.int32)
    tables[slot] = table
    build = lambda rows, tag: _build_window_step(  # noqa: E731
        cfg, rows, B, PL, W, donate=False, label=f"t26:{attend}:{tag}")
    nxt_s, lp_s, k_s, v_s, _ = build(S, "all")(
        params, k0, v0, jnp.asarray(tables), jnp.asarray(tokens),
        jnp.asarray(lengths))
    nxt_1, lp_1, k_1, v_1, _ = build(1, "one")(
        params, k0, v0, jnp.asarray(table[None]),
        jnp.asarray(tokens[slot][None]), jnp.asarray([start], jnp.int32))
    assert np.asarray(nxt_1).shape == (1, W)
    # what _admit reads: the argmax and its logprob at the last real token
    assert int(np.asarray(nxt_1)[0, n - 1]) == \
        int(np.asarray(nxt_s)[slot, n - 1])
    np.testing.assert_allclose(np.asarray(lp_1)[0, :n],
                               np.asarray(lp_s)[slot, :n], atol=1e-5)
    assert np.array_equal(np.asarray(nxt_1)[0, :n],
                          np.asarray(nxt_s)[slot, :n])
    mine = sorted(set(table.tolist()) - {0})
    others = [pg for pg in range(1, P) if pg not in mine]
    for new_1, new_s, old in zip(k_1 + v_1, k_s + v_s, k0 + v0):
        new_1, new_s, old = map(np.asarray, (new_1, new_s, old))
        np.testing.assert_allclose(new_1[mine], new_s[mine], atol=1e-6)
        assert np.array_equal(new_1[others], old[others])
        # the suffix really was written (not a vacuous comparison)
        assert not np.array_equal(new_1[mine], old[mine])


def test_admission_mid_decode_leaves_running_streams_unchanged(paged_engine):
    """While other slots are mid-decode, an admission's one-row prefill
    touches neither their pages nor their next tokens: every request's
    greedy output is token-for-token what the same request gives alone."""
    eng, _model, pattern = paged_engine
    jobs = {"a": (pattern[:13], 14), "b": (pattern[3:12], 6),
            "c": (pattern[1:20], 5)}
    alone = {k: eng.submit(p.astype("int64"), max_new_tokens=m).result(
        timeout=300).tolist() for k, (p, m) in jobs.items()}
    futs, seen = {}, []

    def admit_others(_tok):
        # from the worker's own thread, between two of a's decode rounds
        seen.append(1)
        for k, at in (("b", 3), ("c", 6)):
            if len(seen) == at:
                p, m = jobs[k]
                futs[k] = eng.submit(p.astype("int64"), max_new_tokens=m)

    before = _counters(eng)
    p, m = jobs["a"]
    futs["a"] = eng.submit(p.astype("int64"), max_new_tokens=m,
                           on_token=admit_others)
    got_a = futs["a"].result(timeout=300).tolist()
    assert set(futs) == {"a", "b", "c"}
    assert got_a == alone["a"]
    for k in ("b", "c"):
        assert futs[k].result(timeout=300).tolist() == alone[k], k
    after = _counters(eng)
    assert after("prefills_total") - before("prefills_total") == 3
    # b and c really joined while a was decoding: fewer rounds than the
    # three would take one after the other
    assert after("decode_steps") - before("decode_steps") < \
        sum(m for _p, m in jobs.values()) - 3


def _count_backend_compiles():
    """(counter list, unregister): every XLA backend compile in this
    process appends to the list — what the benchmark calls a compile
    inside the window."""
    import jax
    from jax._src import monitoring

    hits = []

    def on(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            hits.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    return hits, lambda: monitoring.unregister_event_duration_listener(on)


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
def test_warmup_compiles_every_program_a_window_calls(tiny_lm, tmp_path,
                                                      draft):
    """After ``warmup()`` a mixed run over every bucket (cold prefills,
    prefix-cache suffixes, decode, programs dispatched ahead of a read, and
    verify with a draft model) records
    zero retrace events, zero compile-cache misses and zero XLA compiles,
    and the engine holds exactly {(S, 1)} ∪ {(1, bucket)} — plus
    (S, k + 1) with a draft model: no all-slots prefill program exists."""
    from paddle_tpu.jit import persistent_cache as pc
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    import paddle_tpu.analysis as A

    model, pattern = tiny_lm
    extra = {}
    if draft:
        paddle.seed(2)
        extra = dict(spec_tokens=3, draft_model=GPTForCausalLM(GPTConfig(
            vocab_size=32, hidden_size=16, num_hidden_layers=1,
            num_attention_heads=2, max_position_embeddings=64,
            dtype="float32")))
    old_dir, old_enabled = pc.cache_dir(), pc.is_enabled()
    pc.enable(str(tmp_path / "cache"))
    pc.reset_stats()
    os.environ["PT_RETRACE_AUDIT"] = "1"
    A.retrace.enable()
    compiles, unregister = _count_backend_compiles()
    try:
        name = "warmgen_draft" if draft else "warmgen"
        eng = serving.GenerationEngine(
            model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                            page_len=8,
                                            prefill_buckets=(8, 16, 24),
                                            **extra), name=name)
        eng.warmup()
        S = eng.config.max_slots
        want = {(S, 1, False), (1, 8, True), (1, 16, True),
                (1, 24, True)} | ({(S, 4, False)} if draft else set())
        assert set(eng._windows) == want
        labels = {k for k in pc.stats()["by_label"]
                  if k.startswith(f"serving:{name}:")}
        assert {f"serving:{name}:window1", f"serving:{name}:prefill8",
                f"serving:{name}:prefill16",
                f"serving:{name}:prefill24"} <= labels
        assert not any(":window8" in k or ":window16" in k
                       or ":window24" in k for k in labels)
        # the program that hands a round run ahead its prompt's first token
        # (a draft's proposals cross the host: nothing runs ahead of them)
        assert (f"serving:{name}:token_feed" in labels) == (not draft)
        warm, n_compiles = pc.stats(), len(compiles)
        assert n_compiles > 0           # the listener does hear compiles
        # (the draft's one insert label sees its three bucket shapes IN
        # warmup; the window and prefill labels see one shape each, ever)
        retraced = eng.retrace_events()
        assert retraced == (2 if draft else 0)
        with eng:
            # suffix lengths 5 / 11 / 19 -> buckets 8 / 16 / 24; the
            # repeats hit the prefix cache and prefill a suffix at start>0
            futs = [eng.submit(pattern[o:o + n].astype("int64"),
                               max_new_tokens=3 + (i % 4))
                    for i, (o, n) in enumerate(
                        [(0, 5), (0, 11), (0, 19), (0, 19), (8, 11),
                         (1, 19), (0, 11), (2, 5)])]
            done, _ = fwait(futs, timeout=300)
            assert len(done) == len(futs)
            for f in futs:
                f.result()
            stats = eng.stats()
        assert stats["counters"]["prefix_hits"] >= 1
        # two slots, eight prompts: programs went out ahead of a read
        assert (stats["run_ahead_rate"] > 0.3) == (not draft), stats
        assert set(eng._windows) == want
        assert stats["retrace_events"] == retraced, stats
        run = pc.stats()
        assert run["misses"] == warm["misses"]
        assert run["by_label"] == warm["by_label"]  # not even a lookup
        assert len(compiles) == n_compiles, compiles[n_compiles:]
    finally:
        unregister()
        A.retrace.disable()
        A.retrace.reset()
        os.environ.pop("PT_RETRACE_AUDIT", None)
        pc.disable()
        pc.reset_stats()
        if old_enabled and old_dir:
            pc.enable(old_dir)


def test_prefill_window_tokens_counter_and_fill_rate(tiny_lm):
    """``prefill_window_tokens_total`` grows by 1 x W per admission (the
    prefill program has ONE row) and ``prefill_fill_rate`` is the share of
    those token-rows that held a real, uncached prompt token."""
    model, pattern = tiny_lm
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                        page_len=8,
                                        prefill_buckets=(8, 16, 24)),
        name="fillgen")
    assert eng.stats()["prefill_fill_rate"] == 0.0    # nothing prefilled
    with eng:
        grew = []
        for n in (5, 19, 19):     # cold W=8, cold W=24, 16 cached -> W=8
            before = eng.metrics.counter("prefill_window_tokens_total")
            eng.submit(pattern[:n].astype("int64"),
                       max_new_tokens=2).result(timeout=300)
            grew.append(
                eng.metrics.counter("prefill_window_tokens_total") - before)
        stats = eng.stats()
    assert grew == [8, 24, 8]
    c = stats["counters"]
    assert c["prompt_tokens_total"] == 43 and c["prefix_hit_tokens"] == 16
    assert stats["prefill_fill_rate"] == round((43 - 16) / 40, 4)
    # the all-slots prefill would have run max_slots x W token-rows
    assert c["prefill_window_tokens_total"] == 40


# -- one window program in flight ---------------------------------------------

def _queued_then_started(eng, jobs, on_first_token=None, **submit):
    """Every request is queued before the worker's first turn (``submit``
    would start it on the first): the schedule is the same in every run.
    ``on_first_token`` streams the first job's tokens."""
    eng.start = lambda: eng
    try:
        futs = [eng.submit(p.astype("int64"), max_new_tokens=m,
                           on_token=None if i else on_first_token, **submit)
                for i, (p, m) in enumerate(jobs)]
    finally:
        del eng.start
    eng.start()
    return futs


def _greedy(model, prompt, max_new):
    return np.asarray(model.generate(
        paddle.to_tensor(prompt.astype("int64")[None]),
        max_new_tokens=max_new, use_cache=True).numpy())[0]


def test_a_round_run_ahead_drops_the_token_past_an_eos(tiny_lm):
    """Two slots, three requests queued. With no slot free a round follows a
    round whatever arrives, so each goes out before the last is read; ``a``
    ends on EOS two tokens before its budget while the next round already
    holds a row for it. That row's token is dropped, the slot goes to ``c``
    at once (its prefill call, and the round behind it, out ahead too), and
    ``b`` and ``c`` read what ``generate`` gives."""
    model, pattern = tiny_lm
    eos = 5
    jobs = [(pattern[:3], 6), (pattern[:6], 7), (pattern[:14], 4)]
    want = [_greedy(model, p, m) for p, m in jobs]
    assert want[0][3:].tolist() == [3, 4, 5, 6, 7, 0]  # 5 comes third
    want[0] = want[0][:6]
    assert all(eos not in w[len(p):] for w, (p, _m) in zip(want[1:], jobs[1:]))
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(
            max_slots=2, max_seq_len=32, page_len=8, prefill_buckets=(8, 16),
            prefix_cache=False, eos_token_id=eos), name="eosgen")
    streamed = []
    futs = _queued_then_started(eng, jobs, streamed.append)
    with eng:
        got = [f.result(timeout=300) for f in futs]
        t0 = time.monotonic()
        while eng.stats()["active_slots"] and time.monotonic() - t0 < 30:
            time.sleep(0.01)
        stats = eng.stats()
    for g, w in zip(got, want):
        assert g.tolist() == w.tolist()
    assert streamed == [3, 4, 5]  # nothing past the EOS reached the client
    c = stats["counters"]
    # a, then b's call behind it; rounds 1-3 behind b's call and each other
    # (the third holds a's dead row); c's call behind round 3, rounds 4-6
    # behind it and each other; after round 6 nobody has a token left
    assert c["prefill_chunks_total"] == 3 and c["decode_steps"] == 6
    assert c["programs_run_ahead_total"] == 8
    assert stats["run_ahead_rate"] == round(8 / 9, 4)
    assert c["slot_rounds"] == 12 and c["tokens_total"] == 11  # one dropped
    # slot 0 served a (3 tokens), then c (4)
    assert [(s, n) for s, _t0, _t1, n in eng._slot_hist] == \
        [(0, 3), (0, 4), (1, 7)]
    assert eng._pool.allocator.live_pages == 0  # a's pages went back once


def test_nothing_runs_ahead_of_a_free_slot_and_an_empty_queue(tiny_lm):
    """Below the knee — a slot is free and nobody waits — the worker reads,
    then decides, over admissions and rounds alike: an arrival never finds a
    round dispatched on a guess in front of its prefill. ``b`` arrives from
    the worker's own thread between two of ``a``'s rounds."""
    model, pattern = tiny_lm
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(
            max_slots=3, max_seq_len=32, page_len=8, prefill_buckets=(8, 16),
            prefix_cache=False), name="calmgen")
    futs, seen = {}, []

    def b_arrives(_tok):
        seen.append(1)
        if len(seen) == 3:
            futs["b"] = eng.submit(pattern[:11].astype("int64"),
                                   max_new_tokens=4)

    with eng:
        a = eng.submit(pattern[:5].astype("int64"), max_new_tokens=8,
                       on_token=b_arrives).result(timeout=300)
        b = futs["b"].result(timeout=300)
        c = eng.submit(pattern[:9].astype("int64"),
                       max_new_tokens=3).result(timeout=300)
        stats = eng.stats()
    for got, (n, m) in zip((a, b, c), ((5, 8), (11, 4), (9, 3))):
        assert got.tolist() == _greedy(model, pattern[:n], m).tolist()
    assert stats["counters"]["prefills_total"] == 3
    assert stats["counters"]["decode_steps"] >= 9
    assert "programs_run_ahead_total" not in stats["counters"]
    assert stats["run_ahead_rate"] == 0.0


def test_a_fault_in_a_round_run_ahead_fails_that_rounds_requests(tiny_lm):
    """``decode_fault@step=2``: round 2 is dispatched while round 1 is still
    unread. Its two requests fail and their slots go back; round 1's tokens
    for them are dropped with them; the prompt that waited is served."""
    from paddle_tpu.distributed.resilience.faults import InjectedFault, injector

    model, pattern = tiny_lm
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(
            max_slots=2, max_seq_len=32, page_len=8, prefill_buckets=(8, 16),
            prefix_cache=False), name="faultgen")
    rule = injector().arm("decode_fault", engine=eng.name, step=2)
    try:
        *doomed, waiting = _queued_then_started(
            eng, [(pattern[:9], 8), (pattern[:11], 8), (pattern[:6], 3)])
        with eng:
            for f in doomed:
                with pytest.raises(InjectedFault):
                    f.result(timeout=300)
            assert waiting.result(timeout=300).tolist() == \
                _greedy(model, pattern[:6], 3).tolist()
            stats = eng.stats()
    finally:
        injector().disarm(rule)
    c = stats["counters"]
    assert c["batch_failures"] == 1 and c["errors_total"] == 2
    assert c["responses_total"] == 1 and stats["active_slots"] == 0
    # rounds 0 and 1 were read (the second for nobody), then the survivor's
    assert c["decode_steps"] == 2 + 2 and c["tokens_total"] == 2 + 2


# -- run-ahead when the pool's pages bind before its slots ----------------------

def _pool_bound_engine(model, name, **over):
    """Four slots over a pool of four usable pages: a request of two pages
    (up to 16 tokens, prompt and budget) shares it with one other, so two
    slots stay free for good while a queue stands."""
    kw = dict(max_slots=4, max_seq_len=32, page_len=8, num_pages=5,
              prefill_buckets=(8, 16), prefix_cache=False)
    kw.update(over)
    return serving.GenerationEngine(model, serving.GenerationConfig(**kw),
                                    name=name)


def _programs(eng):
    """The window programs the worker ran, in order: ``(kind, args)``."""
    from paddle_tpu.observability.trace import tracer

    return [(r["name"].rsplit(".", 1)[1], r["args"])
            for r in tracer().worker_spans(thread=f"pt-serving-{eng.name}")
            if r["name"] in ("pt.serve.decode_round",
                             "pt.serve.prefill_chunk")]


def test_rounds_run_ahead_where_the_pool_holds_none_of_the_prompts_that_wait(
        tiny_lm):
    """Five prompts of two pages each before a pool of four: two run, three
    wait, two slots are free all along. ``_next_request`` picks nobody from
    a queue that is not empty, so the round is decided and goes out before
    the one in front is read — until a budget ends behind the unread round:
    that one is read first, and the prompt its pages make room for joins at
    that boundary. Tokens and logprobs are those of the same engine serving
    one prompt at a time (nothing can run ahead there) and ``generate``'s."""
    model, pattern = tiny_lm
    jobs = [(pattern[:3], 12), (pattern[:6], 9), (pattern[:5], 10),
            (pattern[:4], 8), (pattern[:7], 6)]
    eng = _pool_bound_engine(model, "poolbound")
    futs = _queued_then_started(eng, jobs, return_logprobs=True)
    with eng:
        got = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    alone = _pool_bound_engine(model, "poolbound_alone")
    with alone:
        for (p, m), (full, lps) in zip(jobs, got):
            want, want_lps = alone.submit(
                p.astype("int64"), max_new_tokens=m,
                return_logprobs=True).result(timeout=300)
            assert full.tolist() == want.tolist() == \
                _greedy(model, p, m).tolist()
            np.testing.assert_allclose(lps, want_lps, atol=1e-5)
        assert "programs_run_ahead_total" not in alone.stats()["counters"]
    c = stats["counters"]
    assert c["prefill_chunks_total"] == 5 and c["tokens_total"] == 45 - 5
    # b's call behind a's; then every round but the five behind which a
    # budget ended (the last four run beside an empty queue)
    assert c["decode_steps"] == 22
    assert c["rounds_ahead_pool_bound_total"] == 17
    assert c["programs_run_ahead_total"] == 1 + 17
    assert stats["run_ahead_rate"] >= 0.6
    progs = _programs(eng)
    assert sum(a.get("pool_bound", 0) for _k, a in progs) == 17
    assert all(a["ahead"] for _k, a in progs if a.get("pool_bound"))
    # c, d and e joined at a boundary, with everything read
    assert [a["ahead"] for k, a in progs if k == "prefill_chunk"] == \
        [0, 1, 0, 0, 0]
    assert eng._pool.allocator.live_pages == 0


def test_a_round_behind_which_a_budget_ends_waits_for_the_read(tiny_lm):
    """``a`` has four tokens to give, ``b`` nine, ``c`` waits for pages.
    Rounds 1–3 go out ahead (the pool holds no ``c``); ``a``'s budget ends
    with round 3, so NOTHING goes out behind it: round 3 is read, ``a``'s
    pages come back and ``c`` joins at that boundary — its prefill call is
    the very next program, not a round later. From there a slot is free and
    nobody waits: nothing goes out ahead again."""
    model, pattern = tiny_lm
    jobs = [(pattern[:3], 4), (pattern[:6], 9), (pattern[:5], 10)]
    eng = _pool_bound_engine(model, "poolbound_budget")
    futs = _queued_then_started(eng, jobs, return_logprobs=True)
    with eng:
        got = [f.result(timeout=300)[0] for f in futs]
        stats = eng.stats()
    for (p, m), full in zip(jobs, got):
        assert full.tolist() == _greedy(model, p, m).tolist()
    kinds = [(k, a["ahead"], a.get("pool_bound", 0))
             for k, a in _programs(eng)]
    assert kinds[:6] == [("prefill_chunk", 0, 0), ("prefill_chunk", 1, 0),
                         ("decode_round", 1, 1), ("decode_round", 1, 1),
                         ("decode_round", 1, 1), ("prefill_chunk", 0, 0)]
    assert all(k == ("decode_round", 0, 0) for k in kinds[6:])
    c = stats["counters"]
    assert c["rounds_ahead_pool_bound_total"] == 3
    assert c["programs_run_ahead_total"] == 1 + 3
    assert c["decode_steps"] == 3 + 9 and c["tokens_total"] == 23 - 3


def test_a_full_pool_and_an_empty_queue_send_nothing_ahead(tiny_lm):
    """The first-token rule where the PAGES are gone: two requests hold the
    whole pool, two slots are free and nobody waits. Not one round goes out
    on a guess — an arrival the pool could hold after the unread round
    would prefill behind it."""
    model, pattern = tiny_lm
    jobs = [(pattern[:3], 7), (pattern[:6], 9)]
    eng = _pool_bound_engine(model, "poolbound_calm")
    futs = _queued_then_started(eng, jobs, return_logprobs=True)
    with eng:
        for (p, m), f in zip(jobs, futs):
            assert f.result(timeout=300)[0].tolist() == \
                _greedy(model, p, m).tolist()
        stats = eng.stats()
    c = stats["counters"]
    assert c["decode_steps"] == 8 and c["prefill_chunks_total"] == 2
    assert c["programs_run_ahead_total"] == 1  # b's call, behind a's
    assert "rounds_ahead_pool_bound_total" not in c
    assert all(not a["ahead"] and not a["pool_bound"]
               for k, a in _programs(eng) if k == "decode_round")


def test_a_join_that_meets_pool_exhausted_is_requeued_and_the_round_goes_out(
        tiny_lm):
    """The rarer way in: ``can_allocate`` says yes (here it lies once) and
    the join behind it raises ``PoolExhausted``. The prompt goes back to the
    FRONT of the queue, the slot stays free and the round goes out all the
    same; the schedule and the tokens are those of the honest pool."""
    model, pattern = tiny_lm
    jobs = [(pattern[:3], 4), (pattern[:6], 9), (pattern[:5], 10),
            (pattern[:4], 6)]
    runs = {}
    for lie in (False, True):
        eng = _pool_bound_engine(model, f"poolbound_lie{int(lie)}")
        requeued = []
        if lie:
            real, requeue, told = eng._pool.can_allocate, eng._requeue, []

            def can_allocate(*a, **kw):
                ok = real(*a, **kw)
                if not ok and not told:
                    told.append(1)
                    return True
                return ok

            def at_the_front(req):
                requeue(req)
                requeued.append((eng._queue[0] is req, len(eng._queue),
                                 eng._free_slot()))

            eng._pool.can_allocate, eng._requeue = can_allocate, at_the_front
            with pytest.raises(PoolExhausted):  # what the lie leads to
                eng._pool.allocate(5)
        futs = _queued_then_started(eng, jobs, return_logprobs=True)
        with eng:
            got = [f.result(timeout=300)[0].tolist() for f in futs]
            runs[lie] = (got, eng.stats()["counters"], _programs(eng))
        if lie:
            # c was picked behind b's call, met the exhausted pool and went
            # back in front of d; slot 2 stayed free
            assert requeued == [(True, 2, 2)]
    for (p, m), got in zip(jobs, runs[True][0]):
        assert got == _greedy(model, p, m).tolist()
    assert runs[True][0] == runs[False][0]
    assert runs[True][2] == runs[False][2]
    honest, lied = runs[False][1], runs[True][1]
    assert lied["admits_requeued"] == 1 and "admits_requeued" not in honest
    assert lied["rounds_ahead_pool_bound_total"] == \
        honest["rounds_ahead_pool_bound_total"] >= 3
    assert lied["programs_run_ahead_total"] == \
        honest["programs_run_ahead_total"]


# -- speculative decoding -----------------------------------------------------

@pytest.fixture(scope="module")
def spec_engine(tiny_lm):
    """Target + 1-layer draft, both pattern-trained; spec_tokens=3."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    model, pattern = tiny_lm
    dcfg = GPTConfig(vocab_size=32, hidden_size=16, num_hidden_layers=1,
                     num_attention_heads=2, max_position_embeddings=64,
                     dtype="float32")
    paddle.seed(1)
    draft = GPTForCausalLM(dcfg)
    optimizer = opt.AdamW(learning_rate=3e-3, parameters=draft.parameters())
    step = jit.TrainStep(draft, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.to_tensor(np.tile(np.arange(8), 8)[None, :].astype("int64"))
    for _ in range(80):
        step(ids, ids)
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                        page_len=8,
                                        prefill_buckets=(8, 16, 24),
                                        draft_model=draft, spec_tokens=3),
        name="specgen")
    eng.start()
    yield eng, model, pattern
    eng.close()


@pytest.mark.slow  # extra verify-window compile; ci.sh serving gate runs it
def test_speculative_greedy_parity_vs_generate(spec_engine):
    """Speculative greedy decode must be token-for-token equal to the
    model's own KV-cached greedy path — for EVERY request, whatever the
    draft proposed (acceptance only changes speed)."""
    eng, model, pattern = spec_engine
    before = _counters(eng)
    jobs = [(9, 8), (13, 6), (11, 10), (17, 8)]
    futs = [(p, m, eng.submit(pattern[:p].astype("int64"), max_new_tokens=m))
            for p, m in jobs]
    for p, m, f in futs:
        ref = np.asarray(model.generate(
            paddle.to_tensor(pattern[:p].astype("int64")[None]),
            max_new_tokens=m, use_cache=True).numpy())[0]
        got = f.result(timeout=300)
        assert got.tolist() == ref.tolist(), (p, m)
    after = _counters(eng)
    assert after("spec_rounds") > before("spec_rounds")
    assert after("spec_accepted") > before("spec_accepted")
    snap = eng.stats()
    assert snap["spec_acceptance"] > 0.3          # pattern-trained draft
    assert snap["effective_tokens_per_step"] > 1.2
    # speculation emitted MORE tokens than verify rounds: the whole point
    rounds = after("decode_steps") - before("decode_steps")
    tokens = after("tokens_total") - before("tokens_total")
    assert tokens > rounds


# -- zero retrace steady state ------------------------------------------------

@pytest.mark.slow  # shares the spec engine; ci.sh serving gate runs it
def test_paged_decode_zero_retrace_steady_state(tiny_lm):
    """PT_RETRACE_AUDIT machinery: after first-use compiles (the per-label
    baselines), mixed paged traffic — cold prefills, prefix hits, decode —
    must record ZERO serving-labeled retrace events."""
    model, pattern = tiny_lm
    os.environ["PT_RETRACE_AUDIT"] = "1"
    import paddle_tpu.analysis as A

    A.retrace.enable()
    try:
        eng = serving.GenerationEngine(
            model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                            page_len=8,
                                            prefill_buckets=(8, 16, 24)),
            name="auditgen")
        with eng:
            futs = [eng.submit(pattern[o:o + 9 + (i % 3)].astype("int64"),
                               max_new_tokens=3 + (i % 4))
                    for i, o in enumerate([0, 0, 8, 0, 8, 1, 0, 2])]
            fwait(futs, timeout=300)
            stats = eng.stats()
        assert stats["retrace_events"] == 0, stats
    finally:
        A.retrace.disable()
        A.retrace.reset()
        os.environ.pop("PT_RETRACE_AUDIT", None)


# -- router -------------------------------------------------------------------

class _FakeReplica:
    """GenerationEngine-shaped stub: deterministic router-policy tests
    without device compiles."""

    def __init__(self, name, depth=0, headroom=1.0, match=0, closed=False,
                 full=False):
        from paddle_tpu.serving.metrics import MetricsRegistry

        self.name = name
        self.metrics = MetricsRegistry()
        self.depth, self.headroom, self.match = depth, headroom, match
        self.closed, self.full = closed, full
        self.submitted = []

    def start(self):
        return self

    def close(self, drain=True):
        self.closed = True

    def queue_depth(self):
        return self.depth

    def stats(self):
        return self.metrics.snapshot()

    def kv_headroom(self):
        return self.headroom

    def prefix_match_tokens(self, prompt):
        return self.match

    def submit(self, prompt, max_new_tokens=16, deadline_ms=None):
        if self.closed:
            raise serving.EngineClosed("down")
        if self.full:
            raise serving.QueueFull("full")
        fut = Future()
        self.submitted.append(np.asarray(prompt))
        return fut


def test_router_tenant_quota_and_fleet_backpressure():
    r1 = _FakeReplica("a")
    router = serving.ReplicaRouter(
        [r1], serving.RouterConfig(max_inflight=3, default_quota=2,
                                   tenant_quotas={"vip": 3}))
    p = np.arange(4)
    f1 = router.submit(p, tenant="free")
    router.submit(p, tenant="free")
    with pytest.raises(serving.TenantQuotaExceeded):
        router.submit(p, tenant="free")
    router.submit(p, tenant="vip")                 # own quota
    with pytest.raises(serving.QueueFull):         # fleet-wide bound
        router.submit(p, tenant="vip")
    f1.set_result(np.arange(5))                    # completion frees quota
    router.submit(p, tenant="free")
    st = router.stats()
    assert st["rejected"] == {"quota": 1, "capacity": 1}
    assert st["inflight"]["free"] == 2


def test_router_load_aware_and_prefix_affinity_dispatch():
    idle = _FakeReplica("idle", depth=0, headroom=1.0)
    busy = _FakeReplica("busy", depth=50, headroom=0.1)
    router = serving.ReplicaRouter([busy, idle])
    router.submit(np.arange(8))
    assert len(idle.submitted) == 1 and not busy.submitted
    # affinity overrides moderate load: the replica holding the prefix wins
    holder = _FakeReplica("holder", depth=2, match=8)
    cold = _FakeReplica("cold", depth=0)
    router2 = serving.ReplicaRouter([cold, holder])
    router2.submit(np.arange(8))
    assert len(holder.submitted) == 1 and not cold.submitted
    assert router2.stats()["affinity_hits"] == 1


def test_router_fault_marks_down_and_reroutes():
    dead = _FakeReplica("dead", closed=True)
    live = _FakeReplica("live")
    router = serving.ReplicaRouter([dead, live])
    router.submit(np.arange(4))
    assert len(live.submitted) == 1
    assert router.stats()["down"] == ["dead"]
    full = _FakeReplica("full2", full=True)
    router2 = serving.ReplicaRouter([full])
    with pytest.raises(serving.QueueFull):
        router2.submit(np.arange(4))
    router2._replicas[0].full = False
    router2.submit(np.arange(4))                   # recovers


@pytest.mark.slow  # two real replicas; ci.sh serving gate runs it
def test_router_end_to_end_fleet_with_replica_fault(tiny_lm):
    """Two real replicas behind the router: shared-prefix traffic routes
    with affinity, a replica fault mid-run fences it, and the surviving
    replica drains the rest — every surviving future resolves correctly."""
    model, pattern = tiny_lm

    def mk(name):
        return serving.GenerationEngine(
            model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                            page_len=8,
                                            prefill_buckets=(8, 16, 24)),
            name=name)

    ra, rb = mk("fleet_a"), mk("fleet_b")
    router = serving.ReplicaRouter([ra, rb], name="fleet")
    prompt = pattern[:17].astype("int64")
    with router:
        # cold landing first: its replica becomes the prefix holder
        router.submit(prompt, max_new_tokens=4).result(timeout=300)
        futs = [router.submit(prompt, max_new_tokens=4) for _ in range(5)]
        outs = [f.result(timeout=300) for f in futs]
        for out in outs:
            assert out[17:].tolist() == [(17 + i) % 8
                                         for i in range(len(out) - 17)]
        st = router.stats()
        # same-prefix traffic concentrated on the replica holding the pages
        assert sum(r["routed"] for r in st["replicas"].values()) == 6
        assert st["affinity_hits"] >= 4
        assert max(r["routed"] for r in st["replicas"].values()) >= 5
        # replica fault: close A; traffic keeps draining through B
        ra.close(drain=False)
        futs2 = [router.submit(prompt, max_new_tokens=3) for _ in range(4)]
        for f in futs2:
            out = f.result(timeout=300)
            assert out[17:].tolist() == [(17 + i) % 8
                                         for i in range(len(out) - 17)]
        st = router.stats()
        assert "fleet_a" in st["down"]
        assert router.queue_depth() == 0           # drained, not stuck
        assert st["replicas"]["fleet_b"]["responses"] >= 4


# -- property-style invariants (ISSUE 18 satellite) ---------------------------

def test_pool_exhausted_carries_allocator_state():
    """The exception IS the diagnostic: need/free/live/usable and the
    lifetime alloc/free totals, so an OOM log line is actionable without
    a debugger attached."""
    a = PageAllocator(8)
    held = a.alloc(5)
    with pytest.raises(PoolExhausted) as ei:
        a.alloc(4)
    msg = str(ei.value)
    assert "need 4 pages" in msg and "2 free" in msg
    assert "(5 live) of 7 usable" in msg
    assert "pool=8 incl. scratch" in msg and "alloc_total=5" in msg
    for p in held:
        a.release(p)
    a.check()


def test_token_blocks_roundtrip_property():
    """For random prompts and page sizes: blocks tile the prompt's full
    pages exactly, in order, each of length page_len — concatenating
    them reconstructs the prompt's full-page prefix."""
    rng = np.random.RandomState(7)
    for _ in range(50):
        n = int(rng.randint(0, 65))
        pl = int(rng.randint(1, 17))
        prompt = rng.randint(0, 1000, size=n)
        blocks = token_blocks(prompt, pl)
        assert len(blocks) == n // pl
        assert all(len(b) == pl for b in blocks)
        flat = [t for b in blocks for t in b]
        assert flat == prompt[: (n // pl) * pl].tolist()
        lim = int(rng.randint(0, len(blocks) + 1))
        assert token_blocks(prompt, pl, limit=lim) == blocks[:lim]


def test_allocator_random_ops_invariants_property():
    """Seeded random walks over the FULL allocator surface — alloc,
    release, retain, cow — keep every invariant ``check()`` audits:
    free/live partition the pool, refcounts match holders, no page is
    handed out twice, exhaustion never leaks."""
    for seed in (0, 1, 2, 3):
        rng = np.random.RandomState(seed)
        a = PageAllocator(16)
        refs = {}                     # page -> refs WE hold
        for _ in range(300):
            op = rng.rand()
            held = [p for p, c in refs.items() if c > 0]
            if op < 0.35:
                n = int(rng.randint(1, 5))
                try:
                    for p in a.alloc(n):
                        assert refs.get(p, 0) == 0, "page reissued"
                        refs[p] = 1
                except PoolExhausted:
                    assert n > a.free_pages       # only a true OOM
            elif op < 0.65 and held:
                p = held[int(rng.randint(len(held)))]
                a.release(p)
                refs[p] -= 1
            elif op < 0.85 and held:
                p = held[int(rng.randint(len(held)))]
                a.retain(p)
                refs[p] += 1
            elif held:
                p = held[int(rng.randint(len(held)))]
                try:
                    new, copied = a.cow(p)
                except PoolExhausted:
                    continue
                assert copied == (refs[p] > 1)
                if copied:            # writer moved off the share
                    refs[p] -= 1
                    assert refs.get(new, 0) == 0
                    refs[new] = 1
            a.check()
            live = sum(1 for c in refs.values() if c > 0)
            assert a.live_pages == live
            assert a.free_pages == a.usable_pages - live
            for p, c in refs.items():
                assert a.ref(p) == c
        for p, c in refs.items():
            for _ in range(c):
                a.release(p)
        a.check()
        assert a.free_pages == a.usable_pages and a.live_pages == 0


# -- a cache of two layer kinds (PR 34): the control plane ---------------------

@pytest.mark.parametrize("window,tokens,page_len,want", [
    (512, 1, 128, 6),       # a slot that decodes
    (512, 2048, 128, 21),   # while its 2048-token chunk runs
    (512, 256, 128, 7), (8, 1, 4, 4), (8, 16, 4, 7)])
def test_window_page_bound(window, tokens, page_len, want):
    """The bound is tight: a program of ``tokens`` tokens whose first query
    sits anywhere in a page touches at most that many pages of the window."""
    from paddle_tpu.serving.paged_kv import window_page_bound

    assert window_page_bound(window, tokens, page_len) == want
    worst = max((lo + tokens - 1) // page_len
                - max(lo - (window - 1), 0) // page_len + 1
                for lo in range(window, window + 3 * page_len))
    assert worst <= want <= worst + 1


def test_allocator_tracks_its_peak_and_a_two_kind_pool_reports_both():
    import jax.numpy as jnp

    a = PageAllocator(8)
    held = a.alloc(5)
    for p in held[:3]:
        a.release(p)
    a.alloc(2)
    assert (a.peak_live, a.live_pages) == (5, 4)
    pool = PagedKVPool(CacheLayout.parse(
        {"kind": "kv_by_layer", "window": 8,
         "layers": ["full", "window", "window"]}, None, 3, 4, 2, 8),
        10, jnp.float32, prefix_cache=False, window_pages=6)
    pool.allocate(3)
    pool.window_allocator.alloc(2)
    st = pool.stats()
    assert st["cache"] == "kv_by_layer" and st["pages_live"] == 3
    assert st["window"]["pages_live"] == st["window"]["pages_peak"] == 2
    assert st["window"]["pages_total"] == 6 and st["window"]["window"] == 8
    assert pool.live_pages_by_kind() == {"full": 3, "window": 2}
    assert pool.bytes() == sum(pool.bytes_by_kind().values()) == \
        st["pool_bytes"]
    with pytest.raises(ValueError, match="must name 3 layers"):
        CacheLayout.parse({"kind": "kv_by_layer", "window": 8,
                           "layers": ["full", "ring", "window"]},
                          None, 3, 4, 2, 8)
