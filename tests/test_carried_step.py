"""The carried step (``serving.generation._build_window_step(carry=...)``):
in an engine whose served model qualifies (``ServedModel.carries_rounds``: a
latent cache or a cache by layer kind, and a recurrent state only if it
resumes) a prompt's prefill call of the LARGEST bucket also runs the running
sequences' decode step. Greedy token streams must be the ones the same requests get from
row-only prefill calls and rounds of their own; the counters must add up to
what was served; and set-up must build as many window programs as it did,
one signature each, with GPT-2's and Falcon-H1's untouched."""
import hashlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
from paddle_tpu.models.falcon_h1 import FalconH1Config, FalconH1ForCausalLM
from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                           GlmMoeDsaForCausalLM)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                          NemotronHForCausalLM)
from paddle_tpu.models.openpangu_moe import (OpenPanguMoEConfig,
                                             OpenPanguMoEForCausalLM)
from paddle_tpu.serving import generation as gen
from paddle_tpu.serving import served_model
from paddle_tpu.serving.paged_kv import PoolExhausted

C = 16                  # the largest bucket: its program carries
BUCKETS = (8, C)


def _tiny(kind):
    paddle.seed(11)
    if kind == "laguna":     # window 8 = one page: pages come and go
        cfg = LagunaConfig.tiny()
        return cfg, LagunaForCausalLM(cfg)
    if kind == "openpangu":
        cfg = OpenPanguMoEConfig.tiny()
        return cfg, OpenPanguMoEForCausalLM(cfg)
    if kind == "glm_dsa":    # 6 index keys a query: every context outgrows them
        cfg = GlmMoeDsaConfig.tiny()
        return cfg, GlmMoeDsaForCausalLM(cfg)
    if kind == "falcon_h1":
        cfg = FalconH1Config.tiny()
        return cfg, FalconH1ForCausalLM(cfg)
    if kind == "brumby":     # a state that resumes, NOTHING paged
        cfg = BrumbyConfig.tiny()
        return cfg, BrumbyForCausalLM(cfg)
    if kind == "nemotron_h":  # a state that resumes, pages by layer kind
        cfg = NemotronHConfig.tiny()
        return cfg, NemotronHForCausalLM(cfg)
    cfg = GPTConfig.tiny()
    return cfg, GPTForCausalLM(cfg)


@pytest.fixture(scope="module", params=["laguna", "openpangu", "glm_dsa"])
def served(request):
    return (request.param,) + _tiny(request.param)


def _engine(model, carry=True, **over):
    kw = dict(max_slots=4, max_seq_len=128, page_len=8,
              prefill_buckets=BUCKETS, prefix_cache=False)
    kw.update(over)
    eng = serving.GenerationEngine(model, serving.GenerationConfig(**kw))
    if not carry:
        # switched off at the call: every prefill program is built row-only
        # and the worker finds no call that carries (no user-facing flag)
        eng._carried_rows = lambda W: 0
    return eng


def _serve(eng, first, later, hook=None):
    """``first`` are queued before the worker's first turn; ``later`` arrive
    from the worker's own thread, in the emit of the second token the
    engine hands out (so they find sequences running)."""
    held, seen = [], []

    def arrive(_tok, _lp):
        seen.append(1)
        if len(seen) == 2:
            held.extend(eng.submit(p, max_new_tokens=n, return_logprobs=True)
                        for p, n in later)

    eng.start = lambda: eng
    futs = [eng.submit(p, max_new_tokens=n, return_logprobs=True,
                       on_token=None if i else arrive)
            for i, (p, n) in enumerate(first)]
    del eng.start
    if hook is not None:
        hook(eng)
    with eng:
        done = [f.result(timeout=300) for f in futs]
        done += [f.result(timeout=300) for f in held]
        stats = eng.stats()
    assert len(done) == len(first) + len(later)
    return done, stats


def _prompts(cfg, lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n) for n in lens]


def _spans(eng, name):
    from paddle_tpu.observability.trace.request_trace import tracer

    return [r["args"] for r in tracer().worker_spans()
            if r["thread"].endswith(eng.name) and r["name"] == name]


def _chunk_spans(eng):
    return _spans(eng, "pt.serve.prefill_chunk")


def _round_spans(eng):
    return _spans(eng, "pt.serve.decode_round")


def _lies_once(eng):
    """The pool says yes once when it should say no: the join behind it
    raises ``PoolExhausted`` and the prompt is requeued between two calls."""
    real, told = eng._pool.can_allocate, []

    def can_allocate(*a, **kw):
        ok = real(*a, **kw)
        if not ok and not told:
            told.append(1)
            return True
        return ok

    eng._pool.can_allocate = can_allocate


# name -> (first: [(prompt_len, max_new)], later, engine options, hook)
SCENARIOS = {
    # nobody runs: the carrying program's decode rows are all idle
    "idle_engine": ([(3 * C + 5, 4)], [], {}, None),
    # two rows run; the first one's budget ends while the long prompt's
    # chunks go by, so a later chunk's round has a row less
    "budget_ends_under_chunks": ([(5, 3), (7, 12)], [(4 * C + 3, 5)], {},
                                 None),
    # decode rows cross page and window boundaries while chunks carry them
    # (Laguna tiny: window 8 = page_len, a page goes back every 8 tokens)
    "window_pages_turn_over": ([(6, 30), (11, 26)],
                               [(5 * C + 9, 3), (2 * C + 1, 3)], {}, None),
    # the remainder (3 tokens -> bucket 8) carries nothing; the chunks did
    "remainder_in_a_smaller_bucket": ([(5, 8), (9, 8)], [(2 * C + 3, 4)], {},
                                      None),
    # a pool too small for the third prompt until a sequence ends: its join
    # raises PoolExhausted behind a lie of the pool, it is requeued, and the
    # rounds meanwhile free its pages
    "pool_exhausted_requeue": ([(6, 6), (7, 9)], [(3 * C, 4), (2 * C, 3)],
                               dict(num_pages=14, window_pages=40),
                               _lies_once),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_streams_are_those_of_row_only_calls_and_rounds(served, scenario):
    """Token for token, and the logprobs to rounding: one row of ``C + S``
    tokens through the position-wise work is the same numbers as a ``[1, C]``
    row and ``S`` rows of one."""
    kind, cfg, model = served
    first, later, opts, hook = SCENARIOS[scenario]
    fp = _prompts(cfg, [n for n, _ in first], 3)
    lp = _prompts(cfg, [n for n, _ in later], 4)
    runs = {}
    for carry in (True, False):
        eng = _engine(model, carry=carry, **opts)
        done, stats = _serve(eng, list(zip(fp, [n for _, n in first])),
                             list(zip(lp, [n for _, n in later])), hook)
        runs[carry] = (done, stats, _chunk_spans(eng), _round_spans(eng))
    for (full, lps), (want, want_lps) in zip(runs[True][0], runs[False][0]):
        np.testing.assert_array_equal(full, want)
        np.testing.assert_allclose(lps, want_lps, atol=2e-4)

    done, stats, chunks, rounds = runs[True]
    c = stats["counters"]
    # the run without the carry never carried; this one did wherever a call
    # of the largest bucket found a row running
    assert runs[False][1]["counters"].get("rounds_carried_total", 0) == 0
    assert all("carried" in a for a in chunks)
    assert all(a["carried"] == 0 for a in chunks if a["W"] != C)
    carried = [a["carried"] for a in chunks if a["carried"]]
    assert c.get("rounds_carried_total", 0) == len(carried)
    assert (len(carried) > 0) == bool(later)
    if scenario == "pool_exhausted_requeue":
        assert c["admits_requeued"] >= 1
    # what a round counts, counted once, carried or not
    lens = [(len(p), n) for p, n in zip(fp + lp, [n for _, n in first + later])]
    assert c["decode_steps"] == len(carried) + len(rounds)
    assert c["slot_rounds"] == sum(carried) + sum(a["n_active"]
                                                  for a in rounds)
    assert c["tokens_total"] == c["slot_rounds"] == sum(n - 1
                                                        for _, n in lens)
    # token j of a request is fed at position p + j - 1 and sees p + j keys
    assert c["attn_keys_decode_total"] == sum(
        p + j for p, n in lens for j in range(1, n))
    assert stats["carried_round_rate"] == round(
        len(carried) / c["decode_steps"], 4)
    layers = sum(t == "sparse" for t in cfg.mlp_layer_types) \
        if kind == "laguna" else \
        cfg.num_hidden_layers - cfg.first_k_dense_replace
    assert c["moe_pairs_total"] == cfg.num_experts_per_tok * layers * sum(
        p + n - 1 for p, n in lens)
    # and the same totals as the run with rounds of their own
    want = runs[False][1]["counters"]
    for name in ("tokens_total", "slot_rounds", "attn_keys_decode_total",
                 "moe_pairs_total", "prompt_tokens_total", "prefills_total",
                 "prefill_chunks_total"):
        assert c[name] == want[name], name


def test_an_eos_row_is_the_one_wasted_row(served):
    """A request that ends on EOS while the next chunk, dispatched behind the
    one that emitted it, still holds a row for it: that row's token is
    dropped, its pages have gone back, and every stream is still the one of
    row-only calls and rounds."""
    kind, cfg, model = served
    fp = _prompts(cfg, (5, 7), 5)
    lp = _prompts(cfg, (5 * C + 2,), 6)
    first, later = list(zip(fp, (14, 14))), list(zip(lp, (4,)))
    free, _stats = _serve(_engine(model), first, later)
    # the first request's fifth token ends it: it falls under the chunks
    eos = int(free[0][0][len(fp[0]) + 4])
    runs = {}
    for carry in (True, False):
        eng = _engine(model, carry=carry, eos_token_id=eos)
        runs[carry] = _serve(eng, first, later)
        if carry:
            chunks = _chunk_spans(eng)
    for (full, lps), (want, want_lps) in zip(runs[True][0], runs[False][0]):
        np.testing.assert_array_equal(full, want)
        np.testing.assert_allclose(lps, want_lps, atol=2e-4)
    got = runs[True][0][0][0]
    assert len(got) <= len(fp[0]) + 5 and got[-1] == eos
    c = runs[True][1]["counters"]
    assert c["rounds_carried_total"] >= 4
    # a row more than tokens wherever the EOS row rode one chunk too many
    assert 0 <= c["slot_rounds"] - c["tokens_total"] <= 2
    assert max(a["carried"] for a in chunks) == 2


def test_requests_that_arrive_together_get_slots_of_their_own(served):
    """As many requests as slots, queued together on a drained engine: a
    carried round ends the first (one token left) while the second's chunks
    go by, and the third still gets a slot nobody has used — a free slot is
    the one longest free, so what a request left in its slot (``slot_state``,
    a check's) outlives its neighbours' admission."""
    kind, cfg, model = served
    prompts = _prompts(cfg, (5, 3 * C + 5, 6), 9)
    eng = _engine(model, max_slots=3)
    _serve(eng, list(zip(prompts, (2, 3, 3))), [])
    joined = _spans(eng, "pt.serve.admit")
    assert [a["prompt_len"] for a in joined] == [len(p) for p in prompts]
    assert sorted(a["slot"] for a in joined) == [0, 1, 2]
    # the first had ended before the third joined: its slot was free then
    assert eng.stats()["counters"]["rounds_carried_total"] >= 1
    assert eng._free_slot() == 0


# -- the set-up: as many programs as ever, one signature each -------------------

def _digest(fn, args) -> str:
    """Of the lowered text, the module's name apart: a window program is
    named from its label (``jit_pt_prefill16``, PR 37)."""
    import re

    from paddle_tpu.jit import lowerable

    text = lowerable(fn).lower(*args).as_text()
    return hashlib.sha256(
        re.sub(r"module @\S+", "module @m", text, count=1).encode()
    ).hexdigest()


def _operands(eng, rows, W, prefill):
    import jax.numpy as jnp

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    return (eng._params, eng._pool.k, eng._pool.v,
            i32(*eng._pool.tables_shape(rows)) if eng._layout.paged else None,
            i32(rows, W), i32(rows), i32(rows),
            None if prefill else eng._pool.state)


def _carrying_operands(eng):
    """The largest bucket's operands as its carrying program takes them:
    every one after the arenas a (prompt's, round's) pair — the state too,
    of a model that keeps one (from zero: no row beside the arenas)."""
    S = eng.config.max_slots
    lone, rnd = _operands(eng, 1, C, True), _operands(eng, S, 1, False)
    return lone[:3] + tuple(zip(lone[3:7], rnd[3:7])) + (
        (None, rnd[7]) if eng._layout.stateful else None,)


@pytest.mark.parametrize("kind,draft", [
    ("gpt2", False), ("gpt2", True), ("falcon_h1", False),
    ("openpangu", False), ("laguna", False), ("brumby", False),
    ("nemotron_h", False)])
def test_warmup_builds_the_parents_programs_and_requests_none(kind, draft,
                                                              tmp_path):
    """``warmup()`` builds one window program a bucket and one a round (two
    with a draft model): the carrying program takes the largest bucket's
    place, it does not stand beside it. Each is looked up ONCE, in warm-up:
    requests — chunked, carrying, fed from the device in every form — meet
    no new signature, no cache lookup and no XLA compile."""
    from test_paged_serving import _count_backend_compiles

    from paddle_tpu.jit import persistent_cache as pc

    cfg, model = _tiny(kind)
    extra = dict(prefix_cache=kind == "gpt2")
    if draft:
        paddle.seed(2)
        extra.update(spec_tokens=3, draft_model=GPTForCausalLM(GPTConfig(
            vocab_size=cfg.vocab_size, hidden_size=16, num_hidden_layers=1,
            num_attention_heads=2, max_position_embeddings=128,
            dtype="float32")))
    old_dir, old_enabled = pc.cache_dir(), pc.is_enabled()
    pc.enable(str(tmp_path / "cache"))
    pc.reset_stats()
    compiles, unregister = _count_backend_compiles()
    try:
        import jax

        eng = _engine(model, **extra)
        # as on the chip, where the served weights and the arenas are
        # COMMITTED to their device and so is every program's output: a
        # round's tokens are then one signature whether they come from the
        # host (``device_put``) or from a program (on the CPU backend
        # neither is committed, and the forms would be signatures of their
        # own here alone)
        pool = eng._pool
        eng._params, pool.k, pool.v, pool.state = jax.device_put(
            (eng._params, pool.k, pool.v, pool.state), eng._device)
        eng.warmup()
        S = eng.config.max_slots
        want = {(S, 1, False)} | {(1, b, True) for b in BUCKETS} | \
            ({(S, 4, False)} if draft else set())
        assert set(eng._windows) == want
        carries = kind in ("openpangu", "laguna", "nemotron_h")
        assert eng._sm.carries_rounds == carries
        assert [eng._carried_rows(b) for b in BUCKETS] == \
            [0, S if carries else 0]
        by_label = pc.stats()["by_label"]
        for key in want:
            role = "prefill" if key[2] else "window"
            row = by_label[f"serving:{eng.name}:{role}{key[1]}"]
            # one signature: one lookup, whatever form its tokens came in —
            # and a second for a prefill program whose block resumes (from
            # zero; from the state a chunk left): carrying or not, no more
            forms = 2 if key[2] and eng._sm.resumes_state else 1
            assert row["hits"] + row["misses"] == forms, (key, row)
        warm, n_compiles = pc.stats(), len(compiles)
        assert n_compiles > 0
        lens = [(5, 6), (7, 9), (3 * C + 2, 3), (12, 4), (2 * C, 3), (6, 3)]
        if kind in ("gpt2", "falcon_h1"):   # neither chunks a prompt
            lens = [(min(p, C), n) for p, n in lens]
        prompts = _prompts(cfg, [p for p, _ in lens], 8)
        _serve(eng, list(zip(prompts[:2], [n for _, n in lens[:2]])),
               list(zip(prompts[2:], [n for _, n in lens[2:]])))
        assert set(eng._windows) == want
        run = pc.stats()
        assert run["by_label"] == warm["by_label"]   # not even a lookup
        assert len(compiles) == n_compiles, compiles[n_compiles:]
        if carries:
            assert eng.stats()["counters"]["rounds_carried_total"] >= 2
    finally:
        unregister()
        pc.disable()
        pc.reset_stats()
        if old_enabled and old_dir:
            pc.enable(old_dir)


# what a served model declares -> whether its largest bucket carries
QUALIFIES = {
    "gpt2": (False, False, None, False),
    "falcon_h1": (True, False, None, False),
    "brumby": (True, True, "none", False),
    "nemotron_h": (True, True, "kv_by_layer", True),
    "openpangu": (False, False, "latent", True),
    "laguna": (False, False, "kv_by_layer", True),
    "glm_dsa": (False, False, "latent", True),
}


@pytest.mark.parametrize("kind", sorted(QUALIFIES))
def test_who_carries_follows_from_three_declarations(kind):
    """``carries_rounds`` is derived: a cache whose kernel takes each row's
    own range (``cache_spec["kind"]`` latent or by layer), and a recurrent
    state only if the block resumes it. Nemotron-H carries; Falcon-H1 (no
    ranged cache, does not resume), Brumby (resumes, nothing paged) and GPT-2
    do not. The property reads nothing else: the same three facts on a bare
    ``ServedModel`` give the same answer, and no attribute sets it."""
    _cfg, model = _tiny(kind)
    sm = gen._served(model)
    stateful, resumes, cache, carries = QUALIFIES[kind]
    assert (sm.state_spec is not None, sm.resumes_state,
            (sm.cache_spec or {}).get("kind")) == (stateful, resumes, cache)
    assert sm.carries_rounds is carries
    bare = served_model.ServedModel()
    bare.state_spec, bare.resumes_state = sm.state_spec, resumes
    bare.cache_spec = None if cache is None else {"kind": cache}
    assert bare.carries_rounds is carries
    with pytest.raises(AttributeError):
        bare.carries_rounds = True
    # a state that does NOT resume keeps any cache out
    bare.state_spec, bare.resumes_state = {"s": ((1,), "float32")}, False
    assert not bare.carries_rounds


@pytest.mark.parametrize("kind", ["gpt2", "falcon_h1", "brumby"])
def test_models_that_do_not_qualify_keep_their_programs(kind):
    """GPT-2 (``pt_paged_attention`` walks every page of every slot),
    Falcon-H1 (the same kernel, and a prefill starts its state from zero)
    and Brumby (a state that resumes, but nothing paged: no kernel of its
    takes a round's rows beside a chunk's) do not qualify: the text the
    engine lowers for each of their window programs is the text of the
    builder with the carry switched off at the call, letter for letter — and
    asking the builder for a carrying program of theirs is refused."""
    cfg, model = _tiny(kind)
    eng = _engine(model, prefix_cache=kind == "gpt2")
    assert not eng._sm.carries_rounds
    S = eng.config.max_slots
    for rows, W, prefill in [(S, 1, False)] + [(1, b, True) for b in BUCKETS]:
        args = _operands(eng, rows, W, prefill)
        plain = gen._build_window_step(
            eng._sm, rows, eng._n_blocks, eng._pl, W, eng._donate,
            label="plain", prefill=prefill, carry=0)
        assert _digest(eng._window(rows, W, prefill), args) == \
            _digest(plain, args)
    with pytest.raises(ValueError, match="carries_rounds"):
        gen._build_window_step(eng._sm, 1, eng._n_blocks, eng._pl, C, False,
                               label="refused", prefill=True, carry=S)


def test_only_the_largest_bucket_of_a_qualifying_model_grew(served):
    """The decode program and the smaller buckets' programs of a model that
    qualifies are the programs of the builder with the carry off (their cache
    entries are the parent's); the largest bucket's is another, and takes
    each operand as a pair."""
    kind, cfg, model = served
    eng = _engine(model)
    S = eng.config.max_slots
    for rows, W, prefill in [(S, 1, False), (1, BUCKETS[0], True)]:
        args = _operands(eng, rows, W, prefill)
        plain = gen._build_window_step(
            eng._sm, rows, eng._n_blocks, eng._pl, W, eng._donate,
            label="plain", prefill=prefill, carry=0)
        assert _digest(eng._window(rows, W, prefill), args) == \
            _digest(plain, args)
    lone = _operands(eng, 1, C, True)
    plain = gen._build_window_step(eng._sm, 1, eng._n_blocks, eng._pl, C,
                                   eng._donate, label="plain", prefill=True)
    pair = _carrying_operands(eng)
    assert _digest(eng._window(1, C, True), pair) != _digest(plain, lone)
    with pytest.raises(Exception):
        eng._window(1, C, True)(*lone)   # no row-only form stands beside it


def _unreachable(*_a, **_kw):
    raise AssertionError("the state arm was reached")


def test_a_carrying_program_without_state_is_what_it_was(served, monkeypatch):
    """The state pair is one more arm of the ONE ``step`` body, Python at
    trace time: with that arm made unreachable — whatever builds a state
    layer's pair raises — the builder still gives a latent and a by-layer
    model WITHOUT state their carrying program, and its lowered text is the
    engine's own, letter for letter: nothing of the arm is traced for them."""
    kind, cfg, model = served
    eng = _engine(model)
    S = eng.config.max_slots
    pair = _carrying_operands(eng)
    mine = _digest(eng._window(1, C, True), pair)

    monkeypatch.setattr(gen, "Carried", _unreachable)
    sealed = gen._build_window_step(
        eng._sm, 1, eng._n_blocks, eng._pl, C, eng._donate, label="sealed",
        prefill=True, carry=S, aligned=eng._aligned)
    assert _digest(sealed, pair) == mine


def test_a_state_models_carrying_program_takes_the_state_as_a_pair(
        monkeypatch):
    """Nemotron-H's largest bucket: ONE program in the row-only one's place,
    in both its forms (from zero; from the state a chunk left), each taking
    the (row, arenas) pair and handing back the chunk's final row and the
    arenas; its decode program and its smaller bucket are the builder's with
    the carry off; and with the arm unreachable its build fails — the arm is
    what carries its state."""
    import jax
    import jax.numpy as jnp

    cfg, model = _tiny("nemotron_h")
    eng = _engine(model, page_len=4)
    S = eng.config.max_slots
    assert [eng._carried_rows(b) for b in BUCKETS] == [0, S]
    for rows, W, prefill in [(S, 1, False), (1, BUCKETS[0], True)]:
        args = _operands(eng, rows, W, prefill)
        plain = gen._build_window_step(
            eng._sm, rows, eng._n_blocks, eng._pl, W, eng._donate,
            label="plain", prefill=prefill, carry=0)
        assert _digest(eng._window(rows, W, prefill), args) == \
            _digest(plain, args)
    from paddle_tpu.jit import lowerable

    zero = _carrying_operands(eng)
    fn = eng._window(1, C, True)

    def state_out(args):
        return jax.tree_util.tree_map(
            lambda a: str(a.shape), lowerable(fn).lower(*args).out_info[4])

    arenas = jax.tree_util.tree_map(lambda a: str(a.shape), eng._pool.state)
    a_row = jax.tree_util.tree_map(lambda a: str((1,) + a.shape[1:]),
                                   eng._pool.state)
    assert state_out(zero) == (a_row, arenas)
    row = jax.tree_util.tree_map(
        lambda a: jnp.zeros((1,) + a.shape[1:], a.dtype), eng._pool.state)
    resumed = zero[:7] + ((row, zero[7][1]),)
    assert _digest(fn, resumed) != _digest(fn, zero)
    assert state_out(resumed) == (a_row, arenas)
    with pytest.raises(Exception):
        fn(*_operands(eng, 1, C, True))   # no row-only form beside it
    monkeypatch.setattr(gen, "Carried", _unreachable)
    sealed = gen._build_window_step(
        eng._sm, 1, eng._n_blocks, eng._pl, C, eng._donate, label="sealed",
        prefill=True, carry=S, aligned=eng._aligned)
    with pytest.raises(AssertionError, match="state arm was reached"):
        lowerable(sealed).lower(*zero)


def test_pool_exhausted_is_what_the_lie_raises(served):
    """The scenario's hook does what it says: with the pool lying once, a
    join raises ``PoolExhausted`` (and the worker requeues the prompt)."""
    kind, cfg, model = served
    eng = _engine(model, num_pages=10, window_pages=40)
    eng.start = lambda: eng
    futs = [eng.submit(p, max_new_tokens=4)
            for p in _prompts(cfg, (3 * C, 2 * C), 1)]
    del eng.start
    eng._join(gen._Admission(0, eng._next_request()[0]))
    # 7 of 9 pages are taken: a prompt waits and the pool holds none
    assert eng._next_request() == (None, True)
    _lies_once(eng)
    with pytest.raises(PoolExhausted):
        eng._join(gen._Admission(1, eng._next_request()[0]))
    assert eng._free_slot() == 1            # the slot stayed free
    del futs
