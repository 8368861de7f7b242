"""A one-row prefill call whose window is whole pages writes its chunk into
the paged cache page by page (``serving.generation.write_pages``), every
other window program row by row (``write_rows``). The arenas must hold, at
every position a later program may read, what the row scatter — kept here as
the plain reference, a loop over the tokens — leaves there; the scratch page
0 alone may differ, it is never read unmasked. The engine keeps the
alignment the page write rests on, and GPT-2's and Falcon-H1's programs (the
paged ``attend``) never take it."""
import numpy as np
import pytest

from test_carried_step import _tiny   # (cfg, model) of a tiny served model

from paddle_tpu import serving
from paddle_tpu.serving import generation as gen

PL, KVH, HD, DL = 8, 2, 4, 16     # page length, K/V heads, head dim, latent
P, B, W = 12, 6, 16               # pages, blocks a table, the chunk: 2 pages
R = 3                             # rows of the round a carrying call carries

# the prompt's page table by kind of layer. "window": the blocks behind the
# window's first page went back to the pool (0); "latent" is a full table
TABLES = {"full": [5, 2, 9, 7, 0, 0], "window": [0, 0, 9, 7, 0, 0],
          "latent": [5, 2, 9, 7, 0, 0]}
# (start, n_valid): a full chunk; a last chunk whose second page lies past
# the allocation (4 blocks: scratch page only); a prompt's second chunk
CHUNKS = {"full_chunk": (0, 16), "second_chunk": (16, 16),
          "last_chunk_past_allocation": (24, 5)}


def _arena(kind, rng):
    shape = (P, PL, DL) if kind == "latent" else (P, KVH, PL, HD)
    return rng.standard_normal(shape).astype(np.float32)


def _tokens(kind, n, rng):
    shape = (n, DL) if kind == "latent" else (n, KVH, HD)
    return rng.standard_normal(shape).astype(np.float32)


def row_scatter(arena, table, positions, toks):
    """The plain reference: token ``i`` lands at ``positions[i]`` through
    ``table``, one row at a time; a block past the table or with entry 0
    lands in the scratch page."""
    out = arena.copy()
    for pos, tok in zip(positions, toks):
        blk = pos // PL
        page = table[blk] if blk < len(table) else 0
        if out.ndim == 3:
            out[page, pos % PL] = tok
        else:
            out[page, :, pos % PL] = tok
    return out


def _write_chunk(kind, arena, table, start, toks):
    import jax.numpy as jnp

    ids = gen.chunk_pages(jnp.asarray([table], jnp.int32),
                          jnp.asarray([start], jnp.int32), W // PL, PL)
    return gen.write_pages(jnp.asarray(arena), ids, jnp.asarray(toks))


@pytest.mark.parametrize("chunk", list(CHUNKS))
@pytest.mark.parametrize("kind", list(TABLES))
def test_a_chunk_written_as_pages_is_the_row_scatter(kind, chunk):
    rng = np.random.default_rng(7)
    arena, toks = _arena(kind, rng), _tokens(kind, W, rng)
    table, (start, _n_valid) = TABLES[kind], CHUNKS[chunk]
    # the program writes all W rows of its window, real or padding, as the
    # row scatter does: a padded row lands in the prompt's own last page
    # behind its last token, or in the scratch page
    want = row_scatter(arena, table, range(start, start + W), toks)
    got = np.asarray(_write_chunk(kind, arena, table, start, toks))
    np.testing.assert_array_equal(got[1:], want[1:])
    # and nobody else's page was touched: the chunk's own pages apart, the
    # arena is what it was
    own = {table[b] for b in range(start // PL, (start + W) // PL)
           if b < B} | {0}
    others = [p for p in range(P) if p not in own]
    np.testing.assert_array_equal(got[others], arena[others])


@pytest.mark.parametrize("kind", list(TABLES))
def test_a_carrying_call_writes_pages_then_the_rounds_rows(kind):
    """The chunk's pages and the round's rows, one of them idle (an all-zero
    table: the scratch page), as ``_build_window_step``'s ``land`` does under a
    carry."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    arena, toks = _arena(kind, rng), _tokens(kind, W + R, rng)
    table = TABLES[kind]
    r_tables = [[0, 0, 0, 3, 0, 0], [0] * B, [0, 0, 11, 0, 0, 0]]
    r_pos = [29, 0, 16]
    want = row_scatter(arena, table, range(W), toks[:W])
    for t, pos, tok in zip(r_tables, r_pos, toks[W:]):
        want = row_scatter(want, t, [pos], tok[None])
    got = _write_chunk(kind, arena, table, 0, toks[:W])
    rt = jnp.asarray(r_tables, jnp.int32)
    rp = jnp.asarray(r_pos, jnp.int32)[:, None]
    if kind == "latent":
        got = gen.write_rows(got, gen.flat_rows(rt, rp, PL),
                             jnp.asarray(toks[W:]))
    else:
        got = gen.write_rows(got, gen.flat_kv(rt, rp, PL, KVH),
                             jnp.asarray(toks[W:]), lead=3)
    np.testing.assert_array_equal(np.asarray(got)[1:], want[1:])


def test_rows_are_the_row_scatter_too():
    """``write_rows`` over ``flat_kv`` / ``flat_rows`` — every other window
    program's write — against the same reference, at unaligned starts."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    for kind in TABLES:
        arena, toks = _arena(kind, rng), _tokens(kind, 5, rng)
        table = jnp.asarray([TABLES[kind]], jnp.int32)
        pos = jnp.asarray([[19, 20, 21, 22, 23]], jnp.int32)
        if kind == "latent":
            got = gen.write_rows(jnp.asarray(arena),
                                 gen.flat_rows(table, pos, PL),
                                 jnp.asarray(toks))
        else:
            got = gen.write_rows(jnp.asarray(arena),
                                 gen.flat_kv(table, pos, PL, KVH),
                                 jnp.asarray(toks), lead=3)
        want = row_scatter(arena, TABLES[kind], range(19, 24), toks)
        np.testing.assert_array_equal(np.asarray(got)[1:], want[1:])


@pytest.mark.parametrize("case,want", [
    ("one-row prefill of whole pages", 2), ("a decode round", 0),
    ("a verify window", 0), ("a bucket inside a page", 0),
    ("a bucket that ends inside a page", 0), ("the paged attend", 0)])
def test_which_programs_write_pages(case, want):
    rows, window, prefill, paged = {
        "one-row prefill of whole pages": (1, 16, True, False),
        "a decode round": (4, 1, False, False),
        "a verify window": (4, 8, False, False),
        "a bucket inside a page": (1, 4, True, False),
        "a bucket that ends inside a page": (1, 12, True, False),
        "the paged attend": (1, 16, True, True)}[case]
    assert gen._whole_pages(rows, window, PL, prefill, paged) == want


# -- through the engine -------------------------------------------------------

BUCKETS = (4, 8, 16)     # a bucket inside a page, one page, two pages


def _engine(model, buckets=BUCKETS, rows_only=False):
    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=4, max_seq_len=128, page_len=8, prefill_buckets=buckets,
        prefix_cache=False))
    if rows_only:
        # switched off before a program is built: every one scatters rows
        eng._aligned = False
    return eng


def _serve(eng, prompts, new=6):
    with eng:
        futs = [eng.submit(p, max_new_tokens=new, return_logprobs=True)
                for p in prompts]
        done = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    return done, stats


@pytest.mark.parametrize("name", ["laguna", "openpangu"])
def test_an_engine_serves_the_same_from_pages_as_from_rows(name):
    """Prompts of one call, of several (a second chunk, a last chunk with a
    tail of padding) and of a bucket inside a page, with rounds carried:
    tokens, logprobs and the arenas as the row-only engine leaves them."""
    cfg, model = _tiny(name)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (16, 37, 3, 50)]
    rows_eng, pages_eng = _engine(model, rows_only=True), _engine(model)
    by_rows, rows_stats = _serve(rows_eng, prompts)
    by_pages, pages_stats = _serve(pages_eng, prompts)
    for (seq_a, lp_a), (seq_b, lp_b) in zip(by_rows, by_pages):
        np.testing.assert_array_equal(seq_a, seq_b)
        np.testing.assert_allclose(lp_a, lp_b, rtol=0, atol=1e-5)
    c, rc = pages_stats["counters"], rows_stats["counters"]
    assert rc["kv_pages_written_total"] == 0
    # 16 -> 2 pages; 37 -> 16 + 16 + a bucket of 8: 5 pages; 3 -> a bucket
    # of 4, rows; 50 -> 16 x 3, 6 pages, + a bucket of 4 (2 real tokens), rows
    assert c["kv_pages_written_total"] == 13
    assert c["prefill_window_tokens_total"] == \
        rc["prefill_window_tokens_total"] == 13 * 8 + 4 + 4
    assert rc["kv_rows_written_total"] - c["kv_rows_written_total"] == 13 * 8
    for kind in ("k", "v"):
        for a, b in zip(getattr(rows_eng._pool, kind),
                        getattr(pages_eng._pool, kind)):
            np.testing.assert_allclose(np.asarray(a)[1:], np.asarray(b)[1:],
                                       rtol=0, atol=1e-5)


def test_a_largest_bucket_not_of_whole_pages_keeps_every_program_to_rows():
    cfg, model = _tiny("laguna")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (37,)]
    eng = _engine(model, buckets=(8, 12))      # chunks start at 12, 24, ...
    assert not eng._aligned
    assert [eng._chunk_pages(b) for b in (8, 12)] == [0, 0]
    done, stats = _serve(eng, prompts)
    assert stats["counters"]["kv_pages_written_total"] == 0
    assert stats["counters"]["kv_rows_written_total"] > 0
    want, _ = _serve(_engine(model), prompts)
    np.testing.assert_array_equal(done[0][0], want[0][0])


def test_a_page_write_at_an_unaligned_start_is_refused():
    """``_send_chunk`` states the invariant: were a chunk ever to start
    inside a page, a page write would overwrite cached keys."""
    cfg, model = _tiny("laguna")
    eng = _engine(model)
    chunks = eng._prefill_chunks
    eng._prefill_chunks = lambda start, end: [
        (lo + 3, hi, w) for lo, hi, w in chunks(start, end)]
    with eng:
        fut = eng.submit(np.arange(1, 15) % cfg.vocab_size, max_new_tokens=2)
        with pytest.raises(AssertionError):
            fut.result(timeout=300)


@pytest.mark.parametrize("model", ["gpt2", "falcon_h1"])
def test_the_paged_attend_programs_lower_as_they_did(model):
    """GPT-2 and Falcon-H1 write ``[pages, page_len, heads, dim]`` arenas
    through the paged ``attend``: with the page write switched off in the
    engine their window programs lower to the same text, letter for
    letter."""
    from test_step_parts import PROGRAMS, _strip, lowered_programs

    eng, on = lowered_programs(model)
    assert eng._aligned and not any(eng._chunk_pages(b) for b in (8, 16))
    init = serving.GenerationEngine.__init__

    def rows_only(self, *a, **kw):
        init(self, *a, **kw)
        self._aligned = False

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serving.GenerationEngine, "__init__", rows_only)
        eng2, off = lowered_programs(model)
    assert not eng2._aligned
    for prog in PROGRAMS:
        assert _strip(on[prog].as_text()) == _strip(off[prog].as_text())
