"""The seam ``paged_kv.CacheLayout``: a served model's ``cache_spec`` is read
in ONE place. Every declared shape of it the ten served models use is held to
what the pool and the engine did with it before the parse was one (arenas,
tables, who carries a round, what is refused and in which words); the
malformed specs stay rejected; and nothing in ``serving/generation.py`` or
``serving/served_model.py`` reads the dict again."""
import ast
import os

import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.serving.paged_kv import CacheLayout, PagedKVPool
from test_step_parts import MODELS

# The engine's words, by what the cache is ({model}: the served class; the
# same strings ``docs/serving.md`` quotes).
STATE = "{model} carries recurrent state per slot: "
WINDOW = ("{model} keeps a sliding window of {window} keys in some of its "
          "layers, whose pages go back to the pool as the window passes them: ")
WORDS = {
    ("prefix_cache", "state"): STATE + (
        "a cached K/V prefix has no state to resume from, so the prefix "
        "cache cannot serve it — pass GenerationConfig(prefix_cache=False)"),
    ("prefix_cache", "window"): WINDOW + (
        "a cached prefix's pages behind the window are gone, so the prefix "
        "cache cannot serve it — pass GenerationConfig(prefix_cache=False)"),
    ("draft_model", "state"): STATE + (
        "a rejected draft token would have advanced it and it cannot be "
        "rolled back, so speculative decoding is refused — pass "
        "draft_model=None"),
    ("draft_model", "window"): WINDOW + (
        "a verify round that rejects draft tokens would have to take back "
        "pages already given away, so speculative decoding is refused — "
        "pass draft_model=None"),
    ("draft_model", "index"): (
        "{model} attends the keys an indexer selects: a verify window of "
        "draft tokens would select with them in the cache and no test holds "
        "that path yet, so speculative decoding is refused — pass "
        "draft_model=None"),
    ("warm_pool", "unpaged"): (
        "{model} keeps no K/V pages at all: the warm tier has nothing to "
        "spill or restore — pass GenerationConfig(warm_pool_bytes=0)"),
    ("warm_pool", "mixed"): (
        "{model} keeps pages in some of its layers and a recurrent state in "
        "others (or both in one): the warm tier spills and restores prefixes "
        "of pages, and a prefix's state is in none — pass "
        "GenerationConfig(warm_pool_bytes=0)"),
    ("warm_pool", "window"): WINDOW + (
        "the warm tier spills and restores whole prefixes — pass "
        "GenerationConfig(warm_pool_bytes=0)"),
    ("warm_pool", "latent"): (
        "{model} caches one latent row a token: the warm tier spills and "
        "restores K/V pages — pass GenerationConfig(warm_pool_bytes=0)"),
    ("kv_transfer", "unpaged"): (
        "{model} keeps no K/V pages at all — a sequence is its recurrent "
        "state, and no state snapshot is shipped"),
    ("kv_transfer", "window"): (
        "{model} keeps a sliding window in some of its layers — their pages "
        "behind the window have gone back to the pool, so a prompt's cache "
        "cannot be read out or installed page by page"),
    ("kv_transfer", "latent"): (
        "{model} caches one latent row a token — the page shipper's wire "
        "format is K and V stacks of [pages, page_len, heads, dim] and "
        "cannot carry it yet"),
    ("kv_transfer", "state"): (
        "{model} carries recurrent state per slot — its K/V pages alone do "
        "not resume a sequence, and no state snapshot is shipped with them"),
}

# model -> what its tiny preset's cache comes to in an engine of 3 slots, 64
# positions, pages of 4 and buckets (8, 16): the layout's booleans (paged,
# latent, ranged, by_layer), the K arenas' shapes as (count, shape) runs, the
# V arenas' (None: as K), state arenas, the tables of 3 rows, layers by table
# kind, window, carries_rounds, and which words refuse which feature
FACTS = {
    # cache_spec None
    "gpt2": dict(
        kind="kv", is_=(True, False, False, False),
        k=[(2, (81, 4, 4, 8))], v=None, state=0, tables=(3, 16),
        layers_of={"full": 2}, window=0, carries=False, refused={}),
    # None + state_spec
    "falcon_h1": dict(
        kind="kv", is_=(True, False, False, False),
        k=[(2, (81, 4, 2, 8))], v=None, state=2, tables=(3, 16),
        layers_of={"full": 2}, window=0, carries=False,
        refused={"prefix_cache": "state", "draft_model": "state",
                 "kv_transfer": "state"}),
    # latent
    "openpangu": dict(
        kind="latent", is_=(True, True, True, False),
        k=[(3, (81, 4, 128))], v=[], state=0, tables=(3, 16),
        layers_of={"full": 3}, window=0, carries=True,
        refused={"warm_pool": "latent", "kv_transfer": "latent"}),
    "xing4": dict(
        kind="latent", is_=(True, True, True, False),
        k=[(3, (81, 4, 128))], v=[], state=0, tables=(3, 16),
        layers_of={"full": 3}, window=0, carries=True,
        refused={"warm_pool": "latent", "kv_transfer": "latent"}),
    # latent + index
    "glm_dsa": dict(
        kind="latent", is_=(True, True, True, False),
        k=[(5, (81, 4, 128))], v=[(2, (81, 4, 8))], state=0, tables=(3, 16),
        layers_of={"full": 5}, window=0, carries=True,
        refused={"draft_model": "index", "warm_pool": "latent",
                 "kv_transfer": "latent"}),
    # latent + layers + window_row + index
    "dots3_note": dict(
        kind="latent", is_=(True, True, True, True),
        k=[(2, (81, 4, 128)), (3, (37, 4, 128))], v=[(2, (81, 4, 8))],
        state=0, tables=(2, 3, 16), layers_of={"full": 2, "window": 3},
        window=9, carries=True,
        refused={"prefix_cache": "window", "draft_model": "window",
                 "warm_pool": "window", "kv_transfer": "window"}),
    # kv_by_layer with a window
    "laguna": dict(
        kind="kv_by_layer", is_=(True, False, True, True),
        k=[(1, (81, 2, 4, 16)), (3, (34, 2, 4, 16)), (1, (81, 2, 4, 16))],
        v=None, state=0, tables=(2, 3, 16),
        layers_of={"full": 2, "window": 3}, window=8, carries=True,
        refused={"prefix_cache": "window", "draft_model": "window",
                 "warm_pool": "window", "kv_transfer": "window"}),
    # kv_by_layer with "state" / "none" layers
    "nemotron_h": dict(
        kind="kv_by_layer", is_=(True, False, True, True),
        k=[(1, (81, 2, 4, 8))], v=None, state=3, tables=(1, 3, 16),
        layers_of={"full": 1}, window=0, carries=True,
        refused={"prefix_cache": "state", "draft_model": "state",
                 "warm_pool": "mixed", "kv_transfer": "state"}),
    # kv_by_layer with "full+state" layers
    "zaya1": dict(
        kind="kv_by_layer", is_=(True, False, True, True),
        k=[(3, (81, 2, 4, 8))], v=None, state=3, tables=(1, 3, 16),
        layers_of={"full": 3}, window=0, carries=True,
        refused={"prefix_cache": "state", "draft_model": "state",
                 "warm_pool": "mixed", "kv_transfer": "state"}),
    # none
    "brumby": dict(
        kind="none", is_=(False, False, False, False),
        k=[], v=None, state=2, tables=(3, 0), layers_of={"full": 0},
        window=0, carries=False,
        refused={"prefix_cache": "state", "draft_model": "state",
                 "warm_pool": "unpaged", "kv_transfer": "unpaged"}),
}
ASKS = {"prefix_cache": dict(prefix_cache=True),
        "draft_model": dict(draft_model=object()),
        "warm_pool": dict(warm_pool_bytes=1 << 20)}


def _model(name):
    cls, cfg = MODELS[name]
    paddle.seed(3)
    model = cls(cfg())
    model.eval()
    return model


def _engine(model, **kw):
    return serving.GenerationEngine(model, serving.GenerationConfig(**{
        **dict(max_slots=3, max_seq_len=64, page_len=4,
               prefill_buckets=(8, 16), prefix_cache=False), **kw}))


def _runs(shapes):
    return [shape for n, shape in shapes for _ in range(n)]


def words(name, feature, model):
    fact = FACTS[name]
    return WORDS[feature, fact["refused"][feature]].format(
        model=type(model).__name__, window=fact["window"])


@pytest.mark.parametrize("name", sorted(FACTS))
def test_the_layout_of_every_declared_cache_shape(name):
    """One parse says what the pool, the builder and the engine each worked
    out from the dict: the arenas, the tables, the layers of each paging
    kind, who carries a round, and what the kind cannot use."""
    fact, model = FACTS[name], _model(name)
    sm = model.served_model()
    layout = sm.cache_layout(4)
    assert layout.kind == fact["kind"]
    assert (layout.paged, layout.latent, layout.ranged,
            layout.by_layer) == fact["is_"]
    assert layout.stateful == (sm.state_spec is not None) == \
        bool(fact["state"])
    assert layout.layers_of == fact["layers_of"]
    assert layout.table_kinds == tuple(fact["layers_of"])
    assert layout.window == fact["window"]
    assert sm.carries_rounds is fact["carries"]
    assert len(layout.keeps) == sm.num_layers
    assert sum(kind is not None for kind, _row in layout.keeps) == \
        len(_runs(fact["k"]))
    assert set(layout.refuses) == set(fact["refused"])
    eng = _engine(model)
    pool = eng._pool
    assert pool.layout == layout and eng._layout is pool.layout
    assert [a.shape for a in pool.k] == _runs(fact["k"])
    assert [a.shape for a in pool.v] == _runs(
        fact["k"] if fact["v"] is None else fact["v"])
    assert len(pool.state or ()) == fact["state"]
    assert pool.tables_shape(3) == fact["tables"]
    assert pool.layer_kinds == (sm.cache_spec or {}).get("layers")
    assert pool.stats()["cache"] == fact["kind"]
    eng.close()


@pytest.mark.parametrize("name", sorted(FACTS))
def test_what_a_cache_kind_cannot_use_is_refused_in_the_same_words(name):
    """The constructor's refusals and ``export`` / ``install_kv_pages``' are
    one table by feature; each user-facing message is the one it was."""
    fact, model = FACTS[name], _model(name)
    for feature, ask in ASKS.items():
        if feature in fact["refused"]:
            with pytest.raises(ValueError) as e:
                _engine(model, **ask)
            assert str(e.value) == words(name, feature, model)
        elif feature != "draft_model":      # (a draft needs a real model)
            _engine(model, **ask).close()
    eng = _engine(model)
    for what in ("export_kv_pages", "install_kv_pages"):
        if "kv_transfer" in fact["refused"]:
            with pytest.raises(RuntimeError) as e:
                getattr(eng, what)(list(range(8)), *([[], []] * (
                    what == "install_kv_pages")))
            assert str(e.value) == f"{what}: " + words(
                name, "kv_transfer", model)
        else:
            eng._refuse_kv_transfer(what)
    eng.close()


STATE_SPEC = {"s": ((2,), jnp.float32)}
MALFORMED = {
    "unknown_kind": ({"kind": "ring"}, None,
                     "unknown cache kind 'ring': a served model's cache_spec "
                     "is None (K and V), 'latent', 'kv_by_layer' or 'none'"),
    "none_without_state": ({"kind": "none"}, None,
                           "cache_spec of kind 'none' with no state_spec: "
                           "the model would remember nothing"),
    "unknown_layer": ({"kind": "kv_by_layer", "layers": ["full", "ring"]},
                      None, "cache_spec['layers'] must name 2 layers 'full' "
                      "or 'window' (pages), 'state' or 'none', or "
                      "'full+state' (pages and a state row), got "
                      "['full', 'ring']"),
    "too_few_layers": ({"kind": "kv_by_layer", "layers": ["full"]}, None,
                       "cache_spec['layers'] must name 2 layers"),
    "state_layer_without_spec": (
        {"kind": "kv_by_layer", "layers": ["full", "state"]}, None,
        "cache_spec['layers'] names a layer that keeps state ('state', "
        "'full+state') exactly where the model declares a state_spec: got "
        "['full', 'state'] and state_spec None"),
    "spec_without_state_layer": (
        {"kind": "kv_by_layer", "layers": ["full", "none"]}, STATE_SPEC,
        "exactly where the model declares a state_spec"),
    "pages_nothing": (
        {"kind": "kv_by_layer", "layers": ["state", "none"]}, STATE_SPEC,
        "cache_spec['layers'] ['state', 'none'] pages nothing: a model with "
        "nothing paged declares a cache_spec of kind 'none'"),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_specs_stay_rejected(case):
    spec, state_spec, message = MALFORMED[case]
    with pytest.raises(ValueError) as e:
        CacheLayout.parse(spec, state_spec, 2, 4, 2, 8)
    assert message in str(e.value)


@pytest.mark.parametrize("spec,state_spec,message", [
    ({"kind": "kv_by_layer", "layers": ["full", "full"]}, None,
     "a cache of two layer kinds has no prefix cache and no warm tier: a "
     "shared page behind a window has been given back, and a layer's "
     "recurrent state is in no page"),
    ({"kind": "none"}, STATE_SPEC,
     "a model with nothing paged has no prefix cache and no warm tier: "
     "there is no page to share or spill")])
def test_a_pool_whose_pages_cannot_be_shared_takes_no_prefix_cache(
        spec, state_spec, message):
    layout = CacheLayout.parse(spec, state_spec, 2, 4, 2, 8)
    with pytest.raises(ValueError) as e:
        PagedKVPool(layout, 8, jnp.float32, prefix_cache=True, max_slots=2)
    assert str(e.value) == message
    PagedKVPool(layout, 8, jnp.float32, prefix_cache=False, max_slots=2)


@pytest.mark.parametrize("module", ["generation", "served_model"])
def test_nothing_outside_the_parse_reads_a_cache_spec(module):
    """In the manner of ``test_kernel_seam.py``: in the engine's and the
    protocol's modules nothing subscripts or ``.get``s an attribute named
    ``cache_spec`` and nothing compares against a kind's name — the format
    is ``paged_kv.CacheLayout``'s alone. (``_attention``'s choice of kernel
    by the NAME it is asked for is one decision in one place and stays.)"""
    path = os.path.join(os.path.dirname(serving.__file__), module + ".py")
    with open(path) as f:
        tree = ast.parse(f.read())

    def is_spec(node):
        return isinstance(node, ast.Attribute) and node.attr == "cache_spec"

    bad = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript) and is_spec(node.value):
            bad.append((node.lineno, "cache_spec[...]"))
        if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and is_spec(node.func.value):
            bad.append((node.lineno, f"cache_spec.{node.func.attr}(...)"))
        if isinstance(node, ast.Compare):
            for side in [node.left] + node.comparators:
                if isinstance(side, ast.Constant) and side.value in (
                        "kv_by_layer",):
                    bad.append((node.lineno, f"== {side.value!r}"))
        # the spec bound to a local and read through that
        if isinstance(node, ast.Assign) and is_spec(node.value):
            bad.append((node.lineno, "a local alias of cache_spec"))
    assert not bad, f"{module}.py reads the cache_spec format: {bad}"
