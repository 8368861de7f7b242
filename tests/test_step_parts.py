"""The parts of a served model step (``observability.trace.parts``): every
window program of the served models names the part of the model that
asked for each piece of work, the names change nothing but metadata, and the
programs tell themselves apart by name."""
import re

import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import (BrumbyConfig, BrumbyForCausalLM,
                               Dots3NoteConfig, Dots3NoteForCausalLM,
                               FalconH1Config, FalconH1ForCausalLM,
                               GlmMoeDsaConfig, GlmMoeDsaForCausalLM,
                               GPTConfig, GPTForCausalLM, LagunaConfig,
                               LagunaForCausalLM, NemotronHConfig,
                               NemotronHForCausalLM, OpenPanguMoEConfig,
                               OpenPanguMoEForCausalLM, Xing4Config,
                               Xing4ForCausalLM, Zaya1Config,
                               Zaya1ForCausalLM)
from paddle_tpu.observability.trace import parts

MODELS = {
    "gpt2": (GPTForCausalLM, lambda: GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)),
    "falcon_h1": (FalconH1ForCausalLM, FalconH1Config.tiny),
    "openpangu": (OpenPanguMoEForCausalLM, OpenPanguMoEConfig.tiny),
    "laguna": (LagunaForCausalLM, LagunaConfig.tiny),
    "glm_dsa": (GlmMoeDsaForCausalLM, GlmMoeDsaConfig.tiny),
    "brumby": (BrumbyForCausalLM, BrumbyConfig.tiny),
    "dots3_note": (Dots3NoteForCausalLM, Dots3NoteConfig.tiny),
    "xing4": (Xing4ForCausalLM, Xing4Config.tiny),
    "nemotron_h": (NemotronHForCausalLM, NemotronHConfig.tiny),
    "zaya1": (Zaya1ForCausalLM, Zaya1Config.tiny),
}
# decode, the largest prefill bucket (which carries the round where the
# model's ``carries_rounds``), and a smaller bucket
PROGRAMS = {"decode": (3, 1, False), "prefill_largest": (1, 16, True),
            "prefill_small": (1, 8, True)}
HEAVY = ("dot_general", "scatter", "custom_call", "reduce", "sort")


def lowered_programs(name):
    """``{program: jax Lowered}`` of a tiny engine of model ``name``: the
    window programs ``warmup()`` builds, each lowered from the operands of
    its first call."""
    cls, cfg = MODELS[name]
    paddle.seed(3)
    model = cls(cfg())
    model.eval()
    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=3, max_seq_len=64, page_len=4, prefill_buckets=(8, 16),
        prefix_cache=False))
    seen, window = {}, eng._window

    def recording(rows, W, prefill=False):
        fn = window(rows, W, prefill)

        def call(*args):
            seen.setdefault((rows, W, prefill), (fn, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)))
            return fn(*args)

        return call

    eng._window = recording
    eng.warmup()
    del eng._window
    return eng, {prog: seen[key][0].lower(*seen[key][1])
                 for prog, key in PROGRAMS.items()}


@pytest.fixture(scope="module")
def lowered():
    cache = {}

    def get(name):
        if name not in cache:
            cache[name] = lowered_programs(name)
        return cache[name]

    return get


_LOC_DEF = re.compile(r'^(#loc\d+) = loc\((.*)\)$')
_LOC_REF = re.compile(r'loc\((#loc\d+)\)\s*$')
_NAME = re.compile(r'^"([^"]*)"')


def ops_with_name_stacks(text):
    """``[(op, name stack)]`` of a lowered module printed with its debug
    info. An op's location is a ``#loc`` alias whose definition starts with
    the jaxpr name stack in quotes; the ops of a private function (an inner
    ``jax.jit``) carry their stack from the function down, and the ``call``
    the rest — XLA joins the two when it inlines the call, and so does
    this, once a call site."""
    defs = {m.group(1): m.group(2) for line in text.splitlines()
            if (m := _LOC_DEF.match(line))}

    def stack(ref):
        for _ in range(8):       # an alias of an alias: callsite / fused
            body = defs.get(ref, "")
            if (m := _NAME.match(body)):
                return m.group(1)
            nxt = re.search(r"#loc\d+", body)
            if not nxt:
                return ""
            ref = nxt.group(0)
        return ""

    funcs, cur = {}, None
    for line in text.splitlines():
        if (m := re.match(r"\s*func\.func \w+ @(\w+)\(", line)):
            cur = funcs.setdefault(m.group(1), [])
            continue
        ref = _LOC_REF.search(line)
        if cur is None or not ref:
            continue
        if (m := re.search(r"\bcall @(\w+)\(", line)):
            cur.append(("call", m.group(1), stack(ref.group(1))))
        elif (m := re.search(r"\b(?:stablehlo|chlo|mhlo)\.([a-z_]+)", line)):
            cur.append(("op", m.group(1), stack(ref.group(1))))

    out = []

    def walk(fn, prefix):
        for kind, name, own in funcs[fn]:
            full = "/".join(s for s in (prefix, own) if s)
            if kind == "call":
                walk(name, full)
            else:
                out.append((name, full))

    walk("main", "")
    return out


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("model", list(MODELS))
def test_every_heavy_op_sits_under_a_part(lowered, model, program):
    _eng, progs = lowered(model)
    ops = ops_with_name_stacks(progs[program].as_text(debug_info=True))
    heavy = [(op, st) for op, st in ops if op in HEAVY]
    assert len(heavy) > 10
    bare = [(op, st) for op, st in heavy if parts.part_of(st) is None]
    assert not bare, bare[:5]
    used = {parts.part_of(st) for _op, st in heavy}
    want = {"attn_proj", "cache_write", "norm", "head"}
    if model == "brumby":   # nothing paged: no cache to write
        want = {"attn_proj", "attention", "norm", "mlp", "head"}
        assert "cache_write" not in used and "mixer" not in used
    want |= {"mixer"} if model in ("falcon_h1", "nemotron_h") else set()
    want |= {"router", "experts"} if model in (
        "openpangu", "laguna", "glm_dsa", "dots3_note", "xing4",
        "nemotron_h", "zaya1") else set()
    # the shared expert of a stack whose layers are one mixer each
    want |= {"mlp"} if model == "nemotron_h" else set()
    assert want <= used <= set(parts.PARTS), used


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_the_indexer_is_a_scope_inside_parts_not_a_part(lowered, program):
    """The nested ``pt.indexer`` scope marks the index projections (inside
    ``attn_proj``) and the scores and top-k (inside ``attention``): every op
    under it still has one of the ten parts, and both kinds are there."""
    _eng, progs = lowered("glm_dsa")
    ops = ops_with_name_stacks(progs[program].as_text(debug_info=True))
    inside = [(op, st) for op, st in ops if "pt.indexer" in st.split("/")]
    assert len(inside) > 10
    around = {parts.part_of(st) for _op, st in inside}
    assert {"attn_proj", "attention"} <= around <= set(parts.PARTS), around
    assert "indexer" not in parts.PARTS and "indexer" in parts.SUBPARTS
    with pytest.raises(ValueError, match="not a subpart"):
        parts.subpart("attention")


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_the_retention_is_a_scope_inside_attention_not_a_part(lowered,
                                                              program):
    """The nested ``pt.retention`` scope marks a power retention layer's
    kernel calls and their glue: every op under it is ``attention``'s (not
    ``mixer``'s: that part is Falcon-H1's), and the projections, the head
    norms, RoPE and the gate are ``attn_proj``'s."""
    _eng, progs = lowered("brumby")
    ops = ops_with_name_stacks(progs[program].as_text(debug_info=True))
    inside = [(op, st) for op, st in ops if "pt.retention" in st.split("/")]
    assert len(inside) > 10
    assert {parts.part_of(st) for _op, st in inside} == {"attention"}
    assert parts.SUBPARTS[:3] == ("indexer", "retention", "mhc")
    assert "retention" not in parts.PARTS
    gate = [st for op, st in ops if op == "logistic" or "log_sigmoid" in st]
    assert gate and all(parts.part_of(st) == "attn_proj" for st in gate)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_the_residual_path_is_a_scope_inside_parts_not_a_part(lowered,
                                                              program):
    """The nested ``pt.mhc`` scope marks the residual path of a model whose
    stream is several rows — a sublayer's maps and its mix: the attention
    sublayer's sit inside ``attn_proj``, the MLP sublayer's inside ``mlp``,
    every op under it still has one of the ten parts, and the Sinkhorn
    iterations are ONE loop a sublayer, not twenty unrolled."""
    eng, progs = lowered("xing4")
    ops = ops_with_name_stacks(progs[program].as_text(debug_info=True))
    inside = [(op, st) for op, st in ops if "pt.mhc" in st.split("/")]
    assert len(inside) > 10
    around = {parts.part_of(st) for _op, st in inside}
    assert around == {"attn_proj", "mlp"}, around
    assert "mhc" not in parts.PARTS and "mhc" in parts.SUBPARTS
    # a loop's body is traced once: two divisions (columns, rows) a sublayer
    divides = [st for op, st in inside
               if op == "divide" and "while/body" in st]
    assert len(divides) == 2 * 2 * eng._sm.num_layers
    # the router, the experts and the latent kernel are not the path's
    assert not any("pt.router" in st or "pt.experts" in st
                   or "pt.attention" in st for _op, st in inside)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_the_state_space_scan_is_a_scope_inside_the_mixer(lowered, program):
    """The nested ``pt.ssm_scan`` scope marks a Mamba-2 layer's recurrence —
    the chunked scan of a prefill, the one step of a round — and nothing
    else of the layer: every op under it is ``mixer``'s, the in / out
    projections and the conv are ``mixer``'s outside it, and the expert
    layers and the attention layer hold none of it."""
    eng, progs = lowered("nemotron_h")
    ops = ops_with_name_stacks(progs[program].as_text(debug_info=True))
    inside = [(op, st) for op, st in ops if "pt.ssm_scan" in st.split("/")]
    assert len(inside) > 10
    assert {parts.part_of(st) for _op, st in inside} == {"mixer"}
    assert "ssm_scan" not in parts.PARTS and "ssm_scan" in parts.SUBPARTS
    outside = [op for op, st in ops if parts.part_of(st) == "mixer"
               and "pt.ssm_scan" not in st.split("/")]
    # in_proj and out_proj of each Mamba-2 layer
    assert outside.count("dot_general") == 2 * eng._pool.layer_kinds.count(
        "state")
    assert not any("pt.router" in st or "pt.experts" in st
                   or "pt.attention" in st for _op, st in inside)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_the_conv_mixing_is_a_scope_inside_the_projections(lowered, program):
    """The nested ``pt.cca_mix`` scope marks what a compressed convolutional
    attention does between its projections and the cache — the two causal
    convs behind the tail, the q-k mean, the norm and temperature, the
    partial RoPE — and nothing else of the layer: every op under it is
    ``attn_proj``'s (a norm inside is the mixing's own: no weight), the
    latent and output projections are ``attn_proj``'s outside it, and the
    router, the experts and the attention hold none of it."""
    eng, progs = lowered("zaya1")
    ops = ops_with_name_stacks(progs[program].as_text(debug_info=True))
    inside = [(op, st) for op, st in ops if "pt.cca_mix" in st.split("/")]
    assert len(inside) > 10
    assert {parts.part_of(st) for _op, st in inside} == {"attn_proj"}
    assert "cca_mix" not in parts.PARTS and "cca_mix" in parts.SUBPARTS
    layers = len(eng._pool.layer_kinds)
    # the second conv is two grouped matmuls a layer — a chunk's and a
    # round's where the program carries one
    convs = [op for op, _st in inside].count("dot_general")
    assert convs in (2 * layers, 4 * layers)
    outside = [op for op, st in ops if parts.part_of(st) == "attn_proj"
               and "pt.cca_mix" not in st.split("/")]
    # Wq | Wk, Wv1 | Wv2 and Wo of each layer
    assert outside.count("dot_general") == 3 * layers
    assert not any("pt.router" in st or "pt.experts" in st
                   or "pt.attention" in st for _op, st in inside)


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("model", ["openpangu", "laguna", "glm_dsa",
                                   "dots3_note", "xing4", "nemotron_h",
                                   "zaya1"])
def test_expert_models_programs_hand_back_their_weight_streams(
        lowered, model, program):
    """Every window program of a model with expert layers hands back, beside
    the routed pairs, how often its grouped matmuls streamed an expert's
    weights: an int32 scalar, summed over the layers on the device."""
    eng, programs = lowered(model)
    counted = programs[program].out_info[-1]
    assert set(counted) >= set(eng._sm.program_counters) >= {
        "moe_experts_hit_total", "moe_weight_streams_total"}
    streams = counted["moe_weight_streams_total"]
    assert streams.shape == () and streams.dtype == "int32"


def _strip(text):
    return re.sub(r"module @\S+", "module @m", text)


@pytest.mark.parametrize("model", list(MODELS))
def test_the_parts_are_names_and_nothing_else(lowered, model, monkeypatch):
    """With the scopes off, every program lowers to the same text letter
    for letter (locations and the module's name apart)."""
    _eng, scoped = lowered(model)
    # both forms of the helper, wherever it was imported to, open no scope
    monkeypatch.setattr(parts.part, "__enter__", lambda self: None)
    monkeypatch.setattr(parts.part, "__exit__", lambda self, *exc: False)
    jax.clear_caches()      # a jitted helper keeps the jaxpr it traced
    try:
        _eng2, plain = lowered_programs(model)
    finally:
        jax.clear_caches()
    named = re.compile(r"pt\.(%s)\b" % "|".join(parts.PARTS))
    for prog in PROGRAMS:
        assert named.search(scoped[prog].as_text(debug_info=True))
        assert not named.search(plain[prog].as_text(debug_info=True))
        assert _strip(scoped[prog].as_text()) == _strip(plain[prog].as_text())


def test_a_name_outside_the_vocabulary_raises():
    with pytest.raises(ValueError, match="not a part"):
        parts.part("softmax")
    with pytest.raises(ValueError, match="not a part"):
        parts.part("pt.norm")
    assert parts.part_of("jit(pt_window1)/pt.attn_proj/pt.norm/mul") == "norm"
    assert parts.part_of("jit(pt_window1)/pt.nothing/mul") is None
    assert parts.part_of("") is None


@pytest.mark.parametrize("model", list(MODELS))
def test_window_programs_have_distinct_names(lowered, model):
    eng, progs = lowered(model)
    names = [re.search(r"module @(\S+)", p.as_text()).group(1)
             for p in progs.values()]
    assert len(set(names)) == len(names), names
    assert all(n.startswith("jit_pt_") for n in names), names
    carries = eng._sm.carries_rounds
    assert names[1].endswith("_carry") == carries
    assert "jit_pt_window1" in names and "jit_pt_prefill8" in names
