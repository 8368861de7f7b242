"""The layer recompute keeps the projection outputs the chip has room for
(ISSUE 54).

``models/llama.py`` names q / k (after RoPE), v, o (off ``mp``), up and gate
where they are produced; ``stage_stack.remat_wrap`` keeps the ``keep`` it is
handed besides today's set; ``jit/remat_fit.py`` picks ``keep`` against the
compiled step's ``memory_analysis()`` and the device's ``bytes_limit``,
remembers the pick, and reports it. All on the CPU, where a device states no
limit: the tests that walk the ladder give it one.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu import jit, observability
from paddle_tpu.core import autograd
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.meta_parallel import stage_stack as ss
from paddle_tpu.jit import persistent_cache, remat_fit
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

REPLAY = "remat2"   # jax.checkpoint's primitive: the backward's replayed body
GB = 10 ** 9


def _eqns(jaxpr, inside=()):
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


def _replayed_dots(jaxpr):
    return sum(1 for eqn, inside in _eqns(jaxpr)
               if eqn.primitive.name == "dot_general" and REPLAY in inside)


def _model(**overrides):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(**{**dict(
        hidden_size=32, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=96,
        max_position_embeddings=32, use_recompute=True), **overrides})
    return LlamaForCausalLM(cfg)


IDS = np.random.default_rng(0).integers(0, 96, (2, 16)).astype(np.int32)


def _value_and_grads(model, names):
    """(the gradient program's jaxpr, (loss, gradients)) of the model's loss
    in float32, traced while the stack keeps ``names``."""
    params = list(model.parameters())
    ids = jnp.asarray(IDS)

    def loss_of(arrays):
        with jit._Binder(params) as b:
            b.bind(list(arrays))
            with autograd.no_grad():
                loss = model(Tensor(ids), labels=Tensor(ids))
        return loss.data.astype(jnp.float32)

    arrays = tuple(p.data for p in params)
    with ss.keeping(names):
        fn = jax.jit(jax.value_and_grad(loss_of))
        return fn.trace(arrays).jaxpr.jaxpr, fn(arrays)


@pytest.fixture(scope="module")
def stack():
    """One two-layer scanned Llama, its un-recomputed gradients, and the
    dots its lean recompute runs in the backward scan."""
    model = _model()
    model.llama.layers.recompute = False
    _, plain = _value_and_grads(model, ())
    model.llama.layers.recompute = True
    lean_jaxpr, _ = _value_and_grads(model, ())
    return model, jax.tree_util.tree_leaves(plain), lean_jaxpr


# a layer's seven weight matmuls and the XLA softmax's two products, each two
# dots in the backward (18); the lean replay runs again all but down_proj (8)
LEAN_DOTS = 18 + 8


@pytest.mark.parametrize("label,names", remat_fit.RUNGS,
                         ids=[label for label, _ in remat_fit.RUNGS])
def test_a_rung_keeps_its_projections_and_the_gradients(stack, label, names):
    """Float32 gradients of the scanned stack at every rung equal the
    un-recomputed stack's bit for bit, and the backward's replay holds
    exactly one ``dot_general`` fewer for each projection the rung keeps."""
    model, plain, lean_jaxpr = stack
    assert _replayed_dots(lean_jaxpr) == LEAN_DOTS
    jaxpr, out = _value_and_grads(model, names)
    assert _replayed_dots(jaxpr) == LEAN_DOTS - len(names)
    # what the ladder reads off the program: the names it still replays
    assert set(remat_fit.named_bytes(jaxpr)) == \
        remat_fit.LADDER_NAMES - set(names)
    for a, b in zip(jax.tree_util.tree_leaves(out), plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_named_bytes_are_the_stacked_values(stack):
    """2 x 16 tokens, hidden 32 (q, o), 2 of 4 heads (k, v), MLP 128, float32,
    two layers: the bytes a rung's stacked residuals hold."""
    _, _, lean_jaxpr = stack
    row = 2 * 16 * 4 * 2
    assert remat_fit.named_bytes(lean_jaxpr) == {
        ss.ATTN_Q: 32 * row, ss.ATTN_K: 16 * row, ss.ATTN_V: 16 * row,
        ss.ATTN_O: 32 * row, ss.MLP_UP: 128 * row, ss.MLP_GATE: 128 * row}
    rungs = remat_fit.ladder(remat_fit.named_bytes(lean_jaxpr))
    assert [r.label for r in rungs] == [label for label, _ in remat_fit.RUNGS]
    assert [r.kept // (32 * row) for r in rungs] == [0, 1, 2, 3, 6, 7, 8, 10,
                                                     11]


def test_a_stack_without_the_names_or_the_recompute_has_one_rung():
    """No recompute: the names sit in the forward alone and none is a
    candidate. A mesh with ``mp`` (o_proj names its own sum): no ``attn_o``,
    so the rungs with and without it are one."""
    model = _model(use_recompute=False)
    jaxpr, _ = _value_and_grads(model, ())
    assert remat_fit.named_bytes(jaxpr) == {}
    assert [r.label for r in remat_fit.ladder({})] == ["lean"]
    no_o = {ss.ATTN_Q: 8, ss.ATTN_K: 4, ss.ATTN_V: 4, ss.MLP_UP: 32,
            ss.MLP_GATE: 32}
    assert [r.label for r in remat_fit.ladder(no_o)] == \
        ["lean", "kv", "qkv", "qkv_up", "up_gate", "qkv_up_gate"]


@pytest.mark.parametrize("policy", ["flash", "dots", "moe"])
def test_the_other_policies_do_not_read_keep(policy):
    """``FLAGS_remat_policy``'s other values mean what they meant: a ``keep``
    handed to the stack changes nothing under them."""
    model = _model()   # the flag is read at a stack's first trace
    paddle.set_flags({"FLAGS_remat_policy": policy})
    try:
        base, _ = _value_and_grads(model, ())
        kept, _ = _value_and_grads(model, dict(remat_fit.RUNGS)["qkvo_up_gate"])
    finally:
        paddle.set_flags({"FLAGS_remat_policy": ""})
    assert _replayed_dots(kept) == _replayed_dots(base)
    # 'dots' keeps every matmul without a batch dim: the softmax's two stay
    assert _replayed_dots(base) == (18 + 2 if policy == "dots" else LEAN_DOTS)


# -- the chooser, a pure function ---------------------------------------------

# ISSUE 54's table of cell 1, GB: what each rung keeps (34 MB a layer for
# k + v, for q and for o; 134 for up and for gate; 12 layers) and what the
# compiled step measured, of a limit of 16.91
KEPT = [0, 0.40, 0.81, 1.21, 2.42, 2.82, 3.22, 4.03, 4.43]
LEAN, QKV, UP_GATE, LIMIT = 14.66, 16.27, 20.11, 16.91
KV, QKVO, QKV_UP = 15.46, 17.08, 19.50   # at the 2 x the table shows


def _walk(measure, kept=KEPT, limit=LIMIT, margin=0.02):
    """Drive ``next_step`` to its end; ``measure(i)`` is the compiler."""
    measured, order = {}, []
    while True:
        cand, final = remat_fit.next_step(
            [int(k * GB) for k in kept], measured, int(limit * GB), margin)
        if cand is None:
            return final, order
        assert cand not in measured
        order.append(cand)
        measured[cand] = measure(cand)


def _gb(table):
    return lambda i: None if table[i] is None else int(table[i] * GB)


def test_chooser_lands_cell_1_on_qkv_in_two_compiles():
    """Lean 14.66 of 16.91: at the prior's 2 x q / k / v is the richest rung
    in reach; it measures 16.27, which prices a kept byte at 2, which puts
    q / k / v / o (17.08) out of reach: two compiles."""
    table = [LEAN, KV, QKV, QKVO, QKV_UP, None, UP_GATE, None, None]
    assert _walk(_gb(table)) == (2, [0, 2])


def test_chooser_steps_down_from_a_rung_that_measures_over():
    """Cell 3's ladder (``mp`` names o_proj's sum: no ``attn_o``) from a lean
    program of 12.45: q / k / v is the probe, it prices a byte at 1, the
    price reaches for all five; they measure over (or the compiler refuses
    them) and the walk steps down once, inside ``MAX_COMPILES``."""
    kept = [0, 0.40, 0.81, 2.42, 3.22, 4.03]
    at_1x = [12.45 + k for k in kept]
    for top in (16.70, None):
        final, order = _walk(_gb(at_1x[:-1] + [top]), kept=kept)
        assert (final, order) == (4, [0, 2, 5, 4])
        assert len(order) == remat_fit.MAX_COMPILES
    # a byte that costs 1.1 there: up + gate at once, three compiles
    at_11 = [12.45 + 1.1 * k for k in kept]
    assert _walk(_gb(at_11), kept=kept) == (4, [0, 2, 4])
    # dearer above than the probe said, twice: no fifth compile, the richest
    # rung that measured under the budget runs
    assert _walk(_gb(at_1x[:4] + [16.60, 17.2]), kept=kept) == \
        (2, [0, 2, 5, 4])


def test_chooser_margin_and_the_ends_of_the_ladder():
    table = [LEAN, KV, QKV, QKVO, QKV_UP, None, UP_GATE, None, None]
    # q / k / v leaves 3.8 % of the limit: a margin above that lands on k / v
    assert _walk(_gb(table), margin=0.05) == (1, [0, 1])
    # no room for the least rung even at 1 x: the lean program, one compile
    assert _walk(_gb(table), limit=14.9) == (0, [0])
    # room for the least rung at 1 x but not at the prior: ask, once
    assert _walk(_gb(table), limit=15.5) == (0, [0, 1])
    # everything fits: the top, lean + one rung + the top
    roomy = [1.0 + k for k in KEPT]
    assert _walk(_gb(roomy)) == (8, [0, 8])
    # one rung: nothing to choose either
    assert _walk(_gb([LEAN]), kept=[0]) == (0, [0])


# -- the step: the walk, the remembered choice, the gauges ---------------------

def _step(model=None):
    model = model or _model()
    optimizer = opt.AdamW(learning_rate=3e-3, parameters=model.parameters(),
                          weight_decay=0.1)
    return jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)


def _losses(step, n=3):
    x = paddle.to_tensor(IDS)
    return [float(step(x, x)) for _ in range(n)]


@pytest.fixture
def compiles(monkeypatch, tmp_path):
    """The memo beside a cache of this test's own, and every program the
    ladder compiles, by the names it kept."""
    monkeypatch.setattr(persistent_cache, "default_dir", lambda: str(tmp_path))
    lowered, seen = {}, []
    lower, compile_ = remat_fit.FittedStep._lower, \
        remat_fit.FittedStep._compile

    def lowering(self, names, args):
        out = lower(self, names, args)
        lowered[id(out[2])] = tuple(names)
        return out

    def compiling(jitted, low, sig, may_refuse=True):
        seen.append(lowered[id(low)])
        return compile_(jitted, low, sig, may_refuse)

    monkeypatch.setattr(remat_fit.FittedStep, "_lower", lowering)
    monkeypatch.setattr(remat_fit.FittedStep, "_compile",
                        staticmethod(compiling))
    return seen


def _gauges():
    snap = observability.hub().snapshot()["gauges"]
    return {k: v for k, v in snap.items() if k.startswith("train.")}


def test_no_bytes_limit_runs_todays_program_and_nothing_else(compiles):
    """The CPU states no limit: the step is the plain ``CachedJit`` of
    today's set, the ladder compiles nothing, and the program's replay holds
    every projection."""
    assert remat_fit.bytes_limit() is None
    step = _step()
    plain = _losses(step)
    assert type(step._jitted) is persistent_cache.CachedJit
    assert compiles == []
    x = jnp.asarray(IDS)
    jaxpr = step._jitted.trace(*jit.step_args(
        step, (x, x), jax.random.key(0))).jaxpr.jaxpr
    assert _replayed_dots(jaxpr) == LEAN_DOTS
    assert plain[-1] < plain[0]


def test_the_walk_the_memo_and_the_gauges(monkeypatch, compiles, caplog):
    """A device with room: the top rung runs (lean, one rung, the top: three
    compiles), trains bit-equal to today's program, and a second build loads
    ONE program — until the limit or the flag changes."""
    plain = _losses(_step())
    top = dict(remat_fit.RUNGS)["qkvo_up_gate"]

    monkeypatch.setattr(remat_fit, "bytes_limit", lambda: 10 ** 12)
    with caplog.at_level(logging.INFO, logger=remat_fit.__name__):
        step = _step()
        assert _losses(step) == plain
    assert type(step._jitted) is remat_fit.FittedStep
    assert compiles[0] == () and compiles[-1] == top and len(compiles) <= 3
    g = _gauges()
    assert g["train.remat_rung"] == len(remat_fit.RUNGS) - 1
    assert g["train.remat_kept_bytes"] == 11 * 32 * 2 * 16 * 4 * 2
    assert g["train.remat_candidates_compiled"] == len(compiles)
    assert g["train.remat_remembered"] == 0
    assert g["train.step_bytes_limit"] == 10 ** 12
    assert 0 < g["train.step_program_bytes"] < 10 ** 12
    assert "keeps qkvo_up_gate" in caplog.text
    # what the step lowers to by hand is the program it runs
    x = jnp.asarray(IDS)
    text = step.lower(x, x).as_text()
    with ss.keeping(top):
        again = jit.lowerable(step._build()).lower(*jit.step_args(
            step, (x, x), jax.random.key(0))).as_text()
    assert text == again

    del compiles[:]
    assert _losses(_step()) == plain          # the remembered choice
    assert compiles == [top]
    g = _gauges()
    assert g["train.remat_candidates_compiled"] == 1
    assert g["train.remat_remembered"] == 1
    assert g["train.remat_rung"] == len(remat_fit.RUNGS) - 1

    del compiles[:]
    monkeypatch.setattr(remat_fit, "bytes_limit", lambda: 10 ** 12 + 1)
    assert _losses(_step()) == plain          # another limit: walked again
    assert compiles[0] == () and len(compiles) > 1

    del compiles[:]
    paddle.set_flags({"FLAGS_remat_policy": "flash"})
    try:
        step = _step()
        assert _losses(step) == plain         # the flag says: no ladder
    finally:
        paddle.set_flags({"FLAGS_remat_policy": ""})
    assert type(step._jitted) is persistent_cache.CachedJit
    assert compiles == []


def test_a_tight_limit_runs_the_lean_program(monkeypatch, compiles):
    """A limit the lean program alone fills: one compile, today's set, and
    the memo says so to the next build."""
    x = jnp.asarray(IDS)
    lean = remat_fit.program_bytes(_step().lower(x, x).compile())
    monkeypatch.setattr(remat_fit, "bytes_limit", lambda: lean)
    step = _step()
    _losses(step, n=1)
    assert compiles == [()]
    assert _gauges()["train.remat_rung"] == 0
    assert _gauges()["train.remat_kept_bytes"] == 0
    del compiles[:]
    _losses(_step(), n=1)
    assert compiles == [()]
    assert _gauges()["train.remat_remembered"] == 1


def test_a_memo_whose_program_outgrew_the_limit_is_forgotten(monkeypatch,
                                                             compiles):
    """The key cannot see the program's text: a remembered rung that no
    longer fits is dropped and the ladder walked again."""
    monkeypatch.setattr(remat_fit, "bytes_limit", lambda: 10 ** 12)
    _losses(_step(), n=1)
    del compiles[:]
    real, grown = remat_fit.program_bytes, iter([10 ** 13])
    monkeypatch.setattr(remat_fit, "program_bytes",
                        lambda compiled: next(grown, None) or real(compiled))
    _losses(_step(), n=1)
    top = dict(remat_fit.RUNGS)["qkvo_up_gate"]
    assert compiles[0] == top and compiles[1] == () and len(compiles) > 2
    assert _gauges()["train.remat_remembered"] == 0


def test_a_plain_checkpoint_is_found_before_a_second_compile(monkeypatch,
                                                             compiles):
    """The unscanned layer list recomputes under ``jax.checkpoint``'s own
    policy, which reads no ``keep``: the first candidate lowers to the lean
    program's text and the walk ends there."""
    monkeypatch.setattr(remat_fit, "bytes_limit", lambda: 10 ** 12)
    step = _step(_model(scan_layers=False))
    _losses(step, n=1)
    assert compiles == [()]
    assert _gauges()["train.remat_rung"] == 0
    assert _gauges()["train.remat_candidates_compiled"] == 1


@pytest.mark.dist
def test_the_sharded_step_walks_the_same_ladder(monkeypatch, compiles):
    """``ShardedTrainStep`` on ``dp=2 x mp=2``: the ladder runs over the
    mesh's program (o_proj's sum is ``mp``'s to name, so the top rung is
    q / k / v + up + gate), the compiled step takes the placed parameters
    and hands back the losses of today's program, and a second build loads
    one program."""
    import paddle_tpu.distributed as dist

    def losses():
        dist.reset_mesh()
        dist.init_mesh(dp=2, mp=2, devices=jax.devices()[:4])
        try:
            model = _model()
            optimizer = opt.AdamW(learning_rate=3e-3,
                                  parameters=model.parameters(),
                                  weight_decay=0.1)
            step = dist.ShardedTrainStep(
                model, lambda m, x, y: m(x, labels=y), optimizer)
            return _losses(step), step
        finally:
            dist.reset_mesh()

    plain, step = losses()
    assert type(step._jitted) is persistent_cache.CachedJit
    monkeypatch.setattr(remat_fit, "bytes_limit", lambda: 10 ** 12)
    kept, step = losses()
    assert type(step._jitted) is remat_fit.FittedStep
    assert kept == plain
    top = dict(remat_fit.RUNGS)["qkv_up_gate"]
    assert compiles[0] == () and compiles[-1] == top and len(compiles) <= 3
    assert _gauges()["train.remat_rung"] == \
        [label for label, _ in remat_fit.RUNGS].index("qkv_up_gate")
    del compiles[:]
    assert losses()[0] == plain
    assert compiles == [top]
