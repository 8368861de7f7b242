"""The layer recompute keeps what the flash forward kernel produced (ISSUE 51).

``kernels/flash_attention.py`` names the custom-vjp's RESIDUALS ``o`` and
``lse`` (``flash_o`` / ``flash_lse``) when the dense entry asks for it, and
``stage_stack.remat_wrap`` saves those names under every policy: the backward's
replay of a layer holds no ``pt_flash_fwd``. All on the CPU, the kernel in
Pallas interpret mode (what ``flash_attention._interpret`` picks there).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu import jit
from paddle_tpu.distributed.meta_parallel import stage_stack
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

B, S, H, D = 2, 16, 2, 8
REPLAY = "remat2"   # jax.checkpoint's primitive: the backward's replayed body


def _eqns(jaxpr, inside=()):
    """Every equation under ``jaxpr`` with the primitives it sits inside; a
    ``pallas_call``'s own body is the kernel, not the program, and is not
    entered."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


def _kernels(jaxpr, name):
    return [inside for eqn, inside in _eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.params["name"] == name]


def _names(jaxpr):
    return sorted(eqn.params["name"] for eqn, _ in _eqns(jaxpr)
                  if eqn.primitive.name == "name")


def _layer(x, w):
    qkv = (x @ w).reshape(B, S, 3, H, D)
    o = fa.flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                           causal=True)
    return x + o.reshape(B, S, H * D)


def _operands():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, S, H * D)), jnp.float32)
    ws = jnp.asarray(rng.standard_normal((2, H * D, 3 * H * D)) * 0.2,
                     jnp.float32)
    return x, ws


@pytest.fixture
def policy(request):
    paddle.set_flags({"FLAGS_remat_policy": request.param})
    yield request.param
    paddle.set_flags({"FLAGS_remat_policy": ""})


@pytest.mark.parametrize("policy", ["", "flash", "dots", "moe"], indirect=True)
def test_the_replayed_layer_holds_no_flash_forward(policy):
    """Two recomputed attention layers: ``flash_o`` / ``flash_lse`` are named
    once a layer, the forward kernel runs once a layer — in the forward — and
    the recompute (``remat2`` in the gradient's jaxpr) runs the two
    backward kernels alone."""
    def loss(x, ws):
        for w in ws:
            x = stage_stack.remat_wrap(_layer)(x, w)
        return jnp.sum(x)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(*_operands()).jaxpr
    assert _names(jaxpr) == ["flash_lse", "flash_lse", "flash_o", "flash_o"]
    forward = _kernels(jaxpr, "pt_flash_fwd")
    assert len(forward) == 2, forward
    assert not any(REPLAY in inside for inside in forward), forward
    for kernel in ("pt_flash_bwd_dkv", "pt_flash_bwd_dq"):
        backward = _kernels(jaxpr, kernel)
        assert len(backward) == 2
        assert all(REPLAY in inside for inside in backward), backward


def test_a_plain_checkpoint_replays_the_kernel():
    """The counting above can tell: under ``jax.checkpoint``'s own policy
    (the unscanned layer list's, ``utils_recompute``) nothing is kept by name
    and each layer's forward kernel is in the program twice."""
    def loss(x, ws):
        for w in ws:
            x = jax.checkpoint(_layer)(x, w)
        return jnp.sum(x)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(*_operands()).jaxpr
    forward = _kernels(jaxpr, "pt_flash_fwd")
    assert len(forward) == 4, forward
    assert sum(REPLAY in inside for inside in forward) == 2


# -- the whole train step -----------------------------------------------------

def _flash_everywhere(monkeypatch):
    """This process's backend is the CPU, where ``attention_backend`` answers
    ``xla``; the test steers it (the program has no option for it)."""
    from paddle_tpu.nn.functional import attention

    monkeypatch.setattr(attention, "attention_backend",
                        lambda sq, sk, hd, platform=None: "flash")


def _train(steps=3):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(
        hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=96,
        max_position_embeddings=32, use_recompute=True)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-3, parameters=model.parameters(),
                          weight_decay=0.1)
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = np.random.default_rng(0).integers(0, 96, (2, 16)).astype(np.int32)
    x = paddle.to_tensor(ids)
    losses = [float(step(x, x)) for _ in range(steps)]
    params = [np.asarray(p.data) for p in model.parameters()]
    return losses, params, step, x


def _traced(step, x):
    fn = step._jitted
    while not hasattr(fn, "trace"):
        fn = fn.__wrapped__
    args = jit.step_args(step, jit._batch_arrays((x, x)), jax.random.key(0))
    return fn.trace(*args).jaxpr.jaxpr


def test_train_step_keeps_flash_residuals_bit_equal_to_plain_recompute(
        monkeypatch):
    """A tiny Llama's scanned, recomputed step with flash attention: one
    ``pt_flash_fwd`` in the program (the forward scan's; two under
    ``jax.checkpoint``'s plain policy), and over three optimizer steps the
    losses and every parameter — so every gradient — are the plain
    policy's, bit for bit."""
    _flash_everywhere(monkeypatch)
    kept_losses, kept_params, step, x = _train()
    jaxpr = _traced(step, x)
    assert len(_kernels(jaxpr, "pt_flash_fwd")) == 1
    # the layer's projection outputs carry names too (tests/test_remat_fit.py)
    assert [n for n in _names(jaxpr) if n.startswith("flash_")] == \
        ["flash_lse", "flash_o"]

    monkeypatch.setattr(stage_stack, "remat_wrap",
                        lambda fn, keep=(): jax.checkpoint(fn))
    plain_losses, plain_params, step, x = _train()
    assert len(_kernels(_traced(step, x), "pt_flash_fwd")) == 2
    assert kept_losses == plain_losses
    assert kept_losses[-1] < kept_losses[0]
    for a, b in zip(kept_params, plain_params):
        np.testing.assert_array_equal(a, b)


# -- who is named -------------------------------------------------------------

def _qkv(*shape):
    rng = np.random.default_rng(1)
    return [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(3)]


def test_the_ring_path_calls_carry_no_name():
    """``flash_attention_with_lse`` as the ring calls it (no ``named=``):
    differentiated, nothing in the program carries a name — ``cp`` partial
    ``o`` / ``lse`` a layer are not kept ``cp`` times over."""
    from paddle_tpu.distributed.context_parallel import ring_attention_bhsd

    def ring_step(q, k, v):
        o, lse = fa.flash_attention_with_lse(q, k, v, offset=0, causal=True)
        return jnp.sum(o) + jnp.sum(lse)

    def one_rank(q, k, v):     # cp = 1: the ring's direct call
        return jnp.sum(ring_attention_bhsd(q, k, v, causal=True, env=None))

    for f in (ring_step, one_rank):
        grad = jax.grad(f, argnums=(0, 1, 2))
        jaxpr = jax.make_jaxpr(grad)(*_qkv(B * H, S, D)).jaxpr
        assert _names(jaxpr) == []
        assert len(_kernels(jaxpr, "pt_flash_fwd")) == 1


# sha256 of the dense entry's lowered text (interpret mode: the kernel's body
# as plain HLO, no locations), taken by this very test's ``dense`` on a
# checkout of this PR's parent (46adca0), where the names sat on the outputs
PARENT_DENSE_SHA256 = (
    "4221994174de5150d5f8f7b14dd3ac4ff7cf158881bea02654c0eac0e900a820")


def test_the_dense_entry_names_only_when_differentiated():
    """An undifferentiated call traces the primal function: no name, one
    kernel, and it lowers to the parent's text."""
    q, k, v = _qkv(B, S, H, D)

    def dense(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    jaxpr = jax.make_jaxpr(dense)(q, k, v).jaxpr
    assert _names(jaxpr) == []
    assert len(_kernels(jaxpr, "pt_flash_fwd")) == 1
    text = jax.jit(dense).lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_DENSE_SHA256
    grad = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(dense(*a))))(q, k, v)
    assert _names(grad.jaxpr) == ["flash_lse", "flash_o"]


def test_backward_refuses_a_q_block_the_row_form_cannot_tile(monkeypatch):
    """``lse`` / ``delta`` reach the backward kernels in row blocks
    ``[1, 1, block_q]``: compiled (not interpreted), a q block that is neither
    a multiple of 128 nor the whole sequence raises here, readably, not in
    Mosaic."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 256, 1, 64), jnp.float32)

    def grad(block):
        return jax.eval_shape(jax.grad(lambda q: jnp.sum(fa.flash_attention(
            q, q, q, causal=True, block_q=block, block_k=block))), q)

    with pytest.raises(ValueError, match="multiple of 128"):
        grad(64)
    assert grad(128).shape == grad(256).shape == q.shape
