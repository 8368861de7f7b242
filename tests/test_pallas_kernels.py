"""ISSUE-13: the kernels/pallas fused-op layer.

Interpret-mode (the Pallas kernels through the Pallas interpreter) vs
the jnp references — forward AND gradients — for fused MoE routing/
dispatch, RMSNorm(+residual), RoPE and paged attention, including odd /
non-divisible shapes, GQA head ratios and the flash ``q_offset``
context-parallel path; the registry's table (its decision is
tests/test_kernel_seam.py); the retrace-auditable attention-path
threshold (``FLAGS_flash_min_seq``); zero-retrace on the warm kernel
path; and the planner's fused-kernel cost entries.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import flags as flags_mod
from paddle_tpu.kernels import registry as kreg
from paddle_tpu.kernels.pallas import moe_dispatch as kmoe
from paddle_tpu.kernels.pallas import paged_attention as kpaged
from paddle_tpu.kernels.pallas import rmsnorm as krms
from paddle_tpu.kernels.pallas import rope as krope

TOL = dict(rtol=2e-5, atol=2e-5)


def _calls(op):
    """Decisions the seam took for ``op`` so far, by answer."""
    return dict(kreg.kernel_table()["ops"][op]["calls"])


def _close(a, b, **kw):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               **(kw or TOL))


@pytest.fixture(autouse=True)
def _restore_flags():
    prior = flags_mod.get_flags(["FLAGS_moe_dispatch",
                                 "FLAGS_flash_min_seq"])
    yield
    flags_mod.set_flags(prior)


# -- registry seam ------------------------------------------------------------

def test_registry_resolve_and_table():
    before = _calls("rms_norm")
    impl = kreg.resolve("rms_norm")
    assert impl == ("pallas" if jax.default_backend() == "tpu"
                    else "reference")
    table = kreg.kernel_table()
    assert set(table) == {"backend", "ops"}
    assert set(table["ops"]) >= {"rms_norm", "rope", "moe_dispatch",
                                 "paged_attention", "ssm_step"}
    row = table["ops"]["rms_norm"]
    assert set(row) == {"impl", "calls", "doc"} and row["impl"] == impl
    # one decision, counted once; reading the table counts nothing
    assert row["calls"][impl] == before[impl] + 1
    assert _calls("rms_norm") == row["calls"]
    # the table is a hub provider
    from paddle_tpu import observability as obs

    assert "fused_kernels" in obs.snapshot()


# -- RMSNorm ------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 96), (2, 7, 96), (3, 5, 130)])
def test_rms_norm_parity_fwd(shape):
    """Interpret vs reference, directly and through the primitive, odd
    widths."""
    ks = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(ks[0], shape, jnp.float32)
    w = jax.random.normal(ks[1], shape[-1:], jnp.float32)
    yi = krms.rms_norm(x, w, 1e-6, impl="interpret")
    yc = krms.rms_norm(x, w, 1e-6, impl="reference")
    from paddle_tpu.nn.functional.common import _rms_norm

    _close(yi, yc)
    _close(_rms_norm.fn(x, w, eps=1e-6, impl="interpret"), yc)


def test_rms_norm_residual_parity_fwd_and_grad():
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (3, 9, 96), jnp.float32)
    r = jax.random.normal(ks[1], (3, 9, 96), jnp.float32)
    w = jax.random.normal(ks[2], (96,), jnp.float32)

    def loss(impl):
        def f(x, r, w):
            y, s = krms.rms_norm_residual(x, r, w, 1e-6, impl=impl)
            return jnp.sum(y * 1.3) + jnp.sum(jnp.sin(s))
        return f

    yi, si = krms.rms_norm_residual(x, r, w, 1e-6, impl="interpret")
    yc, sc = krms.rms_norm_residual(x, r, w, 1e-6, impl="reference")
    _close(yi, yc)
    _close(si, sc)
    _close(si, x + r)  # the new residual IS the sum
    # the kernel's hand-written VJP against JAX's autodiff of the reference
    gi = jax.grad(loss("interpret"), argnums=(0, 1, 2))(x, r, w)
    gc = jax.grad(loss("reference"), argnums=(0, 1, 2))(x, r, w)
    for a, b in zip(gi, gc):
        _close(a, b)


def test_rms_norm_functional_routes_through_the_seam(monkeypatch):
    """The functional passes the seam's answer as a primitive attr: the
    interpreted kernel under PT_PALLAS_INTERPRET=1, the reference without —
    same numbers."""
    import paddle_tpu.nn.functional as F

    x = paddle.randn([2, 5, 64])
    w = paddle.ones([64])
    y_ref = np.asarray(F.rms_norm(x, w).numpy())
    before = _calls("rms_norm")
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    y_int = np.asarray(F.rms_norm(x, w).numpy())
    y2, s2 = F.rms_norm_residual(x, x, w)
    assert _calls("rms_norm") == {**before,
                                  "interpret": before["interpret"] + 2}
    _close(y_ref, y_int)
    _close(np.asarray(s2.numpy()), 2 * np.asarray(x.numpy()))


# -- RoPE ---------------------------------------------------------------------

# d = 128 is the lane-roll path; ``rows`` caps the sequence block so that a
# short sequence spans several blocks, the last one ragged (80 = 32+32+16)
@pytest.mark.parametrize("shape,offset,dtype,rows", [
    ((2, 12, 3, 8), 0, jnp.float32, None),
    ((1, 10, 5, 6), 7, jnp.float32, None),
    ((2, 16, 4, 64), 3, jnp.float32, None),
    ((2, 80, 4, 128), 0, jnp.float32, 32),
    ((2, 80, 8, 128), 5, jnp.float32, 32),
    ((1, 64, 16, 128), 0, jnp.float32, 32),
    ((2, 40, 3, 128), 9, jnp.float32, 32),
    ((2, 24, 5, 128), 2, jnp.float32, None),
    ((2, 80, 4, 128), 3, jnp.bfloat16, 32),
    ((1, 48, 8, 128), 0, jnp.bfloat16, 32),
    ((1, 40, 16, 128), 11, jnp.bfloat16, 32),
    ((2, 36, 3, 64), 4, jnp.bfloat16, 32),
])
def test_rope_parity_fwd_and_grad(shape, offset, dtype, rows, monkeypatch):
    if rows:
        monkeypatch.setattr(krope, "_MAX_ROWS", rows)
        assert -(-shape[1] // krope._pick_seq_block(
            *shape[1:], jnp.dtype(dtype).itemsize)) > 1
    # 1 ulp of inv_freq between exp() and the reference's pow() is an angle
    # error of position x 6e-8: the float32 tolerance grows with the span
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4) if shape[1] + offset > 32 else TOL
    x = jax.random.normal(jax.random.key(2), shape, jnp.float32).astype(dtype)
    oi = krope.rope_apply(x, 1e4, offset, impl="interpret")
    oc = krope.rope_apply(x, 1e4, offset, impl="reference")
    from paddle_tpu.models.llama import _rope

    assert oi.dtype == dtype and oi.shape == shape
    _close(oi, oc, **tol)
    _close(_rope.fn(x, theta=1e4, pos_offset=offset, impl="interpret"), oc,
           **tol)

    def loss(impl):
        return lambda z: jnp.sum(jnp.sin(krope.rope_apply(
            z, 1e4, offset, impl=impl).astype(jnp.float32)))

    # the kernel's inverse-rotation VJP against autodiff of the reference
    _close(jax.grad(loss("interpret"))(x), jax.grad(loss("reference"))(x),
           **tol)


@pytest.mark.parametrize("shape,offset,rows", [((2, 80, 4, 128), 6, 32),
                                               ((1, 10, 5, 6), 7, None)])
def test_rope_inverse_undoes_forward(shape, offset, rows, monkeypatch):
    """The residual-free VJP rests on it: the rotation is orthogonal, so
    the backward half (the inverse rotation) applied to the forward's
    output is the input."""
    if rows:
        monkeypatch.setattr(krope, "_MAX_ROWS", rows)
    fwd, bwd = krope.rope_halves(1e4, offset, "interpret")
    x = jax.random.normal(jax.random.key(3), shape, jnp.float32)
    y, res = fwd(x)
    assert res == ()
    assert float(jnp.abs(y - x).max()) > 0.1      # it did rotate
    _close(bwd(res, y)[0], x, rtol=1e-5, atol=1e-5)


def test_rope_rejects_odd_head_dim():
    x = jnp.zeros((1, 4, 2, 7))
    with pytest.raises(ValueError):
        krope.rope_apply(x, 1e4, 0, impl="reference")


# -- fused MoE routing/dispatch ----------------------------------------------

def _moe_weights(h=32, e=4, i=48, key=7):
    ks = jax.random.split(jax.random.key(key), 5)
    return (jax.random.normal(ks[1], (h, e), jnp.float32) * 0.1,
            jax.random.normal(ks[2], (e, h, i), jnp.float32) * 0.1,
            jax.random.normal(ks[3], (e, h, i), jnp.float32) * 0.1,
            jax.random.normal(ks[4], (e, i, h), jnp.float32) * 0.1)


def test_fused_route_parity_and_order():
    """The routing kernel's gates/positions/counts/aux match the jnp
    reference, and positions reproduce the gmm path's stable-argsort order."""
    h, e, k = 24, 4, 2
    wg, *_ = _moe_weights(h=h, e=e)
    xt = jax.random.normal(jax.random.key(3), (30, h), jnp.float32)
    gi_out = kmoe.fused_route(xt, wg, k, "interpret")
    gc_out = kmoe.fused_route(xt, wg, k, "reference")
    for a, b in zip(gi_out, gc_out):
        _close(a, b)
    gv, gi, pos, cnt, aux = gc_out
    # index outputs ride as f32 across the custom-vjp boundary (float0
    # tangent avoidance) — integer-exact
    gi, pos, cnt = (np.asarray(a).astype(np.int32) for a in (gi, pos, cnt))
    assert np.all(np.asarray(gc_out[1]) == gi)  # exact integers as floats
    # stable-argsort order: dest is a permutation, grouped by expert in
    # token-major traversal order
    flat_e = np.asarray(gi).reshape(-1)
    offsets = np.concatenate([[0], np.cumsum(np.asarray(cnt))[:-1]])
    dest = offsets[flat_e] + np.asarray(pos).reshape(-1)
    assert sorted(dest) == list(range(len(dest)))
    order = np.argsort(flat_e, kind="stable")
    ref_dest = np.empty_like(order)
    ref_dest[order] = np.arange(len(order))
    assert np.array_equal(dest, ref_dest)


def test_fused_moe_parity_vs_gmm_and_index():
    """Fwd + grads vs the gmm (dropless twin) and index (no-drop
    capacity) paths, odd token counts included."""
    from paddle_tpu.nn.layer import moe as moe_mod

    wg, w_gate, w_up, w_down = _moe_weights()
    x = jax.random.normal(jax.random.key(4), (2, 15, 32), jnp.float32)

    def floss(impl):
        def f(x, wg, w_gate, w_up, w_down):
            o, aux = kmoe.fused_moe_mlp(x, wg, w_gate, w_up, w_down,
                                        top_k=2, impl=impl)
            return jnp.sum(o * o) + 0.1 * aux
        return f

    def gmm_loss(x, wg, w_gate, w_up, w_down):
        o, aux = moe_mod._moe_mlp_gmm(x, wg, w_gate, w_up, w_down, top_k=2)
        return jnp.sum(o * o) + 0.1 * aux

    def idx_loss(x, wg, w_gate, w_up, w_down):
        # capacity_factor == num_experts guarantees zero drops
        o, aux = moe_mod._moe_mlp_index(x, wg, w_gate, w_up, w_down,
                                        top_k=2, capacity_factor=4.0,
                                        ep_degree=1)
        return jnp.sum(o * o) + 0.1 * aux

    args = (x, wg, w_gate, w_up, w_down)
    of, auxf = kmoe.fused_moe_mlp(*args, top_k=2, impl="interpret")
    oc, auxc = kmoe.fused_moe_mlp(*args, top_k=2, impl="reference")
    og, auxg = moe_mod._moe_mlp_gmm(*args, top_k=2)
    _close(of, oc)
    _close(of, og)
    _close(auxf, auxg)
    gi = jax.grad(floss("interpret"), argnums=tuple(range(5)))(*args)
    gc = jax.grad(floss("reference"), argnums=tuple(range(5)))(*args)
    gg = jax.grad(gmm_loss, argnums=tuple(range(5)))(*args)
    gx = jax.grad(idx_loss, argnums=tuple(range(5)))(*args)
    for a, b in zip(gi, gc):
        _close(a, b)
    for a, b in zip(gi, gg):
        _close(a, b)
    for a, b in zip(gi, gx):  # router + expert grads match the index path
        _close(a, b, rtol=1e-4, atol=1e-4)


def test_moe_layer_fused_flag_end_to_end():
    """FLAGS_moe_dispatch='fused' through the real MoELayer primitive,
    vs gmm — identical dropless math."""
    from paddle_tpu.nn.layer import moe as moe_mod

    wg, w_gate, w_up, w_down = _moe_weights()
    x = jax.random.normal(jax.random.key(5), (2, 12, 32), jnp.float32)
    of, auxf = moe_mod._moe_mlp.fn(x, wg, w_gate, w_up, w_down, top_k=2,
                                   capacity_factor=1.25, ep_degree=1,
                                   dispatch="fused")
    og, auxg = moe_mod._moe_mlp.fn(x, wg, w_gate, w_up, w_down, top_k=2,
                                   capacity_factor=1.25, ep_degree=1,
                                   dispatch="gmm")
    _close(of, og)
    _close(auxf, auxg)
    # ep_degree > 1 falls back to the index path (no ragged a2a)
    oi, _ = moe_mod._moe_mlp.fn(x, wg, w_gate, w_up, w_down, top_k=2,
                                capacity_factor=1.25, ep_degree=2,
                                dispatch="fused")
    assert oi.shape == x.shape


def test_fused_moe_grad_under_scan():
    """Regression: differentiating fused_moe_mlp inside a lax.scan body
    (the scanned decoder stack) must not materialize float0 tangents —
    the routing indices cross the custom-vjp boundary as floats."""
    wg, w_gate, w_up, w_down = _moe_weights()
    x = jax.random.normal(jax.random.key(12), (2, 8, 32), jnp.float32)

    def loss(x, wg):
        def body(c, _):
            o, aux = kmoe.fused_moe_mlp(c, wg, w_gate, w_up, w_down,
                                        top_k=2, impl="reference")
            return o, aux
        out, auxes = jax.lax.scan(body, x, None, length=2)
        return jnp.sum(out * out) + 0.1 * jnp.sum(auxes)

    g = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, wg)
    assert all(np.isfinite(np.asarray(a)).all() for a in g)
    assert float(jnp.abs(g[1]).sum()) > 0  # router grads flow


def test_fused_moe_rejects_too_many_experts():
    h, e = 8, 130
    wg = jnp.zeros((h, e))
    with pytest.raises(ValueError):
        kmoe.fused_moe_mlp(jnp.zeros((1, 4, h)), wg,
                           jnp.zeros((e, h, 8)), jnp.zeros((e, h, 8)),
                           jnp.zeros((e, 8, h)), top_k=2, impl="reference")


# -- paged attention ----------------------------------------------------------

@pytest.mark.parametrize("nh,kvh,hd,PL", [(4, 4, 16, 4), (4, 2, 16, 4),
                                          (6, 2, 12, 5)])
def test_paged_attention_parity(nh, kvh, hd, PL):
    """Interpret vs reference (the PR-11 gather math), GQA ratios and
    non-divisible page/head shapes; grads through the VJP."""
    S, W, P, B = 3, 2, 11, 3
    ks = jax.random.split(jax.random.key(6), 5)
    q = jax.random.normal(ks[0], (S, W, nh, hd), jnp.float32)
    ka = jax.random.normal(ks[1], (P, PL, kvh, hd), jnp.float32)
    va = jax.random.normal(ks[2], (P, PL, kvh, hd), jnp.float32)
    tables = jax.random.randint(ks[3], (S, B), 0, P).astype(jnp.int32)
    pos = jnp.array([[3, 4], [0, 1], [2 * PL, 2 * PL + 1]], jnp.int32)
    pi = kpaged.paged_attention(q, ka, va, tables, pos, impl="interpret")
    pc = kpaged.paged_attention(q, ka, va, tables, pos, impl="reference")
    _close(pi, pc)
    gi = jax.grad(lambda a, b_, c: jnp.sum(kpaged.paged_attention(
        a, b_, c, tables, pos, impl="interpret") ** 2),
        argnums=(0, 1, 2))(q, ka, va)
    gc = jax.grad(lambda a, b_, c: jnp.sum(kpaged.paged_attention(
        a, b_, c, tables, pos, impl="reference") ** 2),
        argnums=(0, 1, 2))(q, ka, va)
    for a, b in zip(gi, gc):
        _close(a, b)


def test_paged_attention_masks_by_position():
    """A key past pos is invisible: growing pos by one token changes the
    row; keys beyond the allocation never leak in."""
    S, W, nh, hd, P, PL, B = 1, 1, 2, 8, 6, 4, 2
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (S, W, nh, hd), jnp.float32)
    ka = jax.random.normal(ks[1], (P, PL, nh, hd), jnp.float32)
    va = jax.random.normal(ks[2], (P, PL, nh, hd), jnp.float32)
    tables = jnp.array([[2, 3]], jnp.int32)
    o3 = kpaged.paged_attention(q, ka, va, tables,
                                jnp.array([[3]], jnp.int32),
                                impl="interpret")
    o4 = kpaged.paged_attention(q, ka, va, tables,
                                jnp.array([[4]], jnp.int32),
                                impl="interpret")
    assert not np.allclose(np.asarray(o3), np.asarray(o4))
    # pos = 3: only page 2's 4 keys visible -> equals dense attention
    # over those keys
    keys = np.asarray(ka)[2]                       # [PL, nh, hd]
    vals = np.asarray(va)[2]
    logits = np.einsum("whd,Lhd->whL", np.asarray(q)[0], keys) / np.sqrt(hd)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ref = np.einsum("whL,Lhd->whd", probs, vals)
    _close(o3[0], ref, rtol=1e-4, atol=1e-4)


def test_window_step_seam_token_parity(monkeypatch):
    """The serving window step traced to the jnp reference and to the
    interpreted Pallas kernel: identical argmaxes, logprobs and K/V
    writes. Each program asks the seam as it is traced, and the two get
    different answers — not one program compared with itself."""
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving.generation import _build_window_step

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    dtype="float32")
    model = GPTForCausalLM(cfg)
    params = model.served_model().params(model)
    S, B, PL, W = 2, 4, 8, 2
    P = S * B + 1
    hd = cfg.hidden_size // cfg.num_attention_heads
    ks = jax.random.split(jax.random.key(9), 2)
    mk = lambda kk: [jax.random.normal(kk, (P, PL, 4, hd), jnp.float32) * 0.1
                     for _ in range(2)]
    tables = jnp.arange(S * B, dtype=jnp.int32).reshape(S, B) + 1
    tokens = jnp.array([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.array([5, 11], jnp.int32)
    outs = {}
    for impl in ("reference", "interpret"):
        if impl == "interpret":
            monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        before = _calls("paged_attention")
        stp = _build_window_step(cfg, S, B, PL, W, donate=False,
                                 label=f"t:{impl}")
        outs[impl] = stp(params, mk(ks[0]), mk(ks[1]), tables, tokens,
                         lengths)
        took = [k for k, v in _calls("paged_attention").items()
                if v > before[k]]
        assert took == [impl], took
    assert np.array_equal(np.asarray(outs["reference"][0]),
                          np.asarray(outs["interpret"][0]))
    for a, b in zip(jax.tree_util.tree_leaves(outs["reference"][1:4]),
                    jax.tree_util.tree_leaves(outs["interpret"][1:4])):
        _close(a, b)
    with pytest.raises(ValueError, match="no longer selects"):
        _build_window_step(cfg, S, B, PL, W, donate=False, label="t:off",
                           fused=False)


# -- flash q_offset (context-parallel path) -----------------------------------

def test_flash_q_offset_matches_full_causal():
    """A q chunk attending the full K/V prefix with its global offset
    (the ring-attention rank view) matches the same rows of full causal
    flash — the cp path's correctness contract."""
    from paddle_tpu.kernels.flash_attention import flash_attention_with_lse

    bh, s, d = 2, 32, 16
    ks = jax.random.split(jax.random.key(10), 3)
    q = jax.random.normal(ks[0], (bh, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (bh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (bh, s, d), jnp.float32)
    full, _ = flash_attention_with_lse(q, k, v, 0, True, 0.25, 8, 8)
    half, _ = flash_attention_with_lse(q[:, s // 2:], k, v, s // 2, True,
                                       0.25, 8, 8)
    _close(half, full[:, s // 2:], rtol=1e-5, atol=1e-5)


# -- attention path threshold (FLAGS_flash_min_seq) ---------------------------

def test_attention_backend_threshold_and_flags():
    from paddle_tpu.nn.functional.attention import attention_backend

    # CPU always lands on the fused-XLA path
    assert attention_backend(4096, 4096, 128, platform="cpu") == "xla"
    # TPU: threshold + structural constraints
    assert attention_backend(4096, 4096, 128, platform="tpu") == "flash"
    assert attention_backend(64, 64, 128, platform="tpu") == "xla"
    assert attention_backend(4096, 4096, 80, platform="tpu") == "xla"
    assert attention_backend(4100, 4096, 128, platform="tpu") == "xla"
    flags_mod.set_flags({"FLAGS_flash_min_seq": 8192})
    assert attention_backend(4096, 4096, 128, platform="tpu") == "xla"
    assert attention_backend(8192, 8192, 128, platform="tpu") == "flash"
    flags_mod.set_flags({"FLAGS_flash_min_seq": 128})
    prior = flags_mod.get_flags("FLAGS_use_pallas_flash_attention")
    try:
        flags_mod.set_flags({"FLAGS_use_pallas_flash_attention": False})
        assert attention_backend(4096, 4096, 128, platform="tpu") == "xla"
    finally:
        flags_mod.set_flags(prior)
    os.environ["PADDLE_TPU_DISABLE_FLASH"] = "1"
    try:
        assert attention_backend(4096, 4096, 128, platform="tpu") == "xla"
    finally:
        os.environ.pop("PADDLE_TPU_DISABLE_FLASH", None)


def test_attention_impl_attr_is_cache_key_participant():
    """The chosen path rides the sdpa primitive's attrs — two impls, two
    jit cache keys (what makes a threshold flip retrace-auditable)."""
    from paddle_tpu.core.dispatch import _FWD_CACHE, get_primitive

    prim = get_primitive("sdpa")
    f_x = prim.fwd({"causal": True, "scale": 0.1, "impl": "xla"})
    f_f = prim.fwd({"causal": True, "scale": 0.1, "impl": "flash"})
    assert f_x is not f_f
    assert ("sdpa", (("causal", True), ("impl", "xla"),
                     ("scale", 0.1))) in _FWD_CACHE


# -- zero-retrace on the warm kernel path --------------------------------------

def test_warm_kernel_path_zero_retrace(monkeypatch):
    """With the audit armed, repeated kernel-path calls at fixed shapes
    add ZERO retrace events; another answer from the seam is a NEW key,
    not a silent recompile of the old one."""
    import paddle_tpu.analysis as A
    import paddle_tpu.nn.functional as F

    monkeypatch.setenv("PT_RETRACE_AUDIT", "1")
    A.retrace.enable()
    try:
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        x = paddle.randn([2, 6, 64])
        w = paddle.ones([64])
        F.rms_norm(x, w)
        F.rms_norm_residual(x, x, w)
        base = A.retrace.get_auditor().summary()["retrace_events"]
        for _ in range(3):  # warm path: same shapes, same answer
            F.rms_norm(x, w)
            F.rms_norm_residual(x, x, w)
        assert A.retrace.get_auditor().summary()["retrace_events"] == base
        monkeypatch.delenv("PT_PALLAS_INTERPRET")
        F.rms_norm(x, w)  # the change is an AUDITED new key: one event
        aud = A.retrace.get_auditor()
        assert aud.summary()["retrace_events"] == base + 1
        ev = aud.events[-1]
        assert "impl" in str(ev.deltas), ev.deltas  # names the attr
    finally:
        A.retrace.disable()
        A.retrace.reset()


# -- llama end-to-end seam parity ----------------------------------------------

def test_llama_seam_loss_parity(monkeypatch):
    """tiny-Llama fwd+bwd through the interpreted Pallas kernels (RMSNorm,
    RMSNorm+residual and RoPE with their hand-written VJPs) equals the jnp
    references under JAX's autodiff to float tolerance."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny()
    losses = {}
    for impl in ("reference", "interpret"):
        if impl == "interpret":
            monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        before = {op: _calls(op) for op in ("rms_norm", "rope")}
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = jit.TrainStep(m, lambda mm, x, y: mm(x, labels=y), o)
        ids = paddle.randint(0, cfg.vocab_size, [2, 32])
        losses[impl] = [float(step(ids, ids)) for _ in range(2)]
        for op, was in before.items():  # the step really took this path
            assert _calls(op)[impl] > was[impl], (op, impl)
    np.testing.assert_allclose(losses["reference"], losses["interpret"],
                               rtol=1e-4, atol=1e-5)


# -- planner cost entries -----------------------------------------------------

def test_planner_fused_entries_reprice_and_rerank():
    """plan(fused_kernels=True) records per-op cost deltas on every
    candidate; the MoE model prices the dispatch entry too."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaMoEConfig

    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    off = dist.plan(m, n_devices=8, hbm_bytes=16e9, batch=16, seq=64,
                    fused_kernels=False)
    on = dist.plan(m, n_devices=8, hbm_bytes=16e9, batch=16, seq=64,
                   fused_kernels=True)
    by_key = {str(c.config): c.predicted_step_s for c in off}
    deltas = [by_key[str(c.config)] - c.predicted_step_s
              for c in on if str(c.config) in by_key]
    assert any(d > 0 for d in deltas), "fused entries changed no cost"
    assert on[0].breakdown.get("fused_gain_s", 0) > 0
    assert "rms_norm" in on[0].breakdown["fused_ops"]

    paddle.seed(0)
    moe = dist.plan(LlamaForCausalLM(LlamaMoEConfig.tiny()), n_devices=8,
                    hbm_bytes=16e9, batch=16, seq=64, fused_kernels=True)
    assert "moe_dispatch" in moe[0].breakdown["fused_ops"]
    # fused_kernels=None follows the platform (CPU -> none)
    paddle.seed(0)
    auto = dist.plan(m, n_devices=8, hbm_bytes=16e9, batch=16, seq=64)
    if jax.default_backend() == "cpu":
        assert "fused_gain_s" not in auto[0].breakdown


def test_calibration_persist_roundtrip(tmp_path, monkeypatch):
    """calibrate_from_counters persists per-(topology, jax version) next
    to the persistent cache; link_model_for merges it under
    PT_LINK_CALIBRATION=1; fused entries calibrate the same way."""
    from paddle_tpu.cost_model import comm
    from paddle_tpu.cost_model.fused import fused_entries

    monkeypatch.setenv("PT_CALIBRATION_DIR", str(tmp_path))
    lm = comm.link_model_for("cpu-host")
    path = comm.save_calibration(
        lm.override(ici_bytes_per_s=3.21e10),
        fused={"moe_dispatch": {"dispatch_share_composed": 0.2,
                                "dispatch_share_fused": 0.05}})
    assert os.path.exists(path) and "cpu-host" in path
    monkeypatch.setenv("PT_LINK_CALIBRATION", "1")
    assert comm.link_model_for("cpu-host").ici_bytes_per_s == 3.21e10
    ent = fused_entries("cpu-host")["moe_dispatch"]
    assert ent.dispatch_share_composed == 0.2
    monkeypatch.setenv("PT_LINK_CALIBRATION", "0")
    assert comm.link_model_for("cpu-host").ici_bytes_per_s != 3.21e10


def test_calibrate_from_counters_reads_device_trace(monkeypatch):
    """The XPlane op-table feed: collective device time + collective
    byte counters refit the ICI link; a flops hint refits peak_flops."""
    from paddle_tpu import observability as obs
    from paddle_tpu.cost_model import comm

    fake = {
        "device_trace": {
            "op_table": [
                {"op": "all-reduce.1", "total_us": 2000.0},
                {"op": "fusion.7", "total_us": 5000.0},
            ],
            "device_compute_us": {"per_step_avg": 7000.0},
            "steps_correlated": 2,
        },
        "step_timeline": {"steps": 10},
        "collectives": {"values": {"all_reduce|bytes": 8e7,
                                   "all_reduce|calls": 4}},
    }
    monkeypatch.setattr(obs, "snapshot", lambda: fake)
    lm = comm.calibrate_from_counters(comm.link_model_for("cpu-host"),
                                      flops_per_step=7e9)
    # cumulative bytes normalize over ALL 10 timeline steps; device time
    # over the 2 captured steps: (8e7/10) / (2000us/2 per step)
    assert lm.ici_bytes_per_s == pytest.approx((8e7 / 10) / 1e-3)
    assert lm.peak_flops == pytest.approx(7e9 / 7e-3)


# -- ranged paged attention (PR 34) --------------------------------------------

def _ranged_case(S, W, Hg, starts, G=2, d=128, PL=8, B=16, seed=0):
    from paddle_tpu.kernels.pallas import ranged_paged_attention as kr

    rng = np.random.default_rng(seed)
    P = 1 + S * B
    ka = jnp.asarray(rng.normal(size=(P, G, PL, d)), jnp.float32)
    va = jnp.asarray(rng.normal(size=(P, G, PL, d)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(S * B).reshape(S, B), jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, W, G * Hg, d)), jnp.float32)
    return kr, q, ka, va, tables, jnp.asarray(starts, jnp.int32)


@pytest.mark.parametrize("S,W,Hg,window,starts", [
    (3, 1, 6, None, [0, 37, 90]),      # decode, rows of unequal length
    (3, 1, 8, 8, [0, 37, 90]),         # decode in a window layer: lo > 0
    (1, 16, 8, 8, [21]),               # a chunk across the window
    (1, 16, 6, None, [40]),            # a chunk in a full layer, 6 heads
    (2, 4, 6, 8, [5, 60]),             # two rows, W = 4
    (1, 64, 8, 24, [30]),              # a tiled chunk (TW = 32, two tiles)
])
def test_ranged_paged_attention_parity(S, W, Hg, window, starts):
    """The Pallas kernel (interpreted) against its jnp reference: 6 and 8
    query heads a K/V head, W = 1 and tiled chunks, a window whose first
    page is not page 0."""
    kr, q, ka, va, tables, st = _ranged_case(S, W, Hg, starts)
    got = kr.ranged_paged_attention(q, ka, va, tables, st, window=window,
                                    scale=0.09, impl="interpret")
    want = kr.ranged_paged_attention(q, ka, va, tables, st, window=window,
                                     scale=0.09, impl="reference")
    _close(got, want)


@pytest.mark.parametrize("tiles,S,W,Hg,window,starts,given_back", [
    # a chunk whose tile spans several blocks: 4 tiles of 16 tokens, blocks
    # of 2 pages, 150 tokens cached in front (blocks 0..12)
    ((16, 2), 1, 64, 6, None, [150], False),
    # ... and whose tile is the whole window (one grid step a row)
    ((64, 4), 1, 64, 8, None, [40], False),
    # a window edge that falls inside a larger block: blocks of 4 pages (32
    # keys) against a window of 24, tiles that start in the middle of one
    ((8, 4), 1, 32, 8, 24, [53], False),
    ((32, 4), 1, 64, 8, 24, [30], False),
    # a block of ONE page, the window three pages wide
    ((1, 1), 3, 1, 8, 24, [0, 37, 90], False),
    ((4, 1), 2, 4, 6, 24, [5, 60], False),
    # pages given back before lo: the table's entries behind the window are
    # the scratch page, inside the first block walked too
    ((1, 4), 2, 1, 8, 24, [70, 100], True),
    ((16, 2), 1, 32, 8, 24, [77], True),
    # a decode round over blocks of 3 pages: no power of two is asked of KP
    ((1, 3), 3, 1, 6, None, [0, 37, 90], False),
])
def test_ranged_paged_attention_parity_over_tiles(
        monkeypatch, tiles, S, W, Hg, window, starts, given_back):
    """The kernel under the KINDS of tiling ``choose_tiles`` hands out at the
    served shapes — many tokens a tile, blocks of one page and of several,
    a whole window in one step — at this file's tiny pages: every one must
    give the reference's result."""
    kr, q, ka, va, tables, st = _ranged_case(S, W, Hg, starts, B=32, seed=5)
    monkeypatch.setattr(kr, "choose_tiles", lambda *a, **k: tiles)
    want = kr.ranged_paged_attention(q, ka, va, tables, st, window=window,
                                     scale=0.09, impl="reference")
    if given_back:
        gone = np.asarray(tables).copy()
        for r, s0 in enumerate(starts):
            gone[r, :(s0 - (window - 1)) // 8] = 0
        tables = jnp.asarray(gone)
        ka = ka.at[0].set(1e3)      # the scratch page holds anything
    got = kr.ranged_paged_attention(q, ka, va, tables, st, window=window,
                                    scale=0.09, impl="interpret")
    _close(got, want)


@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_ranged_paged_attention_window_edges(impl):
    """Off by one on neither end: the query at position i sees exactly the
    keys i - window < j <= i. A key's value is its own position, so a head's
    context under uniform scores is the mean of the positions it saw."""
    from paddle_tpu.kernels.pallas import ranged_paged_attention as kr

    G, Hg, d, PL, B, window = 1, 8, 128, 8, 8, 8
    P = 1 + B
    pos = np.arange(P * PL, dtype=np.float32).reshape(P, 1, PL, 1) - PL
    va = jnp.asarray(np.broadcast_to(pos, (P, G, PL, d)))   # page 1 = 0..7
    ka = jnp.zeros((P, G, PL, d), jnp.float32)              # uniform scores
    tables = jnp.asarray(1 + np.arange(B)[None], jnp.int32)
    q = jnp.ones((1, 4, G * Hg, d), jnp.float32)
    out = kr.ranged_paged_attention(q, ka, va, tables, jnp.asarray([19]),
                                    window=window, scale=1.0, impl=impl)
    for w in range(4):   # position 19 + w sees 12 + w .. 19 + w
        i = 19 + w
        np.testing.assert_allclose(np.asarray(out)[0, w, :, 0],
                                   np.mean(np.arange(i - 7, i + 1)),
                                   rtol=1e-6)
    full = kr.ranged_paged_attention(q, ka, va, tables, jnp.asarray([19]),
                                     window=None, scale=1.0, impl=impl)
    np.testing.assert_allclose(np.asarray(full)[0, 0, :, 0],
                               np.mean(np.arange(0, 20)), rtol=1e-6)


def test_ranged_paged_attention_ignores_pages_given_back():
    """A window layer's table holds the scratch page for the blocks behind
    the window: whatever lies there, the result does not move."""
    kr, q, ka, va, tables, st = _ranged_case(2, 1, 8, [70, 100], seed=3)
    want = kr.ranged_paged_attention(q, ka, va, tables, st, window=8,
                                     scale=0.09, impl="interpret")
    gone = np.asarray(tables).copy()
    gone[0, :(70 - 7) // 8] = 0
    gone[1, :(100 - 7) // 8] = 0
    ka = ka.at[0].set(1e3)      # the scratch page holds anything
    got = kr.ranged_paged_attention(q, ka, va, jnp.asarray(gone), st,
                                    window=8, scale=0.09, impl="interpret")
    _close(got, want)
    # a live row whose table STARTS with the scratch page is not idle
    assert (np.abs(np.asarray(got)).max(axis=(1, 2, 3)) > 0).all()
    with pytest.raises(ValueError, match="query heads over"):
        kr.ranged_paged_attention(q[:, :, :7], ka, va, tables, st, scale=1.0)


# rows of a round of six that hold no sequence (lengths 0, a table of zeros)
_IDLE_ROWS = {"first": [0, 1], "last": [4, 5], "consecutive": [2, 3],
              "alternate": [0, 2, 4], "all": [0, 1, 2, 3, 4, 5]}


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("idle", list(_IDLE_ROWS))
def test_ranged_paged_attention_skips_idle_rows(monkeypatch, idle, W, window):
    """A row whose first token's own key lies in the scratch page starts no
    walk and returns zeros, wherever it sits among the live rows (whose
    first block the step before starts: the pipeline crosses the idle rows
    too). The live rows give the reference's result and, bit for bit, what
    the kernel gives them with the idle rows taken out of the call."""
    starts = [3, 37, 90, 12, 64, 121]
    kr, q, ka, va, tables, st = _ranged_case(6, W, 8 if window else 6,
                                             starts, B=32, seed=7)
    # a round's tiles at the served shapes: blocks of 4 pages, of 1 in a
    # window layer; W = k + 1 = 4 in two tiles a row
    monkeypatch.setattr(kr, "choose_tiles", lambda *a, **k: (
        1 if W == 1 else 2, 1 if window else 4))
    gone = _IDLE_ROWS[idle]
    live = [r for r in range(6) if r not in gone]
    tables, st = np.asarray(tables).copy(), np.asarray(st).copy()
    tables[gone], st[gone] = 0, 0
    if window:   # the live rows' pages behind the window went back too
        for r in live:
            tables[r, :max(starts[r] - (window - 1), 0) // 8] = 0
    tables, st = jnp.asarray(tables), jnp.asarray(st)
    ka, va = ka.at[0].set(1e3), va.at[0].set(1e3)   # never read

    def attend(rows, impl):
        rows = jnp.asarray(rows, jnp.int32)
        return np.asarray(kr.ranged_paged_attention(
            q[rows], ka, va, tables[rows], st[rows], window=window,
            scale=0.09, impl=impl))

    got = attend(range(6), "interpret")
    assert not got[gone].any()
    _close(got, attend(range(6), "reference"))
    if live:
        assert np.abs(got[live]).max(axis=(1, 2, 3)).min() > 0
        np.testing.assert_array_equal(got[live], attend(live, "interpret"))


@pytest.mark.parametrize("tiles,W,Hg,window,starts", [
    # what `choose_tiles` hands the three served configurations' chunks, in
    # this file's pages of 8: one tile a chunk over blocks of 8 pages,
    ((32, 8), 32, 4, None, [40, 0, 8]),
    # many tiles a row (every tile's first block started by the tile before)
    ((8, 8), 32, 16, None, [136, 0, 64]),
    ((16, 8), 64, 6, None, [200, 0, 16]),
    # a window layer's blocks of 2 pages, each tile its own ``lo``
    ((8, 2), 32, 8, 24, [53, 0, 120]),
    ((16, 2), 64, 8, 24, [77, 0, 8]),
    # and a round's (1, 4) and (1, 1) under W = k + 1 = 3 tiles a row
    ((1, 4), 3, 4, None, [61, 0, 95]),
    ((1, 1), 3, 8, 24, [61, 0, 95]),
])
def test_ranged_paged_attention_pipeline_over_tiles(monkeypatch, tiles, W, Hg,
                                                    window, starts):
    """Rows of several tiles with an idle row between them: the walk's
    pipeline runs from tile to tile, over the idle row and into the next
    row, under every kind of tiling the chooser returns at the served
    shapes."""
    kr, q, ka, va, tables, st = _ranged_case(3, W, Hg, starts, B=40, seed=11)
    monkeypatch.setattr(kr, "choose_tiles", lambda *a, **k: tiles)
    tables = tables.at[1].set(0)
    ka = ka.at[0].set(1e3)
    got, want = (np.asarray(kr.ranged_paged_attention(
        q, ka, va, tables, st, window=window, scale=0.09, impl=impl))
        for impl in ("interpret", "reference"))
    assert not got[1].any() and np.abs(got[[0, 2]]).min(axis=-1).max() > 0
    _close(got, want)
