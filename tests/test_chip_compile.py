"""AOT compiles for the described chip (on-chip-measurement §2 step 3).

Every Pallas kernel the two main paths reach (``chip_smoke.py``: Llama
train at hidden 2048 / seq 2048 / 16 heads x 128, GPT-2-small serve) is
lowered and compiled here for a ``v5e:2x2`` topology that is described,
not attached: what the chip's compiler refuses (tiling, VMEM, missing
lowerings) fails a tier-1 test instead of a chip call. Interpret-mode
parity lives in ``test_pallas_kernels.py``; nothing here runs a kernel.

The topology is described inside a module-scoped fixture only (never at
import / collection: one process at a time may load the TPU library, and
xdist workers import every test file). All compiles happen in this
process; JAX's persistent cache is off around them (a described-device
compile is written to the cache but can never be read back).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


_CUSTOM_CALL = re.compile(
    r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"")


def _compile(fn, one_chip, *shapes, names, foreign=None):
    """Lower + compile ``fn`` for the described chip; returns the
    compiled program's text. Every Mosaic custom call in it must carry
    its kernel's name (``pt_<kernel>``: what the device trace shows on
    the ``XLA Ops`` line) and ``names`` must all be there; ``foreign``
    names a kernel that is JAX's own (megablox ``gmm``)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    calls = _CUSTOM_CALL.findall(text)
    assert calls, "kernel is not in the program"
    assert "_unknown_" not in text
    # jvp / transpose / checkpoint wrap the scope's name, never replace
    # it: ``transpose(jvp(pt_flash_bwd_dq))`` -> transpose_jvp_pt_..._dq__
    calls = [c for c in calls if not (foreign and foreign in c)]
    kernels = [re.search(r"pt_[a-z0-9_]*[a-z0-9]", c) for c in calls]
    assert all(kernels), calls
    assert {m.group(0) for m in kernels} == set(names), calls
    return text


@pytest.mark.parametrize("b,s,h,d", [(4, 2048, 16, 128), (8, 1024, 12, 64)])
def test_flash_fwd_bwd(one_chip, monkeypatch, b, s, h, d):
    from paddle_tpu.kernels import flash_attention as fa

    # this process's backend is the CPU; the kernel must compile, not
    # interpret — steered here, in the test (the program has no option)
    monkeypatch.setattr(fa, "_interpret", lambda: False)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    qkv = ((b, s, h, d), BF16)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qkv, qkv, qkv,
             names=("pt_flash_fwd", "pt_flash_bwd_dkv", "pt_flash_bwd_dq"))


def _recomputed_attention_scan(attend):
    """The loss of two scanned, recomputed attention layers (projections,
    ``attend``, output projection, residual) at cell 1's widths: 16 heads of
    128 over hidden 2048, stacked weights as the layer scan holds them."""
    from paddle_tpu.distributed.meta_parallel.stage_stack import remat_wrap

    def layer(x, w):
        wqkv, wo = w
        q, k, v = jnp.einsum("bsh,hcnd->cbsnd", x, wqkv)
        return x + jnp.einsum("bsnd,ndh->bsh", attend(q, k, v), wo), None

    def loss(x, wqkv, wo):
        y, _ = jax.lax.scan(remat_wrap(layer), x, (wqkv, wo))
        return jnp.sum(y.astype(jnp.float32))

    shapes = [(4, 2048, 2048), (2, 2048, 3, 16, 128), (2, 16, 128, 2048)]
    return jax.grad(loss, argnums=(0, 1, 2)), shapes


def _flash_forward_calls(text):
    return [c for c in _CUSTOM_CALL.findall(text) if "pt_flash_fwd" in c]


def test_recomputed_scan_holds_one_flash_forward(one_chip, monkeypatch):
    """The layer recompute keeps the forward kernel's ``o`` / ``lse`` (its
    backward's residuals, named; every policy saves them): the gradient of
    the scan holds ONE ``pt_flash_fwd`` custom call — the forward scan's —
    where the replayed layer used to hold a second (ISSUE 51)."""
    from paddle_tpu.kernels import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    grad, shapes = _recomputed_attention_scan(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
    text = _compile(grad, one_chip, *[(s, BF16) for s in shapes],
                    names=("pt_flash_fwd", "pt_flash_bwd_dkv",
                           "pt_flash_bwd_dq"))
    assert len(_flash_forward_calls(text)) == 1, _flash_forward_calls(text)


def test_recomputed_scan_on_the_mesh_holds_one_flash_forward(topo,
                                                             monkeypatch):
    """Cell 3's form: on ``dp=2 x mp=2`` flash rides
    ``run_forward_kernel_on_mesh``, the custom-vjp INSIDE the manual region.
    ``shard_map``'s partial evaluation hands the recompute's policy to the
    inner program, so the names are honoured there too: one ``pt_flash_fwd``
    custom call for the four described chips, not two."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.mesh import activation_spec
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional.attention import _sdpa
    from jax.sharding import PartitionSpec as P

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    grad, shapes = _recomputed_attention_scan(
        lambda q, k, v: _sdpa.fn(q, k, v, causal=True, scale=128 ** -0.5,
                                 impl="flash"))
    dist.reset_mesh()
    env = dist.init_mesh(dp=2, mp=2, devices=list(topo.devices))
    try:
        held = [env.sharding_for(activation_spec(shapes[0], "rows")),
                env.sharding_for(P(None, None, None, "mp", None)),
                env.sharding_for(P(None, "mp", None, None))]
        args = [jax.ShapeDtypeStruct(s, BF16, sharding=sh)
                for s, sh in zip(shapes, held)]
        text = jax.jit(grad, out_shardings=tuple(held)).lower(*args) \
            .compile().as_text()
    finally:
        dist.reset_mesh()
    assert len(_flash_forward_calls(text)) == 1, _flash_forward_calls(text)
    assert any("pt_flash_bwd_dkv" in c for c in _CUSTOM_CALL.findall(text))


def _recomputed_llama_scan(rope, attend, keep, o_named=True):
    """The loss of two scanned, recomputed Llama layers at cell 1's widths
    (hidden 2048, 16 x 128 query / 8 x 128 K/V heads, MLP 8192) with the
    projection outputs named as ``models/llama.py`` names them, and the
    recompute keeping ``keep`` (``stage_stack.remat_wrap``)."""
    from jax.ad_checkpoint import checkpoint_name as named

    from paddle_tpu.distributed.meta_parallel import stage_stack as ss

    def layer(x, w):
        wq, wk, wv, wo, wg, wu, wd = w
        q = named(rope(jnp.einsum("bsh,hnd->bsnd", x, wq)), ss.ATTN_Q)
        k = named(rope(jnp.einsum("bsh,hnd->bsnd", x, wk)), ss.ATTN_K)
        v = named(jnp.einsum("bsh,hnd->bsnd", x, wv), ss.ATTN_V)
        o = attend(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))
        o = jnp.einsum("bsnd,ndh->bsh", o, wo)
        x = x + (named(o, ss.ATTN_O) if o_named else o)
        gate = named(x @ wg, ss.MLP_GATE)
        return x + (jax.nn.silu(gate) * named(x @ wu, ss.MLP_UP)) @ wd, None

    def loss(x, *ws):
        y, _ = jax.lax.scan(ss.remat_wrap(layer, keep), x, ws)
        return jnp.sum(y.astype(jnp.float32))

    shapes = [(4, 2048, 2048), (2, 2048, 16, 128), (2, 2048, 8, 128),
              (2, 2048, 8, 128), (2, 16, 128, 2048), (2, 2048, 8192),
              (2, 2048, 8192), (2, 8192, 2048)]
    return jax.grad(loss, argnums=tuple(range(8))), shapes


def _rung_programs(grad_of, labels, args, **jit_kwargs):
    """{label: (pt_flash_fwd calls, pt_rope calls, program bytes)} of the
    scan's gradient compiled at each rung."""
    from paddle_tpu.jit import remat_fit

    out = {}
    for label in labels:
        grad, _ = grad_of(dict(remat_fit.RUNGS)[label])
        compiled = jax.jit(grad, **jit_kwargs).lower(*args).compile()
        calls = _CUSTOM_CALL.findall(compiled.as_text())
        out[label] = (len([c for c in calls if "pt_flash_fwd" in c]),
                      len([c for c in calls if "pt_rope" in c]),
                      remat_fit.program_bytes(compiled))
    return out


def test_recomputed_scan_keeps_the_projections_a_rung_names(one_chip,
                                                            monkeypatch):
    """The layer scan at cell 1's widths compiles for the described chip at
    the lean, the q / k / v and the top rung (ISSUE 54): one ``pt_flash_fwd``
    at each; ``pt_rope`` six times lean (q and k in the forward, in the
    replay and in the backward) and four where q and k are kept; and a
    ``memory_analysis()`` that grows with the rung."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels.pallas import rope as krope

    monkeypatch.setattr(fa, "_interpret", lambda: False)

    def grad_of(keep):
        return _recomputed_llama_scan(
            lambda t: krope.rope_apply(t, 1e6, 0, impl="pallas"),
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True), keep)

    args = [jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
            for s in grad_of(())[1]]
    got = _rung_programs(grad_of, ("lean", "qkv", "qkvo_up_gate"), args)
    assert [got[r][:2] for r in got] == [(1, 6), (1, 4), (1, 4)], got
    assert got["lean"][2] < got["qkv"][2] < got["qkvo_up_gate"][2], got


def test_recomputed_scan_on_the_mesh_keeps_the_projections(topo, monkeypatch):
    """Cell 3's form of the same: on ``dp=2 x mp=2`` RoPE and flash each
    ride their manual region inside the recomputed layer, o_proj's sum is
    ``mp``'s to name, and the rungs are read there too: one ``pt_flash_fwd``
    for the four described chips, fewer ``pt_rope`` calls with q and k kept,
    more bytes a chip with the rung."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.mesh import activation_spec
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.models.llama import _rope
    from paddle_tpu.nn.functional.attention import _sdpa
    from jax.sharding import PartitionSpec as P

    monkeypatch.setattr(fa, "_interpret", lambda: False)

    def grad_of(keep):
        return _recomputed_llama_scan(
            lambda t: _rope.fn(t, theta=1e6, pos_offset=0, impl="pallas"),
            lambda q, k, v: _sdpa.fn(q, k, v, causal=True, scale=128 ** -0.5,
                                     impl="flash"), keep, o_named=False)

    dist.reset_mesh()
    env = dist.init_mesh(dp=2, mp=2, devices=list(topo.devices))
    try:
        shapes = grad_of(())[1]
        shapes[0] = (8,) + shapes[0][1:]   # four rows a data replica
        by_head, rows, cols = P(None, None, "mp", None), \
            P(None, "mp", None, None), P(None, None, "mp")
        held = [env.sharding_for(p) for p in (
            activation_spec(shapes[0], "rows"), by_head, by_head, by_head,
            rows, cols, cols, P(None, "mp", None))]
        args = [jax.ShapeDtypeStruct(s, BF16, sharding=sh)
                for s, sh in zip(shapes, held)]
        got = _rung_programs(grad_of, ("lean", "qkv", "qkv_up_gate"), args,
                             out_shardings=tuple(held))
    finally:
        dist.reset_mesh()
    assert all(got[r][0] == 1 for r in got), got
    assert got["lean"][1] > got["qkv"][1] == got["qkv_up_gate"][1], got
    assert got["lean"][2] < got["qkv"][2] < got["qkv_up_gate"][2], got


@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_fwd_bwd(one_chip, residual):
    from paddle_tpu.kernels.pallas import rmsnorm as krms

    n, hdim = 8192, 2048

    if residual:
        def loss(x, r, w):
            y, s = krms.rms_norm_residual(x, r, w, 1e-6, impl="pallas")
            return jnp.sum(y.astype(jnp.float32)) + \
                jnp.sum(s.astype(jnp.float32))
        shapes = [((n, hdim), BF16), ((n, hdim), BF16), ((hdim,), BF16)]
        argnums = (0, 1, 2)
        names = ("pt_rmsnorm_fwd_residual", "pt_rmsnorm_bwd_residual")
    else:
        def loss(x, w):
            return jnp.sum(krms.rms_norm(x, w, 1e-6, impl="pallas")
                           .astype(jnp.float32))
        shapes = [((n, hdim), BF16), ((hdim,), BF16)]
        argnums = (0, 1)
        names = ("pt_rmsnorm_fwd", "pt_rmsnorm_bwd")
    _compile(jax.grad(loss, argnums=argnums), one_chip, *shapes, names=names)


# cell 1's q / k, a larger batch, and cell 3's per-chip q / k (mp = 2)
@pytest.mark.parametrize("b,h", [(4, 16), (16, 16), (4, 8), (4, 4)])
def test_rope_fwd_inverse(one_chip, b, h):
    from paddle_tpu.kernels.pallas import rope as krope

    def loss(x):  # grad = the inverse rotation through the same kernel
        return jnp.sum(krope.rope_apply(x, 1e4, 0, impl="pallas")
                       .astype(jnp.float32))

    _compile(jax.value_and_grad(loss), one_chip, ((b, 2048, h, 128), BF16),
             names=("pt_rope",))


def test_norms_and_rope_on_the_mesh(topo):
    """Cell 3's kernels as the mesh runs them (``run_kernel_on_mesh``: the
    forward one manual region, the op's own backward another) compile for
    the four described chips on ``dp=2 x mp=2``, and no gradient of an
    activation is all-reduced: what a shard computed for its rows is the
    gradient of its rows (ISSUE 31)."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.mesh import (activation_spec,
                                             compiled_collectives)
    from paddle_tpu.models.llama import _rope
    from paddle_tpu.nn.functional.common import _rms_norm_residual

    def loss(x, r, w, q):
        y, s = _rms_norm_residual.fn(x, r, w, eps=1e-5, impl="pallas")
        o = _rope.fn(q, theta=1e6, pos_offset=0, impl="pallas")
        return sum(jnp.sum(a.astype(jnp.float32)) for a in (y, s, o))

    dist.reset_mesh()
    env = dist.init_mesh(dp=2, mp=2, devices=list(topo.devices))
    try:
        shapes = [(8, 2048, 2048)] * 2 + [(2048,), (8, 2048, 16, 128)]
        layouts = ["rows", "rows", None, "bshd"]
        held = [env.sharding_for(activation_spec(s, lay)) if lay
                else env.replicated() for s, lay in zip(shapes, layouts)]
        args = [jax.ShapeDtypeStruct(s, BF16, sharding=sh)
                for s, sh in zip(shapes, held)]
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)),
                       out_shardings=tuple(held)).lower(*args) \
            .compile().as_text()
    finally:
        dist.reset_mesh()
    kernels = {re.search(r"pt_[a-z0-9_]*[a-z0-9]", c).group(0)
               for c in _CUSTOM_CALL.findall(text)}
    assert kernels == {"pt_rmsnorm_fwd_residual", "pt_rmsnorm_bwd_residual",
                       "pt_rope"}
    reduced = [(r["axes"], r["shapes"])
               for r in compiled_collectives(text, env.mesh)]
    # dw alone: the rows lie over dp and, the sequence, over mp (ISSUE 56)
    assert reduced == [(("dp", "mp"), ("bf16[2048]",))], reduced


def test_mesh_step_hides_its_gathers_and_sends_no_all_reduce(topo,
                                                             monkeypatch):
    """The pin for the next compiler upgrade (ISSUE 56): a two-layer Llama at
    cell 3's widths through ``ShardedTrainStep`` on ``dp=2 x mp=2``, compiled
    for the four described chips. The stream between sublayers lies
    sequence-sharded over ``mp`` and the scan body walks a replica's rows as
    two halves, so every sum over ``mp`` of an activation is a reduce-scatter
    (an ``all-reduce-scatter`` fusion) of HALF the rows, no plain ``mp``
    all-reduce of an activation is left, and all-gathers ride
    ``async-collective-start`` / ``-done`` pairs in the forward loop's body
    AND in the backward's — this compiler makes an all-gather asynchronous
    where independent work exists, never an all-reduce (PERF.md section 7)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.distributed.mesh import compiled_collectives
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import registry
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn.functional import attention

    # this process's backend is the CPU: steer the program's backend
    # branches to their TPU side here, and hold no array on a described chip
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    backend = attention.attention_backend
    monkeypatch.setattr(attention, "attention_backend",
                        lambda sq, sk, hd, platform=None:
                        backend(sq, sk, hd, "tpu"))
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    b, s, h = 8, 2048, 2048
    dist.reset_mesh()
    env = dist.init_mesh(dp=2, mp=2, devices=list(topo.devices))
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=4096, hidden_size=h, intermediate_size=8192,
            num_hidden_layers=2, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=s,
            rope_theta=1e6, use_recompute=True))
        step = dist.ShardedTrainStep(
            model, lambda m, x, y: m(x, labels=y),
            opt.AdamW(learning_rate=3e-4, parameters=model.parameters()))
        ids = [jnp.zeros((b, s), jnp.int32)] * 2
        param_sh, state_sh, frozen_sh, batch_sh = step._sharding_plan(ids)
        repl = env.replicated()
        structs = jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tuple(jit.step_args(step, ids, jax.random.key(0))),
            (param_sh, state_sh, frozen_sh, repl, repl, repl, *batch_sh))
        text = jit.lowerable(step._build(ids)).lower(*structs) \
            .compile().as_text()
    finally:
        dist.reset_mesh()
    rows = compiled_collectives(text, env.mesh)
    over_mp = [r for r in rows if r["axes"] == ("mp",)]
    activation = re.compile(rf"bf16\[\d+,\d+,{h}\]")

    plain = [r for r in over_mp if r["op"] == "all-reduce"
             and any(activation.fullmatch(sh) for sh in r["shapes"])]
    assert not plain, plain
    scattered = {sh: r["count"] for r in over_mp
                 if r["op"] == "reduce-scatter" for sh in r["shapes"]}
    # o_proj's and down_proj's sums forward, the input gradients of q / k / v
    # and of gate / up backward, each for both halves; the embedding's whole
    assert scattered == {f"bf16[{b // 4},{s // 2},{h}]": 8,
                         f"bf16[{b // 2},{s // 2},{h}]": 1}, rows
    gathers = [r for r in over_mp if r["op"] == "all-gather"]
    assert sum(r["async"] for r in gathers) >= 6, gathers
    assert all(r["async"] == 0 for r in rows if r["op"] != "all-gather")

    # where the pairs sit: a start's computation holds the all-gather, whose
    # name stack says which loop body asked for it
    started = set(re.findall(
        r"%async-collective-start[\w.\-]* = .*\bcalls=%([\w.\-]+)", text))
    body, phases = "", set()
    for line in text.splitlines():
        head = re.match(r"%([\w.\-]+) \(", line)
        body = head.group(1) if head else body
        if body in started and " all-gather(" in line:
            stack = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "pt.stack" in stack and "/while/body/" in stack, stack
            phases.add("backward" if "transpose(jvp(" in stack else "forward")
    assert phases == {"forward", "backward"}, phases


def _compile_paged(one_chip, S, W, nh, hd, PL, P, B, dtype):
    from paddle_tpu.kernels.pallas import paged_attention as kpaged

    def run(q, k, v, tables, pos):
        return kpaged.paged_attention(q, k, v, tables, pos, impl="pallas")

    _compile(run, one_chip,
             ((S, W, nh, hd), dtype), ((P, PL, nh, hd), dtype),
             ((P, PL, nh, hd), dtype), ((S, B), jnp.int32),
             ((S, W), jnp.int32), names=("pt_paged_attention",))


@pytest.mark.parametrize("W", [1, 5, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, BF16])
def test_paged_attention(one_chip, W, dtype):
    """GPT-2-small heads (12 x 64), page_len 16: decode (W=1), the
    speculative verify window (W=spec_tokens+1) and a prefill bucket."""
    _compile_paged(one_chip, S=8, W=W, nh=12, hd=64, PL=16, P=257, B=32,
                   dtype=dtype)


@pytest.mark.parametrize("W", [64, 128, 256])
def test_paged_attention_one_row_prefill(one_chip, W):
    """The prefill's call: ONE row (an admission serves one request) at
    GPT-2-large heads (20 x 64) against the serve cell's pool (1153 pages
    of 16 tokens, 64 blocks a row), every prefill bucket."""
    _compile_paged(one_chip, S=1, W=W, nh=20, hd=64, PL=16, P=1153, B=64,
                   dtype=BF16)


@pytest.mark.parametrize("S,W", [(64, 1), (1, 128), (1, 256)])
def test_paged_attention_grouped_query_128(one_chip, S, W):
    """Falcon-H1-34B's attention: 20 query heads over 4 K/V heads of 128
    against the cell's pool (2113 pages of 16 tokens, 32 blocks a row) —
    the 64-slot decode round and both one-row prefill buckets."""
    from paddle_tpu.kernels.pallas import paged_attention as kpaged

    def run(q, k, v, tables, pos):
        return kpaged.paged_attention(q, k, v, tables, pos, impl="pallas")

    _compile(run, one_chip,
             ((S, W, 20, 128), BF16), ((2113, 16, 4, 128), BF16),
             ((2113, 16, 4, 128), BF16), ((S, 32), jnp.int32),
             ((S, W), jnp.int32), names=("pt_paged_attention",))


def test_ssm_step_published_widths(one_chip):
    """One step of the Mamba-2 recurrence at Falcon-H1-34B's widths (32
    heads x 128, state 256, 2 groups) over a 64-slot arena: the state goes
    in and comes out of the SAME buffer."""
    from paddle_tpu.kernels.pallas import ssm_step as kssm

    f32 = jnp.float32
    R, H, P, N, G = 64, 32, 128, 256, 2
    text = _compile(
        lambda s, x, dt, a, b, c, d: kssm.ssm_step(s, x, dt, a, b, c, d,
                                                   impl="pallas"),
        one_chip, ((R, H, P, N), f32), ((R, H, P), f32), ((R, H), f32),
        ((H,), f32), ((R, G, N), f32), ((R, G, N), f32), ((H,), f32),
        names=("pt_ssm_step",))
    assert "output_to_operand_aliasing" in text


def test_retention_step_published_widths(one_chip):
    """One decode step of power retention at Brumby-14B's widths (40 query
    heads x 128 over 8 K/V heads, the tiled phi's 8704 rows) over a 20-slot
    arena: both states go in and come out of the SAME buffers."""
    from paddle_tpu.kernels.pallas import power_retention as pr

    f32 = jnp.float32
    R, H, Hk, d = 20, 40, 8, 128
    text = _compile(
        lambda S, Z, q, k, v, lg, valid: pr.retention_step(
            S, Z, q, k, v, lg, valid, impl="pallas"),
        one_chip, ((R, Hk, pr.phi_dim(d), d), f32), ((R, Hk, d, d), f32),
        ((R, H, d), BF16), ((R, Hk, d), BF16), ((R, Hk, d), BF16),
        ((R, Hk), f32), ((R,), jnp.bool_), names=("pt_retention_step",))
    assert "output_to_operand_aliasing" in text


def test_retention_chunk_published_widths(one_chip):
    """A 2048-token prefill chunk of power retention at Brumby-14B's widths
    from a given state, inner chunks of 128, the state resident in VMEM."""
    from paddle_tpu.kernels.pallas import power_retention as pr

    f32 = jnp.float32
    W, H, Hk, d = 2048, 40, 8, 128
    text = _compile(
        lambda S, Z, q, k, v, lg, valid: pr.retention_chunk(
            S, Z, q, k, v, lg, valid, chunk=128, impl="pallas"),
        one_chip, ((1, Hk, pr.phi_dim(d), d), f32), ((1, Hk, d, d), f32),
        ((1, W, H, d), BF16), ((1, W, Hk, d), BF16), ((1, W, Hk, d), BF16),
        ((1, W, Hk), f32), ((1, W), jnp.bool_),
        names=("pt_retention_chunk",))
    assert "output_to_operand_aliasing" in text


def test_moe_routing_dispatch(one_chip, monkeypatch):
    """The ``moe`` recipe's layer (hidden 1536, 8 experts, top-2, expert
    MLP 2048) through the fused routing/dispatch kernels, fwd + bwd.
    The expert FFN rides megablox ``gmm``, which gates on the backend —
    steered to its TPU branch here, in the test."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.kernels.pallas import moe_dispatch as kmoe

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    b, s, h, e, inter = 2, 2048, 1536, 8, 2048

    def loss(x, wg, w_gate, w_up, w_down):
        out, aux = kmoe.fused_moe_mlp(x, wg, w_gate, w_up, w_down,
                                      top_k=2, impl="pallas")
        return jnp.sum(out.astype(jnp.float32)) + aux

    _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
             ((b, s, h), BF16), ((h, e), jnp.float32),
             ((e, h, inter), BF16), ((e, h, inter), BF16),
             ((e, inter, h), BF16),
             names=("pt_moe_route", "pt_moe_dispatch", "pt_moe_combine"),
             foreign="gmm_")


@pytest.mark.parametrize("S,W", [(128, 1), (1, 256), (1, 512)])
def test_mla_paged_attention_published_widths(one_chip, S, W):
    """openPangu-Ultra-MoE's absorbed latent attention: 128 heads against
    rows of 576 (laid out at 640: whole lanes) in the cell's pool (4161
    pages of 128 tokens, 32 blocks a row) — the 128-slot decode round (a
    ``[128, 640]`` slab a row) and both one-row prefill chunks."""
    from paddle_tpu.kernels.pallas import mla_paged_attention as kmla

    def run(q, arena, tables, start):
        return kmla.mla_paged_attention(q, arena, tables, start, dv=512,
                                        scale=192 ** -0.5, impl="pallas")

    _compile(run, one_chip, ((S, W, 128, 640), BF16),
             ((4161, 128, 640), BF16), ((S, 32), jnp.int32),
             ((S,), jnp.int32), names=("pt_mla_paged_attention",))


# GLM-5.2 (``glm-5.2-d5e16``): 64 heads against latent rows of 576 laid out at
# 640, 32 index heads of 128, contexts to 49 664 tokens (388 pages of 128): the
# 32-slot decode round, the tail buckets and the 2048-token chunk
_DSA_WINDOWS = [(32, 1), (1, 256), (1, 2048)]
_DSA_POOL, _DSA_BLOCKS, _DSA_LP = 3300, 388, 49664


@pytest.mark.parametrize("S,W", _DSA_WINDOWS)
def test_dsa_index_scores_published_widths(one_chip, S, W):
    """The lightning indexer's scores: a tile of 8 tokens x 32 index heads
    against blocks of 512 paged index keys, the tile's ``[8, 49664]`` float32
    scores resident in VMEM."""
    from paddle_tpu.kernels.pallas import dsa_index

    def run(qi, wi, arena, tables, start):
        return dsa_index.dsa_index_scores(qi, wi, arena, tables, start,
                                          impl="pallas")

    _compile(run, one_chip, ((S, W, 32, 128), BF16), ((S, W, 32), jnp.float32),
             ((_DSA_POOL, 128, 128), BF16), ((S, _DSA_BLOCKS), jnp.int32),
             ((S,), jnp.int32), names=("pt_dsa_index_scores",))


@pytest.mark.parametrize("S,W", _DSA_WINDOWS)
def test_mla_sparse_attention_published_widths(one_chip, S, W):
    """Selected latent attention: ``mla_paged_attention``'s slab of 64 heads
    with the selection's ``[8, 512]`` bias tile landing beside each block."""
    from paddle_tpu.kernels.pallas import mla_sparse_attention as ksp

    def run(q, arena, tables, start, bias):
        return ksp.mla_sparse_attention(q, arena, tables, start, bias, dv=512,
                                        scale=256 ** -0.5, impl="pallas")

    _compile(run, one_chip, ((S, W, 64, 640), BF16),
             ((_DSA_POOL, 128, 640), BF16), ((S, _DSA_BLOCKS), jnp.int32),
             ((S,), jnp.int32), ((S, -(-W // 8) * 8, _DSA_LP), jnp.float32),
             names=("pt_mla_sparse_attention",))


# dots3-note-prev (``dots3-note-prev-d5e16``): latent attention of two kinds in
# one cache. Full layers: 128 heads against rows of 576 (640 lanes) under 64
# index heads of 128; window layers: 64 heads against rows of 1088 (1152
# lanes), values 1024 wide, a window of 513 keys. Contexts to 67 584 tokens
# (528 pages of 128): the 32-slot decode round, the tail buckets, the chunk
_D3_WINDOWS = [(32, 1), (1, 256), (1, 512), (1, 2048)]
_D3_POOL, _D3_WINDOW_POOL, _D3_BLOCKS, _D3_LP = 11400, 400, 528, 67584


@pytest.mark.parametrize("S,W", _D3_WINDOWS)
def test_mla_window_attention_published_widths(one_chip, S, W):
    """``mla_paged_attention`` with a window at the shapes no other call has:
    ``dv`` 1024, rows of 1152 lanes, 64 heads — its own name in the program."""
    from paddle_tpu.kernels.pallas import mla_paged_attention as kmla

    def run(q, arena, tables, start):
        return kmla.mla_paged_attention(q, arena, tables, start, dv=1024,
                                        scale=256 ** -0.5, window=513,
                                        impl="pallas")

    _compile(run, one_chip, ((S, W, 64, 1152), BF16),
             ((_D3_WINDOW_POOL, 128, 1152), BF16), ((S, _D3_BLOCKS), jnp.int32),
             ((S,), jnp.int32), names=("pt_mla_window_attention",))


@pytest.mark.parametrize("S,W", [(32, 1), (1, 256), (1, 2048)])
def test_dots3_sparse_layer_kernels_published_widths(one_chip, S, W):
    """The full layers' two kernels at sizes they had never run: 64 index
    heads (the tile's ``[8, 67584]`` float32 scores resident) and 128 query
    heads a slab under the selection's bias."""
    from paddle_tpu.kernels.pallas import dsa_index
    from paddle_tpu.kernels.pallas import mla_sparse_attention as ksp

    def scores(qi, wi, arena, tables, start):
        return dsa_index.dsa_index_scores(qi, wi, arena, tables, start,
                                          impl="pallas")

    _compile(scores, one_chip, ((S, W, 64, 128), BF16),
             ((S, W, 64), jnp.float32), ((_D3_POOL, 128, 128), BF16),
             ((S, _D3_BLOCKS), jnp.int32), ((S,), jnp.int32),
             names=("pt_dsa_index_scores",))

    def attend(q, arena, tables, start, bias):
        return ksp.mla_sparse_attention(q, arena, tables, start, bias, dv=512,
                                        scale=192 ** -0.5, impl="pallas")

    _compile(attend, one_chip, ((S, W, 128, 640), BF16),
             ((_D3_POOL, 128, 640), BF16), ((S, _D3_BLOCKS), jnp.int32),
             ((S,), jnp.int32), ((S, -(-W // 8) * 8, _D3_LP), jnp.float32),
             names=("pt_mla_sparse_attention",))



@pytest.mark.parametrize("h,w,held,tokens", [
    (7680, 2048, 16, 128), (7680, 2048, 16, 512), (7680, 2048, 16, 640),
    (2048, 512, 256, 2176), (2048, 512, 256, 128), (6144, 2048, 16, 2080)],
    ids=["openpangu-round", "openpangu-chunk", "openpangu-carry",
         "laguna-carry", "laguna-round", "glm-carry"])
def test_held_experts_grouped_matmuls_published_widths(one_chip, monkeypatch,
                                                       h, w, held, tokens):
    """An expert layer's held share at the three served configurations'
    widths, under a router of 256 outputs, top 8: openPangu-Ultra-MoE's 16
    held experts of 7680 x 2048 (a decode round's 128 tokens, a 512-token
    chunk, the chunk that carries a round), Laguna's WHOLE layer (256 of
    2048 x 512: the carrying call's 2048 + 128 tokens, a round) and
    GLM-5.2's 16 of 6144 x 2048 (2048 + 32 tokens). The three grouped
    matmuls ride megablox ``gmm`` (JAX's own kernel: the trace shows it as
    ``gmm.N``), which gates on the backend — steered to its TPU branch here
    — under the tiles ``choose_tiling`` takes from these shapes: a tile the
    chip's VMEM refuses fails here, not in a chip call."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.nn.layer.moe import moe_held_experts_mlp

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)

    def run(x, router, w_gate, w_up, w_down):
        return moe_held_experts_mlp(x, router, w_gate, w_up, w_down, top_k=8,
                                    first=0, scale=2.5)

    text = _compile(run, one_chip, ((tokens, h), BF16),
                    ((h, 256), jnp.float32), ((held, h, w), BF16),
                    ((held, h, w), BF16), ((held, w, h), BF16), names=(),
                    foreign="gmm")
    assert sum(c.startswith("gmm") for c in _CUSTOM_CALL.findall(text)) == 3
    # the kernels hand back the rows' dtype: no float32 result the width of
    # the routed pairs is written for a pass outside to round
    assert not re.search(rf"= f32\[{tokens * 8},({h}|{w})\]\S* custom-call",
                         text)


@pytest.mark.parametrize("S,W,H,window", [
    (128, 1, 48, None), (128, 1, 64, 512), (1, 2048, 48, None),
    (1, 2048, 64, 512), (1, 256, 64, 512)])
def test_ranged_paged_attention_published_widths(one_chip, monkeypatch, S, W,
                                                 H, window):
    """Laguna-XS.2's attention over a range of pages: 48 / 64 query heads
    over 8 K/V heads of 128 against ``[pages, 8, 128, 128]`` arenas, 128
    blocks a row (16k tokens) — the 128-slot decode round in both kinds of
    layer, and one-row chunks of up to 2048 tokens (``pt_paged_attention``
    compiles no window above 256) — under the tiles ``choose_tiles`` takes
    from each shape and the ``vmem_limit_bytes`` ``walk_cost`` counts for
    them: tiles the chip's VMEM refuses fail here, not in a chip call."""
    from paddle_tpu.kernels.pallas import ranged_paged_attention as kr

    asked = []
    cost = kr.walk_cost

    def spy(*args, **kwargs):
        asked.append((args[-2], cost(*args, **kwargs)))
        return asked[-1][1]

    monkeypatch.setattr(kr, "walk_cost", spy)

    def run(q, ka, va, tables, start):
        return kr.ranged_paged_attention(q, ka, va, tables, start,
                                         window=window, scale=128 ** -0.5,
                                         impl="pallas")

    pages = 5121 if window is None else 832
    shapes = (((S, W, H, 128), BF16), ((pages, 8, 128, 128), BF16),
              ((pages, 8, 128, 128), BF16), ((S, 128), jnp.int32),
              ((S,), jnp.int32))
    name = "pt_ranged_attention_" + ("full" if window is None else "window")
    _compile(run, one_chip, *shapes, names=(name,))
    # the call compiled under the limit the chooser's tiles asked for (the
    # last reckoning is the call's own, the ones before it the chooser's)
    tiles, c = asked[-1]
    assert tiles == kr.choose_tiles(W, H // 8, 8, 128, 128, window, 2)
    assert c["vmem"] <= kr.VMEM_BUDGET
    if (S, W) == (1, 256):
        # and the limit is the kernel's: a call (traced afresh) that asks
        # for a tenth of what its buffers take is refused
        monkeypatch.setattr(kr, "walk_cost", lambda *a, **k: dict(
            cost(*a, **k), vmem=c["vmem"] // 10))
        with pytest.raises(Exception, match="vmem"):
            _compile(lambda *a: run(*a), one_chip, *shapes, names=(name,))


# Xing4.0 (``xing4.0-29b-a4b-d5``): a stream of 4 x 3584 float32 a token, 32
# heads against latent rows of 576 laid out at 640 in a pool of 6400 pages (66
# blocks a row: contexts to 8448), ALL 64 experts of 3584 x 1024 under a
# router of 64 outputs, top 4: the 128-slot decode round, a tail bucket and
# the 2048-token chunk that carries a round
@pytest.mark.parametrize("tokens", [128, 256, 2176])
def test_mhc_kernel_pair_published_widths(one_chip, tokens):
    """The residual path's two kernels: a tile of 128 tokens' whole stream
    (7.3 MB) resident in VMEM beside the packed projection, the Sinkhorn loop
    on its ``[128, 128]`` logits, the mix into a stream of its own (an
    aliased one read wrong inside a program on the chip: the kernel's
    docstring)."""
    from paddle_tpu.kernels.pallas import mhc

    def pre(x, proj, bias):
        return mhc.mhc_pre(x, proj, bias, n=4, iters=20, eps=1e-6, lo=-30.0,
                           hi=30.0, impl="pallas")

    _compile(pre, one_chip, ((tokens, 14336), jnp.float32),
             ((2, 14336, 128), BF16), ((1, 128), jnp.float32),
             names=("pt_mhc_pre",))

    def post(x, y, maps):
        return mhc.mhc_post(x, y, maps, n=4, impl="pallas")

    text = _compile(post, one_chip, ((tokens, 14336), jnp.float32),
                    ((tokens, 3584), jnp.float32),
                    ((tokens, 128), jnp.float32), names=("pt_mhc_post",))
    assert '"aliasing_operands":{"lists":[]}' in text


@pytest.mark.parametrize("S,W", [(128, 1), (1, 256), (1, 2048)])
def test_xing4_latent_attention_published_widths(one_chip, S, W):
    from paddle_tpu.kernels.pallas import mla_paged_attention as kmla

    def run(q, arena, tables, start):
        return kmla.mla_paged_attention(
            q, arena, tables, start, dv=512,
            scale=192 ** -0.5 * 1.41589 ** 2, impl="pallas")

    _compile(run, one_chip, ((S, W, 32, 640), BF16),
             ((6400, 128, 640), BF16), ((S, 66), jnp.int32),
             ((S,), jnp.int32), names=("pt_mla_paged_attention",))


@pytest.mark.parametrize("tokens", [128, 2176], ids=["round", "carry"])
def test_xing4_whole_expert_layer_published_widths(one_chip, monkeypatch,
                                                   tokens):
    """Every one of the 64 experts held, chosen by the top 4 of score + bias:
    three ``gmm`` calls under the tiles ``choose_tiling`` takes from these
    shapes."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.nn.layer.moe import moe_held_experts_mlp

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)

    def run(x, x32, router, bias, w_gate, w_up, w_down):
        return moe_held_experts_mlp(x, router, w_gate, w_up, w_down, top_k=4,
                                    first=0, scale=2.0, x_route=x32,
                                    bias=bias)

    text = _compile(run, one_chip, ((tokens, 3584), BF16),
                    ((tokens, 3584), jnp.float32), ((3584, 64), jnp.float32),
                    ((64,), jnp.float32), ((64, 3584, 1024), BF16),
                    ((64, 3584, 1024), BF16), ((64, 1024, 3584), BF16),
                    names=(), foreign="gmm")
    assert sum(c.startswith("gmm") for c in _CUSTOM_CALL.findall(text)) == 3


# Nemotron-3-Nano (``nemotron-3-nano-30b-a3b-d9``): layers of ONE mixer each.
# A Mamba-2 layer steps a state of [128 slots, 64 heads, 64, 128] with 8 heads
# a group (a block a (slot, group) of 262 kB where Falcon-H1's is 2.1 MB); the
# attention layer reads 2 K/V heads of 128 under 32 query heads (16 a K/V
# head) in a pool of 4097 pages, 64 blocks a row; an expert layer holds ALL
# 128 two-matrix experts of 2688 x 1856, stored at 1920 lanes, top 6
def test_nemotron_ssm_step_published_widths(one_chip):
    from paddle_tpu.kernels.pallas import ssm_step as kssm

    f32 = jnp.float32
    R, H, P, N, G = 128, 64, 64, 128, 8
    text = _compile(
        lambda s, x, dt, a, b, c, d: kssm.ssm_step(s, x, dt, a, b, c, d,
                                                   impl="pallas"),
        one_chip, ((R, H, P, N), f32), ((R, H, P), f32), ((R, H), f32),
        ((H,), f32), ((R, G, N), f32), ((R, G, N), f32), ((H,), f32),
        names=("pt_ssm_step",))
    assert "output_to_operand_aliasing" in text


@pytest.mark.parametrize("S,W", [(128, 1), (1, 256), (1, 2048)])
def test_nemotron_ranged_attention_published_widths(one_chip, S, W):
    from paddle_tpu.kernels.pallas import ranged_paged_attention as kr

    def run(q, ka, va, tables, start):
        return kr.ranged_paged_attention(q, ka, va, tables, start,
                                         window=None, scale=128 ** -0.5,
                                         impl="pallas")

    _compile(run, one_chip, ((S, W, 32, 128), BF16),
             ((4097, 2, 128, 128), BF16), ((4097, 2, 128, 128), BF16),
             ((S, 64), jnp.int32), ((S,), jnp.int32),
             names=("pt_ranged_attention_full",))


@pytest.mark.parametrize("tokens", [128, 2048], ids=["round", "chunk"])
def test_nemotron_ungated_expert_layer_published_widths(one_chip, monkeypatch,
                                                        tokens):
    """Every one of the 128 experts held, chosen by the top 6 of score +
    bias: TWO ``gmm`` calls (no gate), the first handing back float32 — and no
    copy of a layer's stacked matrices in front of them (at 1856 columns the
    chip lays ``up`` out with another axis last and copies 1.28 GB a call:
    ``NemotronHConfig.expert_lanes``)."""
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.models import NemotronHConfig
    from paddle_tpu.nn.layer.moe import moe_held_experts_mlp

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    lanes = NemotronHConfig().expert_lanes
    assert lanes == 1920

    def run(x, x32, router, bias, w_up, w_down):
        return moe_held_experts_mlp(x, router, None, w_up, w_down, top_k=6,
                                    first=0, scale=2.5, x_route=x32,
                                    bias=bias)

    text = _compile(run, one_chip, ((tokens, 2688), BF16),
                    ((tokens, 2688), jnp.float32), ((2688, 128), jnp.float32),
                    ((128,), jnp.float32), ((128, 2688, lanes), BF16),
                    ((128, lanes, 2688), BF16), names=(), foreign="gmm")
    assert sum(c.startswith("gmm") for c in _CUSTOM_CALL.findall(text)) == 2
    assert not re.search(r"bf16\[128,\d+,\d+\]\S* copy\(", text)


# ZAYA1-8B (``zaya1-8b-d20``): every layer keeps K/V pages of 2 heads x 128
# AND a conv tail by slot ([256 slots, 2688] float32), 8 query heads (4 a K/V
# head), all 16 experts of 2048 x 2048 held, top-1. Two of the published
# layers, the engine's own programs: the 256-row round and the 2048-token
# chunk that carries it, from the tail the chunk before it left
@pytest.mark.parametrize("program", ["round", "chunk2048+round:resume"])
def test_zaya1_programs_copy_no_arena_of_either_kind(one_chip, monkeypatch,
                                                     program):
    """The compiled text of the decode round and of the 2048-token carrying
    program at the published widths: the ranged kernel and the three grouped
    matmuls a layer by name, the K/V arenas and the tail arenas updated in
    place — no copy of a whole arena of pages or of tails."""
    import dataclasses

    from paddle_tpu.jit import lowerable
    from paddle_tpu.kernels import grouped_matmul as gm
    from paddle_tpu.kernels import registry
    from paddle_tpu.models import Zaya1Config
    from paddle_tpu.serving import generation as gen

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    L, S, P, PL, B = 2, 256, 513, 128, 48
    sm = dataclasses.replace(Zaya1Config(), num_hidden_layers=L,
                             layer_types=("hybrid",) * L).served_model()
    assert sm.carries_rounds and sm.cache_spec["layers"] == ["full+state"] * L

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def i32(*s):
        return sd(s, jnp.int32)

    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype),
                                    sm.param_shapes())
    page, tail = (P, 2, PL, 128), (S, sm.cfg.tail_dim)
    arena = [sd(page, BF16) for _ in range(L)]
    tails = [{"tail": sd(tail, jnp.float32)} for _ in range(L)]
    if program == "round":
        step = gen._build_window_step(sm, S, B, PL, 1, True, label="aot:z",
                                      attends={})
        ops = (i32(1, S, B), i32(S, 1), i32(S), i32(S), tails)
    else:
        step = gen._build_window_step(sm, 1, B, PL, 2048, True,
                                      label="aot:z", prefill=True, carry=S,
                                      attends={})
        pair = zip((i32(1, 1, B), i32(1, 2048), i32(1), i32(1)),
                   (i32(1, S, B), i32(S, 1), i32(S), i32(S)))
        row = [{"tail": sd((1, sm.cfg.tail_dim), jnp.float32)}
               for _ in range(L)]
        ops = tuple(pair) + ((row, tails),)
    text = lowerable(step).lower(params, arena, arena, *ops).compile() \
        .as_text()
    calls = [c for c in _CUSTOM_CALL.findall(text)]
    twice = 1 if program == "round" else 2      # the chunk's and the round's
    assert sum("pt_ranged_attention_full" in c for c in calls) == twice * L
    assert sum(c.startswith("gmm") for c in calls) == 3 * L
    assert "_unknown_" not in text
    for dtype, shape in (("bf16", page), ("f32", tail)):
        dims = ",".join(map(str, shape))
        assert not re.search(rf"= {dtype}\[{dims}\]\S* copy\(", text), shape
    # an expert layer's stacked matrices are read where they lie
    assert not re.search(r"bf16\[16,2048,2048\]\S* copy\(", text)
