"""Streamed parameter offload (VERDICT r3 next #3): the stacked decoder
weights live in (pinned) host memory and stream through HBM layer by layer.
On the CPU test backend memory kinds are inert, so these tests check the
NUMERICS of the unrolled streaming path against the scan path; the capacity
lift is not measured on the chip (ROADMAP D3)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu import jit


def _run(streamed, steps=4, grad_clip=None):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=4, hidden_size=64,
                           intermediate_size=128, num_attention_heads=4,
                           num_key_value_heads=4, vocab_size=128)
    m = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters(),
                  grad_clip=grad_clip)
    cls = jit.StreamedTrainStep if streamed else jit.TrainStep
    step = cls(m, lambda mm, x, y: mm(x, labels=y), o)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (4, 16)).astype("int32"))
    return [float(step(ids, ids)) for _ in range(steps)], m


def test_streamed_matches_resident_training():
    base, _ = _run(False)
    st, _ = _run(True)
    np.testing.assert_allclose(st, base, rtol=2e-4)
    assert st[-1] < st[0]


def test_streamed_requires_stacked_run():
    import paddle_tpu.nn as nn

    net = nn.Sequential(nn.Linear(4, 4))
    o = opt.SGD(learning_rate=0.1, parameters=net.parameters())
    with pytest.raises(ValueError, match="StackedStageRun"):
        jit.StreamedTrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(),
                              o)


def test_streamed_rejects_pp_mesh():
    """stream is a single-chip capacity feature; pp would fight it."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.meta_parallel import stage_stack

    dist.reset_mesh()
    dist.init_mesh(pp=2, dp=4)
    try:
        stage_stack._STREAM_MODE[0] = True
        with pytest.raises(ValueError, match="single-chip"):
            _run(False, steps=1)  # stack forward sees stream+pp
    finally:
        stage_stack._STREAM_MODE[0] = False
        dist.reset_mesh()


def test_pack_roundtrip():
    """Aligned-slab packing: pack_np -> device unpack restores exactly."""
    import jax.numpy as jnp

    from paddle_tpu.jit.offload_stream import (_needs_pack, _pack_dev,
                                               _pack_np, _unpack_dev)

    rng = np.random.RandomState(0)
    for shape in [(2048,), (11,), (64, 3), (1,), (640, 128)]:
        arr = rng.rand(4, *shape).astype("float32")
        packed = _pack_np(arr)
        assert packed.shape[2] == 128 and packed.shape[1] % 8 == 0
        for i in range(4):
            got = np.asarray(_unpack_dev(jnp.asarray(packed[i]), shape))
            np.testing.assert_array_equal(got, arr[i])
        # device-side pack matches numpy packing
        repacked = np.asarray(_pack_dev(jnp.asarray(arr[2]),
                                        packed.shape[1:]))
        np.testing.assert_array_equal(repacked, packed[2])
    # big matmul weights stay natural
    assert not _needs_pack((2048, 5632), 2)
    assert _needs_pack((2048,), 2)
    assert _needs_pack((2048, 3), 2)
    assert not _needs_pack((16, 128), 2)


def test_streamed_reconstruction_is_safe():
    """Building a second StreamedTrainStep on the same model/optimizer must
    not re-pack already-parked buffers (which would corrupt slab state)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=4, hidden_size=64,
                           intermediate_size=128, num_attention_heads=4,
                           num_key_value_heads=4, vocab_size=128)
    m = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (4, 16)).astype("int32"))
    s1 = jit.StreamedTrainStep(m, lambda mm, x, y: mm(x, labels=y), o)
    a = float(s1(ids, ids))
    s2 = jit.StreamedTrainStep(m, lambda mm, x, y: mm(x, labels=y), o)
    b = float(s2(ids, ids))
    c = float(s2(ids, ids))
    assert np.isfinite([a, b, c]).all()
    assert c < a  # training continued across reconstruction


def test_streamed_global_norm_clip_matches_resident():
    """VERDICT r4 next #10: ClipGradByGlobalNorm on the streamed path — one
    extra norm pass over the host grads — must equal resident clipping.
    A tiny clip_norm makes the coefficient bite every step."""
    import paddle_tpu.nn as nn

    clip = nn.ClipGradByGlobalNorm(0.05)
    base, _ = _run(False, grad_clip=clip)
    st, _ = _run(True, grad_clip=clip)
    np.testing.assert_allclose(st, base, rtol=2e-4)
    assert st[-1] < st[0]


def test_streamed_rejects_per_tensor_clip():
    import paddle_tpu.nn as nn

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    m = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters(),
                  grad_clip=nn.ClipGradByNorm(1.0))
    with pytest.raises(NotImplementedError, match="ClipGradByGlobalNorm"):
        jit.StreamedTrainStep(m, lambda mm, x, y: mm(x, labels=y), o)


def test_segmented_matches_resident_training():
    """VERDICT r4 next #4: the hand-segmented backward (per-layer host
    buffers, no stacked grad accumulator, per-layer vjp + immediate update)
    must reproduce resident training step-for-step."""
    base, _ = _run(False)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=4, hidden_size=64,
                           intermediate_size=128, num_attention_heads=4,
                           num_key_value_heads=4, vocab_size=128)
    m = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = jit.SegmentedTrainStep(m, lambda mm, x, y: mm(x, labels=y), o)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (4, 16)).astype("int32"))
    seg = [float(step(ids, ids)) for _ in range(4)]
    np.testing.assert_allclose(seg, base, rtol=2e-4)
    # checkpoint hook: stacked reassembly matches the trained per-layer rows
    arrs = step.state_dict_arrays()
    assert all(a.shape[0] == 4 for a in arrs.values())
    # ordinary checkpointing must see REAL weights, not freed placeholders
    sd = m.state_dict()
    stacked = [v for k, v in sd.items() if getattr(v, "ndim", 0) >= 1
               and v.shape and v.shape[0] == 4]
    assert stacked, "segmented state_dict lost the decoder stacks"
    assert all(float(np.abs(np.asarray(v.numpy(), dtype="float32")).sum()) > 0
               for v in stacked)


def test_segmented_requires_single_run():
    import paddle_tpu.nn as nn

    net = nn.Sequential(nn.Linear(4, 4))
    o = opt.SGD(learning_rate=0.1, parameters=net.parameters())
    with pytest.raises(ValueError, match="StackedStageRun"):
        jit.SegmentedTrainStep(net, lambda m, x, y: ((m(x) - y) ** 2).mean(),
                               o)


def test_segmented_buffers_keep_true_shapes(monkeypatch):
    """r5 TPU regression guard: with a real host sharding, SegmentedTrainStep
    must park per-layer buffers at their TRUE shapes (StreamedTrainStep's
    [L,R,128] slab packing bound slab-shaped weights into the template on
    TPU — CPU tests missed it because _memory_sharding is None there).
    Forcing a plain CPU SingleDeviceSharding exercises the non-None path."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.distributed.meta_parallel import stage_stack
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cpu = jax.devices("cpu")[0]
    monkeypatch.setattr(stage_stack, "_memory_sharding",
                        lambda kind: SingleDeviceSharding(cpu))
    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=3, hidden_size=64,
                           intermediate_size=96,  # 96 % 128 != 0: odd shape
                           num_attention_heads=4, num_key_value_heads=4,
                           vocab_size=128)
    m = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = jit.SegmentedTrainStep(m, lambda mm, x, y: mm(x, labels=y), o)
    tpl = dict(step.run._template[0].named_parameters())
    for j, (safe, orig) in enumerate(step.run._names):
        want = tuple(tpl[orig].shape)
        for i in range(step.depth):
            got = tuple(step._layer_params[i][j].shape)
            assert got == want, f"layer {i} param {orig}: {got} != {want}"
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (2, 16)).astype("int32"))
    losses = [float(step(ids, ids)) for _ in range(3)]
    assert losses[-1] < losses[0]
