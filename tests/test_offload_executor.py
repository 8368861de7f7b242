"""Latency-hiding streaming executor (ISSUE-5): the offload train path
streams params/optimizer state per GROUP through a double-buffered
host<->device lane instead of round-tripping the whole set serialized.
On the CPU test backend both "host" and "device" are the same chip, so
overlap buys no wall clock here — these tests pin NUMERICS (overlapped
bit-equal to serialized), the group SCHEDULE (pipelined submission
order, also under accumulate(k)), and the telemetry/analysis surfaces;
the latency story is not measured on the chip (ROADMAP D3)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.jit.offload_stream import StreamLane, plan_stream_groups

# group sizing that forces a multi-group walk on the tiny test net
_KNOBS = dict(segment_size=2048, buffer_max_size=4096)


# -- planner ------------------------------------------------------------------

def test_plan_stream_groups_coalesce_order_and_cap():
    # small params coalesce until segment_size, never growing past the cap
    groups = plan_stream_groups([2048, 128, 2048, 64], 2048, 4096)
    assert groups == [[0], [1, 2], [3]]
    # partition: every index exactly once, walk order preserved
    flat = [i for g in groups for i in g]
    assert flat == list(range(4))
    # one param larger than the cap still gets its own (unsplittable) group
    assert plan_stream_groups([10 ** 9, 64], 2048, 4096) == [[0], [1]]
    # everything fits one segment -> one group
    assert plan_stream_groups([10, 10, 10], 2 ** 20, 2 ** 23) == [[0, 1, 2]]


# -- lane ---------------------------------------------------------------------

@pytest.mark.parametrize("overlap", [True, False])
def test_stream_lane_counters(overlap):
    import jax

    cpu = jax.devices("cpu")[0]
    lane = StreamLane(overlap=overlap)
    try:
        a = np.ones((256,), np.float32)
        h = lane.submit("h2d", [a, a], cpu, tag=0)
        out = h.wait()
        assert len(out) == 2 and float(out[0][0]) == 1.0
        lane.submit("d2h", [out[0]], cpu, tag=0).wait()
        s = lane.stats()
        assert s["h2d_bytes"] == 2 * a.nbytes
        assert s["d2h_bytes"] == a.nbytes
        assert s["transfers"] == 2
        assert s["overlap"] is overlap
        assert 0.0 <= s["overlap_efficiency"] <= 1.0
        if not overlap:
            # inline transfers: the consumer waited for every ms
            assert s["overlap_efficiency"] == 0.0
        assert lane.events == [("h2d", 0), ("d2h", 0)]
    finally:
        lane.close()


def test_stream_lane_error_surfaces_at_wait():
    lane = StreamLane(overlap=True)
    try:
        bad = lane.submit("h2d", [object()], None, tag=9)
        with pytest.raises(Exception):
            bad.wait()
    finally:
        lane.close()


# -- the executor -------------------------------------------------------------

def _stream_run(overlap, accumulate=0, steps=4, level="os_g", clip=None,
                eager=True):
    """One offload training run with the lane forced (non-)overlapping;
    returns losses, final params, and the step object (mesh torn down)."""
    paddle.seed(7)
    dist.reset_mesh()
    dist.init_mesh(dp=2, sharding=4)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
    o = opt.AdamW(learning_rate=0.02, parameters=net.parameters(),
                  grad_clip=clip)
    model, o = dist.group_sharded_parallel(net, o, level=level, offload=True,
                                           **_KNOBS)
    step = dist.ShardedTrainStep(net, lambda m, x, y: F.mse_loss(m(x), y), o)
    step._stream_overlap = overlap
    step._stream_eager = eager
    if accumulate:
        step = step.accumulate(accumulate)
    x = paddle.to_tensor(np.random.RandomState(3).rand(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(4).rand(8, 16).astype("float32"))
    losses = [float(step(x, y)) for _ in range(steps)]
    params = [np.asarray(p.data) for p in net.parameters()]
    inner = step._step if accumulate else step
    dist.reset_mesh()
    return losses, params, inner


@pytest.mark.dist
def test_overlapped_bit_equal_to_serialized():
    """The acceptance parity: same executables, same dispatch order —
    hiding the transfers must not change a single bit. Includes a
    global-norm clip, which the executor hoists out of the per-group
    updates (clipping one group's grads alone would be wrong)."""
    clip = nn.ClipGradByGlobalNorm(0.5)
    ov_l, ov_p, ov_step = _stream_run(True, clip=clip)
    se_l, se_p, se_step = _stream_run(False, clip=clip)
    assert ov_l == se_l  # float-exact
    for a, b in zip(ov_p, se_p):
        np.testing.assert_array_equal(a, b)
    assert ov_l[-1] < ov_l[0]
    # multi-group walk actually happened, and only the overlapped lane hid
    # transfer time behind compute
    assert len(ov_step._stream[0]) >= 2
    assert ov_step.stream_stats()["overlap_efficiency"] > 0.0
    assert se_step.stream_stats()["overlap_efficiency"] == 0.0


@pytest.mark.dist
def test_group_schedule_is_pipelined():
    """While group i's update computes, group i+1's grads are already
    going down and group i-1's params up — pinned via the lane's
    submission log."""
    _, _, step = _stream_run(True, steps=2)
    groups = step._stream[0]
    g = len(groups)
    assert g >= 3, "knobs must force a multi-group walk"
    sched = step.stream_schedule()
    per_step = len(sched) // 2
    one = sched[:per_step]
    assert sched[per_step:] == one  # schedule is stable across steps
    downs = [tag for kind, tag in one if kind == "d2h"]
    ups = [tag for kind, tag in one if kind == "h2d"]
    assert downs == list(range(g)) and ups == list(range(g))
    for gi in range(g):
        # a group's grads go down before its params come back up
        assert one.index(("d2h", gi)) < one.index(("h2d", gi))
        if gi + 1 < g:
            # the NEXT group's download is in flight before this group's
            # upload — the double buffer, not a serial round-trip
            assert one.index(("d2h", gi + 1)) < one.index(("h2d", gi))


@pytest.mark.dist
def test_accumulate_composes_with_streaming_offload():
    """step.accumulate(k) on the offload path: one fused fwd+bwd window,
    then the SAME per-group streaming update — bit-equal overlapped vs
    serialized, same pipelined schedule, and allclose to the resident
    fused accumulate."""
    ov_l, ov_p, ov_step = _stream_run(True, accumulate=2)
    se_l, se_p, _ = _stream_run(False, accumulate=2)
    assert ov_l == se_l
    for a, b in zip(ov_p, se_p):
        np.testing.assert_array_equal(a, b)
    sched = ov_step.stream_schedule()
    g = len(ov_step._stream[0])
    one = sched[:len(sched) // 4]
    assert [t for k, t in one if k == "d2h"] == list(range(g))

    # resident twin (no offload) of the same window
    paddle.seed(7)
    dist.reset_mesh()
    dist.init_mesh(dp=2, sharding=4)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
    o = opt.AdamW(learning_rate=0.02, parameters=net.parameters())
    model, o = dist.group_sharded_parallel(net, o, level="os_g")
    step = dist.ShardedTrainStep(
        net, lambda m, x, y: F.mse_loss(m(x), y), o).accumulate(2)
    x = paddle.to_tensor(np.random.RandomState(3).rand(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(4).rand(8, 16).astype("float32"))
    res_l = [float(step(x, y)) for _ in range(4)]
    dist.reset_mesh()
    np.testing.assert_allclose(ov_l, res_l, rtol=2e-5)


@pytest.mark.dist
def test_offload_stream_observability():
    """The lane shows up from the outside: ``offload_stream`` counter
    family carries the bytes, the step timeline gains a ``stream_wait``
    phase, and both land in the one-JSON snapshot."""
    import paddle_tpu.observability as obs

    fam = obs.family("offload_stream")
    tl = obs.timeline()
    tl.reset()
    h2d0 = fam.get(("h2d_bytes",))
    _, _, step = _stream_run(True, steps=2)
    assert fam.get(("h2d_bytes",)) > h2d0
    assert fam.get(("transfers",)) > 0
    s = tl.summary()
    assert s["steps"] == 2
    assert s["phases"]["stream_wait"]["count"] >= 1, s["phases"]
    snap = obs.snapshot()
    assert "offload_stream" in snap
    # exposition renders the derived overlap line for pd_top
    text = obs.render_snapshot(snap)
    assert "offload_stream" in text and "overlap_efficiency" in text
    # per-step-object counters agree in kind
    st = step.stream_stats()
    assert st["h2d_bytes"] > 0 and st["d2h_bytes"] > 0


@pytest.mark.dist
def test_analysis_models_two_group_working_set():
    """The HBM estimator charges the streamed step the two-group staging
    working set, not the full master+state residency."""
    import paddle_tpu.analysis as analysis

    paddle.seed(7)
    dist.reset_mesh()
    dist.init_mesh(dp=2, sharding=4)
    net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
    o = opt.AdamW(learning_rate=0.02, parameters=net.parameters())
    model, o = dist.group_sharded_parallel(net, o, level="os_g",
                                           offload=True, **_KNOBS)
    step = dist.ShardedTrainStep(net, lambda m, x, y: F.mse_loss(m(x), y), o)
    plan = analysis.offload_stream_plan(step)
    assert plan["groups"] >= 2
    assert plan["working_set_bytes"] == 2 * plan["max_group_staging_bytes"]
    assert plan["working_set_bytes"] < plan["full_residency_bytes"]
    x = paddle.to_tensor(np.zeros((8, 16), np.float32))
    y = paddle.to_tensor(np.zeros((8, 16), np.float32))
    est = analysis.estimate_offload_stream_hbm(step, x, y)
    assert est["peak_bytes"] == (est["device_program_peak_bytes"]
                                 + est["stream_working_set_bytes"])
    diags = analysis.stream_plan_check(step, x, y, hbm_bytes=int(16e9))
    assert [d.code for d in diags] == ["MM012"]  # tiny net fits
    dist.reset_mesh()


@pytest.mark.dist
@pytest.mark.slow
def test_llama_stream_ab_parity():
    """The overlap A/B at test scale (run by tools/ci.sh; slow-marked for
    tier-1 wall clock): a tiny Llama through
    group_sharded_parallel(offload=True), overlapped vs serialized lane,
    losses bit-equal and transfer time measurably hidden."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    def run(overlap):
        paddle.seed(0)
        dist.reset_mesh()
        dist.init_mesh(dp=2, sharding=4)
        m = LlamaForCausalLM(LlamaConfig.tiny())
        o = opt.AdamW(learning_rate=3e-4, parameters=m.parameters())
        m2, o = dist.group_sharded_parallel(m, o, level="os", offload=True)
        step = dist.ShardedTrainStep(m, lambda mm, x, y: mm(x, labels=y), o)
        step._stream_overlap = overlap
        ids = paddle.randint(0, 128, [8, 16])
        losses = [float(step(ids, ids)) for _ in range(3)]
        eff = step.stream_stats()["overlap_efficiency"]
        dist.reset_mesh()
        return losses, eff

    ov_l, ov_eff = run(True)
    se_l, se_eff = run(False)
    assert ov_l == se_l
    assert ov_l[-1] < ov_l[0]
    assert ov_eff > 0.0 and se_eff == 0.0


# -- cross-step pipeline fill + pinned staging (ISSUE-10 PR-5 carried) --------

@pytest.mark.dist
def test_eager_fill_bit_equal_to_boundary_drain():
    """The cross-step fill (final uploads handed to the next dispatch as
    jax futures, so the next step's group-0 grad download overlaps the
    fwd+bwd window) changes SCHEDULING only: losses and params must stay
    bit-equal to the drain-at-boundary walk AND to the serialized lane."""
    clip = nn.ClipGradByGlobalNorm(0.5)
    ea_l, ea_p, ea_step = _stream_run(True, clip=clip, eager=True)
    dr_l, dr_p, _ = _stream_run(True, clip=clip, eager=False)
    se_l, se_p, _ = _stream_run(False, clip=clip)
    assert ea_l == dr_l == se_l  # float-exact
    for a, b in zip(ea_p, dr_p):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ea_p, se_p):
        np.testing.assert_array_equal(a, b)
    # the walk really pipelined (multi-group) and hid transfer time
    assert len(ea_step._stream[0]) >= 2
    assert ea_step.stream_stats()["overlap_efficiency"] > 0.0


@pytest.mark.dist
def test_eager_fill_composes_with_accumulate():
    ea_l, ea_p, _ = _stream_run(True, accumulate=2, eager=True)
    dr_l, dr_p, _ = _stream_run(True, accumulate=2, eager=False)
    assert ea_l == dr_l
    for a, b in zip(ea_p, dr_p):
        np.testing.assert_array_equal(a, b)


def test_wait_dispatched_returns_usable_futures():
    """Lane-level contract of the fill: wait_dispatched() hands back the
    transfer's result arrays as soon as they are issued; consuming them
    (or waiting again) sees the same landed bytes wait() would."""
    import jax

    cpu = jax.devices("cpu")[0]
    lane = StreamLane(overlap=True)
    try:
        a = np.arange(512, dtype=np.float32)
        h = lane.submit("h2d", [a], cpu, tag=0)
        early = h.wait_dispatched()
        assert len(early) == 1
        np.testing.assert_array_equal(np.asarray(early[0]), a)
        landed = h.wait()
        assert landed[0] is early[0]
        # serialized lanes resolve at submit: both surfaces identical
        ser = StreamLane(overlap=False)
        try:
            h2 = ser.submit("h2d", [a], cpu, tag=1)
            assert h2.wait_dispatched()[0] is h2.wait()[0]
        finally:
            ser.close()
    finally:
        lane.close()


def test_wait_dispatched_surfaces_lane_failure():
    lane = StreamLane(overlap=True)
    try:
        bad = lane.submit("h2d", [object()], None, tag=3)
        with pytest.raises(Exception):
            bad.wait_dispatched()
    finally:
        lane.close()


def test_pinned_staging_probe_falls_back_on_cpu():
    """Satellite contract: the pinned-host memory_kind staging arms ONLY
    where the backend exposes a usable pinned_host space — the CPU tier-1
    backend must take the direct path untouched."""
    from paddle_tpu.jit.offload_stream import pinned_host_supported

    assert pinned_host_supported() is False  # CPU test backend
    lane = StreamLane(overlap=True, pinned_staging=True)  # explicit ask
    try:
        assert lane.pinned_staging is False  # probe fell back cleanly
        import jax

        cpu = jax.devices("cpu")[0]
        a = np.ones((64,), np.float32)
        out = lane.submit("h2d", [a], cpu, tag=0).wait()
        np.testing.assert_array_equal(np.asarray(out[0]), a)
        s = lane.stats()
        assert s["pinned_staging"] is False
        assert s["pinned_staged"] == 0
    finally:
        lane.close()
