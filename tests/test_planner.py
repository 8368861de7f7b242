"""Cost-model-driven auto-parallel planner (ISSUE-10 tentpole).

Reference: auto_parallel/planner.py + cost_model.py — plan(model, chips,
hbm) returns the predicted-fastest feasible config. These tests pin the
contract on the 1-device CPU tier-1 box (scoring is arithmetic over one
abstract capture; nothing needs 8 real devices):

- candidate enumeration respects head/kv/expert divisibility and batch
  divisibility over the data axes;
- HBM-infeasible configs are pruned (deliberately tiny hbm_bytes);
- ranking is deterministic call-to-call;
- every dryrun-matrix config round-trips through plan() scoring;
- Engine.prepare(auto_plan=True) applies the top pick end to end.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed.auto_parallel import planner
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, LlamaMoEConfig

# the exact mesh configs the 8-device dryrun matrix executes
# (__graft_entry__._mesh_configs(8))
DRYRUN_MATRIX = (
    {"dp": 2, "mp": 2, "cp": 2},
    {"sharding": 4, "dp": 2, "level": "os_g"},
    {"sharding": 2, "mp": 2, "dp": 2, "level": "p_g_os"},
    {"pp": 2, "dp": 4},
    {"ep": 2, "mp": 2, "dp": 2},
)


def _tiny_profile(batch=16, seq=64, moe=False):
    paddle.seed(0)
    cfg = LlamaMoEConfig.tiny() if moe else LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    return planner.profile_model(model, batch=batch, seq=seq), model


class TestProfile:
    def test_profile_measures_flops_and_acts(self):
        prof, model = _tiny_profile()
        n_params = sum(p.size for p in model.parameters()
                       if not p.stop_gradient)
        assert prof.param_elems == n_params
        assert prof.flops_per_step > 0 and prof.act_bytes > 0
        assert prof.num_heads == 4 and prof.num_kv_heads == 2
        assert prof.batch == 16 and prof.seq == 64

    def test_sample_batch_overrides_shape(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        ids = paddle.randint(0, 256, [4, 32])
        prof = planner.profile_model(model, sample_batch=(ids, ids))
        assert prof.batch == 4 and prof.seq == 32

    def test_non_lm_model_requires_sample_batch(self):
        net = nn.Linear(8, 8)
        with pytest.raises(ValueError, match="sample_batch"):
            planner.profile_model(net, batch=4, seq=8)


class TestEnumeration:
    def test_head_and_kv_divisibility(self):
        prof, _ = _tiny_profile()  # heads=4, kv=2
        cfgs = planner.enumerate_candidates(8, prof, batch=16)
        assert cfgs
        for c in cfgs:
            mp = c["mesh"]["mp"]
            assert prof.num_heads % mp == 0
            assert prof.num_kv_heads % mp == 0
            # kv=2 excludes mp=4 and mp=8 outright
            assert mp <= 2

    def test_expert_divisibility(self):
        prof, _ = _tiny_profile(moe=True)  # 4 experts
        cfgs = planner.enumerate_candidates(8, prof, batch=16)
        eps = {c["mesh"]["ep"] for c in cfgs}
        assert eps - {1}, "expert axis never proposed for a MoE model"
        for c in cfgs:
            assert prof.num_experts % c["mesh"]["ep"] == 0

    def test_no_expert_axis_for_dense_model(self):
        prof, _ = _tiny_profile()
        cfgs = planner.enumerate_candidates(8, prof, batch=16)
        assert all(c["mesh"]["ep"] == 1 for c in cfgs)

    def test_batch_divides_data_axes_and_microbatches(self):
        prof, _ = _tiny_profile(batch=16)
        for c in planner.enumerate_candidates(8, prof, batch=16):
            data = c["mesh"]["dp"] * c["mesh"]["sharding"]
            k = c["accumulate_steps"]
            assert 16 % data == 0
            assert 16 % k == 0 and (16 // k) % data == 0

    def test_mesh_product_always_matches_device_count(self):
        # odd leftover data degrees must not silently shrink the mesh
        # (the dp=2/sharding=data//2 split needs an even data degree)
        prof, _ = _tiny_profile(batch=40)
        for n in (6, 8, 10, 12):
            cfgs = planner.enumerate_candidates(n, prof, batch=40)
            for c in cfgs:
                total = 1
                for d in c["mesh"].values():
                    total *= d
                assert total == n, (n, c["mesh"])

    def test_offload_requires_zero_level(self):
        prof, _ = _tiny_profile()
        for c in planner.enumerate_candidates(8, prof, batch=16):
            if c["offload"]:
                assert c["level"] in ("os", "os_g", "p_g_os")


class TestScoringAndRanking:
    def test_infeasible_pruned_with_tiny_budget(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        # a budget smaller than one param replica: nothing fits
        with pytest.warns(UserWarning, match="no candidate fits"):
            cands = dist.plan(model, n_devices=8, hbm_bytes=1e4,
                              batch=16, seq=64)
        assert cands and all(not c.feasible for c in cands)
        # default return prunes them: a realistic budget returns ONLY
        # feasible candidates unless include_infeasible is passed
        ok = dist.plan(model, n_devices=8, hbm_bytes=16e9,
                       batch=16, seq=64)
        assert ok and all(c.feasible for c in ok)
        both = dist.plan(model, n_devices=8, hbm_bytes=2e6, batch=16,
                         seq=64, include_infeasible=True)
        assert any(not c.feasible for c in both)
        # feasible (if any) strictly precede infeasible in the ranking
        flags = [c.feasible for c in both]
        assert flags == sorted(flags, reverse=True)

    def test_ranking_deterministic(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        a = dist.plan(model, n_devices=8, hbm_bytes=16e9, batch=16, seq=64)
        b = dist.plan(model, n_devices=8, hbm_bytes=16e9, batch=16, seq=64)
        assert [c.describe() for c in a] == [c.describe() for c in b]
        assert [c.predicted_step_s for c in a] == \
            [c.predicted_step_s for c in b]

    def test_bigger_model_needs_more_memory(self):
        prof, _ = _tiny_profile()
        cand = planner.score_config(prof, {"dp": 8}, hbm_bytes=16e9,
                                    drift_ratio=1.0)
        # same config, 100x the params: peak must scale up
        import dataclasses

        prof_big = dataclasses.replace(
            prof, param_bytes=prof.param_bytes * 100,
            param_elems=prof.param_elems * 100)
        big = planner.score_config(prof_big, {"dp": 8}, hbm_bytes=16e9,
                                   drift_ratio=1.0)
        assert big.predicted_peak_bytes > 10 * cand.predicted_peak_bytes

    def test_offload_trades_state_residency_for_transfer_time(self):
        # at flagship scale the host-parked master/state dwarfs the lane's
        # two-group staging working set (tiny models go the OTHER way —
        # staging exceeds the saved state — which the model also captures)
        import dataclasses

        prof, _ = _tiny_profile()
        prof = dataclasses.replace(prof,
                                   param_bytes=prof.param_bytes * 200,
                                   param_elems=prof.param_elems * 200)
        base = planner.score_config(
            prof, {"sharding": 8, "level": "os_g"}, hbm_bytes=16e9,
            drift_ratio=1.0)
        off = planner.score_config(
            prof, {"sharding": 8, "level": "os_g", "offload": True},
            hbm_bytes=16e9, drift_ratio=1.0)
        assert off.predicted_peak_bytes < base.predicted_peak_bytes
        assert off.predicted_step_s > base.predicted_step_s

    def test_multichip_r05_matrix_roundtrips(self):
        """Every config the 8-device dryrun matrix executes must score
        without error and produce finite time + memory predictions."""
        prof_dense, _ = _tiny_profile()
        prof_moe, _ = _tiny_profile(moe=True)
        for raw in DRYRUN_MATRIX:
            prof = prof_moe if raw.get("ep", 1) > 1 else prof_dense
            cand = planner.score_config(prof, dict(raw), hbm_bytes=16e9)
            assert np.isfinite(cand.predicted_step_s) and \
                cand.predicted_step_s > 0, raw
            assert cand.predicted_peak_bytes > 0, raw
            assert cand.feasible, raw  # tiny model, real budget
            # the mesh degrees survive normalization exactly
            for ax, d in raw.items():
                if ax in planner.AXES:
                    assert cand.config["mesh"][ax] == d, (raw, cand.config)

    def test_plan_candidate_config_surfaces(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        cands = dist.plan(model, n_devices=8, hbm_bytes=16e9,
                          batch=16, seq=64)
        top = cands[0]
        mesh = top.mesh
        total = int(np.prod(list(mesh.values())))
        assert total == 8, mesh
        pc = top.pipeline_configs()
        assert pc["accumulate_steps"] >= 1
        assert pc["accumulate_steps"] * pc["micro_batch_size"] == 16
        # the dict feeds fleet's validated strategy directly
        from paddle_tpu.distributed import fleet

        strat = fleet.DistributedStrategy()
        strat.pipeline_configs = pc  # raises on malformed plans
        d = top.to_dict()
        assert d["feasible"] is True and "breakdown" in d

    def test_drift_ratio_scales_the_gate(self):
        prof, _ = _tiny_profile()
        under = planner.score_config(prof, {"dp": 8}, hbm_bytes=16e9,
                                     drift_ratio=0.5)
        over = planner.score_config(prof, {"dp": 8}, hbm_bytes=16e9,
                                    drift_ratio=2.0)
        # a ratio < 1 means the estimator under-predicts XLA: the
        # calibrated peak must be LARGER
        assert under.predicted_peak_bytes > over.predicted_peak_bytes


class TestEngineAutoPlan:
    def test_prepare_auto_plan_applies_top_pick_and_fits(self):
        dist.reset_mesh()
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
        o = opt.AdamW(learning_rate=1e-3, parameters=net.parameters())
        eng = dist.Engine(model=net, loss=lambda out, y: F.mse_loss(out, y),
                          optimizer=o)
        x = paddle.randn([8, 16])
        y = paddle.randn([8, 16])
        # the CPU backend reports no bytes_limit: the caller states one
        eng.prepare(sample_batch=(x, y), auto_plan=True, hbm_bytes=16e9)
        assert eng.applied_plan is not None
        assert eng.plan_candidates and eng.plan_candidates[0].feasible
        assert eng.applied_plan is eng.plan_candidates[0]

        rng = np.random.RandomState(0)

        class DS:
            def __len__(self):
                return 16

            def __getitem__(self, i):
                v = rng.rand(16).astype("float32")
                return v, v * 0.5

        hist = eng.fit(DS(), epochs=1, batch_size=8)
        assert len(hist) == 1 and np.isfinite(hist[0])
        dist.reset_mesh()

    def test_prepare_refuses_infeasible_plan(self):
        """An impossible HBM budget must fail at prepare() time with an
        actionable error, not install a config predicted to OOM."""
        dist.reset_mesh()
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
        o = opt.AdamW(learning_rate=1e-3, parameters=net.parameters())
        eng = dist.Engine(model=net, loss=lambda out, y: F.mse_loss(out, y),
                          optimizer=o)
        x = paddle.randn([8, 16])
        y = paddle.randn([8, 16])
        with pytest.warns(UserWarning, match="no candidate fits"):
            with pytest.raises(ValueError, match="no candidate fits"):
                eng.prepare(sample_batch=(x, y), auto_plan=True,
                            hbm_bytes=10.0)
        assert eng.applied_plan is None
        dist.reset_mesh()

    def test_cost_model_surface_delegates(self):
        from paddle_tpu.cost_model import CostModel

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        cands = CostModel().plan_parallel(model, n_devices=8,
                                          hbm_bytes=16e9, batch=16, seq=64)
        assert cands and cands[0].feasible
