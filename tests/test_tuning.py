"""ISSUE 20: online auto-tuning — the runtime that retunes itself.

Pure-layer coverage for the tuning stack: the regression detector's
trigger/no-trigger matrix (a single spike never fences a fleet), the
quantile-cover derivation (property-style over seeded workloads), the
restart-safe telemetry windows (``SloTracker`` monotonic rebase +
``HistogramWindow``), ``BucketSpec`` validation shared by hand-declared
and derived specs, live planner re-scoring with measured anchors, the
``ServingEngine.respec`` zero-retrace cutover, the policy driver
(``OnlineTuner``: ledger, embargo, kill-switch), and the elastic plan
tuner's full keep/rollback protocol over a fake control-plane store.
The real multi-process loop is drilled end to end by
``tools/tuning_drill.py`` (ci.sh gate).
"""
import json
import math
import random
import time
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.tuning import (
    OnlineTuner, Proposal, RegressionDetector, TuningPolicy,
    derive_buckets_from_histogram, derive_slots_from_histogram,
    padding_waste, quantile_cover, shape_digest, sizes_from_histogram,
    weighted_quantile,
)


# -- regression detector (satellite 3: unit matrix) ---------------------------

def _warm(det, ms=100.0, n=12):
    for _ in range(n):
        det.update(ms)
    return det


class TestRegressionDetector:
    def test_warming_then_ok(self):
        det = RegressionDetector(min_samples=8)
        for i in range(7):
            assert det.update(100.0) == "warming"
        assert det.update(100.0) == "ok"
        assert det.baseline_ms() == pytest.approx(100.0)

    def test_single_spike_never_triggers(self):
        det = _warm(RegressionDetector(sustain_n=5))
        assert det.update(1000.0) == "ok"     # one spike: GC, scrape, ...
        for _ in range(20):
            assert det.update(100.0) == "ok"
        assert det.triggers == 0

    def test_noise_below_threshold_never_triggers(self):
        det = RegressionDetector(trigger_ratio=1.3, min_abs_ms=5.0)
        rng = random.Random(0)
        for _ in range(300):
            det.update(100.0 + rng.uniform(-8, 8))  # +-8% jitter
        assert det.triggers == 0
        assert det.state == "ok"

    def test_sustained_regression_triggers_and_anchors(self):
        det = _warm(RegressionDetector(sustain_n=5))
        states = [det.update(200.0) for _ in range(5)]
        assert states[:4] == ["ok"] * 4 and states[4] == "regressed"
        assert det.triggers == 1
        # the anchor is the measured degraded level, not the baseline
        assert det.regressed_ms() == pytest.approx(200.0)
        assert det.baseline_ms() == pytest.approx(100.0)  # frozen

    def test_baseline_frozen_during_elevated_run(self):
        det = _warm(RegressionDetector(sustain_n=5, baseline_window=8))
        for _ in range(30):
            det.update(300.0)
        # 30 elevated samples did NOT drag the baseline up to 300
        assert det.baseline_ms() == pytest.approx(100.0)

    def test_hysteresis_recovery(self):
        det = _warm(RegressionDetector(sustain_n=3, recover_n=4,
                                       trigger_ratio=1.3,
                                       recover_ratio=1.1))
        for _ in range(3):
            det.update(200.0)
        assert det.state == "regressed"
        # sitting between recover and trigger thresholds: still regressed
        for _ in range(10):
            assert det.update(125.0) == "regressed"
        # recovery needs recover_n CONSECUTIVE healthy samples
        for _ in range(3):
            det.update(100.0)
        det.update(150.0)  # breaks the run
        for _ in range(3):
            assert det.update(100.0) == "regressed"
        assert det.update(100.0) == "ok"

    def test_validation(self):
        with pytest.raises(ValueError):
            RegressionDetector(recover_ratio=1.5, trigger_ratio=1.3)
        with pytest.raises(ValueError):
            RegressionDetector(sustain_n=1)


# -- quantile-cover (satellite 3: property-style) -----------------------------

class TestQuantileCover:
    def test_covers_quantile_and_bounds_waste_or_exhausts_budget(self):
        rng = random.Random(42)
        for trial in range(25):
            n = rng.randint(20, 400)
            dist_kind = trial % 3
            if dist_kind == 0:
                sizes = [rng.randint(1, 512) for _ in range(n)]
            elif dist_kind == 1:  # zipf-ish head-heavy
                sizes = [min(512, int(rng.paretovariate(1.2)))
                         for _ in range(n)]
            else:  # bimodal
                sizes = [rng.choice((8, 9, 10, 300, 310))
                         for _ in range(n)]
            q, max_waste, max_buckets = 0.99, 0.25, 6
            buckets = quantile_cover(sizes, q=q, max_waste=max_waste,
                                     max_buckets=max_buckets)
            assert buckets == tuple(sorted(set(buckets)))  # strict asc
            pq = weighted_quantile(sizes, q)
            assert buckets[-1] >= pq, "p99 must be covered"
            covered = [s for s in sizes if s <= pq]
            w = padding_waste(covered, buckets)
            # the waste bound holds UNLESS the bucket budget ran out
            assert w <= max_waste + 1e-9 or len(buckets) == max_buckets, \
                (trial, w, buckets)

    def test_deterministic(self):
        rng = random.Random(7)
        sizes = [rng.randint(1, 200) for _ in range(150)]
        a = quantile_cover(sizes, q=0.95, max_waste=0.2)
        b = quantile_cover(list(sizes), q=0.95, max_waste=0.2)
        assert a == b

    def test_align_and_min_bucket(self):
        buckets = quantile_cover([3, 5, 17, 40], q=1.0, max_waste=0.0,
                                 align=8, max_buckets=8)
        assert all(b % 8 == 0 for b in buckets)
        buckets = quantile_cover([1, 2, 3, 100], q=1.0, max_waste=0.0,
                                 min_bucket=16, max_buckets=8)
        assert min(buckets) >= 16

    def test_max_size_drops_over_limit_sizes_and_clamps_cover(self):
        # sizes past the engine hard limit are REJECTED, not padded —
        # they leave the derivation; the cover clamps to the limit
        buckets = quantile_cover([10, 20, 90], q=1.0, max_size=64,
                                 align=64)
        assert buckets == (64,)
        # but a clamp never un-covers an in-range quantile
        buckets = quantile_cover([10, 20, 60], q=1.0, max_size=48)
        assert buckets[-1] >= 48 or buckets[-1] == 20

    def test_single_size_single_bucket(self):
        assert quantile_cover([32] * 50, q=0.99, max_waste=0.1) == (32,)
        assert padding_waste([32] * 50, (32,)) == 0.0

    def test_empty_and_validation(self):
        assert quantile_cover([], q=0.99) == ()
        with pytest.raises(ValueError):
            quantile_cover([1], q=0.0)
        with pytest.raises(ValueError):
            quantile_cover([1], max_waste=1.0)

    def test_weighted_pairs_match_expanded(self):
        expanded = [4] * 30 + [16] * 10
        pairs = [(4, 30.0), (16, 10.0)]
        assert quantile_cover(expanded, q=0.99) == \
            quantile_cover(pairs, q=0.99)


# -- histogram adapters -------------------------------------------------------

class TestHistogramAdapters:
    def test_sizes_collapse_to_upper_bound_and_inf_clamps(self):
        bounds = (4.0, 16.0, 64.0, float("inf"))
        counts = (10, 5, 0, 2)
        sizes = sizes_from_histogram(bounds, counts)
        assert sizes == [(4, 10.0), (16, 5.0), (64, 2.0)]

    def test_derive_buckets_and_slots(self):
        bounds = (4.0, 16.0, 64.0, float("inf"))
        buckets = derive_buckets_from_histogram(bounds, (80, 15, 5, 0),
                                                q=0.99, max_waste=0.3)
        assert buckets and buckets[-1] >= 64
        assert 4 in buckets  # the dominant mass gets its own bucket
        slots = derive_slots_from_histogram((1.0, 2.0, 4.0, 8.0),
                                            (5, 10, 40, 2), q=0.99,
                                            headroom=1)
        assert slots == 9  # p99 occupancy 8 + 1 headroom
        assert derive_slots_from_histogram((1.0,), (0, 0)) is None

    def test_shape_digest_stable_and_order_free(self):
        a = shape_digest({"prefill_buckets": [4, 8], "max_slots": 3})
        b = shape_digest({"max_slots": 3, "prefill_buckets": [4, 8]})
        assert a == b and len(a) == 12
        assert a != shape_digest({"prefill_buckets": [4, 8],
                                  "max_slots": 4})


# -- restart-safe windows (satellite 1) ---------------------------------------

class TestRestartSafety:
    def test_slo_tracker_restart_mid_window_counts_new_traffic(self):
        """A replica restart must read as a PAUSE: the window neither
        goes negative nor spikes, and post-restart traffic keeps
        counting inside the same window (no muted remainder)."""
        from paddle_tpu.observability.fleet import SloPolicy, SloTracker
        from paddle_tpu.observability.registry import Histogram

        trk = SloTracker(SloPolicy(target_ms=10.0, objective=0.9,
                                   window_s=100.0))
        h = Histogram("lat", buckets=(10.0, 100.0))
        trk.update(0.0, per_pool={}, fleet=h.snapshot())
        for _ in range(10):
            h.observe(1.0)
        v = trk.update(10.0, per_pool={}, fleet=h.snapshot())
        assert v["fleet"]["requests_window"] == 10

        # restart mid-window: cumulative counts step backward
        fresh = Histogram("lat", buckets=(10.0, 100.0))
        v = trk.update(20.0, per_pool={}, fleet=fresh.snapshot())
        f = v["fleet"]
        assert f["requests_window"] >= 0 and f["errors_window"] >= 0
        assert f["requests_window"] <= 10  # never a phantom spike

        # post-restart traffic lands in the SAME window immediately
        for _ in range(6):
            fresh.observe(1.0)
        for _ in range(2):
            fresh.observe(50.0)
        v = trk.update(30.0, per_pool={}, fleet=fresh.snapshot())
        f = v["fleet"]
        assert f["requests_window"] == 18  # 10 pre + 8 post restart
        assert f["errors_window"] == 2
        assert f["burn_rate"] > 0

    def test_histogram_window_delta_and_restart_rebase(self):
        from paddle_tpu.observability.fleet import HistogramWindow
        from paddle_tpu.observability.registry import Histogram

        win = HistogramWindow(window_s=100.0)
        h = Histogram("sz", buckets=(4.0, 16.0))
        for v in (1, 2, 10):
            h.observe(v)
        win.update(0.0, h.snapshot())
        for v in (1, 1, 20):
            h.observe(v)
        win.update(10.0, h.snapshot())
        bounds, counts = win.delta(10.0)
        assert bounds == (4.0, 16.0, float("inf"))
        assert counts == [2, 0, 1]  # only the second batch is in-delta
        assert win.total(10.0) == 3

        # restart: a fresh histogram's lower counts must not go negative
        fresh = Histogram("sz", buckets=(4.0, 16.0))
        fresh.observe(3)
        win.update(20.0, fresh.snapshot())
        _b, counts = win.delta(20.0)
        assert all(c >= 0 for c in counts)
        assert win.rebases == 1
        fresh.observe(3)
        fresh.observe(3)
        win.update(30.0, fresh.snapshot())
        _b, counts = win.delta(30.0)
        assert counts[0] >= 2  # post-restart traffic visible in-window

    def test_histogram_window_layout_change_resets(self):
        from paddle_tpu.observability.fleet import HistogramWindow
        from paddle_tpu.observability.registry import Histogram

        win = HistogramWindow(window_s=100.0)
        a = Histogram("sz", buckets=(4.0, 16.0))
        a.observe(1)
        win.update(0.0, a.snapshot())
        b = Histogram("sz", buckets=(8.0, 32.0))  # respec'd layout
        b.observe(1)
        win.update(1.0, b.snapshot())
        bounds, counts = win.delta(1.0)
        assert bounds == (8.0, 32.0, float("inf"))
        assert sum(counts) == 0  # no cross-layout delta is invented


# -- BucketSpec validation (satellite 2) --------------------------------------

class TestBucketSpecValidation:
    def test_duplicates_rejected(self):
        from paddle_tpu.serving import BucketSpec

        with pytest.raises(ValueError, match="duplicate"):
            BucketSpec(batch_sizes=(1, 2, 2, 4))
        with pytest.raises(ValueError, match="duplicate"):
            BucketSpec(batch_sizes=(1,), seq_lens=(8, 8))

    def test_non_positive_and_non_int_rejected(self):
        from paddle_tpu.serving import BucketSpec

        with pytest.raises(ValueError, match="positive"):
            BucketSpec(batch_sizes=(0, 1))
        with pytest.raises(ValueError, match="positive"):
            BucketSpec(batch_sizes=(1,), seq_lens=(8, -16))
        with pytest.raises(ValueError, match="positive"):
            BucketSpec(batch_sizes=(1.5, 2))

    def test_order_insensitive_canonicalized(self):
        from paddle_tpu.serving import BucketSpec

        spec = BucketSpec(batch_sizes=(8, 1, 4, 2), seq_lens=(64, 16))
        assert spec.batch_sizes == (1, 2, 4, 8)
        assert spec.seq_lens == (16, 64)

    def test_observed_floor_rejects_dead_buckets(self):
        from paddle_tpu.serving import BucketSpec

        with pytest.raises(ValueError, match="observed"):
            BucketSpec(batch_sizes=(1,), seq_lens=(8, 64),
                       observed_floor=16)
        ok = BucketSpec(batch_sizes=(1,), seq_lens=(16, 64),
                        observed_floor=16)
        assert ok.observed_floor == 16

    def test_derived_specs_share_the_validation_path(self):
        """A tuner-derived shape validates through the same code as a
        hand-declared one — a bad derivation fails BEFORE any warmup."""
        from paddle_tpu.serving import BucketSpec
        from paddle_tpu.tuning.serving_tuner import _validate_shape

        buckets = quantile_cover([17, 33, 129], q=1.0, align=16)
        spec = BucketSpec(batch_sizes=(1, 2), seq_lens=buckets,
                          observed_floor=17)
        assert spec.seq_lens == buckets
        with pytest.raises(ValueError, match="duplicate"):
            _validate_shape({"prefill_buckets": [8, 8]})
        with pytest.raises(ValueError, match="observed"):
            _validate_shape({"seq_buckets": [8, 64],
                             "observed_floor": 16})
        with pytest.raises(ValueError, match="max_slots"):
            _validate_shape({"max_slots": 0})


# -- planner re-scoring -------------------------------------------------------

class TestRescore:
    @pytest.fixture(scope="class")
    def profile_and_cands(self):
        from paddle_tpu.distributed.auto_parallel import planner
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        prof = planner.profile_model(model, batch=16, seq=64)
        cands = planner.plan(model, n_devices=1, hbm_bytes=64e9,
                             batch=16, remat=(False, True),
                             accumulate=(1,), levels=(None,),
                             offload=(False,), cp_degrees=(1,))
        assert len(cands) >= 2
        return prof, cands

    def test_plan_digest_stable_and_distinct(self, profile_and_cands):
        from paddle_tpu.distributed.auto_parallel.planner import plan_digest

        _prof, cands = profile_and_cands
        digests = [plan_digest(c.config) for c in cands]
        assert len(set(digests)) == len(digests)
        assert plan_digest(cands[0].config) == \
            plan_digest(dict(cands[0].config))

    def test_rescore_matches_plan_ranking_unanchored(self,
                                                     profile_and_cands):
        from paddle_tpu.distributed.auto_parallel.planner import (
            rescore_candidates)

        prof, cands = profile_and_cands
        ranked = rescore_candidates(prof, cands, hbm_bytes=64e9)
        assert [c.config for c in ranked] == [c.config for c in cands]

    def test_measured_anchor_demotes_the_regressed_active(
            self, profile_and_cands):
        from paddle_tpu.distributed.auto_parallel.planner import (
            plan_digest, rescore_candidates)

        prof, cands = profile_and_cands
        active = plan_digest(cands[0].config)
        # the active plan measures 100x its model prediction: anchored
        reg_s = cands[0].predicted_step_s * 100
        ranked = rescore_candidates(prof, cands, hbm_bytes=64e9,
                                    measured={active: reg_s})
        assert plan_digest(ranked[0].config) != active
        anchored = [c for c in ranked
                    if plan_digest(c.config) == active][0]
        assert anchored.predicted_step_s == pytest.approx(reg_s)
        assert anchored.breakdown["measured_anchor_s"] == \
            pytest.approx(reg_s)

    def test_rescore_accepts_published_descriptors(self,
                                                   profile_and_cands):
        from paddle_tpu.distributed.auto_parallel.planner import (
            rescore_candidates)

        prof, cands = profile_and_cands
        descs = [json.loads(json.dumps(c.to_dict())) for c in cands]
        ranked = rescore_candidates(prof, descs, hbm_bytes=64e9)
        assert [c.config["mesh"] for c in ranked] == \
            [c.config["mesh"] for c in cands]


# -- respec: live bucket swap keeps the zero-retrace invariant ----------------

class TestRespec:
    def test_respec_prewarms_before_swap_and_serves_without_compiles(self):
        from paddle_tpu import serving

        eng = serving.ServingEngine(
            lambda x: x * 2.0,
            buckets=serving.BucketSpec(batch_sizes=(2,),
                                       seq_lens=(8, 16)),
            input_specs=[((None,), "float32")],
            config=serving.ServingConfig(max_batch_wait_ms=5.0))
        with eng:
            f = eng.submit([np.ones(5, np.float32)])
            np.testing.assert_array_equal(
                f.result(timeout=60)[0][:5], np.full(5, 2.0, np.float32))
            compiled_before = dict(eng._compiled)
            new = serving.BucketSpec(batch_sizes=(1, 2),
                                     seq_lens=(4, 8, 16))
            eng.respec(new)
            assert eng.buckets is new
            # old runners retained, new family warmed
            assert set(compiled_before) <= set(eng._compiled)
            stats = eng.stats()
            assert stats["counters"]["respecs"] == 1
            assert stats["counters"]["respec_compiles"] > 0
            misses0 = stats["counters"].get("compile_cache_misses", 0)
            # a request landing in a NEW bucket (seq 3 -> 4, batch 1)
            # must execute on the pre-warmed runner: no fresh compile
            f = eng.submit([np.ones(3, np.float32)])
            np.testing.assert_array_equal(
                f.result(timeout=60)[0][:3], np.full(3, 2.0, np.float32))
            assert eng.stats()["counters"].get(
                "compile_cache_misses", 0) == misses0

    def test_respec_rejects_invalid_spec(self):
        from paddle_tpu import serving

        with pytest.raises(ValueError, match="duplicate"):
            serving.BucketSpec(batch_sizes=(2, 2))


# -- apply_tuned_shape (replica-side respec) ----------------------------------

class TestApplyTunedShape:
    def test_generation_engine_rebuilt_with_derived_shape(self):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving.generation import (GenerationConfig,
                                                   GenerationEngine)
        from paddle_tpu.tuning.serving_tuner import apply_tuned_shape

        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=32, hidden_size=16, num_hidden_layers=1,
            num_attention_heads=2, max_position_embeddings=64,
            dtype="float32"))
        eng = GenerationEngine(model, GenerationConfig(
            max_slots=2, prefill_buckets=(16, 32)))
        tuned = apply_tuned_shape(eng, {"prefill_buckets": [8, 16],
                                        "max_slots": 3})
        assert tuned is not eng
        assert tuned.config.prefill_buckets == (8, 16)
        assert tuned.config.max_slots == 3
        # the original engine's declared knobs are untouched
        assert eng.config.prefill_buckets == (16, 32)

    def test_invalid_shape_fails_before_any_rebuild(self):
        from paddle_tpu.tuning.serving_tuner import apply_tuned_shape

        with pytest.raises(ValueError):
            apply_tuned_shape(object(), {"prefill_buckets": [4, 4]})

    def test_unknown_engine_passes_through(self):
        from paddle_tpu.tuning.serving_tuner import apply_tuned_shape

        sentinel = object()
        assert apply_tuned_shape(sentinel, {"max_slots": 2}) is sentinel


# -- OnlineTuner driver -------------------------------------------------------

class _ScriptedPolicy(TuningPolicy):
    name = "scripted"
    cooldown_s = 0.0

    def __init__(self, verdicts):
        self.verdicts = list(verdicts)  # measure() results, per apply
        self.log = []
        self.applied = None

    def observe(self, signals):
        self.log.append(("observe", dict(signals)))

    def propose(self):
        return Proposal(policy=self.name, kind="test", from_digest="a",
                        to_digest="b", payload={"x": 1},
                        predicted={"win": 1.0})

    def apply(self, proposal):
        self.log.append(("apply", proposal.to_digest))
        self.applied = proposal.to_digest
        return True

    def measure(self, proposal):
        return self.verdicts.pop(0) if self.verdicts else True

    def rollback(self, proposal):
        self.log.append(("rollback", proposal.to_digest))
        self.applied = None


class TestOnlineTuner:
    def test_kill_switch_disables_everything(self, monkeypatch):
        monkeypatch.setenv("PT_ONLINE_TUNING", "0")
        pol = _ScriptedPolicy([True])
        tuner = OnlineTuner([pol], provider_name=None)
        tuner.tick()
        assert tuner.ticks == 0 and pol.log == []
        snap = tuner.snapshot()
        assert snap["enabled"] is False  # visibly off, not silently stuck

    def test_keep_path_counts_and_ledger(self):
        pol = _ScriptedPolicy([None, True])  # window fills, then keep
        tuner = OnlineTuner([pol], signal_sources={"k": lambda: 7},
                            provider_name=None)
        tuner.tick()   # propose + apply
        tuner.tick()   # measure -> None (filling)
        tuner.tick()   # measure -> True (keep)
        snap = tuner.snapshot()["policies"]["scripted"]
        assert snap["proposals"] == 1 and snap["applies"] == 1
        assert snap["keeps"] == 1 and snap["rollbacks"] == 0
        events = [d["event"] for d in tuner.snapshot()["decisions"]]
        assert events == ["propose", "apply", "keep"]
        # signals reached the policy as one assembled view
        assert pol.log[0] == ("observe", {"k": 7})

    def test_rollback_embargoes_the_digest(self):
        pol = _ScriptedPolicy([False])  # refuted on first measure
        tuner = OnlineTuner([pol], provider_name=None)
        tuner.tick()   # propose+apply
        tuner.tick()   # measure -> False -> rollback
        snap = tuner.snapshot()["policies"]["scripted"]
        assert snap["rollbacks"] == 1 and snap["rejected"] == ["b"]
        assert pol.applied is None  # rollback() actually ran
        applies_before = snap["applies"]
        tuner.tick()   # same digest proposed again: embargoed
        snap = tuner.snapshot()["policies"]["scripted"]
        assert snap["applies"] == applies_before

    def test_dead_signal_source_does_not_stop_tuning(self):
        def boom():
            raise RuntimeError("scrape died")

        pol = _ScriptedPolicy([True])
        tuner = OnlineTuner([pol], signal_sources={"bad": boom},
                            provider_name=None)
        tuner.tick()
        assert "error" in pol.log[0][1]["bad"]
        assert tuner.snapshot()["policies"]["scripted"]["applies"] == 1


# -- elastic plan tuner over a fake control plane -----------------------------

class _FakeStore:
    def __init__(self):
        self.kv = {}
        self.counters = {}

    def set(self, k, v):
        self.kv[k] = v

    def get(self, k):
        return self.kv[k]

    def add(self, k, n):
        self.counters[k] = self.counters.get(k, 0) + int(n)
        return self.counters[k]


def _mk_plan_tuner(store, gen, prof, cands, **kw):
    from paddle_tpu.tuning.plan_tuner import ElasticPlanTuner

    ctx = SimpleNamespace(store=store, gen=gen, rank=0)
    kw.setdefault("detector",
                  RegressionDetector(min_samples=4, baseline_window=8,
                                     sustain_n=3))
    kw.setdefault("margin", 0.2)
    kw.setdefault("measure_steps", 3)
    kw.setdefault("skip_steps", 1)
    return ElasticPlanTuner(ctx, prof, cands, hbm_bytes=64e9,
                            register_provider_name=None, **kw)


class TestElasticPlanTuner:
    @pytest.fixture(scope="class")
    def profile_and_cands(self):
        from paddle_tpu.distributed.auto_parallel import planner
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        prof = planner.profile_model(model, batch=16, seq=64)
        cands = planner.plan(model, n_devices=1, hbm_bytes=64e9,
                             batch=16, remat=(False, True),
                             accumulate=(1,), levels=(None,),
                             offload=(False,), cp_degrees=(1,))
        return prof, cands

    def _publish_plan(self, store, gen, cand):
        from paddle_tpu.distributed.fleet.runtime import _publish

        _publish(store, f"fleet/{gen}/plan", cand.to_dict())

    def test_regression_raises_planned_fence_with_override(
            self, profile_and_cands):
        from paddle_tpu.distributed.auto_parallel.planner import plan_digest
        from paddle_tpu.distributed.fleet.runtime import _probe_json
        from paddle_tpu.tuning.plan_tuner import (PLAN_OVERRIDE_KEY,
                                                  PLAN_STATE_KEY)

        prof, cands = profile_and_cands
        store = _FakeStore()
        self._publish_plan(store, 0, cands[0])
        tuner = _mk_plan_tuner(store, 0, prof, cands)
        for _ in range(6):
            tuner.on_step(100.0)  # healthy baseline
        assert store.counters.get("fleet/0/fence", 0) == 0
        for _ in range(3):
            tuner.on_step(400.0)  # sustained regression
        # fence raised with the planned retune reason, override published
        assert store.counters["fleet/0/fence"] == 1
        assert json.loads(store.kv["fleet/0/fence_reason"]) == \
            "retune:plan"
        ov = _probe_json(store, PLAN_OVERRIDE_KEY)
        assert plan_digest(ov["config"]) != plan_digest(cands[0].config)
        st = _probe_json(store, PLAN_STATE_KEY)
        assert st["phase"] == "measure"
        assert st["counters"]["proposals"] == 1
        assert st["counters"]["applies"] == 1
        # further steps in the dying generation are inert
        tuner.on_step(400.0)
        assert store.counters["fleet/0/fence"] == 1

    def _regress_and_fence(self, prof, cands):
        store = _FakeStore()
        self._publish_plan(store, 0, cands[0])
        t0 = _mk_plan_tuner(store, 0, prof, cands)
        for _ in range(6):
            t0.on_step(100.0)
        for _ in range(3):
            t0.on_step(400.0)
        return store

    def test_next_generation_keeps_a_confirmed_win(self,
                                                   profile_and_cands):
        from paddle_tpu.distributed.auto_parallel.planner import plan_digest
        from paddle_tpu.distributed.fleet.runtime import _probe_json
        from paddle_tpu.tuning.plan_tuner import PLAN_STATE_KEY

        prof, cands = profile_and_cands
        store = self._regress_and_fence(prof, cands)
        # gen 1: the new plan is fast (the regression WAS plan-bound)
        t1 = _mk_plan_tuner(store, 1, prof, cands)
        for _ in range(4):  # skip 1 + 3 measure steps
            t1.on_step(100.0)
        st = _probe_json(store, PLAN_STATE_KEY)
        assert st["phase"] == "idle"
        assert st["counters"]["keeps"] == 1
        assert st["counters"]["rollbacks"] == 0
        assert st["last_verdict"]["kept"] is True
        assert st["active"] != plan_digest(cands[0].config)
        # no rollback fence was raised in gen 1
        assert store.counters.get("fleet/1/fence", 0) == 0

    def test_next_generation_rolls_back_a_refuted_win(
            self, profile_and_cands):
        from paddle_tpu.distributed.auto_parallel.planner import plan_digest
        from paddle_tpu.distributed.fleet.runtime import _probe_json
        from paddle_tpu.tuning.plan_tuner import (PLAN_OVERRIDE_KEY,
                                                  PLAN_STATE_KEY)

        prof, cands = profile_and_cands
        store = self._regress_and_fence(prof, cands)
        # gen 1: still slow — the regression was environmental
        t1 = _mk_plan_tuner(store, 1, prof, cands)
        for _ in range(4):
            t1.on_step(400.0)
        st = _probe_json(store, PLAN_STATE_KEY)
        assert st["counters"]["rollbacks"] == 1
        assert st["active"] == plan_digest(cands[0].config)
        assert st["last_verdict"]["kept"] is False
        # the override now restores the ORIGINAL plan, via a new fence
        ov = _probe_json(store, PLAN_OVERRIDE_KEY)
        assert plan_digest(ov["config"]) == plan_digest(cands[0].config)
        assert json.loads(store.kv["fleet/1/fence_reason"]) == \
            "retune:rollback"
        # gen 2: regression persists, but the loser is embargoed — the
        # tuner must NOT flap back onto it
        t2 = _mk_plan_tuner(store, 2, prof, cands)
        self._publish_plan(store, 2, cands[0])
        for _ in range(6):
            t2.on_step(100.0)
        time.sleep(0)  # cooldown from the rollback may still hold
        st = _probe_json(store, PLAN_STATE_KEY)
        rejected = st["rejected"]
        assert rejected and rejected[0] != plan_digest(cands[0].config)

    def test_kill_switch_freezes_the_plan_tuner(self, monkeypatch,
                                                profile_and_cands):
        prof, cands = profile_and_cands
        monkeypatch.setenv("PT_ONLINE_TUNING", "0")
        store = _FakeStore()
        self._publish_plan(store, 0, cands[0])
        tuner = _mk_plan_tuner(store, 0, prof, cands)
        for _ in range(6):
            tuner.on_step(100.0)
        for _ in range(10):
            tuner.on_step(500.0)
        assert store.counters.get("fleet/0/fence", 0) == 0
        assert "fleet/plan_override" not in store.kv


# -- worker replan honors the override ----------------------------------------

class TestReplanOverride:
    def test_override_wins_when_mesh_covers_world(self, monkeypatch):
        from paddle_tpu.distributed.fleet.runtime import (
            FleetWorkerContext, _probe_json, _publish)

        store = _FakeStore()
        ov = {"config": {"mesh": {"dp": 1, "mp": 1, "pp": 1, "cp": 1,
                                  "ep": 1, "sharding": 1},
               "accumulate_steps": 1, "remat": True}}
        _publish(store, "fleet/plan_override", ov)
        ctx = FleetWorkerContext(rank=0, world=1, gen=3, store=store)
        got = ctx.replan(None, batch=8)  # model unused: override wins
        assert got == ov
        # and it is republished as THIS generation's plan
        assert _probe_json(store, "fleet/3/plan") == ov

    def test_stale_override_for_wrong_world_is_ignored(self):
        from paddle_tpu.distributed.fleet.runtime import (
            FleetWorkerContext, _publish)
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        store = _FakeStore()
        ov = {"config": {"mesh": {"dp": 4, "mp": 1, "pp": 1, "cp": 1,
                                  "ep": 1, "sharding": 1}}}
        _publish(store, "fleet/plan_override", ov)
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        ctx = FleetWorkerContext(rank=0, world=1, gen=0, store=store)
        got = ctx.replan(model, batch=16, hbm_bytes=16e9)
        assert got["config"]["mesh"]["dp"] == 1  # freshly planned
