#!/usr/bin/env python
"""Online auto-tuner drill — the ISSUE-20 acceptance run.

Three legs, all against REAL multi-process fleets:

serving        a 2-replica ``ServingFleet`` boots on hand-declared
               prefill buckets sized for long prompts; the live
               workload is short (a shift).  The ``OnlineTuner`` +
               ``ServingShapePolicy`` derive tighter buckets/slots from
               the merged prompt/slot histograms (quantile-cover),
               actuate them through ``apply_serving_shape`` (a rolling
               restart in which every replica AOT-warms the NEW shape
               BEFORE re-admitting traffic), and the post-apply
               measurement window confirms the predicted padding-waste
               win (keep).  The SAME request set replayed across the
               cutover must produce BIT-IDENTICAL token streams.  The
               ``tuner`` hub provider (proposals/applies/keeps/active
               digests + the decision ledger) is asserted from the
               telemetry dump, and the ``PT_ONLINE_TUNING=0``
               kill-switch is exercised.

plan-keep      a 2-worker ``ElasticFleet`` trains under the planner's
               best pure-dp plan while rank 0 runs ``ElasticPlanTuner``
               from a fit callback.  A fault keyed to the ACTIVE plan
               digest slows every step (sustained — the windowed
               detector never fires on one spike); the tuner re-scores
               the cached candidates with the degraded measurement
               anchored, publishes the winner as ``fleet/plan_override``
               and raises a ``retune:plan`` fence.  The gang drains at
               the checkpoint boundary, restarts PLANNED (report
               ``restarts == 0`` — no crash budget spent), the next
               generation adopts the override, the slowdown vanishes
               (it was keyed to the old digest) and the cross-
               generation measurement window confirms: keep.

plan-rollback  same fleet, but the slowdown is UNCONDITIONAL: the
               swapped-to plan measures just as slow, the tuner rolls
               back through a second planned fence (``retune:rollback``)
               onto the original plan, embargoes the refuted digest,
               and the run completes with no flapping.

With ``PT_LOCKDEP=1`` every leg re-runs under the runtime lock-order
witness and must stay cycle-free.  Exit 0 only when every assertion
holds.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run from anywhere

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compile cache ON at its one fixed place (<checkout>/.cache/jax, or
# JAX_COMPILATION_CACHE_DIR): children and restarts warm from it
os.environ.setdefault("PT_PERSISTENT_CACHE", "1")

# -- serving leg constants ----------------------------------------------------
DECLARED_PREFILL = (32, 40)      # sized for long prompts; traffic is short
ROUND_REQUESTS = 24
WAVE = 8
MAX_NEW = 4

# -- elastic leg constants ----------------------------------------------------
ELASTIC_WORLD = 2
ELASTIC_GLOBAL_BATCH = 8
ELASTIC_SAMPLES = 240            # 30 global steps, 1 epoch
ELASTIC_CKPT_EVERY = 2
SLOW_AFTER_STEPS = 10            # fault arms after the baseline window
SLOW_SLEEP_S = 0.12


def _assert_lockdep(tag: str) -> None:
    if os.environ.get("PT_LOCKDEP", "") in ("", "0", "false"):
        return
    from paddle_tpu.analysis import lockdep

    snap = lockdep.snapshot()
    assert snap["armed"] and snap["locks"], \
        f"[{tag}] PT_LOCKDEP=1 but the witness saw no locks"
    assert snap["cycles"] == [], f"[{tag}] lock-order cycles: {snap['cycles']}"
    print(f"[{tag}] lockdep ok: {len(snap['locks'])} witnessed locks, "
          f"{len(snap['edges'])} order edges, zero cycles", flush=True)


# ---------------------------------------------------------------------------
# serving leg
# ---------------------------------------------------------------------------

def build_replica():
    """Replica builder (runs INSIDE each serving worker): the tiny
    pattern-trained GPT every serving drill uses, on DELIBERATELY coarse
    declared prefill buckets — the shape the tuner will beat."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit, serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=64,
                    dtype="float32")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-3,
                          parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.to_tensor(
        np.tile(np.arange(8), 8)[None, :].astype("int64"))
    for _ in range(80):
        step(ids, ids)
    return serving.GenerationEngine(
        model, serving.GenerationConfig(
            max_slots=2, max_seq_len=48, page_len=8, num_pages=48,
            prefill_buckets=DECLARED_PREFILL))


def _round_prompts():
    import numpy as np

    pattern = np.tile(np.arange(8), 8)
    prompts = []
    for i in range(ROUND_REQUESTS):
        plen = 8 if i % 2 else 16
        start = (i * 3) % 8
        prompts.append(pattern[start:start + plen].astype(np.int64))
    return prompts


def _run_round(fleet):
    """Submit the deterministic request set (in capacity-sized waves)
    and return every full output token list, stream-checked."""
    outs = []
    prompts = _round_prompts()
    for base in range(0, len(prompts), WAVE):
        futs = []
        for prompt in prompts[base:base + WAVE]:
            streamed = []
            futs.append((len(prompt), streamed,
                         fleet.submit(prompt, max_new_tokens=MAX_NEW,
                                      on_token=streamed.append)))
        for plen, streamed, fut in futs:
            out = fut.result(timeout=300).tolist()
            assert len(out) == plen + MAX_NEW, (plen, out)
            assert streamed == out[plen:], "stream dup/loss"
            outs.append(out)
    return outs


def serving_leg(work_root: str) -> dict:
    import paddle_tpu.observability as obs
    from paddle_tpu.serving import ServingFleet, ServingFleetPolicy
    from paddle_tpu.serving.router import RouterConfig
    from paddle_tpu.tuning import OnlineTuner
    from paddle_tpu.tuning.serving_tuner import (DECLARED_DIGEST,
                                                 ServingShapePolicy)

    policy = ServingFleetPolicy(
        heartbeat_interval=0.25, heartbeat_timeout=3.0,
        backoff_base_s=0.2, backoff_max_s=2.0, poll_interval=0.05,
        hedge_ms=None, replica_capacity=WAVE, drain_timeout_s=30.0,
        telemetry_interval_s=0.5)
    fleet = ServingFleet(
        builder=os.path.abspath(__file__) + ":build_replica",
        n_replicas=2, names=["r0", "r1"], policy=policy,
        router_config=RouterConfig(),
        flight_root=os.path.join(work_root, "flight"),
        log_dir=os.path.join(work_root, "logs"))
    t0 = time.time()
    fleet.start(wait_ready=True, timeout=600)
    print(f"[serving] 2-replica fleet ready in {time.time() - t0:.1f}s "
          f"on declared prefill buckets {list(DECLARED_PREFILL)}",
          flush=True)

    shape_policy = ServingShapePolicy(
        fleet,
        declared={"prefill_buckets": list(DECLARED_PREFILL),
                  "max_slots": 2},
        window_s=600.0, min_count=10, q=0.99, max_waste=0.2,
        max_buckets=6, improve_margin=0.02, max_slots_cap=3,
        measure_count=12, measure_timeout_s=60.0, cooldown_s=0.5)
    tuner = OnlineTuner([shape_policy],
                        signal_sources={"fleet_telemetry":
                                        fleet.scrape_now},
                        provider_name="tuner")

    # -- kill-switch: a disabled tuner must not tick, propose or actuate
    os.environ["PT_ONLINE_TUNING"] = "0"
    tuner.tick()
    off = obs.snapshot()["tuner"]
    assert tuner.ticks == 0 and off["enabled"] is False, off
    assert off["policies"]["serving_shape"]["proposals"] == 0, off
    os.environ.pop("PT_ONLINE_TUNING", None)
    print("[serving] kill-switch ok: PT_ONLINE_TUNING=0 ticked nothing",
          flush=True)

    # -- pre-cutover traffic: shifted-short workload on coarse buckets
    tuner.tick()  # zero-baseline scrape before any traffic
    expected = None
    applies = 0
    for round_no in range(6):
        outs = _run_round(fleet)
        if expected is None:
            expected = outs
        else:
            assert outs == expected, "pre-cutover streams drifted"
        tuner.tick()
        applies = obs.snapshot()["tuner"]["policies"][
            "serving_shape"]["applies"]
        if applies:
            break
    assert applies == 1, \
        f"tuner never actuated a derived shape (applies={applies})"

    snap = obs.snapshot()["tuner"]
    pol = snap["policies"]["serving_shape"]
    shape = pol["active_shape"]
    assert pol["active"] != DECLARED_DIGEST, pol
    assert pol["phase"] == "measuring", pol
    derived = shape.get("prefill_buckets") or []
    assert derived and max(derived) < min(DECLARED_PREFILL), \
        f"derived buckets {derived} should be tighter than declared " \
        f"{DECLARED_PREFILL}"
    events = [d["event"] for d in snap["decisions"]]
    assert events[-2:] == ["propose", "apply"], events
    fl = fleet.provider_snapshot()
    assert fl["counters"].get("shape_applies", 0) == 1, fl["counters"]
    assert fl["counters"].get("rolling_restarts", 0) == 1, fl["counters"]
    print(f"[serving] respec ok: derived prefill={derived} "
          f"max_slots={shape.get('max_slots')} rolled across the fleet "
          f"(digest {pol['active']})", flush=True)

    # -- bit-identical streams across the cutover
    post = _run_round(fleet)
    assert post == expected, \
        "token streams changed across the shape cutover"
    print(f"[serving] cutover ok: {len(post)} replayed requests "
          f"produced bit-identical streams", flush=True)

    # -- the measurement window confirms the waste claim: keep
    keeps = 0
    for _ in range(8):
        tuner.tick()
        pol = obs.snapshot()["tuner"]["policies"]["serving_shape"]
        keeps = pol["keeps"]
        if keeps:
            break
        _run_round(fleet)
    assert keeps == 1 and pol["rollbacks"] == 0, pol
    live = pol["live_waste"].get("prefill_buckets_waste")
    assert live is not None and live <= 0.1, pol["live_waste"]
    ledger = [d["event"] for d in
              obs.snapshot()["tuner"]["decisions"]]
    assert ledger[-3:] == ["propose", "apply", "keep"], ledger
    print(f"[serving] keep ok: live prefill waste {live} under the "
          f"derived shape (ledger {ledger[-3:]})", flush=True)

    _assert_lockdep("serving-supervisor")
    fleet.close()
    return {"derived_prefill": derived,
            "max_slots": shape.get("max_slots"),
            "live_waste": live, "applies": 1, "keeps": keeps,
            "replayed": len(post)}


# ---------------------------------------------------------------------------
# elastic legs (plan re-rank: keep / rollback)
# ---------------------------------------------------------------------------

def _run_elastic_child(out_dir: str) -> None:
    """One elastic worker: rank 0 drives ``ElasticPlanTuner`` from a fit
    callback; the scripted slowdown is the regression under test."""
    world = int(os.environ.get("PT_FLEET_WORLD", "1"))
    coord = os.environ.get("PT_FLEET_COORDINATOR")
    if world > 1 and coord:
        import jax

        jax.distributed.initialize(
            coordinator_address=coord, num_processes=world,
            process_id=int(os.environ.get("PT_FLEET_RANK", "0")))
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.fleet.runtime import elastic_fit

    slow_mode = os.environ.get("PT_DRILL_SLOW", "")

    class ToyDataset(paddle.io.Dataset):
        def __init__(self, n):
            rng = np.random.default_rng(3)
            self.x = rng.standard_normal((n, 8)).astype("float32")
            w = rng.standard_normal((8,)).astype("float32")
            self.y = (self.x @ w > 0).astype("int64")

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return len(self.x)

    holder = {}

    def _write(res):
        res = dict(res)
        tuner = holder.get("tuner")
        if tuner is not None:
            try:
                res["tuner"] = tuner.snapshot()
            except Exception:
                pass
        path = os.path.join(out_dir, f"g{res['gen']}_r{res['rank']}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(res, f)
        os.replace(path + ".tmp", path)

    class TunerStepCallback(paddle.callbacks.Callback):
        """Times every completed step into ``tuner.on_step`` and injects
        the scripted slowdown: ``first`` slows only while the INITIAL
        plan digest is active (the regression the swap escapes),
        ``always`` slows unconditionally (the swap cannot help — it
        must be refuted and rolled back)."""

        def __init__(self, tuner, initial_digest, gen):
            self.tuner = tuner
            self.initial = initial_digest
            self.gen = gen
            self.steps = 0
            self._last = None

        def on_train_batch_end(self, step, logs=None):
            self.steps += 1
            armed = self.gen > 0 or self.steps > SLOW_AFTER_STEPS
            if slow_mode == "first":
                armed = armed and \
                    self.tuner.active_digest() == self.initial
            elif slow_mode != "always":
                armed = False
            if armed:
                time.sleep(SLOW_SLEEP_S)
            now = time.perf_counter()
            if self._last is not None:
                self.tuner.on_step((now - self._last) * 1e3)
            self._last = now

    def build(ctx):
        paddle.seed(7)  # identical init on every rank; resume overwrites
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        ce = nn.CrossEntropyLoss()
        ds = ToyDataset(ELASTIC_SAMPLES)
        xb = np.stack([ds[i][0] for i in range(ELASTIC_GLOBAL_BATCH)])
        yb = np.stack([ds[i][1] for i in range(ELASTIC_GLOBAL_BATCH)])

        def loss_fn(m, x, y):
            return ce(m(x), y)

        cbs = []
        if ctx.rank == 0 and ctx.store is not None and ctx.world > 1:
            from paddle_tpu.distributed.auto_parallel import planner
            from paddle_tpu.distributed.fleet.runtime import \
                replan_for_world
            from paddle_tpu.tuning import (ElasticPlanTuner,
                                           RegressionDetector)

            prof = planner.profile_model(net, sample_batch=(xb, yb),
                                         loss_fn=loss_fn)
            cands = planner.plan(
                net, n_devices=ctx.world, hbm_bytes=64e9,
                batch=ELASTIC_GLOBAL_BATCH, sample_batch=(xb, yb),
                loss_fn=loss_fn, accumulate=(1,), remat=(False, True),
                levels=(None,), offload=(False,), cp_degrees=(1,))
            # only plans the CPU fleet can execute: pure-dp over world
            pure = [c for c in cands
                    if c.config["mesh"].get("dp", 1) == ctx.world
                    and all(v == 1 for k, v in c.config["mesh"].items()
                            if k != "dp")]
            assert len(pure) >= 2, \
                f"need >=2 pure-dp candidates to swap between, got " \
                f"{len(pure)}"
            base = replan_for_world(net, ctx.world,
                                    batch=ELASTIC_GLOBAL_BATCH,
                                    sample_batch=(xb, yb),
                                    loss_fn=loss_fn, hbm_bytes=64e9)
            initial = planner.plan_digest(base.config)
            tuner = ElasticPlanTuner(
                ctx, prof, pure, margin=0.2, measure_steps=5,
                skip_steps=2, cooldown_s=10.0, hbm_bytes=64e9,
                detector=RegressionDetector(
                    baseline_window=8, min_samples=4, sustain_n=3,
                    trigger_ratio=1.3, min_abs_ms=30.0))
            holder["tuner"] = tuner
            cbs.append(TunerStepCallback(tuner, initial, ctx.gen))
        return {"network": net, "optimizer": opt, "loss": ce,
                "dataset": ds, "sample_batch": (xb, yb),
                "loss_fn": loss_fn, "hbm_bytes": 64e9,
                "callbacks": cbs, "on_exit": _write}

    res = elastic_fit(build, global_batch=ELASTIC_GLOBAL_BATCH, epochs=1,
                      checkpoint_every=ELASTIC_CKPT_EVERY)
    _write(res)
    _assert_lockdep("elastic-child")


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def elastic_leg(mode: str) -> dict:
    """Run the 2-worker elastic fleet with the scripted slowdown and
    assert the keep (``mode='first'``) or rollback (``mode='always'``)
    path end to end."""
    from paddle_tpu.distributed.auto_parallel.planner import plan_digest
    from paddle_tpu.distributed.fleet.runtime import (ElasticFleet,
                                                      FleetPolicy,
                                                      _probe_json)
    from paddle_tpu.tuning.plan_tuner import PLAN_STATE_KEY

    leg = "plan-keep" if mode == "first" else "plan-rollback"
    work = tempfile.mkdtemp(prefix=f"pt_tuning_{leg}_")
    out_dir = os.path.join(work, "out")
    os.makedirs(out_dir, exist_ok=True)
    here = os.path.abspath(__file__)
    root = os.path.dirname(os.path.dirname(here))
    print(f"[{leg}] 2-worker elastic fleet, scripted slowdown "
          f"mode={mode!r} after step {SLOW_AFTER_STEPS}", flush=True)
    fleet = ElasticFleet(
        [sys.executable, here, "--elastic-child", "--out", out_dir],
        np=ELASTIC_WORLD,
        policy=FleetPolicy(min_world=ELASTIC_WORLD, max_restarts=2,
                           heartbeat_timeout=8.0, backoff_base_s=0.2,
                           drain_timeout_s=30.0),
        log_dir=os.path.join(work, "logs"),
        ckpt_root=os.path.join(work, "ckpt"),
        extra_env={
            "PYTHONPATH": root + os.pathsep +
            os.environ.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "PT_DRILL_SLOW": mode,
        })
    try:
        report = fleet.run(timeout=600)
        state = _probe_json(fleet.store, PLAN_STATE_KEY)
    finally:
        fleet.close()

    events = [e["event"] for e in report["timeline"]]
    print(f"[{leg}] phase={report['phase']} "
          f"restarts={report['restarts']} events={events}", flush=True)
    assert report["phase"] == "completed", report
    # PLANNED fences spend no crash budget
    assert report["restarts"] == 0, report
    recs = report["recoveries"]
    want_gens = 1 if mode == "first" else 2
    assert len(recs) == want_gens, recs
    assert all(r["planned"] for r in recs), recs
    assert recs[0]["reason"] == "retune:plan", recs
    if mode == "always":
        assert recs[1]["reason"] == "retune:rollback", recs

    plans = {str(k): v for k, v in report["plans"].items()}
    digests = {g: plan_digest(p["config"])
               for g, p in plans.items()}
    assert digests["1"] != digests["0"], \
        f"gen1 never adopted the override: {digests}"

    assert isinstance(state, dict), state
    counters = state["counters"]
    assert counters["proposals"] == 1 and counters["applies"] == 1, \
        counters
    verdict = state["last_verdict"]
    if mode == "first":
        assert counters["keeps"] == 1 and counters["rollbacks"] == 0, \
            counters
        assert verdict and verdict["kept"] is True, verdict
        assert state["active"] == digests["1"], (state["active"], digests)
    else:
        assert counters["keeps"] == 0 and counters["rollbacks"] == 1, \
            counters
        assert verdict and verdict["kept"] is False, verdict
        # rolled back onto the original plan, refuted digest embargoed
        assert state["active"] == digests["0"], (state["active"], digests)
        assert digests["2"] == digests["0"], digests
        assert state["rejected"] == [digests["1"]], state["rejected"]
        assert verdict["measured_ms"] > state["target_ms"] > 0, verdict

    # the worker-side ``tuner`` provider surface rode along in the final
    # generation's result dump
    final = _read(os.path.join(out_dir, f"g{want_gens}_r0.json"))
    tsnap = final.get("tuner")
    assert tsnap and tsnap["enabled"] is True, tsnap
    assert tsnap["counters"] == counters, (tsnap["counters"], counters)
    return {"restarts": report["restarts"],
            "recoveries": [r["reason"] for r in recs],
            "counters": counters,
            "verdict": verdict,
            "measured_ms": verdict.get("measured_ms"),
            "target_ms": state.get("target_ms")}


# ---------------------------------------------------------------------------

def main(legs) -> int:
    headline = {}
    if "serving" in legs:
        work_root = tempfile.mkdtemp(prefix="pt_tuning_serving_")
        headline["serving"] = serving_leg(work_root)
    if "plan-keep" in legs:
        headline["plan_keep"] = elastic_leg("first")
    if "plan-rollback" in legs:
        headline["plan_rollback"] = elastic_leg("always")
    _assert_lockdep("supervisor")
    print("TUNING_DRILL_OK " + json.dumps(headline), flush=True)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--elastic-child", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--leg", action="append",
                    choices=("serving", "plan-keep", "plan-rollback"),
                    help="run only the named leg(s); default: all")
    args = ap.parse_args()
    if args.elastic_child:
        _run_elastic_child(args.out)
        sys.exit(0)
    sys.exit(main(args.leg or ("serving", "plan-keep", "plan-rollback")))
