#!/usr/bin/env python
"""Kill-and-resume drills for tools/ci.sh's resilience + elastic gates.

Single-process leg (default, ISSUE-6): SIGTERM a real training
subprocess mid-run, resume on a changed XLA device count, stitched
losses bit-equal.

Multi-process leg (``--fleet``, ISSUE-11): a REAL 4-process
``jax.distributed`` fleet under the ``ElasticFleet`` supervisor,
training data-parallel (fixed global batch, host-side grad allreduce
through the control-plane store) with async checkpointing. A scripted
``worker_crash@rank=2&step=6`` kills one worker mid-run; the supervisor
fences the generation, survivors drain and exit, the gang restarts at
world=3 with the PR-9 planner picking the new config (pure-dp over 3
chips), every rank resumes from the fleet-wide newest committed
checkpoint, and training completes. Asserted: exactly one bounded
restart, planner dp == new world, 0 torn checkpoints anywhere, the
fleet provider's membership timeline records the eviction + restart
(with the recovery wall-clock breakdown), and the stitched rank-0 loss
curve (gen0 up to the resume point + gen1 to the end) matches an
uninterrupted world-1 reference run of the same global batch
(allclose — the dp re-split changes fp summation order, not math).

The single-process leg orchestrates three subprocesses of the SAME
deterministic ``Model.fit``:

  ref      the uninterrupted run                          (2 XLA devices)
  victim   ``checkpoint_every=2``, delivered a real
           ``SIGTERM`` by THIS process once >=1 async
           commit has landed on disk                      (2 XLA devices)
  resume   ``fit(resume=True)`` from the committed
           checkpoint, on a CHANGED device count          (4 XLA devices)

and asserts the ISSUE-6 acceptance: the victim exits 0 after a final
preempt-reason commit (>=1 ``preemptions`` counted, 0 torn checkpoints),
and victim+resume per-step losses concatenate to the uninterrupted run's
loss sequence (allclose) despite the device-count change.

The in-process halves (commit atomicity, crash-mid-save injection,
re-sharding) live in tests/test_resilience.py; this drill is the
cross-process SIGTERM half that a pytest process cannot deliver to itself
without also killing the test runner.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

EPOCHS = 2
BATCH = 8
N_SAMPLES = 128  # 16 steps/epoch, 32 total
SEED = 11
VICTIM_STEP_SLEEP_S = 0.12  # widen the SIGTERM window; math is unchanged


def _run_child(mode: str, ckpt: str, out: str) -> None:
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.resilience import metrics as rm

    class ToyDataset(paddle.io.Dataset):
        def __init__(self, n):
            rng = np.random.default_rng(0)
            self.x = rng.standard_normal((n, 8)).astype("float32")
            w = rng.standard_normal((8,)).astype("float32")
            self.y = (self.x @ w > 0).astype("int64")

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return len(self.x)

    losses = []

    class Recorder(paddle.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            losses.append(float(np.asarray(logs["loss"])))
            if mode == "victim":
                time.sleep(VICTIM_STEP_SLEEP_S)

    # resume gets a DIFFERENT seed: its fresh weights/optimizer must be
    # fully overwritten by the restore for the loss tail to line up
    paddle.seed(SEED if mode != "resume" else 99)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    fit_kw = dict(epochs=EPOCHS, batch_size=BATCH, shuffle=False, verbose=0,
                  callbacks=[Recorder()])
    if mode != "ref":
        fit_kw.update(checkpoint_every=2, checkpoint_dir=ckpt,
                      resume=(mode == "resume"))
    model.fit(ToyDataset(N_SAMPLES), **fit_kw)

    record = {"mode": mode, "devices": len(__import__("jax").devices()),
              "losses": losses,
              "preemptions": rm.get("preemptions"),
              "torn_checkpoints": rm.get("torn_checkpoints"),
              "saves": rm.get("saves"), "restores": rm.get("restores")}
    with open(out, "w") as f:
        json.dump(record, f)
    _assert_lockdep(f"child:{mode}")


def _assert_lockdep(tag: str) -> None:
    """Armed re-run gate (ci.sh): the drill must finish with the witness
    live, locks actually witnessed, and a cycle-free order graph."""
    if os.environ.get("PT_LOCKDEP", "") in ("", "0", "false"):
        return
    from paddle_tpu.analysis import lockdep

    snap = lockdep.snapshot()
    assert snap["armed"] and snap["locks"], \
        f"[{tag}] PT_LOCKDEP=1 but the witness saw no locks"
    assert snap["cycles"] == [], \
        f"[{tag}] lock-order cycles: {snap['cycles']}"
    print(f"[{tag}] lockdep ok: {len(snap['locks'])} witnessed locks, "
          f"{len(snap['edges'])} order edges, zero cycles", flush=True)


def _spawn(mode: str, ckpt: str, out: str, devices: int) -> subprocess.Popen:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         "--ckpt", ckpt, "--out", out],
        env=env, cwd=root)


def _read(out: str) -> dict:
    with open(out) as f:
        return json.load(f)


def main() -> int:
    import numpy as np

    work = tempfile.mkdtemp(prefix="pt_resilience_drill_")
    ckpt = os.path.join(work, "ckpt")
    outs = {m: os.path.join(work, f"{m}.json") for m in
            ("ref", "victim", "resume")}

    print("[drill] ref run (uninterrupted, 2 devices)")
    assert _spawn("ref", ckpt, outs["ref"], devices=2).wait() == 0, \
        "ref run failed"

    print("[drill] victim run (checkpoint_every=2, 2 devices) ...")
    victim = _spawn("victim", ckpt, outs["victim"], devices=2)
    latest = os.path.join(ckpt, "LATEST")
    t0 = time.time()
    while not os.path.exists(latest):
        if victim.poll() is not None:
            print("[drill] FAIL: victim finished before any commit "
                  f"(rc={victim.returncode})")
            return 1
        if time.time() - t0 > 120:
            victim.kill()
            print("[drill] FAIL: no committed checkpoint within 120s")
            return 1
        time.sleep(0.05)
    print(f"[drill] first commit landed after {time.time() - t0:.1f}s "
          "-> kill -TERM")
    victim.send_signal(signal.SIGTERM)
    rc = victim.wait(timeout=120)
    assert rc == 0, f"victim did not exit cleanly after SIGTERM (rc={rc})"

    ref, vic = _read(outs["ref"]), _read(outs["victim"])
    assert vic["preemptions"] >= 1, vic
    assert vic["torn_checkpoints"] == 0, vic
    assert 0 < len(vic["losses"]) < len(ref["losses"]), \
        f"SIGTERM did not cut the run mid-flight: {len(vic['losses'])} " \
        f"of {len(ref['losses'])} steps"
    # commit-protocol layout, read directly (the parent process does not
    # import jax): LATEST names the tag, tag/manifest.json carries meta
    with open(os.path.join(ckpt, "LATEST")) as f:
        tag = json.load(f)["tag"]
    with open(os.path.join(ckpt, tag, "manifest.json")) as f:
        meta = json.load(f)["meta"]
    assert meta["reason"] == "preempt", meta
    assert meta["step"] == len(vic["losses"]) - 1, \
        f"commit step {meta['step']} != last trained step " \
        f"{len(vic['losses']) - 1}"

    print("[drill] resume run (resume=True, CHANGED device count: 4)")
    assert _spawn("resume", ckpt, outs["resume"], devices=4).wait() == 0, \
        "resume run failed"
    res = _read(outs["resume"])
    assert res["devices"] == 4 and vic["devices"] == 2, (vic, res)
    assert res["restores"] >= 1, res
    assert res["torn_checkpoints"] == 0, res

    stitched = vic["losses"] + res["losses"]
    assert len(stitched) == len(ref["losses"]), \
        f"step count mismatch: {len(vic['losses'])}+{len(res['losses'])} " \
        f"!= {len(ref['losses'])}"
    np.testing.assert_allclose(stitched, ref["losses"], rtol=1e-6, atol=1e-8,
                               err_msg="resumed loss tail diverged from the "
                                       "uninterrupted run")
    bit_equal = stitched == ref["losses"]
    print(json.dumps({
        "resilience_drill": "OK", "steps": len(ref["losses"]),
        "victim_steps": len(vic["losses"]), "resume_steps": len(res["losses"]),
        "preempt_commit_step": meta["step"], "preemptions": vic["preemptions"],
        "torn_checkpoints": 0, "devices": [vic["devices"], res["devices"]],
        "losses_bit_equal": bool(bit_equal),
    }))
    return 0


# ---------------------------------------------------------------------------
# multi-process fleet leg (ISSUE-11)
# ---------------------------------------------------------------------------

FLEET_GLOBAL_BATCH = 12
FLEET_SAMPLES = 240          # 20 global steps, 1 epoch
FLEET_CRASH_STEP = 6
FLEET_CKPT_EVERY = 2


def _run_fleet_child(out_dir: str) -> None:
    """One fleet worker: rank/world/gen and the control plane all come
    from the supervisor's PT_FLEET_* env; world=1 + no endpoint is the
    standalone reference run."""
    # jax.distributed MUST initialize before any computation — and
    # importing paddle_tpu runs some (generator seeding, backend probes)
    # — so the coordinator handshake is the worker's first act
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
    from paddle_tpu.distributed.resilience import metrics as rm

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

    def _write(res):
        res = dict(res)
        res["torn_checkpoints"] = rm.get("torn_checkpoints")
        res["restores"] = rm.get("restores")
        res["saves"] = rm.get("saves")
        path = os.path.join(out_dir, f"g{res['gen']}_r{res['rank']}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(res, f)
        os.replace(path + ".tmp", path)

    def build(ctx):
        paddle.seed(7)  # identical init on every rank; resume overwrites
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        ds = ToyDataset(FLEET_SAMPLES)
        xb = np.stack([ds[i][0] for i in range(FLEET_GLOBAL_BATCH)])
        yb = np.stack([ds[i][1] for i in range(FLEET_GLOBAL_BATCH)])
        ce = nn.CrossEntropyLoss()
        return {"network": net, "optimizer": opt, "loss": ce,
                "dataset": ds, "sample_batch": (xb, yb),
                "loss_fn": lambda m, x, y: ce(m(x), y),
                "hbm_bytes": 16e9,  # CPU fleet: the backend reports none
                "on_exit": _write}

    res = elastic_fit(build, global_batch=FLEET_GLOBAL_BATCH, epochs=1,
                      checkpoint_every=FLEET_CKPT_EVERY)
    _write(res)
    _assert_lockdep("fleet-child")


def fleet_main() -> int:
    import numpy as np

    # the parent imports the supervisor from the repo (python puts
    # tools/ on sys.path, not the repo root)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.distributed.fleet.runtime import ElasticFleet, \
        FleetPolicy

    work = tempfile.mkdtemp(prefix="pt_fleet_drill_")
    out_dir = os.path.join(work, "out")
    ckpt_root = os.path.join(work, "ckpt")
    flight_root = os.path.join(work, "flight")
    for d in (out_dir, ckpt_root, flight_root):
        os.makedirs(d, exist_ok=True)
    here = os.path.abspath(__file__)
    root = os.path.dirname(os.path.dirname(here))
    base_env = {
        "PYTHONPATH": root + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
    }

    print("[fleet] reference run (standalone, world=1)")
    env = dict(os.environ, **base_env)
    env["PT_FLEET_WORLD"] = "1"
    rc = subprocess.call(
        [sys.executable, here, "--fleet-child", "--out", out_dir],
        env=env, cwd=root)
    assert rc == 0, f"reference run failed rc={rc}"
    ref = _read(os.path.join(out_dir, "g0_r0.json"))
    ref_losses = ref["losses"]
    os.rename(os.path.join(out_dir, "g0_r0.json"),
              os.path.join(out_dir, "ref.json"))
    assert len(ref_losses) == FLEET_SAMPLES // FLEET_GLOBAL_BATCH, ref

    print("[fleet] 4-worker jax.distributed fleet, "
          f"worker_crash@rank=2&step={FLEET_CRASH_STEP}&gen=0")
    fleet = ElasticFleet(
        [sys.executable, here, "--fleet-child", "--out", out_dir],
        np=4,
        policy=FleetPolicy(min_world=2, max_restarts=2,
                           heartbeat_timeout=8.0, backoff_base_s=0.2,
                           drain_timeout_s=30.0),
        log_dir=os.path.join(work, "logs"),
        ckpt_root=ckpt_root, flight_root=flight_root,
        extra_env=dict(
            base_env,
            PT_FAULTS=f"worker_crash@rank=2&step={FLEET_CRASH_STEP}&gen=0",
        ))
    try:
        report = fleet.run(timeout=600)
    finally:
        fleet.close()

    events = [e["event"] for e in report["timeline"]]
    print(f"[fleet] phase={report['phase']} restarts={report['restarts']} "
          f"events={events}")
    assert report["phase"] == "completed", report
    assert report["restarts"] == 1, report

    # membership timeline: the crash is recorded as an eviction, then the
    # fence and the bounded restart at the surviving world size
    evicts = [e for e in report["timeline"] if e["event"] == "evict"]
    assert any(e["rank"] == 2 and e["gen"] == 0 for e in evicts), evicts
    restarts = [e for e in report["timeline"] if e["event"] == "restart"]
    assert len(restarts) == 1 and restarts[0]["world"] == 3, restarts
    assert any(e["event"] == "complete" for e in report["timeline"])

    # recovery wall-clock breakdown (fence -> drain -> teardown ->
    # respawn; resume_ms lands once gen1's rank 0 trains its first step)
    rec = report["recoveries"][0]
    for k in ("drain_ms", "teardown_ms", "respawn_ms", "new_world"):
        assert k in rec, rec
    assert rec["new_world"] == 3, rec

    # the planner picked the new config: pure-dp over the surviving chips
    plan1 = report["plans"].get("1")
    assert plan1 is not None, report["plans"].keys()
    assert plan1["config"]["mesh"]["dp"] == 3, plan1

    # per-rank, per-generation results: gen0 rank0 drained at the fence;
    # gen1's three ranks resumed from the fleet-wide newest commit and
    # completed
    g0 = _read(os.path.join(out_dir, "g0_r0.json"))
    g1 = {r: _read(os.path.join(out_dir, f"g1_r{r}.json"))
          for r in range(3)}
    assert g0["world"] == 4 and all(v["world"] == 3 for v in g1.values())
    for v in list(g1.values()) + [g0]:
        assert v["torn_checkpoints"] == 0, v
    assert all(v["restores"] >= 1 for v in g1.values()), \
        {r: v["restores"] for r, v in g1.items()}
    resumed = {v["resumed_from"] for v in g1.values()}
    assert len(resumed) == 1 and None not in resumed, resumed

    # stitched rank-0 losses == the uninterrupted reference (the resumed
    # generation replays from the last commit, so trim gen0's overlap)
    start = g1[0]["start_step"]
    assert 0 < start <= FLEET_CRASH_STEP + 1, (start, g0)
    stitched = g0["losses"][:start] + g1[0]["losses"]
    assert len(stitched) == len(ref_losses), \
        f"{start}+{len(g1[0]['losses'])} != {len(ref_losses)}"
    np.testing.assert_allclose(
        stitched, ref_losses, rtol=2e-3, atol=1e-5,
        err_msg="fleet loss curve diverged from the world-1 reference")
    # every gen1 rank records the SAME allreduced loss sequence
    for r in (1, 2):
        np.testing.assert_allclose(g1[r]["losses"], g1[0]["losses"],
                                   rtol=0, atol=0)

    print(json.dumps({
        "fleet_drill": "OK", "steps": len(ref_losses),
        "gen0_steps": len(g0["losses"]), "resume_step": start,
        "restarts": report["restarts"], "new_world": rec["new_world"],
        "plan_dp": plan1["config"]["mesh"]["dp"],
        "torn_checkpoints": 0,
        "recovery_ms": {k: rec[k] for k in
                        ("drain_ms", "teardown_ms", "respawn_ms")
                        if k in rec},
        "resume_ms": rec.get("resume_ms"),
        "max_abs_loss_delta": float(np.max(np.abs(
            np.asarray(stitched) - np.asarray(ref_losses)))),
    }))
    _assert_lockdep("fleet-supervisor")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", choices=("ref", "victim", "resume"))
    ap.add_argument("--ckpt")
    ap.add_argument("--out")
    ap.add_argument("--fleet", action="store_true",
                    help="run the multi-process elastic fleet leg")
    ap.add_argument("--fleet-child", action="store_true")
    args = ap.parse_args()
    if args.fleet_child:
        _run_fleet_child(args.out)
        sys.exit(0)
    if args.child:
        _run_child(args.child, args.ckpt, args.out)
        sys.exit(0)
    sys.exit(fleet_main() if args.fleet else main())
