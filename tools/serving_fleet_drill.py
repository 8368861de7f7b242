#!/usr/bin/env python
"""Serving-fleet chaos drill — the ISSUE-15 acceptance run.

A REAL 3-process CPU fleet (one ``GenerationEngine`` + draft model per
process, socket RPC, heartbeats through the control-plane TCPStore)
under continuous load, driven through every failure the supervisor must
survive:

1. ``replica_crash`` mid-stream: one replica hard-exits at its 4th
   submit while requests are in flight ⇒ the supervisor fences it,
   replays its work onto survivors, and EVERY accepted request
   completes with its exact expected token sequence (replayed requests
   bit-identical to the uninterrupted ``model.generate`` reference —
   no duplicate or missing streamed token); the replica restarts with
   bounded backoff and is re-admitted (serves traffic again);
2. ``replica_hang``: a replica wedges its serve loop ⇒ heartbeats stop
   and it is fenced within the heartbeat grace window (stale-silence
   measured and asserted), then restarted;
3. ``replica_slow`` + hedging: a per-request slowdown on one replica
   pushes requests past the hedge deadline ⇒ a speculative second
   submission on a survivor wins and the loser is cancelled;
4. brownout: a low-priority burst past capacity walks the stages
   (speculation off → clamp → shed) and decays back to normal;
5. ``rolling_restart()``: the whole fleet rolls one replica at a time
   under load with ZERO failed requests;
6. the ``serving_fleet`` hub provider and the telemetry dump carry the
   fence/restart timeline and the hedge/replay/brownout counters.

Exit code 0 only when every assertion holds.
"""
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run from anywhere

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compile cache ON at its one fixed place (<checkout>/.cache/jax, or
# JAX_COMPILATION_CACHE_DIR): children and restarts warm from it
os.environ.setdefault("PT_PERSISTENT_CACHE", "1")

import numpy as np  # noqa: E402


def build_replica():
    """The replica builder (runs INSIDE each worker process): a tiny
    pattern-trained GPT + a pattern-trained draft — every process builds
    bit-identical weights from the same seeded recipe, which is what
    makes failover replay bit-identical under greedy decoding."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit, serving
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    def train(seed, hidden):
        cfg = GPTConfig(vocab_size=32, hidden_size=hidden,
                        num_hidden_layers=1, num_attention_heads=2,
                        max_position_embeddings=64, dtype="float32")
        paddle.seed(seed)
        model = GPTForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=3e-3,
                              parameters=model.parameters())
        step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y),
                             optimizer)
        ids = paddle.to_tensor(
            np.tile(np.arange(8), 8)[None, :].astype("int64"))
        for _ in range(80):
            step(ids, ids)
        return model

    model = train(0, 32)
    draft = train(1, 16)
    return serving.GenerationEngine(
        model, serving.GenerationConfig(
            max_slots=2, max_seq_len=32, page_len=8,
            prefill_buckets=(8, 16, 24), draft_model=draft,
            spec_tokens=3))


def main():
    import paddle_tpu as paddle
    import paddle_tpu.observability as obs
    from paddle_tpu import serving
    from paddle_tpu.serving import BrownoutShed, ServingFleet, \
        ServingFleetPolicy
    from paddle_tpu.serving.router import RouterConfig

    pattern = np.tile(np.arange(8), 8)
    work_root = tempfile.mkdtemp(prefix="pt_svfleet_drill_")

    # the same recipe the workers run, for the uninterrupted reference
    t0 = time.time()
    ref_engine = build_replica()
    ref_model = ref_engine.model
    print(f"[drill] reference model built in {time.time() - t0:.1f}s",
          flush=True)

    def expect(prompt, max_new):
        return np.asarray(ref_model.generate(
            paddle.to_tensor(np.asarray(prompt, np.int64)[None]),
            max_new_tokens=max_new, use_cache=True).numpy())[0].tolist()

    # deterministic chaos, armed by env so the WORKERS inherit it:
    #   r1 crashes at its 4th submit; r2 wedges at its 6th submit
    #   (crash + hang in phase A/B); r3 serves 600ms slow forever —
    #   under the 3s grace window, over the 250ms hedge deadline.
    # inc=0 pins each rule to the FIRST incarnation: a restarted worker
    # re-parses PT_FAULTS, and without the pin r1 would crash again at
    # its 2nd post-restart submit, forever (budget-exhausting the
    # drill). Low seq thresholds keep the triggers robust to placement
    # spread (load-aware scoring decides who gets how many submits).
    os.environ["PT_FAULTS"] = (
        "replica_crash@name=r1&seq=2&inc=0,"
        "replica_hang@name=r2&seq=3&inc=0,"
        "replica_slow@name=r3&ms=600&times=-1")

    # hedging stays OFF for phases A/B so the crash/hang recovery runs
    # through the REPLAY path (with hedge_ms armed, the hedges complete
    # the victims before the fence gets to replay them — also correct,
    # but then the drill would not exercise replay at all); phase C
    # arms it
    policy = ServingFleetPolicy(
        heartbeat_interval=0.25, heartbeat_timeout=3.0,
        backoff_base_s=0.2, backoff_max_s=2.0, poll_interval=0.05,
        hedge_ms=None, replica_capacity=8, drain_timeout_s=30.0)
    fleet = ServingFleet(
        builder=os.path.abspath(__file__) + ":build_replica",
        n_replicas=3, names=["r1", "r2", "r3"], policy=policy,
        router_config=RouterConfig(),
        flight_root=os.path.join(work_root, "flight"),
        log_dir=os.path.join(work_root, "logs"))
    t0 = time.time()
    fleet.start(wait_ready=True, timeout=600)
    print(f"[drill] 3-process fleet ready in {time.time() - t0:.1f}s",
          flush=True)

    def run_load(jobs, tag):
        """Submit, collect streamed tokens per request, assert every
        request completes with its EXACT expected sequence and that the
        stream equals the result's generated tail (zero lost or
        duplicated tokens)."""
        futs = []
        for off, plen, mx in jobs:
            prompt = pattern[off:off + plen].astype(np.int64)
            streamed = []
            fut = fleet.submit(prompt, max_new_tokens=mx,
                               on_token=streamed.append)
            futs.append((prompt, mx, streamed, fut))
        for prompt, mx, streamed, fut in futs:
            out = fut.result(timeout=300).tolist()
            want = expect(prompt, mx)
            assert out == want, (tag, prompt.tolist(), out, want)
            assert streamed == out[len(prompt):], \
                (tag, "stream dup/loss", streamed, out[len(prompt):])
        return len(futs)

    # -- phase A: crash mid-stream -> fence, replay, bit-identical ------------
    # long generations (prompt + budget pinned so a replayed prefix
    # still fits the largest prefill bucket: plen + max_new - 1 <= 24)
    # keep requests IN FLIGHT when r1 dies at its 4th submit — the
    # replay path, not just re-dispatch, is what phase A must cross
    jobs = []
    for i in range(18):
        plen = 9 + (i % 3)
        jobs.append(((i * 3) % 8, plen, 24 - plen))
    n = run_load(jobs, "crash_phase")
    deadline = time.time() + 60
    while time.time() < deadline:
        snap = fleet.provider_snapshot()
        if snap["replicas"]["r1"]["state"] == "ready" and \
                snap["replicas"]["r1"]["incarnation"] >= 1:
            break
        time.sleep(0.2)
    snap = fleet.provider_snapshot()
    assert snap["replicas"]["r1"]["state"] == "ready", snap["replicas"]
    # the crash is detected by whichever layer sees it first: the
    # monitor's proc poll ("crash"), a lost RPC mid-request
    # ("rpc_fault"), or a failed submit send ("submit_fault") — the
    # same fence; the RPC layers usually beat the poll
    crash_recs = [r for r in snap["recoveries"]
                  if r["replica"] == "r1"
                  and r["cause"] in ("crash", "rpc_fault",
                                     "submit_fault")]
    assert crash_recs, snap["recoveries"]
    assert snap["counters"].get("fences", 0) >= 1
    print(f"[drill] phase A ok: {n} requests exact through a crash; "
          f"r1 fenced+restarted+re-admitted "
          f"(ready_ms={crash_recs[0].get('ready_ms')})", flush=True)

    # -- phase B: hang -> stale-heartbeat fence WITHIN the grace window -------
    n = run_load([((i * 5) % 8, 10 + (i % 2), 14 - (i % 2))
                  for i in range(10)], "hang_phase")
    deadline = time.time() + 60
    while time.time() < deadline:
        snap = fleet.provider_snapshot()
        stale = [r for r in snap["recoveries"]
                 if r["replica"] == "r2" and r["cause"] ==
                 "stale_heartbeat"]
        if stale and snap["replicas"]["r2"]["state"] == "ready":
            break
        time.sleep(0.2)
    snap = fleet.provider_snapshot()
    stale = [r for r in snap["recoveries"]
             if r["replica"] == "r2" and r["cause"] == "stale_heartbeat"]
    assert stale, ("r2 never fenced for staleness", snap["recoveries"])
    silent = stale[0].get("silent_s")
    assert silent is not None and \
        silent <= policy.heartbeat_timeout + 1.5, \
        ("fence exceeded the grace window", stale[0])
    assert snap["replicas"]["r2"]["state"] == "ready", snap["replicas"]
    print(f"[drill] phase B ok: r2 hang fenced after {silent:.2f}s "
          f"silence (grace {policy.heartbeat_timeout}s), restarted",
          flush=True)

    # -- phase C: slow replica -> hedged re-prefill, first wins ---------------
    fleet.policy.hedge_ms = 250.0  # arm hedging (read live per tick)
    run_load([((i * 7) % 8, 9, 5) for i in range(12)], "hedge_phase")
    snap = fleet.provider_snapshot()
    assert snap["counters"].get("hedges", 0) >= 1, snap["counters"]
    assert snap["counters"].get("hedge_wins", 0) >= 1, snap["counters"]
    print(f"[drill] phase C ok: hedges={snap['counters']['hedges']} "
          f"wins={snap['counters']['hedge_wins']}", flush=True)

    # -- phase D: brownout walks the stages and decays ------------------------
    fleet.policy.replica_capacity = 1  # tiny capacity: the burst overloads
    burst = [fleet.submit(pattern[:9].astype(np.int64), max_new_tokens=4)
             for _ in range(10)]
    deadline = time.time() + 30
    seen_stage = 0
    shed = 0
    while time.time() < deadline:
        seen_stage = max(seen_stage,
                         fleet.provider_snapshot()["brownout"]["stage"])
        try:
            fleet.submit(pattern[:9].astype(np.int64), max_new_tokens=2,
                         priority=0)  # sheddable class
        except BrownoutShed:
            shed += 1
        except serving.QueueFull:
            pass
        if seen_stage >= 3 and shed:
            break
        time.sleep(0.05)
    for f in burst:
        f.result(timeout=300)
    fleet.policy.replica_capacity = 8
    deadline = time.time() + 30
    while time.time() < deadline and \
            fleet.provider_snapshot()["brownout"]["stage"] != 0:
        time.sleep(0.1)
    snap = fleet.provider_snapshot()
    assert seen_stage >= 3, ("brownout never reached shed", seen_stage)
    assert shed >= 1
    assert snap["brownout"]["stage"] == 0, snap["brownout"]
    assert snap["counters"].get("brownout_transitions", 0) >= 2
    print(f"[drill] phase D ok: brownout peaked at stage {seen_stage}, "
          f"shed {shed} low-priority, decayed to normal", flush=True)

    # -- phase E: rolling restart under load, zero failed requests ------------
    # start from an all-ready fleet (phase C/D churn may have left a
    # replica mid-recovery)
    deadline = time.time() + 90
    while time.time() < deadline:
        snap = fleet.provider_snapshot()
        if all(r["state"] == "ready" for r in snap["replicas"].values()):
            break
        time.sleep(0.2)
    snap = fleet.provider_snapshot()
    assert all(r["state"] == "ready" for r in snap["replicas"].values()), \
        (snap["replicas"], snap["recoveries"], snap["rank_restarts"])

    import threading

    stop = threading.Event()
    roll_failures = []
    rolled_ok = {}

    def background_load():
        i = 0
        while not stop.is_set():
            try:
                run_load([((i * 3) % 8, 9 + (i % 2), 3)], "roll_phase")
            except Exception as e:  # pragma: no cover - the assertion
                roll_failures.append(repr(e))
            i += 1
            time.sleep(0.05)

    th = threading.Thread(target=background_load, daemon=True,
                          name="pt-drill-roll-load")
    th.start()
    res = fleet.rolling_restart()
    stop.set()
    th.join(timeout=120)
    rolled_ok = res
    assert res["ok"], res
    assert not roll_failures, roll_failures
    snap = fleet.provider_snapshot()
    assert snap["counters"].get("rolled_replicas", 0) == 3
    assert all(r["state"] == "ready" for r in snap["replicas"].values())
    print(f"[drill] phase E ok: rolling restart of 3 replicas under "
          f"load, zero failed requests ({rolled_ok})", flush=True)

    # -- provider + telemetry dump --------------------------------------------
    events = [e["event"] for e in snap["timeline"]]
    for needed in ("join", "evict", "fence", "restart", "roll_drain",
                   "roll_done", "brownout"):
        assert needed in events, (needed, events)
    for c in ("fences", "replays", "restarts", "hedges", "hedge_wins",
              "brownout_transitions", "shed_brownout", "completed"):
        assert snap["counters"].get(c, 0) >= 1, (c, snap["counters"])
    dump_path = os.path.join(work_root, "telemetry.json")
    obs.dump(dump_path)
    with open(dump_path) as f:
        tele = json.load(f)
    sf = tele["serving_fleet"]
    assert sf["counters"]["replays"] >= 1 and sf["timeline"], \
        "serving_fleet provider missing from the telemetry dump"
    print("[drill] telemetry ok: serving_fleet provider in dump")
    if os.environ.get("PT_LOCKDEP", "") not in ("", "0", "false"):
        # armed re-run (ci.sh): the whole chaos drill must complete with
        # the lock-order witness live and a cycle-free graph
        ld = tele.get("lockdep")
        assert ld and ld.get("armed"), \
            "PT_LOCKDEP=1 but the lockdep provider is missing/disarmed"
        assert ld["cycles"] == [], f"lock-order cycles: {ld['cycles']}"
        assert ld["locks"], "lockdep witnessed no locks"
        print(f"[drill] lockdep ok: {len(ld['locks'])} witnessed locks, "
              f"{len(ld['edges'])} order edges, zero cycles", flush=True)

    fleet.close()
    headline = {
        "replicas": 3,
        "completed": snap["counters"]["completed"],
        "fences": snap["counters"]["fences"],
        "replays": snap["counters"]["replays"],
        "restarts": snap["counters"]["restarts"],
        "hedge_wins": snap["counters"]["hedge_wins"],
        "brownout_peak": seen_stage,
        "stale_silence_s": round(silent, 2),
        "rolled": snap["counters"]["rolled_replicas"],
        "stream_mismatch": snap["counters"].get("stream_mismatch", 0),
    }
    assert headline["stream_mismatch"] == 0, headline
    print("SERVING_FLEET_DRILL_OK " + json.dumps(headline), flush=True)
    shutil.rmtree(work_root, ignore_errors=True)


if __name__ == "__main__":
    main()
