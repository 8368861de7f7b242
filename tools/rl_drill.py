#!/usr/bin/env python
"""Post-training RL drill — the ISSUE-17 acceptance run.

A REAL 3-process CPU loop: 2 serving-replica processes (one
``GenerationEngine`` each, socket RPC under the ``ServingFleet``
supervisor) plus 1 trainer process running the RL objective under
``elastic_fit``, stitched together by the control-plane ``TCPStore``
and the streaming weight-distribution service:

    rollout (fleet) -> reward (replay buffer) -> train (trainer proc)
        -> publish (WeightPublisher) -> swap in place (subscribers)

and asserts, end to end:

1. learning: over ``ROUNDS`` rounds of rejection-sampling distillation
   on the cyclic-pattern task, mean rollout reward IMPROVES by a solid
   margin over the half-trained starting policy (seeded, greedy — the
   whole loop is deterministic modulo float scheduling);
2. exactly-once through chaos: ``r1`` hard-crashes mid-rollout
   (PT_FAULTS) ⇒ the fleet fences it, replays onto the survivor with
   the WEIGHT-VERSION PIN (a pinned request never stitches across
   versions), every rollout request still completes, zero
   lost/duplicated tokens, and the restarted replica re-subscribes and
   catches up to the latest published version;
3. push under load: long generations are IN FLIGHT when the final
   version lands ⇒ admission pauses, every request finishes
   bit-identically on a single version (verified against a reference
   engine fed the exact digest-verified states the subscribers
   applied), and the streamed tokens equal each result's tail;
4. the ``post_training`` hub provider (loop rounds/rewards, rollout
   and buffer counters, applied versions, push latency) lands in
   ``observability.snapshot()`` and the telemetry dump.

Exit code 0 only when every assertion holds.
"""
import json
import os
import shutil
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

import numpy as np  # noqa: E402

# the tuned recipe (see docs/post_training.md): a HALF-trained policy
# (30 pretrain steps -> greedy reward ~0.42 on random-phase prompts)
# improves through rejection-sampling distillation — keep only
# (near-)perfect trajectories, train prompt continuations as plain CE
# and generated tokens importance-weighted, 12 inner steps per round
PATTERN = list(range(8))
ROUNDS = 8
B = 16                  # rollouts per round == train batch rows
PROMPT_LEN = 6
MAX_NEW = 6
SEQ_LEN = 12
INNER_STEPS = 12
LR = 2e-3
PROMPT_WEIGHT = 2.0
SELECT_THRESH = 0.99
PREFIX = "ptq"
BASE_VERSION = 1        # v1 = the pretrained policy, pushed at start


def build_policy_model():
    """The shared policy recipe — replicas, trainer, and the reference
    engine all build bit-identical weights from the same seed. The
    pretrain rows cover every phase of the pattern (a single-phase
    corpus teaches a POSITION prior that never transfers to
    random-phase prompts), and 30 steps leaves reward headroom."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=64,
                    dtype="float32")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-3,
                          parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y),
                         optimizer)
    rows = np.stack([(np.arange(32) + r) % len(PATTERN)
                     for r in range(len(PATTERN))])
    ids = paddle.to_tensor(rows.astype("int64"))
    for _ in range(30):
        step(ids, ids)
    return model


def build_replica():
    """Replica builder (runs INSIDE each worker process)."""
    from paddle_tpu import serving

    return serving.GenerationEngine(
        build_policy_model(),
        serving.GenerationConfig(max_slots=2, max_seq_len=32, page_len=8,
                                 prefill_buckets=(8, 16)))


def trainer_main(store_addr: str) -> int:
    """The trainer process: rebuild the policy, publish it as v1, then
    run ``rl_fit`` — each round blocks on the rollout process's batch
    key, trains INNER_STEPS on it, and streams the update as the next
    version. Afterwards it holds the publisher open for the drill's
    under-load push and verification."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.post_training import WeightPublisher, rl_fit, track
    from paddle_tpu.serving.generation import (_extract_gpt_params,
                                               flatten_gpt_params)

    host, port = store_addr.rsplit(":", 1)
    store = TCPStore(host=host, port=int(port), world_size=1,
                     timeout=600)
    model = build_policy_model()

    def snap():
        return flatten_gpt_params(_extract_gpt_params(model))

    pub = track(WeightPublisher(name="trainer", keep_versions=4).start())
    pub.publish(snap(), version=BASE_VERSION, meta={"init": True})
    store.set(f"{PREFIX}/pub", f"{pub.host}:{pub.port}")
    print(f"[trainer] publisher up at {pub.host}:{pub.port}, "
          f"v{BASE_VERSION} = pretrained policy", flush=True)

    def build(ctx):
        return {"network": model,
                "optimizer": opt.Adam(parameters=model.parameters(),
                                      learning_rate=LR)}

    out = rl_fit(build, store=store, publisher=pub, rounds=ROUNDS,
                 batch_size=B, seq_len=SEQ_LEN,
                 steps_per_round=INNER_STEPS, base_version=BASE_VERSION,
                 prefix=PREFIX)
    print(f"[trainer] rl_fit done: pushed versions {out['pushed']}",
          flush=True)
    store.set(f"{PREFIX}/done", json.dumps(out["pushed"]))

    # under-load phase: publish one more version ON COMMAND, while the
    # rollout process holds long generations in flight
    store.wait([f"{PREFIX}/push_now"])
    pub.publish(snap(), meta={"final": True})
    store.set(f"{PREFIX}/final_version", str(pub.latest_version()))
    print(f"[trainer] final under-load push: v{pub.latest_version()}",
          flush=True)
    store.wait([f"{PREFIX}/exit"])
    pub.close()
    return 0


def main():
    import paddle_tpu.observability as obs
    import paddle_tpu.post_training as pt
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.post_training import (ReplayBuffer, RolloutWorker,
                                          WeightSubscriber,
                                          cyclic_prompts, make_rl_batch,
                                          pattern_reward, put_batch)
    from paddle_tpu.serving import ServingFleet, ServingFleetPolicy
    from paddle_tpu.serving.router import RouterConfig

    work_root = tempfile.mkdtemp(prefix="pt_rl_drill_")
    store = TCPStore(is_master=True, port=0, world_size=1, timeout=900)

    trainer_log = open(os.path.join(work_root, "trainer.log"), "wb")
    trainer = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "trainer",
         f"127.0.0.1:{store.port}"],
        env=dict(os.environ), stdout=trainer_log, stderr=trainer_log)

    def wait_key(key, deadline_s=600):
        # short per-call wait timeouts so every blocking wait on a
        # trainer-produced key polls trainer liveness between attempts
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if trainer.poll() is not None:
                trainer_log.flush()
                with open(trainer_log.name) as f:
                    tail = f.read()[-4000:]
                raise AssertionError(
                    f"trainer died (rc={trainer.returncode}) waiting "
                    f"for {key}:\n{tail}")
            try:
                store.wait([key], timeout=2)
                return store.get(key).decode()
            except TimeoutError:
                pass
        raise AssertionError(f"timed out waiting for store key {key}")

    try:
        _run(work_root, store, wait_key, obs, pt, ReplayBuffer,
             RolloutWorker, WeightSubscriber, cyclic_prompts,
             make_rl_batch, pattern_reward, put_batch, ServingFleet,
             ServingFleetPolicy, RouterConfig)
    finally:
        store.set(f"{PREFIX}/exit", "1")
        try:
            trainer.wait(timeout=30)
        except subprocess.TimeoutExpired:
            trainer.kill()
        trainer_log.close()
    shutil.rmtree(work_root, ignore_errors=True)


def _run(work_root, store, wait_key, obs, pt, ReplayBuffer,
         RolloutWorker, WeightSubscriber, cyclic_prompts, make_rl_batch,
         pattern_reward, put_batch, ServingFleet, ServingFleetPolicy,
         RouterConfig):
    # deterministic chaos: r1 hard-exits at its 20th submit — mid-way
    # through a rollout round (~8 submits/replica/round), with pinned
    # requests in flight. inc=0 pins the rule to the first incarnation
    # so the restarted r1 serves cleanly.
    os.environ["PT_FAULTS"] = "replica_crash@name=r1&seq=20&inc=0"
    policy = ServingFleetPolicy(
        heartbeat_interval=0.25, heartbeat_timeout=3.0,
        backoff_base_s=0.2, backoff_max_s=2.0, poll_interval=0.05,
        hedge_ms=None, replica_capacity=8, drain_timeout_s=30.0)
    fleet = ServingFleet(
        builder=os.path.abspath(__file__) + ":build_replica",
        n_replicas=2, names=["r1", "r2"], policy=policy,
        router_config=RouterConfig(),
        flight_root=os.path.join(work_root, "flight"),
        log_dir=os.path.join(work_root, "logs"))
    t0 = time.time()
    fleet.start(wait_ready=True, timeout=600)
    print(f"[drill] 2-process serving fleet ready in "
          f"{time.time() - t0:.1f}s", flush=True)

    # -- weight service hookup ------------------------------------------------
    pub_host, pub_port = wait_key(f"{PREFIX}/pub").rsplit(":", 1)
    pub_port = int(pub_port)
    fleet.subscribe_weights(pub_host, pub_port, poll_interval=0.05)
    # the drill's own subscriber mirrors every applied state — the
    # digest-verified bytes the replicas run become the REFERENCE
    states = {}
    ref_sub = pt.track(WeightSubscriber(
        pub_host, pub_port, name="ref", poll_interval=0.05,
        on_update=lambda st, ver, meta: states.__setitem__(ver, st)))
    ref_sub.start()

    def wait_versions(target, deadline_s=180, names=("r1", "r2")):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            vers = fleet.replica_weight_versions()
            if all(vers.get(n, -1) >= target for n in names):
                return vers
            time.sleep(0.05)
        raise AssertionError(
            f"replicas never reached v{target}: "
            f"{fleet.replica_weight_versions()} "
            f"{fleet.provider_snapshot()['replicas']}")

    wait_versions(BASE_VERSION)
    print(f"[drill] both replicas serving v{BASE_VERSION} "
          f"(pretrained policy)", flush=True)

    # -- the loop -------------------------------------------------------------
    buf = pt.track(ReplayBuffer(capacity=1024, seed=0, staleness_limit=4,
                                reward_fn=pattern_reward(PATTERN)))
    worker = pt.track(RolloutWorker(
        fleet, cyclic_prompts(PATTERN, PROMPT_LEN, seed=3),
        max_new_tokens=MAX_NEW, timeout=300))

    rewards, push_lat_ms, pool = [], [], []
    for k in range(ROUNDS):
        trajs = worker.rollout(B, on_trajectory=buf.add)
        # exactly-once: every rollout request completes — including the
        # round r1 dies under — with one behavior logprob per token
        assert len(trajs) == B, (k, worker.stats())
        assert all(len(t.tokens) == MAX_NEW and
                   len(t.logprobs) == MAX_NEW for t in trajs), trajs
        rewards.append(round(float(np.mean([t.reward for t in trajs])),
                             3))
        pool.extend(trajs)
        pool = pool[-4 * B:]
        # rejection sampling: train on (near-)perfect trajectories
        # only, replicated to fill the batch; before any exist, the
        # best of the pool
        good = sorted([t for t in pool if t.reward >= SELECT_THRESH],
                      key=lambda t: -t.id)
        best = good or sorted(pool, key=lambda t: -t.reward)
        best = (best * ((B - 1) // len(best) + 1))[:B]
        ids, y = make_rl_batch(best, SEQ_LEN, baseline=0.0,
                               prompt_weight=PROMPT_WEIGHT)
        t_put = time.time()
        put_batch(store, PREFIX, k, ids, y)
        vers = wait_versions(BASE_VERSION + k + 1)
        push_lat_ms.append(round((time.time() - t_put) * 1e3, 1))
        pt.loop_note(round=k + 1, rounds=ROUNDS, rewards=rewards,
                     replica_versions=vers,
                     train_and_push_ms=push_lat_ms,
                     selected_reward=round(float(np.mean(
                         [t.reward for t in best])), 3))
        print(f"[drill] round {k}: reward={rewards[-1]:.3f} "
              f"selected={np.mean([t.reward for t in best]):.3f} "
              f"versions={vers} "
              f"(train+push {push_lat_ms[-1]:.0f}ms)", flush=True)

    pushed = json.loads(wait_key(f"{PREFIX}/done"))
    assert pushed == list(range(BASE_VERSION + 1,
                                BASE_VERSION + ROUNDS + 1)), pushed

    # -- learning assert ------------------------------------------------------
    assert rewards[-1] >= rewards[0] + 0.10, rewards
    assert max(rewards) >= rewards[0] + 0.15, rewards
    assert float(np.mean(rewards[-2:])) > float(np.mean(rewards[:2])), \
        rewards
    print(f"[drill] learning ok: reward {rewards[0]:.3f} -> "
          f"{rewards[-1]:.3f} over {ROUNDS} rounds: {rewards}",
          flush=True)

    # -- crash recovery assert ------------------------------------------------
    snap = fleet.provider_snapshot()
    crash_recs = [r for r in snap["recoveries"]
                  if r["replica"] == "r1"
                  and r["cause"] in ("crash", "rpc_fault",
                                     "submit_fault")]
    assert crash_recs, snap["recoveries"]
    assert snap["counters"].get("fences", 0) >= 1, snap["counters"]
    assert snap["replicas"]["r1"]["incarnation"] >= 1, snap["replicas"]
    assert snap["replicas"]["r1"]["state"] == "ready", snap["replicas"]
    assert snap["counters"].get("stream_mismatch", 0) == 0, \
        snap["counters"]
    # the restarted r1 re-subscribed and caught up (wait_versions above
    # already proved it rejoined at the current version)
    assert snap["counters"].get("weight_subscribes", 0) >= 3, \
        snap["counters"]
    print(f"[drill] crash ok: r1 fenced+restarted+resubscribed "
          f"mid-rollout (cause={crash_recs[0]['cause']}), "
          f"zero token loss/dup", flush=True)

    # -- push under load: in-flight requests stay version-pure ----------------
    last_ver = BASE_VERSION + ROUNDS
    jobs = []
    for i in range(10):
        prompt = np.asarray([PATTERN[(i + j) % len(PATTERN)]
                             for j in range(PROMPT_LEN)], np.int64)
        streamed = []
        fut = fleet.submit(prompt, max_new_tokens=24,
                           on_token=streamed.append)
        jobs.append((prompt, streamed, fut))
    store.set(f"{PREFIX}/push_now", "1")
    final_ver = int(wait_key(f"{PREFIX}/final_version"))
    assert final_ver == last_ver + 1, (final_ver, last_ver)
    # the publish must LAND mid-flight: the reference subscriber
    # applies it while the long generations are still running
    deadline = time.time() + 60
    while final_ver not in states and time.time() < deadline:
        time.sleep(0.01)
    in_flight_at_push = sum(1 for _, _, f in jobs if not f.done())
    assert final_ver in states, (final_ver, sorted(states))
    assert in_flight_at_push >= 1, "push landed after all requests"

    results = []
    for prompt, streamed, fut in jobs:
        out = np.asarray(fut.result(timeout=300)).tolist()
        assert streamed == out[len(prompt):], \
            ("stream dup/loss under push", streamed, out[len(prompt):])
        ver = worker._request_version(fut)
        results.append((prompt.tolist(), out, ver))
    assert {v for _, _, v in results} == {last_ver}, results

    # bit-identical verification: a reference engine swaps in the SAME
    # digest-verified states the replicas applied; every under-load
    # output must match exactly one version's greedy decode — a
    # mid-request swap would produce a mixture matching neither
    ref_engine = build_replica()
    ref_engine.start()

    def ref_decode(version, prompt, mx):
        ref_engine.swap_weights(states[version], version=version)
        return np.asarray(ref_engine.submit(
            np.asarray(prompt, np.int64), mx).result(
                timeout=120)).tolist()

    matched = {last_ver: 0, final_ver: 0}
    for prompt, out, _ in results:
        if out == ref_decode(last_ver, prompt, 24):
            matched[last_ver] += 1
        else:
            assert out == ref_decode(final_ver, prompt, 24), \
                ("output matches NO single version", prompt, out)
            matched[final_ver] += 1
    assert matched[last_ver] >= 1, matched
    # after the in-flight work drains, the staged swap lands fleetwide
    wait_versions(final_ver)
    ref_engine.close()
    print(f"[drill] under-load push ok: {len(results)} long requests "
          f"bit-identical (v{last_ver}: {matched[last_ver]}, "
          f"v{final_ver}: {matched[final_ver]}), "
          f"{in_flight_at_push} in flight at publish, fleet now at "
          f"v{final_ver}", flush=True)

    # -- provider + telemetry -------------------------------------------------
    pt.loop_note(final_version=final_ver, matched=matched,
                 push_latency_ms=ref_sub.stats()["last"].get(
                     "push_latency_ms"))
    hub = obs.snapshot()["post_training"]
    assert hub["loop"]["round"] == ROUNDS, hub["loop"]
    assert hub["loop"]["rewards"] == rewards, hub["loop"]
    kinds = {r["kind"] for r in hub["components"]}
    assert {"ReplayBuffer", "RolloutWorker",
            "WeightSubscriber"} <= kinds, kinds
    b_row = next(r for r in hub["components"]
                 if r["kind"] == "ReplayBuffer")
    assert b_row["depth"] > 0 and b_row["added"] == ROUNDS * B, b_row
    s_row = next(r for r in hub["components"]
                 if r["kind"] == "WeightSubscriber")
    assert s_row["applied_version"] == final_ver, s_row
    assert s_row["last"]["push_latency_ms"] is not None, s_row

    dump_path = os.path.join(work_root, "telemetry.json")
    obs.dump(dump_path)
    with open(dump_path) as f:
        tele = json.load(f)
    assert tele["post_training"]["loop"]["rewards"] == rewards, \
        "post_training provider missing from the telemetry dump"
    print("[drill] telemetry ok: post_training provider in dump",
          flush=True)
    if os.environ.get("PT_LOCKDEP", "") not in ("", "0", "false"):
        ld = tele.get("lockdep")
        assert ld and ld.get("armed"), \
            "PT_LOCKDEP=1 but the lockdep provider is missing/disarmed"
        assert ld["cycles"] == [], f"lock-order cycles: {ld['cycles']}"
        assert any("post_training" in name for name in ld["locks"]), \
            "lockdep witnessed no post_training locks"
        print(f"[drill] lockdep ok: {len(ld['locks'])} witnessed locks, "
              f"zero cycles", flush=True)

    ref_sub.stop()
    fleet.close()
    headline = {
        "rounds": ROUNDS,
        "reward_first": rewards[0], "reward_last": rewards[-1],
        "rewards": rewards,
        "trajectories": worker.stats()["completed"],
        "versions_pushed": len(pushed) + 2,  # + init + under-load
        "fences": snap["counters"].get("fences", 0),
        "stream_mismatch": snap["counters"].get("stream_mismatch", 0),
        "version_reprefill": snap["counters"].get("version_reprefill",
                                                  0),
        "version_restitch": snap["counters"].get("version_restitch", 0),
        "inflight_at_final_push": in_flight_at_push,
        "underload_matched": {str(k): v for k, v in matched.items()},
        "push_latency_ms": ref_sub.stats()["last"].get(
            "push_latency_ms"),
    }
    print("RL_DRILL_OK " + json.dumps(headline), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "trainer":
        sys.exit(trainer_main(sys.argv[2]))
    main()
