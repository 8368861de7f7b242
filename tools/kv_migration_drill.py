#!/usr/bin/env python
"""KV-migration drill — the ISSUE-18 acceptance run.

A REAL 3-process CPU fleet split into pools (1 prefill + 2 decode
replicas, socket RPC, heartbeats through the control-plane TCPStore)
driving the disaggregated serving path end to end:

1. migration: every eligible request runs its prefill leg (exactly one
   token) on the prefill replica, its paged-KV pages are packed,
   chunked, SHA-verified and installed on a decode replica over the
   fleet wire protocol, and the decode leg continues the stream —
   every request BIT-IDENTICAL to the uninterrupted
   ``model.generate`` reference, with ZERO re-prefill fallbacks;
2. failover by page ship: a decode replica hard-crashes mid-decode ⇒
   the supervisor re-ships the retained pages to the surviving decode
   replica and replays there (counter-asserted ``failover_ship``, not
   re-prefill), streams still exact; the crashed replica restarts and
   is re-admitted;
3. warm tier: repeats of one prompt hit the fleet-wide host-RAM cache
   (ghost-gated admission: export twice, then serve from RAM) —
   ``warm_hits`` counted, streams still exact;
4. the ``kv_migration`` hub provider and the telemetry dump carry the
   ship/install/failover/warm counters and the pool map.

With ``PT_LOCKDEP=1`` the whole drill re-runs under the runtime
lock-order witness and must stay cycle-free.  Exit code 0 only when
every assertion holds.
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
    pattern-trained GPT — every process builds bit-identical weights
    from the same seeded recipe, which is what makes the shipped-pages
    continuation bit-identical under greedy decoding."""
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
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y),
                         optimizer)
    ids = paddle.to_tensor(
        np.tile(np.arange(8), 8)[None, :].astype("int64"))
    for _ in range(80):
        step(ids, ids)
    # buckets reach 40: a decode leg re-prefilling prompt+progress after
    # a failover must still fit (16-token prompt + up to 20 generated)
    return serving.GenerationEngine(
        model, serving.GenerationConfig(
            max_slots=2, max_seq_len=48, page_len=8, num_pages=48,
            prefill_buckets=(8, 16, 24, 32, 40)))


def main():
    import paddle_tpu as paddle
    import paddle_tpu.observability as obs
    from paddle_tpu.serving import ServingFleet, ServingFleetPolicy
    from paddle_tpu.serving.router import RouterConfig

    pattern = np.tile(np.arange(8), 8)
    work_root = tempfile.mkdtemp(prefix="pt_kvmig_drill_")

    t0 = time.time()
    ref_model = build_replica().model
    print(f"[drill] reference model built in {time.time() - t0:.1f}s",
          flush=True)

    def expect(prompt, max_new):
        return np.asarray(ref_model.generate(
            paddle.to_tensor(np.asarray(prompt, np.int64)[None]),
            max_new_tokens=max_new, use_cache=True).numpy())[0].tolist()

    # deterministic chaos, armed by env so the WORKERS inherit it: d0
    # hard-exits at its 3rd submit (phase-2 decode legs land 3 in-flight
    # streams on it).  inc=0 pins the rule to the first incarnation so
    # the restarted worker serves instead of crash-looping.
    os.environ["PT_FAULTS"] = "replica_crash@name=d0&seq=3&inc=0"

    # hedging OFF: the failover must cross the SHIP path, not a hedge
    policy = ServingFleetPolicy(
        heartbeat_interval=0.25, heartbeat_timeout=3.0,
        backoff_base_s=0.2, backoff_max_s=2.0, poll_interval=0.05,
        hedge_ms=None, replica_capacity=8, drain_timeout_s=30.0)
    fleet = ServingFleet(
        builder=os.path.abspath(__file__) + ":build_replica",
        n_replicas=3, names=["p0", "d0", "d1"],
        pools={"prefill": ["p0"], "decode": ["d0", "d1"]},
        min_ship_tokens=8,
        policy=policy, router_config=RouterConfig(),
        flight_root=os.path.join(work_root, "flight"),
        log_dir=os.path.join(work_root, "logs"))
    t0 = time.time()
    fleet.start(wait_ready=True, timeout=600)
    print(f"[drill] 3-process pooled fleet ready in "
          f"{time.time() - t0:.1f}s", flush=True)

    def run_load(jobs, tag):
        """Submit, collect streams, assert EXACT sequences and an
        exactly-once stream per request."""
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

    # -- phase 1: migration, bit-identical, zero fallbacks --------------------
    # distinct >=2-page prompts; every one is prefill-pool eligible
    # (plen >= min_ship_tokens=8, max_new > 1)
    jobs = [((i * 3) % 8, 16 + (i % 2) * 8, 6 + (i % 3))
            for i in range(8)]
    n = run_load(jobs, "migrate_phase")
    snap = fleet.provider_snapshot()
    mig = fleet.kv_migration_snapshot()
    assert snap["counters"].get("prefill_handoffs", 0) >= n, \
        snap["counters"]
    assert snap["counters"].get("migrations", 0) >= n, snap["counters"]
    assert mig["migrate_fallback"] == 0, mig
    assert mig["ships"] >= n and mig["installs"] >= n, mig
    assert mig["pages_shipped"] >= 2 * n, mig
    assert mig["pools"] == {"p0": "prefill", "d0": "decode",
                            "d1": "decode"}, mig["pools"]
    print(f"[drill] phase 1 ok: {n} requests exact through "
          f"prefill->decode migration "
          f"(ships={mig['ships']}, pages={mig['pages_shipped']}, "
          f"wire={mig['wire_bytes']}B, fallbacks=0)", flush=True)

    # -- phase 2: decode crash -> failover by page SHIP, not re-prefill -------
    # 6 long decode legs spread over d0/d1; d0 dies at its 3rd submit
    # with in-flight streams that must replay on d1 from shipped pages
    n = run_load([((i * 5) % 8, 16, 18 + (i % 3)) for i in range(6)],
                 "failover_phase")
    mig = fleet.kv_migration_snapshot()
    assert mig["failover_ship"] >= 1, mig
    assert mig["failover_reprefill"] == 0, mig
    deadline = time.time() + 60
    while time.time() < deadline:
        snap = fleet.provider_snapshot()
        if snap["replicas"]["d0"]["state"] == "ready" and \
                snap["replicas"]["d0"]["incarnation"] >= 1:
            break
        time.sleep(0.2)
    snap = fleet.provider_snapshot()
    assert snap["replicas"]["d0"]["state"] == "ready", snap["replicas"]
    assert snap["counters"].get("fences", 0) >= 1, snap["counters"]
    print(f"[drill] phase 2 ok: {n} requests exact through a decode "
          f"crash; failover re-shipped pages "
          f"(failover_ship={mig['failover_ship']}, reprefill=0); "
          f"d0 fenced+restarted+re-admitted", flush=True)

    # -- phase 3: repeats hit the fleet-wide warm tier ------------------------
    # one fixed 4-page prompt, 4 sequential submits: export #1 feeds the
    # ghost counter, #2 admits the payload, #3/#4 serve from host RAM
    before = fleet.kv_migration_snapshot()
    for _ in range(4):
        run_load([(0, 32, 6)], "warm_phase")
    mig = fleet.kv_migration_snapshot()
    warm_delta = mig["warm_hits"] - before["warm_hits"]
    export_delta = mig["exports"] - before["exports"]
    assert warm_delta >= 1, (before, mig)
    assert export_delta <= 3, (before, mig)
    assert mig["warm_cache"]["entries"] >= 1, mig["warm_cache"]
    print(f"[drill] phase 3 ok: 4 repeat submits exact, "
          f"{warm_delta} warm hits, {export_delta} exports "
          f"(cache: {mig['warm_cache']['entries']} entries, "
          f"{mig['warm_cache']['bytes']}B)", flush=True)

    # -- provider + telemetry dump --------------------------------------------
    hub = obs.snapshot()["kv_migration"]
    assert hub["ships"] >= 1 and hub["transit"] == "fp32", hub
    dump_path = os.path.join(work_root, "telemetry.json")
    obs.dump(dump_path)
    with open(dump_path) as f:
        tele = json.load(f)
    km = tele["kv_migration"]
    assert km["ships"] >= 1 and km["pools"], \
        "kv_migration provider missing from the telemetry dump"
    print("[drill] telemetry ok: kv_migration provider in dump")
    if os.environ.get("PT_LOCKDEP", "") not in ("", "0", "false"):
        ld = tele.get("lockdep")
        assert ld and ld.get("armed"), \
            "PT_LOCKDEP=1 but the lockdep provider is missing/disarmed"
        assert ld["cycles"] == [], f"lock-order cycles: {ld['cycles']}"
        assert ld["locks"], "lockdep witnessed no locks"
        print(f"[drill] lockdep ok: {len(ld['locks'])} witnessed locks, "
              f"{len(ld['edges'])} order edges, zero cycles", flush=True)

    snap = fleet.provider_snapshot()
    fleet.close()
    headline = {
        "replicas": {"prefill": 1, "decode": 2},
        "completed": snap["counters"]["completed"],
        "prefill_handoffs": snap["counters"]["prefill_handoffs"],
        "migrations": snap["counters"]["migrations"],
        "ships": mig["ships"],
        "pages_shipped": mig["pages_shipped"],
        "wire_mb": round(mig["wire_bytes"] / 1e6, 3),
        "failover_ship": mig["failover_ship"],
        "failover_reprefill": mig["failover_reprefill"],
        "migrate_fallback": mig["migrate_fallback"],
        "warm_hits": mig["warm_hits"],
        "stream_mismatch": snap["counters"].get("stream_mismatch", 0),
    }
    assert headline["stream_mismatch"] == 0, headline
    print("KV_MIGRATION_DRILL_OK " + json.dumps(headline), flush=True)
    shutil.rmtree(work_root, ignore_errors=True)


if __name__ == "__main__":
    main()
