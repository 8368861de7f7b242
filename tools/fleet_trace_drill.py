#!/usr/bin/env python
"""Fleet-observability drill — the ISSUE-19 acceptance run.

A REAL 3-process CPU fleet split into pools (1 prefill + 2 decode
replicas, socket RPC, heartbeats through the control-plane TCPStore)
driving the fleet observability plane end to end:

1. cross-process tracing: KV-migrated requests render as SINGLE merged
   chrome traces — the supervisor's routing + wire-transfer spans and
   the replica-side prefill/decode/kv spans all land under one
   ``fleet-<id>`` trace context, with spans from >=3 DISTINCT os pids
   (supervisor, prefill replica, decode replica) in one export;
2. telemetry scrape + merge: the supervisor's collector pulls every
   replica's hub snapshot over the ``telemetry`` RPC and merges
   histogram families bucket-wise — the fleet ``request_latency_ms``
   sum/count must equal the sum of the per-replica snapshots EXACTLY;
3. SLO signals: per-pool p95/p99 and a finite burn rate computed ONLY
   from the merged buckets (no supervisor-side latency sampling);
4. exposition: the on-disk Prometheus file carries per-replica
   ``replica``/``pool`` labeled series plus the fleet aggregate and
   ``pt_fleet_slo_*`` gauges.

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
    """The replica builder (runs INSIDE each worker process): the tiny
    pattern-trained GPT every serving drill uses — cheap to build,
    deterministic across processes."""
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
    return serving.GenerationEngine(
        model, serving.GenerationConfig(
            max_slots=2, max_seq_len=48, page_len=8, num_pages=48,
            prefill_buckets=(8, 16, 24, 32, 40)))


def main():
    import paddle_tpu.observability as obs
    from paddle_tpu.serving import ServingFleet, ServingFleetPolicy
    from paddle_tpu.serving.router import RouterConfig

    pattern = np.tile(np.arange(8), 8)
    work_root = tempfile.mkdtemp(prefix="pt_fleettrace_drill_")
    prom_path = os.path.join(work_root, "fleet_metrics.prom")

    policy = ServingFleetPolicy(
        heartbeat_interval=0.25, heartbeat_timeout=3.0,
        backoff_base_s=0.2, backoff_max_s=2.0, poll_interval=0.05,
        hedge_ms=None, replica_capacity=8, drain_timeout_s=30.0,
        telemetry_interval_s=0.5, slo_target_ms=2000.0,
        slo_objective=0.99, slo_window_s=60.0)
    fleet = ServingFleet(
        builder=os.path.abspath(__file__) + ":build_replica",
        n_replicas=3, names=["p0", "d0", "d1"],
        pools={"prefill": ["p0"], "decode": ["d0", "d1"]},
        min_ship_tokens=8,
        policy=policy, router_config=RouterConfig(),
        flight_root=os.path.join(work_root, "flight"),
        log_dir=os.path.join(work_root, "logs"),
        prom_path=prom_path)
    t0 = time.time()
    fleet.start(wait_ready=True, timeout=600)
    print(f"[drill] 3-process pooled fleet ready in "
          f"{time.time() - t0:.1f}s", flush=True)

    # -- load: every request crosses prefill -> wire -> decode ----------------
    futs = []
    for i in range(6):
        plen = 16 + (i % 2) * 8
        mx = 4 + (i % 3)
        prompt = pattern[(i * 3) % 8:(i * 3) % 8 + plen].astype(np.int64)
        streamed = []
        futs.append((plen, mx, streamed,
                     fleet.submit(prompt, max_new_tokens=mx,
                                  on_token=streamed.append)))
    for plen, mx, streamed, fut in futs:
        out = fut.result(timeout=300).tolist()
        assert len(out) == plen + mx, (plen, mx, out)
        assert streamed == out[plen:], "stream dup/loss"
    n = len(futs)
    snap = fleet.provider_snapshot()
    assert snap["counters"].get("migrations", 0) >= 1, snap["counters"]
    print(f"[drill] load ok: {n} requests migrated prefill->decode",
          flush=True)

    # -- 1. one merged chrome trace spanning >=3 real processes ---------------
    trace_path = os.path.join(work_root, "fleet_trace.json")
    best_fid, best_pids = None, {}
    deadline = time.time() + 30
    while time.time() < deadline:
        fleet.export_fleet_trace(trace_path)
        for fid in fleet.traces.merged():
            pids = fleet.traces.span_pids(fid)
            if len(pids) > len(best_pids):
                best_fid, best_pids = fid, pids
        if len(best_pids) >= 3:
            break
        time.sleep(0.25)
    assert best_fid is not None and best_fid.startswith("fleet-"), best_fid
    assert len(best_pids) >= 3, \
        f"want spans from >=3 pids under one fleet trace, got {best_pids}"
    sup_pid = os.getpid()
    assert sup_pid in best_pids, (sup_pid, best_pids)
    assert "route" in best_pids[sup_pid], best_pids[sup_pid]
    assert "wire_transfer" in best_pids[sup_pid], \
        ("supervisor wire-transfer span missing", best_pids[sup_pid])
    # the export file itself carries the same >=3-pid trace
    with open(trace_path) as f:
        doc = json.load(f)
    ev_pids = {e["pid"] for e in doc["traceEvents"]
               if e.get("ph") == "X"
               and e.get("args", {}).get("fleet") == best_fid}
    assert len(ev_pids) >= 3, ev_pids
    col = fleet.traces.snapshot()
    assert col["fleet_traces"] >= n, col
    print(f"[drill] trace ok: fleet trace {best_fid} spans "
          f"{len(best_pids)} pids "
          f"({ {p: len(s) for p, s in best_pids.items()} } spans/pid); "
          f"export carries {col['traces']} traces from "
          f"{col['pids']} pids", flush=True)

    # -- 2. scrape + EXACT bucket-wise merge ----------------------------------
    merged = fleet.scrape_now()
    rows = merged["replicas"]
    assert set(rows) == {"p0", "d0", "d1"}, rows
    worker_pids = {r["pid"] for r in rows.values()}
    assert len(worker_pids) == 3 and sup_pid not in worker_pids, rows
    assert rows["p0"]["pool"] == "prefill", rows["p0"]
    assert merged["merge_errors"] == [], merged["merge_errors"]
    lat = merged["histograms"]["request_latency_ms"]
    per_rep = lat["per_replica"]
    assert lat["fleet"]["count"] == \
        sum(s["count"] for s in per_rep.values()), lat
    assert lat["fleet"]["sum_exact"] == \
        sum(s["sum_exact"] for s in per_rep.values()), \
        "fleet histogram sum must be the EXACT per-replica total"
    # every request produced one prefill-leg and one decode-leg latency
    assert lat["fleet"]["count"] >= 2 * n, lat["fleet"]["count"]
    assert set(lat["per_pool"]) == {"prefill", "decode"}, lat["per_pool"]
    print(f"[drill] merge ok: fleet request_latency_ms count="
          f"{lat['fleet']['count']} == sum of {len(per_rep)} replica "
          f"snapshots, sum_exact matches bit-for-bit", flush=True)

    # -- 3. SLO signals from merged buckets only ------------------------------
    slo = fleet.slo_snapshot()
    assert slo["target_ms"] == 2000.0, slo
    f = slo["fleet"]
    assert np.isfinite(f["burn_rate"]) and f["burn_rate"] >= 0.0, f
    assert np.isfinite(f["p95_ms"]) and f["p95_ms"] > 0.0, f
    assert f["count_total"] == lat["fleet"]["count"], \
        "slo counts must come from the merged histogram, nothing else"
    for pool in ("prefill", "decode"):
        pv = slo["pools"][pool]
        assert np.isfinite(pv["p95_ms"]) and pv["count_total"] >= n, pv
    print(f"[drill] slo ok: fleet p95={f['p95_ms']}ms "
          f"p99={f['p99_ms']}ms burn={f['burn_rate']} "
          f"(decode p95={slo['pools']['decode']['p95_ms']}ms)",
          flush=True)

    # -- 4. labeled exposition on disk ----------------------------------------
    assert os.path.exists(prom_path), prom_path
    with open(prom_path) as fh:
        text = fh.read()
    for rep in ("p0", "d0", "d1"):
        assert f'replica="{rep}"' in text, f"missing {rep} labels"
    assert 'pool="decode"' in text and 'pool="prefill"' in text, text[:400]
    assert "pt_request_latency_ms_count" in text
    assert "pt_fleet_slo_p95_ms" in text, "fleet p95 gauge missing"
    assert "pt_fleet_slo_burn_rate" in text
    print(f"[drill] exposition ok: {prom_path} carries per-replica "
          f"labels + fleet SLO gauges ({len(text.splitlines())} lines)",
          flush=True)

    # -- hub providers + lockdep ----------------------------------------------
    hub = obs.snapshot()
    assert hub["fleet_telemetry"]["totals"]["replicas"] == 3
    assert hub["slo"]["fleet"]["count_total"] >= 2 * n
    assert hub["fleet_trace"]["pids"] >= 3, hub["fleet_trace"]
    if os.environ.get("PT_LOCKDEP", "") not in ("", "0", "false"):
        ld = hub.get("lockdep")
        assert ld and ld.get("armed"), \
            "PT_LOCKDEP=1 but the lockdep provider is missing/disarmed"
        assert ld["cycles"] == [], f"lock-order cycles: {ld['cycles']}"
        assert ld["locks"], "lockdep witnessed no locks"
        print(f"[drill] lockdep ok: {len(ld['locks'])} witnessed locks, "
              f"{len(ld['edges'])} order edges, zero cycles", flush=True)

    fleet.close()
    headline = {
        "replicas": {"prefill": 1, "decode": 2},
        "completed": snap["counters"]["completed"],
        "fleet_traces": col["fleet_traces"],
        "trace_pids": sorted(best_pids),
        "merged_count": lat["fleet"]["count"],
        "fleet_p95_ms": f["p95_ms"],
        "burn_rate": f["burn_rate"],
        "scrapes": merged.get("scraped_at") is not None,
    }
    print("FLEET_TRACE_DRILL_OK " + json.dumps(headline), flush=True)
    shutil.rmtree(work_root, ignore_errors=True)


if __name__ == "__main__":
    main()
