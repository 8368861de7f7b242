#!/usr/bin/env bash
# CI entrypoint: repo self-lint + the tier-1 test suite.
#
#   bash tools/ci.sh            # both gates
#   bash tools/ci.sh --lint     # self-lint only (fast)
#
# Mirrors the reference's hard CI gates (tools/ci_op_benchmark.sh role):
# a PR that trips the static checker or the tier-1 suite does not land.
set -o pipefail
cd "$(dirname "$0")/.."

echo "== pd_check --self (repo footgun lint) =="
JAX_PLATFORMS=cpu python tools/pd_check.py --self || exit 1

echo "== pd_check --concurrency (CC lint: threads & locks) =="
# repo-wide blocking-under-lock / signal-handler-lock / thread-leak /
# lock-order pass; any error-severity finding fails the build
JAX_PLATFORMS=cpu python tools/pd_check.py --concurrency || exit 1

if [ "${1:-}" = "--lint" ]; then
    exit 0
fi

echo "== serving gate (engine tests + demo) =="
JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python examples/serve_gpt.py --clients 4 || exit 1
# ISSUE-12 serving tier: the full paged-KV/speculative/router test file
# (slow legs included: spec greedy parity vs model.generate, zero-retrace
# audit, 2-replica fleet with injected fault), then the router drill —
# 2 replicas, shared-system-prompt traffic -> prefix hits, zero fresh XLA
# compiles on the warm replica (persistent-cache counter), queue drains
# after the injected replica fault, zero serving retrace events
JAX_PLATFORMS=cpu python -m pytest tests/test_paged_serving.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python tools/router_drill.py || exit 1

echo "== perf gate (warm path: persistent-cache warm start) =="
# the full warm-path file, slow-marked legs included (tier-1 excludes
# them for wall clock): a fresh process must warm previously-compiled
# programs with ZERO fresh XLA compiles (the ISSUE-3 acceptance counter)
JAX_PLATFORMS=cpu python -m pytest tests/test_warm_path.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== streaming-offload gate (executor tests, slow legs included) =="
# overlapped-vs-serialized bit parity, pipelined group schedule (also
# under accumulate(k)), stream_wait/offload_stream telemetry, and the
# Llama-scale A/B (slow-marked for tier-1 wall clock, run here)
JAX_PLATFORMS=cpu python -m pytest tests/test_offload_executor.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== kernels gate (ISSUE-13: Pallas fused-op layer) =="
# interpret-vs-reference parity (fwd + grad) for fused MoE dispatch,
# RMSNorm+residual, RoPE and paged attention; the registry's one
# decision; retrace-audited attention threshold; planner fused cost
# entries
JAX_PLATFORMS=cpu python -m pytest tests/test_pallas_kernels.py \
    tests/test_kernel_seam.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
# the planner must re-rank or record cost deltas when fused entries are on
JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

paddle.seed(0)
m = LlamaForCausalLM(LlamaConfig.tiny())
kw = dict(n_devices=8, hbm_bytes=16e9, batch=16, seq=64)
off = dist.plan(m, fused_kernels=False, **kw)
on = dist.plan(m, fused_kernels=True, **kw)
by = {str(c.config): c.predicted_step_s for c in off}
deltas = [by[str(c.config)] - c.predicted_step_s
          for c in on if str(c.config) in by]
assert sum(1 for d in deltas if d > 0) >= 1, "no fused cost delta recorded"
assert on[0].breakdown.get("fused_gain_s", 0) > 0, on[0].breakdown
reranked = [c.describe() for c in off[:10]] != [c.describe() for c in on[:10]]
print("planner fused entries OK:", {
    "configs_repriced": sum(1 for d in deltas if d > 0),
    "top_reranked": reranked,
    "top_gain_ms": round(on[0].breakdown["fused_gain_s"] * 1e3, 4)})
PY

echo "== sparse gate (ISSUE-14: streamed embedding tables) =="
# cache policy determinism, streamed-vs-resident bit parity (incl.
# accumulate(k) and early-prefetch staleness), OOV policy, hapi flush,
# PS shard source, serving zero-retrace, planner term, lane row API
JAX_PLATFORMS=cpu python -m pytest tests/test_sparse_embedding.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== memory-truth gate (ISSUE-8: memory family + drift bound + OOM drill) =="
# full memory-truth test file (slow legs included), then the injected-OOM
# forensics drill: PT_FAULTS="oom@step=N" must leave a complete parseable
# bundle whose memory report names the top live buffers
JAX_PLATFORMS=cpu python -m pytest tests/test_memory_truth.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python tools/mem_drill.py || exit 1

echo "== device-truth tracing gate (ISSUE-7: capture/serving-trace/flight drills + full test file) =="
# XPlane parse round-trips, trace-ID propagation, flight-recorder
# trigger->bundle — the heavy capture tests are slow-marked for tier-1
# wall clock but run IN FULL here
JAX_PLATFORMS=cpu python -m pytest tests/test_trace.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
# the three ISSUE-7 acceptance asserts: a CPU-traced step window reports
# XPlane-correlated device_compute_us + >=1 device-attributed op; one
# serving request's spans share a trace ID end to end; an injected
# slow-transfer regression trips the flight recorder into a complete
# parseable pd_dump bundle
JAX_PLATFORMS=cpu python tools/trace_drill.py || exit 1

echo "== planner gate (ISSUE-10: cost-model auto-parallel planner) =="
# the full planner test file (enumeration divisibility, HBM pruning,
# deterministic ranking, the dryrun matrix round-trip, Engine auto_plan)
JAX_PLATFORMS=cpu python -m pytest tests/test_planner.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
# smoke plan() on the tiny-Llama shape: a non-empty ranked list whose top
# pick is feasible
JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

paddle.seed(0)
cands = dist.plan(LlamaForCausalLM(LlamaConfig.tiny()), n_devices=8,
                  hbm_bytes=16e9, batch=16, seq=64)
assert cands, "plan() returned an empty ranked list"
assert cands[0].feasible, cands[0].to_dict()
assert cands[0].predicted_step_s > 0
print("planner gate OK:", {"candidates": len(cands),
                           "top": cands[0].describe(),
                           "predicted_ms": round(
                               cands[0].predicted_step_s * 1e3, 2)})
PY
echo "== resilience gate (commit protocol + kill-and-resume drill) =="
# the full resilience file (crash-mid-save injection, torn-checkpoint
# detection, in-process preempt/resume), then the cross-process half:
# a REAL kill -TERM of a training subprocess mid-run, resumed on a
# CHANGED XLA device count — stitched losses must match the
# uninterrupted run (the ISSUE-6 kill-and-resume acceptance)
JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
python tools/resilience_drill.py || exit 1

echo "== elastic gate (ISSUE-11: multi-process fleet runtime) =="
# the recovery state machine + hardened heartbeats + sync_peers
# diagnostics + supervisor failure paths (slow process legs included),
# then the end-to-end drill: a REAL 4-process jax.distributed fleet
# survives an injected worker_crash — fence, bounded restart at
# world=3, planner-selected new config, checkpoint-resumed completion,
# 0 torn checkpoints, membership timeline records eviction + restart
JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_runtime.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
python tools/resilience_drill.py --fleet || exit 1

echo "== serving-fleet gate (ISSUE-15: fault-tolerant multi-process serving) =="
# the reliability protocol in-process (classified fence errors, health
# re-admission, replay dedup ledger, hedging, brownout stages, rolling
# restart, retry jitter, replica fault kinds — slow legs included:
# real-engine stream/cancel + the 2-process crash-failover e2e), then
# the REAL 3-process chaos drill: replica_crash mid-stream fenced and
# replayed bit-identically (zero lost-or-duplicated tokens), a hung
# replica fenced within the heartbeat grace window, hedged re-prefill
# first-wins, brownout walk + decay, and a rolling restart under load
# with zero failed requests; counters + timeline land in the
# serving_fleet hub provider and the telemetry dump
JAX_PLATFORMS=cpu python -m pytest tests/test_serving_fleet.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python tools/serving_fleet_drill.py || exit 1

echo "== lockdep gate (ISSUE-16: armed drills, zero lock-order cycles) =="
# concurrency lint + witness unit drills (seeded AB/BA deadlock, CC
# true-positive fixtures), then the two heaviest multi-threaded drills
# re-run with the runtime lock-order witness ARMED: each must complete
# bit-identical with a populated lockdep provider and zero cycles
JAX_PLATFORMS=cpu python -m pytest tests/test_concurrency_lint.py \
    tests/test_lockdep.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
PT_LOCKDEP=1 python tools/resilience_drill.py || exit 1
JAX_PLATFORMS=cpu PT_LOCKDEP=1 python tools/serving_fleet_drill.py || exit 1

echo "== post-training gate (ISSUE-17: rollout -> reward -> train -> publish) =="
# the weight-distribution service (roundtrip bit-equality, per-chunk +
# whole-blob digest rejection, mid-transfer crash -> resumed transfer,
# backpressure, engine apply), behavior-logprob streams (crash-mid-
# stream parity), version-pinned replay (no cross-version stitch),
# buffer/reward/trainer units — then the REAL 3-process RL drill:
# 2 serving replicas + 1 elastic_fit trainer streaming weight versions;
# reward improves on the pattern task, r1 crashes mid-rollout with zero
# lost/duplicated tokens, the final push lands under load and every
# in-flight request finishes bit-identically on a single version; the
# lockdep-armed re-run must stay cycle-free
JAX_PLATFORMS=cpu python -m pytest tests/test_post_training.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python tools/rl_drill.py || exit 1
JAX_PLATFORMS=cpu PT_LOCKDEP=1 python tools/rl_drill.py || exit 1

echo "== kv migration gate (ISSUE-18: disaggregated prefill/decode) =="
# wire-format units (pack/unpack fp32 bit-exact, int8 <= 0.55x bytes,
# chunk digests, ghost-gated fleet cache, pool-aware routing, cost
# model), the slow engine loopback (export -> pack -> install on a
# second engine, continuation BIT-identical) and in-process pooled
# fleet — then the REAL 3-process drill: 1 prefill + 2 decode replicas,
# every request migrated over the wire with zero re-prefill fallbacks,
# a decode crash failed over by re-SHIPPING the retained pages, warm
# repeats served from the fleet-wide host-RAM tier; lockdep-armed
# re-run must stay cycle-free
JAX_PLATFORMS=cpu python -m pytest tests/test_kv_migration.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python tools/kv_migration_drill.py || exit 1
JAX_PLATFORMS=cpu PT_LOCKDEP=1 python tools/kv_migration_drill.py || exit 1

echo "== fleet observability gate (ISSUE-19: traces + merged telemetry + SLO) =="
# merge-API units (bucket-wise Histogram.merge exactness, label-aware
# CounterFamily.merge, quantile/burn math, tracer drain filters,
# collector dedup) and the in-process trace edge cases (hedge loser
# cancelled under the same fleet id, failover replay leg, ledger-
# complete with no re-dispatch, migrate_fallback reason) — then the
# REAL 3-process drill: one KV-migrated request renders as a single
# merged chrome trace with spans from >=3 distinct pids under one
# fleet trace id, the merged exposition carries per-replica labels
# with the fleet sum/count EXACTLY equal to the per-replica total, and
# the slo provider reports a finite burn rate; lockdep-armed re-run
# must stay cycle-free
JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_observability.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python tools/fleet_trace_drill.py || exit 1
JAX_PLATFORMS=cpu PT_LOCKDEP=1 python tools/fleet_trace_drill.py || exit 1

echo "== tuning gate (ISSUE-20: online auto-tuner closed loop) =="
# detector matrix (single spike never triggers, sustained regression
# does), quantile-cover property tests, restart-safe histogram
# windows, BucketSpec validation on derived shapes, rescore/respec
# units, OnlineTuner ledger + kill-switch — then the REAL multi-
# process drill, three legs: (serving) a workload shift drives bucket
# re-derivation applied through a rolling restart with bit-identical
# replayed streams and a confirmed keep; (plan-keep) a scripted
# slowdown trips the detector, the fleet fences PLANNED at a
# checkpoint boundary (zero restart budget), swaps plans and keeps;
# (plan-rollback) a persistent slowdown fails the post-apply measure
# and rolls back to the original digest with the candidate embargoed;
# lockdep-armed re-run must stay cycle-free
JAX_PLATFORMS=cpu python -m pytest tests/test_tuning.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
JAX_PLATFORMS=cpu python tools/tuning_drill.py || exit 1
JAX_PLATFORMS=cpu PT_LOCKDEP=1 python tools/tuning_drill.py || exit 1

echo "== tier-1 test suite =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit $rc
