#!/usr/bin/env python
"""Router drill — the ISSUE-12 serving-gate acceptance run.

Two ``GenerationEngine`` replicas behind ``ReplicaRouter``, CPU-only:

1. replica A compiles its executable set under a fresh persistent cache;
   replica B (the "restarted" replica) then builds the SAME set and must
   warm entirely from the cache: **zero fresh XLA compiles** (the
   persistent-cache counter, same contract as the ISSUE-3 warm start);
2. shared-system-prompt traffic through the router: **prefix_hit_rate >
   0** and every continuation correct;
3. injected replica fault: A closes mid-run; the router fences it and the
   remaining traffic **drains through B** (queue depth returns to 0);
4. the paged decode path reports **zero retrace events** steady-state
   (``analysis.retrace`` counter with ``PT_RETRACE_AUDIT=1``).

Exit code 0 only when every assertion holds.
"""
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run from anywhere

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PT_RETRACE_AUDIT"] = "1"
# a fixed path (the path is part of a cache key), emptied at start so that
# replica A really compiles: the drill measures cold-then-warm
_CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache", "router_drill")
shutil.rmtree(_CACHE_DIR, ignore_errors=True)
os.environ["PT_PERSISTENT_CACHE_DIR"] = _CACHE_DIR  # read at import

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.analysis as A  # noqa: E402
import paddle_tpu.optimizer as opt  # noqa: E402
from paddle_tpu import jit, serving  # noqa: E402
from paddle_tpu.jit import persistent_cache as pcache  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402


def main():
    A.retrace.enable()
    assert pcache.is_enabled(), "persistent cache must be on for the drill"

    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=64,
                    dtype="float32")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    pattern = np.tile(np.arange(8), 8)
    ids = paddle.to_tensor(pattern[None, :].astype("int64"))
    for _ in range(80):
        loss = step(ids, ids)
    assert float(loss) < 0.1, float(loss)

    def mk(name):
        return serving.GenerationEngine(
            model, serving.GenerationConfig(max_slots=2, max_seq_len=32,
                                            page_len=8,
                                            prefill_buckets=(8, 16, 24)),
            name=name)

    # -- 1. warm-replica zero-compile contract --------------------------------
    rep_a = mk("replica_a").warmup()
    base = pcache.stats()
    assert base["compiles"] > 0, base  # A really compiled something
    rep_b = mk("replica_b").warmup()
    warm = pcache.stats()
    fresh_on_warm = warm["compiles"] - base["compiles"]
    warm_hits = warm["hits"] - base["hits"]
    assert fresh_on_warm == 0, \
        f"warm replica paid {fresh_on_warm} fresh XLA compiles"
    assert warm_hits > 0, warm

    # -- 2. shared-system-prompt traffic through the router -------------------
    router = serving.ReplicaRouter([rep_a, rep_b], name="drill_fleet")
    prompt = pattern[:17].astype("int64")  # two full 8-blocks shared
    with router:
        router.submit(prompt, max_new_tokens=4).result(timeout=300)
        futs = [router.submit(prompt, max_new_tokens=4) for _ in range(7)]
        for f in futs:
            out = f.result(timeout=300)
            want = [(17 + i) % 8 for i in range(len(out) - 17)]
            assert out[17:].tolist() == want, (out[17:].tolist(), want)
        st = router.stats()
        fleet_hit = max(r["prefix_hit_rate"] or 0.0
                        for r in st["replicas"].values())
        assert fleet_hit > 0, st
        assert st["affinity_hits"] > 0, st

        # -- 3. injected replica fault: fence + drain through B ---------------
        victim = max(st["replicas"],
                     key=lambda n: st["replicas"][n]["routed"])
        survivor = "replica_b" if victim == "replica_a" else "replica_a"
        dict(replica_a=rep_a, replica_b=rep_b)[victim].close(drain=False)
        futs = [router.submit(prompt, max_new_tokens=3) for _ in range(6)]
        for f in futs:
            out = f.result(timeout=300)
            want = [(17 + i) % 8 for i in range(len(out) - 17)]
            assert out[17:].tolist() == want
        st = router.stats()
        assert victim in st["down"], st
        assert router.queue_depth() == 0, "queue stuck after replica fault"
        assert st["replicas"][survivor]["responses"] >= 6, st

        # -- 4. zero retrace steady-state -------------------------------------
        for rep in (rep_a, rep_b):
            rt = rep.retrace_events()
            assert rt == 0, (rep.name, rt)

    print("router drill OK:", json.dumps({
        "warm_replica_fresh_compiles": fresh_on_warm,
        "warm_replica_cache_hits": warm_hits,
        "prefix_hit_rate": fleet_hit,
        "affinity_hits": st["affinity_hits"],
        "faulted": victim,
        "survivor_responses": st["replicas"][survivor]["responses"],
        "retrace_events": 0,
    }))


if __name__ == "__main__":
    try:
        main()
    finally:
        shutil.rmtree(_CACHE_DIR, ignore_errors=True)
