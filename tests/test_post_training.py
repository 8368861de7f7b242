"""ISSUE 17: the post-training RL loop and its weight-distribution
service.

The weight service is covered alone (chunked publish/subscribe
roundtrip bit-equality, digest-mismatch rejection, mid-transfer crash
-> resumed transfer, backpressure under a non-reading subscriber), the
fleet-side satellites with engine-shaped fakes (behavior-logprob
parity across a crash-mid-stream failover, the version-pinned replay
path refusing a cross-version stitch), and the buffer/trainer pieces
directly (seeded determinism, staleness eviction, batch packing
geometry, the importance-weighted loss actually training). The real
3-process loop — rollout through serving replicas, elastic_fit
trainer, streamed weight pushes under load — is drilled end to end by
``tools/rl_drill.py`` (ci.sh post-training gate).
"""
import socket
import struct
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import post_training as ptt
from paddle_tpu.post_training.buffer import (
    ReplayBuffer, Trajectory, model_scored_reward, pattern_reward,
)
from paddle_tpu.post_training.rollout import RolloutWorker, cyclic_prompts
from paddle_tpu.post_training.trainer import make_rl_batch, make_rl_loss
from paddle_tpu.post_training.weights import (
    WeightPublisher, WeightSubscriber, pack_state, unpack_state, _sha,
)
from paddle_tpu.serving import ServingFleet, ServingFleetPolicy
from paddle_tpu.serving.metrics import MetricsRegistry


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


# -- the weight service alone -------------------------------------------------


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "embed": rng.standard_normal((32, 16)).astype(np.float32),
        "layers.0.qkv_w": rng.standard_normal((16, 48)).astype(np.float32),
        "steps": np.asarray([seed], dtype=np.int64),
    }


def test_pack_unpack_roundtrip_bit_equality():
    st = _state(3)
    blob, names = pack_state(st)
    back = unpack_state(blob, names)
    assert sorted(back) == sorted(st)
    for k in st:
        assert back[k].dtype == st[k].dtype
        assert np.array_equal(back[k], st[k])
    # packing is order-independent: same digest either way
    blob2, _ = pack_state(dict(reversed(list(st.items()))))
    assert _sha(blob) == _sha(blob2)


def test_publish_subscribe_roundtrip_bit_equality():
    got = {}
    with WeightPublisher(name="rt", chunk_bytes=256) as pub:
        sub = WeightSubscriber(
            pub.host, pub.port, name="rt",
            on_update=lambda s, v, m: got.update(s=s, v=v, m=m))
        st = _state(1)
        assert pub.publish(st, meta={"round": 4}) == 1
        assert sub.fetch_once() == 1
        assert got["v"] == 1 and got["m"] == {"round": 4}
        for k in st:
            assert np.array_equal(got["s"][k], st[k])
            assert got["s"][k].dtype == st[k].dtype
        # already-applied head: a second poll is a no-op
        assert sub.fetch_once() is None
        stats = sub.stats()
        assert stats["applies"] == 1 and stats["applied_version"] == 1
        assert stats["last"]["push_latency_ms"] >= 0


def test_digest_mismatch_rejected_per_chunk_and_whole_blob():
    with WeightPublisher(name="bad", chunk_bytes=64) as pub:
        pub.publish(_state(2))
        applied = []
        sub = WeightSubscriber(pub.host, pub.port, name="bad",
                               on_update=lambda s, v, m: applied.append(v))
        # (a) corrupt a chunk in place: its stored sha no longer matches
        pub.corrupt_chunk_for_test(1, 0)
        with pytest.raises(ConnectionError, match="hash mismatch"):
            sub.fetch_once()
        assert sub.stats()["chunk_rejects"] == 1
        # (b) corrupt AND re-hash the chunk: per-chunk shas pass, the
        # whole-blob digest catches it, nothing is applied
        with pub._lock:
            rec = pub._versions[1]
            rec["sha"] = [_sha(c) for c in rec["chunks"]]
        sub2 = WeightSubscriber(pub.host, pub.port, name="bad2",
                                on_update=lambda s, v, m: applied.append(v))
        with pytest.raises(RuntimeError, match="digest mismatch"):
            sub2.fetch_once()
        assert sub2.stats()["digest_rejects"] == 1
        assert applied == []


def test_mid_transfer_crash_resumes_without_refetch():
    with WeightPublisher(name="crash", chunk_bytes=32) as pub:
        st = {"w": np.arange(64, dtype=np.float32)}  # 8 chunks
        pub.publish(st)
        got = {}
        sub = WeightSubscriber(pub.host, pub.port, name="crash",
                               on_update=lambda s, v, m: got.update(s=s))
        pub.drop_after_chunks = 3  # serve 3 chunk asks, then cut the conn
        with pytest.raises(ConnectionError):
            sub.fetch_once()
        assert sub.stats()["partial_chunks"] == 3
        assert sub.fetch_once() == 1  # reconnect; pulls ONLY the rest
        assert np.array_equal(got["s"]["w"], st["w"])
        s = sub.stats()
        assert s["resumed_transfers"] == 1
        assert s["chunks_fetched"] == 8  # 3 + 5, nothing twice
        assert pub.stats()["chunks_served"] == 8


def test_backpressure_slow_reader_does_not_stall_fast_subscriber():
    with WeightPublisher(name="bp", chunk_bytes=1024) as pub:
        pub.publish({"w": np.zeros(4096, dtype=np.float32)})
        # a subscriber that ASKS for chunks but never reads the replies:
        # the publisher parks them in that conn's outbuf only
        slow = socket.create_connection((pub.host, pub.port), timeout=5)
        for i in range(8):
            req = b'{"op":"chunk","version":1,"index":0,"rid":%d}' % i
            slow.sendall(struct.pack(">I", len(req)) + req)
        got = {}
        sub = WeightSubscriber(pub.host, pub.port, name="fast",
                               on_update=lambda s, v, m: got.update(v=v))
        t0 = time.monotonic()
        assert sub.fetch_once() == 1
        assert time.monotonic() - t0 < 5.0
        assert got["v"] == 1
        slow.close()


def test_pathological_nonreader_disconnected_at_outbuf_cap():
    with WeightPublisher(name="cap", chunk_bytes=1 << 20,
                         max_outbuf=1 << 20) as pub:
        pub.publish({"w": np.zeros(1 << 19, dtype=np.float32)})  # 2MB
        slow = socket.create_connection((pub.host, pub.port), timeout=5)
        try:
            for i in range(64):  # ~1.4MB b64 frames, never read
                req = b'{"op":"chunk","version":1,"index":0,"rid":%d}' % i
                slow.sendall(struct.pack(">I", len(req)) + req)
        except (ConnectionResetError, BrokenPipeError):
            pass  # cut off mid-loop already: the very outcome under test
        assert _wait(lambda: pub.stats().get("slow_disconnects", 0) >= 1)
        slow.close()


def test_subscriber_applies_through_engine_swap_and_skips_failed():
    class _Eng:
        weight_version = 0

        def __init__(self):
            self.swaps = []
            self.fail = False

        def swap_weights(self, state, version=None, timeout=None):
            if self.fail:
                raise RuntimeError("engine busy")
            self.swaps.append((version, sorted(state)))
            self.weight_version = version
            return version

    eng = _Eng()
    with WeightPublisher(name="eng") as pub:
        sub = WeightSubscriber(pub.host, pub.port, engine=eng, name="eng")
        pub.publish(_state(5))
        assert sub.fetch_once() == 1
        assert eng.swaps[0][0] == 1
        # an apply failure marks the version failed — no retry spin
        eng.fail = True
        pub.publish(_state(6))
        with pytest.raises(RuntimeError, match="engine busy"):
            sub.fetch_once()
        assert sub.fetch_once() is None  # version 2 is poisoned
        eng.fail = False
        pub.publish(_state(7))
        assert sub.fetch_once() == 3  # the NEXT version applies again
        assert sub.stats()["apply_errors"] == 1


# -- fleet satellites: logprob ledger + version-pinned replay -----------------


class _LpReplica:
    """Engine-shaped fake that streams (token, logprob) pairs and
    carries a weight_version, for fleet failover tests."""

    def __init__(self, name, version=0):
        self.name = name
        self.metrics = MetricsRegistry()
        self.weight_version = version
        self.jobs = []  # (prompt, max_new, on_token, want_lp, future)
        self.healthy = True
        self.restarts = 0

    def start(self):
        return self

    def close(self, drain=True):
        pass

    def restart(self):
        self.restarts += 1

    def fence(self):
        pass

    def drain(self):
        pass

    def health(self):
        return self.healthy

    def queue_depth(self):
        return len(self.jobs)

    def stats(self):
        return self.metrics.snapshot()

    def kv_headroom(self):
        return 1.0

    def prefix_match_tokens(self, prompt, blocks=None):
        return 0

    def set_speculative(self, on):
        pass

    def cancel(self, fut):
        return False

    def submit(self, prompt, max_new_tokens=16, deadline_ms=None,
               on_token=None, return_logprobs=False):
        fut = Future()
        self.jobs.append((np.asarray(prompt), int(max_new_tokens),
                          on_token, bool(return_logprobs), fut))
        return fut

    @staticmethod
    def _lp_for(tok):
        # deterministic logprob per TOKEN VALUE: a replay of the same
        # continuation reproduces the same logprobs (greedy parity)
        return -0.25 - 0.01 * (int(tok) % 8)

    def step(self, n=1, i=0):
        """Stream n tokens of job i (continuation: prompt[-1]+1, ...)."""
        prompt, mx, cb, want_lp, fut = self.jobs[i]
        done = getattr(fut, "_streamed", 0)
        for j in range(done, min(done + n, mx)):
            t = int(prompt[-1]) + 1 + j
            if cb:
                cb(t, self._lp_for(t)) if want_lp else cb(t)
        fut._streamed = min(done + n, mx)

    def finish(self, i=0):
        prompt, mx, cb, want_lp, fut = self.jobs.pop(i)
        toks = [int(prompt[-1]) + 1 + j for j in range(mx)]
        seq = np.asarray(list(prompt) + toks, np.int64)
        if want_lp:
            lps = np.asarray([self._lp_for(t) for t in toks], np.float32)
            fut.set_result((seq, lps))
        else:
            fut.set_result(seq)


def _lp_fleet(versions=(0, 0), **policy_kw):
    pol = ServingFleetPolicy(poll_interval=0.02, **policy_kw)
    reps = [_LpReplica(f"f{i}", version=v)
            for i, v in enumerate(versions)]
    fleet = ServingFleet(replicas=reps, policy=pol).start()
    return fleet, reps


@pytest.mark.thread_leak_ok
def test_crash_mid_stream_logprob_parity():
    """Satellite (a): a failover-stitched trajectory carries the SAME
    behavior logprobs an uninterrupted one would — streamed pairs and
    the final (seq, logprobs) both match the ledger exactly-once."""
    fleet, (a, b) = _lp_fleet()
    try:
        streamed = []
        fut = fleet.submit([7], max_new_tokens=4, return_logprobs=True,
                           on_token=lambda t, lp: streamed.append((t, lp)))
        assert _wait(lambda: a.jobs or b.jobs)
        holder = a if a.jobs else b
        survivor = b if holder is a else a
        holder.step(2)                       # 8, 9 streamed with lps
        fleet.fence_replica(holder.name, cause="test_crash")
        assert _wait(lambda: survivor.jobs)
        rp, rmx, _cb, want_lp, _f = survivor.jobs[0]
        assert rp.tolist() == [7, 8, 9] and rmx == 2 and want_lp
        survivor.finish()
        seq, lps = fut.result(timeout=10)
        assert seq.tolist() == [7, 8, 9, 10, 11]
        ref = [_LpReplica._lp_for(t) for t in (8, 9, 10, 11)]
        assert lps.dtype == np.float32
        np.testing.assert_allclose(lps, ref, rtol=1e-6)
        # the stream saw each (token, logprob) exactly once, in order
        assert [t for t, _ in streamed] == [8, 9, 10, 11]
        np.testing.assert_allclose([lp for _, lp in streamed], ref,
                                   rtol=1e-6)
        snap = fleet.provider_snapshot()
        assert snap["counters"]["replays"] == 1
        assert snap["counters"].get("stream_mismatch", 0) == 0
    finally:
        fleet.close()


@pytest.mark.thread_leak_ok
def test_version_pin_refuses_cross_version_stitch():
    """Satellite (b): with an emitted prefix pinned to version 1 and
    only a version-2 survivor left, the replay must NOT stitch — it
    re-prefills from the prompt on the new version and position-dedups
    the stream (no lost or duplicated token)."""
    fleet, (a, b) = _lp_fleet(versions=(1, 2))
    try:
        streamed = []
        fut = fleet.submit([7], max_new_tokens=4, return_logprobs=True,
                           on_token=lambda t, lp: streamed.append(t))
        assert _wait(lambda: a.jobs or b.jobs)
        holder = a if a.jobs else b
        survivor = b if holder is a else a
        holder.step(2)                       # pinned to holder's version
        fleet.fence_replica(holder.name, cause="test_crash")
        assert _wait(lambda: survivor.jobs)
        rp, rmx, _cb, _want, _f = survivor.jobs[0]
        # re-prefill: prompt only, FULL budget — not prompt+emitted
        assert rp.tolist() == [7] and rmx == 4
        survivor.step(4)                     # re-walks positions 0,1
        survivor.finish()
        seq, lps = fut.result(timeout=10)
        assert seq.tolist() == [7, 8, 9, 10, 11]
        assert streamed == [8, 9, 10, 11]    # position-deduped
        assert len(lps) == 4
        snap = fleet.provider_snapshot()
        assert snap["counters"]["version_reprefill"] == 1
        assert snap["counters"].get("stream_mismatch", 0) == 0
        # the request is now pinned to the survivor's version
        assert getattr(fut, "_pt_req").weight_version == 2
    finally:
        fleet.close()


@pytest.mark.thread_leak_ok
def test_version_pin_prefers_same_version_survivor():
    """Three replicas, two on the pinned version: the replay stitches
    onto the same-version survivor (prompt+emitted, remaining budget),
    never the newer one."""
    fleet, (a, b, c) = _lp_fleet(versions=(1, 1, 2))
    try:
        fut = fleet.submit([3], max_new_tokens=3, return_logprobs=True)
        assert _wait(lambda: a.jobs or b.jobs or c.jobs)
        holder = next(r for r in (a, b, c) if r.jobs)
        assert holder is not c or holder.weight_version == 2
        if holder is c:  # pinned to v2: fence -> must re-prefill (no
            pytest.skip("dispatched to the v2 replica first")
        same = b if holder is a else a
        holder.step(1)
        fleet.fence_replica(holder.name, cause="test_crash")
        assert _wait(lambda: same.jobs or c.jobs)
        assert same.jobs and not c.jobs
        rp, rmx, _cb, _want, _f = same.jobs[0]
        assert rp.tolist() == [3, 4] and rmx == 2  # a true stitch
        same.finish()
        seq, _lps = fut.result(timeout=10)
        assert seq.tolist() == [3, 4, 5, 6]
        snap = fleet.provider_snapshot()
        assert snap["counters"].get("version_reprefill", 0) == 0
    finally:
        fleet.close()


# -- buffer + rewards ---------------------------------------------------------


def test_pattern_reward_per_token_credit():
    rf = pattern_reward(range(8))
    t = Trajectory([5, 6, 7], [0, 1, 3, 3], [-0.1] * 4, 0)
    r, per = rf(t)
    assert per == [1.0, 1.0, 0.0, 1.0] and r == 0.75


def test_model_scored_reward_is_mean_logprob():
    class _Scorer:
        def __call__(self, ids):
            b, s = np.asarray(ids).shape
            logits = np.zeros((b, s, 4), np.float32)
            logits[:, :, 2] = 10.0  # scorer loves token 2
            return logits

    rf = model_scored_reward(_Scorer())
    hi, per_hi = rf(Trajectory([0, 1], [2, 2], [0, 0], 0))
    lo, _ = rf(Trajectory([0, 1], [3, 3], [0, 0], 0))
    assert hi > lo and len(per_hi) == 2
    assert abs(hi) < 1e-3  # ~log(1) for the loved token


def test_buffer_seeded_determinism_and_staleness_eviction():
    def fill(buf):
        for i, v in enumerate((0, 0, 1, 1, 2, 2)):
            buf.add(Trajectory([i], [1], [0.0], v, reward=v))
        return buf

    b1 = fill(ReplayBuffer(seed=7, staleness_limit=1))
    b2 = fill(ReplayBuffer(seed=7, staleness_limit=1))
    s1 = [(t.prompt[0], t.weight_version)
          for t in b1.sample(3, current_version=2)]
    s2 = [(t.prompt[0], t.weight_version)
          for t in b2.sample(3, current_version=2)]
    assert s1 == s2
    assert all(v >= 1 for _, v in s1)  # v0 evicted as stale
    st = b1.stats()
    assert st["evicted_stale"] == 2 and st["depth"] == 4
    assert st["version_histogram"] == {"1": 2, "2": 2}


def test_buffer_capacity_eviction_and_reward_fn_on_add():
    buf = ReplayBuffer(capacity=3, seed=0, reward_fn=pattern_reward(range(8)))
    for i in range(5):
        buf.add(Trajectory([0], [1], [-0.1], i))
    st = buf.stats()
    assert st["depth"] == 3 and st["evicted_capacity"] == 2
    assert st["mean_reward"] == 1.0  # 0 -> 1 is the pattern continuation


# -- rollout worker -----------------------------------------------------------


class _FakeFleetForRollout:
    """submit() resolves immediately with (seq, lps) and stamps the
    version-pin seam the way ServingFleet does."""

    def __init__(self, version=3):
        self.version = version
        self.calls = []

    def submit(self, prompt, max_new_tokens=8, return_logprobs=False,
               **kw):
        assert return_logprobs
        self.calls.append(np.asarray(prompt))
        toks = [(int(prompt[-1]) + 1 + j) % 8
                for j in range(max_new_tokens)]
        fut = Future()

        class _Req:
            weight_version = self.version

        fut._pt_req = _Req()
        fut.set_result((np.asarray(list(prompt) + toks, np.int64),
                        np.asarray([-0.5] * len(toks), np.float32)))
        return fut


def test_rollout_worker_builds_versioned_trajectories():
    fleet = _FakeFleetForRollout(version=3)
    rw = RolloutWorker(fleet, cyclic_prompts(range(8), 4, seed=1),
                       max_new_tokens=3, name="t")
    trajs = rw.rollout(4)
    assert len(trajs) == 4
    for tr in trajs:
        assert tr.weight_version == 3
        assert len(tr.tokens) == 3 and len(tr.logprobs) == 3
        # the fake continues the cycle: a perfect pattern rollout
        assert pattern_reward(range(8))(tr)[0] == 1.0
    # seeded prompt source: a fresh worker replays the same prompts
    rw2 = RolloutWorker(_FakeFleetForRollout(), cyclic_prompts(
        range(8), 4, seed=1), max_new_tokens=3, name="t2")
    assert [t.prompt for t in rw2.rollout(4)] == \
        [t.prompt for t in trajs]
    assert rw.stats()["completed"] == 4


# -- batch packing + loss -----------------------------------------------------


def test_make_rl_batch_geometry():
    t = Trajectory([5, 6], [7, 0, 2], [-0.1, -0.2, -0.3], 1,
                   token_rewards=[1.0, 1.0, 0.0])
    ids, y = make_rl_batch([t], seq_len=6, baseline=0.5,
                           prompt_weight=2.0)
    assert ids.tolist() == [[5, 6, 7, 0, 2, 0]]
    # generated token j supervises position len(prompt)+j-1
    assert y[0, 1, 0] == 7 and y[0, 2, 0] == 0 and y[0, 3, 0] == 2
    np.testing.assert_allclose(y[0, 1:4, 1], [-0.1, -0.2, -0.3])
    np.testing.assert_allclose(y[0, 1:4, 2], [0.5, 0.5, -0.5])
    assert y[0, :, 3].tolist() == [1, 1, 1, 1, 0, 0]
    # position 0 predicts the prompt's own continuation: supervised
    # (sup=1, ratio pinned), advantage = prompt_weight, behavior 0
    assert y[0, 0].tolist() == [6.0, 0.0, 2.0, 1.0, 1.0]
    assert y[0, :, 4].tolist() == [1, 0, 0, 0, 0, 0]
    # prompt_weight=0 restores the pure-RL mask
    _, y0 = make_rl_batch([t], seq_len=6, baseline=0.5, prompt_weight=0.0)
    assert y0[0, :, 3].tolist() == [0, 1, 1, 1, 0, 0]
    assert y0[0, :, 4].tolist() == [0, 0, 0, 0, 0, 0]


def test_rl_loss_trains_pattern_continuation():
    """The importance-weighted objective moves a tiny GPT toward the
    rewarded continuation: correct-token logprob rises over steps."""
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    import paddle_tpu.optimizer as opt

    cfg = GPTConfig(vocab_size=16, hidden_size=16, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=16,
                    dtype="float32")
    paddle.seed(0)
    net = GPTForCausalLM(cfg)
    rf = pattern_reward(range(8))
    trajs = []
    rng = np.random.default_rng(0)
    for i in range(8):
        start = int(rng.integers(0, 8))
        prompt = [(start + j) % 8 for j in range(3)]
        toks = [(prompt[-1] + 1 + j) % 8 if j % 2 == 0 else
                int(rng.integers(8, 16)) for j in range(4)]
        tr = Trajectory(prompt, toks, [-2.0] * 4, 0)
        tr.reward, tr.token_rewards = rf(tr)
        trajs.append(tr)
    ids, y = make_rl_batch(trajs, seq_len=8, baseline=0.5)
    m = Model(net)
    m.prepare(optimizer=opt.Adam(parameters=net.parameters(),
                                 learning_rate=3e-3),
              loss=make_rl_loss(2.0))

    def correct_lp():
        logits = np.asarray(net(paddle.to_tensor(ids)), np.float64)
        lse = np.log(np.exp(logits - logits.max(-1, keepdims=True))
                     .sum(-1)) + logits.max(-1)
        tot = n = 0.0
        for b, tr in enumerate(trajs):
            for j in range(len(tr.tokens)):
                if tr.token_rewards[j] != 1.0:
                    continue
                p = len(tr.prompt) + j - 1
                want = (tr.prompt[-1] + 1 + j) % 8
                tot += logits[b, p, want] - lse[b, p]
                n += 1
        return tot / n

    before = correct_lp()
    for _ in range(12):
        m.train_batch([ids], [y])
    after = correct_lp()
    assert after > before + 0.05, (before, after)


# -- hub provider -------------------------------------------------------------


def test_post_training_provider_in_hub_snapshot():
    from paddle_tpu import observability

    buf = ptt.track(ReplayBuffer(seed=0, name="prov-buf"))
    buf.add(Trajectory([0], [1], [0.0], 2, reward=1.0))
    with ptt.track(WeightPublisher(name="prov-pub")) as pub:
        pub.publish({"w": np.zeros(4, np.float32)})
        ptt.loop_note(round=3, mean_reward=0.5, push_latency_ms=12.5)
        prov = observability.snapshot()["post_training"]
        assert prov["loop"]["round"] == 3
        kinds = {r["kind"] for r in prov["components"]}
        assert {"ReplayBuffer", "WeightPublisher"} <= kinds
        row = next(r for r in prov["components"]
                   if r["kind"] == "ReplayBuffer" and
                   r["name"] == "prov-buf")
        assert row["depth"] == 1
