"""paddle_tpu.serving: micro-batching engine + continuous-batching decode.

Covers the ISSUE 2 acceptance surface: batching correctness under
concurrent clients (>= 8), bucket-padding round-trip equivalence with the
unbatched ``inference.Predictor.run``, deadline shedding, per-request error
isolation, steady-state zero-retrace under the ``PT_RETRACE_AUDIT``
machinery, and the stats snapshot (QPS / latency percentiles / occupancy).
"""
import os
import threading
import time
from concurrent.futures import wait as fwait
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import inference, serving


# -- fixtures -----------------------------------------------------------------

@pytest.fixture(scope="module")
def mlp_predictor(tmp_path_factory):
    """Batch-polymorphic saved MLP + a Predictor over it."""
    from paddle_tpu.static import InputSpec

    d = tmp_path_factory.mktemp("serving_model")
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    net.eval()
    prefix = str(d / "model")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec((None, 8), "float32")])
    pred = inference.create_predictor(inference.Config(prefix))
    return pred, net


def _mk_engine(pred, **cfg):
    conf = serving.ServingConfig(**cfg)
    return serving.ServingEngine(
        pred, buckets=serving.BucketSpec(batch_sizes=(1, 2, 4, 8)),
        config=conf)


# -- batching correctness -----------------------------------------------------


def _assert_rows_match(got, ref):
    """A row served from a padded bucket-N batch vs the same row run alone
    (batch 1): the two are DIFFERENT XLA programs, and jax 0.9's CPU
    backend tiles their matmul reductions differently, so they agree to a
    few float32 ulps (5.96e-08 observed), not bit for bit. What the test
    pins is that no other row (or padding) leaks into a result — a leak is
    an O(1) difference, far outside this tolerance."""
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_concurrent_clients_match_unbatched_predictor(mlp_predictor):
    """8 concurrent client threads; every batched result must be
    bit-identical to an unbatched Predictor.run of the same sample."""
    pred, _net = mlp_predictor
    n_clients, per_client = 8, 6
    rng = np.random.RandomState(3)
    samples = rng.randn(n_clients, per_client, 8).astype("float32")
    with _mk_engine(pred, max_batch_wait_ms=5.0) as eng:
        results = [[None] * per_client for _ in range(n_clients)]

        def client(c):
            futs = [eng.submit([samples[c, j]]) for j in range(per_client)]
            for j, f in enumerate(futs):
                results[c][j] = f.result(timeout=60)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        stats = eng.stats()
    for c in range(n_clients):
        for j in range(per_client):
            ref = pred.run([samples[c, j][None]])[0][0]
            _assert_rows_match(results[c][j][0], ref)
    # the stats snapshot carries the acceptance metrics
    assert stats["counters"]["responses_total"] == n_clients * per_client
    assert stats["qps"] > 0
    for k in ("p50", "p95", "p99"):
        assert stats["latency_ms"][k] >= 0
    assert 0 < stats["batch_occupancy"] <= 1.0
    # coalescing actually happened: fewer batches than requests
    assert stats["counters"]["batches_total"] < n_clients * per_client


def test_batch_padding_roundtrip_rows(mlp_predictor):
    """3 requests ride the 4-bucket (one padded row); the padded row must
    not leak into real results."""
    pred, _net = mlp_predictor
    rng = np.random.RandomState(7)
    xs = [rng.randn(8).astype("float32") for _ in range(3)]
    with _mk_engine(pred, max_batch_wait_ms=50.0) as eng:
        futs = [eng.submit([x]) for x in xs]
        outs = [f.result(timeout=60) for f in futs]
        stats = eng.stats()
    for x, o in zip(xs, outs):
        _assert_rows_match(o[0], pred.run([x[None]])[0][0])
    # all three coalesced into ONE bucket-4 batch: occupancy 3/4
    assert stats["counters"]["batches_total"] == 1
    assert abs(stats["batch_occupancy"] - 0.75) < 1e-6


@pytest.mark.slow  # tier-1 wall-clock relief (ISSUE-5): run in full by tools/ci.sh's serving gate
def test_seq_bucket_padding_equivalence_causal_layer():
    """Seq-bucketed serving of a causal LM Layer: tail padding must leave
    logits at real positions equal to the unpadded forward."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(1)
    model = GPTForCausalLM(GPTConfig(vocab_size=32, hidden_size=32,
                                     num_hidden_layers=2,
                                     num_attention_heads=2,
                                     max_position_embeddings=32,
                                     dtype="float32"))
    model.eval()
    eng = serving.ServingEngine(
        model,
        buckets=serving.BucketSpec(batch_sizes=(2,), seq_lens=(8, 16)),
        input_specs=[((None,), "int64")],
        config=serving.ServingConfig(max_batch_wait_ms=20.0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 32, n).astype("int64") for n in (5, 11, 8)]
    with eng:
        futs = [eng.submit([p]) for p in prompts]
        outs = [f.result(timeout=120) for f in futs]
    for p, o in zip(prompts, outs):
        ref = np.asarray(model(paddle.to_tensor(p[None])).numpy(),
                         dtype="float32")[0]
        got = np.asarray(o[0], dtype="float32")
        # only the REAL positions are the request's answer
        np.testing.assert_allclose(got[: len(p)], ref, rtol=2e-5, atol=2e-5)


# -- admission control / robustness -------------------------------------------

class _SlowFakePredictor:
    """Predictor-shaped target whose executable blocks: deterministic
    backpressure and shedding tests."""

    def __init__(self, delay_s):
        self._layer = self._slow_layer(delay_s)

    @staticmethod
    def _slow_layer(delay_s):
        def layer(*arrays):
            time.sleep(delay_s)
            return [SimpleNamespace(data=np.asarray(arrays[0]))]
        return layer

    def run(self, inputs=None):  # pragma: no cover - marker attribute
        raise NotImplementedError


def _slow_engine(delay_s=0.15, **cfg):
    conf = serving.ServingConfig(warmup_on_start=False, **cfg)
    return serving.ServingEngine(
        _SlowFakePredictor(delay_s),
        buckets=serving.BucketSpec(batch_sizes=(1, 2)),
        input_specs=[((4,), "float32")], config=conf)


def test_queue_full_backpressure():
    eng = _slow_engine(delay_s=0.2, max_queue=2, max_batch_wait_ms=0.0)
    eng.start()
    x = np.zeros(4, np.float32)
    futs = [eng.submit([x])]          # occupies the worker
    time.sleep(0.05)                  # let the worker take it
    with pytest.raises(serving.QueueFull):
        for _ in range(10):           # must trip while the worker sleeps
            futs.append(eng.submit([x]))
    assert eng.metrics.counter("rejected_total") >= 1
    eng.close()
    for f in futs:
        f.result(timeout=30)          # drained on close


def test_deadline_shedding():
    eng = _slow_engine(delay_s=0.25, max_batch_wait_ms=0.0)
    eng.start()
    x = np.zeros(4, np.float32)
    first = eng.submit([x])           # occupies the worker ~250ms
    t0 = time.monotonic()
    while eng.queue_depth() > 0 and time.monotonic() - t0 < 10:
        time.sleep(0.005)             # wait until the worker TOOK first:
    # anything queued now sits behind a ~250ms execution
    doomed = eng.submit([x], deadline_ms=50.0)   # expires while queued
    ok = eng.submit([x])                          # no deadline: survives
    with pytest.raises(serving.DeadlineExceeded):
        doomed.result(timeout=30)
    first.result(timeout=30)
    ok.result(timeout=30)
    assert eng.metrics.counter("shed_total") == 1
    eng.close()


def test_bad_payload_fails_own_future_only(mlp_predictor):
    pred, _net = mlp_predictor
    with _mk_engine(pred, max_batch_wait_ms=10.0) as eng:
        good1 = eng.submit([np.zeros(8, np.float32)])
        bad_dtype = eng.submit([np.zeros(8, np.int32)])
        bad_rank = eng.submit([np.zeros((2, 8), np.float32)])
        bad_arity = eng.submit([np.zeros(8, np.float32)] * 2)
        good2 = eng.submit([np.ones(8, np.float32)])
        for bad in (bad_dtype, bad_rank, bad_arity):
            with pytest.raises(serving.BadRequest):
                bad.result(timeout=30)
        ref1 = pred.run([np.zeros((1, 8), np.float32)])[0][0]
        ref2 = pred.run([np.ones((1, 8), np.float32)])[0][0]
        _assert_rows_match(good1.result(timeout=60)[0], ref1)
        _assert_rows_match(good2.result(timeout=60)[0], ref2)
        assert eng.metrics.counter("bad_requests") == 3


def test_engine_closed_rejects():
    eng = _slow_engine(delay_s=0.01)
    eng.start()
    eng.close()
    with pytest.raises(serving.EngineClosed):
        eng.submit([np.zeros(4, np.float32)])


def test_profiler_sees_serving_spans(mlp_predictor):
    """Executed batches surface as RecordEvent spans ("Serving" category)
    on the profiler's host timeline."""
    from paddle_tpu import profiler

    pred, _net = mlp_predictor
    with _mk_engine(pred, max_batch_wait_ms=2.0) as eng:
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        p.start()
        futs = [eng.submit([np.zeros(8, np.float32)]) for _ in range(4)]
        fwait(futs, timeout=60)
        p.stop()
    names = [e[0] for e in p.events]
    assert any(n.startswith("serving::batch") for n in names)
    assert "serving::batch" in p.summary()


# -- steady-state zero-retrace ------------------------------------------------

def test_steady_state_zero_retrace(mlp_predictor):
    """PT_RETRACE_AUDIT machinery: warmup compiles are the per-bucket
    baselines; serving mixed batch sizes afterwards must record ZERO
    serving-labeled retrace events and zero compile-cache misses."""
    pred, _net = mlp_predictor
    os.environ["PT_RETRACE_AUDIT"] = "1"
    import paddle_tpu.analysis as A

    A.retrace.enable()
    try:
        eng = _mk_engine(pred, max_batch_wait_ms=2.0)
        with eng:
            rng = np.random.RandomState(11)
            futs = [eng.submit([rng.randn(8).astype("float32")])
                    for _ in range(24)]
            fwait(futs, timeout=120)
            stats = eng.stats()
        assert stats["retrace_events"] == 0
        assert stats["counters"].get("compile_cache_misses", 0) == 0
        assert stats["counters"]["compile_cache_hits"] >= 1
        assert stats["counters"]["warmup_compiles"] == 4  # one per bucket
    finally:
        A.retrace.disable()
        A.retrace.reset()
        os.environ.pop("PT_RETRACE_AUDIT", None)


# -- continuous batching ------------------------------------------------------

@pytest.fixture(scope="module")
def trained_tiny_gpt():
    """Tiny GPT trained to continue a repeating 0..7 pattern (the
    generate_gpt.py recipe): confident logits make greedy decode stable."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=32, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    dtype="float32")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-3,
                          parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    pattern = np.tile(np.arange(8), 8)[None, :]
    ids = paddle.to_tensor(pattern.astype("int64"))
    for _ in range(60):
        loss = step(ids, ids)
    assert float(loss) < 0.1
    return model, pattern[0]


@pytest.fixture(scope="module")
def gen_engine(trained_tiny_gpt):
    """ONE decode executable shared by the generation tests (the compile is
    the expensive part); tests assert on counter DELTAS so they stay
    order-independent."""
    model, pattern = trained_tiny_gpt
    eng = serving.GenerationEngine(
        model, serving.GenerationConfig(max_slots=2, max_seq_len=48,
                                        prefill_buckets=(16, 24)))
    eng.start()
    yield eng, model, pattern
    eng.close()


def _counters(eng):
    snap = eng.metrics.snapshot()["counters"]
    return lambda name: snap.get(name, 0)


@pytest.mark.slow  # tier-1 wall-clock relief (ISSUE-5): run in full by tools/ci.sh's serving gate
def test_continuous_batching_joins_midflight(gen_engine):
    """4 prompts through 2 slots: the later prompts must join as earlier
    sequences finish — and every continuation must be correct."""
    eng, _model, pattern = gen_engine
    before = _counters(eng)
    jobs = [(13, 6), (9, 5), (15, 6), (11, 4)]
    futs = [(p, eng.submit(pattern[:p].astype("int64"), max_new_tokens=m))
            for p, m in jobs]
    outs = [(p, f.result(timeout=300)) for p, f in futs]
    after = _counters(eng)
    for p, full in outs:
        gen = full[p:]
        want = [(p + i) % 8 for i in range(len(gen))]
        assert gen.tolist() == want, (p, gen.tolist(), want)
        np.testing.assert_array_equal(full[:p], pattern[:p])
    assert after("prefills_total") - before("prefills_total") == 4
    assert after("responses_total") - before("responses_total") == 4
    # 4 sequences over 2 slots: decode must have run at high occupancy
    steps = after("decode_steps") - before("decode_steps")
    tokens = after("tokens_total") - before("tokens_total")
    assert tokens >= sum(m - 1 for _p, m in jobs)
    assert tokens / (steps * eng.config.max_slots) > 0.5


@pytest.mark.slow  # tier-1 wall-clock relief (ISSUE-5): run in full by tools/ci.sh's serving gate
def test_generation_matches_model_generate(gen_engine):
    """Slot decode must reproduce the model's own KV-cached greedy path."""
    eng, model, pattern = gen_engine
    prompt = pattern[:13].astype("int64")
    ref = np.asarray(model.generate(paddle.to_tensor(prompt[None]),
                                    max_new_tokens=6,
                                    use_cache=True).numpy())[0]
    got = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
    assert got.tolist() == ref.tolist()


@pytest.mark.slow  # tier-1 wall-clock relief (ISSUE-5): run in full by tools/ci.sh's serving gate
def test_generation_bad_prompt_isolated(gen_engine):
    eng, _model, pattern = gen_engine
    bad_shape = eng.submit(pattern[:6].reshape(2, 3), max_new_tokens=2)
    # past max_seq_len 48 (a prompt past the largest bucket alone is
    # prefilled in chunks: tests/test_openpangu_moe.py)
    too_long = eng.submit(np.zeros(50, np.int64), max_new_tokens=2)
    # prompt fits a prefill bucket but prompt+max_new_tokens overruns the
    # slot arena: reject instead of silently truncating the continuation
    overrun = eng.submit(pattern[:16].astype("int64"), max_new_tokens=64)
    good = eng.submit(pattern[:9].astype("int64"), max_new_tokens=3)
    with pytest.raises(serving.BadRequest):
        bad_shape.result(timeout=30)
    with pytest.raises(serving.BadRequest):
        too_long.result(timeout=30)
    with pytest.raises(serving.BadRequest, match="max_seq_len"):
        overrun.result(timeout=30)
    out = good.result(timeout=300)
    assert len(out) == 9 + 3
    assert out[9:].tolist() == [(9 + i) % 8 for i in range(len(out) - 9)]


# -- chaos (ISSUE-6 fault-injection harness against the engines) --------------

def test_serving_queue_drains_after_repeated_batch_faults():
    """Repeated injected batch faults: every faulted batch fails ONLY its
    own futures, later traffic still serves, and the queue depth drains to
    zero — no leaked futures, no dead worker."""
    from paddle_tpu.distributed.resilience.faults import InjectedFault, injector

    eng = _slow_engine(delay_s=0.0, max_batch_wait_ms=0.0)
    eng.start()
    x = np.zeros(4, np.float32)
    inj = injector()
    # batches 0, 2 and 4 die; everything else executes
    rules = [inj.arm("batch_fault", engine=eng.name, batch=b)
             for b in (0, 2, 4)]
    try:
        futs = [eng.submit([x]) for _ in range(16)]
        done = fwait(futs, timeout=60)
        assert not done.not_done, "leaked futures after injected faults"
        failed = [f for f in futs if f.exception() is not None]
        ok = [f for f in futs if f.exception() is None]
        assert failed and ok, (len(failed), len(ok))
        for f in failed:
            assert isinstance(f.exception(), InjectedFault)
        t0 = time.monotonic()
        while eng.queue_depth() > 0 and time.monotonic() - t0 < 10:
            time.sleep(0.005)
        assert eng.queue_depth() == 0
        assert eng.metrics.counter("batch_failures") == 3
        # the engine still serves after the chaos
        eng.submit([x]).result(timeout=30)
    finally:
        for r in rules:
            inj.disarm(r)
        eng.close()


@pytest.mark.slow  # shared decode executable: run in full by tools/ci.sh's serving gate
def test_generation_decode_fault_releases_slots(gen_engine):
    """A decode-batch fault mid-flight fails exactly the in-flight
    requests, releases their slots, and the next prompt decodes clean."""
    from paddle_tpu.distributed.resilience.faults import InjectedFault, injector

    eng, _model, pattern = gen_engine
    inj = injector()
    rule = inj.arm("decode_fault", engine=eng.name)  # next decode step dies
    try:
        doomed = [eng.submit(pattern[:9].astype("int64"), max_new_tokens=4),
                  eng.submit(pattern[:11].astype("int64"), max_new_tokens=4)]
        for f in doomed:
            with pytest.raises(InjectedFault):
                f.result(timeout=300)
    finally:
        inj.disarm(rule)
    t0 = time.monotonic()
    while eng.stats()["active_slots"] and time.monotonic() - t0 < 30:
        time.sleep(0.01)
    assert eng.stats()["active_slots"] == 0  # slots released, not leaked
    out = eng.submit(pattern[:9].astype("int64"),
                     max_new_tokens=3).result(timeout=300)
    assert out[9:].tolist() == [(9 + i) % 8 for i in range(len(out) - 9)]
