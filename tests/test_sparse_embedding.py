"""ISSUE-14: giant streamed embedding tables — host-sharded canonical
storage, device hot-row cache (ghost-counter admission + LRU eviction),
StreamLane miss streaming with cross-step prefetch, host-side sparse row
updates, the nn.Embedding(sparse=True) front end, the F.embedding OOV
policy, the ServingEngine lookup path, and the planner term."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework import flags as flags_mod
from paddle_tpu.optimizer import SGD
from paddle_tpu.optimizer.sparse import (SparseRowAdagrad, SparseRowAdam,
                                         SparseRowSGD, make_row_rule)
from paddle_tpu.sparse import (HotRowCache, LocalShards,
                               ShardedEmbeddingTable, zipf_ids)


# ---------------------------------------------------------------------------
# storage + rules
# ---------------------------------------------------------------------------

def test_local_shards_init_deterministic_across_shard_counts():
    ids = np.arange(101)
    one = LocalShards(101, 6, n_shards=1, seed=9)
    for n in (2, 3, 7):
        many = LocalShards(101, 6, n_shards=n, seed=9)
        np.testing.assert_array_equal(one.gather(ids), many.gather(ids))


def test_sparse_row_rules_match_dense_math():
    rows = np.ones((3, 4), np.float32)
    g = np.full((3, 4), 0.5, np.float32)

    sgd = SparseRowSGD(lr=0.1)
    out, _ = sgd.apply(rows.copy(), g, {})
    np.testing.assert_allclose(out, 1.0 - 0.1 * 0.5)

    ada = SparseRowAdagrad(lr=0.1, epsilon=1e-6)
    st = ada.init_state(3, 4)
    out, st2 = ada.apply(rows.copy(), g, {k: v for k, v in st.items()})
    m = g * g
    np.testing.assert_allclose(st2["moment"], m)
    np.testing.assert_allclose(out, 1.0 - 0.1 * 0.5 / (np.sqrt(m) + 1e-6))

    adam = SparseRowAdam(lr=0.1)
    st = adam.init_state(3, 4)
    out, st2 = adam.apply(rows.copy(), g, st)
    # lazy per-row step count advanced exactly once
    np.testing.assert_allclose(st2["count"], 1.0)
    with pytest.raises(ValueError):
        make_row_rule("nope")


def test_shard_apply_updates_only_touched_rows():
    src = LocalShards(50, 3, n_shards=4, seed=1)
    before = src.gather(np.arange(50))
    ids = np.array([3, 17, 40])
    g = np.ones((3, 3), np.float32)
    new = src.apply(ids, g, SparseRowSGD(lr=0.5))
    after = src.gather(np.arange(50))
    np.testing.assert_allclose(new, before[ids] - 0.5)
    np.testing.assert_allclose(after[ids], before[ids] - 0.5)
    untouched = np.setdiff1d(np.arange(50), ids)
    np.testing.assert_array_equal(after[untouched], before[untouched])


# ---------------------------------------------------------------------------
# hot-row cache policy
# ---------------------------------------------------------------------------

def test_admission_threshold_and_lru_eviction_deterministic():
    c = HotRowCache(capacity=2, dim=2, admit_threshold=2)
    rows = np.zeros((1, 2), np.float32)

    def access(i):
        ids = np.array([i])
        c.note_access(ids)
        hit, _ = c.slots_of(ids)
        adm = c.admittable(ids[~hit])
        if adm:
            c.admit(adm, rows, pinned={i})
        c.touch(ids[hit])
        return bool(hit[0])

    assert access(7) is False          # first sight: ghost=1, not admitted
    assert access(7) is False          # ghost=2 -> admitted DURING this miss
    assert access(7) is True           # now cached
    access(8), access(8)               # 8 admitted
    assert len(c) == 2
    access(7)                          # 7 most-recent
    access(9), access(9)               # admit 9 -> LRU victim is 8
    assert c.slots_of(np.array([8]))[0][0] == np.False_
    assert c.slots_of(np.array([7]))[0][0] == np.True_
    assert c.evictions == 1


def test_pinned_rows_never_evicted():
    c = HotRowCache(capacity=1, dim=2, admit_threshold=1)
    c.admit([1], np.zeros((1, 2), np.float32))
    # capacity full, the only resident row is pinned: admission skipped
    assert c.admit([2], np.zeros((1, 2), np.float32), pinned={1}) == 0
    assert c.slots_of(np.array([1]))[0][0] == np.True_


def test_ghost_counter_aging_bounded():
    c = HotRowCache(capacity=1, dim=1, admit_threshold=10, ghost_cap=4)
    for i in range(8):
        c.note_access(np.array([i]))
    assert len(c._ghost) <= 4  # aged: halved + zeros dropped


def test_zipf_hit_rate_deterministic_and_pinned():
    def run():
        ids = zipf_ids(256 * 30, 4000, a=2.0, seed=3)
        batches = ids.reshape(30, 256)
        c = HotRowCache(capacity=500, dim=1, admit_threshold=2)
        hits = miss = 0
        for i, b in enumerate(batches):
            uniq = np.unique(b)
            c.note_access(uniq)
            h, _ = c.slots_of(uniq)
            if i >= 10:  # past warmup
                hits += int(h.sum())
                miss += int((~h).sum())
            adm = c.admittable(uniq[~h])
            if adm:
                c.admit(adm, np.zeros((len(adm), 1), np.float32),
                        pinned=set(int(r) for r in uniq))
            c.touch(uniq[h])
        return hits / (hits + miss)

    r1, r2 = run(), run()
    assert r1 == r2                    # seeded stream -> pinned policy
    assert r1 >= 0.8


# ---------------------------------------------------------------------------
# training lookup: values, grads, parity
# ---------------------------------------------------------------------------

def test_lookup_values_and_sparse_adagrad_update():
    paddle.seed(0)
    t = ShardedEmbeddingTable(100, 4, cache_rows=16, n_shards=3,
                              rule="adagrad", lr=0.1, seed=5)
    ids = np.array([[1, 2], [2, 7]], np.int64)
    before = t.source.gather(np.array([1, 2, 7]))
    out = t.lookup(paddle.to_tensor(ids))
    assert out.shape == [2, 2, 4]
    np.testing.assert_array_equal(out.numpy()[0, 0], before[0])
    np.testing.assert_array_equal(out.numpy()[1, 0], before[1])
    loss = (out * out).sum()
    loss.backward()
    assert t.flush(update=True) == 3
    after = t.source.gather(np.array([1, 2, 7]))
    # duplicate id 2 accumulates: grad = 2*row per occurrence, x2
    for k, (rid, mult) in enumerate([(1, 1.0), (2, 2.0), (7, 1.0)]):
        g = 2.0 * before[k] * mult
        m = g * g
        exp = before[k] - 0.1 * g / (np.sqrt(m) + 1e-6)
        np.testing.assert_allclose(after[k], exp, rtol=1e-6)


def test_out_of_range_lookup_raises():
    t = ShardedEmbeddingTable(10, 2, cache_rows=4)
    with pytest.raises(ValueError):
        t.lookup(np.array([3, 10]))


def _train(cache_rows, *, rows=120, prefetch=False, accum=1, steps=8,
           early_prefetch=False):
    paddle.seed(0)
    t = ShardedEmbeddingTable(rows, 4, cache_rows=cache_rows, n_shards=2,
                              rule="adagrad", lr=0.1, seed=11)
    tower = nn.Linear(4, 1)
    opt = SGD(learning_rate=0.05, parameters=tower.parameters())
    rng = np.random.RandomState(2)
    stream = [rng.randint(0, rows, (16,)).astype(np.int64)
              for _ in range(steps)]
    losses = []
    for i, ids in enumerate(stream):
        out = t.lookup(ids)
        if early_prefetch and i + 1 < steps:
            t.prefetch(stream[i + 1])   # BEFORE this step's update lands
        logit = tower(out)
        loss = (logit * logit).mean()
        losses.append(float(loss.numpy()))
        loss.backward()
        upd = (i + 1) % accum == 0
        t.flush(update=upd)
        if upd:
            opt.step()
            opt.clear_grad()
        if prefetch and not early_prefetch and i + 1 < steps:
            t.prefetch(stream[i + 1])
    return losses, t


def test_streamed_bit_equal_to_all_resident():
    ref, _ = _train(120)               # cache holds every row
    got, t = _train(16)                # streamed small cache
    assert ref == got                  # BIT-equal losses
    assert t.stats()["miss_rows"] > 0  # it really streamed


def test_streamed_bit_equal_under_accumulate_k():
    ref, _ = _train(120, accum=2)
    got, _ = _train(16, accum=2)
    assert ref == got


def test_prefetch_overlap_bit_equal_and_stale_refetch():
    ref, _ = _train(120)
    got, t = _train(16, early_prefetch=True)
    assert ref == got
    s = t.stats()
    assert s["prefetch_hits"] > 0
    # updates landed between prefetch and consume -> rows were re-fetched
    assert s["prefetch_stale_rows"] > 0


def test_clear_pending_drops_the_window():
    _, t = _train(16, steps=2)
    out = t.lookup(np.array([1, 2, 3]))
    (out * out).sum().backward()
    t.clear_pending()
    assert t.flush(update=True) == 0   # nothing survived the drop


# ---------------------------------------------------------------------------
# F.embedding OOV policy + padding_idx regression
# ---------------------------------------------------------------------------

def test_embedding_oov_error_by_default():
    w = paddle.randn([8, 3])
    ids = paddle.to_tensor(np.array([1, 9], np.int64))
    with pytest.raises(ValueError, match="out of range"):
        F.embedding(ids, w)
    with pytest.raises(ValueError, match="out of range"):
        F.embedding(paddle.to_tensor(np.array([-1, 2], np.int64)), w)


def test_embedding_oov_clip_opt_in_matches_legacy():
    w = paddle.randn([8, 3])
    ids = paddle.to_tensor(np.array([1, 9], np.int64))
    out = F.embedding(ids, w, oov_policy="clip")
    np.testing.assert_array_equal(out.numpy()[1], w.numpy()[7])
    flags_mod.set_flags({"FLAGS_embedding_oov_policy": "clip"})
    try:
        out2 = F.embedding(ids, w)
        np.testing.assert_array_equal(out2.numpy(), out.numpy())
    finally:
        flags_mod.set_flags({"FLAGS_embedding_oov_policy": "error"})
    with pytest.raises(ValueError, match="oov_policy"):
        F.embedding(ids, w, oov_policy="wat")


def test_padding_idx_zero_gradient_regression():
    # dense path: the padding row's output is zeroed AND receives no grad
    emb = nn.Embedding(6, 3, padding_idx=2)
    ids = paddle.to_tensor(np.array([[2, 1], [3, 2]], np.int64))
    out = emb(ids)
    assert np.allclose(out.numpy()[0, 0], 0.0)
    out.sum().backward()
    g = emb.weight.grad.numpy()
    assert np.allclose(g[2], 0.0)
    assert not np.allclose(g[1], 0.0)
    # sparse-table path: the padding row is zeroed in the output and its
    # canonical host row is NOT updated by the flush
    t = ShardedEmbeddingTable(50, 3, cache_rows=8, rule="sgd", lr=0.5,
                              seed=4)
    layer = nn.Embedding(50, 3, padding_idx=2, sparse=True, sparse_table=t)
    before = t.source.gather(np.array([2]))
    out = layer(paddle.to_tensor(ids))
    assert np.allclose(out.numpy()[0, 0], 0.0)
    out.sum().backward()
    t.flush(update=True)
    np.testing.assert_array_equal(t.source.gather(np.array([2])), before)


# ---------------------------------------------------------------------------
# nn.Embedding(sparse=True) routing + hapi fit
# ---------------------------------------------------------------------------

def test_sparse_routing_dense_fallback_and_table_mode():
    small = nn.Embedding(64, 4, sparse=True)   # below min_rows: dense
    assert small._table is None
    assert small.weight is not None
    flags_mod.set_flags({"FLAGS_sparse_embedding_min_rows": 128})
    try:
        big = nn.Embedding(256, 4, sparse=True)
        assert big._table is not None
        assert big.weight is None              # no dense Parameter
        assert [p for p in big.parameters() if p is not None] == []
    finally:
        flags_mod.set_flags({"FLAGS_sparse_embedding_min_rows": 16384})
    with pytest.raises(ValueError, match="sparse_table shape"):
        nn.Embedding(10, 3, sparse_table=ShardedEmbeddingTable(9, 3))


class _RecNet(nn.Layer):
    def __init__(self, table):
        super().__init__()
        self.emb = nn.Embedding(table.num_rows, table.dim, sparse=True,
                                sparse_table=table)
        self.fc = nn.Linear(table.dim, 1)

    def forward(self, ids):
        return self.fc(self.emb(ids).mean(axis=1))


def _fit_losses(cache_rows, accum=1):
    paddle.seed(0)
    t = ShardedEmbeddingTable(300, 4, cache_rows=cache_rows, rule="adagrad",
                              lr=0.1, seed=13)
    net = _RecNet(t)
    model = paddle.Model(net)
    opt = SGD(learning_rate=0.05, parameters=net.fc.parameters())
    model.prepare(optimizer=opt, loss=lambda pred, y: ((pred - y) ** 2).mean())
    rng = np.random.RandomState(7)
    batches = [(rng.randint(0, 300, (8, 4)).astype(np.int64),
                rng.randn(8, 1).astype(np.float32)) for _ in range(6)]
    losses = []
    for i, (ids, y) in enumerate(batches):
        upd = (i + 1) % accum == 0
        out = model.train_batch([ids], [y], update=upd,
                                _loss_scale=1.0 / accum)
        losses.append(out[0])
    return losses, t


def test_hapi_train_batch_flushes_sparse_grads():
    ref, _ = _fit_losses(300)
    got, t = _fit_losses(32)
    assert ref == got
    assert t.stats()["updates"] == 6


def test_hapi_accumulate_window_applies_at_boundary():
    ref, _ = _fit_losses(300, accum=2)
    got, t = _fit_losses(32, accum=2)
    assert ref == got
    assert t.stats()["updates"] == 3   # one apply per window


def test_hapi_fit_end_to_end_with_sparse_table():
    paddle.seed(0)
    t = ShardedEmbeddingTable(300, 4, cache_rows=32, rule="adagrad",
                              lr=0.1, seed=13)
    net = _RecNet(t)
    model = paddle.Model(net)
    opt = SGD(learning_rate=0.05, parameters=net.fc.parameters())
    model.prepare(optimizer=opt, loss=lambda p, y: ((p - y) ** 2).mean())
    rng = np.random.RandomState(7)
    data = [(rng.randint(0, 300, (4,)).astype(np.int64),
             rng.randn(1).astype(np.float32)) for _ in range(16)]
    model.fit(data, batch_size=4, epochs=1, verbose=0, shuffle=False)
    assert t.stats()["updates"] >= 4


# ---------------------------------------------------------------------------
# serving path
# ---------------------------------------------------------------------------

def test_serving_lookup_zero_retrace_and_parity():
    from paddle_tpu import analysis as A
    from paddle_tpu.serving import BucketSpec, ServingEngine

    paddle.seed(0)
    t = ShardedEmbeddingTable(2000, 8, cache_rows=128, seed=3)
    # warm the hot set a little (training-side traffic)
    for i in range(2):
        t.lookup(zipf_ids(64, 2000, a=1.6, seed=i))
        t.clear_pending()
    A.retrace.enable()
    try:
        eng = ServingEngine(t.serving_target(),
                            buckets=BucketSpec((1, 2), seq_lens=(8,)),
                            input_specs=[((None,), "int64")],
                            name="embed_t")
        eng.start()
        warm = len(t._serve_fns)
        futs = [eng.submit([np.arange(i, i + 6, dtype=np.int64)])
                for i in range(8)]
        outs = [f.result()[0] for f in futs]
        for i, o in enumerate(outs):
            ids = np.arange(i, i + 6, dtype=np.int64)
            np.testing.assert_array_equal(o[:6], t.source.gather(ids))
        st = eng.stats()
        assert st["retrace_events"] == 0
        assert len(t._serve_fns) == warm   # zero fresh executables warm
        eng.close()
    finally:
        A.retrace.disable()
        A.retrace.reset()


def test_router_routes_lookup_by_cache_affinity():
    from paddle_tpu.serving import BucketSpec, ServingEngine
    from paddle_tpu.serving.router import ReplicaRouter, RouterConfig
    from paddle_tpu.sparse import LookupReplica

    paddle.seed(0)
    hot_a = np.arange(0, 6, dtype=np.int64)
    hot_b = np.arange(500, 506, dtype=np.int64)
    reps = []
    for name, hot in (("emb_a", hot_a), ("emb_b", hot_b)):
        t = ShardedEmbeddingTable(1000, 4, cache_rows=32, seed=6,
                                  admit_threshold=1, name=name)
        t.lookup(hot)              # warm THIS replica's hot set
        t.clear_pending()
        eng = ServingEngine(t.serving_target(),
                            buckets=BucketSpec((1,), seq_lens=(6,)),
                            input_specs=[((None,), "int64")], name=name)
        reps.append(LookupReplica(eng, t))
    router = ReplicaRouter(reps, RouterConfig(w_affinity=5.0)).start()
    try:
        fut = router.submit(hot_b)         # ids hot on replica B
        out = fut.result()[0]
        np.testing.assert_array_equal(out[:6],
                                      reps[1].table.source.gather(hot_b))
        st = router.stats()
        assert st["replicas"]["emb_b"]["routed"] == 1  # affinity -> B
        assert st["replicas"]["emb_a"]["routed"] == 0
        assert st["affinity_hits"] == 1
    finally:
        router.close()


def test_serve_lookup_read_through_no_admission():
    t = ShardedEmbeddingTable(100, 4, cache_rows=8, admit_threshold=1)
    before = len(t.cache)
    out = t.serve_lookup(np.array([[1, 2, 3]], np.int64), miss_caps=8)
    assert out.shape == (1, 3, 4)
    assert len(t.cache) == before      # serving never admits
    assert t.stats()["serve_miss_rows"] == 3
    # the cap is picked under the lock from the ACTUAL miss split: the
    # smallest fitting bucket of a declared family
    out2 = t.serve_lookup(np.array([[4, 5]], np.int64), miss_caps=(1, 2, 8))
    assert out2.shape == (1, 2, 4)
    with pytest.raises(ValueError, match="exceed the largest"):
        t.serve_lookup(np.array([[6, 7, 8]], np.int64), miss_caps=(1,))


def test_traced_lookup_raises_instead_of_baking_zeros():
    from paddle_tpu.sparse.embedding import abstract_zero_lookups
    import jax
    import jax.numpy as jnp

    t = ShardedEmbeddingTable(100, 4, cache_rows=8)

    def f(ids):
        return t.lookup(ids).data.sum()

    with pytest.raises(NotImplementedError, match="cannot be traced"):
        jax.make_jaxpr(f)(jnp.zeros((3,), jnp.int32))
    with abstract_zero_lookups():      # the planner's sanctioned capture
        jax.make_jaxpr(f)(jnp.zeros((3,), jnp.int32))


def test_model_load_warns_on_missing_table_checkpoint():
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        paddle.seed(0)
        t = ShardedEmbeddingTable(300, 4, cache_rows=32, name="missing_t")
        net = _RecNet(t)
        model = paddle.Model(net)
        model.prepare(optimizer=SGD(learning_rate=0.05,
                                    parameters=net.fc.parameters()),
                      loss=lambda p, y: ((p - y) ** 2).mean())
        model.save(d + "/m")
        import os
        os.remove(d + "/m.sparse.missing_t.npz")
        with pytest.warns(RuntimeWarning, match="no sparse-table checkpoint"):
            model.load(d + "/m")


def test_serve_lookup_does_not_mutate_caller_ids():
    t = ShardedEmbeddingTable(10, 2, cache_rows=4)
    ids = np.array([[1, 99]], np.int64)   # 99 out of range -> clamped
    t.serve_lookup(ids, miss_caps=4)
    np.testing.assert_array_equal(ids, [[1, 99]])  # caller array intact


def test_explicit_miss_caps_always_cover_worst_case():
    t = ShardedEmbeddingTable(100, 2, cache_rows=4)
    tgt = t.serving_target(miss_caps=[8])
    assert tgt.caps_for(32) == (8, 32)    # terminal cap = every-id-cold
    runner = tgt.build_serving_runner(1, (("int64", (32,)),))
    out = runner([np.arange(32, dtype=np.int64).reshape(1, 32)])
    assert out[0].shape == (1, 32, 2)     # 32 cold misses still served


def test_table_save_load_roundtrip():
    import tempfile

    def steps(t, n, seed):
        rng = np.random.RandomState(seed)
        for _ in range(n):
            out = t.lookup(rng.randint(0, 80, (12,)).astype(np.int64))
            (out * out).sum().backward()
            t.flush(update=True)

    with tempfile.TemporaryDirectory() as d:
        a = ShardedEmbeddingTable(80, 3, cache_rows=16, n_shards=2,
                                  rule="adagrad", lr=0.1, seed=6)
        steps(a, 4, seed=1)
        path = a.save(d + "/tbl")
        steps(a, 3, seed=2)                 # diverge after the save
        b = ShardedEmbeddingTable(80, 3, cache_rows=16, n_shards=2,
                                  rule="adagrad", lr=0.1, seed=99)
        b.load(path)
        steps(b, 3, seed=2)                 # replay the post-save steps
        np.testing.assert_array_equal(a.source.gather(np.arange(80)),
                                      b.source.gather(np.arange(80)))
        # rule state (Adagrad moments) restored too — bit-equal shards
        wrong = ShardedEmbeddingTable(81, 3, cache_rows=16, n_shards=2)
        with pytest.raises(ValueError, match="checkpoint shape"):
            wrong.load(path)


def test_model_save_load_carries_sparse_table():
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        paddle.seed(0)
        t = ShardedEmbeddingTable(300, 4, cache_rows=32, rule="adagrad",
                                  lr=0.1, seed=13, name="ckpt_t")
        net = _RecNet(t)
        model = paddle.Model(net)
        opt = SGD(learning_rate=0.05, parameters=net.fc.parameters())
        model.prepare(optimizer=opt,
                      loss=lambda p, y: ((p - y) ** 2).mean())
        rng = np.random.RandomState(7)
        for _ in range(3):
            model.train_batch([rng.randint(0, 300, (8, 4)).astype(np.int64)],
                              [rng.randn(8, 1).astype(np.float32)])
        model.save(d + "/m")
        trained = t.source.gather(np.arange(300))
        # a fresh model restores BOTH the tower and the table rows
        paddle.seed(1)
        t2 = ShardedEmbeddingTable(300, 4, cache_rows=32, rule="adagrad",
                                   lr=0.1, seed=77, name="ckpt_t")
        net2 = _RecNet(t2)
        model2 = paddle.Model(net2)
        model2.prepare(optimizer=SGD(learning_rate=0.05,
                                     parameters=net2.fc.parameters()),
                       loss=lambda p, y: ((p - y) ** 2).mean())
        model2.load(d + "/m")
        np.testing.assert_array_equal(t2.source.gather(np.arange(300)),
                                      trained)


def test_oov_error_checks_plain_python_lists():
    w = paddle.randn([8, 3])
    with pytest.raises(ValueError, match="out of range"):
        F.embedding([1, 10 ** 9], w)


# ---------------------------------------------------------------------------
# ps wiring
# ---------------------------------------------------------------------------

def test_ps_shard_source_parity_with_local():
    from paddle_tpu.distributed.ps import (ParameterServer, PsShardSource,
                                           PsTrainer)
    from paddle_tpu.distributed.store import TCPStore

    store = TCPStore(is_master=True, world_size=1)
    try:
        servers = [ParameterServer(store, server_id=i, n_servers=2)
                   .create_table("emb", (60, 4), lr=0.1, seed=21).run()
                   for i in range(2)]
        trainer = PsTrainer(store, n_servers=2)
        src = PsShardSource(trainer, "emb", 60, 4)
        t_ps = ShardedEmbeddingTable(60, 4, cache_rows=16, source=src,
                                     rule="sgd", lr=0.1)
        t_local = ShardedEmbeddingTable(60, 4, cache_rows=16, n_shards=2,
                                        rule="sgd", lr=0.1, seed=21)
        ids = np.array([1, 5, 33, 59], np.int64)
        np.testing.assert_array_equal(t_ps.lookup(ids).numpy(),
                                      t_local.lookup(ids).numpy())
        for t in (t_ps, t_local):
            out = t.lookup(ids)
            (out * out).sum().backward()
            t.flush(update=True)
        # the server-side SGD (lr from create_table) matches the local
        # SparseRowSGD rule bit-for-bit
        np.testing.assert_array_equal(t_ps.source.gather(ids),
                                      t_local.source.gather(ids))
        for s in servers:
            s.stop()
    finally:
        store.close()


# ---------------------------------------------------------------------------
# planner + observability + lane API
# ---------------------------------------------------------------------------

def test_planner_prices_embedding_stream():
    from paddle_tpu.distributed.auto_parallel.planner import (profile_model,
                                                              score_config)

    paddle.seed(0)
    t = ShardedEmbeddingTable(5000, 8, cache_rows=64, seed=1)
    net = _RecNet(t)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 5000, (8, 4)).astype(np.int64))
    prof = profile_model(net, sample_batch=[ids],
                         loss_fn=lambda m, x: m(x).sum())
    assert prof.embed_stream_bytes > 0
    cand = score_config(prof, {"dp": 1}, hbm_bytes=16e9)
    assert cand.breakdown.get("embed_stream_s", 0) > 0
    # a dense model carries no embedding term
    dense = nn.Linear(4, 4)
    x = paddle.randn([4, 4])
    prof_d = profile_model(dense, sample_batch=[x],
                           loss_fn=lambda m, a: m(a).sum())
    assert prof_d.embed_stream_bytes == 0
    cand_d = score_config(prof_d, {"dp": 1}, hbm_bytes=16e9)
    assert "embed_stream_s" not in cand_d.breakdown


def test_observability_family_and_memory_component():
    from paddle_tpu import observability as obs
    from paddle_tpu.observability.exposition import render_snapshot
    from paddle_tpu.observability.memory import memory_monitor

    t = ShardedEmbeddingTable(500, 4, cache_rows=32, name="obs_t",
                              admit_threshold=1)
    out = t.lookup(np.array([1, 2, 3], np.int64))
    (out * out).sum().backward()
    t.flush(update=True)
    snap = obs.snapshot()
    vals = snap["embedding_stream"].get("values", snap["embedding_stream"])
    assert vals.get("lookups", 0) >= 1
    txt = render_snapshot(snap)
    assert "embedding_stream" in txt and "hit_rate" in txt
    comps = memory_monitor().snapshot().get("components", {})
    assert comps.get("sparse:obs_t:hot_cache", 0) == t.cache_bytes()


def test_lane_row_stream_api():
    from paddle_tpu.jit.offload_stream import StreamLane

    lane = StreamLane(overlap=True)
    rows = np.random.RandomState(0).randn(16, 8).astype(np.float32)
    h = lane.submit_rows(rows, tag=("rows", 0))
    np.testing.assert_array_equal(np.asarray(h.rows()), rows)
    s = lane.stats()
    assert s["h2d_bytes"] == rows.nbytes
    lane.close()
