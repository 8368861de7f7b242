"""PS hardening: multi-server sharding, dense tables, async communicator,
and the 2-server/2-trainer gang e2e (reference the_one_ps.py:796 topology,
brpc_ps_client.h fan-out)."""
import json
import os
import socket
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.distributed.ps import (AsyncCommunicator, ParameterServer,
                                       PsTrainer, SparseEmbedding)


@pytest.fixture
def store():
    s = TCPStore(is_master=True, world_size=1)
    yield s
    s.close()


class TestMultiServer:
    def test_sharded_pull_matches_full_init(self, store):
        servers = [ParameterServer(store, server_id=i, n_servers=2)
                   .create_table("t", (40, 8), lr=0.1, seed=3).run()
                   for i in range(2)]
        trainer = PsTrainer(store, n_servers=2)
        full = (np.random.RandomState(3).randn(40, 8) * 0.01).astype("float32")
        ids = np.array([0, 1, 5, 17, 38, 39])
        rows = trainer.pull("t", ids)
        np.testing.assert_allclose(rows, full[ids], rtol=1e-6)
        for s in servers:
            s.stop()

    def test_sharded_push_updates_owners(self, store):
        servers = [ParameterServer(store, server_id=i, n_servers=2)
                   .create_table("t", (10, 4), lr=1.0, init_std=0.0).run()
                   for i in range(2)]
        trainer = PsTrainer(store, n_servers=2)
        ids = np.array([2, 3, 7])
        g = np.ones((3, 4), "float32")
        trainer.push("t", ids, g, wait=True)
        rows = trainer.pull("t", ids)
        np.testing.assert_allclose(rows, -np.ones((3, 4)), rtol=1e-6)
        untouched = trainer.pull("t", np.array([0, 1]))
        np.testing.assert_allclose(untouched, 0.0)
        for s in servers:
            s.stop()

    def test_dense_table_roundtrip(self, store):
        w0 = np.arange(12, dtype="float32").reshape(3, 4)
        servers = [ParameterServer(store, server_id=i, n_servers=2)
                   .create_dense_table("w", w0, lr=0.5).run()
                   for i in range(2)]
        trainer = PsTrainer(store, n_servers=2)
        np.testing.assert_allclose(trainer.pull_dense("w"), w0)
        g = np.ones_like(w0)
        trainer.push_dense("w", g, wait=True)
        np.testing.assert_allclose(trainer.pull_dense("w"), w0 - 0.5)
        for s in servers:
            s.stop()

    def test_async_communicator_applies_and_flushes(self, store):
        server = ParameterServer(store, server_id=0, n_servers=1) \
            .create_table("t", (6, 2), lr=1.0, init_std=0.0).run()
        trainer = PsTrainer(store, n_servers=1)
        comm = AsyncCommunicator(trainer, max_queue=4)
        emb = SparseEmbedding(trainer, "t", 2, communicator=comm)
        out = emb(np.array([[1, 2]]))
        emb.push_grad(np.ones((1, 2, 2), "float32"))
        comm.flush()
        rows = trainer.pull("t", np.array([1, 2]))
        np.testing.assert_allclose(rows, -np.ones((2, 2)))
        comm.stop()
        server.stop()


_PS_WORKER = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.distributed.ps import ParameterServer, PsTrainer

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    endpoint = os.environ["PS_ENDPOINT"]
    work = sys.argv[1]
    host, port = endpoint.rsplit(":", 1)
    N_SRV, N_TRN, STEPS, LR = 2, 2, 4, 0.05
    B, F, D, ROWS = 8, 3, 4, 30

    store = TCPStore(host=host, port=int(port), world_size=N_SRV + N_TRN)
    rng = np.random.RandomState(7)
    ids_full = rng.randint(0, ROWS, (B, F))
    y_full = rng.rand(B).astype("float32")
    w_init = (np.arange(D, dtype="float32") + 1.0) * 0.1

    if rank < N_SRV:  # server role
        ps = ParameterServer(store, server_id=rank, n_servers=N_SRV)
        ps.create_table("emb", (ROWS, D), lr=LR, seed=11)
        ps.create_dense_table("w", w_init, lr=LR)
        ps.run()
        store.wait(["ps/shutdown"])
        ps.stop()
        sys.exit(0)

    # trainer role: half the batch each, sum-loss so grads add like 1-proc
    tid = rank - N_SRV
    # barriers rendezvous the TRAINER gang only -> world_size counts trainers
    store = TCPStore(host=host, port=int(port), world_size=N_TRN)
    trainer = PsTrainer(store, n_servers=N_SRV)
    sl = slice(tid * B // N_TRN, (tid + 1) * B // N_TRN)
    ids, y = ids_full[sl], y_full[sl]
    for step in range(STEPS):
        store.barrier(f"step{step}a")
        w = trainer.pull_dense("w")
        uniq, inv = np.unique(ids.ravel(), return_inverse=True)
        rows = trainer.pull("emb", uniq)
        # a synchronous step: every trainer has pulled before any pushes —
        # without this rendezvous a trainer descheduled between the first
        # barrier and its pull reads the other's update (the parity below
        # then fails by 0.6 %: 3 of 24 runs with six of them at once)
        store.barrier(f"step{step}pulled")
        e = rows[inv].reshape(len(y), F, D)
        s = e.sum(1)
        pred = s @ w
        dpred = 2.0 * (pred - y)
        dw = s.T @ dpred
        ds = np.outer(dpred, w)
        de = np.repeat(ds[:, None, :], F, axis=1).reshape(-1, D)
        acc = np.zeros((len(uniq), D), "float32")
        np.add.at(acc, inv, de)
        trainer.push("emb", uniq, acc, wait=True)
        trainer.push_dense("w", dw, wait=True)
        store.barrier(f"step{step}b")
    if tid == 0:
        w = trainer.pull_dense("w")
        uniq, inv = np.unique(ids_full.ravel(), return_inverse=True)
        rows = trainer.pull("emb", uniq)
        e = rows[inv].reshape(B, F, D)
        loss = float(np.sum((e.sum(1) @ w - y_full) ** 2))
        with open(os.path.join(work, "result.json"), "w") as f:
            json.dump({"loss": loss, "w": w.tolist()}, f)
        store.set("ps/shutdown", b"1")
""")


@pytest.mark.dist
def test_two_server_two_trainer_parity(tmp_path):
    """Gang-spawned 2 servers + 2 trainers == single-process training."""
    from paddle_tpu.distributed.launch.process import ProcessContext

    script = tmp_path / "ps_worker.py"
    script.write_text(_PS_WORKER)
    master = TCPStore(is_master=True, world_size=1)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        ctx = ProcessContext.start(
            [sys.executable, str(script), str(tmp_path)], 4,
            base_env={"PS_ENDPOINT": f"127.0.0.1:{master.port}",
                      "PYTHONPATH": repo + os.pathsep +
                      os.environ.get("PYTHONPATH", "")},
            log_dir=str(tmp_path / "logs"))
        rc = ctx.wait(timeout=180)
        assert rc == 0, ctx.logs()
    finally:
        master.close()

    got = json.loads((tmp_path / "result.json").read_text())

    # single-process reference, identical math
    N_SRV, STEPS, LR = 2, 4, 0.05
    B, F, D, ROWS = 8, 3, 4, 30
    rng = np.random.RandomState(7)
    ids_full = rng.randint(0, ROWS, (B, F))
    y = rng.rand(B).astype("float32")
    table = (np.random.RandomState(11).randn(ROWS, D) * 0.01).astype("float32")
    w = (np.arange(D, dtype="float32") + 1.0) * 0.1
    for _ in range(STEPS):
        e = table[ids_full]
        s = e.sum(1)
        pred = s @ w
        dpred = 2.0 * (pred - y)
        dw = s.T @ dpred
        ds = np.outer(dpred, w)
        de = np.repeat(ds[:, None, :], F, axis=1).reshape(-1, D)
        np.subtract.at(table, ids_full.ravel(), LR * de)
        w = w - LR * dw
    e = table[ids_full]
    ref_loss = float(np.sum((e.sum(1) @ w - y) ** 2))

    np.testing.assert_allclose(got["w"], w, rtol=1e-4)
    np.testing.assert_allclose(got["loss"], ref_loss, rtol=1e-4)


class TestSpillTable:
    """VERDICT r4 next #9: disk-spill sparse table + accessor seam
    (reference ssd_sparse_table.h:21, ctr_accessor.cc)."""

    def test_spill_matches_in_ram_table(self, store, tmp_path):
        """Same seed, table larger than the hot tier: pulls and pushes must
        be byte-identical to the all-RAM table, and rows must actually
        spill to disk."""
        rows, dim = 400, 8
        sv_ram = ParameterServer(store, server_id=0, n_servers=1) \
            .create_table("ram", (rows, dim), lr=0.5, seed=9).run()
        # hot tier fits ~32 rows of a 400-row table
        sv_sp = ParameterServer(store, server_id=0, n_servers=1) \
            .create_table("sp", (rows, dim), lr=0.5, seed=9,
                          hot_bytes=32 * dim * 4,
                          spill_dir=str(tmp_path)).run()
        tr = PsTrainer(store, n_servers=1)
        rng = np.random.RandomState(0)
        for it in range(6):
            ids = rng.randint(0, rows, 64)
            g = rng.randn(64, dim).astype("float32")
            tr.push("ram", ids, g, wait=True)
            tr.push("sp", ids, g, wait=True)
        probe = rng.randint(0, rows, 128)
        np.testing.assert_allclose(tr.pull("sp", probe),
                                   tr.pull("ram", probe), rtol=1e-6)
        spill = sv_sp.tables["sp"]
        assert spill.spills > 0  # the cold tier was exercised
        assert len(spill._hot) <= spill.hot_budget_rows
        sv_ram.stop()
        sv_sp.stop()

    def test_ctr_accessor_slots_and_damping(self, store, tmp_path):
        from paddle_tpu.distributed.ps.spill_table import CtrAccessor

        rows, dim = 50, 4
        sv = ParameterServer(store, server_id=0, n_servers=1) \
            .create_table("ctr", (rows, dim), lr=1.0, init_std=0.0,
                          hot_bytes=1 << 20, spill_dir=str(tmp_path),
                          accessor=CtrAccessor()).run()
        tr = PsTrainer(store, n_servers=1)
        ids = np.array([3, 3, 7])  # duplicate id: shows accumulate
        g = np.ones((3, dim), "float32")
        tr.push("ctr", ids, g, wait=True)
        table = sv.tables["ctr"]
        meta3 = table._load(3)[1]
        meta7 = table._load(7)[1]
        assert meta3[0] == 2.0 and meta7[0] == 1.0  # show counts
        # damped update: -lr * 2g / sqrt(1+2) for row 3
        np.testing.assert_allclose(table.gather([3])[0],
                                   -2.0 / np.sqrt(3.0), rtol=1e-6)
        np.testing.assert_allclose(table.gather([7])[0],
                                   -1.0 / np.sqrt(2.0), rtol=1e-6)
        sv.stop()

    def test_spill_flush_persists_to_disk(self, store, tmp_path):
        rows, dim = 64, 4
        sv = ParameterServer(store, server_id=0, n_servers=1) \
            .create_table("f", (rows, dim), lr=1.0, init_std=0.0,
                          hot_bytes=8 * dim * 4,
                          spill_dir=str(tmp_path)).run()
        tr = PsTrainer(store, n_servers=1)
        tr.push("f", np.arange(32), np.ones((32, dim), "f4"), wait=True)
        table = sv.tables["f"]
        table.flush()
        mm = np.memmap(str(tmp_path / "ps_f_s0.bin"), dtype="float32",
                       mode="r", shape=(rows, dim))
        np.testing.assert_allclose(mm[:32], -1.0)
        np.testing.assert_allclose(mm[32:], 0.0)
        sv.stop()
