"""Round-6 satellite fixes: master rendezvous diagnostics, port
reservations, checkpoint accumulator resharding."""
import json
import os
import socket
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu import jit
from paddle_tpu.distributed.run.master import (
    Master, free_port, release_reserved_ports, reserve_port)


# -- master.py: mixed-rank gang diagnostics ----------------------------------

def test_sync_peers_mixed_explicit_auto_ranks():
    """An explicit-rank MAIN + auto participants used to hang forever on
    main_taken; the explicit node now publishes the arrival marker."""
    port = free_port()
    main = Master(f"127.0.0.1:{port}")
    assert main.role == Master.MAIN
    out = {}

    def auto_participant():
        m = Master(f"127.0.0.1:{port}")
        out["auto"] = m.sync_peers("/t/mixed", "b", 2, rank=-1,
                                   main_timeout=20.0)

    t = threading.Thread(target=auto_participant)
    t.start()
    # MAIN joins with an EXPLICIT rank (the mixed-gang configuration)
    peers, rank = main.sync_peers("/t/mixed", "a", 2, rank=0)
    t.join(timeout=30)
    assert not t.is_alive(), "auto participant hung in mixed-rank gang"
    assert rank == 0 and peers == ["a", "b"]
    assert out["auto"][1] == 1
    main.stop()


def test_sync_peers_auto_skips_explicitly_claimed_ranks():
    """Mixed gang with explicit ranks {0,1} + one auto node: the auto node
    must land on rank 2, not collide with the explicit rank 1."""
    port = free_port()
    main = Master(f"127.0.0.1:{port}")
    out = {}

    def explicit_r1():
        m = Master(f"127.0.0.1:{port}")
        out["r1"] = m.sync_peers("/t/skip", "b", 3, rank=1)

    def auto():
        m = Master(f"127.0.0.1:{port}")
        out["auto"] = m.sync_peers("/t/skip", "c", 3, rank=-1,
                                   main_timeout=20.0)

    t1 = threading.Thread(target=explicit_r1)
    t1.start()
    import time as _time

    _time.sleep(0.3)  # explicit nodes first (the documented mixed layout)
    t2 = threading.Thread(target=auto)
    t2.start()
    peers, rank = main.sync_peers("/t/skip", "a", 3, rank=0)
    t1.join(timeout=30)
    t2.join(timeout=30)
    assert not t1.is_alive() and not t2.is_alive()
    assert rank == 0 and peers == ["a", "b", "c"]
    assert out["r1"][1] == 1
    assert out["auto"][1] == 2  # skipped the claimed rank 1
    main.stop()


def test_sync_peers_duplicate_rank_raises_instead_of_hanging():
    """Two nodes claiming one rank slot (duplicate explicit --rank, or a
    mixed-gang arrival/explicit collision) must raise, not silently
    overwrite one payload and hang the gang on the missing slot."""
    port = free_port()
    main = Master(f"127.0.0.1:{port}")
    result = {}

    def dup():
        m = Master(f"127.0.0.1:{port}")
        try:
            m.sync_peers("/t/dup", "b", 3, rank=1)
        except RuntimeError as e:
            result["err"] = str(e)

    main.store.add("/t/dup/main_present", 1)  # avoid the main wait
    t = threading.Thread(target=dup)
    # first claimant of rank 1 wins silently
    main.store.add("/t/dup/claim/1", 1)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert "claimed twice" in result.get("err", "")
    main.stop()


def test_sync_peers_no_main_raises_diagnosis_quickly():
    port = free_port()
    main = Master(f"127.0.0.1:{port}")   # hosts the store only
    m = Master(f"127.0.0.1:{port}")
    assert m.role == Master.PARTICIPANT
    with pytest.raises(RuntimeError, match="misconfiguration"):
        # nobody ever joins as MAIN/explicit: must raise fast, not hang
        m.sync_peers("/t/nomain", "x", 2, rank=-1, main_timeout=1.0)
    main.stop()


# -- master.py: free_port TOCTOU ---------------------------------------------

def test_reserved_port_stays_bound_until_release():
    port = reserve_port()
    probe = socket.socket()
    try:
        with pytest.raises(OSError):
            probe.bind(("", port))   # held: a thief cannot take it
    finally:
        probe.close()
    release_reserved_ports()
    probe2 = socket.socket()
    try:
        probe2.bind(("", port))      # released: the real server binds
    finally:
        probe2.close()


def test_node_payload_ports_are_reserved():
    from paddle_tpu.distributed.run.master import _HELD_PORTS, node_payload

    release_reserved_ports()
    payload = json.loads(node_payload(2))
    held = {r.port for r in _HELD_PORTS}
    assert payload["coord_port"] in held
    assert payload["ps_port"] in held
    release_reserved_ports()


# -- incubate/checkpoint: accumulator resharding on restore ------------------

def test_auto_checkpoint_restores_accumulators_to_param_sharding(tmp_path):
    from paddle_tpu.incubate.checkpoint import train_epoch_range

    def build():
        paddle.seed(7)
        net = paddle.nn.Linear(8, 4)
        o = opt.AdamW(learning_rate=1e-3, parameters=net.parameters())
        step = jit.TrainStep(
            net, lambda m, x, y: ((m(x) - y) ** 2).mean(), o)
        return net, o, step

    x = paddle.randn([4, 8])
    y = paddle.randn([4, 4])

    from paddle_tpu.incubate.checkpoint import _EpochRange

    net, o, step = build()
    for epoch in train_epoch_range(2, name="accs", state={"opt": o},
                                   checkpoint_dir=str(tmp_path)):
        step(x, y)
    to_pos, _ = _EpochRange._pos_key_maps(o)
    moments = {to_pos(k): np.asarray(v.data if hasattr(v, "data") else v)
               for k, v in o.state_dict().items() if hasattr(v, "shape")}
    assert moments, "optimizer saved no accumulator state"

    # fresh process equivalent: new objects (param names DIFFER — the
    # global tensor counter advanced), resumed range restores state
    net2, o2, _ = build()
    r = train_epoch_range(2, name="accs", state={"opt": o2},
                          checkpoint_dir=str(tmp_path))
    for _ in r:
        pass  # both epochs completed: fast-forward, restore only
    assert r.restored_from == 1
    to_pos2, _ = _EpochRange._pos_key_maps(o2)
    restored = {to_pos2(k): v for k, v in o2.state_dict().items()
                if hasattr(v, "shape")}
    for k, v in moments.items():
        got = restored.get(k)
        assert got is not None, \
            f"accumulator {k} missing after restore ({sorted(restored)})"
        arr = got.data if hasattr(got, "data") else got
        np.testing.assert_allclose(np.asarray(arr, np.float32),
                                   v.astype(np.float32), rtol=1e-6)
        if hasattr(arr, "sharding") and k.startswith("__p"):
            # the resharding contract: moment-shaped state lands on its
            # parameter's sharding, not the default device placement
            idx = int(k[3:].split("__", 1)[0])
            owner = o2._parameter_list[idx]
            if tuple(arr.shape) == tuple(owner.shape):
                assert arr.sharding.is_equivalent_to(
                    owner.data.sharding, len(arr.shape))
