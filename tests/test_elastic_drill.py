"""Elastic failure drill (reference fleet/elastic/manager.py:130): kill a
worker mid-training, manager/controller emits RESTART, gang relaunches at the
surviving world size, training resumes from the sharded checkpoint."""
import json
import os
import sys
import textwrap

import numpy as np
import pytest


_WORKER = textwrap.dedent("""
    import json, os, sys, time
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.devices()  # backend up BEFORE the rendezvous below (importing
    # paddle_tpu no longer creates it): the workers' first steps stay aligned
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet.elastic import elastic_worker_env

    rank, world, restart_id, store, manager = elastic_worker_env()
    work = sys.argv[1]
    TOTAL = 8

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8))
    o = opt.SGD(learning_rate=0.05, parameters=net.parameters())
    start = 0
    latest = os.path.join(work, "LATEST")
    if os.path.exists(latest):
        with open(latest) as f:
            meta = json.load(f)
        start = meta["step"] + 1
        sd = net.state_dict()
        dist.load_state_dict(sd, meta["dir"])

    x = paddle.to_tensor(np.random.RandomState(1).rand(4, 8).astype("float32"))
    y = paddle.to_tensor((np.random.RandomState(1).rand(4, 8) * 0.1).astype("float32"))
    for step in range(start, TOTAL):
        loss = F.mse_loss(net(x), y)
        loss.backward(); o.step(); o.clear_grad()
        if rank == 0:
            d = os.path.join(work, f"ckpt_{step}")
            dist.save_state_dict(net.state_dict(), d, process_rank=0)
            tmp = latest + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"step": step, "dir": d}, f)
            os.replace(tmp, latest)
            # trace LAST (after the marker flip): a kill landing between
            # trace and marker would replay this step on resume and log a
            # duplicate step number (flaky under load)
            with open(os.path.join(work, "trace.log"), "a") as f:
                f.write(json.dumps({"step": step, "world": world,
                                    "restart": restart_id,
                                    "loss": float(loss)}) + "\\n")
        if rank == 1 and restart_id == 0 and step == 3:
            # the drill kills a node MID-training, i.e. once a checkpoint
            # exists: each worker creates its JAX backend after the
            # rendezvous (importing paddle_tpu no longer does), so first
            # steps can be skewed by more than this rank's 3 steps
            t_end = time.time() + 30
            while not os.path.exists(latest) and time.time() < t_end:
                time.sleep(0.01)
            os.kill(os.getpid(), 9)  # simulated node failure
        # a step long enough that start-up skew between the workers (a few
        # 100 ms: backend + first compile) cannot carry rank 0 past its last
        # step before rank 1 dies at step 3
        time.sleep(0.25)
    with open(os.path.join(work, f"done.{rank}.r{restart_id}"), "w") as f:
        f.write("done")
""")


@pytest.mark.dist
def test_kill_restart_resume(tmp_path):
    from paddle_tpu.distributed.fleet.elastic import (ElasticController,
                                                      ElasticStatus)

    script = tmp_path / "elastic_worker.py"
    script.write_text(_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ctl = ElasticController(
        [sys.executable, str(script), str(tmp_path)], np=4, min_np=2,
        log_dir=str(tmp_path / "logs"),
        extra_env={"JAX_PLATFORMS": "cpu",
                   "PYTHONPATH": repo + os.pathsep +
                   os.environ.get("PYTHONPATH", "")})
    try:
        status = ctl.run(max_restarts=2, timeout=300)
        if status != ElasticStatus.COMPLETED:
            import subprocess

            logs = subprocess.run(
                ["find", str(tmp_path / "logs"), "-type", "f"],
                capture_output=True, text=True).stdout
            pytest.fail(f"status={status} events={ctl.events} logs:\n{logs}")
    finally:
        ctl.close()

    # one restart happened, at world size 3
    restarts = [e for e in ctl.events if e["status"] == "restart"]
    assert len(restarts) == 1 and restarts[0]["world"] == 3
    # survivors finished at world 3
    assert (tmp_path / "done.0.r1").exists()
    assert (tmp_path / "done.2.r1").exists()

    # training resumed from the checkpoint: the step sequence continues past
    # the kill point instead of starting over, and the loss keeps decreasing
    trace = [json.loads(l) for l in
             (tmp_path / "trace.log").read_text().splitlines()]
    steps = [t["step"] for t in trace]
    assert steps == sorted(steps) and len(steps) == len(set(steps)), steps
    assert steps[-1] == 7
    w3 = [t for t in trace if t["world"] == 3]
    # resumed from a checkpoint, not from scratch: rank 0 checkpoints every
    # step but may lag rank 1's kill at step 3 (it does extra I/O per
    # step), so the resume point is >= 1 — not necessarily >= 3
    assert w3 and w3[0]["step"] >= 1, trace
    losses = [t["loss"] for t in trace]
    assert losses[-1] < losses[0]
