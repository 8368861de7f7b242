"""paddle_tpu.analysis: capture, retrace audit, SPMD lint, HBM estimator,
repo self-lint, and the pd_check CLI."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.analysis as A
import paddle_tpu.optimizer as opt
from paddle_tpu import jit
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_train_step():
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=3e-4,
                          parameters=model.parameters())
    step = jit.TrainStep(model, lambda m, x, y: m(x, labels=y), optimizer)
    ids = paddle.randint(0, cfg.vocab_size, [2, 32])
    return step, ids


# -- program capture ---------------------------------------------------------

def test_capture_callable_and_totals():
    def f(x, y):
        return (x @ y).sum()

    prog = A.capture(f, jnp.ones((32, 64)), jnp.ones((64, 16)))
    assert prog.total_flops() >= 2 * 32 * 64 * 16  # the matmul dominates
    names = {n.name for n in prog.nodes}
    assert "dot_general" in names
    # source locations resolve to user frames
    dot = prog.find("dot_general")[0]
    assert dot.location is None or ":" in dot.location


def test_capture_train_step_walks_whole_step():
    step, ids = _tiny_train_step()
    prog = A.capture(step, ids, ids)
    assert prog.label == "TrainStep"
    assert len(prog.nodes) > 100          # fwd + bwd + update
    assert any(prog.donated_invars)       # donation mask captured
    # the pass runner executes every registered pass without error
    diags = A.run_passes(prog, hbm_bytes=int(16e9))  # CPU reports none
    assert all(d.severity in ("info", "warning", "error") for d in diags)


def test_capture_static_program():
    import paddle_tpu.static as static

    paddle.enable_static()
    try:
        main = static.Program()
        startup = static.Program()
        with static.program_guard(main, startup):
            x = static.data(name="X", shape=[None, 4], dtype="float32")
            h = paddle.nn.Linear(4, 3)(x)
            _ = h.sum()
        prog = A.capture(main)
        assert prog.total_flops() > 0
        assert any(n.name == "dot_general" for n in prog.nodes)
    finally:
        paddle.disable_static()


# -- retrace auditor ---------------------------------------------------------

def test_retrace_names_dtype_drift():
    A.retrace.reset()
    A.retrace.enable()
    try:
        a = paddle.to_tensor([[1.0, 2.0]])
        _ = a + a                                   # baseline f32 compile
        b = paddle.to_tensor([[1, 2]], dtype="int32")
        _ = b + b                                   # induced dtype drift
    finally:
        A.retrace.disable()
    events = [e for e in A.retrace.get_auditor().events
              if e.label.startswith("op:add fwd")]
    assert events, "dtype drift was not recorded as a retrace"
    assert any("dtype float32 -> int32" in d for e in events
               for d in e.deltas)
    diags = A.retrace.report()
    assert any(d.code == "RT001" for d in diags)


def test_retrace_names_shape_drift_on_train_step():
    A.retrace.reset()
    step, ids = _tiny_train_step()
    A.retrace.enable()
    try:
        step(ids, ids)                              # baseline [2,32] compile
        ids2 = paddle.randint(0, 256, [2, 48])      # seq drift -> recompile
        step(ids2, ids2)
    finally:
        A.retrace.disable()
    events = [e for e in A.retrace.get_auditor().events
              if e.label.startswith("TrainStep#")]
    assert events, "TrainStep retrace was not recorded"
    assert any("(2, 32)" in d and "(2, 48)" in d
               for e in events for d in e.deltas)


def test_retrace_two_train_steps_no_phantom_drift():
    """Two independent TrainSteps with different batch shapes compile once
    each — the auditor must not pool their signatures into one bucket."""
    A.retrace.reset()
    step_a, ids_a = _tiny_train_step()
    step_b, _ = _tiny_train_step()
    ids_b = paddle.randint(0, 256, [4, 16])
    A.retrace.enable()
    try:
        step_a(ids_a, ids_a)
        step_b(ids_b, ids_b)   # different shape, different instance: fine
    finally:
        A.retrace.disable()
    phantom = [e for e in A.retrace.get_auditor().events
               if e.label.startswith("TrainStep#")]
    assert phantom == [], [e.deltas for e in phantom]


def test_retrace_disabled_leaves_dispatch_unhooked():
    from paddle_tpu.core import dispatch

    A.retrace.disable()
    assert dispatch._AUDIT_HOOK is None
    assert jit._TRACE_AUDIT_HOOK is None
    # default-off: dispatch returns the raw cached jitted callable, not an
    # auditing wrapper
    prim = dispatch.get_primitive("add")
    f = prim.fwd({})
    assert f is dispatch._FWD_CACHE[("add", dispatch._attrs_key({}))]


# -- SPMD / collective lint --------------------------------------------------

def _mesh_8(pp=4, dp=2):
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:pp * dp]).reshape(pp, dp)
    return Mesh(devs, ("pp", "dp"))


def test_spmd_flags_broken_ppermute_pair():
    from jax import lax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_8()

    def f(x):
        a = lax.ppermute(x, "pp", [(0, 1), (1, 2), (2, 3)])
        # deliberately broken partner: duplicate destination + not the
        # forward perm's inverse
        b = lax.ppermute(a, "pp", [(0, 2), (1, 2)])
        return a + b

    sm = shard_map(f, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"),
                   check_rep=False)
    prog = A.capture(sm, jnp.ones((8, 4)))
    diags = A.run_passes(prog, passes=["spmd"], hbm_bytes=int(16e9))
    codes = {d.code for d in diags}
    assert "SP002" in codes   # malformed perm (duplicate destination)
    assert "SP003" in codes   # mismatched stage handoff
    assert any(d.severity == "error" for d in diags)


def test_spmd_clean_pipeline_has_no_findings():
    from jax import lax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_8()
    fwd = [(i, i + 1) for i in range(3)]

    def f(x):
        return lax.ppermute(x, "pp", fwd)

    sm = shard_map(f, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"),
                   check_rep=False)
    prog = A.capture(sm, jnp.ones((8, 4)))
    diags = A.run_passes(prog, passes=["spmd"], hbm_bytes=int(16e9))
    assert not [d for d in diags if d.severity == "error"]


def test_spmd_flags_fat_unsharded_intermediate():
    def f(x):
        big = jnp.broadcast_to(x, (4096, 4096, 64))  # 4 GB f32
        return big.sum()

    prog = A.capture(f, jnp.ones((64,), jnp.float32))
    diags = A.run_passes(prog, passes=["spmd"],
                         hbm_bytes=int(16e9), hbm_frac=0.25)
    assert any(d.code == "SP004" for d in diags)


# -- memory estimator --------------------------------------------------------

def test_memory_estimate_exact_on_analytic_chain():
    # x(4MB) -> relu(4MB) -> sum(4B): peak = inputs + one live temp
    n = 1024 * 1024

    def f(x):
        y = jax.nn.relu(x)
        return y.sum()

    prog = A.capture(f, jnp.ones((n,), jnp.float32))
    est = A.estimate_peak(prog)
    mb = 4 * n
    assert mb * 1.99 <= est.peak_bytes <= mb * 2.2  # input + relu temp


def test_memory_estimate_matches_xla_within_20pct():
    """The acceptance bar: live-range estimate within 20% of the measured
    envelope (XLA's own buffer assignment) for a ShardedTrainStep recipe."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.framework import random as random_mod

    dist.reset_mesh()
    dist.init_mesh(devices=jax.devices()[:1])  # single-chip mesh recipe
    try:
        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        optimizer = opt.AdamW(learning_rate=3e-4,
                              parameters=model.parameters())
        step = dist.ShardedTrainStep(model, lambda m, x, y: m(x, labels=y),
                                     optimizer)
        ids = paddle.randint(0, cfg.vocab_size, [2, 32])
        est = A.estimate_train_step_hbm(step, ids, ids)

        arrays = [ids.data, ids.data]
        o = step.optimizer
        params = [p.data for p in step.train_params]
        states = [o._accumulators[id(p)] for p in step.train_params]
        frozen = [t.data for t in step.frozen]
        lr = jnp.asarray(0.1, jnp.float32)
        sn = jnp.asarray(1, jnp.int32)
        compiled = step._build(arrays).lower(
            params, states, frozen, lr, sn, random_mod.next_key(),
            *arrays).compile()
        ma = compiled.memory_analysis()
        measured = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
                    ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        if measured <= 0:
            pytest.skip("backend reports no memory analysis")
        ratio = est.peak_bytes / measured
        assert 0.8 <= ratio <= 1.2, (est.peak_bytes, measured)
    finally:
        dist.reset_mesh()


def test_memory_pass_flags_static_oom():
    def f(x):
        big = jnp.broadcast_to(x, (4096, 4096, 256))  # 16 GB f32
        return (big * 2.0).sum()

    prog = A.capture(f, jnp.ones((256,), jnp.float32))
    diags = A.run_passes(prog, passes=["memory"], hbm_bytes=int(16e9))
    assert any(d.code == "MM003" and d.severity == "error" for d in diags)


# -- self-lint ---------------------------------------------------------------

PLANTED = '''
import jax

@jax.jit
def hot_step(x):
    v = jax.device_get(x)          # SL001
    import numpy as np
    r = np.random.rand()           # SL003
    print(v)                       # SL002
    x[0] = r                       # SL004
    return x
'''


def test_selfcheck_catches_planted_device_get(tmp_path):
    fixture = tmp_path / "planted.py"
    fixture.write_text(PLANTED)
    diags = A.selfcheck.lint_file(str(fixture))
    codes = [d.code for d in diags]
    assert "SL001" in codes and "SL003" in codes
    assert any(d.severity == "error" for d in diags)
    # the same violations are suppressible line-by-line
    suppressed = PLANTED.replace(
        "v = jax.device_get(x)          # SL001",
        "v = jax.device_get(x)  # pd-lint: disable=SL001")
    diags2 = A.selfcheck.lint_file(str(fixture), suppressed)
    assert "SL001" not in [d.code for d in diags2]


def test_selfcheck_repo_is_clean():
    diags = A.selfcheck.run_selfcheck()
    assert diags == [], A.render(diags)


def test_selfcheck_ignores_pallas_ref_stores(tmp_path):
    src = '''
import jax.experimental.pallas as pl

def _my_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2
def call(x):
    return pl.pallas_call(_my_kernel, out_shape=None)(x)
'''
    fixture = tmp_path / "kern.py"
    fixture.write_text(src)
    assert A.selfcheck.lint_file(str(fixture)) == []


# -- CLI + cost model --------------------------------------------------------

def test_pd_check_self_cli():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "pd_check.py"),
         "--self"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_pd_check_json_single_model():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "pd_check.py"),
         "--json", "--models", "bert", "--no-retrace-demo"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout[-500:] + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    names = [b["name"] for b in out["blocks"]]
    assert "bert" in names and "selfcheck" in names


def test_cost_model_static_program_cost():
    cm = paddle.cost_model.CostModel()
    out = cm.static_program_cost(lambda x: (x @ x.T).sum(),
                                 jnp.ones((64, 32)))
    assert out["total_flops"] >= 2 * 64 * 32 * 64
    assert out["peak_hbm_bytes"] > 0
    assert out["est_step_ms"] > 0
