"""One process per chip: a supervisor that spawns workers must not hold
the chip itself. A parent that has touched JAX owns the TPU library, and
every child that needs it then fails or hangs — so importing the supervisor
modules creates no JAX backend, and ``bench.py``'s parent side never imports
jax or paddle_tpu at all. Checked in subprocesses (this process has a
backend already)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, cwd: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=cwd,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": REPO})
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_supervisor_imports_create_no_backend(tmp_path):
    out = _run("""
import paddle_tpu
import paddle_tpu.serving.fleet
import paddle_tpu.distributed.fleet.runtime
import paddle_tpu.distributed.launch
from jax._src import xla_bridge
assert not xla_bridge._backends, list(xla_bridge._backends)
print("no-backend")
""", str(tmp_path))
    assert "no-backend" in out


def test_bench_parent_never_touches_jax(tmp_path):
    """Both sides of ``bench.main()`` (platform reported as cpu, then as
    tpu) with the children faked: at every spawn and at the end, neither
    jax nor paddle_tpu has been imported by the parent."""
    out = _run("""
import sys
import bench

def clean(where):
    loaded = [m for m in ("jax", "paddle_tpu") if m in sys.modules]
    assert not loaded, (where, loaded)

for platform in ("cpu", "tpu"):
    spawned = []

    def fake_spawn(name, timeout=1200, env=None, platform=platform):
        clean(name)
        spawned.append(name)
        if name == "platform":
            return {"platform": platform}
        return {"mfu": 50.0, "params_m": 1.0, "params_b": 1.0,
                "step_time_s": 1.0, "warmup_s": 1.0, "cache_hits": 0,
                "fresh_xla_compiles": 0}

    bench._spawn = fake_spawn
    bench._arm_budget = lambda: None
    bench.main()
    clean("end of main")
    assert spawned[0] == "platform" and len(spawned) > 2, spawned
    print("spawned", platform, len(spawned))
print("parent-clean")
""", str(tmp_path))
    assert "parent-clean" in out
    # the CPU side never writes a number under the device metric's name
    cpu_lines = out.split("spawned cpu")[0]
    assert '"value": null' in cpu_lines and '"value": 50.0' not in cpu_lines
