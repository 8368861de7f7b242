"""One process per chip: a supervisor that spawns workers must not hold
the chip itself. A parent that has touched JAX owns the TPU library, and
every child that needs it then fails or hangs — so importing the supervisor
modules creates no JAX backend. Checked in a subprocess (this process has a
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
