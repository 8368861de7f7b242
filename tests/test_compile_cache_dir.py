"""The compile cache lives in ONE place: ``JAX_COMPILATION_CACHE_DIR`` when
set (and then no code sets another), else the fixed
``<checkout>/.cache/jax`` — never a temp dir, a pid or a time (the path is
part of the cache key: a directory that moves never hits)."""
import os
import subprocess
import sys

import jax

from paddle_tpu.jit import persistent_cache as pcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_dir_resolution(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PT_PERSISTENT_CACHE_DIR", raising=False)
    assert pcache.default_dir() == os.path.join(REPO, ".cache", "jax")
    monkeypatch.setenv("PT_PERSISTENT_CACHE_DIR", str(tmp_path / "pt"))
    assert pcache.default_dir() == str(tmp_path / "pt")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    assert pcache.default_dir() == str(tmp_path / "jax")


def test_env_dir_wins_and_is_never_overridden(monkeypatch, tmp_path):
    prior_dir, prior_on = pcache._STATE.dir, pcache._STATE.enabled
    jax_dir_before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "one"))
    try:
        # an explicit path loses to the environment's one directory
        assert pcache.enable(str(tmp_path / "other")) == \
            str(tmp_path / "one")
        assert not (tmp_path / "other").exists()
        # neither seam points JAX's own cache anywhere else in code
        pcache._fallback_jax_cache()
        assert pcache.enable_jax_compilation_cache() == \
            str(tmp_path / "one")
        assert jax.config.jax_compilation_cache_dir == jax_dir_before
    finally:
        pcache._STATE.dir, pcache._STATE.enabled = prior_dir, prior_on


_SCRIPT = """
import jax, jax.numpy as jnp
import chip_smoke
cache = chip_smoke.CompileCacheCounter()
print("DIR", cache.dir)
jax.jit(lambda x: jnp.tanh(x) @ x.T + 21)(jnp.ones((64, 64))).block_until_ready()
print("HITS", cache.hits, "MISSES", cache.misses)
"""


def _run(env):
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=300, cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu", **env})
    assert r.returncode == 0, r.stderr[-2000:]
    out = dict(line.split(" ", 1) for line in r.stdout.splitlines()
               if line.startswith(("DIR", "HITS")))
    hits = int(out["HITS"].split()[0])
    return out["DIR"], hits


def test_second_process_hits_the_env_dir(tmp_path):
    """What ``chip_smoke.py`` does before its first compile, twice, in
    fresh processes: the second one reports cache hits from the
    environment's directory."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
    d1, hits1 = _run(env)
    d2, hits2 = _run(env)
    assert d1 == d2 == str(tmp_path / "cc")
    assert hits1 == 0 and hits2 > 0
