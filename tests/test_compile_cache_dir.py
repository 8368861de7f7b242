"""The compile cache lives in ONE place: ``JAX_COMPILATION_CACHE_DIR`` when
set (and then no code sets another), else the fixed
``<checkout>/.cache/jax`` — never a temp dir, a pid or a time (the path is
part of the cache key: a directory that moves never hits)."""
import functools
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


# -- a build runs in one chunk of the interpreter's stack ----------------------

def test_a_build_runs_under_one_stack_chunk(monkeypatch, tmp_path):
    """``CachedJit._build`` traces and lowers under
    ``in_one_stack_chunk``: a frame whose size makes CPython hand it a chunk
    with room for every frame of the trace above it."""
    assert pcache.in_one_stack_chunk.__code__.co_stacksize > 1 << 16
    assert pcache.in_one_stack_chunk(lambda a, b=2: (a, b), 1, b=5) == (1, 5)
    seen = []
    real = pcache.in_one_stack_chunk

    def spy(fn, *args, **kwargs):
        seen.append(getattr(fn, "__name__", ""))
        return real(fn, *args, **kwargs)

    monkeypatch.setattr(pcache, "in_one_stack_chunk", spy)
    prior_dir, prior_on = pcache._STATE.dir, pcache._STATE.enabled
    try:
        pcache.enable(str(tmp_path / "c"))
        f = pcache.cached_jit(lambda x: x * 2 + 1, label="one_chunk")
        assert float(f(jax.numpy.ones(()))) == 3.0
        assert float(f(jax.numpy.ones(()))) == 3.0     # no second build
    finally:
        pcache._STATE.dir, pcache._STATE.enabled = prior_dir, prior_on
    assert len(seen) == 1 and "lower" in seen[0]


def test_an_engines_warmup_runs_under_one_stack_chunk(monkeypatch):
    """``GenerationEngine.warmup()`` builds its programs under the helper
    whichever cache is on (the benchmark runs with JAX's own, where
    ``CachedJit`` builds nothing itself)."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    seen = []
    real = pcache.in_one_stack_chunk

    def spy(fn, *args, **kwargs):
        seen.append(fn.__name__)
        return real(fn, *args, **kwargs)

    monkeypatch.setattr(pcache, "in_one_stack_chunk", spy)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=32, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, max_position_embeddings=64,
        intermediate_size=64))
    eng = serving.GenerationEngine(model, serving.GenerationConfig(
        max_slots=2, max_seq_len=32, prefill_buckets=(8,)))
    assert eng.warmup() is eng
    assert seen[0] == "_warmup" and len(eng._windows) == 2
    eng.close()


def test_no_call_depth_is_a_hundred_times_slower_under_one_chunk():
    """The pathology the helper is for, where this interpreter has it
    (CPython 3.11 on: a call site at the end of a 16 KiB data-stack chunk
    maps and unmaps a chunk on every call): some recursion depth makes a
    loop of trivial calls tens of times slower; under
    ``in_one_stack_chunk`` none does."""
    import statistics
    import time

    def leaf():
        return 1

    def hot(n):
        t = time.perf_counter()
        for _ in range(n):
            leaf()
        return time.perf_counter() - t

    def rec(d, n):
        return hot(n) if d == 0 else rec(d - 1, n)

    def best(measure, d):
        # the thrash belongs to a depth and is there on every repeat; a
        # sample that was slow because the machine was busy is not
        return min(measure(d, 20000) for _ in range(3))

    depths = range(0, 360)
    plain = [best(rec, d) for d in depths]
    under = [best(functools.partial(pcache.in_one_stack_chunk, rec), d)
             for d in depths]
    med = statistics.median(plain)
    assert max(under) < 10 * med, (max(under), med)
    if max(plain) > 30 * med:        # this interpreter thrashes somewhere
        assert max(under) < max(plain) / 5
