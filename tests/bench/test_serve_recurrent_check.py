"""The state half of the ``serve_recurrent`` runner's ``correct``, on
hand-made states: which head the long-memory limit reads, what a rounding of
the state to bfloat16 does to a head that sums many steps, and that another
slot's row fails the coarse limit."""
import numpy as np
import pytest

from benchmark.runners import serve_recurrent as R


def _layer(rng, heads=4, p=8, n=16):
    return {"ssm": rng.normal(size=(heads, p, n)).astype(np.float32),
            "conv": rng.normal(size=(3, 24)).astype(np.float32),
            "log_decay": np.array([-40.0, -0.2, -7.0, -90.0], np.float32)}


def test_state_errors_read_the_longest_memory_head_of_each_layer():
    rng = np.random.default_rng(0)
    want = [_layer(rng), _layer(rng)]
    got = [{k: v.copy() for k, v in layer.items()} for layer in want]
    got[0]["ssm"][1] *= 1.01      # the head whose log_decay is nearest 0
    got[1]["ssm"][3] *= 1.04      # a head that forgets: only the worst sees it
    worst, conv, long_memory = R._state_errors(got, want)
    assert worst == pytest.approx(0.04, rel=1e-3) and conv == 0.0
    assert long_memory == [pytest.approx(0.01, rel=1e-3), 0.0]


def test_a_sum_of_many_steps_kept_in_bfloat16_passes_the_long_memory_limit_no_more():
    """300 steps of a head that does not decay, the state rounded to bfloat16
    at every write as a bfloat16 arena would: the rounding alone, with exact
    inputs, is over ``STATE_LONG_RTOL``; in float32 it is nowhere near."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    steps = rng.normal(size=(300, 1, 8, 16)).astype(np.float32)
    exact = steps.astype(np.float64).sum(0)
    f32 = bf16 = jnp.zeros((1, 8, 16), jnp.float32)
    for s in steps:
        f32 = f32 + s
        bf16 = jax.lax.reduce_precision(bf16 + s, exponent_bits=8,
                                        mantissa_bits=7)
    assert float(R._rel_err(f32, exact)[0]) < R.STATE_LONG_RTOL / 100
    assert float(R._rel_err(bf16, exact)[0]) > R.STATE_LONG_RTOL


def test_another_slots_row_fails_the_coarse_state_limit():
    rng = np.random.default_rng(2)
    mine, theirs = [_layer(rng)], [_layer(rng)]
    worst, conv, _long = R._state_errors(theirs, mine)
    assert worst > 10 * R.STATE_RTOL and conv > 10 * R.STATE_RTOL
