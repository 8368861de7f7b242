"""Global RNG management.

The reference exposes a stateful global seed (``paddle.seed``; per-device
generators in paddle/fluid/framework/generator.h). JAX RNG is functional
(threefry keys), so we keep a small stateful wrapper: a root key advanced by a
counter via ``fold_in``. Under a jit trace the *counter at trace time* is baked
in — compiled-path users should thread keys explicitly (our train-step compiler
does), matching how the reference's static graphs bake seed attributes into ops.
"""
from __future__ import annotations

import threading

import jax


class Generator:
    """Stateful RNG stream over a functional threefry key."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        # the root key is made on first use: building it here would run a
        # computation (and so create a JAX backend) at `import paddle_tpu`,
        # and a supervisor that imports the package must not hold the chip
        self._root = None
        self._counter = 0
        self._trace_keys = []
        self._trace_counter = 0
        return self

    # Under a jit trace, stateful key-splitting would bake a constant key into
    # the executable. The capture path (paddle_tpu.jit) installs a traced key
    # here so dropout etc. stay random across compiled calls. A stack, because
    # traces nest (recompute inside a compiled train step).
    def set_trace_key(self, key):
        self._trace_keys.append(key)
        self._trace_counter = 0

    def clear_trace_key(self):
        if self._trace_keys:
            self._trace_keys.pop()

    @property
    def _key(self):
        if self._root is None:
            self._root = jax.random.key(self._seed)
        return self._root

    @_key.setter
    def _key(self, key):
        self._root = key

    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        with self._lock:
            if self._trace_keys:
                self._trace_counter += 1
                return jax.random.fold_in(self._trace_keys[-1], self._trace_counter)
            self._counter += 1
            return jax.random.fold_in(self._key, self._counter)

    def get_state(self):
        return (self._seed, self._counter)

    def set_state(self, state):
        self._seed, self._counter = state
        self._root = None


_GLOBAL_GENERATOR = Generator(0)


def seed(value: int) -> Generator:
    """Set the global seed (paddle.seed equivalent)."""
    return _GLOBAL_GENERATOR.manual_seed(value)


def default_generator() -> Generator:
    return _GLOBAL_GENERATOR


def next_key():
    return _GLOBAL_GENERATOR.next_key()


def get_rng_state():
    return _GLOBAL_GENERATOR.get_state()


def set_rng_state(state):
    _GLOBAL_GENERATOR.set_state(state)
