"""RNG state management.

Counterpart of the reference's ``phi::Generator`` (``paddle/phi/core/generator.h``)
and the TP-aware ``RNGStatesTracker`` (``fleet/layers/mpu/random.py:34``), built on
JAX's functional PRNG: the framework keeps a root key and splits a fresh subkey per
random op in eager mode; under ``jit`` tracing, a traced key can be installed with
``rng_guard`` so random ops stay functional.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import jax


class Generator:
    """A splittable PRNG stream.

    Key construction is lazy: ``jax.random.key`` initializes the JAX backend, and
    ``import paddle_tpu`` must never do that — a chip belongs to the first process
    that touches JAX, so an import that did would make the pure process-management
    launcher take the chip from the child it starts. The key is built on first use
    instead.
    """

    def __init__(self, seed: int = 0):
        self._seed = seed
        self._key = None  # built lazily on first use
        self._lock = threading.Lock()

    def manual_seed(self, seed: int) -> "Generator":
        with self._lock:
            self._seed = seed
            self._key = None
        return self

    @property
    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        with self._lock:
            if self._key is None:
                self._key = jax.random.key(self._seed)
            self._key, sub = jax.random.split(self._key)
            return sub

    def get_state(self):
        with self._lock:
            if self._key is None:
                self._key = jax.random.key(self._seed)
            return self._key

    def set_state(self, key) -> None:
        with self._lock:
            self._key = key


_DEFAULT = Generator(0)

# Optional traced-key override stack (for use inside jit-traced functions).
_TRACED: list = []


def default_generator() -> Generator:
    return _DEFAULT


def seed(s: int) -> Generator:
    """Seed the global generator (``paddle.seed`` equivalent)."""
    _DEFAULT.manual_seed(int(s))
    for g in _TRACKER._states.values():
        g.manual_seed(int(s))
    return _DEFAULT


_warned_traced_eager_key = False

try:  # private jax API; degrade to no warning if it moves
    from jax._src.core import trace_state_clean as _trace_state_clean
except Exception:  # pragma: no cover
    _trace_state_clean = None


def next_key():
    """Fresh PRNG key for one random op."""
    global _warned_traced_eager_key
    if _TRACED:
        key, sub = jax.random.split(_TRACED[-1][0])
        _TRACED[-1][0] = key
        return sub
    if (not _warned_traced_eager_key and _trace_state_clean is not None
            and not _trace_state_clean()):
        _warned_traced_eager_key = True
        import warnings

        warnings.warn(
            "a PRNG key was drawn during jit tracing without rng_guard: the "
            "key becomes a compile-time constant, so every call of the "
            "compiled function reuses identical randomness. Thread a key "
            "functionally (TrainStep/to_static do this automatically).",
            stacklevel=2)
    return _DEFAULT.next_key()


@contextlib.contextmanager
def rng_guard(key):
    """Install a (possibly traced) key as the source for random ops.

    Used when tracing a model under jit: ``with rng_guard(step_key): model(x)``
    keeps dropout etc. functional in the traced program.
    """
    _TRACED.append([key])
    try:
        yield
    finally:
        _TRACED.pop()


class RNGStatesTracker:
    """Named RNG domains (reference: ``mpu/random.py`` RNGStatesTracker).

    Tensor-parallel dropout needs *different* streams per model-parallel rank for
    non-replicated activations and the *same* stream for replicated ones; named
    domains provide that.
    """

    def __init__(self):
        self._states: Dict[str, Generator] = {}

    def add(self, name: str, seed_: int) -> None:
        if name in self._states:
            raise ValueError(f"rng state {name!r} already exists")
        self._states[name] = Generator(seed_)

    @contextlib.contextmanager
    def rng_state(self, name: str = "global_seed"):
        gen = self._states.get(name)
        if gen is None:
            gen = Generator(_DEFAULT.initial_seed)
            self._states[name] = gen
        old = _DEFAULT.get_state()
        _DEFAULT.set_state(gen.get_state())
        try:
            yield
        finally:
            gen.set_state(_DEFAULT.get_state())
            _DEFAULT.set_state(old)


_TRACKER = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _TRACKER


def get_rng_state(device=None):
    """Generator state list (reference ``paddle.get_rng_state`` returns one
    state per device; one program == one logical device here)."""
    return [_DEFAULT.get_state()]


def set_rng_state(state_list, device=None) -> None:
    """Inverse of :func:`get_rng_state`."""
    states = state_list if isinstance(state_list, (list, tuple)) else [state_list]
    _DEFAULT.set_state(states[0])


def get_cuda_rng_state():
    """Reference CUDA-surface alias: the accelerator RNG here IS the
    functional key of the default generator."""
    return get_rng_state()


def set_cuda_rng_state(state_list) -> None:
    set_rng_state(state_list)
