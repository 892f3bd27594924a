"""Global RNG state.

Paddle exposes a global seeded generator (``paddle.seed``) plus per-parallel-
axis generators (``get_rng_state_tracker`` in fleet, for TP-correct dropout).
jax wants explicit keys. Resolution: a named registry of ``Generator`` objects
each holding a persistable key tensor; every draw splits the key and writes
back, so the to_static functionalizer captures RNG state like any other state
(SURVEY.md §7 "hard parts": RNG under trace).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .core import Tensor

__all__ = ["seed", "Generator", "default_generator", "get_rng_state",
           "set_rng_state", "next_key", "RNGStatesTracker",
           "get_rng_state_tracker"]


class Generator:
    def __init__(self, seed_: int = 0, name: str = "default"):
        self.name = name
        self._seed = seed_
        # key creation is LAZY: building a PRNGKey initializes the XLA
        # backend, and the module-level default generator would otherwise
        # do that at import time — breaking jax.distributed.initialize
        # (which must run before any backend init) for every worker that
        # imports paddle_tpu first
        self._state_t: Tensor | None = None

    @property
    def _state(self) -> Tensor:
        if self._state_t is None:
            t = Tensor(jax.random.PRNGKey(self._seed), stop_gradient=True)
            t.persistable = True
            t.name = f"rng_{self.name}"
            self._state_t = t
        return self._state_t

    def manual_seed(self, seed_: int) -> "Generator":
        self._seed = seed_
        if self._state_t is None:
            return self    # stays lazy: key built from _seed on first use
        self._state.set_data(jax.random.PRNGKey(seed_))
        return self

    def next_key(self):
        """Split: return a fresh subkey, store the new state."""
        key = self._state.jax()  # records a state read under tracking
        new_state, sub = jax.random.split(key)
        self._state.set_data(new_state)
        return sub

    # both copy: the generator's key is state a compiled function
    # reassigns, so to_static donates (deletes) the buffer it held — a
    # saved state must be restorable after that, and more than once

    def get_state(self) -> Tensor:
        return Tensor(jnp.array(self._state.jax()))

    def set_state(self, state) -> None:
        self._state.set_data(jnp.array(
            state.jax() if isinstance(state, Tensor) else state))


default_generator = Generator(0, "default")


def seed(value: int) -> Generator:
    """``paddle.seed`` — reseed the default generator (and axis trackers)."""
    default_generator.manual_seed(value)
    _tracker.reseed_all(value)
    return default_generator


def next_key():
    return default_generator.next_key()


def get_rng_state():
    return [default_generator.get_state()]


def set_rng_state(states) -> None:
    if isinstance(states, (list, tuple)):
        states = states[0]
    default_generator.set_state(states)


class RNGStatesTracker:
    """Named RNG states for parallelism — mirrors fleet's
    ``get_rng_state_tracker`` (meta_parallel/parallel_layers/random.py,
    UNVERIFIED): e.g. dropout inside a TP region must differ per model-rank
    ('local_seed') but match across ('global_seed')."""

    def __init__(self):
        self.states: dict[str, Generator] = {}

    def add(self, name: str, seed_: int) -> None:
        if name in self.states:
            raise ValueError(f"RNG state {name!r} already exists")
        self.states[name] = Generator(seed_, name)

    def reseed_all(self, base_seed: int) -> None:
        for i, (name, gen) in enumerate(sorted(self.states.items())):
            gen.manual_seed(base_seed + 1000 + i)

    def rng_state(self, name: str = "global_seed"):
        import contextlib

        @contextlib.contextmanager
        def ctx():
            gen = self.states.get(name)
            if gen is None:
                # lazily create deterministically from the name; crc32 is
                # stable across processes (str hash is salted per process,
                # which would desync TP ranks)
                import zlib
                gen = Generator(zlib.crc32(name.encode()) % (2**31), name)
                self.states[name] = gen
            global default_generator
            from . import random as _self
            prev = _self.default_generator
            _self.default_generator = gen
            try:
                yield
            finally:
                _self.default_generator = prev
        return ctx()


_tracker = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _tracker
