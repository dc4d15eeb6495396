"""Global interpreter state: grad mode, PRNG threading, AMP state.

Reference parity: paddle/fluid/imperative/tracer.cc (has_grad / amp state)
and python/paddle/framework/random.py — redesigned around JAX's explicit
PRNG keys so that randomness is reproducible and trace-safe on TPU.
"""
from __future__ import annotations

import contextlib
import threading

import jax


class _ThreadState(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.amp_dtype = None      # active autocast dtype (np dtype) or None
        self.amp_level = "O0"
        self.amp_custom_white = set()
        self.amp_custom_black = set()


_state = _ThreadState()


def grad_enabled() -> bool:
    return _state.grad_enabled


@contextlib.contextmanager
def no_grad_ctx():
    prev = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = prev


@contextlib.contextmanager
def enable_grad_ctx():
    prev = _state.grad_enabled
    _state.grad_enabled = True
    try:
        yield
    finally:
        _state.grad_enabled = prev


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)


def amp_state():
    return _state


# ---------------------------------------------------------------------------
# PRNG: a stateful global key for eager mode, plus an explicit key-context
# stack so compiled (traced) code can thread step-dependent keys through
# random ops (dropout etc.) without retracing.
# ---------------------------------------------------------------------------
class _PRNGState:
    def __init__(self, seed: int = 0):
        self._np_lock = threading.Lock()
        self.seed(seed)
        self._ctx_stack = []  # list of [key, counter]

    def seed(self, s: int):
        self._seed = int(s)
        # LAZY: creating the key here would initialize the jax backend at
        # `import paddle_tpu` time — seconds of TPU start-up (or a
        # failure when another process holds the chip) before any
        # user code runs. The key materializes on first random use.
        self._key = None
        self._eager_counter = 0

    def _base_key(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        return self._key

    def next_key(self):
        """Return a fresh PRNG key.

        Inside a key context (compiled path) keys derive from the pushed
        (possibly traced) key via fold_in with a static counter; in eager
        mode we advance the global stateful key.
        """
        if self._ctx_stack:
            entry = self._ctx_stack[-1]
            k = jax.random.fold_in(entry[0], entry[1])
            entry[1] += 1
            return k
        self._eager_counter += 1
        return jax.random.fold_in(self._base_key(), self._eager_counter)

    def next_np_seed(self) -> int:
        """Derive a 32-bit seed for host-side numpy Generators (samplers,
        dataset shuffles). Deterministic under seed(); each caller gets its
        own Generator so no thread shares mutable numpy RNG state."""
        with self._np_lock:
            self._eager_counter += 1
            return (self._seed * 1000003 + self._eager_counter) & 0xFFFFFFFF

    @contextlib.contextmanager
    def key_ctx(self, key):
        self._ctx_stack.append([key, 0])
        try:
            yield
        finally:
            self._ctx_stack.pop()


prng = _PRNGState(0)


def seed(s: int):
    prng.seed(s)
    return prng


def get_rng_state():
    return {"seed": prng._seed, "counter": prng._eager_counter}


def set_rng_state(st):
    prng.seed(st["seed"])
    prng._eager_counter = st["counter"]
