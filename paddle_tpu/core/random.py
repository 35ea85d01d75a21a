"""RNG management.

Replaces the reference's per-device stateful Philox generators
(reference: paddle/fluid/framework/generator.h:99-126) with JAX key
semantics, while keeping the stateful ``paddle.seed()`` UX:

- Eager mode: a process-global :class:`Generator` hands out fresh subkeys.
- Traced (jit) mode: stateful key draws are illegal under tracing, so a
  context-scoped *trace key* is installed by the jit wrapper; draws fold an
  increasing counter into it — pure and reproducible.
- TP-safe parallel RNG (reference: fleet/meta_parallel/parallel_layers/random.py:32
  RNGStatesTracker) is built on the same mechanism: named states are extra
  fold constants.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import numpy as np


#: Eager key derivation goes through ONE jitted fold_in. An rbg key (the
#: package default) folds by vmapping the threefry fold, and doing that
#: eagerly re-enters jax's Python tracing path on every call — host work
#: per train step and per served token, which CompileCounter.jaxpr_traces
#: reports. The jitted form is a warm C++ dispatch with the same bits.
fold_in = jax.jit(jax.random.fold_in)


class Generator:
    """Stateful key source for eager mode."""

    def __init__(self, seed: int = 0):
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._key = jax.random.key(int(seed))
        self._count = 0
        return self

    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        self._count += 1
        return fold_in(self._key, self._count)

    def get_state(self):
        return (self._seed, self._count)

    def set_state(self, state):
        self._seed, self._count = state
        self._key = jax.random.key(self._seed)


_default_generator = Generator(0)
_tls = threading.local()


def seed(value: int) -> Generator:
    """paddle.seed analogue: reseed the global generator."""
    return _default_generator.manual_seed(value)


def default_generator() -> Generator:
    return _default_generator


@contextlib.contextmanager
def trace_rng(key):
    """Install a pure trace-scoped key; draws are counter-folded subkeys."""
    prev = getattr(_tls, "trace_key", None)
    prev_count = getattr(_tls, "trace_count", 0)
    _tls.trace_key = key
    _tls.trace_count = 0
    try:
        yield
    finally:
        _tls.trace_key = prev
        _tls.trace_count = prev_count


def in_trace_rng() -> bool:
    return getattr(_tls, "trace_key", None) is not None


def make_rng(name: Optional[str] = None):
    """Return a fresh PRNG key.

    ``name`` selects a named stream (used by TP-safe dropout: the
    'local_seed' stream differs per model-parallel rank, 'global_seed' is
    identical across ranks — mirroring the reference's RNGStatesTracker).
    """
    key = getattr(_tls, "trace_key", None)
    if key is not None:
        # inside a trace the fold is part of the program being built
        fold = jax.random.fold_in
        _tls.trace_count = getattr(_tls, "trace_count", 0) + 1
        key = fold(key, _tls.trace_count)
    else:
        fold = fold_in
        key = _default_generator.next_key()
    if name is None:
        name = getattr(_tls, "stream_name", None)  # active stream_scope
    if name is not None:
        key = fold(key, _stream_id(name))
    return key


@contextlib.contextmanager
def stream_scope(name: Optional[str]):
    """Route unnamed make_rng draws to a named stream for this scope (used
    by the TP RNGStatesTracker so dropout inside model-parallel regions
    draws from the per-rank 'local_seed' stream)."""
    prev = getattr(_tls, "stream_name", None)
    _tls.stream_name = name
    try:
        yield
    finally:
        _tls.stream_name = prev


_STREAMS = {}


def _stream_id(name: str) -> int:
    if name not in _STREAMS:
        # Deterministic across processes/runs (python's str hash is salted
        # per-process; named streams like 'global_seed' must agree across
        # model-parallel ranks).
        import hashlib
        digest = hashlib.sha256(name.encode()).digest()
        _STREAMS[name] = (int.from_bytes(digest[:4], "little") & 0x7FFFFFFF) or 1
    return _STREAMS[name]


def register_rng_stream(name: str, offset: int):
    """Register a named RNG stream with an explicit fold offset.

    Used by model-parallel setup so the 'local' stream folds in the tp rank.
    """
    _STREAMS[name] = int(offset) & 0x7FFFFFFF


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)
