"""Global PRNG state for eager execution.

The reference keeps per-device RNG states in the resource manager
(ref: src/resource.cc ResourceRequest::kRandom, mx.random.seed). JAX RNG is
stateless, so the eager (`mx.nd`) layer keeps ONE root key here and splits a
fresh subkey per sampling op; jitted/hybridized code threads keys explicitly
instead (see gluon.block), which is the TPU-idiomatic path.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax


def _default_impl():
    """PRNG bit-generator implementation.

    threefry (JAX's default) is counter-based and fully reproducible but
    costs real MXU time to generate big masks — measured 32 ms of a
    131 ms BERT-base step (24%!) just making dropout masks
    (docs/perf_notes.md round 3). On TPU the default here is ``rbg``
    (XLA's hardware RngBitGenerator): same stateless key-threading
    semantics, ~free mask generation. Override with MXNET_PRNG_IMPL=
    threefry2x32|rbg (e.g. for bit-exact cross-platform repro); CPU
    keeps threefry so test suites stay deterministic."""
    impl = os.environ.get("MXNET_PRNG_IMPL")
    if impl:
        return impl
    return "rbg" if jax.default_backend() == "tpu" else "threefry2x32"


def _make_key(seed_val):
    # every key creation is a backend touch (array on device) — route it
    # through the diagnostics guard so the dial is journaled and a stalled
    # device runtime leaves a breadcrumb instead of a silent hang
    from .diagnostics import guard
    guard.ensure_backend(tag="rng-global-key")
    return jax.random.key(int(seed_val), impl=_default_impl())


_lock = threading.Lock()
# LAZY by contract: created on first seed()/key use. Nothing at module
# scope may call jax.default_backend()/jax.random.key — an import-time
# key here dials the backend on `import mxnet_tpu`, which takes the chip
# for every process that merely imports the package (the reference
# builds RNG states lazily in src/resource.cc's ResourceManager).
# tests/test_diagnostics.py pins this with an import-hermeticity test.
_key = None
_trace = threading.local()


def _ensure_key_locked():
    """Create the global key on first use (caller holds ``_lock``)."""
    global _key
    if _key is None:
        _key = _make_key(0)
    return _key


def seed(seed_state: int):
    """ref: mx.random.seed — reseed the global generator."""
    global _key
    with _lock:
        _key = _make_key(int(seed_state))


def next_key():
    """Split off a fresh subkey for one op invocation.

    Inside a hybridize trace (``trace_key`` scope) the subkey is derived from
    the *traced* key argument via ``fold_in``, so the jitted program takes the
    key as a runtime input — each call of the compiled function sees fresh
    randomness instead of a baked-in constant."""
    stack = getattr(_trace, "stack", None)
    if stack:
        entry = stack[-1]
        entry[1] += 1
        return jax.random.fold_in(entry[0], entry[1])
    global _key
    with _lock:
        _key, sub = jax.random.split(_ensure_key_locked())
    return sub


@contextlib.contextmanager
def trace_key(key):
    """Scope used while tracing a hybridized block: route ``next_key`` through
    a traced key argument (the TPU-idiomatic explicit-key threading)."""
    stack = getattr(_trace, "stack", None)
    if stack is None:
        stack = _trace.stack = []
    stack.append([key, 0])
    try:
        yield
    finally:
        stack.pop()


def in_trace() -> bool:
    return bool(getattr(_trace, "stack", None))


def get_state():
    """Snapshot the eager generator: (raw key bits uint32, impl name).
    Together with ``set_state`` this makes checkpoint/resume bit-exact for
    every op that draws from the global key (dropout masks, samplers)."""
    import numpy as np
    with _lock:
        key = _ensure_key_locked()
        return (np.asarray(jax.random.key_data(key)),
                str(jax.random.key_impl(key)))


def set_state(data, impl):
    global _key
    import jax.numpy as jnp
    from .diagnostics import guard
    guard.ensure_backend(tag="rng-set-state")
    with _lock:
        _key = jax.random.wrap_key_data(
            jnp.asarray(data, dtype=jnp.uint32), impl=impl)
