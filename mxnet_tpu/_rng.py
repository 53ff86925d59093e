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
from jax._src.array import ArrayImpl   # no public uncommitted constructor


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


# re-entrant: ``split_in_program`` holds it across the call of a compiled
# program, whose first call traces Python that may itself draw an eager key
# (a loss block with a sampler)
_lock = threading.RLock()
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
    """Split off a fresh subkey for one op invocation: an eager
    ``jax.random.split`` of the root this module owns. A compiled program
    that wants the draw without the eager split's device programs takes it
    through :func:`split_in_program`.

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


class _ProgramDraw:
    """What :func:`split_in_program` yields: ``root`` goes into the program,
    ``new_root_data`` takes the bits of the root the program returned."""
    __slots__ = ("root", "new_root_data")

    def __init__(self, root):
        self.root, self.new_root_data = root, None


@contextlib.contextmanager
def split_in_program():
    """One draw of the stream, split inside the compiled program that
    consumes the subkey (``ShardedTrainer.step`` / ``run_steps``)::

        with _rng.split_in_program() as draw:
            ..., draw.new_root_data = program(..., draw.root, ...)

    The program takes the root key as an input, does ``root, sub =
    jax.random.split(root)`` itself, uses ``sub`` where it would have used
    ``next_key()``, and returns ``jax.random.key_data(root)``. On exit the
    new root becomes the module's key, under the lock ``next_key`` takes
    and which is held for the whole block, so the stream is bit for bit
    what an eager split gives, and the draw starts no device program of its
    own (eagerly, ``jax.random.split`` is five). Between calls the root
    lives here and nowhere else: ``get_state`` / ``set_state`` / ``seed``
    and eager ``next_key()`` see every split a program made. A block that
    raises, or never sets ``new_root_data``, leaves the stream where it was.

    A context manager and not a function that takes the call: the body runs
    in the caller's own frame. JAX's lowering of a first call was measured
    8x slower (24 s against 3 s for BERT-base's ``run_steps(8)`` on a v5e
    host) when the compiled function was called three Python frames deeper
    than ``step()`` itself (PERF.md, Findings, PR 30)."""
    global _key
    with _lock:
        draw = _ProgramDraw(_ensure_key_locked())
        yield draw
        if draw.new_root_data is not None:
            _key = jax.random.wrap_key_data(_host_view(draw.new_root_data),
                                            impl=jax.random.key_impl(draw.root))


def _host_view(data):
    """The bits of a key that a program returned, as the eager layer keeps
    its root: on one device and uncommitted, like ``jax.random.key``'s.

    A program's output is committed to the program's devices (under a mesh:
    replicated over all of them), and every eager op that mixed a subkey of
    it with an array elsewhere (``nd.random.*`` or Dropout on ``cpu(3)``)
    would be refused for incompatible devices. This takes the first
    addressable shard's buffer as it is: no program, no copy, no
    device-to-host read (and the raw bits, because unwrapping a typed key
    on the host costs more than the rest of this). JAX has no public
    constructor for an uncommitted array over an existing buffer, hence
    ``ArrayImpl``."""
    data = data.addressable_data(0)
    return ArrayImpl(data.aval, data.sharding, [data], committed=False)


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
