"""Compiled-predictor cache — a bounded LRU of jitted executables.

The serving analog of ``CachedOp``: each entry is ONE jitted XLA
program for one padded shape ``(batch_bucket,) + feature_key``, built
through :func:`gluon.block.functional_apply` (the same predictor-
extraction primitive the sharded/pipelined trainers compile through).
Parameters enter the program as **runtime arguments**, so a hot-reload
that swaps parameter values retraces nothing — only a novel padded
shape compiles, and the bucket grid bounds how many of those exist.

The LRU bound makes the executable population bounded even when the
configured grid is large (a misconfigured 10^3-cell grid must degrade to
evictions, not to unbounded device-memory growth).  Counters
(hits/misses/evictions; misses == compiles) feed the per-batch journal
record and the compile-bound acceptance test.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict

import jax

from .. import _rng
from ..gluon.block import functional_apply
from ..parallel.mesh import use_mesh

__all__ = ["CompiledPredictor", "PredictorCache"]

_key_spec_memo = None


def key_spec():
    """Abstract (shape, dtype) of one serving PRNG key, computed ONCE
    per process (the first call consumes a single global-stream key).
    Both the AOT arg signature and the cache fingerprint read it, so a
    cold start and a warm start advance the global PRNG stream by the
    same amount — a per-operation ``next_key()`` here would skew the
    stream cold-vs-warm and cost a backend dial per cache lookup.  The
    impl (and so the dtype) is fixed per process by ``MXNET_PRNG_IMPL``;
    a mid-process reseed keeps it."""
    global _key_spec_memo
    if _key_spec_memo is None:
        k = _rng.next_key()
        _key_spec_memo = jax.ShapeDtypeStruct(k.shape, k.dtype)
    return _key_spec_memo


class CompiledPredictor:
    """One jitted inference program at one padded shape.

    ``__call__(x_padded)`` fetches the block's *current* parameter
    arrays (so a between-batches hot-reload is picked up with no
    recompile), threads a fresh PRNG key, and returns the flat tuple of
    output device arrays plus the traced output treedef.

    Two dispatch paths share one calling convention: the lazy
    ``jax.jit`` closure (compiles at first call — the historical path)
    and an ahead-of-time ``jax.stages.Compiled`` executable installed by
    :meth:`aot_compile` (an eager lower+compile) or
    :meth:`from_serialized` (a deserialized on-disk executable,
    ``serving/aotcache.py``).  Parameters stay runtime arguments on both
    paths, so the zero-retrace hot-reload contract is unchanged.

    With a :class:`~.shardplan.ShardPlan` the SAME program becomes a
    GSPMD tensor-parallel executable: parameters arrive already placed
    on the plan's mesh (their ``NamedSharding`` rides the runtime
    arguments on the lazy path and the abstract arg specs on the AOT
    path), the padded input is committed to the plan's activation
    sharding before dispatch, and XLA partitions the computation —
    no second code path, exactly one executable per padded shape.
    """

    def __init__(self, block, ctx=None, plan=None):
        self._block = block
        self._ctx = ctx
        self.plan = plan
        self._treedef = None
        self._compiled = None          # AOT executable when present
        self.aot = None                # None | "compiled" | "loaded"

        def fn(key, tr_datas, aux_datas, x):
            # trace under the plan's mesh, as the trainers do: mesh-aware
            # ops and the kernel tier read it (a Mosaic kernel is not
            # staged into a program the compiler partitions)
            scope = (contextlib.nullcontext() if plan is None
                     else use_mesh(plan.mesh))
            with scope:
                outs, treedef, _aux_new = functional_apply(
                    block, key, tr_datas, aux_datas, [x],
                    training=False, ctx=ctx)
            # inference never writes aux state back (BatchNorm running
            # stats stay frozen); treedef is captured at trace time
            self._treedef = treedef
            return tuple(outs)

        self._jitted = jax.jit(fn)

    def _runtime_args(self):
        trainable, aux = self._block._param_split()
        return ([p._data[0]._data for p in trainable],
                [p._data[0]._data for p in aux])

    @property
    def ready(self) -> bool:
        """True once an executable exists — a first call will NOT pay
        an XLA compile (the server's compile-span gate reads this)."""
        return self._compiled is not None

    def __call__(self, x_padded):
        tr_datas, aux_datas = self._runtime_args()
        key = _rng.next_key()
        if self.plan is not None:
            # commit the padded batch (and the key) to the plan's
            # shardings BEFORE dispatch so the lazy and AOT paths see
            # identical arg placements (one executable, either way in)
            x_padded = jax.device_put(
                x_padded, self.plan.activation_sharding(x_padded.shape))
            key = jax.device_put(key, self.plan.replicated())
        fn = self._compiled if self._compiled is not None else self._jitted
        outs = fn(key, tr_datas, aux_datas, x_padded)
        return outs, self._treedef

    # -- ahead-of-time path (serving/aotcache.py) ---------------------------
    def _arg_specs(self, x_shape, x_dtype):
        """Abstract arg signature of one padded-shape call: (key,
        trainable arrays, aux arrays, x) as ShapeDtypeStructs matching
        what ``__call__`` passes at runtime.  The key spec comes from
        the process-memoized :func:`key_spec` so its (impl-dependent)
        dtype is exact without consuming a stream key per build.

        Under a shard plan the specs carry shardings: parameters use the
        LIVE arrays' placements (the plan already landed them on the
        mesh), the input uses the plan's activation sharding, and the
        key replicates — so an AOT lowering partitions exactly like the
        lazy path's first call."""
        tr_datas, aux_datas = self._runtime_args()
        plan = self.plan

        def spec(a):
            if plan is None:
                return jax.ShapeDtypeStruct(a.shape, a.dtype)
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)

        ks = key_spec()
        if plan is not None:
            ks = jax.ShapeDtypeStruct(ks.shape, ks.dtype,
                                      sharding=plan.replicated())
            x_spec = jax.ShapeDtypeStruct(
                tuple(x_shape), x_dtype,
                sharding=plan.activation_sharding(tuple(x_shape)))
        else:
            x_spec = jax.ShapeDtypeStruct(tuple(x_shape), x_dtype)
        return (ks, [spec(a) for a in tr_datas],
                [spec(a) for a in aux_datas], x_spec)

    def aot_compile(self, x_shape, x_dtype) -> "CompiledPredictor":
        """Lower + compile at the padded shape ahead of the first call
        (tracing captures the output treedef as a side effect).  The
        resulting executable is bit-identical to what the lazy path
        would build — and is what :meth:`serialize_aot` persists."""
        lowered = self._jitted.lower(*self._arg_specs(x_shape, x_dtype))
        self._compiled = lowered.compile()
        self.aot = "compiled"
        return self

    def serialize_aot(self):
        """(executable payload bytes, pytree blob bytes) for the disk
        store.  Raises when the backend's compilation does not support
        serialization — the cache degrades to memory-only."""
        import pickle

        from jax.experimental import serialize_executable as _se
        if self._compiled is None:
            raise ValueError("predictor has no AOT executable to "
                             "serialize (call aot_compile first)")
        payload, in_tree, out_tree = _se.serialize(self._compiled)
        trees = pickle.dumps((in_tree, out_tree, self._treedef))
        return payload, trees

    @classmethod
    def from_serialized(cls, block, payload, trees, ctx=None, plan=None):
        """Rebuild a predictor from persisted bytes WITHOUT tracing or
        compiling.  ``payload``/``trees`` must already be CRC- and
        envelope-validated by the caller (serving/aotcache.py is the one
        read path; graftlint G21 enforces the discipline)."""
        import pickle

        from jax.experimental import serialize_executable as _se

        from ..diagnostics import guard
        obj = cls(block, ctx=ctx, plan=plan)
        in_tree, out_tree, treedef = pickle.loads(trees)
        # load onto the devices the program was compiled for — the plan's
        # own, or the default device of an unsharded lowering. Left to
        # default, the executable is spread over every local device and
        # refused at its first call on a host with more than it uses.
        devices = (list(plan.mesh.devices.flat) if plan is not None
                   else guard.devices(local=True)[:1])
        obj._compiled = _se.deserialize_and_load(
            payload, in_tree, out_tree, backend=devices[0].client,
            execution_devices=devices)
        obj._treedef = treedef
        obj.aot = "loaded"
        return obj


class PredictorCache:
    """Bounded LRU over :class:`CompiledPredictor` entries.

    ``get(key, builder)`` returns ``(entry, hit)``; a miss invokes
    ``builder()`` (the compile) and may evict the least-recently-used
    entry.  Dropping an entry releases the jitted closure, so the
    underlying XLA executable becomes collectable — the cache is the one
    owner.  Thread-safe, though the serving worker is the only caller in
    steady state."""

    def __init__(self, max_entries=16):
        if max_entries < 1:
            raise ValueError("PredictorCache needs max_entries >= 1")
        self.max_entries = int(max_entries)
        self._lru = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.last_build_s = None

    def get(self, key, builder):
        with self._lock:
            entry = self._lru.get(key)
            if entry is not None:
                self._lru.move_to_end(key)
                self.hits += 1
                return entry, True
        # build outside the lock: a multi-second XLA compile must not
        # block a stats() snapshot from another thread. (The XLA compile
        # itself happens at the entry's FIRST CALL — the server wraps
        # that in the timed compile_span; this build is just the trace
        # closure.)
        t0 = time.perf_counter()
        entry = builder()
        build_s = time.perf_counter() - t0
        with self._lock:
            raced = self._lru.get(key)
            if raced is not None:         # concurrent builder won
                self._lru.move_to_end(key)
                self.hits += 1
                return raced, True
            self.misses += 1
            self.last_build_s = round(build_s, 4)
            self._lru[key] = entry
            while len(self._lru) > self.max_entries:
                self._lru.popitem(last=False)
                self.evictions += 1
        return entry, False

    def drop_where(self, predicate) -> int:
        """Drop every entry whose key satisfies ``predicate`` (counted
        as evictions) — the tenant fleet's page-out/remove path: a cold
        or removed tenant's executables must not occupy LRU slots the
        hot tenants need.  Returns how many entries were dropped."""
        with self._lock:
            doomed = [k for k in self._lru if predicate(k)]
            for k in doomed:
                del self._lru[k]
            self.evictions += len(doomed)
            return len(doomed)

    def __len__(self):
        with self._lock:
            return len(self._lru)

    def clear(self):
        with self._lock:
            self._lru.clear()

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {"entries": len(self._lru),
                    "max_entries": self.max_entries,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "hit_rate": round(self.hits / total, 4) if total else None,
                    "last_build_s": self.last_build_s}
