"""Persistent AOT executable cache — zero-cold-start serving restarts.

The bucket grid bounds how many XLA programs a serving process compiles
(PR 4), but every process still pays them from scratch: a rolling
``restart()`` recompiles the whole bucket lattice under live traffic,
and a cold tenant's page-in repays multi-second compiles the page-out
threw away.  This module turns the bounded-*compile* guarantee into a
bounded-*startup* guarantee: the in-memory :class:`~.cache.PredictorCache`
LRU is backed by an on-disk store of serialized AOT executables
(``jax.experimental.serialize_executable`` under the hood), so a
restart — or a tenant page-in, or a fresh pool worker — *loads* its
executables instead of compiling them.

Key schema (docs/serving.md): an entry is addressed by

- the **padded input shape** ``(batch bucket,) + feature key`` and
  request **dtype** — one executable per bucket-grid cell, exactly the
  in-memory cache's granularity;
- a **param-tree structure fingerprint** — block class + repr + the
  structural parameter names/shapes/dtypes + the PRNG key dtype.
  Parameter *values* stay runtime arguments (the PR-4 zero-retrace
  contract), so a hot-reload keeps hitting the same entries.

Every entry carries a **compatibility envelope** (jax/jaxlib versions,
backend platform, device kind, local device count): an entry written by
a different toolchain or topology is *invalidated* (degrades to a
compile), never loaded.  Entries commit atomically via
``resilience.atomic`` with CRC section manifests (serving/aot_report.py
owns the byte format); the read path validates magic, bounds, header
CRC, envelope, and section CRCs **before** any deserializer sees a byte
(graftlint G21).  A corrupt, truncated, or stale entry journals an
``aot_fallback`` and compiles normally — never wrong numerics
(loaded-vs-compiled bit parity is test-gated).  The directory is LRU
garbage-collected under a byte budget.

Knobs: ``MXNET_TPU_AOT_CACHE_DIR`` (the store root; unset = disabled),
``MXNET_TPU_AOT_CACHE_BYTES`` (GC budget, default 1 GiB),
``MXNET_TPU_AOT_CACHE`` = ``rw|ro|off`` (``ro`` loads but never writes
— immutable deploy images; ``off`` is the kill switch; malformed
degrades to ``rw``, journaled).
"""
from __future__ import annotations

import hashlib
import os
import threading
import time

import numpy as np

from ..diagnostics.journal import get_journal
from ..observability import instrument as _obs
from ..resilience import atomic as _atomic
from . import aot_report as _fmt
from .cache import CompiledPredictor

__all__ = ["AOTCache"]

_MODES = ("rw", "ro", "off")
DEFAULT_BUDGET = 1 << 30


def _env_bytes():
    try:
        return int(os.environ.get("MXNET_TPU_AOT_CACHE_BYTES",
                                  DEFAULT_BUDGET))
    except ValueError:
        return DEFAULT_BUDGET


def _bump(event: str) -> None:
    """One ``mxnet_tpu_aot_cache_events{event}`` counter tick (lazy
    registry import: the module stays cheap when the cache is idle)."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        "mxnet_tpu_aot_cache_events",
        "persistent AOT executable cache counters "
        "(hit/miss/store/fallback/evict)",
        ("event",)).labels(event=event).inc()


class AOTCache:
    """On-disk tier behind the in-memory predictor LRU (see module
    docstring).  One instance per Server/Fleet; safe for concurrent
    processes on one directory (pid-unique atomic staging, whole-file
    commits, CRC-checked reads)."""

    def __init__(self, root, max_bytes=None, mode=None):
        self.root = os.fspath(root)
        os.makedirs(self.root, exist_ok=True)
        raw_mode = mode if mode is not None else \
            os.environ.get("MXNET_TPU_AOT_CACHE", "rw")
        if raw_mode not in _MODES:
            get_journal().event("aot_cache_bad_mode", mode=str(raw_mode),
                                fallback="rw")
            raw_mode = "rw"
        self.mode = raw_mode
        self.max_bytes = _env_bytes() if max_bytes is None \
            else int(max_bytes)
        self._envelope = None
        self._lock = threading.Lock()
        self.counters = {"hits": 0, "misses": 0, "stores": 0,
                         "store_failures": 0, "fallbacks": 0,
                         "evictions": 0}
        # crashed writers' staging litter from a previous incarnation
        _atomic.sweep_tmp(self.root)

    @classmethod
    def maybe(cls, root) -> "AOTCache | None":
        """Construct unless disabled: falsy root or the ``off`` kill
        switch return None (callers keep the compile-only path)."""
        if not root:
            return None
        if os.environ.get("MXNET_TPU_AOT_CACHE") == "off":
            return None
        return cls(root)

    # -- identity ----------------------------------------------------------
    def envelope(self) -> dict:
        """The compatibility envelope stamped on every entry — computed
        once per instance (one guarded backend dial)."""
        if self._envelope is None:
            import jax
            import jaxlib

            from ..diagnostics import guard
            dev = guard.devices(local=True)
            self._envelope = {
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "platform": dev[0].platform,
                "device_kind": dev[0].device_kind,
                "n_local": len(dev),
            }
        return self._envelope

    @staticmethod
    def fingerprint(block, x_dtype, plan=None) -> str:
        """Param-tree *structure* fingerprint: block identity (class +
        repr — layer configs/activations print there) + structural
        parameter names/shapes + the runtime array shapes/dtypes in
        ``_param_split`` order + the PRNG key dtype (the impl bakes a
        different program).  Parameter VALUES are absent by design:
        hot-reload swaps values, never the program.

        With a shard plan the mesh signature + rule set join the key
        material (``plan.fingerprint_token``) — a tensor-parallel
        executable is only valid on its exact mesh shape, and the same
        model served single-device and sharded must occupy two entries.
        ``plan=None`` contributes NOTHING to the hash, byte-identical to
        the pre-plan scheme, so existing caches stay warm.

        Memoized on the block (``__dict__`` directly — bypasses Block's
        attribute registration): page-in restores call this once per
        warm shape on the worker thread, and repr + a full param walk
        per call is real stall time.  The memo dies with the block;
        post-hoc structural mutation (``cast``, added children) changes
        the runtime arg avals, which the AOT executable's own argument
        check rejects loudly — staleness can't reach numerics."""
        plan_token = None if plan is None else plan.fingerprint_token()
        dt_key = (str(np.dtype(x_dtype)), plan_token)
        memo = block.__dict__.setdefault("_aot_fp_memo", {})
        got = memo.get(dt_key)
        if got is not None:
            return got
        from .cache import key_spec
        parts = [f"{type(block).__module__}.{type(block).__qualname__}",
                 repr(block), dt_key[0]]
        if plan_token is not None:
            parts.append(f"plan:{plan_token}")
        names = block._structural_names()
        parts.append("|".join(
            f"{k}:{tuple(p.shape) if p.shape else ()}"
            for k, p in sorted(names.items())))
        trainable, aux = block._param_split()
        for tag, params in (("tr", trainable), ("aux", aux)):
            for p in params:
                d = p._data[0]._data
                parts.append(f"{tag}:{tuple(d.shape)}:{d.dtype}")
        parts.append(str(key_spec().dtype))
        raw = "\x1f".join(parts).encode("utf-8", "replace")
        memo[dt_key] = hashlib.sha1(raw).hexdigest()
        return memo[dt_key]

    def entry_path(self, block, shape, dtype, plan=None) -> str:
        fp = self.fingerprint(block, dtype, plan=plan)
        digest = hashlib.sha1(
            f"{fp}|{tuple(shape)}|{np.dtype(dtype)}".encode()).hexdigest()
        return os.path.join(self.root, f"aot-{digest[:24]}{_fmt.SUFFIX}")

    # -- read path ---------------------------------------------------------
    def load(self, block, shape, dtype, ctx=None,
             site="serving_predictor", plan=None):
        """Return a loaded :class:`CompiledPredictor` or None (cold
        miss / invalidated entry).  Never raises for a bad entry: every
        failure past existence journals an ``aot_fallback`` with its
        reason and the caller compiles normally."""
        path = self.entry_path(block, shape, dtype, plan=plan)
        if not os.path.exists(path):
            self._note("misses", "miss")
            return None
        header, sections, reason = _fmt.read_entry(path)
        if header is None:
            return self._fallback(path, reason)
        if header.get("envelope") != self.envelope():
            return self._fallback(path, "envelope",
                                  entry_envelope=header.get("envelope"))
        payload = sections.get("exec")
        trees = sections.get("trees")
        if payload is None or trees is None:
            return self._fallback(path, "missing_section")
        try:
            with _obs.aot_load_span(site, path=path,
                                    bytes=len(payload) + len(trees),
                                    shape=list(shape)):
                pred = CompiledPredictor.from_serialized(
                    block, payload, trees, ctx=ctx, plan=plan)
        except Exception as exc:
            return self._fallback(path,
                                  f"deserialize:{type(exc).__name__}")
        self._note("hits", "hit")
        self._touch(path)
        return pred

    def _fallback(self, path, reason, **extra):
        self._note("fallbacks", "fallback")
        with self._lock:
            self.counters["misses"] += 1
        _bump("miss")
        get_journal().event("aot_fallback", path=path, reason=reason,
                            **extra)
        return None

    @staticmethod
    def _touch(path) -> None:
        """Refresh mtime so the LRU GC sees a load as recency (best
        effort — a read-only image just stays in FIFO order)."""
        try:
            os.utime(path)
        except OSError:
            pass

    # -- write path --------------------------------------------------------
    def store(self, pred, block, shape, dtype, plan=None) -> bool:
        """Persist one AOT-compiled predictor (no-op in ``ro`` mode).
        A backend that cannot serialize its executables degrades to
        memory-only caching, journaled once per store attempt."""
        if self.mode != "rw":
            return False
        path = self.entry_path(block, shape, dtype, plan=plan)
        t0 = time.perf_counter()
        try:
            payload, trees = pred.serialize_aot()
            key_doc = {"shape": list(shape),
                       "dtype": str(np.dtype(dtype)),
                       "fingerprint": self.fingerprint(block, dtype,
                                                       plan=plan)}
            if plan is not None:
                key_doc["shard_plan"] = plan.fingerprint_token()
            blob = _fmt.pack_entry(
                {"envelope": self.envelope(), "key": key_doc,
                 "created": time.time()},
                {"exec": payload, "trees": trees})
            with _atomic.atomic_write(path, "wb") as f:
                f.write(blob)
        except Exception as exc:
            self._note("store_failures", "store_failure")
            get_journal().event("aot_store_failed", path=path,
                                error=type(exc).__name__,
                                detail=str(exc)[:300])
            return False
        self._note("stores", "store")
        get_journal().event("aot_store", path=path, bytes=len(blob),
                            shape=list(shape),
                            ms=round((time.perf_counter() - t0) * 1e3, 2))
        self.gc()
        return True

    # -- the one entry point the serving cache uses ------------------------
    def load_or_compile(self, block, shape, dtype, ctx=None,
                        site="serving_predictor", plan=None):
        """Disk-first predictor build: a valid entry loads (``aot_load``
        span, no compile); otherwise compile eagerly at the padded shape
        (``xla_compile`` span, same site family as the lazy path) and
        write through.  ``plan`` keys (and shards) the executable — a
        tensor-parallel replica restarting on the same mesh loads its
        partitioned programs with zero XLA compiles."""
        pred = self.load(block, shape, dtype, ctx=ctx, site=site,
                         plan=plan)
        if pred is not None:
            return pred
        pred = CompiledPredictor(block, ctx=ctx, plan=plan)
        with _obs.compile_span(site, shape=list(shape),
                               dtype=str(np.dtype(dtype)), aot=True):
            pred.aot_compile(tuple(shape), dtype)
        self.store(pred, block, shape, dtype, plan=plan)
        return pred

    # -- bookkeeping -------------------------------------------------------
    def _note(self, counter, event) -> None:
        with self._lock:
            self.counters[counter] += 1
        _bump(event)

    def gc(self) -> dict:
        """Evict least-recently-used entries until the directory fits
        the byte budget.  Concurrent writers/GCs tolerate each other
        (unlink races are suppressed; atomic staging litter is not an
        entry)."""
        entries = []
        total = 0
        try:
            names = os.listdir(self.root)
        except OSError:
            return {"evicted": 0, "bytes": 0}
        for name in names:
            if not name.endswith(_fmt.SUFFIX):
                continue
            path = os.path.join(self.root, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, path))
            total += st.st_size
        evicted = freed = 0
        if total > self.max_bytes:
            for _mtime, size, path in sorted(entries):
                if total - freed <= self.max_bytes:
                    break
                try:
                    os.unlink(path)
                except OSError:
                    continue
                evicted += 1
                freed += size
            if evicted:
                with self._lock:
                    self.counters["evictions"] += evicted
                for _ in range(evicted):
                    _bump("evict")
                get_journal().event("aot_gc", evicted=evicted,
                                    bytes_freed=freed,
                                    budget=self.max_bytes)
        return {"evicted": evicted, "bytes": total - freed}

    def stats(self) -> dict:
        with self._lock:
            c = dict(self.counters)
        return {"dir": self.root, "mode": self.mode,
                "max_bytes": self.max_bytes, **c}
