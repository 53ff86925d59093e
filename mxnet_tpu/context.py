"""Device context model.

Re-design of the reference's ``Context`` (ref: include/mxnet/base.h struct
Context; python/mxnet/context.py) for TPU: a Context names a logical device
(`cpu`, `gpu`, `tpu`, plus the reference's pinned/shared CPU variants) and
resolves to a concrete ``jax.Device``. Per the north star, ``mx.tpu()`` is a
first-class Context so scripts port by swapping ``ctx=mx.tpu()``.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared",
           "current_context", "num_gpus", "num_tpus", "gpu_memory_info"]


class Context:
    """A logical device. Mirrors the reference API: ``Context(kind, device_id)``,
    comparable/hashable, usable as a ``with`` scope to set the default device
    (ref: python/mxnet/context.py Context.__enter__).
    """

    # device type codes keep the reference's numbering, with TPU appended
    # (ref: include/mxnet/base.h Context::DeviceType)
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        elif isinstance(device_type, str):
            if device_type not in Context.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        else:
            self.device_typeid = int(device_type)
            self.device_id = device_id

    @property
    def device_type(self) -> str:
        typ = Context.devtype2str[self.device_typeid]
        # pinned/shared CPU collapse onto plain host memory on TPU systems
        return typ

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    __repr__ = __str__

    # -- resolution onto jax ------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """Resolve this Context to a concrete jax.Device."""
        return _resolve_device(self.device_type, self.device_id)

    def __enter__(self):
        if not hasattr(Context._default, "stack"):
            Context._default.stack = []
        Context._default.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default.stack.pop()

    def empty_cache(self):
        """Release cached device memory (ref: Storage pool ReleaseAll via
        MXStorageEmptyCache). XLA owns pooling; best-effort no-op."""
        try:
            self.jax_device.client.defragment()  # pragma: no cover
        except Exception:
            pass


def _platform_devices(platform: str):
    """Process-LOCAL devices of a platform, asked of that backend by name.
    A Context must resolve to an addressable device: in multi-process jobs
    jax.devices() lists the whole job's devices but only local ones accept
    transfers; and the default backend's list alone has, on a TPU host, no
    CPU device in it."""
    from .diagnostics import guard
    try:
        return guard.devices(local=True, backend=platform)
    except RuntimeError:        # this process has no such backend
        return []


def _resolve_device(device_type: str, device_id: int) -> jax.Device:
    """A Context resolves to a device of the platform it names or raises:
    ``mx.cpu()`` is never quietly an accelerator, nor ``mx.tpu()`` anything
    but a TPU."""
    if device_type in ("cpu", "cpu_pinned", "cpu_shared"):
        devs = _platform_devices("cpu")
        if not devs:
            raise MXNetError(
                "no CPU devices visible to JAX (JAX_PLATFORMS="
                f"{jax.config.jax_platforms!r} leaves the cpu backend out)")
        # the host is one memory: every cpu(i) beyond the devices JAX
        # exposes is the last of them (the reference accepts any id)
        return devs[min(device_id, len(devs) - 1)]
    if device_type == "tpu":
        devs = _platform_devices("tpu")
        if not devs:
            raise MXNetError("no TPU devices visible to JAX")
        if device_id >= len(devs):
            raise MXNetError(f"tpu({device_id}) out of range: {len(devs)} devices")
        return devs[device_id]
    if device_type == "gpu":
        devs = _platform_devices("gpu")
        if devs:
            return devs[device_id]
        # Compatibility affordance: scripts written for the reference use
        # mx.gpu(i); on a TPU system map them onto its chips so they run
        # unmodified (documented divergence).
        devs = _platform_devices("tpu")
        if devs:
            return devs[min(device_id, len(devs) - 1)]
        raise MXNetError("no GPU/accelerator devices visible to JAX")
    raise MXNetError(f"unknown device type {device_type!r}")


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """TPU context (new in this framework; the north-star API addition)."""
    return Context("tpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def num_gpus() -> int:
    """ref: mx.context.num_gpus; counts accelerators on TPU systems."""
    return len(_platform_devices("gpu"))


def num_tpus() -> int:
    return len(_platform_devices("tpu"))


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes, ref: mx.context.gpu_memory_info."""
    # device-memory queries dial the backend; guard them so the touch is
    # journaled (docs/diagnostics.md)
    from .diagnostics import guard
    guard.ensure_backend(tag="device-memory-info")
    dev = _resolve_device("gpu", device_id)
    stats = getattr(dev, "memory_stats", lambda: None)()
    if stats:
        total = stats.get("bytes_limit", 0)
        used = stats.get("bytes_in_use", 0)
        return (total - used, total)
    return (0, 0)


def current_context() -> Context:
    """The default context (ref: Context::CurrentContext via with-scopes).
    Defaults to cpu(0) like the reference."""
    stack = getattr(Context._default, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def default_ctx_for_accel() -> Context:
    """Best training context on this host: tpu(0) if present else cpu(0)."""
    return tpu(0) if _platform_devices("tpu") else cpu(0)
