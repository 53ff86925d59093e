"""Device-mesh construction — the TPU-native substrate for every parallelism.

The reference discovers topology per-backend: CUDA P2P probing for
``CommDevice`` (ref: src/kvstore/comm.h EnableP2P), NCCL ring setup for
``KVStoreNCCL`` (ref: src/kvstore/kvstore_nccl.h), DMLC env wiring for
ps-lite clusters (ref: 3rdparty/ps-lite/src/postoffice.cc). On TPU all of
that collapses to ONE object: a ``jax.sharding.Mesh`` over the pod slice.
Collectives ride ICI within a slice and DCN across slices; XLA picks the
ring/tree schedule (the reference's ``CommDeviceTree`` heuristics are the
compiler's job here).

Axis-name conventions used throughout the framework:
  ``data``   — data parallel (batch dim)
  ``model``  — tensor/model parallel (hidden dims)
  ``seq``    — sequence/context parallel (ring attention)
  ``pipe``   — pipeline stages
"""
from __future__ import annotations

import math
from contextlib import contextmanager

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError

__all__ = ["make_mesh", "current_mesh", "default_mesh", "use_mesh",
           "data_parallel_spec", "mesh_signature", "replicated",
           "PartitionSpec", "NamedSharding", "Mesh"]

_mesh_stack = []


def make_mesh(axes=None, devices=None) -> Mesh:
    """Build a named device mesh.

    ``axes`` is an ordered mapping / list of (name, size) pairs; a size of
    ``-1`` absorbs the remaining devices (like a reshape). Default: all
    visible devices on one ``data`` axis — the reference's default
    data-parallel layout (``ctx=[mx.gpu(i) for i in ...]``,
    ref: python/mxnet/module/executor_group.py DataParallelExecutorGroup).
    """
    if devices is None:
        from ..diagnostics import guard
        devices = guard.devices()
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    if isinstance(axes, dict):
        items = list(axes.items())
    else:
        items = [(k, v) for k, v in axes]
    names = [k for k, _ in items]
    sizes = [v for _, v in items]
    n_fixed = math.prod(s for s in sizes if s != -1)
    for i, s in enumerate(sizes):
        if s == -1:
            sizes[i] = n // n_fixed
    if math.prod(sizes) != n:
        raise MXNetError(
            f"mesh axes {dict(zip(names, sizes))} do not tile the "
            f"{n} visible devices")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def default_mesh() -> Mesh:
    return make_mesh()


def current_mesh() -> Mesh:
    """The innermost ``use_mesh`` scope, or a fresh all-``data`` mesh."""
    if _mesh_stack:
        return _mesh_stack[-1]
    return default_mesh()


def active_mesh():
    """The innermost ``use_mesh`` scope, or None outside any — unlike
    :func:`current_mesh` it builds nothing and touches no device."""
    return _mesh_stack[-1] if _mesh_stack else None


@contextmanager
def use_mesh(mesh: Mesh):
    """Scope a mesh as the framework-wide default (analog of the reference's
    kvstore-type selection picking the comm topology)."""
    _mesh_stack.append(mesh)
    try:
        yield mesh
    finally:
        _mesh_stack.pop()


def data_parallel_spec(mesh: Mesh, ndim: int, batch_axis: int = 0):
    """PartitionSpec sharding ``batch_axis`` over every data-like mesh axis
    present (``data`` and, if defined, ``pipe``-free batch splitting)."""
    spec = [None] * ndim
    if "data" in mesh.axis_names:
        spec[batch_axis] = "data"
    return PartitionSpec(*spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def mesh_signature(mesh: Mesh) -> dict:
    """JSON-able identity of a mesh — device count + axis sizes — for
    journal records and checkpoint metadata (the elastic tier logs the
    before/after shapes of a survivor rebuild, docs/elastic.md)."""
    return {"devices": int(mesh.devices.size),
            "axes": {name: int(mesh.shape[name])
                     for name in mesh.axis_names}}
