"""``mx.runtime`` — build/runtime feature detection
(ref: python/mxnet/runtime.py Features/feature_list over libinfo.cc), and
where an entry point keeps JAX's persistent compilation cache."""
from __future__ import annotations

import os

from .base import MXNetError

__all__ = ["Feature", "Features", "feature_list", "enable_compile_cache",
           "DEVICE_PEAKS", "device_peaks", "device_record", "tpu_devices",
           "NoAccelerator"]

# Published peaks of one chip, keyed by the ``device_kind`` JAX reports —
# the one table every utilization, roofline share or ceiling in the tree
# divides by. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def device_peaks(device) -> dict:
    """Peaks of ``device`` (a ``jax.Device``). A device the table does not
    know is an error, not a default: a ratio against a guessed peak reads
    like a measurement and is not one."""
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise MXNetError(
            f"no published peaks for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); known: "
            f"{sorted(DEVICE_PEAKS)}") from None


class NoAccelerator(MXNetError):
    """A measurement found no TPU to run on."""


def device_record(devices) -> dict:
    """The device a result ran on, as every result line names it."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def tpu_devices(metric: str):
    """The devices of this process, for an entry point that measures
    ``metric`` on them. Raises :class:`NoAccelerator` on any other backend:
    a device number is measured on a device, and a job shrunk to fit a CPU
    printed under the same name is not that number."""
    from .diagnostics import guard
    devices = guard.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"{metric} is a device metric and JAX found "
            f"{device_record(devices)}; it is not measured on a CPU")
    return devices


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    For entry points (``chip_smoke.py``, ``bench.py``, ``benchmarks/``,
    ``examples/``, ``python -m mxnet_tpu.serving``) to call before their
    first compile — never at package import, so a library user and the
    test suite keep whatever they configured. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing is
    done; else the cache goes to ``<checkout>/.jax_cache``. The path is
    part of the cache's key, so it is fixed: a directory that moves (a
    temp dir, a pid, a date) never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    from .diagnostics import guard
    feats = {}
    platforms = {d.platform for d in guard.devices()}
    feats["TPU"] = "tpu" in platforms
    feats["CUDA"] = bool(platforms & {"gpu", "cuda"})
    feats["CPU"] = True
    feats["BLAS_OPEN"] = True              # via XLA's host backend
    feats["F16C"] = True                   # bf16/fp16 via XLA
    try:
        import cv2  # noqa: F401
        feats["OPENCV"] = True
    except ImportError:
        feats["OPENCV"] = False
    try:
        from . import _native
        feats["NATIVE_IO"] = _native.get_lib() is not None
    except Exception:
        feats["NATIVE_IO"] = False
    feats["DIST_KVSTORE"] = True           # jax.distributed path
    from jax.experimental.pallas.ops.tpu import flash_attention  # noqa: F401
    feats["PALLAS_FLASH_ATTENTION"] = True
    try:
        import onnx  # noqa: F401
        feats["ONNX"] = True
    except ImportError:
        feats["ONNX"] = False
    feats["INT8_QUANTIZATION"] = False     # calibration only this round
    return feats


class Features(dict):
    """ref: runtime.Features — dict of Feature with is_enabled()."""

    def __init__(self):
        super().__init__({name: Feature(name, on)
                          for name, on in _detect().items()})

    def is_enabled(self, name):
        name = name.upper()
        return name in self and self[name].enabled

    def __repr__(self):
        return "[" + ", ".join(repr(f) for f in self.values()) + "]"


def feature_list():
    return list(Features().values())
