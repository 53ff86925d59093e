"""Guarded custom-kernel registry — the one gate every hand kernel runs
through (ROADMAP item 2; docs/pallas.md).

docs/perf_notes.md ends the XLA-level optimization story at two profiled
ceilings (RN50 conv fusions at ~76% of HBM bandwidth, BERT at ~56% MFU in
XLA's matmul fusions). Hand Pallas kernels are the named lever — but a hand
kernel that silently changes numerics, or silently runs an unverified code
path on a backend it was never tested on, is a worse defect class than the
ceilings it chases. This registry is the guard:

- every kernel registers as a ``(pallas_impl, xla_reference, tolerance)``
  triple; the reference is the *semantic contract* and the tolerance is the
  budget the implementation must meet (enforced by tests/test_pallas.py's
  interpret-mode parity gate over every registered kernel — a kernel
  without a passing parity gate cannot ship);
- dispatch auto-selects the custom path only where it is verified to run
  (``backends``), the shape is supported (``supports``), and the operator
  has not been killed (``MXNET_TPU_PALLAS=off``); everything else falls
  back to the XLA reference — journaled (``pallas_fallback`` records with a
  reason) and counted, never silent;
- per-op tier provenance (:func:`tier_provenance`) is a first-class
  output: ``bench.py --pallas {on,off,auto}`` stamps it into the BENCH
  artifact so an A/B number always says which tier produced it.

The registry — not any one kernel — is the subsystem's deliverable: future
hand kernels (int8 GEMMs, MoE dispatch) register here and inherit the
parity gate, the fallback matrix, and the journal story for free.
"""
from __future__ import annotations

import functools
import os
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["KernelSpec", "register_kernel", "get_kernel", "kernels",
           "dispatch", "declines", "mode", "set_mode", "tier_provenance",
           "reset_provenance", "MODES", "block_ok", "default_block"]

MODES = ("auto", "on", "off")
_PORTABLE = frozenset(("cpu", "gpu", "tpu"))

# The tiling rule of the 2D epilogue kernels, in one place for the kernels,
# the tuned-table check and the autotuner's space. The chip's compiler
# takes a block whose last two dims are multiples of (8, 128) or span the
# whole array dim; tests/test_chip_compile.py holds the rule to a v5e.
BLOCK_CAPS = (512, 256)


def block_ok(r: int, c: int, br: int, bc: int) -> bool:
    """True when the chip's compiler takes a (br, bc) block of an (r, c)
    array. The block need not divide the array: the grid is ``pl.cdiv``."""
    return (0 < br <= r and 0 < bc <= c
            and (br % 8 == 0 or br == r) and (bc % 128 == 0 or bc == c))


def default_block(r: int, c: int) -> Tuple[int, int]:
    """The built-in tiling: the whole dim where it is under the cap, else
    the cap (itself a multiple of the alignment)."""
    return min(r, BLOCK_CAPS[0]), min(c, BLOCK_CAPS[1])

_REGISTRY: Dict[str, "KernelSpec"] = {}
_lock = threading.Lock()
_mode_override: Optional[str] = None
# journal dedupe + provenance: dispatch runs per eager op call (and per
# trace under jit) — one journal line per (kernel, reason) per process,
# with full counts kept in the provenance table instead
_journaled: set = set()
_prov: Dict[str, Dict] = {}


@dataclass
class KernelSpec:
    """One guarded custom kernel: the impl, its semantic contract, and the
    selection gates.

    ``pallas_impl(*args, interpret=False, **params)`` and
    ``xla_reference(*args, **params)`` share one signature; parity within
    ``tolerance`` (max abs error on fp32-cast outputs) is enforced by the
    registration-time test gate over ``example()``'s representative
    arguments, so registering a kernel without a passing gate fails CI,
    and the tier can never silently change numerics."""

    name: str
    pallas_impl: Callable
    xla_reference: Callable
    tolerance: float
    backends: Tuple[str, ...] = ("tpu",)
    supports: Optional[Callable] = None   # (*args, **params) -> None | reason
    example: Optional[Callable] = None    # () -> (args, params) for the gate
    doc: str = ""
    differentiable: bool = True
    # (*args, **params) -> shape-class string ("RxC") | None: the tuned-
    # table key under which an autotuned block shape applies to a call.
    # None = the kernel takes no tuned knobs (autotune never touches it).
    tune_key: Optional[Callable] = None


def register_kernel(name: str, *, xla_reference: Callable, tolerance: float,
                    backends: Sequence[str] = ("tpu",),
                    supports: Optional[Callable] = None,
                    example: Optional[Callable] = None,
                    doc: str = "", differentiable: bool = True,
                    tune_key: Optional[Callable] = None):
    """Decorator registering ``fn`` as the custom impl of kernel ``name``."""
    def deco(fn):
        with _lock:
            if name in _REGISTRY:
                raise MXNetError(f"duplicate pallas kernel registration: "
                                 f"{name!r}")
            _REGISTRY[name] = KernelSpec(
                name=name, pallas_impl=fn, xla_reference=xla_reference,
                tolerance=float(tolerance), backends=tuple(backends),
                supports=supports, example=example,
                doc=doc or (fn.__doc__ or ""),
                differentiable=differentiable, tune_key=tune_key)
        return fn
    return deco


def get_kernel(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise MXNetError(
            f"pallas kernel {name!r} is not registered "
            f"({sorted(_REGISTRY)} known)") from None


def kernels() -> Dict[str, KernelSpec]:
    """Snapshot of the registry (name -> spec), for the parity gate."""
    from . import kernels as _k   # noqa: F401  (registration side effect)
    with _lock:
        return dict(sorted(_REGISTRY.items()))


# ---------------------------------------------------------------------------
# mode / backend resolution
# ---------------------------------------------------------------------------
def mode() -> str:
    """Effective tier mode: ``set_mode`` override, else the
    ``MXNET_TPU_PALLAS`` env knob, else ``auto``. A malformed knob value
    degrades to ``auto`` (journaled once) — a typo in an env var must
    never flip a training run onto an unverified path OR kill it."""
    if _mode_override is not None:
        return _mode_override
    raw = os.environ.get("MXNET_TPU_PALLAS", "auto").strip().lower()
    if raw in MODES:
        return raw
    _journal_once("__mode__", f"bad_mode:{raw}",
                  detail=f"MXNET_TPU_PALLAS={raw!r} not in {MODES}; "
                         f"using 'auto'")
    return "auto"


def set_mode(value: Optional[str]) -> None:
    """Process-level override of the env knob (``None`` resets). The
    bench A/B flag and tests use this; production selection should use
    the env var so child processes inherit it."""
    global _mode_override
    if value is not None and value not in MODES:
        raise MXNetError(f"pallas mode must be one of {MODES}; "
                         f"got {value!r}")
    _mode_override = value


def _backend() -> str:
    """Call-time backend name. ``jax.default_backend()`` here is a
    call-time dial like ops/contrib.py's — never at import (G1)."""
    import jax
    return jax.default_backend()


def runs_on(args) -> Tuple[str, bool]:
    """``(platform, staged)``: where a computation over ``args`` runs.

    Concrete operands: the platform they live on — on a TPU host an array
    made on ``mx.cpu()`` (the reference's default context) is computed on
    the host, whatever the process's default backend. Traced operands
    (inside ``jit``): the default backend with ``staged=True`` — the real
    platform is only known when the program is lowered, so a caller with a
    platform-specific kernel stages both paths with
    ``lax.platform_dependent`` and the lowering keeps the one that fits."""
    import jax
    arrays = [a for a in jax.tree_util.tree_leaves(args)
              if isinstance(a, jax.Array)]
    if arrays and not any(isinstance(a, jax.core.Tracer) for a in arrays):
        return next(iter(arrays[0].devices())).platform, False
    return _backend(), True


def _auto_partitioned(args, staged: bool) -> int:
    """Over how many devices the COMPILER would have to partition a kernel
    call on ``args`` (1 = not at all). It cannot partition a Mosaic kernel
    ("wrap the call in a shard_map"), so such a call falls back in the
    open. Partitioned by hand — inside a ``shard_map`` whose axes are all
    manual — every shard runs the kernel on its own block, which is fine.

    Traced operands: the non-manual axes of the abstract mesh inside a
    ``shard_map``; else the mesh of the enclosing ``parallel.use_mesh``
    scope (how ShardedTrainer, PipelinedTrainer and the tensor-parallel
    predictor trace their GSPMD programs); no scope means a one-device
    program. Concrete operands: the devices the arrays are laid out over."""
    import math

    import jax
    if not staged:
        return max(len(a.devices()) for a in jax.tree_util.tree_leaves(args)
                   if isinstance(a, jax.Array))
    am = jax.sharding.get_abstract_mesh()
    if not am.empty:
        return math.prod(am.shape[n] for n in am.axis_names
                         if n not in am.manual_axes)
    from ..parallel.mesh import active_mesh
    mesh = active_mesh()
    return 1 if mesh is None else int(mesh.devices.size)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def _journal_once(kernel: str, reason: str, **fields) -> None:
    key = (kernel, reason)
    with _lock:
        if key in _journaled:
            return
        _journaled.add(key)
    from ..diagnostics import get_journal
    get_journal().event("pallas_fallback", kernel=kernel, reason=reason,
                        **fields)


# tuned-table injection: one journal line per (kernel, shape_class)
# outcome — dispatch is per-op-call hot, the journal is not
_tuned_logged: set = set()


def _tuned_block(spec: "KernelSpec", args, params):
    """The tuned block for this dispatch, or None: consult the active
    tuned table (MXNET_TPU_TUNED_TABLE via autotune.table.tuned_for —
    cached, validated, never raises) at the kernel's shape class.  An
    entry the chip's compiler would refuse (:func:`block_ok`) is refused
    here with a journaled ``tuned_fallback`` (the kernels would clamp it
    anyway — refusing early keeps the journal truthful about what ran)."""
    from ..autotune import table as _tt
    doc = _tt.tuned_for("pallas")
    if doc is None:
        return None
    cls = spec.tune_key(*args, **params)
    if not cls:
        return None
    entry = _tt.pallas_entry(doc, spec.name, cls)
    blk = entry.get("block") if isinstance(entry, dict) else None
    if blk is None:
        return None
    log_key = (spec.name, cls)
    try:
        r, c = (int(v) for v in cls.split("x"))
        br, bc = int(blk[0]), int(blk[1])
        ok = block_ok(r, c, br, bc)
    except (TypeError, ValueError):
        ok = False
    with _lock:
        first = log_key not in _tuned_logged
        if first:
            _tuned_logged.add(log_key)
    if not ok:
        if first:
            from ..diagnostics import get_journal
            get_journal().event(
                "tuned_fallback", reason="invalid_block", site="pallas",
                kernel=spec.name, shape_class=cls, block=blk,
                fallback="builtin_defaults")
        return None
    if first:
        from ..diagnostics import get_journal
        get_journal().event("tuned_load", site="pallas", kernel=spec.name,
                            shape_class=cls, block=[br, bc])
    return (br, bc)


def _note(kernel: str, tier: str, reason: Optional[str] = None) -> None:
    with _lock:
        rec = _prov.setdefault(kernel, {"pallas": 0, "xla": 0,
                                        "fallback_reasons": {}})
        rec[tier] += 1
        if reason:
            rr = rec["fallback_reasons"]
            rr[reason] = rr.get(reason, 0) + 1


def tier_provenance() -> Dict[str, Dict]:
    """Per-kernel dispatch accounting since process start (or the last
    :func:`reset_provenance`): how many times each tier ran and why the
    XLA tier was chosen. Counts are per *dispatch decision* — once per
    eager op call, once per trace under jit — which is exactly the
    provenance a BENCH artifact needs ("which tier compiled into the
    measured program")."""
    with _lock:
        return {k: {"pallas": v["pallas"], "xla": v["xla"],
                    "fallback_reasons": dict(v["fallback_reasons"])}
                for k, v in sorted(_prov.items())}


def reset_provenance() -> None:
    with _lock:
        _prov.clear()
        _journaled.clear()
        _tuned_logged.clear()


def _gate(spec: "KernelSpec", m: str, args, params,
          interpret: bool) -> Tuple[Optional[str], bool]:
    """``(reason, staged)``: why :func:`dispatch` would run the reference
    on ``args`` (None: it would run the kernel), and whether the operands
    are traced. The selection order is :func:`dispatch`'s."""
    if m == "off":
        return "mode_off", False
    if spec.supports is not None:
        reason = spec.supports(*args, **params)
        if reason is not None:
            return reason, False
    if interpret:
        return None, False
    platform, staged = runs_on(args)
    if platform not in spec.backends:
        return f"backend:{platform}", staged
    # a kernel with every platform among its backends is plain JAX; the
    # others are Mosaic kernels, which only a TPU lowering takes and the
    # compiler cannot partition
    if not _PORTABLE <= set(spec.backends) \
            and (n := _auto_partitioned(args, staged)) > 1:
        return f"auto_partition:{n}dev", staged
    return None, staged


def declines(name: str, *args, **params) -> Optional[str]:
    """The reason :func:`dispatch` would fall back on these operands, or
    None where it would take the kernel; counts and journals nothing. For
    an op that has to choose its own form before the kernel is called (the
    embedding lookup, whose backward is the kernel). ``args`` may hold
    ``jax.ShapeDtypeStruct`` where an operand does not exist yet."""
    return _gate(get_kernel(name), mode(), args, params, False)[0]


def dispatch(name: str, *args, interpret: bool = False, **params):
    """Run kernel ``name``: the custom tier where it is verified to
    apply, the XLA reference everywhere else.

    Selection order (first hit wins, reason journaled once + counted):

    1. ``mode() == "off"`` — the kill switch beats everything, including
       ``interpret`` (an operator turning the tier off must get the
       reference, period).
    2. ``supports`` rejects the concrete shapes/dtypes — unsupported
       inputs fall back *before* the backend gate so the reason an
       operator sees on any host names the real blocker.
    3. the platform the operands run on (:func:`runs_on`) is not in
       ``spec.backends`` — unless ``interpret=True``, which runs the
       custom impl in interpret mode (the CPU parity gate's path; never
       the default on any backend).
    4. the compiler would have to partition the call over several devices
       (``auto_partition:<n>dev``, :func:`_auto_partitioned`): it refuses
       to partition a Mosaic kernel, so under a GSPMD mesh of more than one
       device the reference runs, and the kernel only inside a
       ``shard_map`` or on a one-device program.

    Inside ``jit`` the platform is only known at lowering, so there a
    kernel that is not portable is staged beside its reference with
    ``lax.platform_dependent``: the program lowered for a TPU holds the
    kernel, the same function lowered for the host CPU of a TPU machine
    holds the reference, and the compiler sees no conditional either way.
    The provenance count ``pallas`` then reads "the kernel, wherever the
    program is lowered for a platform the kernel has".

    ``mode() == "on"`` does not force an unsupported kernel onto the
    hardware — it makes every fallback LOUD (a ``RuntimeWarning`` on top
    of the journal line), for A/B runs that must not quietly measure the
    reference tier.
    """
    spec = get_kernel(name)
    m = mode()
    reason, staged = _gate(spec, m, args, params, interpret)
    mosaic = not _PORTABLE <= set(spec.backends)
    # dispatch decisions ride the active trace span (if any): a traced
    # step's span says which kernel tier compiled into it, and why a
    # fallback happened (docs/observability.md)
    from ..observability import trace as _trace
    if reason is None:
        # tuned tiling rides the pallas tier only — an explicit block=
        # always wins, the reference tier never sees injected knobs
        if spec.tune_key is not None and "block" not in params:
            blk = _tuned_block(spec, args, params)
            if blk is not None:
                params = dict(params, block=blk)
        _note(name, "pallas")
        _trace.annotate(**{f"pallas.{name}": "pallas"})
        if staged and mosaic:
            from jax import lax
            custom = functools.partial(spec.pallas_impl, **params)
            return lax.platform_dependent(
                *args,
                default=functools.partial(spec.xla_reference, **params),
                **{p: custom for p in spec.backends})
        return spec.pallas_impl(*args, interpret=interpret, **params)
    _note(name, "xla", reason)
    _trace.annotate(**{f"pallas.{name}": f"xla:{reason}"})
    _journal_once(name, reason, mode=m)
    if m == "on" and reason != "mode_off":
        import warnings
        warnings.warn(
            f"pallas kernel {name!r} fell back to the XLA reference "
            f"({reason}) despite MXNET_TPU_PALLAS=on", RuntimeWarning,
            stacklevel=2)
    return spec.xla_reference(*args, **params)
