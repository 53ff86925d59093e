"""Device-dial guard — the ONE sanctioned path to JAX backend init.

Anything that touches the backend (``jax.devices()``, first array
creation, profiler start) initializes the device runtime, which takes
seconds and — when the chip is held by another process or the runtime is
unhealthy — can fail or stall. The reference never dials devices at
library load — per-device resources are built lazily by
``src/resource.cc``'s ResourceManager — and this module is the TPU-native
equivalent choke point:

- ``ensure_backend()`` is the in-process dial: journal breadcrumbs
  bracket the touch and a deadline timer dumps all-thread tracebacks if
  the dial stalls, so even an unkillable C-level hang leaves an
  attributable artifact.
- ``probe_backend()`` dials ``jax.devices()`` in a THROWAWAY subprocess
  under a hard deadline. A chip belongs to one process at a time, so this
  is only for callers that themselves never touch JAX — the
  ``python -m mxnet_tpu.diagnostics`` CLI. A process that will dial (a
  benchmark, a trainer, a server) must not probe first: while the child
  holds the chip the parent cannot have it, and each dial pays a runtime
  start for nothing.

Import-light by contract: jax is imported lazily inside functions, so
``import mxnet_tpu.diagnostics`` can run in processes that must never
risk a backend touch.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

from .journal import get_journal

__all__ = ["DeviceUnreachable", "probe_backend", "ensure_backend",
           "backend_dialed", "devices", "probe_deadline_s",
           "local_tpu_chips", "check_chip_children"]

DEFAULT_PROBE_DEADLINE_S = 150.0   # a healthy TPU runtime starts in ~10 s
DEFAULT_BACKOFF_S = (0.0,)         # one attempt unless the caller opts in

_PROBE_CODE = (
    "import json, sys\n"
    "import jax\n"
    "ds = jax.devices()\n"
    "print(json.dumps({'platform': ds[0].platform, 'n': len(ds),\n"
    "                  'kinds': sorted({d.device_kind for d in ds}),\n"
    "                  'process_index': jax.process_index(),\n"
    "                  'process_count': jax.process_count()}))\n"
)


def probe_deadline_s(deadline_s=None) -> float:
    """Resolve the probe deadline: explicit arg, else
    ``MXNET_TPU_PROBE_DEADLINE`` (seconds), else 150."""
    if deadline_s is not None:
        return float(deadline_s)
    env = os.environ.get("MXNET_TPU_PROBE_DEADLINE")
    try:
        return float(env) if env else DEFAULT_PROBE_DEADLINE_S
    except ValueError:
        return DEFAULT_PROBE_DEADLINE_S


class DeviceUnreachable(RuntimeError):
    """The backend did not answer within the deadline. Carries a
    machine-readable record (``to_dict()``) so callers can emit it on
    their one-structured-line artifact contract instead of dying with an
    information-free timeout."""

    def __init__(self, detail: str, deadline_s: float, attempts: int,
                 stderr_tail: str = ""):
        super().__init__(detail)
        self.detail = detail
        self.deadline_s = float(deadline_s)
        self.attempts = int(attempts)
        self.stderr_tail = stderr_tail[-500:]

    def to_dict(self) -> dict:
        return {"error": "device_unreachable", "detail": self.detail,
                "deadline_s": self.deadline_s, "attempts": self.attempts,
                "stderr_tail": self.stderr_tail}


def _parse_info_line(stdout: str):
    """Last parseable probe-info line of a probe child's stdout, or None.
    Malformed child output (a library spraying text or JSON-shaped logs
    onto stdout, a write truncated by a dying child) must degrade to
    a structured failure, never an exception or a bogus success — so
    the dict must carry the probe's required keys before it counts."""
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                info = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                continue
            if isinstance(info, dict) and "platform" in info \
                    and "n" in info:
                return info
    return None


def probe_backend(deadline_s=None, backoff_s=None, env=None,
                  _code=None) -> dict:
    """Dial ``jax.devices()`` in a throwaway subprocess under a hard
    deadline. Returns ``{"platform", "n", "kinds", "process_index",
    "process_count", "probe_s"}`` on success; raises
    :class:`DeviceUnreachable` after all attempts.

    Only for a caller that never touches JAX itself (the diagnostics
    CLI): the child takes the chip while it runs, and a parent that
    already holds it makes the child fail.

    ``backoff_s`` is a tuple of pre-attempt sleeps — its length is the
    attempt count. Each attempt's outcome is journaled, so a stderr tail
    shows *why*, not just rc.
    """
    deadline_s = probe_deadline_s(deadline_s)
    backoff_s = tuple(backoff_s) if backoff_s is not None else \
        DEFAULT_BACKOFF_S
    code = _code or _PROBE_CODE
    j = get_journal()
    last_err = ""
    for attempt, backoff in enumerate(backoff_s, start=1):
        if backoff:
            time.sleep(backoff)
        t0 = time.perf_counter()
        try:
            out = subprocess.run([sys.executable, "-c", code],
                                 capture_output=True, text=True, env=env,
                                 timeout=deadline_s)
        except subprocess.TimeoutExpired:
            last_err = (f"probe attempt {attempt}/{len(backoff_s)} timed "
                        f"out after {deadline_s:g}s")
            j.event("probe_timeout", attempt=attempt,
                    deadline_s=deadline_s)
            continue
        dt = time.perf_counter() - t0
        if out.returncode == 0:
            info = _parse_info_line(out.stdout)
            if info is not None:
                info["probe_s"] = round(dt, 1)
                j.event("probe_ok", attempt=attempt, **info)
                return info
            last_err = (f"probe attempt {attempt}/{len(backoff_s)}: rc=0 "
                        f"but no parseable JSON on stdout")
        else:
            last_err = (f"probe attempt {attempt}/{len(backoff_s)} failed "
                        f"rc={out.returncode}")
        j.event("probe_failed", attempt=attempt, rc=out.returncode,
                stderr_tail=out.stderr.strip()[-300:])
    raise DeviceUnreachable(
        f"jax.devices() did not answer within {deadline_s:g}s in any of "
        f"{len(backoff_s)} attempt(s) (backoffs {backoff_s}s); last: "
        f"{last_err}", deadline_s, len(backoff_s), last_err)


_dial_lock = threading.RLock()
_backend_info: dict | None = None


def backend_dialed() -> bool:
    """True once :func:`ensure_backend` has completed in this process."""
    return _backend_info is not None


def ensure_backend(deadline_s=None, tag=None) -> dict:
    """Initialize (or confirm) the JAX backend through the guarded path.

    - Cached: after the first success this returns immediately, so
      routing hot paths (the RNG global key, profiler start) through it
      costs one dict lookup.
    - The in-process dial is bracketed by journal breadcrumbs, and a
      deadline timer dumps all-thread faulthandler tracebacks into the
      journal if the dial stalls — an rc:124 artifact then carries
      ``backend_dial`` as the last-known phase plus the hung stack.

    Returns ``{"platform", "n", "dial_s", ...}``.
    """
    global _backend_info
    if _backend_info is not None:
        return _backend_info
    with _dial_lock:
        if _backend_info is not None:
            return _backend_info
        deadline = probe_deadline_s(deadline_s)
        j = get_journal()
        stalled = threading.Event()

        def _on_stall():
            stalled.set()
            from .watchdog import _all_thread_tracebacks
            j.event("backend_dial_stall", tag=tag, deadline_s=deadline,
                    tracebacks=_all_thread_tracebacks())

        timer = threading.Timer(deadline, _on_stall)
        timer.daemon = True
        # set-up stage `backend_start` (docs/observability.md); the import
        # waits for the dial: this package loads nothing of mxnet_tpu
        from ..observability.instrument import setup_stage
        with j.phase("backend_dial"), setup_stage("backend_start"):
            j.event("backend_dial_begin", tag=tag, deadline_s=deadline)
            timer.start()
            t0 = time.perf_counter()
            try:
                import jax
                devs = jax.devices()   # graftlint: disable=G4 this IS the guard
                info = {"platform": devs[0].platform, "n": len(devs),
                        "dial_s": round(time.perf_counter() - t0, 1)}
            finally:
                timer.cancel()
            if stalled.is_set():
                j.event("backend_dial_recovered", tag=tag)
            j.event("backend_ok", tag=tag, **info)
        _backend_info = info
        return info


def devices(local: bool = False, backend: str | None = None):
    """The sanctioned live device list — what static rule G4 points
    every direct ``jax.devices()`` call site at. The first call pays one
    guarded dial (:func:`ensure_backend`: journaled, deadline-timed);
    afterwards the probe is a cached-client lookup. ``local=True``
    returns only this process's addressable devices (in multi-host jobs
    ``jax.devices()`` lists the whole job's). ``backend`` names a
    platform ("cpu", "tpu"); left None it is the default backend, which
    on a TPU host lists no CPU device. A backend this process does not
    have raises ``RuntimeError``, as JAX does."""
    ensure_backend(tag="device-list")
    import jax
    if local:
        # graftlint: disable=G4 sanctioned accessor
        return jax.local_devices(backend=backend)
    return jax.devices(backend)     # graftlint: disable=G4 sanctioned accessor


def local_tpu_chips() -> int:
    """TPU chips attached to this host, counted on the PCI bus — what JAX
    itself reads before it decides to load libtpu. Initializes no backend
    and takes no chip."""
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def check_chip_children(envs, what: str) -> None:
    """One process for each chip: refuse to start local child processes
    that would fight over this host's chips.

    ``envs`` holds one environment per child about to start (None = this
    process's). A child pinned to ``JAX_PLATFORMS=cpu`` never needs a chip
    and is not counted. The others would each take EVERY chip of the host
    at their first JAX call — nothing here hands a child a chip of its
    own — so on a host with chips at most one may start, and none when
    this process has already initialized a backend and so holds them.
    The way to use several chips from one host is one process that drives
    them all (a mesh, or in-process replicas, each on its own device)."""
    need = sum(
        1 for e in envs
        if (os.environ if e is None else e)
        .get("JAX_PLATFORMS", "").strip().lower() != "cpu")
    if not need or not local_tpu_chips():
        return
    from jax._src import xla_bridge
    held = (xla_bridge.backends_are_initialized()
            and "tpu" in xla_bridge.backends())
    if held or need > 1:
        raise RuntimeError(
            f"{what}: refusing to start {need} local child process(es) "
            f"that may use the TPU — "
            + ("this process has initialized a JAX backend and holds the "
               "host's chips; " if held else
               "each would take every chip of this host; ")
            + "a chip belongs to one process at a time and no child is "
              "assigned its own. Use one process for the host's chips "
              "(a mesh, or in-process replicas), or pin the children to "
              "JAX_PLATFORMS=cpu.")


def _reset_for_tests() -> None:
    global _backend_info
    _backend_info = None
