"""Device time by named scope: which HLO instruction belongs to which
``jax.named_scope``.

On a TPU v5e the profiler's ``XLA Ops`` events carry no ``op_name`` stat
that ``jax.profiler.ProfileData`` exposes, so ``jax.named_scope`` alone
names no device time. The compiled program's own text does: every
instruction of ``lowered.compile().as_text()`` has ``metadata={op_name=
"jit(step)/.../mxnet_tpu.mamba2.ssd/..."}``, and the trace names every
event by its instruction. :func:`device_scopes` gives the first half of
that join for the programs the live trainers have built; a reader of the
trace (``chipbench/layer_metrics/device_scopes.py``) does the second.

Scopes are opened with :func:`..instrument.device_scope` (one prefix,
``mxnet_tpu.``). The innermost scope of an instruction is the last one in
its ``op_name``: backward and recomputed instructions keep the scope of
the forward code they come from (``transpose(jvp(mxnet_tpu.mlp))``). A
fusion takes the scope of its root instruction, because the compiler gives
a fusion one metadata and it may be that of any fused instruction.

Nothing here runs until :func:`device_scopes` is called: trainers are held
by weak reference, and a call lowers and compiles each of their programs
again (``ShardedTrainer.program_texts``; the persistent compile cache makes
that a reload). Parsing is stdlib-only.
"""
from __future__ import annotations

import itertools
import re
import weakref

from .instrument import ANNOTATION_PREFIX

__all__ = ["device_scopes", "scope_of", "scopes_of_program", "watch"]

# in the order they were registered (a WeakSet has none): callers take the
# last program listed for the newest trainer's
_trainers = weakref.WeakValueDictionary()
_registered = itertools.count()

_SCOPE = re.compile(re.escape(ANNOTATION_PREFIX) + r"([\w.]*\w)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")


def watch(trainer):
    """Register a trainer whose ``program_texts()`` :func:`device_scopes`
    reads while it lives."""
    _trainers[next(_registered)] = trainer


def scope_of(op_name):
    """The innermost ``mxnet_tpu.`` scope in an ``op_name``, without the
    prefix; ``None`` where there is none."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def scopes_of_program(text) -> dict:
    """``{"module": <HLO module name>, "scopes": {instruction: scope}}`` from
    optimized HLO text. Only instructions with a scope are listed; names are
    without the leading ``%``, as ``XLA Ops`` events have them after it."""
    module = None
    own, roots, fused_by = {}, {}, {}
    computation = None
    for line in text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTRUCTION.match(line)
        if m and computation is not None:
            is_root, name = m.groups()
            op_name = _OP_NAME.search(line)
            own[name] = scope_of(op_name.group(1)) if op_name else None
            if is_root:
                roots[computation] = name
            calls = _CALLS.search(line)
            if calls:
                fused_by[name] = calls.group(1)
            continue
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
        elif line.startswith("}"):
            computation = None
    scopes = {}
    for name, scope in own.items():
        if name in fused_by:
            scope = own.get(roots.get(fused_by[name])) or scope
        if scope:
            scopes[name] = scope
    return {"module": module, "scopes": scopes}


def device_scopes() -> dict:
    """``{program: {"module": ..., "scopes": {HLO instruction: innermost
    mxnet_tpu scope}}}`` for every program (``step``, ``run_steps(<k>)``) the
    live trainers have built, the oldest trainer first. With several trainers
    alive, a program name is prefixed by the trainer's position (``1:step``)."""
    out = {}
    for at, trainer in enumerate(list(_trainers.values())):
        for program, text in trainer.program_texts().items():
            out[program if at == 0 else f"{at}:{program}"] = \
                scopes_of_program(text)
    return out
