"""JAX-hazard rules (G-codes) — project-specific semantics grounded in
defects this repo actually shipped:

- G1: the round-4/5 wedge class itself — ``_rng.py`` dialed the backend
  at module scope, so ``import mxnet_tpu`` initialized the device
  runtime (and hung with it, when it was unhealthy) before any guard
  could run.
- G4/G6: ``engine.waitall`` probed devices directly and swallowed every
  failure silently (the anti-pattern the diagnostics journal exists to
  kill).
- G5: the PR-1 deadline lesson — every undeadlined subprocess is a
  future rc:124 with no artifact.

Each rule resolves names through the file's import aliases
(``jnp.asarray`` → ``jax.numpy.asarray``); none of them import jax.
"""
from __future__ import annotations

import ast
import re

from .core import Rule, register

# calls that initialize (or require) a live backend client — including
# jax.numpy array CREATION: the first concrete array is a backend touch
# (guard.py's docstring names it), so a module-scope jnp constant wedges
# importers exactly like a module-scope jax.devices()
BACKEND_DIAL = {
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.device_put", "jax.device_get",
    "jax.default_backend", "jax.process_index", "jax.process_count",
    "jax.block_until_ready", "jax.random.PRNGKey", "jax.random.key",
} | {"jax.numpy." + f for f in (
    "array", "asarray", "zeros", "ones", "full", "empty", "arange",
    "linspace", "eye", "identity", "zeros_like", "ones_like",
    "full_like")}

DEVICE_PROBES = {"jax.devices", "jax.local_devices"}

KEY_MAKERS = {"jax.random.PRNGKey", "jax.random.key"}

# jax.random draws that consume a key (split/fold_in deliberately absent)
SAMPLERS = {
    "uniform", "normal", "bernoulli", "bits", "randint", "permutation",
    "shuffle", "categorical", "gamma", "beta", "exponential", "poisson",
    "truncated_normal", "gumbel", "laplace", "cauchy", "choice",
    "dirichlet", "multivariate_normal", "rademacher", "t", "logistic",
}

JIT_WRAPPERS = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}
PARTIALS = {"functools.partial", "partial"}

# (callable, indices of function-valued args) for traced-body detection
TRACED_ARG_CALLS = {
    "jax.lax.scan": (0,),
    "jax.lax.map": (0,),
    "jax.lax.fori_loop": (2,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": (1,),
    "jax.lax.associative_scan": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
}

HOST_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
HOST_SYNC_CALLS = {"numpy.asarray", "numpy.array", "jax.device_get",
                   "jax.block_until_ready"}


def _is_main_guard(test) -> bool:
    """True for the ``__name__ == "__main__"`` comparison (either
    operand order) — that body runs as a script, never at import."""
    if not (isinstance(test, ast.Compare) and len(test.ops) == 1
            and isinstance(test.ops[0], ast.Eq)):
        return False
    operands = [test.left] + test.comparators
    names = {o.id for o in operands if isinstance(o, ast.Name)}
    consts = {o.value for o in operands if isinstance(o, ast.Constant)}
    return "__name__" in names and "__main__" in consts


def _walk_import_time(tree):
    """Yield (node, import_time) for the whole module: a node is
    import-time iff no function/lambda/genexp body (or ``__main__``
    guard) encloses it. Decorators, default argument values, class
    bodies — and annotations, unless ``from __future__ import
    annotations`` defers them — DO run at import."""
    out = []
    lazy_annotations = any(
        isinstance(n, ast.ImportFrom) and n.module == "__future__"
        and any(a.name == "annotations" for a in n.names)
        for n in tree.body)

    def visit_annotation(ann, import_time):
        if ann is not None and not lazy_annotations:
            visit(ann, import_time)

    def visit(node, import_time):
        out.append((node, import_time))
        if isinstance(node, ast.If) and _is_main_guard(node.test):
            visit(node.test, import_time)
            for child in node.body:
                visit(child, False)
            for child in node.orelse:
                visit(child, import_time)
            return
        if isinstance(node, ast.AnnAssign):
            visit_annotation(node.annotation, import_time)
            visit(node.target, import_time)
            if node.value is not None:
                visit(node.value, import_time)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                visit(d, import_time)
            for d in node.args.defaults:
                visit(d, import_time)
            for d in node.args.kw_defaults:
                if d is not None:
                    visit(d, import_time)
            a = node.args
            for arg in (a.posonlyargs + a.args + a.kwonlyargs
                        + [a.vararg, a.kwarg]):
                if arg is not None:
                    visit_annotation(arg.annotation, import_time)
            visit_annotation(node.returns, import_time)
            for child in node.body:
                visit(child, False)
            return
        if isinstance(node, ast.GeneratorExp):
            # building a genexp evaluates ONLY the first iterable; the
            # body is deferred until iteration
            visit(node.generators[0].iter, import_time)
            for i, gen in enumerate(node.generators):
                visit(gen.target, False)
                if i > 0:
                    visit(gen.iter, False)
                for cond in gen.ifs:
                    visit(cond, False)
            visit(node.elt, False)
            return
        if isinstance(node, ast.Lambda):
            # lambda DEFAULTS evaluate when the expression does (maybe
            # at import); only the body is deferred
            for d in node.args.defaults:
                visit(d, import_time)
            for d in node.args.kw_defaults:
                if d is not None:
                    visit(d, import_time)
            visit(node.body, False)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, import_time)

    visit(tree, True)
    return out


@register
class ModuleScopeBackendDial(Rule):
    code = "G1"
    name = "module-scope-backend-dial"
    severity = "error"
    doc = ("Backend-dialing call (jax.devices/device_put/PRNGKey/...) "
           "reachable at import time — module scope, class body, "
           "decorator, or default argument. An import-time dial hangs "
           "every process that imports the module when the device "
           "runtime is unhealthy, and takes the chip from whichever "
           "process meant to hold it. Defer the "
           "touch into a function and route it through "
           "mxnet_tpu.diagnostics.guard.")

    def check(self, ctx):
        for node, import_time in _walk_import_time(ctx.tree):
            if not import_time or not isinstance(node, ast.Call):
                continue
            name = ctx.resolve_call(node)
            if name in BACKEND_DIAL:
                yield self.finding(
                    ctx, node.lineno,
                    f"module-scope backend dial: {name}() runs at import "
                    f"time; defer it into a function (guarded by "
                    f"diagnostics.guard)")


@register
class PrngDiscipline(Rule):
    code = "G2"
    name = "prng-discipline"
    doc = ("Library code must not bake constant PRNG keys "
           "(jax.random.PRNGKey(0) gives every caller the same stream "
           "and dials the backend wherever it runs), and must not feed "
           "the same key to two draws without an intervening "
           "split/fold_in (identical randomness — the correlated-"
           "dropout-mask class fixed in PR 1). Scope: mxnet_tpu/ "
           "library code.")

    def check(self, ctx):
        if not ctx.is_library():
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) \
                    and ctx.resolve_call(node) in KEY_MAKERS \
                    and ((node.args
                          and isinstance(node.args[0], ast.Constant))
                         or any(kw.arg == "seed"
                                and isinstance(kw.value, ast.Constant)
                                for kw in node.keywords)):
                yield self.finding(
                    ctx, node.lineno,
                    "constant PRNG key in library code: every caller "
                    "draws the identical stream (thread a key in, or use "
                    "_rng.next_key())")
        for fn in ast.walk(ctx.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_reuse(ctx, fn)

    def _check_reuse(self, ctx, fn):
        out = []
        self._scan_block(ctx, fn.body, set(), out)
        return out

    def _scan_block(self, ctx, stmts, drawn, out):
        """Key-lifetime scan, branch-aware: mutually exclusive branches
        each fork the drawn-set (one draw per if/else arm is NOT reuse);
        afterwards the union flows on (a draw in any arm plus a later
        draw of the same key IS)."""
        for stmt in stmts:
            if isinstance(stmt, ast.If):
                self._apply_events(ctx, [stmt.test], drawn, out)
                forks = []
                for block in (stmt.body, stmt.orelse):
                    d = set(drawn)
                    self._scan_block(ctx, block, d, out)
                    # a terminating arm (guard clause) never rejoins the
                    # fall-through flow — its draws don't leak forward
                    if not self._terminates(block):
                        forks.append(d)
                drawn.update(*forks)
            elif isinstance(stmt, ast.Try):
                self._scan_block(ctx, stmt.body, drawn, out)
                # handlers and the else-block are mutually exclusive
                # alternatives after the body
                base = set(drawn)
                forks = []
                blocks = [h.body for h in stmt.handlers]
                if stmt.orelse:
                    blocks.append(stmt.orelse)
                for block in blocks:
                    d = set(base)
                    self._scan_block(ctx, block, d, out)
                    if not self._terminates(block):
                        forks.append(d)
                drawn.update(*forks)
                self._scan_block(ctx, stmt.finalbody, drawn, out)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._apply_events(ctx, [stmt.iter], drawn, out)
                # the loop target rebinds EVERY iteration — a fresh key
                # per pass (`for k in jax.random.split(key, n):`)
                targets = [sub.id for sub in ast.walk(stmt.target)
                           if isinstance(sub, ast.Name)]
                self._scan_loop_body(ctx, stmt.body, drawn, out,
                                     refresh=targets)
                self._scan_block(ctx, stmt.orelse, drawn, out)
            elif isinstance(stmt, ast.While):
                self._apply_events(ctx, [stmt.test], drawn, out)
                self._scan_loop_body(ctx, stmt.body, drawn, out)
                self._scan_block(ctx, stmt.orelse, drawn, out)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                self._apply_events(
                    ctx, [i.context_expr for i in stmt.items], drawn, out)
                for item in stmt.items:     # `as key:` rebinds
                    if item.optional_vars is not None:
                        for sub in ast.walk(item.optional_vars):
                            if isinstance(sub, ast.Name):
                                drawn.discard(sub.id)
                self._scan_block(ctx, stmt.body, drawn, out)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                pass            # separate scope, scanned on its own
            elif isinstance(stmt, ast.Match):
                # match arms are mutually exclusive, like if/else
                self._apply_events(ctx, [stmt.subject], drawn, out)
                forks = []
                for case in stmt.cases:
                    d = set(drawn)
                    self._scan_block(ctx, case.body, d, out)
                    if not self._terminates(case.body):
                        forks.append(d)
                drawn.update(*forks)
            else:
                self._apply_events(ctx, [stmt], drawn, out)

    @staticmethod
    def _terminates(stmts) -> bool:
        """True when a block's flow cannot rejoin the statement after
        its parent (guard clauses: return/raise/break/continue last)."""
        return bool(stmts) and isinstance(
            stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))

    def _scan_loop_body(self, ctx, stmts, drawn, out, refresh=()):
        """Loop bodies run repeatedly: a second pass seeded with the
        first pass's drawn-set catches a same-key draw repeated across
        iterations (the correlated-mask-per-tick class from PR 1) while
        a per-iteration split/fold_in still clears it. ``refresh``
        names (the for-loop target) rebind before every pass."""
        for var in refresh:
            drawn.discard(var)
        self._scan_block(ctx, stmts, drawn, out)
        for var in refresh:
            drawn.discard(var)
        second = []
        self._scan_block(ctx, stmts, drawn, second)
        seen = {(f.line, f.message) for f in out}
        out.extend(f for f in second if (f.line, f.message) not in seen)

    def _apply_events(self, ctx, nodes, drawn, out):
        for node in nodes:
            self._apply_node(ctx, node, drawn, out)

    def _apply_node(self, ctx, node, drawn, out):
        """Fold one node's draw/refresh events into the drawn-set in
        evaluation order, forking at expression-level branches (IfExp,
        short-circuiting BoolOp) exactly like _scan_block forks at
        statement-level if/match. Nested defs/lambdas are own scopes."""
        if node is None or isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        if isinstance(node, ast.IfExp):
            self._apply_node(ctx, node.test, drawn, out)
            forks = []
            for arm in (node.body, node.orelse):
                d = set(drawn)
                self._apply_node(ctx, arm, d, out)
                forks.append(d)
            drawn.update(*forks)
            return
        if isinstance(node, ast.BoolOp):
            # operands after the first may be short-circuited away
            self._apply_node(ctx, node.values[0], drawn, out)
            forks = []
            for v in node.values[1:]:
                d = set(drawn)
                self._apply_node(ctx, v, d, out)
                forks.append(d)
            drawn.update(*forks)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.NamedExpr,
                             ast.AnnAssign)):
            # value evaluates first; binding the targets then REFRESHES
            # them (k, sub = split(k) never reads stale state); walrus
            # and annotated rebinds count too
            if isinstance(node, ast.AnnAssign) and node.value is None:
                return              # bare annotation: nothing binds
            self._apply_node(ctx, node.value, drawn, out)
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        drawn.discard(sub.id)
            return
        if isinstance(node, ast.Call):
            for child in ast.iter_child_nodes(node):
                self._apply_node(ctx, child, drawn, out)
            name = ctx.resolve_call(node) or ""
            if name.startswith("jax.random.") and \
                    name.rsplit(".", 1)[-1] in SAMPLERS and \
                    node.args and isinstance(node.args[0], ast.Name):
                # a refresh happens only when the split/fold_in RESULT is
                # bound (the Assign-target discard) — `split(key)` with
                # the result dropped does not freshen `key`
                var = node.args[0].id
                if var in drawn:
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"PRNG key {var!r} fed to a second draw with no "
                        f"split/fold_in between — identical random bits"))
                else:
                    drawn.add(var)
            return
        for child in ast.iter_child_nodes(node):
            self._apply_node(ctx, child, drawn, out)


def _static_under_trace(arg) -> bool:
    """True when the expression reads tracer METADATA (.shape/.ndim/
    .size/.dtype, len()) — static Python values during tracing, so
    int()/float() over them is trace-safe, not a host sync."""
    for n in ast.walk(arg):
        if isinstance(n, ast.Attribute) and n.attr in (
                "shape", "ndim", "size", "dtype"):
            return True
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id == "len":
            return True
    return False


def _traced_functions(ctx):
    """FunctionDef/Lambda nodes whose bodies run under trace: jit/pjit-
    decorated defs, plus functions handed to lax control-flow combinators
    (scan/while/cond/...) by name or inline lambda."""
    by_name = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
    traced = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = ctx.resolve(target)
                if name in JIT_WRAPPERS:
                    traced.append(node)
                elif isinstance(dec, ast.Call) and name in PARTIALS and \
                        any(ctx.resolve(a) in JIT_WRAPPERS
                            for a in dec.args):
                    traced.append(node)
        elif isinstance(node, ast.Call):
            name = ctx.resolve_call(node)
            arg_idx = ()
            if name in TRACED_ARG_CALLS:
                arg_idx = TRACED_ARG_CALLS[name]
            elif name in JIT_WRAPPERS:
                arg_idx = (0,)
            for i in arg_idx:
                if i < len(node.args):
                    a = node.args[i]
                    if isinstance(a, ast.Name):
                        traced.extend(by_name.get(a.id, ()))
                    elif isinstance(a, ast.Lambda):
                        traced.append(a)
    return traced


@register
class HostSyncInTracedCode(Rule):
    code = "G3"
    name = "host-sync-in-traced-code"
    severity = "error"
    doc = ("Host synchronization (.item()/.tolist()/float()/np.asarray/"
           "block_until_ready) inside jit/pjit-decorated functions or "
           "lax.scan/while/cond bodies. Under trace these either fail "
           "(ConcretizationTypeError) or silently force a device→host "
           "round trip per step, serializing the TPU pipeline.")

    def check(self, ctx):
        seen = set()
        for fn in _traced_functions(ctx):
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            # nested defs/lambdas are separate scopes (pure_callback
            # host helpers legitimately sync); a nested fn that IS
            # traced (e.g. named in lax.scan) is collected above
            stack = list(body)
            while stack:
                node = stack.pop()
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                    continue
                stack.extend(ast.iter_child_nodes(node))
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                hit = self._host_sync_hit(ctx, node)
                if hit:
                    seen.add(id(node))
                    yield self.finding(
                        ctx, node.lineno,
                        f"host sync {hit} inside traced code — fails or "
                        f"forces a device round trip under jit/scan")

    @staticmethod
    def _host_sync_hit(ctx, node):
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in HOST_SYNC_ATTRS:
            return f".{func.attr}()"
        name = ctx.resolve(func)
        if name in HOST_SYNC_CALLS:
            return f"{name}()"
        if isinstance(func, ast.Name) and func.id in ("float", "int") \
                and len(node.args) == 1 \
                and not isinstance(node.args[0], ast.Constant) \
                and not _static_under_trace(node.args[0]):
            return f"{func.id}()"
        return None


@register
class UnguardedDeviceProbe(Rule):
    code = "G4"
    name = "unguarded-device-probe"
    severity = "error"
    doc = ("Direct jax.devices()/jax.local_devices() in library code. "
           "An unhealthy device runtime hangs the caller indefinitely; "
           "diagnostics.guard.devices() / ensure_backend() is the one "
           "sanctioned dial (journaled, deadline-guarded, cached). "
           "Scope: mxnet_tpu/ library code.")

    def check(self, ctx):
        if not ctx.is_library():
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    ctx.resolve_call(node) in DEVICE_PROBES:
                yield self.finding(
                    ctx, node.lineno,
                    "direct device probe in library code — use "
                    "diagnostics.guard.devices() (deadline-guarded, "
                    "journaled) instead of jax.devices()")


@register
class UndeadlinedSubprocess(Rule):
    code = "G5"
    name = "subprocess-without-timeout"
    doc = ("Blocking subprocess call (run/call/check_call/check_output) "
           "without timeout=. A child that dials a wedged backend hangs "
           "the parent for the driver's whole window — every such wait "
           "needs a deadline (the PR-1 lesson; guard.probe_backend is "
           "the model).")

    BLOCKING = {"subprocess.run", "subprocess.call",
                "subprocess.check_call", "subprocess.check_output"}

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.resolve_call(node)
            if name not in self.BLOCKING:
                continue
            kw_names = {kw.arg for kw in node.keywords}
            if "timeout" in kw_names or None in kw_names:  # **kwargs: unknown
                continue
            yield self.finding(
                ctx, node.lineno,
                f"{name}() without timeout= — an undeadlined child "
                f"hang becomes an information-free rc:124")


QUEUE_MAKERS = {"queue.Queue", "queue.LifoQueue", "queue.PriorityQueue"}
ALWAYS_UNBOUNDED_MAKERS = {"queue.SimpleQueue"}
THREAD_MAKERS = {"threading.Thread", "threading.Timer"}


@register
class UnboundedQueueDiscipline(Rule):
    code = "G8"
    name = "unbounded-queue"
    doc = ("Unbounded ``queue.Queue()`` construction, or a blocking "
           "``.get()``/``.join()`` on a queue/thread without "
           "``timeout=``, in library code. An unbounded queue turns "
           "overload into unbounded latency + memory (the serving "
           "subsystem's admission contract: shed with ServerOverloaded "
           "instead — docs/serving.md), and an undeadlined get/join is "
           "the in-process twin of G5's subprocess hang: one wedged "
           "producer thread and the caller blocks for the driver's "
           "whole window. ``queue.Queue.join()`` accepts no timeout at "
           "all — restructure around bounded waits. Scope: mxnet_tpu/ "
           "library code.")

    @staticmethod
    def _const_int(node):
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub) \
                and isinstance(node.operand, ast.Constant) \
                and isinstance(node.operand.value, int):
            return -node.operand.value
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return node.value
        return None

    def _unbounded_construction(self, call):
        kw = {k.arg: k.value for k in call.keywords}
        if None in kw:                       # **kwargs: unknown, trust it
            return False
        maxsize = call.args[0] if call.args else kw.get("maxsize")
        if maxsize is None:
            return True                      # default maxsize=0: unbounded
        c = self._const_int(maxsize)
        return c is not None and c <= 0      # explicit 0/negative

    @staticmethod
    def _receivers(ctx):
        """Dotted receiver names bound to queue / thread constructions
        anywhere in the file ('q', 'self._queue', ...)."""
        queues, threads = set(), set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, (ast.AnnAssign, ast.NamedExpr)) \
                    and node.value is not None:
                value, targets = node.value, [node.target]
            else:
                continue
            if not isinstance(value, ast.Call):
                continue
            name = ctx.resolve_call(value)
            if name in QUEUE_MAKERS | ALWAYS_UNBOUNDED_MAKERS:
                pool = queues
            elif name in THREAD_MAKERS:
                pool = threads
            else:
                continue
            for t in targets:
                dotted = ctx.resolve(t)
                if dotted:
                    pool.add(dotted)
        return queues, threads

    def check(self, ctx):
        if not ctx.is_library():
            return
        queues, threads = self._receivers(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.resolve_call(node)
            if name in ALWAYS_UNBOUNDED_MAKERS:
                yield self.finding(
                    ctx, node.lineno,
                    f"{name}() is unbounded by construction — overload "
                    "becomes unbounded memory/latency; use a bounded "
                    "queue.Queue(maxsize=N) and shed on Full")
                continue
            if name in QUEUE_MAKERS and self._unbounded_construction(node):
                yield self.finding(
                    ctx, node.lineno,
                    f"unbounded {name}() in library code — pass "
                    "maxsize=N and shed on queue.Full (the serving "
                    "admission-control contract)")
                continue
            func = node.func
            if not isinstance(func, ast.Attribute):
                continue
            recv = ctx.resolve(func.value)
            if recv is None:
                continue
            kw_names = {k.arg for k in node.keywords}
            if None in kw_names:             # **kwargs: unknown
                continue
            if func.attr == "get" and recv in queues:
                if "timeout" in kw_names or len(node.args) >= 2:
                    continue
                blk = node.args[0] if node.args else None
                for k in node.keywords:
                    if k.arg == "block":
                        blk = k.value
                if isinstance(blk, ast.Constant) and blk.value is False:
                    continue                 # non-blocking get
                yield self.finding(
                    ctx, node.lineno,
                    f"{recv}.get() without timeout= — a wedged producer "
                    "hangs the consumer for the driver's whole window "
                    "(the G5 lesson, in-process)")
            elif func.attr == "join":
                if recv in queues:
                    yield self.finding(
                        ctx, node.lineno,
                        f"{recv}.join(): queue.Queue.join() accepts no "
                        "timeout — restructure around bounded waits "
                        "(task counting + Event.wait(timeout=))")
                elif recv in threads and "timeout" not in kw_names \
                        and not node.args:
                    yield self.finding(
                        ctx, node.lineno,
                        f"{recv}.join() without timeout= — a wedged "
                        "worker thread hangs shutdown forever; join "
                        "with a deadline and report the stall")


ARTIFACT_SUFFIXES = (".params", ".states", ".pstate", ".json", ".onnx")
_SAVE_FN_RE = re.compile(r"save|checkpoint|export|dump", re.IGNORECASE)


def _functions_with_calls(tree):
    """Yield (call_node, enclosing_function_name_or_None) for every Call
    in the module (innermost function wins)."""
    out = []

    def visit(node, fn_name):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_name = node.name
        if isinstance(node, ast.Call):
            out.append((node, fn_name))
        for child in ast.iter_child_nodes(node):
            visit(child, fn_name)

    visit(tree, None)
    return out


@register
class NonAtomicDurableWrite(Rule):
    code = "G7"
    name = "non-atomic-durable-write"
    doc = ("Durable artifact (.params/.states/.json/...) opened with a "
           "direct open(path, 'w'/'wb') in library code: a preemption "
           "mid-write leaves a torn file the loader misparses (the "
           "crash class docs/checkpointing.md exists for). Route the "
           "write through mxnet_tpu.resilience.atomic.atomic_write "
           "(tmp + fsync + os.replace). Flagged on artifact-suffix "
           "evidence in the path expression, or a bare path variable "
           "inside a save/checkpoint/export/dump-named function. "
           "Scope: mxnet_tpu/ library code.")

    @staticmethod
    def _write_mode(node) -> bool:
        mode = None
        if len(node.args) >= 2:
            mode = node.args[1]
        for kw in node.keywords:
            if kw.arg == "mode":
                mode = kw.value
        return (isinstance(mode, ast.Constant)
                and isinstance(mode.value, str)
                and mode.value.startswith("w"))

    @staticmethod
    def _suffix_evidence(path_arg):
        for sub in ast.walk(path_arg):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                if sub.value.endswith(ARTIFACT_SUFFIXES):
                    return sub.value
        return None

    def check(self, ctx):
        if not ctx.is_library():
            return
        for node, fn_name in _functions_with_calls(ctx.tree):
            if ctx.resolve_call(node) not in ("open", "io.open"):
                continue
            if not node.args or not self._write_mode(node):
                continue
            path_arg = node.args[0]
            suffix = self._suffix_evidence(path_arg)
            named_save = (isinstance(path_arg, (ast.Name, ast.Attribute))
                          and fn_name is not None
                          and _SAVE_FN_RE.search(fn_name))
            if suffix:
                yield self.finding(
                    ctx, node.lineno,
                    f"direct write to durable artifact ({suffix!r}) — a "
                    "crash mid-write leaves a torn file; use "
                    "resilience.atomic.atomic_write")
            elif named_save:
                yield self.finding(
                    ctx, node.lineno,
                    f"open(..., 'w') inside {fn_name}(): checkpoint-"
                    "shaped writers must be atomic — use "
                    "resilience.atomic.atomic_write (tmp + fsync + "
                    "os.replace)")


# -- G9: host-synced finiteness checks in training-loop code -----------------

# the modules that sit on the per-step hot path: a host-synced finiteness
# check here costs a device→host round trip EVERY step (the defect class
# the fused guard replaced — gluon/utils.py's old per-array asscalar()
# loop and amp's per-step has_overflow pull)
TRAINING_PATH_RE = re.compile(
    r"(^|/)mxnet_tpu/(gluon/(trainer|utils)\.py|module/[^/]+\.py|"
    r"parallel/[^/]+\.py|contrib/amp/[^/]+\.py|optimizer/[^/]+\.py)$")
_SCOPE_TRAINING_RE = re.compile(r"#\s*graftlint:\s*scope=training\b")

HOST_FINITENESS = {"numpy.isfinite", "numpy.isnan", "numpy.isinf"}
DEVICE_FINITENESS = {"jax.numpy.isfinite", "jax.numpy.isnan",
                     "jax.numpy.isinf"} | HOST_FINITENESS
# identifiers that smell like per-step training values; float()/.item()/
# .asscalar() over them in a training module is a per-step host sync
GUARD_VALUE_RE = re.compile(r"grad|loss|norm|overflow|finite", re.I)
HOST_PULL_ATTRS = ("item", "asscalar")
SANCTIONED_FETCH = "host_fetch"     # guardrails.fused.host_fetch


@register
class HostSyncedFinitenessCheck(Rule):
    code = "G9"
    name = "host-synced-finiteness-check"
    doc = ("Per-step host-synced finiteness check in training-loop "
           "modules: np.isfinite/np.isnan over step values, or "
           "float()/bool()/.item()/.asscalar() on gradient/loss/norm "
           "values (including values derived from a device-side "
           "isfinite). Each one is a device->host round trip per step "
           "— and on multi-host, a per-rank early return out of a "
           "collective. Use the fused in-program guard "
           "(mxnet_tpu.guardrails.fused.guard_stats) and read its step "
           "outputs through guardrails.fused.host_fetch. Scope: "
           "training-loop library modules (gluon trainer/utils, "
           "module/, parallel/, contrib/amp, optimizer/).")

    def _in_scope(self, ctx) -> bool:
        if TRAINING_PATH_RE.search("/" + ctx.path):
            return True
        return bool(_SCOPE_TRAINING_RE.search("\n".join(ctx.lines[:5])))

    @staticmethod
    def _sanctioned(node) -> bool:
        """True when the expression routes through the one sanctioned
        chokepoint (guardrails.fused.host_fetch) — the fetch is the
        API, not an ad-hoc sync."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == SANCTIONED_FETCH:
                return True
        return False

    @classmethod
    def _assign_pairs(cls, targets, value):
        """Decompose an assignment into (targets, value) taint units:
        tuple unpacking propagates element-wise so in
        `flag, n = jnp.isfinite(g).all(), step` only `flag` is dirtied
        — tainting `n` too would flag a later benign `int(n)`.
        Shape-mismatched or starred unpacking falls back to the whole
        value (conservative)."""
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)) \
                    and isinstance(value, (ast.Tuple, ast.List)) \
                    and len(t.elts) == len(value.elts) \
                    and not any(isinstance(e, ast.Starred)
                                for e in t.elts):
                for te, ve in zip(t.elts, value.elts):
                    yield from cls._assign_pairs([te], ve)
            else:
                yield [t], value

    @staticmethod
    def _scope_map(tree):
        """node → innermost enclosing function (None = module scope).
        Name-set analysis must be per-scope: a `norm` blessed inside one
        function must not exempt a different function's `norm`."""
        scopes = {}

        def visit(node, scope):
            for child in ast.iter_child_nodes(node):
                scopes[child] = scope
                visit(child,
                      child if isinstance(child, (ast.FunctionDef,
                                                  ast.AsyncFunctionDef))
                      else scope)

        visit(tree, None)
        return scopes

    def _tainted_names(self, ctx, scopes):
        """Per-scope name sets from a fixpoint over each scope's
        assignments — returns ``{scope: (tainted, blessed)}``:

        - **tainted** — assigned (transitively) from expressions
          containing a finiteness call: `ok = jnp.all(jnp.isfinite(g))`
          taints `ok`, `flag = ok` taints `flag`;
        - **blessed** — assigned from expressions routing through the
          sanctioned chokepoint: `norm = fused.host_fetch(norm_dev)[0]`
          is already a host value, so a later `np.isfinite(norm)` /
          `float(norm)` costs no device sync and must NOT be flagged
          (it is the exact pattern this rule recommends). Blessing wins
          over taint — `ok, gn = fused.host_fetch(finite, gnorm)`
          blesses `ok` even though `finite` is tainted."""
        per_scope: dict = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                pairs = self._assign_pairs(node.targets, node.value)
            elif isinstance(node, (ast.AnnAssign, ast.NamedExpr)) \
                    and node.value is not None:
                pairs = self._assign_pairs([node.target], node.value)
            else:
                continue
            per_scope.setdefault(scopes.get(node), []).extend(pairs)
        out = {}
        for scope, assigns in per_scope.items():
            taint: set[str] = set()
            blessed: set[str] = set()
            changed = True
            while changed:
                changed = False
                for targets, value in assigns:
                    if self._sanctioned(value):
                        dest = blessed
                    else:
                        dirty = False
                        for sub in ast.walk(value):
                            if isinstance(sub, ast.Call) \
                                    and ctx.resolve_call(sub) \
                                    in DEVICE_FINITENESS:
                                dirty = True
                            elif isinstance(sub, ast.Name) \
                                    and sub.id in taint \
                                    and sub.id not in blessed:
                                dirty = True
                        if not dirty:
                            continue
                        dest = taint
                    for t in targets:
                        for sub in ast.walk(t):
                            if isinstance(sub, ast.Name) \
                                    and sub.id not in dest:
                                dest.add(sub.id)
                                changed = True
            out[scope] = (taint, blessed)
        return out

    @staticmethod
    def _matches_guard_value(node, taint) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and (
                    GUARD_VALUE_RE.search(sub.id) or sub.id in taint):
                return True
            if isinstance(sub, ast.Attribute) \
                    and GUARD_VALUE_RE.search(sub.attr):
                return True
        return False

    @staticmethod
    def _all_names_blessed(node, blessed) -> bool:
        """Every Name in the expression is a host_fetch result (and
        there is at least one): checking/converting it is host-local."""
        names = [s.id for s in ast.walk(node)
                 if isinstance(s, ast.Name)]
        return bool(names) and all(n in blessed for n in names)

    def check(self, ctx):
        if not ctx.is_library() or not self._in_scope(ctx):
            return
        scopes = self._scope_map(ctx.tree)
        per_scope = self._tainted_names(ctx, scopes)
        empty: tuple = (frozenset(), frozenset())
        # _sanctioned walks the whole call subtree — run it only on
        # candidates that already matched the cheap name/taint checks,
        # not on every Call in the file
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            taint, blessed = per_scope.get(scopes.get(node), empty)
            name = ctx.resolve_call(node)
            if name in HOST_FINITENESS:
                if self._sanctioned(node) or (
                        node.args and all(self._all_names_blessed(a,
                                                                  blessed)
                                          for a in node.args)):
                    continue
                yield self.finding(
                    ctx, node.lineno,
                    f"host {name}() in a training-loop module — a "
                    "device->host sync per step; fold the check into "
                    "the compiled step (guardrails.fused.guard_stats)")
                continue
            func = node.func
            if isinstance(func, ast.Name) and func.id in ("float", "bool",
                                                          "int") \
                    and len(node.args) == 1 \
                    and self._matches_guard_value(node.args[0], taint) \
                    and not self._all_names_blessed(node.args[0],
                                                    blessed) \
                    and not self._sanctioned(node):
                yield self.finding(
                    ctx, node.lineno,
                    f"{func.id}() host-syncs a per-step training value "
                    "— return it from the compiled step and read it via "
                    "guardrails.fused.host_fetch")
            elif isinstance(func, ast.Attribute) \
                    and func.attr in HOST_PULL_ATTRS \
                    and self._matches_guard_value(func.value, taint) \
                    and not self._all_names_blessed(func.value, blessed) \
                    and not self._sanctioned(node):
                yield self.finding(
                    ctx, node.lineno,
                    f".{func.attr}() host-syncs a per-step training "
                    "value — use the fused guard's step outputs "
                    "(guardrails.fused.host_fetch)")


@register
class SilentDeviceExceptionSwallow(Rule):
    code = "G6"
    name = "silent-device-exception-swallow"
    doc = ("`except Exception: pass` (or bare) around backend-touching "
           "code. A dead device path that vanishes silently is "
           "undebuggable — journal it via diagnostics.journal (the "
           "engine.waitall lesson) or narrow the catch.")

    BROAD = {"Exception", "BaseException"}

    def _touches_device(self, ctx, try_node):
        # only the PROTECTED code counts (body + else) — a jax call in a
        # sibling handler doesn't make an unrelated handler a G6
        for top in list(try_node.body) + list(try_node.orelse):
            for node in ast.walk(top):
                if isinstance(node, ast.Call):
                    name = ctx.resolve_call(node) or ""
                    if name.startswith("jax."):
                        return True
                    func = node.func
                    if isinstance(func, ast.Attribute) and func.attr in (
                            "block_until_ready", "device_put", "devices"):
                        return True
        return False

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Try):
                continue
            for handler in node.handlers:
                t = handler.type
                broad = t is None or \
                    (isinstance(t, ast.Name) and t.id in self.BROAD) or \
                    (isinstance(t, ast.Tuple)
                     and any(isinstance(e, ast.Name) and e.id in self.BROAD
                             for e in t.elts))
                swallows = len(handler.body) == 1 and (
                    isinstance(handler.body[0], ast.Pass)
                    or (isinstance(handler.body[0], ast.Expr)
                        and isinstance(handler.body[0].value, ast.Constant)))
                if broad and swallows and self._touches_device(ctx, node):
                    yield self.finding(
                        ctx, handler.lineno,
                        "device/runtime failure swallowed silently — "
                        "journal it (diagnostics.journal) or narrow the "
                        "except")


@register
class DirectPallasCall(Rule):
    code = "G10"
    name = "direct-pallas-call"
    severity = "error"
    doc = ("Direct `pl.pallas_call` in library code outside "
           "mxnet_tpu/pallas/. A raw kernel bypasses the registry's "
           "parity gate, backend/shape fallback, and journaled "
           "provenance (docs/pallas.md) — an unverified kernel can then "
           "silently change numerics or run on a backend it was never "
           "tested on. Register it (pallas.register_kernel) and route "
           "callers through pallas.dispatch. "
           "Scope: mxnet_tpu/ library code; mxnet_tpu/pallas/ is the "
           "sanctioned home.")

    PALLAS_CALLS = {"jax.experimental.pallas.pallas_call"}

    def check(self, ctx):
        if not ctx.is_library() or ctx.path.startswith("mxnet_tpu/pallas/"):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    ctx.resolve_call(node) in self.PALLAS_CALLS:
                yield self.finding(
                    ctx, node.lineno,
                    "raw pl.pallas_call in library code bypasses the "
                    "kernel tier's parity/fallback guard — register the "
                    "kernel in mxnet_tpu/pallas/ and dispatch through "
                    "the registry")


@register
class WallclockDuration(Rule):
    code = "G11"
    name = "wallclock-duration"
    severity = "error"
    doc = ("`time.time()` used in duration arithmetic in library code. "
           "The wall clock steps under NTP adjustment, so a "
           "`time.time() - t0` duration can go NEGATIVE (or jump hours) "
           "mid-run — poisoning journal durations, latency summaries "
           "and Time-cost logs. Durations must come from "
           "`time.monotonic()` / `time.perf_counter()`; wall clock is "
           "only for timestamps (a bare `time.time()` with no "
           "subtraction is fine). Per-function scope: a name assigned "
           "from time.time() taints subtractions in the same scope. "
           "Scope: mxnet_tpu/ library code.")

    WALL = "time.time"

    def _scopes(self, tree):
        """(scope_body_nodes) per function/module, nested functions
        excluded from their parent (their taint is their own)."""
        scopes = [tree]
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                scopes.append(node)
        return scopes

    def _walk_scope(self, scope):
        """Nodes belonging to this scope only (stop at nested function
        boundaries)."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(node))

    def _is_wall_call(self, ctx, node):
        return isinstance(node, ast.Call) and \
            ctx.resolve_call(node) == self.WALL

    def check(self, ctx):
        if not ctx.is_library():
            return
        for scope in self._scopes(ctx.tree):
            # line-ordered taint flow: an assignment from time.time()
            # taints its name, a later reassignment from anything else
            # clears it — so rebinding a variable to monotonic doesn't
            # keep a stale error on correct code
            events = []     # (lineno, order, kind, payload)
            for node in self._walk_scope(scope):
                if isinstance(node, ast.Assign):
                    wall = self._is_wall_call(ctx, node.value)
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            events.append((node.lineno, 1,
                                           "taint" if wall else "clear",
                                           tgt.id))
                elif isinstance(node, ast.BinOp) and \
                        isinstance(node.op, ast.Sub):
                    events.append((node.lineno, 0, "sub", node))
            tainted = set()
            for _ln, _order, kind, payload in sorted(
                    events, key=lambda e: (e[0], e[1])):
                if kind == "taint":
                    tainted.add(payload)
                    continue
                if kind == "clear":
                    tainted.discard(payload)
                    continue
                node = payload
                for side in (node.left, node.right):
                    if self._is_wall_call(ctx, side) or \
                            (isinstance(side, ast.Name)
                             and side.id in tainted):
                        yield self.finding(
                            ctx, node.lineno,
                            "duration computed from time.time() — the "
                            "wall clock steps under NTP; use "
                            "time.monotonic()/perf_counter() for "
                            "durations (time.time() is for timestamps "
                            "only)")
                        break


@register
class UnboundedPollLoop(Rule):
    code = "G13"
    name = "unbounded-poll-loop"
    severity = "error"
    doc = ("`while True:` poll loop containing time.sleep() with no "
           "deadline/budget check inside the loop, in library code. "
           "The router/breaker/drain wait-loop hazard class: the "
           "condition being polled for can simply never come (dead "
           "replica, wedged worker, stuck flag) and the thread spins "
           "for the driver's whole window — an information-free rc:124, "
           "in-process. Bound every poll loop: compare a monotonic "
           "clock against a deadline inside the loop "
           "(elastic.membership.Cohort.barrier is the model) or "
           "restructure onto a bounded condition / Event.wait(timeout=). "
           "Scope: mxnet_tpu/ library code.")

    CLOCKS = {"time.monotonic", "time.perf_counter", "time.time",
              "time.monotonic_ns", "time.perf_counter_ns", "time.time_ns"}
    SLEEP = "time.sleep"

    @staticmethod
    def _const_true(test) -> bool:
        return isinstance(test, ast.Constant) and bool(test.value)

    def _scopes(self, tree):
        scopes = [tree]
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                scopes.append(node)
        return scopes

    def _walk_scope(self, scope):
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(node))

    def _is_clock_call(self, ctx, node) -> bool:
        return isinstance(node, ast.Call) and \
            ctx.resolve_call(node) in self.CLOCKS

    def _clock_tainted(self, ctx, scope) -> set:
        """Names assigned (anywhere in this scope) from an expression
        containing a monotonic/wall clock call — deadline variables
        (`deadline = time.monotonic() + x`, `t0 = time.monotonic()`)."""
        tainted = set()
        for node in self._walk_scope(scope):
            if isinstance(node, ast.Assign) and any(
                    self._is_clock_call(ctx, s)
                    for s in ast.walk(node.value)):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        tainted.add(tgt.id)
        return tainted

    def _loop_bounded(self, ctx, loop, tainted) -> bool:
        """A loop is budget-bounded when some Compare inside it reads a
        clock (directly or through a deadline name) — the
        `if time.monotonic() - t0 > deadline: raise` shape."""
        for node in self._loop_body(loop):
            if not isinstance(node, ast.Compare):
                continue
            for sub in ast.walk(node):
                if self._is_clock_call(ctx, sub):
                    return True
                if isinstance(sub, ast.Name) and sub.id in tainted:
                    return True
        return False

    def _loop_body(self, loop):
        """Nodes inside the loop, stopping at nested functions (their
        sleeps and their budgets are their own)."""
        stack = list(loop.body) + list(loop.orelse)
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(node))

    def check(self, ctx):
        if not ctx.is_library():
            return
        for scope in self._scopes(ctx.tree):
            tainted = None          # computed lazily per scope
            for node in self._walk_scope(scope):
                if not (isinstance(node, ast.While)
                        and self._const_true(node.test)):
                    continue
                has_sleep = any(
                    isinstance(sub, ast.Call)
                    and ctx.resolve_call(sub) == self.SLEEP
                    for sub in self._loop_body(node))
                if not has_sleep:
                    continue
                if tainted is None:
                    tainted = self._clock_tainted(ctx, scope)
                if self._loop_bounded(ctx, node, tainted):
                    continue
                yield self.finding(
                    ctx, node.lineno,
                    "unbounded poll loop: while True + time.sleep with "
                    "no deadline/budget check — a condition that never "
                    "comes wedges this thread forever; compare a "
                    "monotonic clock against a deadline inside the loop")


@register
class RankDependentCollectiveEntry(Rule):
    code = "G12"
    name = "rank-dependent-collective-entry"
    severity = "error"
    doc = ("Host-level collective entered under a rank-local condition. "
           "A call like multihost_utils.sync_global_devices / "
           "process_allgather / broadcast_one_to_all guarded by "
           "`if jax.process_index() == 0:` (or a name derived from it) "
           "means SOME ranks enter the collective and others don't — "
           "the guarded ranks wait forever for peers that never arrive. "
           "This is the deadlock class elastic training cannot tolerate "
           "(docs/elastic.md): the PR-5 lesson that a rank-dependent "
           "decision to enter a collective is itself a deadlock. Make "
           "entry unconditional and rank-uniform; decide once on one "
           "rank and share the verdict through a broadcast "
           "(parallel._ckpt group bcast_int / elastic.broadcast_json). "
           "World-SIZE conditionals (`if jax.process_count() == 1:`) "
           "are rank-uniform and fine. Scope: mxnet_tpu/ library code.")

    COLLECTIVES = {
        "jax.experimental.multihost_utils.sync_global_devices",
        "jax.experimental.multihost_utils.process_allgather",
        "jax.experimental.multihost_utils.broadcast_one_to_all",
        "jax.experimental.multihost_utils.assert_equal",
    }
    RANK_SOURCES = {"jax.process_index"}

    def _scopes(self, tree):
        scopes = [tree]
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                scopes.append(node)
        return scopes

    def _scope_children(self, scope):
        """Direct body of this scope, stopping at nested functions
        (each nested scope carries its own taint and guards)."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(node))

    def _is_rank_call(self, ctx, node):
        return isinstance(node, ast.Call) and \
            ctx.resolve_call(node) in self.RANK_SOURCES

    def _mentions_rank(self, ctx, node, tainted):
        for sub in ast.walk(node):
            if self._is_rank_call(ctx, sub):
                return True
            if isinstance(sub, ast.Name) and sub.id in tainted:
                return True
        return False

    def check(self, ctx):
        if not ctx.is_library():
            return
        for scope in self._scopes(ctx.tree):
            # pass 1: names assigned from expressions containing a
            # process_index() call ("rank = jax.process_index()",
            # "is_main = jax.process_index() == 0")
            tainted = set()
            for node in self._scope_children(scope):
                if isinstance(node, ast.Assign) and any(
                        self._is_rank_call(ctx, s)
                        for s in ast.walk(node.value)):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            tainted.add(tgt.id)
            # pass 2: descend tracking whether we are under a
            # rank-dependent condition; flag collectives there
            yield from self._descend(ctx, scope, tainted, False)

    def _descend(self, ctx, node, tainted, guarded):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue            # its own scope handles it
            if isinstance(child, (ast.If, ast.While)):
                rank_test = self._mentions_rank(ctx, child.test, tainted)
                yield from self._descend(ctx, child.test, tainted,
                                         guarded)
                for part in child.body + child.orelse:
                    yield from self._walk_stmt(ctx, part, tainted,
                                               guarded or rank_test)
                continue
            if isinstance(child, ast.IfExp):
                rank_test = self._mentions_rank(ctx, child.test, tainted)
                yield from self._descend(ctx, child.test, tainted,
                                         guarded)
                for part in (child.body, child.orelse):
                    yield from self._walk_stmt(ctx, part, tainted,
                                               guarded or rank_test)
                continue
            if isinstance(child, ast.BoolOp):
                # short-circuit entry: `rank == 0 and allgather(...)`
                seen_rank = False
                for operand in child.values:
                    yield from self._walk_stmt(ctx, operand, tainted,
                                               guarded or seen_rank)
                    seen_rank = seen_rank or \
                        self._mentions_rank(ctx, operand, tainted)
                continue
            if guarded and isinstance(child, ast.Call) and \
                    ctx.resolve_call(child) in self.COLLECTIVES:
                yield self.finding(
                    ctx, child.lineno,
                    "collective entered under a rank-dependent "
                    "condition — guarded ranks wait forever for peers "
                    "that never arrive; make entry unconditional and "
                    "share the one-rank decision via a broadcast "
                    "(docs/elastic.md)")
                # still descend: nested collectives get their own lines
            yield from self._descend(ctx, child, tainted, guarded)

    def _walk_stmt(self, ctx, node, tainted, guarded):
        """Flag a collective at ``node`` itself, then descend."""
        if guarded and isinstance(node, ast.Call) and \
                ctx.resolve_call(node) in self.COLLECTIVES:
            yield self.finding(
                ctx, node.lineno,
                "collective entered under a rank-dependent "
                "condition — guarded ranks wait forever for peers "
                "that never arrive; make entry unconditional and "
                "share the one-rank decision via a broadcast "
                "(docs/elastic.md)")
        yield from self._descend(ctx, node, tainted, guarded)


@register
class UnboundedKeyedRegistry(Rule):
    code = "G14"
    name = "unbounded-keyed-registry"
    severity = "error"
    doc = ("Dict/set attribute in library-code classes indexed by "
           "externally-supplied keys — the key expression names a "
           "request-shaped identifier (tenant, request/req id, step, "
           "path/file name, session/client/user/token, trace/span id) "
           "and the insert sits in a PUBLIC method — with inserts but "
           "no eviction/cap on any path in the class. A long-lived "
           "server then grows host memory one entry per novel key "
           "forever: the ParamStore bad-step-set hazard class "
           "(churning commit root), the per-tenant counter-table "
           "class, the Prometheus label-cardinality class. Bound it: "
           "LRU-cap with popitem/pop, prune against `len(...)` "
           "compares, or reset the container on a lifecycle path. "
           "Containers whose inserts only happen in underscore-private "
           "methods are out of scope (the caller owns the key space), "
           "as are key names outside the vocabulary (operator-bounded "
           "registries). Scope: mxnet_tpu/ library classes.")

    # request-shaped identifier vocabulary: a key built from one of
    # these tokens is presumed externally supplied (request fields,
    # tenant ids, file/step names) rather than operator-configured
    VOCAB = {"tenant", "tenants", "step", "steps", "request", "req",
             "path", "paths", "file", "files", "fname", "filename",
             "client", "session", "user", "token", "trace", "span"}

    CONTAINERS = {"dict", "set", "collections.OrderedDict",
                  "collections.defaultdict", "OrderedDict",
                  "defaultdict"}
    EVICTORS = {"pop", "popitem", "clear", "discard", "remove"}

    @staticmethod
    def _self_attr(node):
        """'x' for a `self.x` attribute expression, else None."""
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "self":
            return node.attr
        return None

    def _container_attrs(self, ctx, cls) -> set:
        """Attrs assigned a fresh dict/set/OrderedDict/defaultdict
        anywhere in the class (the `self._seen = {}` shape)."""
        out = set()
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            fresh = isinstance(v, (ast.Dict, ast.Set)) or (
                isinstance(v, ast.Call)
                and ctx.resolve_call(v) in self.CONTAINERS)
            if not fresh:
                continue
            for tgt in node.targets:
                attr = self._self_attr(tgt)
                if attr:
                    out.add(attr)
        return out

    def _evicted_attrs(self, ctx, cls, attrs) -> set:
        """Attrs with eviction/cap evidence on ANY path: an evictor
        method call, `del self.x[...]`, a `len(self.x)` inside a
        Compare (the `while len(...) > cap: popitem()` shape), or a
        reset-reassignment outside __init__."""
        out = set()
        init = next((n for n in cls.body
                     if isinstance(n, ast.FunctionDef)
                     and n.name == "__init__"), None)
        init_nodes = set(ast.walk(init)) if init is not None else set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in self.EVICTORS:
                attr = self._self_attr(node.func.value)
                if attr in attrs:
                    out.add(attr)
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        attr = self._self_attr(t.value)
                        if attr in attrs:
                            out.add(attr)
            elif isinstance(node, ast.Compare):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Name) and \
                            sub.func.id == "len" and sub.args:
                        attr = self._self_attr(sub.args[0])
                        if attr in attrs:
                            out.add(attr)
            elif isinstance(node, ast.Assign) and node not in init_nodes:
                for tgt in node.targets:
                    attr = self._self_attr(tgt)
                    if attr in attrs:
                        out.add(attr)       # lifecycle reset path
        return out

    def _key_is_external(self, key_expr) -> bool:
        """True when a Name in the key expression carries a
        vocabulary token (`request_id`, `step`, `fname`, ...)."""
        for sub in ast.walk(key_expr):
            if isinstance(sub, ast.Name):
                tokens = sub.id.lower().split("_")
                if any(t in self.VOCAB for t in tokens):
                    return True
        return False

    def _inserts(self, method):
        """(line, attr, key_expr) for each insert in one method:
        `self.x[k] = v`, `self.x.add(k)`, `self.x.setdefault(k, ...)`."""
        for node in ast.walk(method):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for tgt in targets:
                    if isinstance(tgt, ast.Subscript):
                        attr = self._self_attr(tgt.value)
                        if attr:
                            yield node.lineno, attr, tgt.slice
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("add", "setdefault") and node.args:
                attr = self._self_attr(node.func.value)
                if attr:
                    yield node.lineno, attr, node.args[0]

    def check(self, ctx):
        if not ctx.is_library():
            return
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            attrs = self._container_attrs(ctx, cls)
            if not attrs:
                continue
            evicted = self._evicted_attrs(ctx, cls, attrs)
            for method in cls.body:
                if not isinstance(method, ast.FunctionDef):
                    continue
                if method.name.startswith("_"):
                    continue           # private: caller owns the keys
                for line, attr, key_expr in self._inserts(method):
                    if attr not in attrs or attr in evicted:
                        continue
                    if not self._key_is_external(key_expr):
                        continue
                    yield self.finding(
                        ctx, line,
                        f"unbounded keyed registry: `self.{attr}` is "
                        "inserted with an externally-supplied key in a "
                        "public method but nothing in the class ever "
                        "evicts or caps it — a long-lived server grows "
                        "one entry per novel key forever; add an LRU "
                        "cap/pruning (ParamStore's bad-step LRU is the "
                        "model)")


@register
class UnvalidatedCacheDeserialize(Rule):
    code = "G21"
    name = "unvalidated-cache-deserialize"
    severity = "error"
    doc = ("Deserializing a persisted executable/pickle without a "
           "version-envelope or CRC check on the read path: a function "
           "that both reads file bytes AND hands them to an unguarded "
           "deserializer (pickle.load/loads, marshal, an Unpickler, "
           "jax.export.deserialize, serialize_executable."
           "deserialize_and_load) will happily load a torn write, a "
           "bit-flipped sector, or a stale-toolchain artifact as live "
           "state — the failure is wrong NUMERICS or a segfaulting "
           "executable, not a clean error.  The AOT compile cache "
           "(serving/aotcache.py) is the model read path: magic + "
           "bounds + CRC32 + a jax/jaxlib/backend envelope are all "
           "verified (serving/aot_report.read_entry) before any byte "
           "reaches the deserializer.  Evidence that satisfies the "
           "rule, anywhere in the same function: a zlib/binascii CRC "
           "or hashlib digest call, or identifiers carrying "
           "crc/checksum/magic/envelope/sha tokens (a delegated "
           "validate helper names itself).  Deserializing bytes the "
           "caller passed in (no file read in the function) is out of "
           "scope — the reader that pulled them off disk owns the "
           "check.  Scope: mxnet_tpu/ library code.")

    # unguarded deserializers of attacker/corruption-visible bytes
    # (pickle.Unpickler itself is NOT here: the constructor only wraps
    # the stream — the .load() call is the deserialize, matched below)
    DESERIALIZERS = {"pickle.load", "pickle.loads",
                     "marshal.load", "marshal.loads",
                     "jax.export.deserialize"}
    DESER_SUFFIX = ("deserialize_and_load",)
    # file-read shapes: open() in the function, or .read()/.read_bytes()
    READ_ATTRS = {"read", "read_bytes"}
    # validation evidence: digest calls or validation-named identifiers
    EVIDENCE_CALLS = {"zlib.crc32", "binascii.crc32"}
    EVIDENCE_PREFIX = ("hashlib.",)
    EVIDENCE_TOKENS = {"crc", "crc32", "checksum", "magic", "envelope",
                       "sha1", "sha256", "digest"}

    @staticmethod
    def _scope_nodes(scope):
        """Nodes of this function only — nested defs/lambdas are their
        own read paths and carry their own evidence."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(node))

    def _is_deserializer(self, ctx, call) -> bool:
        name = ctx.resolve_call(call)
        if name:
            if name in self.DESERIALIZERS:
                return True
            if name.endswith(self.DESER_SUFFIX):
                return True
        # method spelling: anything.load() on an Unpickler instance is
        # out of reach without types; catch the documented pattern
        # Unpickler(...).load() in one expression
        f = call.func
        if isinstance(f, ast.Attribute) and f.attr == "load" and \
                isinstance(f.value, ast.Call):
            inner = ctx.resolve_call(f.value)
            if inner and inner.endswith("Unpickler"):
                return True
        return False

    def _reads_file(self, ctx, fn) -> bool:
        for node in self._scope_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.resolve_call(node)
            if name == "open":
                return True
            f = node.func
            if isinstance(f, ast.Attribute) and \
                    f.attr in self.READ_ATTRS:
                return True
        return False

    def _has_evidence(self, ctx, fn) -> bool:
        for node in self._scope_nodes(fn):
            if isinstance(node, ast.Call):
                name = ctx.resolve_call(node)
                if name and (name in self.EVIDENCE_CALLS or
                             name.startswith(self.EVIDENCE_PREFIX)):
                    return True
            ident = None
            if isinstance(node, ast.Name):
                ident = node.id
            elif isinstance(node, ast.Attribute):
                ident = node.attr
            if ident:
                tokens = ident.lower().split("_")
                if any(t in self.EVIDENCE_TOKENS for t in tokens):
                    return True
        return False

    def check(self, ctx):
        if not ctx.is_library():
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            deser_lines = [
                n.lineno for n in self._scope_nodes(fn)
                if isinstance(n, ast.Call)
                and self._is_deserializer(ctx, n)]
            if not deser_lines:
                continue
            if not self._reads_file(ctx, fn):
                continue            # caller-supplied bytes: reader owns it
            if self._has_evidence(ctx, fn):
                continue
            for line in deser_lines:
                yield self.finding(
                    ctx, line,
                    "unvalidated cache deserialize: this function reads "
                    "persisted bytes and hands them to a deserializer "
                    "with no CRC/version-envelope check in sight — a "
                    "torn or stale entry becomes wrong numerics instead "
                    "of a clean fallback; validate first "
                    "(serving/aot_report.read_entry is the model) or "
                    "route through a checked reader")
