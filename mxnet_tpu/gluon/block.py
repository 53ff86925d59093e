"""Gluon Block / HybridBlock.

TPU-native re-design of the reference's module system
(ref: python/mxnet/gluon/block.py — Block, HybridBlock, SymbolBlock).

The reference's ``hybridize()`` traces a block into an NNVM graph executed by
``CachedOp`` (ref: src/imperative/cached_op.cc). Here ``hybridize()`` lowers
the block to **one jitted XLA program** via ``jax.jit`` — the mapping SURVEY
§7 calls the most natural in the whole port. Details of the design:

- the traced function takes ``(rng_key, trainable_params, aux_params,
  *inputs)`` so randomness is threaded explicitly (TPU-idiomatic) and XLA
  sees parameters as runtime arguments (no retrace when values change);
- auxiliary state updated during forward (BatchNorm running stats) is
  returned as extra outputs and written back after the call — mutation is
  hoisted out of the pure program;
- under ``autograd.record()`` the whole jitted program records ONE tape node
  whose pullback is the XLA-compiled transpose, so backward is compiled too;
- ``static_alloc``/``static_shape`` flags are accepted for API compatibility
  (XLA's jit cache + buffer assignment already provide both).
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import jax
import numpy as np

from .. import _rng, autograd
from .. import ndarray as nd
from ..base import MXNetError, RECOMPUTE_KEEP, _as_np_dtype
from ..context import Context, current_context
from ..observability import instrument as _obs
from ..observability import stages as _stages
from .parameter import (DEFERRED, DeferredInitializationError, Parameter,
                        ParameterDict)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "functional_apply"]


def functional_apply(block, key, tr_datas, aux_datas, input_datas,
                     training=True, ctx=None):
    """Run a Gluon block as a pure function of its parameter arrays.

    This is the predictor-extraction primitive — the bridge between the
    mutable Gluon world and functional XLA shared by the sharded/pipelined
    trainers (``parallel/``) and the serving predictor cache
    (``serving/cache.py``): parameter handles are temporarily rebound to
    the traced arrays, the block runs eagerly (every op dispatches to jnp
    on tracers), and the handles are restored. Returns ``(out_datas,
    out_treedef, aux_new_datas)``; auxiliary state (BatchNorm running
    stats) is captured from the rebound handles — mutation hoisted into
    explicit outputs.
    """
    trainable, aux = block._param_split()
    if ctx is None:
        ctx = current_context()
    saved = []
    temps = {}
    for param, data in list(zip(trainable, tr_datas)) + \
            list(zip(aux, aux_datas)):
        saved.append((param, param._data))
        arr = nd.NDArray(data, ctx=ctx, _skip_device_put=True)
        temps[id(param)] = arr
        param._data = [arr] * len(param._ctx_list or [ctx])
    try:
        # trace with recording OFF — a jitted program is differentiated
        # as one unit from outside, never via the eager tape
        with _rng.trace_key(key), autograd.pause(train_mode=training):
            out = Block.__call__(block, *[
                nd.NDArray(d, ctx=ctx, _skip_device_put=True)
                if not isinstance(d, nd.NDArray) else d
                for d in input_datas])
        out_flat, treedef = jax.tree_util.tree_flatten(
            out, is_leaf=lambda x: isinstance(x, nd.NDArray))
        out_datas = [o._data if isinstance(o, nd.NDArray) else o
                     for o in out_flat]
        aux_new = [temps[id(p)]._data for p in aux]
    finally:
        for param, data in saved:
            param._data = data
    return out_datas, treedef, aux_new

_naming = threading.local()


def _counters():
    if not hasattr(_naming, "counts"):
        _naming.counts = {}
    return _naming.counts


class _BlockScope:
    """Auto prefix generation (ref: gluon/block.py _BlockScope)."""
    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                counts = _counters()
                idx = counts.get(hint, 0)
                counts[hint] = idx + 1
                prefix = f"{hint}{idx}_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, shared=params)
            return prefix, params
        if prefix is None:
            idx = current._counter.get(hint, 0)
            current._counter[hint] = idx + 1
            prefix = f"{hint}{idx}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, shared=parent._shared)
        else:
            params = ParameterDict(params.prefix, shared=params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


class Block:
    """Base class of all layers and models (ref: gluon/block.py Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return self._scope

    def __repr__(self):
        lines = [f"{self.__class__.__name__}("]
        for key, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({key}): {child_repr}")
        lines.append(")")
        return "\n".join(lines)

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = getattr(self, name, None)
            if isinstance(existing, Block):
                self._children.pop(name, None)
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def collect_params(self, select=None) -> ParameterDict:
        """All parameters of self + descendants (ref: Block.collect_params)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update({p.name: p for p in self._reg_params.values()})
        else:
            pattern = re.compile(select)
            ret.update({p.name: p for p in self._reg_params.values()
                        if pattern.match(p.name)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        # include params registered directly on self.params (name_scope usage)
        if select is None:
            ret.update({name: p for name, p in self._params.items()})
        else:
            pattern = re.compile(select)
            ret.update({name: p for name, p in self._params.items()
                        if pattern.match(name)})
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for param in self._reg_params.values():
            param.cast(dtype)
        self._on_cast(dtype)

    def _on_cast(self, dtype):
        pass

    def zero_grad(self):
        self.collect_params().zero_grad()

    # -- checkpointing (ref: Block.save_parameters / load_parameters) --------
    def _structural_names(self, prefix=""):
        """name → Parameter keyed by *structural* path ('0.weight'), the
        reference's load-anywhere format (ref: block.py
        _collect_params_with_prefix)."""
        out = OrderedDict()
        for attr, param in self._reg_params.items():
            out[prefix + attr] = param
        for name, p in self._params.items():
            # params registered directly on self.params inside name_scope
            key = name[len(self._params.prefix):] \
                if name.startswith(self._params.prefix) else name
            out.setdefault(prefix + key, p)
        for name, child in self._children.items():
            out.update(child._structural_names(prefix + name + "."))
        return out

    def save_parameters(self, filename, deduplicate=False):
        arg_dict = {}
        for key, param in self._structural_names().items():
            arg_dict[key] = param.data(param.list_ctx()[0])
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = nd.load(filename)
        if not isinstance(loaded, dict):
            raise MXNetError(f"{filename} is not a parameter dict file")
        self.load_dict(loaded, ctx=ctx, allow_missing=allow_missing,
                       ignore_extra=ignore_extra, cast_dtype=cast_dtype,
                       dtype_source=dtype_source, source=filename)

    def load_dict(self, loaded, ctx=None, allow_missing=False,
                  ignore_extra=False, cast_dtype=False,
                  dtype_source="current", source="<param dict>"):
        """Load parameters from an already-loaded name→NDArray dict (ref:
        gluon Block.load_dict). The in-memory half of ``load_parameters``
        — the serving hot-reload path applies checkpoint dicts through
        here so a swap needs no extra disk round trip. ``arg:``/``aux:``
        prefixes from ``HybridBlock.export`` artifacts are stripped."""
        if any(k.partition(":")[0] in ("arg", "aux") and ":" in k
               for k in loaded):
            loaded = {k.partition(":")[2] if ":" in k and
                      k.partition(":")[0] in ("arg", "aux") else k: v
                      for k, v in loaded.items()}
        params = self._structural_names()
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        for key, param in params.items():
            if key not in loaded:
                if not allow_missing:
                    raise MXNetError(f"parameter {key} missing from {source}")
                continue
            value = loaded[key]
            if cast_dtype and dtype_source == "current" and \
                    param.dtype is not None:
                value = nd.NDArray(value._data, ctx=value.ctx,
                                   dtype=param.dtype)
            param._load_init(value, ctx)
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"{source} has extra parameters "
                                 f"{sorted(extra)}; pass ignore_extra=True")

    save_params = save_parameters          # deprecated aliases kept
    load_params = load_parameters

    # -- execution -----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if DEFERRED and _stages.current() != "deferred_shapes" \
                and self._resolves_deferred():
            # the eager call that resolves this net's deferred shapes
            with _obs.setup_stage("deferred_shapes"):
                return Block.__call__(self, *args, **kwargs)
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def _resolves_deferred(self):
        return any(p._deferred_init
                   for p in self.collect_params().values())

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        """No-op on plain Blocks; recurses so nested HybridBlocks engage."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def _param_split(self):
        params = [p for p in self.collect_params().values()]
        trainable = [p for p in params if p.grad_req != "null"]
        aux = [p for p in params if p.grad_req == "null"]
        return trainable, aux

    def summary(self, *inputs):
        """Print a per-layer summary (ref: Block.summary), minimal edition."""
        lines = [f"{'Layer':<40}{'Output':<24}{'Params':<12}"]
        total = 0
        for name, param in self.collect_params().items():
            if param.shape and not param._shape_incomplete():
                count = int(np.prod(param.shape))
                total += count
                lines.append(f"{name:<40}{str(param.shape):<24}{count:<12}")
        lines.append(f"Total params: {total}")
        print("\n".join(lines))


class HybridBlock(Block):
    """A Block that can be lowered to one compiled XLA program
    (ref: gluon/block.py HybridBlock; CachedOp ≡ jax.jit per SURVEY §7)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_fns = {}
        self._flags = {}
        self._out_treedef = None
        self._recompute = False

    def recompute(self, active=True):
        """Keep only this block's inputs for the backward pass and compute
        its activations again there (``jax.checkpoint`` around the block's
        forward): the memory of a deep stack becomes one block's internals
        plus every block's input. It takes effect where the block runs in
        training mode inside a compiled program (a hybridized parent, a
        trainer's ``step`` / ``run_steps``) and is a no-op in predict mode
        and on the eager tape. The reference's analog is
        MXNET_BACKWARD_DO_MIRROR (src/executor/graph_executor.cc), which is
        a process-wide switch; this is one block's."""
        self._recompute = bool(active)
        return self

    def _call_recomputed(self, *args):
        trainable, aux = self._param_split()
        ctx = args[0].ctx
        treedefs = []

        def forward(key, tr_datas, aux_datas, *in_datas):
            outs, treedef, aux_new = functional_apply(
                self, key, tr_datas, aux_datas, in_datas, training=True,
                ctx=ctx)
            treedefs.append(treedef)
            return tuple(outs), tuple(aux_new)

        # nothing is kept but what a block names RECOMPUTE_KEEP (a router's
        # choice: ops/moe.py); without such a name this is the default policy
        outs, aux_new = jax.checkpoint(
            forward, policy=jax.checkpoint_policies.save_only_these_names(
                RECOMPUTE_KEEP))(
            _rng.next_key(), [p._data[0]._data for p in trainable],
            [p._data[0]._data for p in aux], *[a._data for a in args])
        for param, new in zip(aux, aux_new):
            param._data[0]._rebind(new)
        return jax.tree_util.tree_unflatten(
            treedefs[0], [nd.NDArray(o, ctx=ctx, _skip_device_put=True)
                          for o in outs])

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, forward_bulk_size=None,
                  backward_bulk_size=None):
        self._active = active
        self._flags = {"static_alloc": static_alloc,
                       "static_shape": static_shape}
        self._cached_fns = {}
        for child in self._children.values():
            child.hybridize(active, static_alloc=static_alloc,
                            static_shape=static_shape)

    def _clear_cached_op(self):
        self._cached_fns = {}

    def infer_shape(self, *args):
        """Set shapes of this block's deferred params from input shapes.
        Leaf layers override; containers resolve via their children."""
        if self._reg_params and any(
                p._deferred_init for p in self._reg_params.values()):
            raise MXNetError(
                f"{self.__class__.__name__} has deferred-init parameters but "
                f"does not implement infer_shape()")

    def _deferred_pending(self):
        return any(p._deferred_init for p in self._reg_params.values())

    def _finish_deferred(self, *args):
        self.infer_shape(*args)
        for param in self._reg_params.values():
            param._finish_deferred_init()

    def forward(self, *args, **kwargs):
        """Gather this block's registered params and run ``hybrid_forward``.
        Symbol inputs trace symbolically (F = mx.sym, params become
        variables) — the reference's dual-world dispatch."""
        from .. import symbol as sym_mod
        if args and isinstance(args[0], sym_mod.Symbol):
            params = {name: p.var()
                      for name, p in self._reg_params.items()}
            return self.hybrid_forward(sym_mod, *args, **kwargs, **params)
        if self._deferred_pending():
            self._finish_deferred(*args)
        ctx = None
        for a in args:
            if isinstance(a, nd.NDArray):
                ctx = a.ctx
                break
        try:
            params = {name: p.data(ctx)
                      for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._finish_deferred(*args)
            params = {name: p.data(ctx)
                      for name, p in self._reg_params.items()}
        return self.hybrid_forward(nd, *args, **kwargs, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- the CachedOp equivalent ---------------------------------------------
    def __call__(self, *args, **kwargs):
        from .. import symbol as sym_mod
        if args and isinstance(args[0], sym_mod.Symbol):
            return super().__call__(*args, **kwargs)   # symbolic trace
        if args:
            self._num_inputs = len(args)
        if self._active and not _rng.in_trace():
            return self._call_cached(*args)
        if self._recompute and not kwargs and _rng.in_trace() and \
                autograd.is_training() and args and \
                all(isinstance(a, nd.NDArray) for a in args):
            return self._call_recomputed(*args)
        return super().__call__(*args, **kwargs)

    def _ensure_ready(self, args):
        """Resolve every descendant's deferred init by a one-time eager pass."""
        pending = any(p._data is None
                      for p in self.collect_params().values())
        if pending:
            with _obs.setup_stage("deferred_shapes"), autograd.pause():
                super().__call__(*args)

    def _build_fn(self, training, n_args, ctx):
        self_block = self

        def fn(rng_key, trainable_datas, aux_datas, *input_datas):
            out_datas, treedef, aux_new = functional_apply(
                self_block, rng_key, trainable_datas, aux_datas,
                list(input_datas), training=training, ctx=ctx)
            self_block._out_treedef = treedef
            return tuple(out_datas) + tuple(aux_new)
        return jax.jit(fn)

    def _call_cached(self, *args):
        self._ensure_ready(args)
        ctx = None
        for a in args:
            if isinstance(a, nd.NDArray):
                ctx = a.ctx
                break
        if ctx is None:
            ctx = current_context()
        training = autograd.is_training()
        from .. import _dispatch
        key = (training, len(args), str(ctx), _dispatch.amp_epoch())
        jitted = self._cached_fns.get(key)
        if jitted is None:
            jitted = self._build_fn(training, len(args), ctx)
            self._cached_fns[key] = jitted

        trainable, aux = self._param_split()
        idx = 0  # hybridized execution uses the primary context replica
        tr_datas = [p._data[idx]._data for p in trainable]
        aux_datas = [p._data[idx]._data for p in aux]
        in_datas = [a._data if isinstance(a, nd.NDArray) else
                    np.asarray(a) for a in args]
        rng_key = _rng.next_key()

        recording = autograd.is_recording() and (
            trainable or any(isinstance(a, nd.NDArray) and
                             (a._tape_node is not None or a._grad is not None)
                             for a in args))
        n_tr = len(tr_datas)
        if recording:
            def wrapped(*xs):
                res = jitted(rng_key, list(xs[:n_tr]), aux_datas,
                             *xs[n_tr:])
                # singleton outputs unpack so the TapeNode cotangent
                # convention (scalar ct for 1 output) matches the vjp tree
                return res[0] if len(res) == 1 else res
            out_all, vjp_fn = jax.vjp(wrapped, *(tr_datas + in_datas))
            if not isinstance(out_all, tuple):
                out_all = (out_all,)
            parents = [(None, 0, p._data[idx]) for p in trainable]
            for a in args:
                if isinstance(a, nd.NDArray) and a._grad is not None:
                    parents.append((None, 0, a))
                elif isinstance(a, nd.NDArray) and a._tape_node is not None:
                    parents.append((a._tape_node, a._tape_out_idx, None))
                else:
                    parents.append((None, 0, None))
            avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_all]
            fwd_inputs = [p._data[idx] for p in trainable] + [
                a if isinstance(a, nd.NDArray) else d
                for a, d in zip(args, in_datas)]
            node = autograd.TapeNode(vjp_fn, parents, avals,
                                     fwd_fn=wrapped, fwd_inputs=fwd_inputs)
        else:
            out_all = jitted(rng_key, tr_datas, aux_datas, *in_datas)
            node = None

        n_aux = len(aux)
        n_out = len(out_all) - n_aux
        out_datas = out_all[:n_out]
        aux_new = out_all[n_out:]
        for param, new in zip(aux, aux_new):
            param._data[idx]._rebind(new)

        outs = []
        for i, data in enumerate(out_datas):
            arr = nd.NDArray(data, ctx=ctx, _skip_device_put=True)
            if node is not None:
                arr._tape_node = node
                arr._tape_out_idx = i
            outs.append(arr)
        if self._out_treedef is not None:
            return jax.tree_util.tree_unflatten(self._out_treedef, outs)
        return outs[0] if len(outs) == 1 else tuple(outs)

    # -- deployment (ref: HybridBlock.export → -symbol.json + .params) -------
    def export(self, path, epoch=0, remove_amp_cast=True):
        """Serialize for deployment: trace the block symbolically into a
        real ``path-symbol.json`` graph (loadable by SymbolBlock.imports /
        mx.sym.load — the reference's deployment contract, SURVEY §3.5) +
        ``path-%04d.params`` weights with arg:/aux: keys."""
        from .. import symbol as sym_mod
        n = getattr(self, "_num_inputs", 1)
        names = ["data"] if n == 1 else [f"data{i}" for i in range(n)]
        out = self(*[sym_mod.var(nm) for nm in names])
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        out.save(f"{path}-symbol.json")
        params = {}
        for name, param in self.collect_params().items():
            params[("arg:" if param.grad_req != "null" else "aux:") + name] = \
                param.data(param.list_ctx()[0])
        nd.save(f"{path}-{epoch:04d}.params", params)
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"


class SymbolBlock(HybridBlock):
    """Runs a loaded Symbol graph as a Gluon block (ref: gluon
    SymbolBlock): the deployment path for ``HybridBlock.export`` /
    ``mx.model.save_checkpoint`` artifacts."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        if isinstance(outputs, (list, tuple)):
            from .. import symbol as sym_mod
            outputs = sym_mod.Group(list(outputs))
        self._outputs = outputs
        self._inputs = inputs
        input_names = {s.name for s in inputs}
        aux = set(outputs.list_auxiliary_states())
        for name in (outputs.list_arguments()
                     + outputs.list_auxiliary_states()):
            if name in input_names or name in self._params:
                continue
            self.params.get(name, grad_req="null" if name in aux
                            else "write", allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        symbol = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        block = SymbolBlock(symbol, inputs)
        if param_file:
            block.collect_params().load(param_file, ctx=ctx,
                                        allow_missing=False,
                                        ignore_extra=True)
        return block

    def forward(self, *args):
        from .. import symbol as sym_mod
        return sym_mod.eval_symbol(self._outputs, self._inputs, args,
                                   self.collect_params())
