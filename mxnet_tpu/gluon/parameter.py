"""Gluon Parameter / ParameterDict.

TPU-native re-design of the reference's parameter container
(ref: python/mxnet/gluon/parameter.py — Parameter, ParameterDict, Constant).
Semantics preserved: deferred shape inference + lazy init, ``grad_req``
write/add/null, per-context replicas (``list_data``/``list_grad``), prefix
scoping, save/load. Differences by design: replicas are only materialised
when multiple contexts are requested — the idiomatic TPU data-parallel path
is a *sharded* parameter on a mesh (see mxnet_tpu.parallel), not N copies.
"""
from __future__ import annotations

import weakref
from collections import OrderedDict
from typing import List, Optional

import numpy as np

from .. import initializer as _init_mod
from .. import ndarray as nd
from ..base import MXNetError, _as_np_dtype, mx_real_t
from ..context import Context, cpu, current_context
from ..observability.instrument import setup_stage

__all__ = ["DeferredInitializationError", "Parameter", "Constant",
           "ParameterDict", "tensor_types"]

tensor_types = (nd.NDArray,)

# Parameters whose initialization waits for a shape: empty in steady state,
# so ``Block.__call__`` looks for a deferred shape of its own only while
# some net in the process has one.
DEFERRED = weakref.WeakSet()


class DeferredInitializationError(MXNetError):
    """Raised when a parameter's data is requested before shape inference."""


class Parameter:
    """A weight/bias/aux tensor of a Block (ref: gluon/parameter.py Parameter).

    Supports deferred initialization: construct with an incomplete shape
    (``None`` or dims of 0); call :meth:`initialize`; the first forward pass
    infers the real shape (``HybridBlock.infer_shape``) and init completes.
    """

    def __init__(self, name, grad_req="write", shape=None, dtype=mx_real_t,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = None
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = _as_np_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._data: Optional[List[nd.NDArray]] = None
        self._grad: Optional[List[nd.NDArray]] = None
        self._ctx_list: Optional[List[Context]] = None
        self._deferred_init = ()
        self._attrs = {}
        if not differentiable:
            grad_req = "null"
        self.grad_req = grad_req
        if stype != "default":
            raise MXNetError("sparse parameter storage is not supported on "
                             "the TPU build (stype must be 'default'); "
                             "grad_stype='row_sparse' IS supported for "
                             "Embedding-style sparse gradients")
        if grad_stype not in ("default", "row_sparse"):
            raise MXNetError(f"grad_stype {grad_stype!r}: must be "
                             f"'default' or 'row_sparse'")
        self._grad_stype = grad_stype

    def __repr__(self):
        return (f"Parameter {self.name} (shape={self.shape}, "
                f"dtype={np.dtype(self.dtype).name})")

    # -- grad_req -----------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {req!r}")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                for arr in self._data:
                    arr._grad = None
                    arr._grad_req = "null"
        elif self._data is not None:
            self._init_grad()

    # -- shape inference ----------------------------------------------------
    def _shape_incomplete(self):
        return self.shape is None or any(s == 0 for s in self.shape)

    def _set_shape(self, new_shape):
        """Called by HybridBlock.infer_shape once input shapes are known."""
        new_shape = tuple(int(s) for s in new_shape)
        if self.shape is not None and not self._shape_incomplete():
            if self.shape != new_shape:
                raise MXNetError(
                    f"inferred shape {new_shape} for {self.name} does not "
                    f"match declared shape {self.shape}")
            return
        if self.shape is not None and len(self.shape) == len(new_shape):
            for declared, inferred in zip(self.shape, new_shape):
                if declared != 0 and declared != inferred:
                    raise MXNetError(
                        f"inferred shape {new_shape} for {self.name} clashes "
                        f"with declared {self.shape}")
        self.shape = new_shape

    # -- init ---------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """ref: Parameter.initialize — allocate and fill on ctx."""
        if self._data is not None and not force_reinit:
            return
        if default_init is None:
            default_init = _init_mod.Uniform()
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx_list = list(ctx)
        if self._shape_incomplete():
            if not self.allow_deferred_init:
                raise MXNetError(
                    f"cannot initialize {self.name}: shape {self.shape} is "
                    f"incomplete and allow_deferred_init=False")
            self._deferred_init = (init, default_init)
            DEFERRED.add(self)
            return
        self._finish_init(init, default_init)

    def _finish_init(self, init, default_init):
        initializer = self.init if self.init is not None else init
        if initializer is None:
            initializer = default_init
        if isinstance(initializer, str):
            initializer = _init_mod.create(initializer)
        desc = _init_mod.InitDesc(self.name, attrs=dict(self._attrs))
        with setup_stage("initialize"):
            data = nd.empty(self.shape, dtype=self.dtype, ctx=cpu())
            initializer(desc, data)
            self._data = [nd.NDArray(data._data, ctx=c, dtype=self.dtype)
                          for c in self._ctx_list]
            self._deferred_init = ()
            DEFERRED.discard(self)
            if self.grad_req != "null":
                self._init_grad()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        if self._shape_incomplete():
            raise DeferredInitializationError(
                f"parameter {self.name} shape is still {self.shape} after "
                f"shape inference")
        init, default_init = self._deferred_init
        self._finish_init(init, default_init)

    def _init_grad(self):
        self._grad = [nd.zeros(self.shape, dtype=self.dtype, ctx=c)
                      for c in self._ctx_list]
        for g in self._grad:
            g._zeroed = True     # fresh: sparse add-deposits may stay sparse
        for arr, g in zip(self._data, self._grad):
            arr._grad = g
            arr._grad_req = self.grad_req

    # -- access -------------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is not None:
            return
        if self._deferred_init:
            raise DeferredInitializationError(
                f"parameter {self.name} has deferred initialization pending "
                f"(shape {self.shape}); run a forward pass to infer shapes")
        raise MXNetError(
            f"parameter {self.name} has not been initialized; call "
            f".initialize() (or net.initialize()) first")

    def _ctx_index(self, ctx):
        if ctx is None:
            return 0
        for i, c in enumerate(self._ctx_list):
            if c == ctx:
                return i
        raise MXNetError(f"parameter {self.name} was not initialized on {ctx}; "
                         f"contexts: {self._ctx_list}")

    def data(self, ctx=None) -> nd.NDArray:
        self._check_initialized(ctx)
        return self._data[self._ctx_index(ctx)]

    def list_data(self):
        self._check_initialized()
        return list(self._data)

    def grad(self, ctx=None) -> nd.NDArray:
        self._check_initialized(ctx)
        if self._grad is None:
            raise MXNetError(f"parameter {self.name} has grad_req='null'")
        buf = self._grad[self._ctx_index(ctx)]
        if getattr(self, "_grad_stype", "default") == "row_sparse":
            rs = getattr(buf, "_sparse", None)
            if rs is not None:
                return rs        # RowSparseNDArray: only touched rows
        return buf

    def list_grad(self):
        self._check_initialized()
        if self._grad is None:
            raise MXNetError(f"parameter {self.name} has grad_req='null'")
        return list(self._grad)

    def list_ctx(self):
        if self._ctx_list is None:
            raise MXNetError(f"parameter {self.name} not initialized")
        return list(self._ctx_list)

    def _load_init(self, data, ctx):
        """Initialize directly from a loaded value (ref: Parameter._load_init
        — the load-into-uninitialized-net path)."""
        self._set_shape(tuple(data.shape))
        if self._ctx_list is None:
            self._ctx_list = [ctx] if isinstance(ctx, Context) else list(ctx)
        if self._data is None:
            self._data = [nd.NDArray(data._data, ctx=c, dtype=self.dtype)
                          for c in self._ctx_list]
            self._deferred_init = ()
            DEFERRED.discard(self)
            if self.grad_req != "null":
                self._init_grad()
        else:
            self.set_data(data)

    def set_data(self, data):
        """Set this parameter's value on every context."""
        if self._data is None and self._deferred_init:
            # adopt the shape from the provided data, finish init, overwrite
            self._set_shape(tuple(data.shape))
            self._finish_deferred_init()
        self._check_initialized()
        src = data._data if isinstance(data, nd.NDArray) else np.asarray(data)
        if tuple(data.shape) != tuple(self.shape):
            raise MXNetError(f"set_data shape {tuple(data.shape)} != parameter "
                             f"shape {self.shape} for {self.name}")
        for i, c in enumerate(self._ctx_list):
            self._data[i]._rebind(
                nd.NDArray(src, ctx=c, dtype=self.dtype)._data)

    def zero_grad(self):
        if self._grad is None:
            return
        for g in self._grad:
            g._sparse = None     # drop any stale row-sparse view too
            g._zeroed = True     # fresh buffer: sparse adds may stay sparse
            g._rebind(nd.zeros(self.shape, dtype=self.dtype, ctx=g.ctx)._data)

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            host = self._data[0]
            self._ctx_list = list(ctx)
            self._data = [nd.NDArray(host._data, ctx=c) for c in ctx]
            if self.grad_req != "null":
                self._init_grad()
        elif self._ctx_list is not None:
            self._ctx_list = list(ctx)

    def cast(self, dtype):
        self.dtype = _as_np_dtype(dtype)
        if self._data is None:
            return
        self._data = [nd.NDArray(a._data, ctx=a.ctx, dtype=self.dtype)
                      for a in self._data]
        if self.grad_req != "null":
            self._init_grad()

    def var(self):
        """A symbolic variable bound to this parameter (ref: Parameter.var —
        used when tracing a block into a Symbol graph for export)."""
        from .. import symbol as sym_mod
        return sym_mod.var(self.name,
                           shape=self.shape if not self._shape_incomplete()
                           else None)


class Constant(Parameter):
    """A non-differentiable parameter with a fixed value (ref: gluon Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, nd.NDArray):
            value = nd.array(value)
        self.value = value

        class _CInit(_init_mod.Initializer):
            def __call__(self, desc, arr):  # bypass name-suffix dispatch
                arr._rebind(value._data)

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_CInit(),
                         differentiable=False)


class ParameterDict:
    """Prefix-scoped dict of Parameters (ref: gluon/parameter.py ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __contains__(self, name):
        return name in self._params

    def __getitem__(self, name) -> Parameter:
        return self._params[name]

    def __repr__(self):
        body = "\n".join(f"  {v!r}" for v in self._params.values())
        return f"ParameterDict '{self._prefix}' (\n{body}\n)"

    def get(self, name, **kwargs) -> Parameter:
        """Get-or-create ``prefix + name`` (the Block param entry point)."""
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            param = Parameter(full, **kwargs)
            self._params[full] = param
        else:
            for key, val in kwargs.items():
                if key == "shape" and val is not None:
                    if param.shape is None or param._shape_incomplete():
                        param.shape = tuple(val)
                elif val is not None and getattr(param, key, None) not in (val, None):
                    raise MXNetError(
                        f"parameter {full} already exists with "
                        f"{key}={getattr(param, key)!r}, requested {val!r}")
        return param

    def get_constant(self, name, value=None) -> Constant:
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            if value is None:
                raise MXNetError(f"constant {full} does not exist and no "
                                 f"value was given")
            param = Constant(full, value)
            self._params[full] = param
        return param

    def _get_impl(self, full_name):
        if full_name in self._params:
            return self._params[full_name]
        if self._shared is not None and full_name in self._shared:
            self._params[full_name] = self._shared[full_name]
            return self._params[full_name]
        return None

    def update(self, other):
        for key, val in other.items():
            if key in self._params and self._params[key] is not val:
                raise MXNetError(f"duplicate parameter name {key}")
            self._params[key] = val

    # -- bulk ops ------------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = _init_mod.Uniform()
        with setup_stage("initialize"):
            for param in self.values():
                param.initialize(None, ctx, default_init=init,
                                 force_reinit=force_reinit)

    def zero_grad(self):
        for param in self.values():
            param.zero_grad()

    def reset_ctx(self, ctx):
        for param in self.values():
            param.reset_ctx(ctx)

    def setattr(self, name, value):
        for param in self.values():
            setattr(param, name, value)

    def save(self, filename, strip_prefix=""):
        """ref: ParameterDict.save → the NDArray .params container format."""
        arg_dict = {}
        for param in self.values():
            name = param.name
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg_dict[name] = param.data(param.list_ctx()[0])
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        loaded = nd.load(filename)
        if not isinstance(loaded, dict):
            raise MXNetError(f"{filename} does not contain a name→array dict")
        # strip arg:/aux: prefixes from export/save_checkpoint artifacts
        # (ref: ParameterDict.load does the same)
        loaded = {(k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                   else k): v for k, v in loaded.items()}
        if restore_prefix:
            loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, param in self.items():
            if name not in loaded:
                if not allow_missing:
                    raise MXNetError(f"parameter {name} missing from "
                                     f"{filename}")
                continue
            param._load_init(loaded[name],
                             ctx if ctx is not None else [current_context()])
        if not ignore_extra:
            extra = set(loaded) - set(self.keys())
            if extra:
                raise MXNetError(f"{filename} contains extra parameters "
                                 f"{sorted(extra)}; pass ignore_extra=True")
