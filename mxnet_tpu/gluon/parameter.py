"""Gluon ``Parameter`` / ``ParameterDict`` / ``Constant``.

Reference: python/mxnet/gluon/parameter.py (SURVEY.md §2.2 "Gluon core").

TPU-native deltas from the reference:
  - A Parameter owns ONE NDArray, not per-context copies: multi-device data
    parallelism is expressed by *sharding* that one array over a mesh
    (jax.sharding), not by replicating Python handles (SURVEY.md §2.5 DP row).
  - Deferred init works the same way (shape with 0s resolved at first
    forward).
  - ``stype``/``grad_stype`` accepted; row_sparse grads fall back to dense
    (XLA apply is dense) with the flag recorded for the KVStore path.
"""
from __future__ import annotations

import re
import warnings

import numpy as _np
import jax
import jax.numpy as jnp

from .. import _tape
from ..base import MXNetError
from ..context import Context, current_context, cpu
from ..ndarray.ndarray import NDArray, zeros as nd_zeros
from ..ndarray import utils as nd_utils
from .. import initializer as init_mod

__all__ = ["Parameter", "ParameterDict", "Constant", "DeferredInitializationError"]


class DeferredInitializationError(MXNetError):
    """Raised when a parameter's data is requested before shapes are known.
    Reference: gluon/parameter.py DeferredInitializationError."""


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = None
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        if not differentiable:
            grad_req = "null"
        if stype not in ("default", "row_sparse", "csr"):
            raise MXNetError(f"invalid stype {stype}")
        self._stype = stype
        self._grad_stype = grad_stype
        self._data = None          # NDArray
        self._deferred_init = None  # (init, ctx, default_init)
        self._ctx = None
        self._shard_spec = None    # parallel.PartitionSpec-like annotation
        self.grad_req = grad_req

    # ------------------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise MXNetError(f"grad_req must be write/add/null, got {req}")
        if not self._differentiable:
            req = "null"
        self._grad_req = req
        if self._data is not None:
            self._data.attach_grad(req, stype=self._grad_stype)

    @property
    def stype(self):
        return self._stype

    @property
    def grad_stype(self):
        return self._grad_stype

    def _check_initialized(self):
        if self._data is not None:
            return
        if self._deferred_init is not None:
            raise DeferredInitializationError(
                f"Parameter '{self.name}' has not been initialized yet because "
                "initialization was deferred. Actual initialization happens "
                "during the first forward pass.")
        raise MXNetError(
            f"Parameter '{self.name}' has not been initialized. You should "
            "first call block.initialize() before using it.")

    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = current_context()
        if isinstance(ctx, (list, tuple)):
            # reference API took a ctx list for multi-GPU; one sharded array
            # covers that here — keep the first ctx
            ctx = ctx[0] if ctx else current_context()
        self._ctx = ctx
        if self.shape is None or any(s <= 0 for s in self.shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init)
                return
            raise MXNetError(
                f"Cannot initialize Parameter '{self.name}' because it has "
                f"invalid shape {self.shape} and deferred init is not allowed.")
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx, default_init):
        data = nd_zeros(self.shape, ctx=ctx, dtype=self.dtype)
        initializer = init if init is not None else \
            (self.init if self.init is not None else default_init)
        if isinstance(initializer, str):
            initializer = init_mod.create(initializer)
        initializer(init_mod.InitDesc(self.name), data)
        # the initializer leaves an uncommitted array (the result of a
        # jax.random op); every later value of the parameter is a jit
        # output committed to its device, and jit keys its cache on that
        # difference — uncommitted here means the forward, the backward
        # and the update all compile twice (47 s of a ResNet-50's second
        # step on a v5e, PR 21)
        data._set_data(jax.device_put(data.data, ctx.jax_device))
        self._data = data
        self._deferred_init = None
        if self._grad_req != "null":
            self._data.attach_grad(self._grad_req,
                                   stype=self._grad_stype)

    def _finish_deferred_init(self, in_shape=None):
        """Called by layers once the input shape is known."""
        if self._deferred_init is None:
            return
        if in_shape is not None:
            new_shape = tuple(s if s > 0 else i
                              for s, i in zip(self.shape, in_shape))
            self.shape = new_shape
        if any(s <= 0 for s in self.shape):
            raise MXNetError(
                f"deferred init of '{self.name}' still has unknown dims "
                f"{self.shape}")
        init_, ctx, default_init = self._deferred_init
        self._finish_init(init_, ctx, default_init)

    def shape_updated(self, shape):
        """Merge newly inferred dims into a partially-known shape."""
        if self.shape is None:
            self.shape = tuple(shape)
            return
        merged = []
        for s, n in zip(self.shape, shape):
            if s > 0 and n > 0 and s != n:
                raise MXNetError(
                    f"inferred shape {shape} incompatible with declared "
                    f"{self.shape} for parameter {self.name}")
            merged.append(s if s > 0 else n)
        self.shape = tuple(merged)

    # ------------------------------------------------------------------
    def data(self, ctx=None):
        self._check_initialized()
        if _USE_ORDER_RECORDERS:
            for rec in _USE_ORDER_RECORDERS:
                rec.note(self)
        override = _TRACE_BINDINGS.get(id(self))
        if override is not None:
            return override
        return self._data

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None):
        self._check_initialized()
        if self._grad_req == "null":
            raise MXNetError(
                f"Cannot get gradient array for Parameter '{self.name}' "
                "because grad_req='null'")
        return self._data.grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        if self._data is None and self._deferred_init is not None:
            return [self._deferred_init[1]]
        self._check_initialized()
        return [self._ctx or current_context()]

    def zero_grad(self):
        if self._data is not None:
            _tape.clear_grad(self._data)

    def set_data(self, data):
        if isinstance(data, NDArray):
            data = data.data
        else:
            data = jnp.asarray(data)
        if self._data is None:
            self.shape = tuple(data.shape)
            self._deferred_init = None
            self._data = NDArray(data, self._ctx or current_context())
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req,
                                       stype=self._grad_stype)
            return
        if tuple(data.shape) != self.shape:
            raise MXNetError(
                f"set_data shape {tuple(data.shape)} != param shape {self.shape}")
        self._data._set_data(data.astype(self._data.data.dtype))

    def reset_ctx(self, ctx):
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        if self._data is not None:
            self._data = self._data.as_in_context(self._ctx)
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req,
                                       stype=self._grad_stype)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            self._data = self._data.astype(dtype)
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req,
                                       stype=self._grad_stype)

    # sharding annotation for pjit paths (TPU-native extension)
    def shard(self, spec):
        self._shard_spec = spec
        return self

    @property
    def shard_spec(self):
        return self._shard_spec

    def var(self):
        from ..symbol import Symbol
        return Symbol._var(self.name)

    def __repr__(self):
        return (f"Parameter {self.name} (shape={self.shape}, "
                f"dtype={self.dtype})")


class Constant(Parameter):
    """Non-differentiable constant parameter (reference gluon.Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            from ..ndarray.ndarray import array
            value = array(value)
        self._value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value.data.dtype), differentiable=False,
                         init="zeros")

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        self._data = self._value
        self._deferred_init = None


# trace-time parameter value overrides (set by CachedOp while tracing)
_TRACE_BINDINGS = {}

# active forward use-order recorders (see record_param_use); a plain list
# so Parameter.data() pays one falsy check when none are active
_USE_ORDER_RECORDERS = []


class record_param_use:
    """Scope recording the order parameters are FIRST accessed in a
    forward — the reverse of backward gradient-ready order, which is
    what a backward-ordered ``zero.BucketPlan(fill_order=...)`` needs
    (parallel.DataParallelTrainer probes one abstract forward under
    this to plan overlap-friendly buckets)."""

    def __init__(self):
        self.order = []          # Parameter objects, first-use order
        self._seen = set()

    def note(self, param):
        if id(param) not in self._seen:
            self._seen.add(id(param))
            self.order.append(param)

    def __enter__(self):
        _USE_ORDER_RECORDERS.append(self)
        return self

    def __exit__(self, *exc):
        _USE_ORDER_RECORDERS.remove(self)
        return False


class _bind_params:
    """Context manager mapping Parameter -> tracer array during jit trace."""

    def __init__(self, mapping):
        self.mapping = mapping

    def __enter__(self):
        for p, arr in self.mapping.items():
            _TRACE_BINDINGS[id(p)] = arr
        return self

    def __exit__(self, *exc):
        for p in self.mapping:
            _TRACE_BINDINGS.pop(id(p), None)
        return False


class ParameterDict:
    """Ordered name->Parameter mapping with a shared prefix.
    Reference: gluon/parameter.py ParameterDict."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}
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

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __repr__(self):
        s = "\n".join(f"  {v}" for v in self._params.values())
        return f"ParameterDict '{self._prefix}' (\n{s}\n)"

    def get(self, name, **kwargs):
        name = self._prefix + name
        if name in self._params:
            param = self._params[name]
            # merge shape info
            if kwargs.get("shape") is not None and param.shape is not None:
                param.shape_updated(tuple(kwargs["shape"]))
            return param
        if self._shared is not None and name in self._shared:
            self._params[name] = self._shared[name]
            return self._shared[name]
        param = Parameter(name, **kwargs)
        self._params[name] = param
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        if name in self._params:
            return self._params[name]
        if value is None:
            raise MXNetError(f"No constant named '{name}'")
        const = Constant(name, value)
        self._params[name] = const
        return const

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError(f"duplicate parameter name {k}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        default = init or init_mod.Uniform()
        for param in self._params.values():
            param.initialize(None, ctx, default, force_reinit=force_reinit)

    def zero_grad(self):
        for param in self._params.values():
            param.zero_grad()

    def reset_ctx(self, ctx):
        for param in self._params.values():
            param.reset_ctx(ctx)

    def setattr(self, name, value):
        for param in self._params.values():
            setattr(param, name, value)

    def save(self, filename, strip_prefix=""):
        arg_dict = {}
        for param in self._params.values():
            block = param.data()
            name = param.name
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg_dict[name] = block
        nd_utils.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        loaded = nd_utils.load(filename)
        if restore_prefix:
            loaded = {restore_prefix + k: v for k, v in loaded.items()}
        # strip legacy arg:/aux: prefixes
        loaded = {_strip_ref_prefix(k): v for k, v in loaded.items()}
        for name, param in self._params.items():
            if name not in loaded:
                if not allow_missing:
                    raise MXNetError(
                        f"Parameter '{name}' is missing in file '{filename}'")
                continue
            param.set_data(loaded[name])
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise MXNetError(
                    f"Parameters {sorted(extra)} in file are not present in "
                    f"this ParameterDict (set ignore_extra=True to skip)")


def _strip_ref_prefix(name):
    for p in ("arg:", "aux:"):
        if name.startswith(p):
            return name[len(p):]
    return name
