"""Gluon ``Block`` / ``HybridBlock`` / ``SymbolBlock`` and the TPU CachedOp.

Reference: python/mxnet/gluon/block.py + src/imperative/cached_op.cc
(SURVEY.md §2.1 "CachedOp" — "the crown jewel mapping").

The mapping implemented here:

  reference                         TPU rebuild
  ---------                         -----------
  hybridize()                       mark block active; build CachedOp
  CachedOp trace (nnvm graph)       jax.jit trace of the block's forward
  static_alloc/static_shape         XLA static shapes + buffer reuse (free)
  shape-keyed graph cache           jax.jit's shape/dtype-keyed cache
  op bulking                        XLA fusion
  export() -> symbol.json+params    jax.export (StableHLO) + params file
  SymbolBlock.imports               deserialize StableHLO, wrap as Block

Training state, PRNG, and BatchNorm aux-state (running mean/var) are threaded
through the traced function explicitly:
  - train/predict mode is a *static* switch: one jitted function per mode
  - a PRNG key is passed per call; Dropout etc. derive sub-keys by fold_in
  - aux updates are collected during trace and returned as extra outputs,
    then written back into the Parameters after each call
    (SURVEY.md §7 hard parts: "BatchNorm aux-state update inside jit")
"""
from __future__ import annotations

import json
import os
import re
import threading

import numpy as _np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..context import current_context
from ..lint.retrace import RetraceMonitor
from ..ndarray.ndarray import NDArray
from ..ndarray import utils as nd_utils
from .. import _tape
from ..ndarray import random as _rnd
from ..telemetry import tracing as _trace
from .parameter import (Parameter, ParameterDict, Constant,
                        DeferredInitializationError, _bind_params)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nn_block_scope"]


# ----------------------------------------------------------------------
# the forward's span: one per outermost call of the Gluon loop
# ----------------------------------------------------------------------

class _ForwardDepth(threading.local):
    depth = 0       # >0 while this thread is inside some block's __call__


_FORWARD = _ForwardDepth()


def _traced_call(block, call, args, kwargs):
    """``call(*args, **kwargs)`` under a ``gluon.forward`` root span when
    this is the outermost block call of the thread: children add nothing,
    nor does a call that an enclosing jit trace inlines (a span per block
    or per operator would be the overhead it measures)."""
    if _FORWARD.depth or _tape._STATE.trace_depth or not _trace.enabled():
        return call(*args, **kwargs)
    _FORWARD.depth = 1
    try:
        with _trace.span("gluon.forward", block=block.name,
                         hybridized=bool(getattr(block, "_active", False)),
                         retrace=False):
            return call(*args, **kwargs)
    finally:
        _FORWARD.depth = 0


# ----------------------------------------------------------------------
# naming scope (reference: gluon/block.py _BlockScope)
# ----------------------------------------------------------------------

class _BlockScope:
    _local = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._local, "current", None)
        if current is None:
            if prefix is None:
                prefix = _global_count(hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = f"{hint}{count}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._local, "current", None)
        _BlockScope._local.current = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return False
        _BlockScope._local.current = self._old_scope
        return False


_GLOBAL_COUNTERS = {}


def _global_count(hint):
    count = _GLOBAL_COUNTERS.get(hint, 0)
    _GLOBAL_COUNTERS[hint] = count + 1
    return f"{hint}{count}"


def nn_block_scope(block):
    return _BlockScope(block)


# ----------------------------------------------------------------------
# aux-update collector (BatchNorm running stats inside jit)
# ----------------------------------------------------------------------

class _AuxCollector(threading.local):
    def __init__(self):
        self.stack = []


_AUX = _AuxCollector()


class _aux_scope:
    def __enter__(self):
        _AUX.stack.append([])
        return _AUX.stack[-1]

    def __exit__(self, *exc):
        _AUX.stack.pop()
        return False


def record_aux_update(param, new_value):
    """Called by layers holding auxiliary (non-grad) state, e.g. BatchNorm.

    Inside a CachedOp trace the update is collected and threaded out of the
    jitted function; in eager mode it is applied immediately.
    """
    if _AUX.stack:
        _AUX.stack[-1].append((param, new_value))
    else:
        param._data._set_data(new_value.data if isinstance(new_value, NDArray)
                              else new_value)


# ----------------------------------------------------------------------
# Block
# ----------------------------------------------------------------------

class Block:
    """Base class for all neural network layers and models.
    Reference: gluon/block.py Block."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    # -- attribute magic ------------------------------------------------
    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)) and \
                    not isinstance(existing, type(value)):
                raise MXNetError(
                    f"Changing attribute type for {name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self._children[name] = value
        elif isinstance(value, Parameter):
            self._reg_params[name] = value
        super().__setattr__(name, value)

    # -- public surface -------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self):
        return self._params

    def name_scope(self):
        return self._scope

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + name: p for name, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def save_parameters(self, filename, deduplicate=False):
        """Reference: Block.save_parameters — structural dotted names."""
        params = self._collect_params_with_prefix()
        arg_dict = {}
        for name, param in params.items():
            if param._data is None:
                continue
            arg_dict[name] = param.data()
        nd_utils.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = nd_utils.load(filename)
        params = self._collect_params_with_prefix()
        # also accept prefix-style names saved by ParameterDict.save
        by_full_name = {p.name: p for p in params.values()}
        for name, value in loaded.items():
            key = name[4:] if name.startswith(("arg:", "aux:")) else name
            if key in params:
                params[key].set_data(value)
            elif key in by_full_name:
                by_full_name[key].set_data(value)
            elif not ignore_extra:
                raise MXNetError(
                    f"Parameter '{key}' loaded from file '{filename}' is not "
                    "present in this Block. Set ignore_extra=True to skip.")
        if not allow_missing:
            missing = [n for n, p in params.items()
                       if p._data is None and p._deferred_init is None
                       and n not in loaded and p.name not in loaded]
            if missing:
                raise MXNetError(
                    f"Parameters {missing} not found in file '{filename}'")

    # legacy aliases (reference deprecated names)
    save_params = save_parameters
    load_params = load_parameters

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for param in self._reg_params.values():
            param.cast(dtype)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        out = self(*inputs)
        n_params = builtins_sum(int(_np.prod(p.shape))
                                for p in self.collect_params().values()
                                if p.shape)
        print(f"{type(self).__name__}: {n_params} parameters, "
              f"output shape {out.shape if isinstance(out, NDArray) else '-'}")
        return out

    def __call__(self, *args, **kwargs):
        return _traced_call(self, self._call, args, kwargs)

    def _call(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __repr__(self):
        s = f"{type(self).__name__}("
        for name, child in self._children.items():
            s += f"\n  ({name}): {child!r}"
        return s + ("\n)" if self._children else ")")


def builtins_sum(it):
    total = 0
    for x in it:
        total += x
    return total


def _abstract_trace(args):
    """True when the enclosing trace is a real (abstract) jit trace — the
    PRNG trace key installed by the tracing scope is itself a Tracer, or a
    tensor argument is. Eager passes under trace_scope (deferred-shape
    resolution) carry concrete keys/arrays and must NOT re-route through
    nested jit/checkpoint (their placement constraints fight commitments)."""
    stack = _rnd._STATE.trace_stack
    if stack and isinstance(stack[-1][0], jax.core.Tracer):
        return True
    return any(isinstance(getattr(a, "data", None), jax.core.Tracer)
               for a in args if a is not None)


# ----------------------------------------------------------------------
# CachedOp — the hybridize() engine
# ----------------------------------------------------------------------

class CachedOp:
    """Shape-cached jitted executor for a HybridBlock subtree.
    Reference: src/imperative/cached_op.{h,cc} (CachedOp::Forward)."""

    def __init__(self, block, static_alloc=False, static_shape=False,
                 inline_limit=2, remat=False):
        self.block = block
        self.static_alloc = static_alloc
        self.static_shape = static_shape
        self.remat = remat
        self._jitted = {}       # train_mode -> jitted fn
        self._param_objs = None  # ordered params
        self._out_tree = {}      # train_mode -> (n_out, structure)
        self._aux_params = {}    # train_mode -> [Parameter]
        self._in_avals = None    # last input signature (for export)
        self._none_pos = ()      # positions of None args (reinserted)
        self._raw = {}           # train_mode -> un-jitted pure fn
        # retrace observability (mx.lint runtime complement): every
        # distinct input signature is a jax.jit cache miss; the monitor
        # warns once past MXTPU_RETRACE_WARN distinct signatures
        self._retrace = RetraceMonitor(block.name or type(block).__name__)

    def _collect(self):
        if self._param_objs is None:
            items = sorted(self.block.collect_params().items())
            self._param_objs = [p for _, p in items]
        return self._param_objs

    def _make_pure(self, train):
        block = self.block
        cached = self

        def _pure(key, param_arrays, input_arrays):
            prev_train = _tape.set_training(train)
            params = cached._param_objs
            binding = {p: NDArray(a) for p, a in zip(params, param_arrays)}
            try:
                with _tape.trace_scope(), _bind_params(binding), \
                        _rnd.trace_key_scope(key), _aux_scope() as aux:
                    ins = [NDArray(a) for a in input_arrays]
                    for i in cached._none_pos:   # optional args elided
                        ins.insert(i, None)
                    out = block.forward(*ins)
            finally:
                _tape.set_training(prev_train)
            flat, tree = _flatten_output(out)
            cached._out_tree[train] = (len(flat), tree)
            cached._aux_params[train] = [p for p, _ in aux]
            outs = tuple(o.data for o in flat) + \
                tuple(v.data if isinstance(v, NDArray) else v for _, v in aux)
            return outs
        return _pure

    def _get_jitted(self, train, raw=False):
        """raw=True returns the (possibly checkpointed) pure fn WITHOUT the
        jax.jit wrapper — used when this block executes inside an enclosing
        trace: a nested jit would pin concrete captured args (PRNG key) to
        one device and fight mesh sharding constraints, while the raw fn
        inlines cleanly with the remat boundary intact."""
        store = self._raw if raw else self._jitted
        if train not in store:
            fn = self._make_pure(train)
            if self.remat:
                # jax.checkpoint: discard this block's activations in the
                # enclosing differentiated program and recompute them in
                # its backward — HBM for FLOPs. Survives inlining into an
                # outer jit (e.g. the fused DataParallelTrainer step), so
                # hybridize(remat=True) per encoder layer gives the classic
                # per-layer rematerialization schedule.
                fn = jax.checkpoint(fn)
            store[train] = fn if raw else jax.jit(fn)
        return store[train]

    def __call__(self, *args):
        # None args (optional masks etc.) fall back to the forward()
        # defaults — jit signatures carry arrays only; _make_pure reinserts
        # them by position
        none_pos = tuple(i for i, a in enumerate(args) if a is None)
        if none_pos != self._none_pos:
            self._none_pos = none_pos
            self._jitted = {}
            self._raw = {}
            self._out_tree = {}
        args = tuple(a for a in args if a is not None)
        params = self._collect()
        # Sparse-grad params can't ride jax.vjp of the fused program (its
        # cotangents are dense O(vocab)): dispatch the block imperatively
        # while grads are being recorded, so the Embedding op's row-sparse
        # pullback stays live. Mirrors the reference, where CachedOp defers
        # to FComputeEx imperative dispatch for sparse storage
        # (src/imperative/cached_op.cc storage-type fallback).
        if _tape.is_recording() and \
                any(p.grad_stype == "row_sparse" for p in params):
            if not getattr(self, "_warned_sparse_fallback", False):
                self._warned_sparse_fallback = True
                import warnings
                warnings.warn(
                    f"{self.block.name}: hybridized block has "
                    "row_sparse-grad parameters; training forward runs "
                    "imperatively to keep O(nnz) gradients (reference "
                    "sparse FComputeEx fallback)")
            return self.block.forward(*args)
        # deferred shapes: run one eager pause()-mode forward to resolve
        if any(p._data is None for p in params):
            with _tape.trace_scope():
                prev = _tape.set_training(_tape.is_training())
                try:
                    self.block.forward(*args)
                finally:
                    _tape.set_training(prev)
            self._param_objs = None
            params = self._collect()
        train = _tape.is_training()
        raw = _tape._STATE.trace_depth > 0
        if not raw:
            # one distinct (mode, shapes, dtypes) signature == one jit
            # cache miss == one full retrace + XLA compile; the raw path
            # inlines into an enclosing trace and has no cache of its own
            if self._retrace.record(
                    (train, self._none_pos,
                     tuple((tuple(a.data.shape), str(a.data.dtype))
                           for a in args))):
                # a new signature: this call compiles, and the ambient
                # gluon.forward span says so
                sp = _trace.current()
                if sp is not None and sp.name == "gluon.forward":
                    sp.args["retrace"] = True
        jfn = self._get_jitted(train, raw=raw)
        key = _rnd.next_key()
        n_params = len(params)
        inputs = [p.data() for p in params] + list(args)
        self._in_avals = [jax.ShapeDtypeStruct(a.data.shape, a.data.dtype)
                          for a in args]

        if train not in self._out_tree:
            # trace abstractly once to learn output structure
            _ = jax.eval_shape(
                lambda *arrs: jfn(key, arrs[:n_params], arrs[n_params:]),
                *[x.data for x in inputs])
        n_out, tree = self._out_tree[train]
        aux_params = self._aux_params[train]
        total_out = n_out + len(aux_params)

        def fn(*arrs):
            outs = jfn(key, arrs[:n_params], arrs[n_params:])
            return outs[0] if total_out == 1 else outs

        outs, node = _tape.apply_op(fn, inputs, n_out=total_out,
                                    name=f"CachedOp({self.block.name})")
        ctx = args[0]._ctx if args else current_context()
        results = []
        for i in range(n_out):
            o = NDArray(outs[i], ctx)
            if node is not None:
                o._node = node
                o._out_index = i
            results.append(o)
        # write aux state back (running stats)
        for p, new_val in zip(aux_params, outs[n_out:]):
            p._data._set_data(new_val)
        return _unflatten_output(results, tree)


def _flatten_output(out):
    if isinstance(out, NDArray):
        return [out], "single"
    if isinstance(out, (list, tuple)):
        flat = []
        tree = []
        for o in out:
            f, t = _flatten_output(o)
            flat.extend(f)
            tree.append((t, len(f)))
        return flat, ("seq", type(out).__name__, tree)
    raise MXNetError(f"unsupported forward output type {type(out)}")


def _unflatten_output(flat, tree):
    if tree == "single":
        return flat[0]
    _, typename, subtrees = tree
    out = []
    i = 0
    for sub, n in subtrees:
        out.append(_unflatten_output(flat[i:i + n], sub))
        i += n
    return tuple(out) if typename == "tuple" else out


# ----------------------------------------------------------------------
# HybridBlock
# ----------------------------------------------------------------------

class HybridBlock(Block):
    """A Block that can be traced to XLA via hybridize().
    Reference: gluon/block.py HybridBlock (hybridize / export / infer_shape).
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, remat=None, **kwargs):
        # MXTPU_EAGER=1: serialize-everything debug switch — the reference's
        # MXNET_ENGINE_TYPE=NaiveEngine equivalent (SURVEY §2.1 row 1):
        # hybridize becomes a no-op so every op dispatches eagerly
        if active and os.environ.get("MXTPU_EAGER", "") == "1":
            active = False
        self._active = active
        if remat is None:   # unspecified: keep a previously-set schedule
            # (ancestor hybridize() recursion must not wipe per-layer remat)
            remat = self._flags.get("remat", False)
        self._flags = {"static_alloc": static_alloc,
                       "static_shape": static_shape,
                       "inline_limit": inline_limit,
                       "remat": remat}
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def cast(self, dtype):
        self._cached_op = None
        super().cast(dtype)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from input shapes. Layers
        override `_infer_shape_impl`; composite blocks resolve by running a
        shape-only forward."""
        self._infer_shape_impl(*args)

    def _infer_shape_impl(self, *args):
        raise DeferredInitializationError(
            f"{type(self).__name__} cannot infer parameter shapes "
            "automatically; run a forward pass first or set in_units/"
            "in_channels explicitly.")

    def _call(self, *args, **kwargs):
        # inside an enclosing trace (outer CachedOp / fused trainer step)
        # blocks normally inline as plain ops — EXCEPT remat blocks, which
        # must still route through their jax.checkpoint-wrapped CachedOp so
        # the rematerialization boundary survives into the outer program.
        # Only when the enclosing trace is abstract (real jit tracing):
        # eager passes under trace_scope (deferred-shape resolution) carry
        # concrete arrays, where the boundary is meaningless and nested
        # placement constraints (ring attention) would fight commitments.
        in_trace = _tape._STATE.trace_depth > 0
        remat_route = self._flags.get("remat") and _abstract_trace(args)
        if self._active and not kwargs and (not in_trace or remat_route):
            if self._cached_op is None:
                self._cached_op = CachedOp(self, **{
                    k: v for k, v in self._flags.items()
                    if k in ("static_alloc", "static_shape", "inline_limit",
                             "remat")})
            return self._cached_op(*args)
        return super()._call(*args, **kwargs)

    def forward(self, *args, **kwargs):
        """Gather this block's own params and call hybrid_forward.
        Children are invoked inside hybrid_forward as attributes."""
        from .. import ndarray as F
        try:
            params = {name: p.data() for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_init_params(*args)
            params = {name: p.data() for name, p in self._reg_params.items()}
        # per-block profiler annotation (SURVEY §5.1): inside a jit trace
        # this names the HLO region, so mx.profiler / TensorBoard traces
        # group ops by the Gluon block that produced them
        with jax.named_scope(self.name or type(self).__name__):
            return self.hybrid_forward(F, *args, **params, **kwargs)

    def _deferred_init_params(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._data is None:
                p._finish_deferred_init()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- export / import -----------------------------------------------
    def export(self, path, epoch=0):
        """Serialize the traced computation (StableHLO via jax.export) plus
        parameters. Writes, like the reference (Block.export):
          path-symbol.json   (metadata: param order, input avals, out tree)
          path-symbol.mlir   (the real artifact: serialized StableHLO)
          path-%04d.params   (arg:/aux:-prefixed parameter file)
        Requires at least one forward pass (to know input signatures) —
        same constraint as the reference. ``SymbolBlock.imports`` reloads
        and runs the artifact with NO Python model class."""
        cached = self._cached_op
        if cached is None or cached._in_avals is None:
            raise MXNetError(
                "Please first call block.hybridize() and then run forward "
                "with this block at least once before calling export.")
        from jax import export as jax_export
        params = cached._collect()
        arg_dict = {}
        for p in params:
            arg_dict[("aux:" if p.grad_req == "null" else "arg:") + p.name] = \
                p.data()
        nd_utils.save(f"{path}-{epoch:04d}.params", arg_dict)

        # Trace an inference-mode pure function over (params..., inputs...)
        # and serialize it. The PRNG key is baked in as a constant — dropout
        # etc. are identity in eval mode anyway.
        key = jax.random.PRNGKey(0)
        pure = cached._make_pure(False)
        n_params = len(params)

        def infer_fn(*arrs):
            outs = pure(key, arrs[:n_params], arrs[n_params:])
            n_out, _ = cached._out_tree[False]
            return outs[:n_out]

        in_avals = (
            [jax.ShapeDtypeStruct(p.shape, p.data().data.dtype)
             for p in params] + list(cached._in_avals))
        exp = jax_export.export(jax.jit(infer_fn))(*in_avals)
        with open(f"{path}-symbol.mlir", "wb") as f:
            f.write(exp.serialize())

        n_out, tree = cached._out_tree[False]
        meta = {
            "format": "mxnet_tpu-stablehlo-v1",
            "name": self.name,
            "params": [("aux:" if p.grad_req == "null" else "arg:") + p.name
                       for p in params],
            "inputs": [{"shape": list(a.shape), "dtype": str(a.dtype)}
                       for a in cached._in_avals],
            "n_out": n_out,
            "out_tree": tree,
            "nodes": [],  # symbol.json stub for tools that parse it
        }
        with open(f"{path}-symbol.json", "w") as f:
            json.dump(meta, f, indent=2)
        return f"{path}-symbol.json"


class SymbolBlock(Block):
    """Run a previously exported computation as a Block.
    Reference: gluon/block.py SymbolBlock.imports(json, input_names, params).

    The portable artifact is the serialized-StableHLO ``-symbol.mlir`` next
    to the ``-symbol.json``: ``imports`` deserializes it (jax.export) and
    runs it with NO Python model class. A ``builder`` callable is an
    optional alternative that rebuilds the network from code (useful when
    further training is needed — the mlir path is inference-only)."""

    def __init__(self, outputs=None, inputs=None, params=None):
        super().__init__(prefix="", params=None)
        self._fn = outputs if callable(outputs) else None
        self._arg_params = params or {}
        self._exported = None
        self._param_arrays = None
        self._out_tree = None

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None,
                builder=None):
        with open(symbol_file) as f:
            meta = json.load(f)
        if builder is not None:
            net = builder()
            if param_file:
                net.load_parameters(param_file, ctx=ctx)
            return net
        mlir_file = str(symbol_file).replace("-symbol.json", "-symbol.mlir")
        if not os.path.exists(mlir_file):
            raise MXNetError(
                f"no serialized program next to {symbol_file} (expected "
                f"{mlir_file}); re-export with this version or pass "
                "`builder` (a zero-arg callable returning the network)")
        from jax import export as jax_export
        with open(mlir_file, "rb") as f:
            exported = jax_export.deserialize(f.read())
        blk = SymbolBlock()
        blk._exported = exported
        blk._out_tree = meta.get("out_tree", "single")
        param_names = meta.get("params", [])
        if param_names:
            if not param_file:
                raise MXNetError(
                    "exported program has parameters; pass param_file")
            loaded = nd_utils.load(param_file)
            try:
                blk._param_arrays = [loaded[n].data for n in param_names]
            except KeyError as e:
                raise MXNetError(
                    f"param file {param_file} is missing key {e} required "
                    f"by {symbol_file}")
        else:
            blk._param_arrays = []
        return blk

    def forward(self, *args):
        if self._exported is not None:
            arrs = [a.data if isinstance(a, NDArray) else jnp.asarray(a)
                    for a in args]
            ctx = args[0]._ctx if args and isinstance(args[0], NDArray) \
                else current_context()
            outs = self._exported.call(*self._param_arrays, *arrs)
            if not isinstance(outs, (list, tuple)):
                outs = (outs,)
            results = [NDArray(o, ctx) for o in outs]
            return _unflatten_output(results, _json_tree(self._out_tree))
        if self._fn is None:
            raise MXNetError("SymbolBlock has no callable attached")
        return self._fn(*args)


def _json_tree(tree):
    """Out-tree structure round-tripped through JSON (lists for tuples)."""
    if tree == "single":
        return "single"
    tag, typename, subtrees = tree
    return (tag, typename, [(_json_tree(s), n) for s, n in subtrees])
