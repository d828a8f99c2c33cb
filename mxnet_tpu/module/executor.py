"""Executor: evaluates a Symbol graph with autograd support.

Reference: src/executor/graph_executor.cc + python/mxnet/executor.py.
Memory planning / op bulking are absorbed by XLA (SURVEY.md §2.1 "Graph
executor" row); what remains is the bind contract: arg arrays, grad arrays,
forward(is_train)/backward().
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..context import current_context
from ..ndarray.ndarray import NDArray, zeros as nd_zeros
from .. import autograd

__all__ = ["Executor"]


class _SymSlot:
    """Marks a symbol-input position (with its inferred shape) during
    shape materialization, so literal tuple arguments survive."""

    __slots__ = ("shape",)

    def __init__(self, shape):
        self.shape = tuple(shape)


class Executor:
    def __init__(self, symbol, ctx=None, shapes=None, args=None,
                 args_grad=None, grad_req="write", label_shapes=None,
                 group2ctxs=None):
        self._symbol = symbol
        self._ctx = ctx or current_context()
        # manual model parallel (reference group2ctx in Symbol.bind):
        # {ctx_group attr -> Context}; ops in a group run on its device
        self._ctx_map = {}
        self._group_placements = None   # name -> device, built lazily
        if group2ctxs:
            g2c = group2ctxs[0] if isinstance(group2ctxs, (list, tuple)) \
                else group2ctxs
            for group, c in g2c.items():
                d = getattr(c, "jax_device", c)
                self._ctx_map[group] = d
        self.grad_req = grad_req
        arg_names = symbol.list_arguments()
        self.arg_dict = {}
        if args is not None:
            if isinstance(args, dict):
                self.arg_dict.update(args)
            else:
                for name, arr in zip(arg_names, args):
                    self.arg_dict[name] = arr
        if shapes:
            for name in arg_names:
                if name in self.arg_dict:
                    continue
                if name in shapes:
                    self.arg_dict[name] = nd_zeros(tuple(shapes[name]),
                                                   ctx=self._ctx)
        self.grad_dict = {}
        if args_grad:
            if isinstance(args_grad, dict):
                self.grad_dict.update(args_grad)
            else:
                for name, arr in zip(arg_names, args_grad):
                    self.grad_dict[name] = arr
        self.aux_dict = {}
        self.outputs = []
        self._req = grad_req if isinstance(grad_req, dict) else \
            {n: grad_req for n in arg_names}
        self._data_names = [n for n in arg_names
                            if n in ("data", "softmax_label", "label") or
                            n.endswith("_label") or n.endswith("data")]

    def _materialize_params(self):
        """Create zero arrays for auto-generated parameter variables.

        Walks the expression graph in eval order; each parameterized op's
        input shape is known by the time the op is reached (data shapes
        come from bind), so its weight shapes follow from
        _PARAM_SHAPE_RULES — the working remnant of the reference's
        InferShape pass."""
        from ..symbol.symbol import Symbol
        if getattr(self, "_materialized", False):
            return      # labels may stay unbound forever (predict path)
        missing = [n for n in self._symbol.list_arguments()
                   if n not in self.arg_dict]
        if not missing:
            self._materialized = True
            return
        import jax
        import jax.numpy as jnp
        shape_env = {n: jax.ShapeDtypeStruct(tuple(a.shape), jnp.float32)
                     for n, a in self.arg_dict.items()}
        created = {}

        def shape_of(s):
            if s._op is None and s._outputs is None:
                if s._name in shape_env:
                    return tuple(shape_env[s._name].shape)
                declared = getattr(s, "_declared_shape", None)
                if declared is not None:
                    created[s._name] = declared
                    shape_env[s._name] = jax.ShapeDtypeStruct(
                        declared, jnp.float32)
                    return declared
                raise MXNetError(
                    f"cannot infer shape for unbound variable '{s._name}' "
                    "(not produced by a parameterized op; declare "
                    "var(shape=...) or bind it explicitly)")
            if s._outputs is not None:
                return shape_of(s._outputs[0])
            return _infer_node(s)

        cache = {}

        def _infer_node(s):
            if id(s) in cache:
                return cache[id(s)]
            if s._op in _LABEL_OPS:
                # label vars are inputs, not params: default to (batch,)
                in_shape = shape_of(s._args[0])
                for a in s._args[1:]:
                    if isinstance(a, Symbol) and a._op is None and \
                            a._name not in shape_env:
                        shape_env[a._name] = jax.ShapeDtypeStruct(
                            (in_shape[0],), jnp.float32)
            rule = _PARAM_SHAPE_RULES.get(s._op)
            if rule is not None:
                in_shape = shape_of(s._args[0])
                shapes = rule(in_shape, s._kwargs)
                for a in s._args[1:]:
                    if isinstance(a, Symbol) and a._op is None and \
                            a._name not in shape_env:
                        suffix = a._name.rsplit("_", 1)[-1]
                        key = ("moving_" + a._name.rsplit("_", 2)[-1]
                               if a._name.endswith(("moving_mean",
                                                    "moving_var"))
                               else suffix)
                        pshape = shapes.get(key) or shapes.get(suffix)
                        if pshape is None:
                            raise MXNetError(
                                f"no shape rule for param '{a._name}' "
                                f"of op {s._op}")
                        shape_env[a._name] = jax.ShapeDtypeStruct(
                            tuple(pshape), jnp.float32)
                        created[a._name] = tuple(pshape)
            # output shape via jax.eval_shape on the single op
            from ..symbol.symbol import _apply_nd_op
            from .. import _tape

            arg_protos = []
            for a in s._args:
                if isinstance(a, Symbol):
                    # marker class, NOT a raw tuple: literal tuple args
                    # (e.g. reshape's positional shape) must pass through
                    # untouched instead of being mistaken for array slots
                    arg_protos.append(_SymSlot(shape_of(a)))
                else:
                    arg_protos.append(a)

            def run(*arrs):
                it = iter(arrs)
                vals = [NDArray(next(it)) if isinstance(p, _SymSlot) else p
                        for p in arg_protos]
                out = _apply_nd_op(s._op, vals, s._kwargs)
                outs = out if isinstance(out, list) else [out]
                return tuple(o.data for o in outs)

            protos = [jax.ShapeDtypeStruct(p.shape, jnp.float32)
                      for p in arg_protos if isinstance(p, _SymSlot)]
            with _tape.trace_scope():
                out_shapes = jax.eval_shape(run, *protos)
            shape = tuple(out_shapes[s._out_index or 0].shape)
            cache[id(s)] = shape
            return shape

        shape_of(self._symbol)
        for name in missing:
            if name in created:
                self.arg_dict[name] = nd_zeros(created[name], ctx=self._ctx)
                if name.rsplit("_", 1)[-1] in ("mean", "var"):
                    self._req[name] = "null"
            elif _is_input_name(name):
                pass    # labels may stay unbound (predict path)
            else:
                raise MXNetError(f"argument '{name}' was never bound and "
                                 "could not be materialized")
        self._materialized = True

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._symbol.list_arguments()]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n)
                for n in self._symbol.list_arguments()]

    @property
    def aux_arrays(self):
        return []

    def _place_group_params(self):
        """Pin each ctx_group's PARAMETERS on its mapped device once (the
        reference binds weights to group2ctx devices at bind time) — so
        only activations hop across stages in _eval, not whole weight
        stacks every step."""
        if not self._ctx_map:
            return
        import jax
        if self._group_placements is None:
            # the graph and ctx_map are fixed after bind: walk ONCE and
            # cache (param name -> device); per-forward cost is then just
            # an identity check per grouped param
            from ..symbol.symbol import Symbol, _collect_nodes
            heads = self._symbol._outputs or [self._symbol]
            nodes = [n for h in heads for n in _collect_nodes(h)]
            placements = {}
            for node in nodes:
                group = node._attrs.get("ctx_group") if node._attrs \
                    else None
                dev = self._ctx_map.get(group)
                if dev is None:
                    continue
                for a in node._args:
                    if isinstance(a, Symbol) and a._op is None and \
                            not _is_input_name(a._name):
                        placements[a._name] = dev
            self._group_placements = placements
        for name, dev in self._group_placements.items():
            arr = self.arg_dict.get(name)
            if arr is not None and arr._data is not None and \
                    arr.data.devices() != {dev}:
                arr._set_data(jax.device_put(arr.data, dev))

    def forward(self, is_train=False, **kwargs):
        for name, value in kwargs.items():
            if name not in self.arg_dict:
                self.arg_dict[name] = value
            else:
                self.arg_dict[name]._set_data(
                    value.data if isinstance(value, NDArray) else value)
        self._materialize_params()
        self._place_group_params()
        bindings = dict(self.arg_dict)
        # unbound labels evaluate as None: output heads then run
        # forward-only (softmax / identity), matching reference predict
        for n in self._symbol.list_arguments():
            if n not in bindings and _is_input_name(n):
                bindings[n] = None
        if is_train:
            for name, arr in self.arg_dict.items():
                req = self._req.get(name, "write")
                if req != "null" and not _is_input_name(name):
                    arr.attach_grad(req)
            with autograd.record():
                out = self._symbol._eval(bindings,
                                         ctx_map=self._ctx_map or None)
        else:
            out = self._symbol._eval(bindings,
                                     ctx_map=self._ctx_map or None)
        self.outputs = out if isinstance(out, list) else [out]
        self._train_outputs = self.outputs if is_train else None
        return self.outputs

    def backward(self, out_grads=None):
        if self._train_outputs is None:
            raise MXNetError("call forward(is_train=True) before backward")
        heads = self._train_outputs
        autograd.backward(heads, out_grads)
        for name, arr in self.arg_dict.items():
            if self._req.get(name, "write") != "null" and \
                    not _is_input_name(name):
                self.grad_dict[name] = arr.grad

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_data(arr.data)
            elif not allow_extra_params:
                raise MXNetError(f"unknown param {name}")


def _fc_rules(in_shape, kw):
    num_hidden = int(kw["num_hidden"])
    flatten = kw.get("flatten", True)
    in_units = 1
    if flatten:
        for s in in_shape[1:]:
            in_units *= int(s)
    else:
        in_units = int(in_shape[-1])
    return {"weight": (num_hidden, in_units), "bias": (num_hidden,)}


def _conv_rules(in_shape, kw):
    nf = int(kw["num_filter"])
    kernel = tuple(kw["kernel"])
    groups = int(kw.get("num_group", 1))
    return {"weight": (nf, int(in_shape[1]) // groups) + kernel,
            "bias": (nf,)}


def _deconv_rules(in_shape, kw):
    # deconv weight layout is (C_in, num_filter//groups, *k) — see
    # gluon/nn/conv_layers.py and nd.Deconvolution(transpose_kernel)
    nf = int(kw["num_filter"])
    kernel = tuple(kw["kernel"])
    groups = int(kw.get("num_group", 1))
    return {"weight": (int(in_shape[1]), nf // groups) + kernel,
            "bias": (nf,)}


def _chan_rules(in_shape, kw):
    c = int(in_shape[1])
    return {"gamma": (c,), "beta": (c,), "moving_mean": (c,),
            "moving_var": (c,)}


def _lastdim_rules(in_shape, kw):
    c = int(in_shape[-1])
    return {"gamma": (c,), "beta": (c,)}


def _embed_rules(in_shape, kw):
    return {"weight": (int(kw["input_dim"]), int(kw["output_dim"]))}


# The reference's InferShape pass (SURVEY.md §2.1 Symbol/nnvm row) reduced
# to what bind actually needs: shapes for auto-created parameter variables,
# derived from the (already materialized) first-input shape of each
# parameterized op during a forward walk of the expression graph.
_PARAM_SHAPE_RULES = {
    "FullyConnected": _fc_rules,
    "Convolution": _conv_rules,
    "Deconvolution": _deconv_rules,
    "BatchNorm": _chan_rules,
    "LayerNorm": _lastdim_rules,
    "InstanceNorm": _lastdim_rules,
    "Embedding": _embed_rules,
}

_NO_GRAD_PARAMS = {"moving_mean", "moving_var"}    # aux states

_LABEL_OPS = ("SoftmaxOutput", "LinearRegressionOutput",
              "MAERegressionOutput", "LogisticRegressionOutput")


def _is_input_name(name):
    return name in ("data", "label", "softmax_label") or \
        name.endswith("_label") or name.endswith("_data") or name == "data"
