"""The operator library: MXNet op names & semantics over jax.numpy / lax.

TPU-native rebuild of the reference's NNVM-registered op library
(SURVEY.md §2.1 "Operator library (dense)", reference dirs:
``src/operator/tensor/``, ``src/operator/nn/``, ``src/operator/random/``,
``src/operator/control_flow.cc``). ~150k LoC of C++/CUDA kernels collapse to
jax.numpy/lax calls that XLA fuses and tiles onto the MXU/VPU; everything
routes through ``apply_nary`` so the imperative autograd tape sees each op.

Op hyper-parameters (dmlc Parameter structs in the reference) become plain
keyword arguments closed over before dispatch, keeping the dispatched function
pure over its array inputs (required for jax.vjp / jit).
"""
from __future__ import annotations

import builtins as _builtins
import math
import os as _os

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .ndarray import NDArray, apply_nary, _dtype_of, _ax, array, zeros, ones, \
    full, arange

__all__ = []  # populated at bottom


def _nd(x, like=None):
    if isinstance(x, NDArray):
        return x
    return array(x, ctx=like._ctx if like is not None else None)


def _register(fn):
    __all__.append(fn.__name__)
    return fn


# ======================================================================
# elementwise unary (reference: src/operator/tensor/elemwise_unary_op*.cc)
# ======================================================================

def _unary_factory(name, jfn):
    def op(data, **kwargs):
        return apply_nary(jfn, [data], name=name)
    op.__name__ = name
    op.__doc__ = f"Elementwise {name}. Reference: src/operator/tensor/elemwise_unary_op_basic.cc ({name})."
    return _register(op)


relu = _unary_factory("relu", jax.nn.relu)
sigmoid = _unary_factory("sigmoid", jax.nn.sigmoid)
softsign = _unary_factory("softsign", jax.nn.soft_sign)
tanh = _unary_factory("tanh", jnp.tanh)
degrees = _unary_factory("degrees", jnp.degrees)
radians = _unary_factory("radians", jnp.radians)
exp = _unary_factory("exp", jnp.exp)
log = _unary_factory("log", jnp.log)
log2 = _unary_factory("log2", jnp.log2)
log10 = _unary_factory("log10", jnp.log10)
log1p = _unary_factory("log1p", jnp.log1p)
expm1 = _unary_factory("expm1", jnp.expm1)
sqrt = _unary_factory("sqrt", jnp.sqrt)
rsqrt = _unary_factory("rsqrt", lax.rsqrt)
cbrt = _unary_factory("cbrt", jnp.cbrt)
square = _unary_factory("square", jnp.square)
abs = _unary_factory("abs", jnp.abs)
sign = _unary_factory("sign", jnp.sign)
round = _unary_factory("round", jnp.round)
rint = _unary_factory("rint", jnp.rint)
ceil = _unary_factory("ceil", jnp.ceil)
floor = _unary_factory("floor", jnp.floor)
trunc = _unary_factory("trunc", jnp.trunc)
fix = _unary_factory("fix", jnp.trunc)
negative = _unary_factory("negative", jnp.negative)
reciprocal = _unary_factory("reciprocal", jnp.reciprocal)
sin = _unary_factory("sin", jnp.sin)
cos = _unary_factory("cos", jnp.cos)
tan = _unary_factory("tan", jnp.tan)
arcsin = _unary_factory("arcsin", jnp.arcsin)
arccos = _unary_factory("arccos", jnp.arccos)
arctan = _unary_factory("arctan", jnp.arctan)
sinh = _unary_factory("sinh", jnp.sinh)
cosh = _unary_factory("cosh", jnp.cosh)
arcsinh = _unary_factory("arcsinh", jnp.arcsinh)
arccosh = _unary_factory("arccosh", jnp.arccosh)
arctanh = _unary_factory("arctanh", jnp.arctanh)
erf = _unary_factory("erf", jax.scipy.special.erf)
erfinv = _unary_factory("erfinv", jax.scipy.special.erfinv)
digamma = _unary_factory("digamma", jax.scipy.special.digamma)


@_register
def hard_sigmoid(data, alpha=0.2, beta=0.5):
    """y = clip(alpha*x + beta, 0, 1). Reference: src/operator/tensor/elemwise_unary_op_basic.cc (hard_sigmoid)."""
    return apply_nary(lambda d: jnp.clip(alpha * d + beta, 0.0, 1.0),
                      [data], name="hard_sigmoid")
gamma = _unary_factory("gamma", lambda d: jnp.exp(jax.scipy.special.gammaln(d)))
gammaln = _unary_factory("gammaln", jax.scipy.special.gammaln)
logical_not = _unary_factory("logical_not",
                             lambda d: (d == 0).astype(jnp.float32))
zeros_like = _unary_factory("zeros_like", jnp.zeros_like)
ones_like = _unary_factory("ones_like", jnp.ones_like)


@_register
def identity(data):
    return apply_nary(lambda d: d, [data], name="identity")


@_register
def cast(data, dtype):
    dt = _dtype_of(dtype)
    return apply_nary(lambda d: d.astype(dt), [data], name="cast")


Cast = cast


@_register
def clip(data, a_min, a_max):
    return apply_nary(lambda d: jnp.clip(d, a_min, a_max), [data], name="clip")


# ======================================================================
# elementwise binary + broadcast (reference: elemwise_binary_broadcast_op*)
# ======================================================================

def _binary_factory(name, jfn):
    def op(lhs, rhs, **kwargs):
        lhs = _nd(lhs, rhs if isinstance(rhs, NDArray) else None)
        if isinstance(rhs, NDArray):
            return apply_nary(jfn, [lhs, rhs], name=name)
        return apply_nary(lambda a: jfn(a, rhs), [lhs], name=name)
    op.__name__ = name
    op.__doc__ = f"Broadcasting binary {name}. Reference: src/operator/tensor/elemwise_binary_broadcast_op_basic.cc."
    return _register(op)


add = _binary_factory("add", jnp.add)
subtract = _binary_factory("subtract", jnp.subtract)
multiply = _binary_factory("multiply", jnp.multiply)
divide = _binary_factory("divide", jnp.divide)
# reference elemwise_binary_op_basic.cc mod is C fmod semantics: the result
# takes the sign of the dividend (unlike numpy/Python mod).
modulo = _binary_factory("modulo", jnp.fmod)
power = _binary_factory("power", jnp.power)
maximum = _binary_factory("maximum", jnp.maximum)
minimum = _binary_factory("minimum", jnp.minimum)
hypot = _binary_factory("hypot", jnp.hypot)
arctan2 = _binary_factory("arctan2", jnp.arctan2)
equal = _binary_factory("equal", lambda a, b: (a == b).astype(jnp.float32))
not_equal = _binary_factory("not_equal",
                            lambda a, b: (a != b).astype(jnp.float32))
greater = _binary_factory("greater", lambda a, b: (a > b).astype(jnp.float32))
greater_equal = _binary_factory("greater_equal",
                                lambda a, b: (a >= b).astype(jnp.float32))
lesser = _binary_factory("lesser", lambda a, b: (a < b).astype(jnp.float32))
lesser_equal = _binary_factory("lesser_equal",
                               lambda a, b: (a <= b).astype(jnp.float32))
logical_and = _binary_factory(
    "logical_and", lambda a, b: ((a != 0) & (b != 0)).astype(jnp.float32))
logical_or = _binary_factory(
    "logical_or", lambda a, b: ((a != 0) | (b != 0)).astype(jnp.float32))
logical_xor = _binary_factory(
    "logical_xor", lambda a, b: ((a != 0) ^ (b != 0)).astype(jnp.float32))

# broadcast_* aliases: in mx.nd elemwise add/sub/... were strict-shape and the
# broadcast_ variants broadcast; jax broadcasts everywhere, so both names map
# to the broadcasting kernel.
for _n in ("add", "sub", "mul", "div", "mod", "power", "maximum", "minimum",
           "hypot", "equal", "not_equal", "greater", "greater_equal",
           "lesser", "lesser_equal", "logical_and", "logical_or",
           "logical_xor"):
    _base = {"sub": subtract, "mul": multiply, "div": divide,
             "mod": modulo}.get(_n) or globals()[_n]
    globals()["broadcast_" + _n] = _base
    __all__.append("broadcast_" + _n)
elemwise_add = add
elemwise_sub = subtract
elemwise_mul = multiply
elemwise_div = divide
__all__ += ["elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div"]


@_register
def add_n(*args):
    """Reference: src/operator/tensor/elemwise_sum.cc (add_n / ElementwiseSum)."""
    if len(args) == 1 and isinstance(args[0], (list, tuple)):
        args = tuple(args[0])
    return apply_nary(lambda *xs: functools_reduce(xs), list(args), name="add_n")


def functools_reduce(xs):
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


ElementWiseSum = add_n
__all__.append("ElementWiseSum")


@_register
def where(condition, x, y):
    return apply_nary(lambda c, a, b: jnp.where(c != 0, a, b),
                      [_nd(condition), _nd(x), _nd(y)], name="where")


# ======================================================================
# reductions (reference: src/operator/tensor/broadcast_reduce_op*)
# ======================================================================

def _reduce_factory(name, jfn, exclude_support=True):
    def op(data, axis=None, keepdims=False, exclude=False, **kwargs):
        ax = _ax(axis)
        if exclude and ax is not None:
            axes = (ax,) if isinstance(ax, int) else tuple(ax)
            ax = tuple(i for i in range(data.ndim) if i not in
                       tuple(a % data.ndim for a in axes))
        return apply_nary(lambda d: jfn(d, axis=ax, keepdims=keepdims),
                          [data], name=name)
    op.__name__ = name
    op.__doc__ = f"Reduction {name}. Reference: src/operator/tensor/broadcast_reduce_op_value.cc."
    return _register(op)


sum = _reduce_factory("sum", jnp.sum)
mean = _reduce_factory("mean", jnp.mean)
prod = _reduce_factory("prod", jnp.prod)
nansum = _reduce_factory("nansum", jnp.nansum)
nanprod = _reduce_factory("nanprod", jnp.nanprod)
max = _reduce_factory("max", jnp.max)
min = _reduce_factory("min", jnp.min)
@_register
def norm(data, ord=2, axis=None, keepdims=False, **kwargs):
    """Reference: src/operator/tensor/broadcast_reduce_op_value.cc (norm);
    supports ord=1 (sum of |x|) and ord=2 (L2)."""
    ax = _ax(axis)
    if ord == 1:
        jfn = lambda d: jnp.sum(jnp.abs(d), axis=ax, keepdims=keepdims)
    elif ord == 2:
        jfn = lambda d: jnp.sqrt(
            jnp.sum(jnp.square(d), axis=ax, keepdims=keepdims))
    else:
        raise MXNetError(f"norm only supports ord=1 or 2, got {ord}")
    return apply_nary(jfn, [data], name="norm")
sum_axis = sum
max_axis = max
min_axis = min
__all__ += ["sum_axis", "max_axis", "min_axis"]


def _arg_index_dtype():
    """Reference argmax/argmin return FLOAT indices; float32 cannot
    represent indices past 2^24 exactly (and rounds 2^31+k to 2^31), so
    the int64 build widens to float64."""
    import jax
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


@_register
def argmax(data, axis=None, keepdims=False):
    return apply_nary(
        lambda d: jnp.argmax(d, axis=axis, keepdims=keepdims)
        .astype(_arg_index_dtype()), [data], name="argmax")


@_register
def argmin(data, axis=None, keepdims=False):
    return apply_nary(
        lambda d: jnp.argmin(d, axis=axis, keepdims=keepdims)
        .astype(_arg_index_dtype()), [data], name="argmin")


@_register
def mp_sum(*a, **k):  # pragma: no cover - alias
    return sum(*a, **k)


# ======================================================================
# linalg: dot / batch_dot (the MXU path)
# ======================================================================

@_register
def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """mx.nd.dot semantics: reduce last axis of lhs with first axis of rhs
    (tensordot over 1 axis), NOT numpy matmul batching.
    Reference: src/operator/tensor/dot-inl.h."""
    def fn(a, b):
        if transpose_a:
            a = jnp.transpose(a)
        if transpose_b:
            b = jnp.transpose(b)
        if a.ndim == 1 and b.ndim == 1:
            return jnp.dot(a, b)
        return jnp.tensordot(a, b, axes=1)
    return apply_nary(fn, [lhs, rhs], name="dot")


@_register
def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """Reference: src/operator/tensor/dot-inl.h (batch_dot): (B, M, K)x(B, K, N)."""
    def fn(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)
    return apply_nary(fn, [lhs, rhs], name="batch_dot")


@_register
def linalg_gemm2(a, b, transpose_a=False, transpose_b=False, alpha=1.0):
    def fn(x, y):
        if transpose_a:
            x = jnp.swapaxes(x, -1, -2)
        if transpose_b:
            y = jnp.swapaxes(y, -1, -2)
        return alpha * jnp.matmul(x, y)
    return apply_nary(fn, [a, b], name="linalg_gemm2")


# ======================================================================
# shape / matrix ops (reference: src/operator/tensor/matrix_op.cc)
# ======================================================================

@_register
def reshape(data, shape, reverse=False):
    """MXNet reshape incl. codes 0/-1/-2/-3/-4 (matrix_op-inl.h
    InferReshapeShape); ``reverse=True`` matches codes from the right."""
    if reverse:
        from .ndarray import _resolve_reshape
        spec = tuple(int(s) for s in shape)
        if -4 in spec:
            raise MXNetError("reshape(reverse=True) with -4 split is not "
                             "supported; write the split explicitly")
        new_shape = _resolve_reshape(tuple(data.shape)[::-1],
                                     spec[::-1])[::-1]
        return data.reshape(new_shape)
    return data.reshape(shape)


Reshape = reshape


@_register
def flatten(data):
    return data.flatten()


Flatten = flatten
__all__ += ["Reshape", "Flatten"]


@_register
def transpose(data, axes=None):
    return data.transpose(axes) if axes else data.transpose()


@_register
def expand_dims(data, axis):
    return data.expand_dims(axis)


@_register
def squeeze(data, axis=None):
    return data.squeeze(axis)


@_register
def broadcast_axis(data, axis, size):
    """Broadcast size-1 axes to the given sizes (reference broadcast_axis /
    broadcast_axes in src/operator/tensor/broadcast_reduce_op_value.cc)."""
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    sizes = (size,) if isinstance(size, int) else tuple(size)
    tgt = list(data.shape)
    for a, s in zip(axes, sizes):
        if tgt[a] != 1:
            raise MXNetError(
                f"broadcast_axis: axis {a} has size {tgt[a]} != 1")
        tgt[a] = s
    return data.broadcast_to(tuple(tgt))


broadcast_axes = broadcast_axis
__all__.append("broadcast_axes")


@_register
def broadcast_to(data, shape):
    return data.broadcast_to(shape)


@_register
def broadcast_like(lhs, rhs):
    return lhs.broadcast_to(rhs.shape)


@_register
def concat(*data, dim=1):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    return apply_nary(lambda *xs: jnp.concatenate(xs, axis=dim), list(data),
                      name="concat")


Concat = concat
__all__.append("Concat")


@_register
def stack(*data, axis=0):
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = tuple(data[0])
    return apply_nary(lambda *xs: jnp.stack(xs, axis=axis), list(data),
                      name="stack")


@_register
def split(data, num_outputs, axis=1, squeeze_axis=False):
    """Reference: src/operator/slice_channel.cc (SliceChannel/split)."""
    def fn(d):
        parts = jnp.split(d, num_outputs, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return tuple(parts)
    return apply_nary(fn, [data], n_out=num_outputs, name="split")


SliceChannel = split
__all__.append("SliceChannel")


@_register
def slice(data, begin, end, step=None):
    """Reference: src/operator/tensor/matrix_op.cc (slice)."""
    begin = tuple(begin)
    end = tuple(end)
    step = tuple(step) if step is not None else (1,) * len(begin)
    def fn(d):
        idx = tuple(_pyslice(b, e, s)
                    for b, e, s in zip(begin, end, step))
        return d[idx + (Ellipsis,)]
    return apply_nary(fn, [data], name="slice")


def _pyslice(b, e, s):
    return _builtins.slice(b, e, s)


@_register
def slice_axis(data, axis, begin, end):
    def fn(d):
        sl = [_pyslice(None, None, None)] * d.ndim
        sl[axis] = _pyslice(begin, end if end is not None else d.shape[axis], None)
        return d[tuple(sl)]
    return apply_nary(fn, [data], name="slice_axis")


@_register
def slice_like(data, shape_like, axes=None):
    def fn(d, ref):
        sl = [_pyslice(None, None, None)] * d.ndim
        dims = axes if axes is not None else range(d.ndim)
        for a in dims:
            sl[a] = _pyslice(0, ref.shape[a], None)
        return d[tuple(sl)]
    return apply_nary(fn, [data, shape_like], name="slice_like")


@_register
def flip(data, axis):
    return apply_nary(lambda d: jnp.flip(d, axis), [data], name="flip")


reverse = flip
__all__.append("reverse")


@_register
def tile(data, reps):
    return data.tile(reps)


@_register
def repeat(data, repeats, axis=None):
    return data.repeat(repeats, axis)


@_register
def pad(data, mode="constant", pad_width=None, constant_value=0.0):
    """Reference: src/operator/pad.cc. pad_width is the flat MXNet layout
    (before_1, after_1, before_2, after_2, ...)."""
    pw = list(pad_width)
    pairs = [(pw[i], pw[i + 1]) for i in range(0, len(pw), 2)]
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    kwargs = {"constant_values": constant_value} if mode == "constant" else {}
    return apply_nary(lambda d: jnp.pad(d, pairs, mode=jmode, **kwargs),
                      [data], name="pad")


@_register
def swapaxes(data, dim1, dim2):
    return data.swapaxes(dim1, dim2)


SwapAxis = swapaxes
__all__.append("SwapAxis")


@_register
def space_to_depth(data, block_size):
    b = block_size
    def fn(d):
        n, c, h, w = d.shape
        d = d.reshape(n, c, h // b, b, w // b, b)
        d = jnp.transpose(d, (0, 3, 5, 1, 2, 4))
        return d.reshape(n, c * b * b, h // b, w // b)
    return apply_nary(fn, [data], name="space_to_depth")


@_register
def depth_to_space(data, block_size):
    b = block_size
    def fn(d):
        n, c, h, w = d.shape
        d = d.reshape(n, b, b, c // (b * b), h, w)
        d = jnp.transpose(d, (0, 3, 4, 1, 5, 2))
        return d.reshape(n, c // (b * b), h * b, w * b)
    return apply_nary(fn, [data], name="depth_to_space")


# ======================================================================
# indexing ops (reference: src/operator/tensor/indexing_op.cc)
# ======================================================================

@_register
def take(a, indices, axis=0, mode="clip"):
    idx = _nd(indices, a)
    def fn(d, i):
        ii = i.astype(jnp.int32)
        if mode == "wrap":
            ii = jnp.mod(ii, d.shape[axis])
        else:
            ii = jnp.clip(ii, 0, d.shape[axis] - 1)
        return jnp.take(d, ii, axis=axis)
    return apply_nary(fn, [a, idx], name="take")


@_register
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    idx = _nd(index, data)
    def fn(d, i):
        ii = jnp.clip(i.astype(jnp.int32), 0, d.shape[axis] - 1)
        out = jnp.take_along_axis(d, jnp.expand_dims(ii, axis % d.ndim if axis >= 0 else axis),
                                  axis=axis)
        return out if keepdims else jnp.squeeze(out, axis=axis)
    return apply_nary(fn, [data, idx], name="pick")


@_register
def gather_nd(data, indices):
    def fn(d, i):
        ii = i.astype(jnp.int32)
        return d[tuple(ii[k] for k in range(ii.shape[0]))]
    return apply_nary(fn, [data, _nd(indices, data)], name="gather_nd")


@_register
def scatter_nd(data, indices, shape):
    def fn(d, i):
        ii = i.astype(jnp.int32)
        out = jnp.zeros(tuple(shape), d.dtype)
        return out.at[tuple(ii[k] for k in range(ii.shape[0]))].add(d)
    return apply_nary(fn, [data, _nd(indices, data)], name="scatter_nd")


@_register
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    dt = _dtype_of(dtype)
    def fn(i):
        oh = jax.nn.one_hot(i.astype(jnp.int32), depth, dtype=dt)
        return oh * (on_value - off_value) + off_value
    return apply_nary(fn, [_nd(indices)], name="one_hot")


@_register
def Embedding(data, weight, input_dim=None, output_dim=None, dtype="float32",
              sparse_grad=False):
    """Reference: src/operator/tensor/indexing_op.cc (Embedding).

    ``sparse_grad=True`` installs a row-sparse pullback: the weight
    cotangent is (unique touched rows, segment-summed values) — memory and
    compute O(nnz), never O(vocab) (reference kRowSparseStorage grad)."""
    def fn(i, w):
        return jnp.take(w, i.astype(jnp.int32), axis=0)
    data_nd, weight_nd = _nd(data), _nd(weight)
    if not sparse_grad:
        return apply_nary(fn, [data_nd, weight_nd], name="Embedding")

    from .ndarray import NDArray as _ND
    from .. import _tape
    outs, node = _tape.apply_op(fn, [data_nd, weight_nd], n_out=1,
                                name="Embedding(sparse_grad)")
    if node is not None:
        # Fully device-side pullback (r2 weak #6 fixed): the cotangent
        # carries the RAW batch ids (duplicates included) — no host
        # np.unique on the forward hot path, nnz bounded by the batch.
        # Dedup is deferred to SparseCotangent.dedup() at leaf
        # materialization (all consumers sum duplicates).
        ids_j = data_nd.data.astype(jnp.int32).ravel()
        vocab_shape = weight_nd.shape

        def sparse_vjp(cot):
            flat = cot.reshape(-1, cot.shape[-1])
            return (None, _tape.SparseCotangent(ids_j, flat, vocab_shape))
        node.vjp_fn = sparse_vjp
    out = _ND(outs[0], data_nd._ctx)
    if node is not None:
        out._node = node
        out._out_index = 0
    return out


embedding = Embedding
__all__.append("embedding")


@_register
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return identity(data)
    def fn(d, sl):
        steps = jnp.arange(d.shape[axis])
        bshape = [1] * d.ndim
        bshape[axis] = d.shape[axis]
        batch_axis = 1 - axis  # mx convention: (T, B, ...) ax0 or (B, T) ax1
        sshape = [1] * d.ndim
        sshape[batch_axis] = d.shape[batch_axis]
        mask = steps.reshape(bshape) < sl.reshape(sshape)
        return jnp.where(mask, d, jnp.asarray(value, d.dtype))
    return apply_nary(fn, [data, _nd(sequence_length, data)],
                      name="sequence_mask")


SequenceMask = sequence_mask
__all__.append("SequenceMask")


@_register
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return slice_axis(data, axis=axis, begin=-1, end=None).squeeze(axis)
    def fn(d, sl):
        idx = (sl.astype(jnp.int32) - 1)
        # index layout depends on the time axis: batch sits on the other of
        # axes {0,1} (reference src/operator/sequence_last.cc supports both)
        batch_axis = 1 - axis
        ishape = [1] * d.ndim
        ishape[batch_axis] = d.shape[batch_axis]
        return jnp.take_along_axis(d, idx.reshape(ishape), axis=axis) \
            .squeeze(axis)
    return apply_nary(fn, [data, _nd(sequence_length, data)],
                      name="sequence_last")


@_register
def sequence_reverse(data, sequence_length=None, use_sequence_length=False,
                     axis=0):
    if not use_sequence_length or sequence_length is None:
        return flip(data, axis)
    def fn(d, sl):
        T = d.shape[axis]
        steps = jnp.arange(T).reshape((-1,) + (1,) * (d.ndim - 1))
        sl_b = sl.astype(jnp.int32).reshape((1, -1) + (1,) * (d.ndim - 2))
        rev_idx = jnp.where(steps < sl_b, sl_b - 1 - steps, steps)
        return jnp.take_along_axis(d, jnp.broadcast_to(rev_idx, d.shape),
                                   axis=0)
    return apply_nary(fn, [data, _nd(sequence_length, data)],
                      name="sequence_reverse")


SequenceReverse = sequence_reverse
SequenceLast = sequence_last
__all__ += ["SequenceReverse", "SequenceLast"]


# ======================================================================
# ordering (reference: src/operator/tensor/ordering_op.cc)
# ======================================================================

@_register
def sort(data, axis=-1, is_ascend=True):
    def fn(d):
        out = jnp.sort(d, axis=axis)
        return out if is_ascend else jnp.flip(out, axis=axis)
    return apply_nary(fn, [data], name="sort")


@_register
def argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    dt = _dtype_of(dtype)
    def fn(d):
        out = jnp.argsort(d, axis=axis)
        if not is_ascend:
            out = jnp.flip(out, axis=axis)
        return out.astype(dt)
    return apply_nary(fn, [data], name="argsort")


@_register
def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False,
         dtype="float32"):
    dt = _dtype_of(dtype)
    def fn(d):
        dd = jnp.swapaxes(d, axis, -1) if axis not in (-1, d.ndim - 1) else d
        vals, idx = lax.top_k(-dd if is_ascend else dd, k)
        if is_ascend:
            vals = -vals
        if axis not in (-1, d.ndim - 1):
            vals = jnp.swapaxes(vals, axis, -1)
            idx = jnp.swapaxes(idx, axis, -1)
        if ret_typ == "value":
            return vals
        if ret_typ == "both":
            return (vals, idx.astype(dt))
        return idx.astype(dt)
    n_out = 2 if ret_typ == "both" else 1
    return apply_nary(fn, [data], n_out=n_out, name="topk")


# ======================================================================
# neural-net ops (reference: src/operator/nn/*)
# ======================================================================

@_register
def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True):
    """Reference: src/operator/nn/fully_connected.cc. weight is (out, in) —
    MXNet layout; the matmul hits the MXU as data @ weight.T.

    MXTPU_COMPUTE_DTYPE=int8|fp8 (ISSUE 20) reroutes the matmul through
    ops.quant_matmul — amax-scaled low-precision operands, f32
    accumulation, custom VJP with quantized grad-side matmuls — making
    this the single seam every Dense/projection in the trainer crosses.
    Resolved at trace time: unset, the op is BITWISE the plain matmul."""
    inputs = [data, weight] + ([] if no_bias or bias is None else [bias])
    from ..ops.quant_matmul import quant_matmul, resolve_compute_dtype
    cd = resolve_compute_dtype()
    def fn(d, w, *b):
        x = d.reshape(d.shape[0], -1) if flatten and d.ndim > 2 else d
        if cd is not None:
            y = quant_matmul(x, w.T, compute_dtype=cd, tag="fc")
        else:
            y = jnp.matmul(x, w.T)
        if b:
            y = y + b[0]
        return y
    return apply_nary(fn, inputs, name="FullyConnected")


fully_connected = FullyConnected
__all__.append("fully_connected")


@_register
def Activation(data, act_type="relu"):
    """Reference: src/operator/nn/activation.cc."""
    fns = {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid,
           "tanh": jnp.tanh, "softrelu": jax.nn.softplus,
           "softsign": jax.nn.soft_sign}
    if act_type not in fns:
        raise MXNetError(f"unknown act_type {act_type}")
    return apply_nary(fns[act_type], [data], name="Activation")


@_register
def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25,
              lower_bound=0.125, upper_bound=0.334):
    """Reference: src/operator/leaky_relu.cc (leaky/prelu/elu/selu/gelu)."""
    if act_type == "leaky":
        return apply_nary(lambda d: jax.nn.leaky_relu(d, slope), [data],
                          name="LeakyReLU")
    if act_type == "elu":
        return apply_nary(lambda d: jax.nn.elu(d, slope), [data])
    if act_type == "selu":
        return apply_nary(jax.nn.selu, [data])
    if act_type == "gelu":
        return apply_nary(lambda d: jax.nn.gelu(d, approximate=False), [data])
    if act_type == "prelu":
        def fn(d, g):
            return jnp.where(d >= 0, d, _reshape_gamma(g, d) * d)
        return apply_nary(fn, [data, gamma], name="prelu")
    raise MXNetError(f"unknown LeakyReLU act_type {act_type}")


def _reshape_gamma(g, d):
    if g.ndim == 1 and d.ndim > 1:
        return g.reshape((1, -1) + (1,) * (d.ndim - 2))
    return g


@_register
def softmax(data, axis=-1, temperature=None, length=None):
    def fn(d):
        x = d / temperature if temperature else d
        return jax.nn.softmax(x, axis=axis)
    return apply_nary(fn, [data], name="softmax")


@_register
def log_softmax(data, axis=-1, temperature=None):
    def fn(d):
        x = d / temperature if temperature else d
        return jax.nn.log_softmax(x, axis=axis)
    return apply_nary(fn, [data], name="log_softmax")


@_register
def softmin(data, axis=-1):
    return apply_nary(lambda d: jax.nn.softmax(-d, axis=axis), [data])


@_register
def SoftmaxActivation(data, mode="instance"):
    axis = 1 if mode == "channel" else -1
    return softmax(data, axis=axis)


@_register
def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1,
                  use_ignore=False, multi_output=False, normalization="null",
                  out_grad=False, smooth_alpha=0.0):
    """Forward = softmax; backward = (p - onehot(label)) — the classic fused
    op. Reference: src/operator/softmax_output.cc. Implemented with a custom
    vjp so the Module/Symbol path trains identically."""
    @jax.custom_vjp
    def _so(d, l):
        return jax.nn.softmax(d, axis=-1)

    def _fwd(d, l):
        p = jax.nn.softmax(d, axis=-1)
        return p, (p, l)

    def _bwd(res, g):
        p, l = res
        oh = jax.nn.one_hot(l.astype(jnp.int32), p.shape[-1], dtype=p.dtype)
        grad = (p - oh) * grad_scale
        if use_ignore:
            mask = (l != ignore_label).astype(p.dtype)
            grad = grad * mask[..., None]
        if normalization == "batch":
            grad = grad / p.shape[0]
        elif normalization == "valid" and use_ignore:
            denom = jnp.maximum(jnp.sum(l != ignore_label), 1).astype(p.dtype)
            grad = grad / denom
        return grad, None

    _so.defvjp(_fwd, _bwd)
    return apply_nary(_so, [data, _nd(label, data)], name="SoftmaxOutput")


@_register
def Dropout(data, p=0.5, mode="training", axes=None, cudnn_off=False):
    """Reference: src/operator/nn/dropout.cc. Uses the framework PRNG stream
    (mx.random) — explicit-key JAX PRNG behind a stateful facade."""
    from . import random as _rnd
    from .. import _tape as _t
    if not _t.is_training() or p <= 0:
        return identity(data)
    key = _rnd.next_key()
    def fn(d):
        shape = d.shape
        if axes:
            shape = tuple(1 if i in axes else s for i, s in enumerate(d.shape))
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        return jnp.where(keep, d / (1.0 - p), jnp.zeros((), d.dtype))
    return apply_nary(fn, [data], name="Dropout")


# ---- convolution / pooling ----

def _conv_dn(ndim):
    # data NC[D]HW, kernel OI[D]HW — MXNet layout throughout
    spec = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}[ndim]
    return lax.conv_dimension_numbers((1, 1) + (1,) * ndim,
                                      (1, 1) + (1,) * ndim, spec)


@_register
def Convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                workspace=None, layout=None, cudnn_off=False,
                cudnn_tune=None):
    """Reference: src/operator/nn/convolution.cc. Lowered to lax.conv_general_dilated
    so XLA:TPU picks MXU tiling (the reference dispatched to cuDNN)."""
    nd = len(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad_ = tuple(pad) if pad else (0,) * nd
    padding = [(p, p) for p in pad_]
    # MXTPU_CONV_LAYOUT=NHWC runs the 2D conv internally channels-last
    # (TPU-native lane layout); boundary transposes between consecutive
    # convs cancel in XLA. User-facing semantics stay NCHW.
    nhwc = nd == 2 and _os.environ.get("MXTPU_CONV_LAYOUT", "") == "NHWC"
    dn = lax.conv_dimension_numbers(
        (1, 1, 1, 1), (1, 1, 1, 1), ("NHWC", "HWIO", "NHWC")) if nhwc \
        else _conv_dn(nd)
    inputs = [data, weight] + ([] if no_bias or bias is None else [bias])
    def fn(d, w, *b):
        # no preferred_element_type: XLA:TPU already accumulates bf16 convs
        # in fp32, and an explicit fp32 hint breaks jax's conv transpose
        # rule (fp32 cotangent x bf16 operand mismatch) under grad
        if nhwc:
            d = jnp.transpose(d, (0, 2, 3, 1))
            w = jnp.transpose(w, (2, 3, 1, 0))
        y = lax.conv_general_dilated(
            d, w, window_strides=stride, padding=padding,
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=num_group)
        if nhwc:
            y = jnp.transpose(y, (0, 3, 1, 2))
        if b:
            y = y + b[0].reshape((1, -1) + (1,) * nd).astype(y.dtype)
        return y.astype(d.dtype)
    return apply_nary(fn, inputs, name="Convolution")


@_register
def Deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, target_shape=None,
                  num_filter=None, num_group=1, no_bias=True, workspace=None,
                  layout=None, cudnn_off=False, cudnn_tune=None):
    """Transposed conv. Reference: src/operator/nn/deconvolution.cc.

    Lowered as ONE grouped ``lax.conv_general_dilated`` (lhs-dilated by
    stride — the textbook transposed-conv-as-conv identity), so groups,
    stride, dilation and adj all compose in a single XLA conv the MXU
    tiles directly."""
    nd = len(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    dilate_ = tuple(dilate) if dilate else (1,) * nd
    pad_ = tuple(pad) if pad else (0,) * nd
    keff = [dilate_[i] * (kernel[i] - 1) + 1 for i in range(nd)]
    if target_shape is not None:
        # reference: target_shape overrides adj to hit the exact size
        ts = tuple(target_shape)
        in_sp = data.shape[2:]
        adj_ = tuple(
            ts[i] - ((in_sp[i] - 1) * stride[i] - 2 * pad_[i] + keff[i])
            for i in range(nd))
        if any(a < 0 or a >= stride[i] for i, a in enumerate(adj_)):
            raise MXNetError(
                f"Deconvolution: target_shape {ts} unreachable from input "
                f"{tuple(in_sp)} with kernel/stride/pad/dilate given")
    else:
        adj_ = tuple(adj) if adj else (0,) * nd
    inputs = [data, weight] + ([] if no_bias or bias is None else [bias])

    def fn(d, w, *b):
        # deconv forward == gradient of conv wrt input: lhs-dilate by
        # stride, pad with (k_eff-1-p), spatially flip the kernel and swap
        # its (in, out/g) dims per group. Output size:
        # (in-1)*s - 2p + k_eff + adj
        g = num_group
        in_g = w.shape[0] // g
        out_g = w.shape[1]
        wk = w.reshape((g, in_g, out_g) + w.shape[2:])
        wk = jnp.swapaxes(wk, 1, 2)
        wk = wk.reshape((g * out_g, in_g) + w.shape[2:])
        wk = jnp.flip(wk, axis=tuple(range(2, 2 + nd)))
        padding = [(keff[i] - 1 - pad_[i],
                    keff[i] - 1 - pad_[i] + adj_[i]) for i in range(nd)]
        y = lax.conv_general_dilated(
            d, wk, window_strides=(1,) * nd, padding=padding,
            lhs_dilation=stride, rhs_dilation=dilate_,
            dimension_numbers=_conv_dn(nd), feature_group_count=g)
        if b:
            y = y + b[0].reshape((1, -1) + (1,) * nd).astype(y.dtype)
        return y
    return apply_nary(fn, inputs, name="Deconvolution")


@_register
def Pooling(data, kernel=None, pool_type="max", global_pool=False,
            stride=None, pad=None, pooling_convention="valid",
            cudnn_off=False, count_include_pad=True, layout=None,
            p_value=2):
    """Reference: src/operator/nn/pooling.cc. Supports max/avg/sum/lp
    (p_value in the reference's {1,2,3}) and the 'valid'|'full'
    pooling_convention quirk (full = ceil division)."""
    def fn(d):
        nd = d.ndim - 2
        if global_pool:
            axes = tuple(range(2, d.ndim))
            if pool_type == "max":
                return jnp.max(d, axis=axes, keepdims=True)
            if pool_type == "sum":
                return jnp.sum(d, axis=axes, keepdims=True)
            if pool_type == "lp":
                # reference pool_utils.h a_pow_p: x^p with NO abs (odd p
                # keeps sign; negative window sums then root to NaN,
                # reference behavior)
                return jnp.sum(d ** p_value, axis=axes,
                               keepdims=True) ** (1.0 / p_value)
            return jnp.mean(d, axis=axes, keepdims=True)
        k = tuple(kernel)
        s = tuple(stride) if stride else (1,) * nd
        p = tuple(pad) if pad else (0,) * nd
        window = (1, 1) + k
        strides = (1, 1) + s
        if pooling_convention == "full":
            # ceil mode: pad right enough so ceil((x+2p-k)/s)+1 windows fit
            extra = []
            for i in range(nd):
                x = d.shape[2 + i] + 2 * p[i]
                out = -(-(x - k[i]) // s[i]) + 1
                need = (out - 1) * s[i] + k[i] - x
                extra.append(builtins_max(need, 0))
            padding = [(0, 0), (0, 0)] + [(p[i], p[i] + extra[i])
                                          for i in range(nd)]
        else:
            padding = [(0, 0), (0, 0)] + [(p[i], p[i]) for i in range(nd)]
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(d.dtype, jnp.floating) else \
                jnp.iinfo(d.dtype).min
            return lax.reduce_window(d, init, lax.max, window, strides,
                                     padding)
        # init must be a CONCRETE zero: lax.reduce_window only dispatches to
        # the differentiable reduce_window_sum monoid when it can see the
        # identity; a traced jnp zero falls back to a generic reduce_window
        # whose linearization fails under vjp-of-jit (hybridize + record)
        zero = _np.zeros((), d.dtype)
        if pool_type == "lp":
            # reference lp pooling: (sum x^p)^(1/p), no abs (see above)
            sp = lax.reduce_window(d ** p_value, zero, lax.add,
                                   window, strides, padding)
            return (sp ** (1.0 / p_value)).astype(d.dtype)
        ssum = lax.reduce_window(d, zero, lax.add, window, strides, padding)
        if pool_type == "sum":
            return ssum
        if count_include_pad:
            return (ssum / _np.prod(k)).astype(d.dtype)
        ones_ = jnp.ones_like(d)
        cnt = lax.reduce_window(ones_, zero, lax.add, window, strides, padding)
        return (ssum / cnt).astype(d.dtype)
    return apply_nary(fn, [data], name="Pooling")


def builtins_max(a, b):
    return a if a > b else b


@_register
def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
              momentum=0.9, fix_gamma=True, use_global_stats=False,
              output_mean_var=False, axis=1, cudnn_off=False):
    """Stateless op-level BatchNorm (normalizes with given stats in eval, batch
    stats in train). Running-stat *updates* are handled by gluon.nn.BatchNorm,
    which threads aux state explicitly (SURVEY.md §7 hard parts).
    Reference: src/operator/nn/batch_norm.cc."""
    from .. import _tape as _t
    training = _t.is_training() and not use_global_stats
    def fn(d, g, b, mm, mv):
        shape = [1] * d.ndim
        shape[axis] = d.shape[axis]
        g_ = jnp.ones_like(g) if fix_gamma else g
        if training:
            axes = tuple(i for i in range(d.ndim) if i != axis)
            m = jnp.mean(d, axis=axes)
            v = jnp.var(d, axis=axes)
        else:
            m, v = mm, mv
        inv = lax.rsqrt(v + eps).reshape(shape)
        return (d - m.reshape(shape)) * inv * g_.reshape(shape) + b.reshape(shape)
    return apply_nary(fn, [data, gamma, beta, moving_mean, moving_var],
                      name="BatchNorm")


@_register
def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """Reference: src/operator/nn/layer_norm.cc."""
    def fn(d, g, b):
        m = jnp.mean(d, axis=axis, keepdims=True)
        v = jnp.var(d, axis=axis, keepdims=True)
        shape = [1] * d.ndim
        shape[axis] = d.shape[axis]
        return (d - m) * lax.rsqrt(v + eps) * g.reshape(shape) + b.reshape(shape)
    return apply_nary(fn, [data, gamma, beta], name="LayerNorm")


@_register
def InstanceNorm(data, gamma, beta, eps=1e-3):
    def fn(d, g, b):
        axes = tuple(range(2, d.ndim))
        m = jnp.mean(d, axis=axes, keepdims=True)
        v = jnp.var(d, axis=axes, keepdims=True)
        shape = (1, -1) + (1,) * (d.ndim - 2)
        return (d - m) * lax.rsqrt(v + eps) * g.reshape(shape) + b.reshape(shape)
    return apply_nary(fn, [data, gamma, beta], name="InstanceNorm")


@_register
def L2Normalization(data, eps=1e-10, mode="instance"):
    def fn(d):
        if mode == "instance":
            axes = tuple(range(1, d.ndim))
        elif mode == "channel":
            axes = (1,)
        else:
            axes = tuple(range(1, d.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(d), axis=axes, keepdims=True) + eps)
        return d / nrm
    return apply_nary(fn, [data], name="L2Normalization")


@_register
def RNN(data, parameters, state, state_cell=None, state_size=None,
        num_layers=1, mode="lstm", bidirectional=False, p=0.0,
        state_outputs=False, projection_size=None, sequence_length=None,
        use_sequence_length=False):
    """Fused multi-layer (bi)directional RNN over a FLAT parameter vector
    (reference src/operator/rnn.cc / cuDNN RNN).

    data: (T, B, I) sequence-major. parameters: the reference's packed
    1-D vector — all weights first (per layer, per direction: W_i2h
    [G*H, in], W_h2h [G*H, H]), then all biases in the same order
    (b_i2h, b_h2h each [G*H]). state: (L*dir, B, H); state_cell for
    lstm. Returns out (T, B, H*dir), plus final states when
    state_outputs=True. The recurrence is ONE lax.scan per direction —
    the same compiled shape the gluon fused layer uses (identical
    _cell_step gate order, so gluon weights flattened into this layout
    reproduce gluon outputs bit-for-bit)."""
    if projection_size is not None or use_sequence_length:
        raise MXNetError("nd.RNN: projection_size/use_sequence_length "
                         "are not supported (reference cuDNN-only paths)")
    from ..gluon.rnn.rnn_layer import run_fused_rnn
    from .. import _tape
    gates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}.get(mode)
    if gates is None:
        raise MXNetError(f"nd.RNN: unknown mode {mode!r}")
    if mode == "lstm" and state_cell is None:
        raise MXNetError("nd.RNN: lstm mode requires state_cell")
    dirs = 2 if bidirectional else 1
    T, B, I = data.shape
    H = int(state_size) if state_size else state.shape[-1]
    if state.shape[0] != num_layers * dirs:
        raise MXNetError(
            f"nd.RNN: state has {state.shape[0]} layer slots, need "
            f"num_layers*dirs = {num_layers * dirs}")
    expected = _builtins.sum(          # `sum` is the reduction op here
        gates * H * (I if layer == 0 else H * dirs) + gates * H * H
        + 2 * gates * H
        for layer in range(num_layers) for _ in range(dirs))
    n_given = int(_np.prod(getattr(parameters, "shape", (len(parameters),))))
    if n_given != expected:
        raise MXNetError(
            f"nd.RNN: packed parameter vector has {n_given} values, "
            f"layout needs {expected} (mode={mode}, num_layers="
            f"{num_layers}, bidirectional={bidirectional}, I={I}, H={H})")
    training = _tape.is_training()
    # hoist the dropout key OUT of the traced fn: tape replay re-executes
    # fn, and a fresh next_key() there would regenerate different masks
    drop_key = None
    if p and training and num_layers > 1:
        from . import random as _rnd
        drop_key = _rnd.next_key()

    def fn(x, w, *state_arrs):
        # unpack the packed vector with static python offsets
        offs = 0
        weights, biases = [], []
        for layer in range(num_layers):
            in_sz = I if layer == 0 else H * dirs
            for _ in range(dirs):
                wih = w[offs:offs + gates * H * in_sz] \
                    .reshape(gates * H, in_sz)
                offs += gates * H * in_sz
                whh = w[offs:offs + gates * H * H].reshape(gates * H, H)
                offs += gates * H * H
                weights.append((wih, whh))
        for layer in range(num_layers):
            for _ in range(dirs):
                bih = w[offs:offs + gates * H]
                offs += gates * H
                bhh = w[offs:offs + gates * H]
                offs += gates * H
                biases.append((bih, bhh))
        return run_fused_rnn(mode, x, state_arrs, weights, biases,
                             num_layers, dirs, p, training, drop_key)

    inputs = [data, _nd(parameters, data), state]
    if mode == "lstm":
        inputs.append(state_cell)
    n_out = 3 if mode == "lstm" else 2
    results = apply_nary(fn, inputs, n_out=n_out, name="RNN")
    if state_outputs:
        return results
    return results[0]


# ======================================================================
# losses at op level (reference: src/operator/loss_binary_op.cc etc.)
# ======================================================================

@_register
def softmax_cross_entropy(data, label):
    def fn(d, l):
        logp = jax.nn.log_softmax(d, axis=-1)
        oh = jax.nn.one_hot(l.astype(jnp.int32), d.shape[-1], dtype=d.dtype)
        return -jnp.sum(oh * logp)
    return apply_nary(fn, [data, _nd(label, data)],
                      name="softmax_cross_entropy")


@_register
def smooth_l1(data, scalar=1.0):
    s2 = scalar * scalar
    def fn(d):
        a = jnp.abs(d)
        return jnp.where(a < 1.0 / s2, 0.5 * s2 * jnp.square(d), a - 0.5 / s2)
    return apply_nary(fn, [data], name="smooth_l1")


@_register
def MakeLoss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return apply_nary(lambda d: d * grad_scale, [data], name="MakeLoss")


@_register
def BlockGrad(data):
    """Reference: src/operator/tensor/elemwise_unary_op_basic.cc (BlockGrad)."""
    return apply_nary(lambda d: lax.stop_gradient(d), [data], name="BlockGrad")


stop_gradient = BlockGrad
__all__.append("stop_gradient")


# ======================================================================
# control flow (reference: src/operator/control_flow.cc — foreach/while/cond)
# ======================================================================

@_register
def foreach(body, data, init_states):
    """lax.scan-backed foreach. body(elem, states) -> (out, new_states).
    Works on NDArrays imperatively (not differentiable through the tape in
    v1 — use inside HybridBlock/jit for the differentiable path)."""
    single = not isinstance(data, (list, tuple))
    datas = [data] if single else list(data)
    states_single = not isinstance(init_states, (list, tuple))
    states = [init_states] if states_single else list(init_states)

    def step(carry, xs):
        c_nd = [NDArray(c) for c in carry]
        x_nd = [NDArray(x) for x in xs]
        out, new_states = body(x_nd[0] if single else x_nd,
                               c_nd[0] if states_single else c_nd)
        outs = [out] if not isinstance(out, (list, tuple)) else list(out)
        ns = [new_states] if not isinstance(new_states, (list, tuple)) \
            else list(new_states)
        return tuple(s._data for s in ns), tuple(o._data for o in outs)

    from .. import _tape as _t
    with _t.trace_scope():
        final, stacked = lax.scan(step, tuple(s._data for s in states),
                                  tuple(d._data for d in datas))
    outs = [NDArray(s) for s in stacked]
    fstates = [NDArray(f) for f in final]
    return (outs[0] if len(outs) == 1 else outs,
            fstates[0] if states_single else fstates)


@_register
def cond(pred, then_func, else_func):
    p = pred.asscalar() if isinstance(pred, NDArray) else pred
    return then_func() if p else else_func()


@_register
def while_loop(cond_fn, func, loop_vars, max_iterations=None):
    steps = 0
    outputs = []
    lv = list(loop_vars)
    while cond_fn(*lv) and (max_iterations is None or steps < max_iterations):
        out, lv = func(*lv)
        lv = list(lv) if isinstance(lv, (list, tuple)) else [lv]
        if out is not None:     # step functions may carry state only
            outputs.append(out)
        steps += 1
    if outputs and isinstance(outputs[0], (list, tuple)):
        outs = [stack(*[o[i] for o in outputs], axis=0)
                for i in range(len(outputs[0]))]
    elif outputs:
        outs = stack(*outputs, axis=0)
    else:
        outs = []
    return outs, lv


# ======================================================================
# optimizer update ops (reference: src/operator/optimizer_op.cc) —
# these are the fused kernels Trainer/Optimizer call per parameter.
# ======================================================================

@_register
def sgd_update(weight, grad, lr, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
               lazy_update=True, out=None):
    def fn(w, g):
        g = g * rescale_grad
        if clip_gradient >= 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        g = g + wd * w
        return w - lr * g
    new_w = apply_nary(fn, [weight, grad], name="sgd_update")
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def sgd_mom_update(weight, grad, mom, lr, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True,
                   out=None):
    def fn(w, g, m):
        g = g * rescale_grad
        if clip_gradient >= 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        g = g + wd * w
        m_new = momentum * m - lr * g
        return (w + m_new, m_new)
    new_w, new_m = apply_nary(fn, [weight, grad, mom], n_out=2,
                              name="sgd_mom_update")
    mom._set_data(new_m._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def adam_update(weight, grad, mean, var, lr, beta1=0.9, beta2=0.999,
                epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                lazy_update=True, out=None):
    def fn(w, g, m, v):
        g = g * rescale_grad
        if clip_gradient >= 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        g = g + wd * w
        m_new = beta1 * m + (1 - beta1) * g
        v_new = beta2 * v + (1 - beta2) * jnp.square(g)
        return (w - lr * m_new / (jnp.sqrt(v_new) + epsilon), m_new, v_new)
    new_w, new_m, new_v = apply_nary(fn, [weight, grad, mean, var], n_out=3,
                                     name="adam_update")
    mean._set_data(new_m._data)
    var._set_data(new_v._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


# ======================================================================
# misc
# ======================================================================

@_register
def shape_array(data):
    return apply_nary(lambda d: jnp.asarray(d.shape, jnp.int64), [data])


@_register
def size_array(data):
    return apply_nary(lambda d: jnp.asarray([d.size], jnp.int64), [data])


@_register
def diag(data, k=0):
    return apply_nary(lambda d: jnp.diag(d, k) if d.ndim <= 2
                      else jnp.diagonal(d, k), [data], name="diag")


@_register
def batch_take(a, indices):
    def fn(d, i):
        return jnp.take_along_axis(
            d, i.astype(jnp.int32).reshape(-1, 1), axis=1).squeeze(1)
    return apply_nary(fn, [a, _nd(indices, a)], name="batch_take")


@_register
def gather_positions(data, positions):
    """Pick rows at per-batch positions: data (B, L, C), positions (B, M)
    -> (B, M, C). The MLM-head gather (reference: gluonnlp BERT decoder
    uses gather_nd for this)."""
    def fn(d, p):
        return jnp.take_along_axis(
            d, p.astype(jnp.int32)[..., None], axis=1)
    return apply_nary(fn, [data, _nd(positions, data)],
                      name="gather_positions")


# ======================================================================
# index raveling (reference: src/operator/tensor/ravel.cc)
# ======================================================================

@_register
def ravel_multi_index(data, shape):
    """(ndim, n) coordinate rows -> flat indices for ``shape``
    (ravel.cc ravel_multi_index)."""
    shape = tuple(int(s) for s in shape)
    def fn(d):
        # index arithmetic in the widest available int: under MXTPU_INT64
        # (jax_enable_x64) flat indices past 2^31 stay exact — the
        # large-tensor mode's reason to exist
        idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        strides = _np.cumprod((1,) + shape[:0:-1],
                              dtype=_np.int64)[::-1].copy()
        return jnp.sum(d.astype(idt) *
                       jnp.asarray(strides, idt)[:, None], axis=0)
    return apply_nary(fn, [data], name="ravel_multi_index")


@_register
def unravel_index(data, shape):
    """Flat indices -> (ndim, n) coordinate rows (ravel.cc
    unravel_index)."""
    shape = tuple(int(s) for s in shape)
    def fn(d):
        idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        coords = jnp.unravel_index(d.astype(idt), shape)
        return jnp.stack(coords, axis=0)
    return apply_nary(fn, [data], name="unravel_index")


@_register
def khatri_rao(*args):
    """Column-wise Khatri-Rao product: (m,k) x (n,k) -> (m*n, k)
    (reference src/operator/contrib/krprod.cc)."""
    if not args:
        raise MXNetError("khatri_rao needs at least one matrix")
    def fn(*ms):
        out = ms[0]
        for m in ms[1:]:
            k = out.shape[1]
            out = jnp.einsum("ik,jk->ijk", out, m).reshape(-1, k)
        return out
    return apply_nary(fn, [_nd(a) for a in args], name="khatri_rao")


# ======================================================================
# spatial sampling (reference: src/operator/grid_generator.cc,
# bilinear_sampler.cc — the SpatialTransformer pair)
# ======================================================================

@_register
def GridGenerator(data, transform_type="affine", target_shape=None):
    """affine: (B, 6) thetas -> (B, 2, H, W) sampling grid in [-1, 1];
    warp: (B, 2, H, W) flow field -> grid. Reference grid_generator.cc."""
    if transform_type == "affine":
        if target_shape is None:
            raise MXNetError("GridGenerator(affine) needs target_shape")
        h, w = int(target_shape[0]), int(target_shape[1])
        def fn(theta):
            b = theta.shape[0]
            ys = jnp.linspace(-1.0, 1.0, h)
            xs = jnp.linspace(-1.0, 1.0, w)
            gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
            base = jnp.stack([gx.ravel(), gy.ravel(),
                              jnp.ones(h * w)])            # (3, H*W)
            t = theta.reshape(b, 2, 3).astype(jnp.float32)
            grid = jnp.einsum("bij,jn->bin", t, base)      # (B, 2, H*W)
            return grid.reshape(b, 2, h, w)
        return apply_nary(fn, [data], name="GridGenerator")
    if transform_type == "warp":
        def fn(flow):
            b, _, h, w = flow.shape
            ys = jnp.arange(h, dtype=jnp.float32)
            xs = jnp.arange(w, dtype=jnp.float32)
            gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
            x = (gx[None] + flow[:, 0]) * 2.0 / max(w - 1, 1) - 1.0
            y = (gy[None] + flow[:, 1]) * 2.0 / max(h - 1, 1) - 1.0
            return jnp.stack([x, y], axis=1)
        return apply_nary(fn, [data], name="GridGenerator")
    raise MXNetError(f"unknown transform_type {transform_type!r}")


@_register
def BilinearSampler(data, grid, cudnn_off=None):
    """Sample data (B, C, H, W) at grid (B, 2, Ho, Wo) ([-1,1] x/y),
    zero padding outside — reference bilinear_sampler.cc. Differentiable
    in both data and grid (jax.vjp through the gather)."""
    def fn(d, g):
        b, c, h, w = d.shape
        x = (g[:, 0] + 1.0) * (w - 1) / 2.0          # (B, Ho, Wo)
        y = (g[:, 1] + 1.0) * (h - 1) / 2.0
        x0 = jnp.floor(x); y0 = jnp.floor(y)
        # per-batch gather, vectorized with vmap
        def sample_one(dd, yy, xx):
            yi = jnp.clip(yy.astype(jnp.int32), 0, h - 1)
            xi = jnp.clip(xx.astype(jnp.int32), 0, w - 1)
            valid = ((yy >= 0) & (yy <= h - 1) &
                     (xx >= 0) & (xx <= w - 1)).astype(dd.dtype)
            return dd[:, yi, xi] * valid[None]        # (C, Ho, Wo)
        def one(dd, xx, yy, xx0, yy0):
            wx = xx - xx0
            wy = yy - yy0
            v00 = sample_one(dd, yy0, xx0)
            v01 = sample_one(dd, yy0, xx0 + 1)
            v10 = sample_one(dd, yy0 + 1, xx0)
            v11 = sample_one(dd, yy0 + 1, xx0 + 1)
            return (v00 * (1 - wy) * (1 - wx) + v01 * (1 - wy) * wx +
                    v10 * wy * (1 - wx) + v11 * wy * wx)
        return jax.vmap(one)(d, x, y, x0, y0)
    return apply_nary(fn, [data, _nd(grid, data)], name="BilinearSampler")


# ======================================================================
# CTC loss (reference: src/operator/nn/ctc_loss.cc)
# ======================================================================

@_register
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """Connectionist temporal classification loss.

    data: (T, B, C) pre-softmax activations; label: (B, L) padded with -1
    (or 0s beyond label_lengths). Returns per-example forward loss (B,).
    The alpha recursion (extended blank-interleaved label sequence, log
    space) runs as a lax.scan and is fully differentiable through jax
    autodiff — reference src/operator/nn/ctc_loss.cc (warpctc-free).
    """
    if blank_label not in ("first", "last"):
        raise MXNetError("blank_label must be 'first' or 'last'")
    NEG = -1e30

    def _one(logp, ext, skip_ok, t_len, l_len):
        """One example: logp (T, C) log-softmax, ext (S,) extended labels."""
        T = logp.shape[0]
        alpha0 = jnp.full(ext.shape, NEG, jnp.float32)
        alpha0 = alpha0.at[0].set(logp[0, ext[0]])
        alpha0 = alpha0.at[1].set(
            jnp.where(l_len > 0, logp[0, ext[1]], NEG))

        def step(alpha, xs):
            lp_t, t = xs
            a_prev = jnp.concatenate([jnp.full((1,), NEG), alpha[:-1]])
            a_prev2 = jnp.concatenate([jnp.full((2,), NEG), alpha[:-2]])
            a = jnp.logaddexp(alpha, a_prev)
            a = jnp.where(skip_ok, jnp.logaddexp(a, a_prev2), a)
            new = a + lp_t[ext]
            return jnp.where(t < t_len, new, alpha), None

        alpha, _ = lax.scan(step, alpha0,
                            (logp[1:], jnp.arange(1, T)))
        end = 2 * l_len                      # last blank of the used prefix
        a_last = jnp.take(alpha, end)
        a_last2 = jnp.where(l_len > 0,
                            jnp.take(alpha, jnp.maximum(end - 1, 0)), NEG)
        return -jnp.logaddexp(a_last, a_last2)

    def fn(d, lab, *lens):
        t, b, c = d.shape
        blank = 0 if blank_label == "first" else c - 1
        logp = jax.nn.log_softmax(
            jnp.transpose(d, (1, 0, 2)).astype(jnp.float32), axis=-1)
        lab = lab.astype(jnp.int32)
        # lens layout strictly follows the use_* flags (inputs are built
        # the same way below — a None length with the flag set raises)
        if use_label_lengths:
            l_len = lens[1 if use_data_lengths else 0].astype(jnp.int32)
        else:
            l_len = jnp.sum((lab > 0) if blank == 0 else (lab >= 0),
                            axis=1).astype(jnp.int32)
        if use_data_lengths:
            t_len = lens[0].astype(jnp.int32)
        else:
            t_len = jnp.full((b,), t, jnp.int32)
        lab = jnp.maximum(lab, 0)
        L = lab.shape[1]
        ext = jnp.full((b, 2 * L + 1), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab)
        skip = jnp.zeros((b, 2 * L + 1), bool)
        skip = skip.at[:, 2:].set((ext[:, 2:] != blank) &
                                  (ext[:, 2:] != ext[:, :-2]))
        return jax.vmap(_one)(logp, ext, skip, t_len, l_len)

    inputs = [data, _nd(label, data)]
    if use_data_lengths:
        if data_lengths is None:
            raise MXNetError("use_data_lengths=True requires data_lengths")
        inputs.append(_nd(data_lengths, data))
    if use_label_lengths:
        if label_lengths is None:
            raise MXNetError(
                "use_label_lengths=True requires label_lengths")
        inputs.append(_nd(label_lengths, data))
    return apply_nary(fn, inputs, name="ctc_loss")


CTCLoss = ctc_loss
__all__.append("CTCLoss")


# ======================================================================
# fused multi-tensor optimizer ops (reference:
# src/operator/optimizer_op.cc multi_sgd_update / multi_sgd_mom_update,
# src/operator/contrib/multi_lamb.cc)
# ======================================================================

def _group_pairs(arrays, per_weight):
    n = len(arrays) // per_weight
    return [arrays[i * per_weight:(i + 1) * per_weight] for i in range(n)]


def _check_num_weights(name, groups, num_weights):
    """Validate the reference API's num_weights kwarg against the group
    count implied by the flat array list."""
    if num_weights is not None and num_weights != len(groups):
        raise MXNetError(f"{name}: num_weights {num_weights} != "
                         f"{len(groups)} weight groups passed")


@_register
def multi_sgd_update(*arrays, lrs, wds, rescale_grad=1.0,
                     clip_gradient=None, num_weights=None, out=None):
    """Fused group SGD: arrays = (w0, g0, w1, g1, ...). ONE dispatch /
    XLA program updates every weight (the reference's multi-tensor-apply);
    weights are updated in place on their handles and returned."""
    groups = _group_pairs(list(arrays), 2)
    _check_num_weights("multi_sgd_update", groups, num_weights)
    def fn(*flat):
        outs = []
        for i in range(0, len(flat), 2):
            w, g = flat[i], flat[i + 1]
            lr, wd = lrs[i // 2], wds[i // 2]
            g = g * rescale_grad
            if clip_gradient is not None and clip_gradient >= 0:
                g = jnp.clip(g, -clip_gradient, clip_gradient)
            outs.append(w - lr * (g + wd * w))
        # apply_nary with n_out=1 expects a bare array, not a 1-tuple
        return tuple(outs) if len(outs) > 1 else outs[0]
    updated = apply_nary(fn, list(arrays), n_out=len(groups),
                         name="multi_sgd_update")
    updated = updated if isinstance(updated, list) else [updated]
    for (w, _), nw in zip(groups, updated):
        w._set_data(nw.data)
    return updated


@_register
def multi_sgd_mom_update(*arrays, lrs, wds, momentum=0.9, rescale_grad=1.0,
                         clip_gradient=None, num_weights=None, out=None):
    """Fused group SGD+momentum: arrays = (w0, g0, m0, w1, g1, m1, ...);
    weights AND momenta update in place (optimizer_op.cc
    multi_sgd_mom_update)."""
    groups = _group_pairs(list(arrays), 3)
    _check_num_weights("multi_sgd_mom_update", groups, num_weights)
    def fn(*flat):
        outs = []
        for i in range(0, len(flat), 3):
            w, g, m = flat[i], flat[i + 1], flat[i + 2]
            lr, wd = lrs[i // 3], wds[i // 3]
            g = g * rescale_grad
            if clip_gradient is not None and clip_gradient >= 0:
                g = jnp.clip(g, -clip_gradient, clip_gradient)
            new_m = momentum * m - lr * (g + wd * w)
            outs.append(w + new_m)
            outs.append(new_m)
        return tuple(outs)
    updated = apply_nary(fn, list(arrays), n_out=2 * len(groups),
                         name="multi_sgd_mom_update")
    for gi, (w, _, m) in enumerate(groups):
        w._set_data(updated[2 * gi].data)
        m._set_data(updated[2 * gi + 1].data)
    return [updated[2 * i] for i in range(len(groups))]


@_register
def multi_lamb_update(*arrays, lrs, wds, beta1=0.9, beta2=0.999,
                      epsilon=1e-6, rescale_grad=1.0, clip_gradient=None,
                      step=1, lower_bound=None, upper_bound=None, out=None):
    """Fused group LAMB: arrays = (w0, g0, mean0, var0, ...); one XLA
    program for the whole group (contrib/multi_lamb.cc)."""
    groups = _group_pairs(list(arrays), 4)
    def fn(*flat):
        outs = []
        for i in range(0, len(flat), 4):
            w, g, mean, var = flat[i:i + 4]
            lr, wd = lrs[i // 4], wds[i // 4]
            g = g * rescale_grad
            if clip_gradient is not None and clip_gradient >= 0:
                g = jnp.clip(g, -clip_gradient, clip_gradient)
            new_mean = beta1 * mean + (1 - beta1) * g
            new_var = beta2 * var + (1 - beta2) * jnp.square(g)
            mhat = new_mean / (1 - beta1 ** step)
            vhat = new_var / (1 - beta2 ** step)
            upd = mhat / (jnp.sqrt(vhat) + epsilon) + wd * w
            wnorm = jnp.linalg.norm(w)
            unorm = jnp.linalg.norm(upd)
            ratio = jnp.where(
                (wnorm > 0) & (unorm > 0),
                wnorm / jnp.maximum(unorm, 1e-12), 1.0)
            if lower_bound is not None:
                ratio = jnp.maximum(ratio, lower_bound)
            if upper_bound is not None:
                ratio = jnp.minimum(ratio, upper_bound)
            outs.extend([w - lr * ratio * upd, new_mean, new_var])
        return tuple(outs)
    updated = apply_nary(fn, list(arrays), n_out=3 * len(groups),
                         name="multi_lamb_update")
    for gi, (w, _, mean, var) in enumerate(groups):
        w._set_data(updated[3 * gi].data)
        mean._set_data(updated[3 * gi + 1].data)
        var._set_data(updated[3 * gi + 2].data)
    return [updated[3 * i] for i in range(len(groups))]


@_register
def arange_like(data, start=0.0, step=1.0, repeat=1, ctx=None, axis=None):
    """arange shaped like ``data`` (or its ``axis`` length) — reference
    src/operator/tensor/init_op.cc (arange_like). ``repeat`` repeats each
    value WITHIN the same element count (the output always has data's
    shape / the axis length)."""
    def fn(d):
        n = d.shape[axis] if axis is not None else d.size
        dt = d.dtype if jnp.issubdtype(d.dtype, jnp.floating) or \
            jnp.issubdtype(d.dtype, jnp.integer) else jnp.float32
        vals = (start + step * (jnp.arange(n) // repeat)).astype(dt)
        return vals if axis is not None else vals.reshape(d.shape)
    return apply_nary(fn, [data], name="arange_like")


# ======================================================================
# remaining classic nn ops (reference: src/operator/{pad,lrn,correlation,
# upsampling,crop}.cc, nn/group_norm, tensor/broadcast_reduce_op)
# ======================================================================

@_register
def Pad(data, mode="constant", pad_width=(), constant_value=0.0):
    """N-d padding (reference src/operator/pad.cc): pad_width is a flat
    (before, after) pair per axis; mode constant|edge|reflect."""
    pw = tuple(int(p) for p in pad_width)
    if len(pw) != 2 * len(data.shape):
        raise MXNetError(f"pad_width needs 2 entries per axis, got "
                         f"{len(pw)} for ndim {len(data.shape)}")
    pairs = tuple((pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2))
    jmode = {"constant": "constant", "edge": "edge",
             "reflect": "reflect"}.get(mode)
    if jmode is None:
        raise MXNetError(f"unknown pad mode {mode!r}")
    def fn(d):
        if jmode == "constant":
            return jnp.pad(d, pairs, mode="constant",
                           constant_values=constant_value)
        return jnp.pad(d, pairs, mode=jmode)
    return apply_nary(fn, [data], name="Pad")


pad = Pad
__all__.append("pad")


@_register
def argmax_channel(data):
    """argmax over the channel axis (axis 1), float output like the
    reference (broadcast_reduce_op_index.cc argmax_channel)."""
    return apply_nary(lambda d: jnp.argmax(d, axis=1).astype(jnp.float32),
                      [data], name="argmax_channel")


@_register
def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5):
    """Group normalization over (C//G)-channel groups of NCHW input
    (reference src/operator/nn/group_norm.cc)."""
    def fn(d, g, b):
        n, c = d.shape[0], d.shape[1]
        rest = d.shape[2:]
        x = d.reshape(n, num_groups, c // num_groups, *rest)
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        x = (x - mean) / jnp.sqrt(var + eps)
        x = x.reshape(d.shape)
        shape = (1, c) + (1,) * len(rest)
        return x * g.reshape(shape) + b.reshape(shape)
    return apply_nary(fn, [data, _nd(gamma, data), _nd(beta, data)],
                      name="GroupNorm")


@_register
def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response normalization across channels (reference
    src/operator/lrn.cc — the AlexNet-era op)."""
    def fn(d):
        sq = jnp.square(d)
        half = nsize // 2
        padded = jnp.pad(sq, ((0, 0), (half, half)) +
                         ((0, 0),) * (d.ndim - 2))
        acc = jnp.zeros_like(d)
        for i in range(nsize):
            acc = acc + lax.slice_in_dim(padded, i, i + d.shape[1], axis=1)
        return d / jnp.power(knorm + alpha * acc / nsize, beta)
    return apply_nary(fn, [data], name="LRN")


@_register
def UpSampling(*data, scale=2, sample_type="nearest", num_filter=0,
               num_args=1):
    """Spatial upsampling of NCHW inputs (reference
    src/operator/upsampling.cc): nearest or bilinear; multiple inputs
    are each upsampled to the FIRST input's target size and concatenated
    along channels (the FCN skip-connection pattern)."""
    def one(d, th, tw):
        n, c, h, w = d.shape
        if sample_type == "nearest" and th == h * scale and tw == w * scale:
            return jnp.repeat(jnp.repeat(d, scale, axis=2), scale, axis=3)
        import jax.image
        method = "nearest" if sample_type == "nearest" else "bilinear"
        return jax.image.resize(d, (n, c, th, tw), method=method)

    def fn(*ds):
        th = ds[0].shape[2] * scale
        tw = ds[0].shape[3] * scale
        outs = [one(d, th, tw) for d in ds]
        return outs[0] if len(outs) == 1 else \
            jnp.concatenate(outs, axis=1)
    return apply_nary(fn, [_nd(d) for d in data], name="UpSampling")


@_register
def Crop(*data, offset=(0, 0), h_w=(0, 0), num_args=1, center_crop=False):
    """Crop the first NCHW input to the size of the second (or to h_w)
    (reference src/operator/crop.cc)."""
    x = data[0]
    if num_args == 2 and len(data) > 1:
        th, tw = data[1].shape[2], data[1].shape[3]
    else:
        th, tw = h_w
    if th <= 0 or tw <= 0:
        raise MXNetError("Crop needs a reference input (num_args=2) or a "
                         f"positive h_w, got {(th, tw)}")
    h, w = x.shape[2], x.shape[3]
    oy, ox = ((h - th) // 2, (w - tw) // 2) if center_crop else offset
    if oy < 0 or ox < 0 or oy + th > h or ox + tw > w:
        raise MXNetError(f"Crop window {(th, tw)} at offset {(oy, ox)} "
                         f"exceeds input {(h, w)}")
    def fn(d):
        return d[:, :, oy:oy + th, ox:ox + tw]
    return apply_nary(fn, [x], name="Crop")


@_register
def Correlation(data1, data2, kernel_size=1, max_displacement=4, stride1=1,
                stride2=1, pad_size=4, is_multiply=True):
    """Correlation layer (reference src/operator/correlation.cc, the
    FlowNet op): per-displacement mean inner product of two feature maps.
    Vectorized as one shifted-multiply per displacement — XLA fuses the
    window sums; no per-pixel loops."""
    if kernel_size != 1:
        raise MXNetError("Correlation: only kernel_size=1 is supported")
    def fn(a, b):
        n, c, h, w = a.shape
        bp = jnp.pad(b, ((0, 0), (0, 0), (pad_size, pad_size),
                         (pad_size, pad_size)))
        d = max_displacement
        outs = []
        for dy in range(-d, d + 1, stride2):
            for dx in range(-d, d + 1, stride2):
                oy, ox = dy + pad_size, dx + pad_size
                shifted = lax.dynamic_slice(
                    bp, (0, 0, oy, ox), (n, c, h, w))
                if is_multiply:
                    prod = a * shifted
                else:
                    prod = jnp.abs(a - shifted)
                outs.append(jnp.mean(prod, axis=1))
        out = jnp.stack(outs, axis=1)           # (N, D*D, H, W)
        if stride1 > 1:
            out = out[:, :, ::stride1, ::stride1]
        return out
    return apply_nary(fn, [data1, _nd(data2, data1)], name="Correlation")


# ======================================================================
# round-3 op tail: activations, numpy-parity, sample_*, legacy outputs
# (reference: src/operator/tensor/elemwise_unary_op*.cc, matrix_op.cc,
#  src/operator/random/sample_op.cc, src/operator/regression_output*.cc)
# ======================================================================

mish = _unary_factory("mish", lambda d: d * jnp.tanh(jax.nn.softplus(d)))
# erf-based (exact) gelu to match the reference and LeakyReLU(act_type=gelu)
gelu = _unary_factory("gelu", lambda d: jax.nn.gelu(d, approximate=False))
rcbrt = _unary_factory("rcbrt", lambda d: 1.0 / jnp.cbrt(d))
relu6 = _unary_factory("relu6", lambda d: jnp.clip(d, 0.0, 6.0))
selu = _unary_factory("selu", jax.nn.selu)
softrelu = _unary_factory("softrelu", jax.nn.softplus)
log_sigmoid = _unary_factory("log_sigmoid", jax.nn.log_sigmoid)
silu = _unary_factory("silu", jax.nn.silu)
swish = _unary_factory("swish", jax.nn.silu)
isnan = _unary_factory("isnan", jnp.isnan)
isinf = _unary_factory("isinf", jnp.isinf)
isfinite = _unary_factory("isfinite", jnp.isfinite)


@_register
def elu(data, alpha=1.0):
    """ELU (reference LeakyReLU act_type='elu')."""
    return apply_nary(lambda d: jnp.where(d > 0, d, alpha * jnp.expm1(d)),
                      [data], name="elu")


def _binary_factory(name, jfn):
    def op(lhs, rhs, **kwargs):
        return apply_nary(jfn, [lhs, _nd(rhs, lhs)], name=name)
    op.__name__ = name
    op.__doc__ = f"Elementwise {name}. Reference: src/operator/tensor/elemwise_binary_op_basic.cc."
    return _register(op)


fmod = _binary_factory("fmod", jnp.fmod)
mod = _binary_factory("mod", jnp.fmod)   # C fmod semantics, see `modulo`
floor_divide = _binary_factory("floor_divide", jnp.floor_divide)
true_divide = _binary_factory("true_divide", jnp.true_divide)
outer = _binary_factory("outer", jnp.outer)
inner = _binary_factory("inner", jnp.inner)
vdot = _binary_factory("vdot", jnp.vdot)
kron = _binary_factory("kron", jnp.kron)
matmul = _binary_factory("matmul", jnp.matmul)


@_register
def tensordot(a, b, axes=2):
    return apply_nary(lambda x, y: jnp.tensordot(x, y, axes=axes),
                      [a, _nd(b, a)], name="tensordot")


@_register
def cumsum(a, axis=None, dtype=None):
    return apply_nary(
        lambda d: jnp.cumsum(d, axis=axis,
                             dtype=_dtype_of(dtype) if dtype else None),
        [a], name="cumsum")


@_register
def cumprod(a, axis=None):
    return apply_nary(lambda d: jnp.cumprod(d, axis=axis), [a],
                      name="cumprod")


@_register
def trace(data, offset=0, axis1=0, axis2=1):
    return apply_nary(lambda d: jnp.trace(d, offset, axis1, axis2), [data],
                      name="trace")


@_register
def rot90(data, k=1, axes=(0, 1)):
    return apply_nary(lambda d: jnp.rot90(d, k, axes), [data], name="rot90")


@_register
def tril(data, k=0):
    return apply_nary(lambda d: jnp.tril(d, k), [data], name="tril")


@_register
def triu(data, k=0):
    return apply_nary(lambda d: jnp.triu(d, k), [data], name="triu")


@_register
def full_like(data, fill_value, dtype=None):
    return apply_nary(
        lambda d: jnp.full_like(d, fill_value,
                                dtype=_dtype_of(dtype) if dtype else None),
        [data], name="full_like")


@_register
def masked_softmax(data, mask, axis=-1, temperature=1.0):
    """Softmax over positions where mask is true; masked positions get 0
    probability (reference src/operator/nn/softmax.cc masked_softmax)."""
    def fn(d, m):
        neg = jnp.finfo(d.dtype if jnp.issubdtype(d.dtype, jnp.floating)
                        else jnp.float32).min
        z = jnp.where(m.astype(bool), d / temperature, neg)
        p = jax.nn.softmax(z, axis=axis)
        return jnp.where(m.astype(bool), p, jnp.zeros((), p.dtype))
    return apply_nary(fn, [data, _nd(mask, data)], name="masked_softmax")


@_register
def meshgrid(*arrays, indexing="xy"):
    arrs = [_nd(a) for a in arrays]
    if len(arrs) == 1:   # numpy semantics: always a list, even for one input
        return [apply_nary(
            lambda d: jnp.meshgrid(d, indexing=indexing)[0], arrs,
            name="meshgrid")]
    return apply_nary(lambda *ds: tuple(jnp.meshgrid(*ds, indexing=indexing)),
                      arrs, n_out=len(arrs), name="meshgrid")


def _stack_factory(name, jfn):
    def op(*arrays, **kwargs):
        if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
            arrays = tuple(arrays[0])
        arrs = [_nd(a) for a in arrays]
        return apply_nary(lambda *ds: jfn(ds), arrs, name=name)
    op.__name__ = name
    op.__doc__ = f"numpy-style {name}."
    return _register(op)


hstack = _stack_factory("hstack", jnp.hstack)
vstack = _stack_factory("vstack", jnp.vstack)
dstack = _stack_factory("dstack", jnp.dstack)


def _np_split_factory(name, jfn):
    def op(data, indices_or_sections):
        n = indices_or_sections if isinstance(indices_or_sections, int) \
            else len(indices_or_sections) + 1
        if n == 1:   # numpy semantics: a one-element list
            return [apply_nary(lambda d: jfn(d, indices_or_sections)[0],
                               [data], name=name)]
        return apply_nary(lambda d: tuple(jfn(d, indices_or_sections)),
                          [data], n_out=n, name=name)
    op.__name__ = name
    op.__doc__ = f"numpy-style {name}."
    return _register(op)


hsplit = _np_split_factory("hsplit", jnp.hsplit)
vsplit = _np_split_factory("vsplit", jnp.vsplit)


@_register
def histogram(data, bins=10, range=None):
    """Histogram counts + bin edges. Not differentiable (counts are
    integer, so the input is detached); runs eagerly on device."""
    data = _nd(data).detach()
    rng = range
    def fn(d):
        return jnp.histogram(d, bins=bins, range=rng)
    return apply_nary(fn, [data], n_out=2, name="histogram")


@_register
def bincount(data, weights=None, minlength=0):
    """Integer-count op: data-dependent output size, eager only; inputs are
    detached (counts are not differentiable w.r.t. indices)."""
    data = _nd(data).detach()
    if weights is None:
        return apply_nary(
            lambda d: jnp.bincount(d.astype(jnp.int32), minlength=minlength,
                                   length=None),
            [data], name="bincount")
    return apply_nary(
        lambda d, w: jnp.bincount(d.astype(jnp.int32), w,
                                  minlength=minlength),
        [data, _nd(weights, data)], name="bincount")


@_register
def unique(data):
    """Sorted unique values. Output size is data-dependent — eager only
    (inside jit/hybridize the size cannot be static); not differentiable, so
    the input is detached from any open tape; reference mx.np.unique."""
    return apply_nary(lambda d: jnp.unique(d), [_nd(data).detach()],
                      name="unique")


# ---- sample_* family: per-element distribution parameters ----
# reference src/operator/random/sample_op.cc: output shape = params.shape
# + shape; each output element drawn from its own parameterization

def _sample_shape(pshape, shape):
    if shape is None:
        return tuple(pshape)
    extra = (shape,) if isinstance(shape, int) else tuple(shape)
    return tuple(pshape) + extra


@_register
def sample_uniform(low, high, shape=None, dtype=None, ctx=None):
    from . import random as _rnd
    low = _nd(low)
    high = _nd(high, low)
    out_shape = _sample_shape(low.shape, shape)
    def fn(lo, hi):
        u = jax.random.uniform(_rnd.next_key(), out_shape,
                               _dtype_of(dtype) if dtype else jnp.float32)
        nd_ = lo.ndim
        bshape = lo.shape + (1,) * (len(out_shape) - nd_)
        return lo.reshape(bshape) + u * (hi - lo).reshape(bshape)
    return apply_nary(fn, [low, high], name="sample_uniform")


@_register
def sample_normal(mu, sigma, shape=None, dtype=None, ctx=None):
    from . import random as _rnd
    mu = _nd(mu)
    sigma = _nd(sigma, mu)
    out_shape = _sample_shape(mu.shape, shape)
    def fn(m, s):
        z = jax.random.normal(_rnd.next_key(), out_shape,
                              _dtype_of(dtype) if dtype else jnp.float32)
        bshape = m.shape + (1,) * (len(out_shape) - m.ndim)
        return m.reshape(bshape) + z * s.reshape(bshape)
    return apply_nary(fn, [mu, sigma], name="sample_normal")


@_register
def sample_gamma(alpha, beta, shape=None, dtype=None, ctx=None):
    from . import random as _rnd
    alpha = _nd(alpha)
    beta = _nd(beta, alpha)
    out_shape = _sample_shape(alpha.shape, shape)
    def fn(a, b):
        bshape = a.shape + (1,) * (len(out_shape) - a.ndim)
        g = jax.random.gamma(_rnd.next_key(),
                             jnp.broadcast_to(a.reshape(bshape), out_shape),
                             dtype=_dtype_of(dtype) if dtype else jnp.float32)
        return g * b.reshape(bshape)
    return apply_nary(fn, [alpha, beta], name="sample_gamma")


@_register
def sample_exponential(lam, shape=None, dtype=None, ctx=None):
    from . import random as _rnd
    lam = _nd(lam)
    out_shape = _sample_shape(lam.shape, shape)
    def fn(l):
        e = jax.random.exponential(
            _rnd.next_key(), out_shape,
            _dtype_of(dtype) if dtype else jnp.float32)
        return e / l.reshape(l.shape + (1,) * (len(out_shape) - l.ndim))
    return apply_nary(fn, [lam], name="sample_exponential")


@_register
def sample_poisson(lam, shape=None, dtype=None, ctx=None):
    from . import random as _rnd
    lam = _nd(lam)
    out_shape = _sample_shape(lam.shape, shape)
    def fn(l):
        lb = jnp.broadcast_to(
            l.reshape(l.shape + (1,) * (len(out_shape) - l.ndim)), out_shape)
        p = jax.random.poisson(_rnd.next_key(), lb, shape=out_shape)
        return p.astype(_dtype_of(dtype) if dtype else jnp.float32)
    return apply_nary(fn, [lam], name="sample_poisson")


@_register
def sample_multinomial(data, shape=None, get_prob=False, dtype="int32"):
    """Draw from rows of probabilities; with get_prob=True also return the
    log-likelihood of each draw for REINFORCE-style training (reference
    src/operator/random/sample_op.cc sample_multinomial: output shape is
    data.shape[:-1] + shape)."""
    from . import random as _rnd
    data = _nd(data)
    extra = () if shape is None else (
        (shape,) if isinstance(shape, int) else tuple(shape))
    n = int(_np.prod(extra)) if extra else 1
    def fn(p):
        logits = jnp.log(jnp.maximum(p, 1e-30))
        draws = jax.random.categorical(
            _rnd.next_key(), logits, axis=-1, shape=(n,) + p.shape[:-1])
        draws = jnp.moveaxis(draws, 0, -1)          # (..., n)
        out_shape = p.shape[:-1] + extra
        out = draws.reshape(out_shape).astype(_dtype_of(dtype))
        if not get_prob:
            return out
        logp = jnp.take_along_axis(
            jnp.broadcast_to(logits[..., None, :],
                             p.shape[:-1] + (n, p.shape[-1])),
            draws[..., :, None].astype(jnp.int32), axis=-1)
        return out, logp[..., 0].reshape(out_shape).astype(p.dtype)
    return apply_nary(fn, [data], n_out=2 if get_prob else 1,
                      name="sample_multinomial")


def random_uniform(low=0.0, high=1.0, shape=None, dtype=None, ctx=None):
    """Alias of mx.nd.random.uniform (reference _random_uniform)."""
    from . import random as _rnd
    return _rnd.uniform(low, high, shape, dtype, ctx)


def random_normal(loc=0.0, scale=1.0, shape=None, dtype=None, ctx=None):
    """Alias of mx.nd.random.normal (reference _random_normal)."""
    from . import random as _rnd
    return _rnd.normal(loc, scale, shape, dtype, ctx)


__all__ += ["random_uniform", "random_normal"]


# ---- legacy Module-era output ops: forward=identity, custom backward ----
# reference src/operator/regression_output{,-inl}.h, svm_output.cc,
# make_loss.cc: backward IGNORES the incoming cotangent and emits the
# op-defined gradient scaled by grad_scale

def _output_op(name, grad_fn):
    def op(data, label, grad_scale=1.0):
        label = _nd(label, data)

        @jax.custom_vjp
        def fwd(d, l):
            return d

        def fwd_fwd(d, l):
            return d, (d, l)

        def fwd_bwd(res, g):
            d, l = res
            return (grad_fn(d, l, grad_scale).astype(d.dtype),
                    jnp.zeros_like(l))

        fwd.defvjp(fwd_fwd, fwd_bwd)
        return apply_nary(fwd, [data, label], name=name)
    op.__name__ = name
    op.__doc__ = (f"{name} (reference src/operator/): identity forward; "
                  "backward is the op-defined gradient, replacing the "
                  "incoming cotangent (legacy Module-era loss op).")
    return _register(op)


def _linreg_grad(d, l, scale):
    return (d - l.reshape(d.shape)) * scale


def _maereg_grad(d, l, scale):
    return jnp.sign(d - l.reshape(d.shape)) * scale


LinearRegressionOutput = _output_op("LinearRegressionOutput", _linreg_grad)
MAERegressionOutput = _output_op("MAERegressionOutput", _maereg_grad)


@_register
def LogisticRegressionOutput(data, label, grad_scale=1.0):
    """Reference src/operator/regression_output.cc (LogisticRegressionOutput):
    forward = sigmoid(data); backward w.r.t. data = (out - label)*grad_scale,
    replacing the incoming cotangent (legacy Module-era loss op)."""
    label = _nd(label, data)

    @jax.custom_vjp
    def fwd(d, l):
        return jax.nn.sigmoid(d)

    def fwd_fwd(d, l):
        out = jax.nn.sigmoid(d)
        return out, (out, l)

    def fwd_bwd(res, g):
        out, l = res
        return (((out - l.reshape(out.shape)) * grad_scale).astype(out.dtype),
                jnp.zeros_like(l))

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return apply_nary(fwd, [data, label], name="LogisticRegressionOutput")


def _svm_grad(d, l, scale, margin=1.0, regularization_coefficient=1.0,
              use_linear=False):
    lab = l.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, d.shape[-1], dtype=d.dtype)
    signed = jnp.where(onehot > 0, -d, d)
    viol = (margin + signed) > 0
    if use_linear:
        g = jnp.where(viol, jnp.where(onehot > 0, -1.0, 1.0), 0.0)
    else:
        g = jnp.where(viol, 2.0 * (margin + signed) *
                      jnp.where(onehot > 0, -1.0, 1.0), 0.0)
    return g * scale * regularization_coefficient


@_register
def SVMOutput(data, label, margin=1.0, regularization_coefficient=1.0,
              use_linear=False, grad_scale=1.0):
    """Hinge-loss output op (reference src/operator/svm_output.cc):
    identity forward, margin-violation gradient backward."""
    label = _nd(label, data)

    @jax.custom_vjp
    def fwd(d, l):
        return d

    def fwd_fwd(d, l):
        return d, (d, l)

    def fwd_bwd(res, g):
        d, l = res
        return (_svm_grad(d, l, grad_scale, margin,
                          regularization_coefficient,
                          use_linear).astype(d.dtype),
                jnp.zeros_like(l))

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return apply_nary(fwd, [data, label], name="SVMOutput")


@_register
def im2col(data, kernel, stride=None, dilate=None, pad=None):
    """Unfold conv patches to a (N, C*prod(kernel), L) matrix (reference
    src/operator/nn/im2col.h via the im2col op). Lowered to
    lax.conv_general_dilated_patches so XLA emits one gather-free windowed
    read; column order matches the reference (channel-major, then kernel
    positions row-major, spatial L last)."""
    ndim = len(kernel)
    stride = tuple(stride) if stride else (1,) * ndim
    dilate = tuple(dilate) if dilate else (1,) * ndim
    pad_ = tuple(pad) if pad else (0,) * ndim
    def fn(d):
        patches = lax.conv_general_dilated_patches(
            d, filter_shape=tuple(kernel), window_strides=stride,
            padding=[(p, p) for p in pad_], rhs_dilation=dilate)
        # patches: (N, C*prod(k), *out_spatial) already channel-major
        n = patches.shape[0]
        c = patches.shape[1]
        return patches.reshape(n, c, -1)
    return apply_nary(fn, [data], name="im2col")


@_register
def col2im(data, output_size, kernel, stride=None, dilate=None, pad=None):
    """Fold a (N, C*prod(kernel), L) matrix back to an image, summing
    overlapping patches (reference col2im op) — implemented as the exact
    linear transpose of im2col via jax.linear_transpose, so the pair is
    adjoint by construction."""
    ndim = len(kernel)
    stride_ = tuple(stride) if stride else (1,) * ndim
    dilate_ = tuple(dilate) if dilate else (1,) * ndim
    pad_ = tuple(pad) if pad else (0,) * ndim
    out_sp = (output_size,) * ndim if isinstance(output_size, int) \
        else tuple(output_size)
    def fn(cols):
        n = cols.shape[0]
        ck = cols.shape[1]
        c = ck // int(_np.prod(kernel))
        img_shape = (n, c) + out_sp
        def unfold(img):
            p = lax.conv_general_dilated_patches(
                img, filter_shape=tuple(kernel), window_strides=stride_,
                padding=[(p_, p_) for p_ in pad_], rhs_dilation=dilate_)
            return p.reshape(n, ck, -1)
        img0 = jnp.zeros(img_shape, cols.dtype)
        transpose = jax.linear_transpose(unfold, img0)
        (img,) = transpose(cols)
        return img
    return apply_nary(fn, [data], name="col2im")


# ======================================================================
# bitwise / integer elementwise (reference: mx.np bitwise ops +
# src/operator/tensor/elemwise_binary_op_logic.cc family)
# ======================================================================

def _int_binary_factory(name, jfn):
    """Integer-only binary ops: a Python-scalar rhs must NOT go through
    _nd (which builds a float32 NDArray jax would reject) — pass it raw
    so jax weak-types it to the lhs integer dtype."""
    def op(lhs, rhs, **kwargs):
        if isinstance(rhs, NDArray):
            return apply_nary(jfn, [lhs, rhs], name=name)
        return apply_nary(lambda a: jfn(a, rhs), [lhs], name=name)
    op.__name__ = name
    op.__doc__ = (f"Elementwise {name}. Reference: mx.np bitwise/int ops "
                  "(src/operator/tensor/elemwise_binary_op_logic.cc "
                  "family).")
    return _register(op)


bitwise_and = _int_binary_factory("bitwise_and", jnp.bitwise_and)
bitwise_or = _int_binary_factory("bitwise_or", jnp.bitwise_or)
bitwise_xor = _int_binary_factory("bitwise_xor", jnp.bitwise_xor)
left_shift = _int_binary_factory("left_shift", jnp.left_shift)
right_shift = _int_binary_factory("right_shift", jnp.right_shift)
lcm = _int_binary_factory("lcm", jnp.lcm)
gcd = _int_binary_factory("gcd", jnp.gcd)


@_register
def bitwise_not(data):
    return apply_nary(jnp.bitwise_not, [data], name="bitwise_not")


invert = bitwise_not
__all__.append("invert")


@_register
def isposinf(data):
    return apply_nary(lambda d: jnp.isposinf(d).astype(jnp.float32), [data],
                      name="isposinf")


@_register
def isneginf(data):
    return apply_nary(lambda d: jnp.isneginf(d).astype(jnp.float32), [data],
                      name="isneginf")


@_register
def nan_to_num(data, copy=True, nan=0.0, posinf=None, neginf=None):
    out = apply_nary(
        lambda d: jnp.nan_to_num(d, nan=nan, posinf=posinf, neginf=neginf),
        [data], name="nan_to_num")
    if not copy:
        # reference copy=False mutates the input in place
        data._set_data(out._data)
        return data
    return out


@_register
def ediff1d(data, to_end=None, to_begin=None):
    def fn(d):
        out = jnp.diff(d.ravel())
        parts = []
        if to_begin is not None:
            parts.append(jnp.atleast_1d(jnp.asarray(to_begin, out.dtype))
                         .ravel())
        parts.append(out)
        if to_end is not None:
            parts.append(jnp.atleast_1d(jnp.asarray(to_end, out.dtype))
                         .ravel())
        return jnp.concatenate(parts) if len(parts) > 1 else out
    return apply_nary(fn, [data], name="ediff1d")


@_register
def interp(x, xp, fp, left=None, right=None):
    def fn(a, b, c):
        return jnp.interp(a, b, c, left=left, right=right)
    return apply_nary(fn, [x, _nd(xp, x), _nd(fp, x)], name="interp")


@_register
def polyval(p, x):
    def fn(pp, xx):
        return jnp.polyval(pp, xx)
    return apply_nary(fn, [_nd(p, x), x], name="polyval")


@_register
def divmod(lhs, rhs):   # noqa: A001 — reference op name
    def fn(a, b):
        q = jnp.floor_divide(a, b)
        return q, a - q * b
    return apply_nary(fn, [lhs, _nd(rhs, lhs)], n_out=2, name="divmod")


@_register
def digitize(data, bins, right=False):
    def fn(d, b):
        return jnp.digitize(d, b, right=right).astype(jnp.int64)
    return apply_nary(fn, [data, _nd(bins, data)], name="digitize")


@_register
def searchsorted(a, v, side="left", sorter=None):
    if sorter is not None:
        raise MXNetError("searchsorted: sorter is not supported; "
                         "pre-sort the input")
    def fn(aa, vv):
        return jnp.searchsorted(aa, vv, side=side).astype(jnp.int64)
    return apply_nary(fn, [a, _nd(v, a)], name="searchsorted")


# ======================================================================
# random_pdf_* family (reference: src/operator/random/pdf_op.cc) —
# pdf of `sample` under per-row distribution parameters. Parameter
# arrays have shape S; samples have shape S + (n,) (dirichlet:
# alpha S + (k,), sample S + (n, k)). All support is_log.
# ======================================================================

def _pdf_op(name, logpdf_fn, n_params, event_dims=0):
    def op(sample, *params, is_log=False):
        if len(params) != n_params:
            raise MXNetError(f"{name} expects {n_params} parameter "
                             f"array(s), got {len(params)}")

        def fn(s, *ps):
            # parameters broadcast over the trailing sample axis (for
            # dirichlet the event axis stays rightmost: insert before it)
            axis = -1 - event_dims
            ps = [jnp.expand_dims(p, axis) for p in ps]
            lp = logpdf_fn(s, *ps)
            return lp if is_log else jnp.exp(lp)
        return apply_nary(fn, [sample] + [_nd(p, sample) for p in params],
                          name=name)
    op.__name__ = name
    op.__doc__ = (f"{name}(sample, params..., is_log=False) — reference "
                  "src/operator/random/pdf_op.cc; grads via jax.vjp.")
    return _register(op)


def _lgamma(x):
    return lax.lgamma(x.astype(jnp.float32))


random_pdf_uniform = _pdf_op(
    "random_pdf_uniform",
    lambda s, low, high: jnp.where(
        (s >= low) & (s <= high), -jnp.log(high - low), -jnp.inf), 2)

random_pdf_normal = _pdf_op(
    "random_pdf_normal",
    lambda s, mu, sigma: -0.5 * jnp.square((s - mu) / sigma)
    - jnp.log(sigma) - 0.5 * math.log(2 * math.pi), 2)

random_pdf_gamma = _pdf_op(
    "random_pdf_gamma",
    lambda s, alpha, beta: (alpha - 1) * jnp.log(s) - s * beta
    + alpha * jnp.log(beta) - _lgamma(alpha), 2)

random_pdf_exponential = _pdf_op(
    "random_pdf_exponential",
    lambda s, lam: jnp.log(lam) - lam * s, 1)

random_pdf_poisson = _pdf_op(
    "random_pdf_poisson",
    lambda s, lam: s * jnp.log(lam) - lam - _lgamma(s + 1), 1)

random_pdf_negative_binomial = _pdf_op(
    "random_pdf_negative_binomial",
    lambda s, k, p: _lgamma(s + k) - _lgamma(s + 1) - _lgamma(k)
    + k * jnp.log(p) + s * jnp.log1p(-p), 2)


def _gnb_logpdf(s, mu, alpha):
    # generalized negative binomial in (mu, alpha) parametrization
    # (reference pdf_op.cc): r = 1/alpha, p = r/(r+mu)
    r = 1.0 / alpha
    p = r / (r + mu)
    return (_lgamma(s + r) - _lgamma(s + 1) - _lgamma(r)
            + r * jnp.log(p) + s * jnp.log1p(-p))


random_pdf_generalized_negative_binomial = _pdf_op(
    "random_pdf_generalized_negative_binomial", _gnb_logpdf, 2)


def _dirichlet_logpdf(s, alpha):
    # s: (..., n, k), alpha broadcast (..., 1, k)
    return (jnp.sum((alpha - 1) * jnp.log(s), axis=-1)
            + _lgamma(jnp.sum(alpha, axis=-1))
            - jnp.sum(_lgamma(alpha), axis=-1))


random_pdf_dirichlet = _pdf_op(
    "random_pdf_dirichlet", _dirichlet_logpdf, 1, event_dims=1)


# ======================================================================
# optimizer update-op tail (reference: src/operator/optimizer_op.cc) —
# raw op-level entry points mirroring the fused kernels Optimizer uses.
# All mutate `weight` (and state) in place and return the weight handle,
# matching the reference's out=weight convention.
# ======================================================================

def _prep_grad(g, w, wd, rescale_grad, clip_gradient):
    g = g * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return g + wd * w


@_register
def signsgd_update(weight, grad, lr, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0, out=None):
    def fn(w, g):
        g = g * rescale_grad
        if clip_gradient >= 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        return (1 - lr * wd) * w - lr * jnp.sign(g)
    new_w = apply_nary(fn, [weight, grad], name="signsgd_update")
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def signum_update(weight, grad, mom, lr, momentum=0.0, wd=0.0,
                  rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0, out=None):
    def fn(w, g, m):
        g = _prep_grad(g, w, wd, rescale_grad, clip_gradient)
        m_new = momentum * m - (1 - momentum) * g
        return ((1 - lr * wd_lh) * w + lr * jnp.sign(m_new), m_new)
    new_w, new_m = apply_nary(fn, [weight, grad, mom], n_out=2,
                              name="signum_update")
    mom._set_data(new_m._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def rmsprop_update(weight, grad, n, lr, gamma1=0.95, epsilon=1e-8, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, clip_weights=-1.0,
                   out=None):
    def fn(w, g, nn_):
        g = _prep_grad(g, w, wd, rescale_grad, clip_gradient)
        n_new = gamma1 * nn_ + (1 - gamma1) * jnp.square(g)
        w_new = w - lr * g / (jnp.sqrt(n_new) + epsilon)
        if clip_weights > 0:
            w_new = jnp.clip(w_new, -clip_weights, clip_weights)
        return (w_new, n_new)
    new_w, new_n = apply_nary(fn, [weight, grad, n], n_out=2,
                              name="rmsprop_update")
    n._set_data(new_n._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def rmspropalex_update(weight, grad, n, g, delta, lr, gamma1=0.95,
                       gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                       clip_gradient=-1.0, clip_weights=-1.0, out=None):
    """RMSProp with the Alex Graves centered variant + momentum delta."""
    def fn(w, gr, nn_, gm, dl):
        gr = _prep_grad(gr, w, wd, rescale_grad, clip_gradient)
        n_new = gamma1 * nn_ + (1 - gamma1) * jnp.square(gr)
        g_new = gamma1 * gm + (1 - gamma1) * gr
        d_new = gamma2 * dl - lr * gr / jnp.sqrt(
            n_new - jnp.square(g_new) + epsilon)
        w_new = w + d_new
        if clip_weights > 0:
            w_new = jnp.clip(w_new, -clip_weights, clip_weights)
        return (w_new, n_new, g_new, d_new)
    new_w, new_n, new_g, new_d = apply_nary(
        fn, [weight, grad, n, g, delta], n_out=4, name="rmspropalex_update")
    n._set_data(new_n._data)
    g._set_data(new_g._data)
    delta._set_data(new_d._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def ftrl_update(weight, grad, z, n, lr, lamda1=0.01, beta=1.0, wd=0.0,
                rescale_grad=1.0, clip_gradient=-1.0, out=None):
    def fn(w, g, zz, nn_):
        g = g * rescale_grad
        if clip_gradient >= 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        n_new = nn_ + jnp.square(g)
        sigma = (jnp.sqrt(n_new) - jnp.sqrt(nn_)) / lr
        z_new = zz + g - sigma * w
        w_new = -(z_new - jnp.sign(z_new) * lamda1) / \
            ((beta + jnp.sqrt(n_new)) / lr + wd)
        w_new = jnp.where(jnp.abs(z_new) <= lamda1,
                          jnp.zeros_like(w_new), w_new)
        return (w_new, z_new, n_new)
    new_w, new_z, new_n = apply_nary(fn, [weight, grad, z, n], n_out=3,
                                     name="ftrl_update")
    z._set_data(new_z._data)
    n._set_data(new_n._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def adagrad_update(weight, grad, history, lr, epsilon=1e-7, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, out=None):
    def fn(w, g, h):
        g = _prep_grad(g, w, wd, rescale_grad, clip_gradient)
        h_new = h + jnp.square(g)
        return (w - lr * g / (jnp.sqrt(h_new) + epsilon), h_new)
    new_w, new_h = apply_nary(fn, [weight, grad, history], n_out=2,
                              name="adagrad_update")
    history._set_data(new_h._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def nag_mom_update(weight, grad, mom, lr, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, out=None):
    def fn(w, g, m):
        g = _prep_grad(g, w, wd, rescale_grad, clip_gradient)
        m_new = momentum * m + g
        return (w - lr * (g + momentum * m_new), m_new)
    new_w, new_m = apply_nary(fn, [weight, grad, mom], n_out=2,
                              name="nag_mom_update")
    mom._set_data(new_m._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def ftml_update(weight, grad, d, v, z, lr, t, beta1=0.6, beta2=0.999,
                epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_grad=-1.0,
                out=None):
    def fn(w, g, dd, vv, zz):
        g = _prep_grad(g, w, wd, rescale_grad, clip_grad)
        v_new = beta2 * vv + (1 - beta2) * jnp.square(g)
        d_new = (1 - beta1 ** t) / lr * (
            jnp.sqrt(v_new / (1 - beta2 ** t)) + epsilon)
        sigma = d_new - beta1 * dd
        z_new = beta1 * zz + (1 - beta1) * g - sigma * w
        return (-z_new / d_new, d_new, v_new, z_new)
    new_w, new_d, new_v, new_z = apply_nary(
        fn, [weight, grad, d, v, z], n_out=4, name="ftml_update")
    d._set_data(new_d._data)
    v._set_data(new_v._data)
    z._set_data(new_z._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def adamax_update(weight, grad, mean, var, lr, beta1=0.9, beta2=0.999,
                  epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                  out=None):
    """lr is expected pre-bias-corrected (lr_t = lr / (1 - beta1^t)),
    matching the reference op contract."""
    def fn(w, g, m, u):
        g = _prep_grad(g, w, wd, rescale_grad, clip_gradient)
        m_new = beta1 * m + (1 - beta1) * g
        u_new = jnp.maximum(beta2 * u, jnp.abs(g))
        return (w - lr * m_new / (u_new + epsilon), m_new, u_new)
    new_w, new_m, new_u = apply_nary(fn, [weight, grad, mean, var], n_out=3,
                                     name="adamax_update")
    mean._set_data(new_m._data)
    var._set_data(new_u._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


_NADAM_SCHED = {}   # (beta1, schedule_decay) -> (mus, cumprods); cumprods[i]
                    # = prod mu_1..mu_i, extended lazily as t grows


def _nadam_schedule(beta1, schedule_decay, t):
    mus, cum = _NADAM_SCHED.setdefault((beta1, schedule_decay),
                                       ([None], [1.0]))
    while len(mus) <= t + 1:
        i = len(mus)
        mu = beta1 * (1 - 0.5 * 0.96 ** (i * schedule_decay))
        mus.append(mu)
        cum.append(cum[-1] * mu)
    return mus[t], mus[t + 1], cum[t], cum[t] * mus[t + 1]


@_register
def nadam_update(weight, grad, mean, var, lr, t, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, wd=0.0,
                 rescale_grad=1.0, clip_gradient=-1.0, out=None):
    """Nesterov Adam (reference python optimizer.Nadam semantics). The
    bias correction uses the CUMULATIVE momentum-schedule product
    m_schedule = prod_i mu_i, not just the current step's mu_t; the
    products are cached per (beta1, schedule_decay) and extended
    incrementally, so step t costs O(1) host work in a training loop."""
    mu_t, mu_tp1, m_schedule, m_schedule_next = _nadam_schedule(
        beta1, schedule_decay, t)

    def fn(w, g, m, v):
        g = _prep_grad(g, w, wd, rescale_grad, clip_gradient)
        m_new = beta1 * m + (1 - beta1) * g
        v_new = beta2 * v + (1 - beta2) * jnp.square(g)
        g_hat = g / (1 - m_schedule)
        m_hat = m_new / (1 - m_schedule_next)
        v_hat = v_new / (1 - beta2 ** t)
        m_bar = (1 - mu_t) * g_hat + mu_tp1 * m_hat
        return (w - lr * m_bar / (jnp.sqrt(v_hat) + epsilon), m_new, v_new)
    new_w, new_m, new_v = apply_nary(fn, [weight, grad, mean, var], n_out=3,
                                     name="nadam_update")
    mean._set_data(new_m._data)
    var._set_data(new_v._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def lamb_update_phase1(weight, grad, mean, var, t, beta1=0.9, beta2=0.999,
                       epsilon=1e-6, bias_correction=True, wd=0.0,
                       rescale_grad=1.0, clip_gradient=-1.0):
    """Phase 1 of the two-phase LAMB update: returns the raw layer update
    direction g' (the trust-ratio scaling happens in phase 2). Mutates
    mean/var in place like the reference op."""
    def fn(w, g, m, v):
        g = g * rescale_grad
        if clip_gradient >= 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        m_new = beta1 * m + (1 - beta1) * g
        v_new = beta2 * v + (1 - beta2) * jnp.square(g)
        if bias_correction:
            m_hat = m_new / (1 - beta1 ** t)
            v_hat = v_new / (1 - beta2 ** t)
        else:
            m_hat, v_hat = m_new, v_new
        return (m_hat / (jnp.sqrt(v_hat) + epsilon) + wd * w, m_new, v_new)
    g_out, new_m, new_v = apply_nary(fn, [weight, grad, mean, var], n_out=3,
                                     name="lamb_update_phase1")
    mean._set_data(new_m._data)
    var._set_data(new_v._data)
    return g_out


@_register
def lamb_update_phase2(weight, g, r1, r2, lr, lower_bound=-1.0,
                       upper_bound=-1.0, out=None):
    """Phase 2: apply the trust ratio r1/r2 (weight norm / update norm);
    a zero norm on either side means ratio 1 (reference semantics)."""
    def fn(w, gg, rr1, rr2):
        rr1 = rr1.reshape(())
        rr2 = rr2.reshape(())
        if lower_bound > 0:
            rr1 = jnp.maximum(rr1, lower_bound)
        if upper_bound > 0:
            rr1 = jnp.minimum(rr1, upper_bound)
        ratio = jnp.where((rr1 > 0) & (rr2 > 0), rr1 / rr2, 1.0)
        return w - lr * ratio * gg
    new_w = apply_nary(fn, [weight, g, _nd(r1, weight), _nd(r2, weight)],
                       name="lamb_update_phase2")
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def mp_sgd_update(weight, grad, weight32, lr, wd=0.0, rescale_grad=1.0,
                  clip_gradient=-1.0, out=None):
    """Mixed-precision SGD: the master fp32 copy carries the update; the
    low-precision weight is the cast of it (reference mp_sgd_update)."""
    def fn(w, g, w32):
        g = _prep_grad(g.astype(jnp.float32), w32, wd, rescale_grad,
                       clip_gradient)
        w32_new = w32 - lr * g
        return (w32_new.astype(w.dtype), w32_new)
    new_w, new_w32 = apply_nary(fn, [weight, grad, weight32], n_out=2,
                                name="mp_sgd_update")
    weight32._set_data(new_w32._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def mp_sgd_mom_update(weight, grad, mom, weight32, lr, momentum=0.0, wd=0.0,
                      rescale_grad=1.0, clip_gradient=-1.0, out=None):
    def fn(w, g, m, w32):
        g = _prep_grad(g.astype(jnp.float32), w32, wd, rescale_grad,
                       clip_gradient)
        m_new = momentum * m - lr * g
        w32_new = w32 + m_new
        return (w32_new.astype(w.dtype), m_new, w32_new)
    new_w, new_m, new_w32 = apply_nary(fn, [weight, grad, mom, weight32],
                                       n_out=3, name="mp_sgd_mom_update")
    mom._set_data(new_m._data)
    weight32._set_data(new_w32._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def mp_nag_mom_update(weight, grad, mom, weight32, lr, momentum=0.0, wd=0.0,
                      rescale_grad=1.0, clip_gradient=-1.0, out=None):
    def fn(w, g, m, w32):
        g = _prep_grad(g.astype(jnp.float32), w32, wd, rescale_grad,
                       clip_gradient)
        m_new = momentum * m + g
        w32_new = w32 - lr * (g + momentum * m_new)
        return (w32_new.astype(w.dtype), m_new, w32_new)
    new_w, new_m, new_w32 = apply_nary(fn, [weight, grad, mom, weight32],
                                       n_out=3, name="mp_nag_mom_update")
    mom._set_data(new_m._data)
    weight32._set_data(new_w32._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


@_register
def mp_lamb_update_phase1(weight, grad, mean, var, weight32, t, beta1=0.9,
                          beta2=0.999, epsilon=1e-6, bias_correction=True,
                          wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """fp32-master LAMB phase 1: statistics and direction in fp32."""
    def fn(w, g, m, v, w32):
        g = g.astype(jnp.float32) * rescale_grad
        if clip_gradient >= 0:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        m_new = beta1 * m + (1 - beta1) * g
        v_new = beta2 * v + (1 - beta2) * jnp.square(g)
        if bias_correction:
            m_hat = m_new / (1 - beta1 ** t)
            v_hat = v_new / (1 - beta2 ** t)
        else:
            m_hat, v_hat = m_new, v_new
        return (m_hat / (jnp.sqrt(v_hat) + epsilon) + wd * w32,
                m_new, v_new)
    g_out, new_m, new_v = apply_nary(
        fn, [weight, grad, mean, var, weight32], n_out=3,
        name="mp_lamb_update_phase1")
    mean._set_data(new_m._data)
    var._set_data(new_v._data)
    return g_out


@_register
def mp_lamb_update_phase2(weight, g, r1, r2, weight32, lr, lower_bound=-1.0,
                          upper_bound=-1.0, out=None):
    def fn(w, gg, rr1, rr2, w32):
        rr1 = rr1.reshape(())
        rr2 = rr2.reshape(())
        if lower_bound > 0:
            rr1 = jnp.maximum(rr1, lower_bound)
        if upper_bound > 0:
            rr1 = jnp.minimum(rr1, upper_bound)
        ratio = jnp.where((rr1 > 0) & (rr2 > 0), rr1 / rr2, 1.0)
        w32_new = w32 - lr * ratio * gg
        return (w32_new.astype(w.dtype), w32_new)
    new_w, new_w32 = apply_nary(
        fn, [weight, g, _nd(r1, weight), _nd(r2, weight), weight32],
        n_out=2, name="mp_lamb_update_phase2")
    weight32._set_data(new_w32._data)
    target = out if out is not None else weight
    target._set_data(new_w._data)
    return target


# ======================================================================
# multi-tensor utility ops (reference: src/operator/contrib/multi_*.cc,
# all_finite.cc — the LARS/AMP support kernels)
# ======================================================================

@_register
def all_finite(data, init_output=True):
    """1.0 if every element is finite (reference all_finite.cc; the AMP
    dynamic-loss-scaler check)."""
    return apply_nary(
        lambda d: jnp.all(jnp.isfinite(d)).astype(jnp.float32).reshape(1),
        [data], name="all_finite")


@_register
def multi_all_finite(*arrays, num_arrays=None, init_output=True):
    if num_arrays is not None and num_arrays != len(arrays):
        raise MXNetError(f"multi_all_finite: num_arrays {num_arrays} != "
                         f"{len(arrays)} inputs")
    def fn(*ds):
        ok = jnp.ones((), jnp.bool_)
        for d in ds:
            ok = ok & jnp.all(jnp.isfinite(d))
        return ok.astype(jnp.float32).reshape(1)
    return apply_nary(fn, list(arrays), name="multi_all_finite")


@_register
def multi_sum_sq(*arrays, num_arrays=None):
    """Per-array sum of squares, one fused launch (reference
    multi_sum_sq.cc — feeds multi_lars). Returns shape (n,)."""
    if num_arrays is not None and num_arrays != len(arrays):
        raise MXNetError(f"multi_sum_sq: num_arrays {num_arrays} != "
                         f"{len(arrays)} inputs")
    def fn(*ds):
        return jnp.stack([jnp.sum(jnp.square(d.astype(jnp.float32)))
                          for d in ds])
    return apply_nary(fn, list(arrays), name="multi_sum_sq")


@_register
def multi_lars(lrs, weights_sum_sq, grads_sum_sq, wds, eta=0.001,
               eps=1e-8, rescale_grad=1.0):
    """LARS trust-ratio layer-wise lr scaling (reference multi_lars.cc):
    lr_i *= eta*||w||/(||g||*rescale + wd*||w|| + eps), identity when
    either norm is zero."""
    def fn(lr, wss, gss, wd):
        wn = jnp.sqrt(wss)
        gn = jnp.sqrt(gss) * rescale_grad
        ratio = eta * wn / (gn + wd * wn + eps)
        return jnp.where((wn > 0) & (gn > 0), lr * ratio, lr)
    return apply_nary(fn, [lrs, _nd(weights_sum_sq, lrs),
                           _nd(grads_sum_sq, lrs), _nd(wds, lrs)],
                      name="multi_lars")


@_register
def amp_cast(data, dtype):
    """AMP-inserted cast (reference src/operator/tensor/amp_cast.cc)."""
    dt = _dtype_of(dtype)
    return apply_nary(lambda d: d.astype(dt), [data], name="amp_cast")


@_register
def amp_multicast(*data, num_outputs=None, cast_narrow=False):
    """Cast all inputs to their widest (or narrowest) floating dtype."""
    if num_outputs is not None and num_outputs != len(data):
        raise MXNetError(f"amp_multicast: num_outputs {num_outputs} != "
                         f"{len(data)} inputs")
    dts = [d.data.dtype for d in data]
    key = (lambda t: jnp.finfo(t).bits) if not cast_narrow else \
        (lambda t: -jnp.finfo(t).bits)
    target = _builtins.max(dts, key=key)   # `max` is the reduction op here
    def fn(*ds):
        return tuple(d.astype(target) for d in ds)
    return apply_nary(fn, list(data), n_out=len(data),
                      name="amp_multicast")


@_register
def moments(data, axes=None, keepdims=False):
    """(mean, variance) in one op (reference src/operator/nn/moments.cc)."""
    ax = tuple(axes) if isinstance(axes, (list, tuple)) else axes
    def fn(d):
        mu = jnp.mean(d, axis=ax, keepdims=keepdims)
        var = jnp.var(d, axis=ax, keepdims=keepdims)
        return (mu, var)
    return apply_nary(fn, [data], n_out=2, name="moments")


# ======================================================================
# preloaded multi-sgd (reference src/operator/contrib/preloaded_multi_sgd.cc
# — lrs/wds live on device as tensors, one launch updates many weights)
# ======================================================================

def _preloaded_multi(name, step, n_per_weight, mutated_idx):
    """Build a preloaded_multi_* op. All n weight-groups update in ONE
    apply_nary dispatch (one traced graph XLA fuses into one launch) with
    lrs/wds consumed in-graph — no per-weight host indexing or sync.
    ``step`` maps one group's raw arrays to the new values of the arrays
    at ``mutated_idx`` within the group."""
    def op(*data, rescale_grad=1.0, clip_gradient=-1.0, momentum=0.0,
           num_weights=None):
        n = num_weights if num_weights is not None else \
            (len(data) - 2) // n_per_weight
        if len(data) != n * n_per_weight + 2:
            raise MXNetError(
                f"{name}: expected {n}*{n_per_weight}+2 arrays "
                f"(groups + lrs + wds), got {len(data)}")
        groups = [data[i * n_per_weight:(i + 1) * n_per_weight]
                  for i in range(n)]
        lrs, wds = data[-2], data[-1]

        def fn(*arrs):
            flat, lr_a, wd_a = arrs[:-2], arrs[-2], arrs[-1]
            outs = []
            for i in range(n):
                grp = flat[i * n_per_weight:(i + 1) * n_per_weight]
                outs.extend(step(grp, lr_a[i], wd_a[i], rescale_grad,
                                 clip_gradient, momentum))
            return tuple(outs)

        flat_in = [a for grp in groups for a in grp] + [lrs, wds]
        n_out = n * len(mutated_idx)
        results = apply_nary(fn, flat_in, n_out=n_out, name=name)
        if n_out == 1:
            results = [results]
        k = 0
        for grp in groups:
            for j in mutated_idx:
                grp[j]._set_data(results[k]._data)
                k += 1
        return [grp[0] for grp in groups]
    op.__name__ = name
    op.__doc__ = (f"{name} — reference contrib/preloaded_multi_sgd.cc; "
                  "lrs/wds are device tensors indexed per weight, the "
                  "whole update is one fused dispatch.")
    return _register(op)


def _plain_sgd_step(grp, lr, wd, rescale, clip, momentum):
    w, g = grp
    g = _prep_grad(g, w, wd, rescale, clip)
    return (w - lr * g,)


def _mom_sgd_step(grp, lr, wd, rescale, clip, momentum):
    w, g, m = grp
    g = _prep_grad(g, w, wd, rescale, clip)
    m_new = momentum * m - lr * g
    return (w + m_new, m_new)


def _mp_sgd_step(grp, lr, wd, rescale, clip, momentum):
    w, g, w32 = grp
    g = _prep_grad(g.astype(jnp.float32), w32, wd, rescale, clip)
    w32_new = w32 - lr * g
    return (w32_new.astype(w.dtype), w32_new)


def _mp_mom_sgd_step(grp, lr, wd, rescale, clip, momentum):
    w, g, m, w32 = grp
    g = _prep_grad(g.astype(jnp.float32), w32, wd, rescale, clip)
    m_new = momentum * m - lr * g
    w32_new = w32 + m_new
    return (w32_new.astype(w.dtype), m_new, w32_new)


preloaded_multi_sgd_update = _preloaded_multi(
    "preloaded_multi_sgd_update", _plain_sgd_step, 2, (0,))
preloaded_multi_sgd_mom_update = _preloaded_multi(
    "preloaded_multi_sgd_mom_update", _mom_sgd_step, 3, (0, 2))
preloaded_multi_mp_sgd_update = _preloaded_multi(
    "preloaded_multi_mp_sgd_update", _mp_sgd_step, 3, (0, 2))
preloaded_multi_mp_sgd_mom_update = _preloaded_multi(
    "preloaded_multi_mp_sgd_mom_update", _mp_mom_sgd_step, 4, (0, 2, 3))


# ======================================================================
# legacy structured ops
# ======================================================================

@_register
def choose_element_0index(data, index, axis=1, keepdims=False):
    """Pick one element per row by index (reference legacy op; alias of
    pick with the row axis)."""
    return pick(data, index, axis=axis, keepdims=keepdims)


@_register
def fill_element_0index(lhs, mhs, rhs):
    """lhs[i, rhs[i]] = mhs[i] per row (reference legacy op)."""
    def fn(l, m, r):
        rows = jnp.arange(l.shape[0])
        return l.at[rows, r.astype(jnp.int32)].set(m)
    return apply_nary(fn, [lhs, _nd(mhs, lhs), _nd(rhs, lhs)],
                      name="fill_element_0index")


@_register
def SpatialTransformer(data, loc, target_shape=None,
                       transform_type="affine", sampler_type="bilinear",
                       cudnn_off=None):
    """Affine spatial transformer = GridGenerator + BilinearSampler
    (reference src/operator/spatial_transformer.cc)."""
    if transform_type != "affine" or sampler_type != "bilinear":
        raise MXNetError("SpatialTransformer supports affine/bilinear "
                         "(reference supports exactly these too)")
    grid = GridGenerator(loc, transform_type="affine",
                         target_shape=target_shape)
    return BilinearSampler(data, grid)


@_register
def IdentityAttachKLSparseReg(data, sparseness_target=0.1, penalty=0.001,
                              momentum=0.9):
    """Identity forward; backward adds the KL sparsity penalty gradient
    pushing mean activation toward sparseness_target (reference
    src/operator/identity_attach_KL_sparse_reg.cc).

    ``momentum`` is accepted for API compatibility and has no effect: the
    reference keeps a momentum-smoothed moving average of the activation
    in auxiliary op state; this functional op has no cross-call state, so
    rho is the current batch mean (equivalent to momentum=0)."""
    t = sparseness_target

    @jax.custom_vjp
    def fwd(d):
        return d

    def fwd_fwd(d):
        return d, d

    def fwd_bwd(d, g):
        rho = jnp.clip(jnp.mean(d, axis=0, keepdims=True), 1e-6, 1 - 1e-6)
        kl_grad = penalty * (-t / rho + (1 - t) / (1 - rho))
        return (g + jnp.broadcast_to(kl_grad, g.shape) / d.shape[0],)

    fwd.defvjp(fwd_fwd, fwd_bwd)
    return apply_nary(fwd, [data], name="IdentityAttachKLSparseReg")


# ======================================================================
# Round-4 registry tail: remaining sample_* distributions, multi-tensor
# mixed-precision updates, legacy utility ops
# ======================================================================

def _gamma_poisson(key_gamma, key_poisson, gshape, gscale, out_shape, dtype):
    """NB sampling via the Gamma-Poisson mixture: lam ~ Gamma(shape, scale)
    then x ~ Poisson(lam) — the standard reparameterization (reference
    draws NB directly in src/operator/random/sampler.h; the mixture is
    exactly the same marginal and maps onto jax primitives)."""
    lam = jax.random.gamma(key_gamma, gshape, out_shape) * gscale
    draws = jax.random.poisson(key_poisson, lam, shape=out_shape)
    return draws.astype(_dtype_of(dtype) if dtype else jnp.float32)


@_register
def sample_negative_binomial(k, p, shape=None, dtype=None, ctx=None):
    """Per-element NB(k successes, success prob p) draws (reference
    sample_negative_binomial in src/operator/random/multisample_op.cc);
    counts failures before the k-th success, mean k*(1-p)/p."""
    from . import random as _rnd
    k = _nd(k)
    p = _nd(p, k)
    out_shape = _sample_shape(k.shape, shape)

    def fn(kk, pp):
        bshape = kk.shape + (1,) * (len(out_shape) - kk.ndim)
        kb = jnp.broadcast_to(kk.reshape(bshape), out_shape)
        pb = jnp.broadcast_to(pp.reshape(bshape), out_shape)
        return _gamma_poisson(_rnd.next_key(), _rnd.next_key(),
                              kb, (1.0 - pb) / jnp.maximum(pb, 1e-12),
                              out_shape, dtype)

    return apply_nary(fn, [k, p], name="sample_negative_binomial")


@_register
def sample_generalized_negative_binomial(mu, alpha, shape=None, dtype=None,
                                         ctx=None):
    """Per-element generalized NB(mean mu, dispersion alpha) draws
    (reference sample_generalized_negative_binomial): equivalent to
    NB with k = 1/alpha, p = 1/(1 + mu*alpha)."""
    from . import random as _rnd
    mu = _nd(mu)
    alpha = _nd(alpha, mu)
    out_shape = _sample_shape(mu.shape, shape)

    def fn(m, a):
        bshape = m.shape + (1,) * (len(out_shape) - m.ndim)
        mb = jnp.broadcast_to(m.reshape(bshape), out_shape)
        ab = jnp.broadcast_to(a.reshape(bshape), out_shape)
        ab = jnp.maximum(ab, 1e-12)
        return _gamma_poisson(_rnd.next_key(), _rnd.next_key(),
                              1.0 / ab, mb * ab, out_shape, dtype)

    return apply_nary(fn, [mu, alpha], name="sample_generalized_"
                                            "negative_binomial")


@_register
def multi_mp_sgd_update(*arrays, lrs, wds, rescale_grad=1.0,
                        clip_gradient=None, num_weights=None, out=None):
    """Fused group mixed-precision SGD: arrays = (w0, g0, w32_0, ...).
    The fp32 master weight carries the update; the low-precision weight
    is its cast (reference optimizer_op.cc multi_mp_sgd_update)."""
    groups = _group_pairs(list(arrays), 3)
    _check_num_weights("multi_mp_sgd_update", groups, num_weights)

    def fn(*flat):
        outs = []
        for i in range(0, len(flat), 3):
            w, g, w32 = flat[i], flat[i + 1], flat[i + 2]
            lr, wd = lrs[i // 3], wds[i // 3]
            g32 = g.astype(jnp.float32) * rescale_grad
            if clip_gradient is not None and clip_gradient >= 0:
                g32 = jnp.clip(g32, -clip_gradient, clip_gradient)
            new32 = w32 - lr * (g32 + wd * w32)
            outs.append(new32.astype(w.dtype))
            outs.append(new32)
        return tuple(outs)

    updated = apply_nary(fn, list(arrays), n_out=2 * len(groups),
                         name="multi_mp_sgd_update")
    for gi, (w, _, w32) in enumerate(groups):
        w._set_data(updated[2 * gi].data)
        w32._set_data(updated[2 * gi + 1].data)
    return [updated[2 * i] for i in range(len(groups))]


@_register
def multi_mp_sgd_mom_update(*arrays, lrs, wds, momentum=0.9,
                            rescale_grad=1.0, clip_gradient=None,
                            num_weights=None, out=None):
    """Fused group mixed-precision SGD+momentum: arrays =
    (w0, g0, mom0, w32_0, ...); momentum and master weight stay fp32
    (reference multi_mp_sgd_mom_update)."""
    groups = _group_pairs(list(arrays), 4)
    _check_num_weights("multi_mp_sgd_mom_update", groups, num_weights)

    def fn(*flat):
        outs = []
        for i in range(0, len(flat), 4):
            w, g, m, w32 = flat[i], flat[i + 1], flat[i + 2], flat[i + 3]
            lr, wd = lrs[i // 4], wds[i // 4]
            g32 = g.astype(jnp.float32) * rescale_grad
            if clip_gradient is not None and clip_gradient >= 0:
                g32 = jnp.clip(g32, -clip_gradient, clip_gradient)
            new_m = momentum * m - lr * (g32 + wd * w32)
            new32 = w32 + new_m
            outs.append(new32.astype(w.dtype))
            outs.append(new_m)
            outs.append(new32)
        return tuple(outs)

    updated = apply_nary(fn, list(arrays), n_out=3 * len(groups),
                         name="multi_mp_sgd_mom_update")
    for gi, (w, _, m, w32) in enumerate(groups):
        w._set_data(updated[3 * gi].data)
        m._set_data(updated[3 * gi + 1].data)
        w32._set_data(updated[3 * gi + 2].data)
    return [updated[3 * i] for i in range(len(groups))]


@_register
def reset_arrays(*arrays, num_arrays=None):
    """Zero every array in place in one dispatch (reference
    contrib/reset_arrays.cc — gradient clearing for grad_req='add')."""
    if num_arrays is not None and num_arrays != len(arrays):
        raise MXNetError(f"reset_arrays: num_arrays {num_arrays} != "
                         f"{len(arrays)} arrays passed")
    for a in arrays:
        a._set_data(jnp.zeros_like(a.data))
    return None


@_register
def one_hot_encode(indices, out):
    """Legacy one-hot writer: out[i, indices[i]] = 1, everything else 0
    (reference mx.nd.onehot_encode / ndarray_function.cc OnehotEncode).
    ``out`` supplies the class count and receives the result in place."""
    if out.ndim != 2 or indices.ndim != 1:
        raise MXNetError("one_hot_encode expects indices (N,), out (N, C)")
    n, c = out.shape
    if indices.shape[0] != n:
        raise MXNetError(f"one_hot_encode: indices length "
                         f"{indices.shape[0]} != out rows {n}")

    def fn(idx):
        return jax.nn.one_hot(idx.astype(jnp.int32), c,
                              dtype=_dtype_of(out.dtype))

    res = apply_nary(fn, [indices], name="one_hot_encode")
    out._set_data(res.data)
    return out


onehot_encode = one_hot_encode
__all__.append("onehot_encode")


# ======================================================================
# latent attention and the dropless expert layer (no reference
# counterpart; gluon.model_zoo.nlp.deepseek_v3 is built from these, and
# amp/lists.py says which of them run in float32 and which in bfloat16)
# ======================================================================

@_register
def rms_norm(data, weight, eps=1e-5):
    """Root-mean-square norm over the last axis, reduced in float32
    (``ops.norm_rope.rms_norm``, which the serving engine shares)."""
    from ..ops.norm_rope import rms_norm as _rms
    return apply_nary(lambda d, w: _rms(d, w, eps), [data, weight],
                      name="rms_norm")


@_register
def moe_router(data, weight, bias=None, top_k=1, routed_scaling_factor=1.0,
               norm_topk_prob=True, scoring_func="sigmoid"):
    """Top-k router.  data: (..., d); weight: (E, d).  ``scoring_func``
    ``"sigmoid"`` (``parallel.moe.route_sigmoid_top_k``): bias (E,) is added
    for the selection only, the weights are scaled by
    ``routed_scaling_factor``; ``"softmax"``
    (``parallel.moe.route_softmax_top_k``): no bias, no scale.  Returns
    ``[experts, weights]``, both (..., top_k) float32: the ids of the chosen
    experts (whole numbers in a float array, as ``topk`` gives its indices,
    so that the tape can carry them) and their combine weights."""
    from ..parallel.moe import route_sigmoid_top_k, route_softmax_top_k
    if scoring_func not in ("sigmoid", "softmax") or \
            (scoring_func == "sigmoid") != (bias is not None):
        raise MXNetError(f"moe_router: scoring_func {scoring_func!r} "
                         f"{'without' if bias is None else 'with'} a bias")

    def fn(d, w, *b):
        flat = d.reshape(-1, d.shape[-1])
        if b:
            experts, gates = route_sigmoid_top_k(
                flat, w, b[0], top_k, scale=routed_scaling_factor,
                norm_topk_prob=norm_topk_prob)
        else:
            experts, gates = route_softmax_top_k(
                flat, w, top_k, norm_topk_prob=norm_topk_prob)
        lead = d.shape[:-1] + (top_k,)
        return (experts.astype(jnp.float32).reshape(lead),
                gates.reshape(lead))
    return apply_nary(fn, [data, weight] + ([] if bias is None else [bias]),
                      n_out=2, name="moe_router")


@_register
def moe_experts(data, experts, weights, w_gate, w_up, w_down,
                expert_offset=0, fixed_rows=None):
    """The held experts' part of a dropless SwiGLU expert layer
    (``parallel.moe.dropless_moe_apply``).  data: (..., d); experts, weights:
    (..., k) from ``moe_router`` (``amp`` leaves both as they arrive:
    ``lists.KEEP_DTYPE_ARGS``); w_gate, w_up: (held, d, h); w_down:
    (held, h, d), the experts ``expert_offset .. expert_offset + held``;
    ``fixed_rows`` a fixed amount of work (``dropless_moe_apply``'s)."""
    from ..parallel.moe import dropless_moe_apply

    def fn(d, e, g, wg, wu, wd):
        k = e.shape[-1]
        out = dropless_moe_apply(
            d.reshape(-1, d.shape[-1]), e.reshape(-1, k).astype(jnp.int32),
            g.reshape(-1, k), wg, wu, wd, expert_offset=expert_offset,
            fixed_rows=fixed_rows)
        return out.reshape(d.shape)
    return apply_nary(fn, [data, experts, weights, w_gate, w_up, w_down],
                      name="moe_experts")


@_register
def mla_attention(q, kv, k_pe, num_heads=1, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
                  use_nope=False):
    """Causal multi-head latent attention in its expanded (training) form.

    q: (B, T, H * (nope + rope)), a head ``[q_nope | q_pe]``; kv:
    (B, T, H * (nope + v)), a head ``[k_nope | v]``, the up-projection of
    the normalised latent; k_pe: (B, T, rope), one rotary key shared by all
    heads.  RoPE (interleaved pairs) on ``q_pe`` and ``k_pe`` — none with
    ``use_nope`` (``mla_use_nope``: the ``rope`` dims stay, no position enters)
    — then ``softmax(q k^T (nope + rope)^-1/2) v`` through
    ``flash_attention`` with Q and K at ``nope + rope`` and V at ``v``.
    Returns (B, T, H * v)."""
    from ..ops.flash_attention import flash_attention
    from ..ops.norm_rope import rope_interleaved as _rot_interleaved
    from .. import telemetry as _telem
    h, nope, rope, dv = num_heads, qk_nope_head_dim, qk_rope_head_dim, \
        v_head_dim

    def fn(qd, kvd, ped):
        b, t = qd.shape[0], qd.shape[1]
        with jax.named_scope("mla.attention"):
            qd = qd.reshape(b, t, h, nope + rope).transpose(0, 2, 1, 3)
            kvd = kvd.reshape(b, t, h, nope + dv).transpose(0, 2, 1, 3)
            if use_nope:
                _telem.inc("mla.nope")
                query, k_pe = qd, ped[:, None]
            else:
                ang = jnp.arange(t, dtype=jnp.float32)[:, None] * \
                    rope_theta ** (-jnp.arange(0, rope, 2,
                                               dtype=jnp.float32) / rope)
                cos, sin = jnp.cos(ang), jnp.sin(ang)         # (t, rope/2)
                q_pe = _rot_interleaved(qd[..., nope:], cos, sin)
                k_pe = _rot_interleaved(ped[:, None], cos, sin)  # (b, 1, t, r)
                query = jnp.concatenate(
                    [qd[..., :nope], q_pe.astype(qd.dtype)], axis=-1)
            key = jnp.concatenate(
                [kvd[..., :nope],
                 jnp.broadcast_to(k_pe.astype(kvd.dtype),
                                  (b, h, t, rope))], axis=-1)
            out = flash_attention(query, key, kvd[..., nope:], causal=True,
                                  sm_scale=(nope + rope) ** -0.5)
            return out.transpose(0, 2, 1, 3).reshape(b, t, h * dv)
    return apply_nary(fn, [q, kv, k_pe], name="mla_attention")


@_register
def sparse_gq_attention(q, k, v, q_index, k_index, x_index, w_index,
                        positions=None, num_heads=1, topk=2048,
                        rope_theta=10000.0, mrope_section=None):
    """Causal grouped-query attention over the ``topk`` keys a learned
    indexer selects for each query, and the indexer's alignment loss
    (``ops.sparse_attention``).

    q: (B, T, H * d) with ``H = num_heads``; k, v: (B, T, Hkv * d) (the
    key-value heads are what their width holds of ``d``), per-head norms
    applied, not yet rotated; q_index: (B, T, HI * dI); k_index: (B, T, dI); x_index: (B, T,
    hidden), the layer's normalised input without a gradient, and w_index:
    (HI, hidden), from which the index weights ``x_index @ w_index.T`` are
    taken here in float32 (``amp`` leaves both as they arrive); positions:
    (3, B, T) temporal / height / width, or None for text (all three the
    token's index).  Rotary in half-split pairs: q and k over ``d`` with the
    frequencies divided among the streams by ``mrope_section``, the index
    query and key over ``dI`` at the temporal position.  Returns ``[out (B,
    T, H * d), index_loss (B,)]``."""
    from ..ops.norm_rope import rope_half_split, sectioned_angles
    from ..ops.sparse_attention import sparse_gq_attention as _sparse
    h = num_heads

    def fn(qd, kd, vd, qid, kid, xd, wd, *pos):
        b, t = qd.shape[0], qd.shape[1]
        d, di = qd.shape[2] // h, kid.shape[2]
        hkv, hi = kd.shape[2] // d, wd.shape[0]
        with jax.named_scope("gqa.project"):
            p3 = pos[0] if pos else jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32), (3, b, t))
            ang = sectioned_angles(
                p3 if mrope_section else p3[0], d, rope_theta,
                tuple(mrope_section) if mrope_section else None)[:, None]
            ang_i = sectioned_angles(p3[0], di, rope_theta)[:, None]

            def heads(a, n, width, angles):     # (B, T, n * w) -> (B, n, T, w)
                a = a.reshape(b, t, n, width).transpose(0, 2, 1, 3)
                return rope_half_split(
                    a.astype(jnp.float32), jnp.cos(angles),
                    jnp.sin(angles)).astype(a.dtype)
            query, key = heads(qd, h, d, ang), heads(kd, hkv, d, ang)
            value = vd.reshape(b, t, hkv, d).transpose(0, 2, 1, 3)
            qi = heads(qid, hi, di, ang_i)
            ki = heads(kid, 1, di, ang_i)[:, 0]
            w = jnp.matmul(xd.astype(jnp.float32), wd.astype(jnp.float32).T,
                           precision=lax.Precision.HIGHEST)
        out, loss = _sparse(query, key, value, qi, ki, w, topk)
        return out.transpose(0, 2, 1, 3).reshape(b, t, h * d), loss
    return apply_nary(
        fn, [q, k, v, q_index, k_index, x_index, w_index]
        + ([] if positions is None else [positions]), n_out=2,
        name="sparse_gq_attention")


@_register
def block_diffusion_attention(q, k, v, num_heads=1, block_length=4,
                              rope_theta=10000.0):
    """Grouped-query attention of a block-diffusion training sequence, the
    noisy copy beside the clean one (``ops.flash_attention
    .block_diffusion_attention``: no (2T)^2 mask, the flash kernels under
    block rules, dead tiles skipped).

    q: (B, 2T, H * d) with ``H = num_heads``; k, v: (B, 2T, Hkv * d), per-head
    norms applied, not yet rotated; positions 0 .. T - 1 the noisy half,
    T .. 2T - 1 the clean one.  Both halves take the positions 0 .. T - 1
    for the rotary embedding (half-split pairs over all of ``d``).  A clean
    query sees the clean keys of its block and the blocks before it; a
    noisy query the clean keys of the blocks before its own and the noisy
    keys of its own block (``block_length`` tokens a block).  Returns (B,
    2T, H * d)."""
    from ..ops.flash_attention import block_diffusion_attention as _bd
    from ..ops.norm_rope import rope_half_split, sectioned_angles
    from .. import telemetry as _telem
    h = num_heads

    def fn(qd, kd, vd):
        b, t2 = qd.shape[0], qd.shape[1]
        d = qd.shape[2] // h
        hkv = kd.shape[2] // d
        _telem.inc("bd.layers")
        with jax.named_scope("gqa.project"):
            ang = sectioned_angles(jnp.arange(t2, dtype=jnp.int32) % (t2 // 2),
                                   d, rope_theta)

            def heads(a, n, rotate=True):       # (B, 2T, n d) -> (B n, 2T, d)
                a = a.reshape(b, t2, n, d).transpose(0, 2, 1, 3)
                if rotate:
                    a = rope_half_split(a.astype(jnp.float32), jnp.cos(ang),
                                        jnp.sin(ang)).astype(a.dtype)
                return a.reshape(b * n, t2, d)
            query, key = heads(qd, h), heads(kd, hkv)
            value = heads(vd, hkv, rotate=False)
        out = _bd(query, key, value, block_length, d ** -0.5)
        return out.reshape(b, h, t2, d).transpose(0, 2, 1, 3).reshape(
            b, t2, h * d)
    return apply_nary(fn, [q, k, v], name="block_diffusion_attention")


# ======================================================================
# Kimi Delta Attention (gluon.model_zoo.nlp.kimi_linear is built from
# these; amp/lists.py keeps the decay's path and beta float32)
# ======================================================================

@_register
def causal_conv1d(data, weight):
    """Causal depthwise convolution over time, then SiLU.  data: (B, T, C);
    weight: (C, K), a weight a channel and tap, no bias: ``y[t] = silu(sum_i
    w[:, i] x[t - K + 1 + i])`` with zeros before the sequence starts, so
    token ``t`` sees ``t - K + 1 .. t`` and no later one.  Taken in float32
    where the weight is (the result's dtype is the promotion of both)."""
    def fn(x, w):
        with jax.named_scope("kda.conv"):
            taps, t = w.shape[1], x.shape[1]
            dtype = jnp.result_type(x.dtype, w.dtype)
            # the shifted rows are cut from the input as it arrives and
            # widened a tap at a time: cut from a float32 copy of a bfloat16
            # input, the unaligned slices cost twice the bytes (4.4 against
            # 2.3 ms a call at (8192, 4096), forward and backward)
            padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
            y = _builtins.sum(
                padded[:, i:i + t].astype(dtype) * w[:, i].astype(dtype)
                for i in range(taps))
            return jax.nn.silu(y)
    return apply_nary(fn, [data, weight], name="causal_conv1d")


@_register
def kda_gate(f, b, a_log, dt_bias):
    """Kimi Delta Attention's decay and step size, float32.  f: (B, T, H *
    dk), the low-rank pair's output; b: (B, T, H); a_log: (H,); dt_bias: (H *
    dk,).  Returns ``[g, beta]``: ``g = -exp(a_log) softplus(f + dt_bias)``
    (B, T, H * dk), the log of the decay a channel, a head's ``a_log`` over
    its ``dk`` channels; ``beta = sigmoid(b)``."""
    def fn(fd, bd, al, db):
        with jax.named_scope("kda.gate"):
            f32 = jnp.float32
            heads = al.shape[0]
            rate = jnp.repeat(jnp.exp(al.astype(f32)), fd.shape[-1] // heads)
            g = -rate * jax.nn.softplus(fd.astype(f32) + db.astype(f32))
            return g, jax.nn.sigmoid(bd.astype(f32))
    return apply_nary(fn, [f, b, a_log, dt_bias], n_out=2, name="kda_gate")


@_register
def kda_attention(q, k, v, g, beta, num_heads=1):
    """Kimi Delta Attention's token mixer: the gated delta rule with a decay
    a key channel, ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} +
    beta_t k_t v_t^T``, ``o_t = S_t^T q_t dk^-1/2``, from a zero state at
    the start of every sequence, as a chunked scan
    (``ops.linear_attention``: the kernels on the chip, the same tiles under
    ``lax.scan`` elsewhere).

    q, k, g: (B, T, H * dk) with ``H = num_heads``; v: (B, T, H * dv); beta:
    (B, T, H); g and beta from ``kda_gate`` (``amp`` leaves both as they
    arrive: float32; a log-decay under -5.5 a token is taken as -5.5).  Each
    head of q and of k is divided by its 2-norm first (``x / sqrt(sum x^2 +
    1e-6)``), in float32 inside the scan's tiles.  Returns (B, T, H * dv)."""
    from ..ops.linear_attention import kda_attention as _kda
    from .. import telemetry as _telem
    h = num_heads

    def fn(qd, kd, vd, gd, bd):
        b, t = qd.shape[0], qd.shape[1]
        _telem.inc("kda.layers")

        def heads(a):
            return a.reshape(b, t, h, -1)
        with jax.named_scope("kda.scan"):
            out, _ = _kda(heads(qd), heads(kd), heads(vd), heads(gd), bd)
            return out.reshape(b, t, -1)
    return apply_nary(fn, [q, k, v, g, beta], name="kda_attention")
