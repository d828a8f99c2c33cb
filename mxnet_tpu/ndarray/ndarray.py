"""NDArray: the imperative tensor, wrapping ``jax.Array``.

TPU-native rebuild of the reference NDArray stack (SURVEY.md §2.1):
  - C++ core ``src/ndarray/ndarray.cc`` + ``include/mxnet/ndarray.h``
  - Python surface ``python/mxnet/ndarray/ndarray.py``

Architecture mapping (SURVEY.md §1 "key architectural idea"): in the reference,
every op is pushed to the dependency engine and the Python thread runs ahead;
here JAX/XLA's async dispatch plays that role — ops return immediately with
futures-like ``jax.Array`` values and ``wait_to_read``/``asnumpy`` are the sync
points (``jax.block_until_ready``).

MXNet semantic quirks preserved on purpose (tested against the contract in
tests/test_ndarray.py, modelled on reference tests/python/unittest/test_ndarray.py):
  - default dtype float32
  - in-place ops (``+=``, ``x[:] = v``) mutate the handle; forbidden on arrays
    that an open autograd tape depends on
  - ``reshape`` supports 0 (copy dim) and -1 (infer) codes
  - scalar ops broadcast like mx.nd (numpy-style here; mx.nd was stricter for
    elemwise — we accept the superset, broadcast_* aliases provided)
"""
from __future__ import annotations

import functools
import os
import warnings
import weakref

import numpy as _np
import jax
import jax.numpy as jnp

if os.environ.get("MXTPU_INT64", "") in ("1", "true"):
    # large-tensor mode (reference MXNET_INT64_TENSOR_SIZE build flag):
    # real int64/float64 instead of the 32-bit truncation below
    jax.config.update("jax_enable_x64", True)

#: weak registry of live NDArrays — waitall() blocks on their buffers
#: (reference engine WaitForAll semantics)
_LIVE_ARRAYS = weakref.WeakSet()

from ..base import MXNetError, numeric_types, integer_types
from ..context import Context, current_context, cpu
from .. import _tape
# use-after-donate sentinel (ISSUE 16): stdlib-only import; the host
# access points below gate on its module bool, so MXTPU_DONATION_CHECK=0
# costs one attribute read per access and changes nothing else
from ..lint import donation as _donation

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concat", "concatenate", "stack", "from_jax", "waitall",
           "eye", "linspace"]


def _dtype_of(dtype):
    if dtype is None:
        return jnp.float32
    if dtype == "bfloat16":
        return jnp.bfloat16
    dt = jnp.dtype(dtype)
    # without jax_enable_x64, 64-bit dtypes are narrowed; the warning is
    # value-aware (see _check_narrowing) — numpy makes every Linux int
    # array int64, so warning unconditionally would be pure noise
    if not jax.config.jax_enable_x64:
        if dt == jnp.dtype("int64"):
            return jnp.int32
        if dt == jnp.dtype("float64"):
            return jnp.float32
        if dt == jnp.dtype("uint64"):
            return jnp.uint32
    return dt


def _check_narrowing(np_arr):
    """Warn when 64-bit integer values actually exceed the 32-bit range
    they are about to be narrowed into (reference large-tensor mode:
    MXNET_INT64_TENSOR_SIZE build flag -> MXTPU_INT64=1 here)."""
    if jax.config.jax_enable_x64 or np_arr.size == 0:
        return
    if np_arr.dtype == _np.int64:
        if np_arr.max(initial=0) > 2**31 - 1 or \
                np_arr.min(initial=0) < -2**31:
            warnings.warn(
                "int64 values exceed the int32 range and will wrap; set "
                "MXTPU_INT64=1 for true 64-bit tensors", stacklevel=3)
    elif np_arr.dtype == _np.uint64:
        if np_arr.max(initial=0) > 2**32 - 1:
            warnings.warn(
                "uint64 values exceed the uint32 range and will wrap; set "
                "MXTPU_INT64=1 for true 64-bit tensors", stacklevel=3)


def _ndarray_from_numpy(host):
    """Unpickle target for NDArray.__reduce__ (module-level so pickle can
    resolve it by name; materializes on the unpickler's default device)."""
    import jax.numpy as jnp
    return NDArray(jnp.asarray(host))


class NDArray:
    """An n-dimensional array on a device context.

    Wraps a ``jax.Array`` (``self._data``). Mutation replaces the wrapped
    value — functional underneath, mutable-looking on top (SURVEY.md §7
    design stance).
    """

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_grad_stype",
                 "_node", "_out_index", "_grad_fresh", "_grad_reduced",
                 "_grad_of", "_grad_hooks",
                 "_io_batch",   # set by DevicePrefetcher alone: its batch
                 "__weakref__")

    # make NDArray win against numpy array in reflected ops
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None):
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        # the gradient array: None until a backward writes one or .grad
        # is read (attach_grad allocates nothing, see _tape.grad_array)
        self._grad = None
        self._grad_req = "null"
        self._grad_stype = None
        self._grad_fresh = False
        # True once the cross-worker sum ran for the CURRENT accumulated
        # gradient; re-armed whenever autograd writes fresh gradient data
        # (all_reduce_gradients must reduce once per cycle, grad_req='add')
        self._grad_reduced = False
        self._grad_of = None
        # {key: fn} grad-ready hooks (autograd.register_grad_ready_hook);
        # None until the first registration — the common case pays nothing
        self._grad_hooks = None
        self._node = None
        self._out_index = 0
        _LIVE_ARRAYS.add(self)

    def _sync_handles(self):
        """Buffers waitall() must block on (sparse overrides: no densify)."""
        return (self._data,)

    def __reduce__(self):
        """Pickle as host numpy (reference NDArrays pickle via their
        binary blob, python/mxnet/ndarray/ndarray.py __reduce__).  Device
        placement is process-local state: the unpickling process
        re-materializes on ITS default device — which is what DataLoader
        process workers need (host-only children, accelerator parent)."""
        import numpy as _host_np
        return (_ndarray_from_numpy, (_host_np.asarray(self._data),))

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        if _donation._ENABLED:
            _donation.touch(self._data, "shape")
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype) if self._data.dtype != jnp.bfloat16 \
            else self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(_np.prod(self.shape)) if self.shape else 1

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def grad(self):
        g = _tape.grad_array(self)
        if g is None:
            return None
        if isinstance(g, NDArray):
            # row_sparse grad (sparse_grad=True path): returned directly,
            # stype preserved for the optimizer's lazy update
            return g
        out = NDArray(g, self._ctx)
        # the wrapper is a live view: in-place mutation of it (clip, scale)
        # writes back to the owner's gradient buffer (see _set_data), so
        # idioms like clip_global_norm([p.grad() ...]) take effect
        out._grad_of = self
        return out

    @property
    def data(self):
        """The underlying jax.Array (TPU-native accessor, not in reference)."""
        return self._data

    # ------------------------------------------------------------------
    # autograd surface (reference: python/mxnet/ndarray/ndarray.py)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        _tape.mark_variable(self, grad_req, stype=stype)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _tape.backward([self], [out_grad] if out_grad is not None else None,
                       retain_graph=retain_graph, train_mode=train_mode)

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def _check_mutable(self):
        if self._node is not None and _tape.is_recording():
            raise MXNetError(
                "in-place mutation of an NDArray produced inside an active "
                "autograd.record() scope is not supported on the TPU rebuild "
                "(the functional tape cannot observe it); use out-of-place "
                "ops or detach() first")

    def _set_data(self, new_data):
        self._check_mutable()
        self._data = new_data
        self._node = None
        self._out_index = 0
        if self._grad_of is not None:
            self._grad_of._grad = new_data

    # ------------------------------------------------------------------
    # conversion & sync points
    # ------------------------------------------------------------------
    def asnumpy(self):
        """Sync point: reference MXNDArraySyncCopyToCPU → WaitForVar."""
        if _donation._ENABLED:
            _donation.touch(self._data, "asnumpy")
        from ..testing import faults as _faults
        _faults.fault_point("ndarray.d2h")
        return _np.asarray(jax.device_get(self._data))

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        jax.block_until_ready(self._data)
        return self

    def astype(self, dtype, copy=True):
        return _apply1(self, lambda d: d.astype(_dtype_of(dtype)))

    def as_in_context(self, ctx):
        """Device copy: reference CopyFromTo (src/ndarray/ndarray.cc)."""
        ctx = Context(ctx) if not isinstance(ctx, Context) else ctx
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._set_data(jax.device_put(self._data, other._ctx.jax_device))
            return other
        ctx = Context(other) if not isinstance(other, Context) else other
        out = NDArray(jax.device_put(self._data, ctx.jax_device), ctx)
        # copies stay differentiable (CopyFromTo registers identity grad)
        if _tape.is_recording() and _tape and (self._node is not None
                                               or self._grad_req != "null"):
            outs, node = _tape.apply_op(lambda d: d, [self], name="copyto")
            out._data = outs[0]
            _attach(out, node, 0)
        return out

    def copy(self):
        return self.copyto(self._ctx)

    def tolist(self):
        return self.asnumpy().tolist()

    # ------------------------------------------------------------------
    # shape manipulation
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if kwargs.get("shape"):
            shape = tuple(kwargs["shape"])
        new_shape = _resolve_reshape(self.shape, shape)
        return _apply1(self, lambda d: d.reshape(new_shape), name="reshape")

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def expand_dims(self, axis):
        return _apply1(self, lambda d: jnp.expand_dims(d, axis))

    def squeeze(self, axis=None):
        return _apply1(self, lambda d: jnp.squeeze(d, axis))

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        axes = axes if axes else None
        return _apply1(self, lambda d: jnp.transpose(d, axes))

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        """MXNet Flatten: collapse all but first axis (NOT numpy ravel)."""
        lead = self.shape[0] if self.ndim else 1
        return _apply1(self, lambda d: d.reshape(lead, -1), name="flatten")

    def swapaxes(self, a1, a2):
        return _apply1(self, lambda d: jnp.swapaxes(d, a1, a2))

    def broadcast_to(self, shape):
        shape = tuple(shape)
        cur = self.shape
        if len(cur) < len(shape):
            cur = (1,) * (len(shape) - len(cur)) + cur
        for c, s in zip(cur, shape):
            if c != s and c != 1:
                raise MXNetError(
                    f"cannot broadcast {self.shape} to {shape}")
        return _apply1(self, lambda d: jnp.broadcast_to(
            d.reshape(cur), shape), name="broadcast_to")

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def tile(self, reps):
        return _apply1(self, lambda d: jnp.tile(d, reps))

    def repeat(self, repeats, axis=None):
        return _apply1(self, lambda d: jnp.repeat(d, repeats, axis))

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        from . import ops as _ops
        return _ops.split(self, num_outputs=num_outputs, axis=axis,
                          squeeze_axis=squeeze_axis)

    # ------------------------------------------------------------------
    # reductions / linalg / misc forwarding (full set in ops.py)
    # ------------------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.sum(d, axis=_ax(axis),
                                               keepdims=keepdims), name="sum")

    def mean(self, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.mean(d, axis=_ax(axis),
                                                keepdims=keepdims))

    def max(self, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.max(d, axis=_ax(axis),
                                               keepdims=keepdims))

    def min(self, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.min(d, axis=_ax(axis),
                                               keepdims=keepdims))

    def prod(self, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.prod(d, axis=_ax(axis),
                                                keepdims=keepdims))

    def argmax(self, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.argmax(d, axis=axis,
                                                  keepdims=keepdims)
                       .astype(jnp.float32))

    def argmin(self, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.argmin(d, axis=axis,
                                                  keepdims=keepdims)
                       .astype(jnp.float32))

    def abs(self):
        return _apply1(self, jnp.abs)

    def sqrt(self):
        return _apply1(self, jnp.sqrt)

    def exp(self):
        return _apply1(self, jnp.exp)

    def log(self):
        return _apply1(self, jnp.log)

    def clip(self, a_min=None, a_max=None):
        return _apply1(self, lambda d: jnp.clip(d, a_min, a_max))

    def dot(self, other):
        from . import ops as _ops
        return _ops.dot(self, other)

    def norm(self, ord=2, axis=None, keepdims=False):
        return _apply1(self, lambda d: jnp.linalg.norm(
            d if d.ndim else d.reshape(1), ord=ord, axis=_ax(axis),
            keepdims=keepdims) if axis is not None else
            jnp.sqrt(jnp.sum(jnp.square(d))) if ord == 2 else
            jnp.sum(jnp.abs(d)), name="norm")

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return _apply1(self, lambda d: jax.nn.one_hot(
            d.astype(jnp.int32), depth) * (on_value - off_value) + off_value)

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        from . import ops as _ops
        return _ops.topk(self, axis=axis, k=k, ret_typ=ret_typ,
                         is_ascend=is_ascend)

    def take(self, indices, axis=0, mode="clip"):
        from . import ops as _ops
        return _ops.take(self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        from . import ops as _ops
        return _ops.pick(self, index, axis=axis, keepdims=keepdims)

    def slice_axis(self, axis, begin, end):
        from . import ops as _ops
        return _ops.slice_axis(self, axis=axis, begin=begin, end=end)

    def softmax(self, axis=-1):
        return _apply1(self, lambda d: jax.nn.softmax(d, axis=axis))

    def log_softmax(self, axis=-1):
        return _apply1(self, lambda d: jax.nn.log_softmax(d, axis=axis))

    def relu(self):
        return _apply1(self, jax.nn.relu)

    def sigmoid(self):
        return _apply1(self, jax.nn.sigmoid)

    def tanh(self):
        return _apply1(self, jnp.tanh)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        if _donation._ENABLED:
            _donation.touch(self._data, "getitem")
        key = _convert_index(key)
        return _apply1(self, lambda d: d[key], name="getitem")

    def __setitem__(self, key, value):
        self._check_mutable()
        key = _convert_index(key)
        if isinstance(value, NDArray):
            value = value._data
        elif not isinstance(value, (jnp.ndarray, jax.Array)):
            value = jnp.asarray(value, dtype=self._data.dtype)
        # through _set_data: ``x.grad[:] = 0`` writes the owner's gradient
        self._set_data(self._data.at[key].set(value))

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    # ------------------------------------------------------------------
    # arithmetic
    # ------------------------------------------------------------------
    def __add__(self, other):
        return _binary(self, other, jnp.add, name="add")

    __radd__ = __add__

    def __sub__(self, other):
        return _binary(self, other, jnp.subtract, name="sub")

    def __rsub__(self, other):
        return _binary(self, other, lambda a, b: b - a, name="rsub")

    def __mul__(self, other):
        return _binary(self, other, jnp.multiply, name="mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return _binary(self, other, jnp.divide, name="div")

    def __rtruediv__(self, other):
        return _binary(self, other, lambda a, b: b / a, name="rdiv")

    def __mod__(self, other):
        # reference mod is C fmod semantics (sign of dividend), not Python %
        return _binary(self, other, jnp.fmod, name="mod")

    def __rmod__(self, other):
        return _binary(self, other, lambda a, b: jnp.fmod(b, a), name="rmod")

    def __pow__(self, other):
        return _binary(self, other, jnp.power, name="pow")

    def __rpow__(self, other):
        return _binary(self, other, lambda a, b: jnp.power(b, a))

    def __neg__(self):
        return _apply1(self, jnp.negative, name="neg")

    def __abs__(self):
        return _apply1(self, jnp.abs)

    def __matmul__(self, other):
        return _binary(self, other, jnp.matmul, name="matmul")

    # in-place: mutate handle (engine-write in the reference)
    def __iadd__(self, other):
        self._set_data(jnp.add(self._data, _raw(other, self)))
        return self

    def __isub__(self, other):
        self._set_data(jnp.subtract(self._data, _raw(other, self)))
        return self

    def __imul__(self, other):
        self._set_data(jnp.multiply(self._data, _raw(other, self)))
        return self

    def __itruediv__(self, other):
        self._set_data(jnp.divide(self._data, _raw(other, self)))
        return self

    # comparisons (return 0/1 float arrays, mx.nd semantics)
    def __eq__(self, other):
        return _binary(self, other,
                       lambda a, b: (a == b).astype(a.dtype
                                                    if jnp.issubdtype(a.dtype, jnp.floating)
                                                    else jnp.float32))

    def __ne__(self, other):
        return _binary(self, other,
                       lambda a, b: (a != b).astype(jnp.float32))

    def __gt__(self, other):
        return _binary(self, other, lambda a, b: (a > b).astype(jnp.float32))

    def __ge__(self, other):
        return _binary(self, other, lambda a, b: (a >= b).astype(jnp.float32))

    def __lt__(self, other):
        return _binary(self, other, lambda a, b: (a < b).astype(jnp.float32))

    def __le__(self, other):
        return _binary(self, other, lambda a, b: (a <= b).astype(jnp.float32))

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("The truth value of an NDArray with multiple "
                         "elements is ambiguous")

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>"

    # numpy interop
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def zeros_like(self):
        return _apply1(self, jnp.zeros_like)

    def ones_like(self):
        return _apply1(self, jnp.ones_like)

    def to_dlpack_for_read(self):
        return jax.dlpack.to_dlpack(self._data)


# ----------------------------------------------------------------------
# dispatch helpers
# ----------------------------------------------------------------------

def _ax(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


def _attach(out, node, idx):
    if node is not None:
        out._node = node
        out._out_index = idx


def _apply1(x, fn, name=""):
    outs, node = _tape.apply_op(fn, [x], name=name)
    out = NDArray(outs[0], x._ctx)
    _attach(out, node, 0)
    return out


def _raw(other, like):
    if isinstance(other, NDArray):
        return other._data
    if isinstance(other, numeric_types):
        return other
    return jnp.asarray(other, dtype=like._data.dtype)


def _binary(lhs, rhs, fn, name=""):
    if isinstance(rhs, NDArray):
        outs, node = _tape.apply_op(fn, [lhs, rhs], name=name)
        out = NDArray(outs[0], lhs._ctx)
        _attach(out, node, 0)
        return out
    scalar = rhs if isinstance(rhs, numeric_types) else jnp.asarray(rhs)
    outs, node = _tape.apply_op(lambda a: fn(a, scalar), [lhs], name=name)
    out = NDArray(outs[0], lhs._ctx)
    _attach(out, node, 0)
    return out


def apply_nary(fn, inputs, ctx=None, n_out=1, name=""):
    """Public dispatch for ops.py: fn over raw arrays, tape-aware."""
    outs, node = _tape.apply_op(fn, list(inputs), n_out=n_out, name=name)
    ctx = ctx or (inputs[0]._ctx if inputs else current_context())
    results = []
    for i, o in enumerate(outs):
        out = NDArray(o, ctx)
        _attach(out, node, i)
        results.append(out)
    return results[0] if n_out == 1 else results


def _resolve_reshape(cur, shape):
    """MXNet reshape codes, full set. Reference semantics:
    src/operator/tensor/matrix_op-inl.h InferReshapeShape:

      0  copy the corresponding input dim
      -1 infer this dim from the remaining size (at most one)
      -2 copy ALL remaining input dims from the current position
      -3 merge two consecutive input dims into one
      -4 split one input dim into the next TWO target entries (one of
         which may be -1)
    """
    shape = tuple(int(s) for s in shape)
    out = []
    src = 0     # cursor into the input shape
    i = 0
    while i < len(shape):
        s = shape[i]
        if s > 0:
            out.append(s)
            src += 1
        elif s == 0:
            if src >= len(cur):
                raise MXNetError(f"reshape code 0 at dim {i} out of range "
                                 f"for shape {cur}")
            out.append(cur[src])
            src += 1
        elif s == -1:
            if -1 in out:
                raise MXNetError("reshape allows at most one -1 "
                                 f"(outside -4 splits): {shape}")
            out.append(-1)
            src += 1
        elif s == -2:
            out.extend(cur[src:])
            src = len(cur)
        elif s == -3:
            if src + 1 >= len(cur):
                raise MXNetError(f"reshape code -3 at dim {i} needs two "
                                 f"input dims, shape {cur} has "
                                 f"{len(cur) - src} left")
            out.append(cur[src] * cur[src + 1])
            src += 2
        elif s == -4:
            if i + 2 >= len(shape):
                raise MXNetError(
                    f"reshape code -4 must be followed by two split dims: "
                    f"{shape}")
            if src >= len(cur):
                raise MXNetError(f"reshape code -4 at dim {i} out of range "
                                 f"for shape {cur}")
            d = cur[src]
            d1, d2 = shape[i + 1], shape[i + 2]
            d1 = d if d1 == 0 else d1
            d2 = d if d2 == 0 else d2
            if d1 == -1 and d2 == -1:
                raise MXNetError("reshape -4 split cannot infer both dims")
            if d1 == -1:
                d1 = d // d2
            if d2 == -1:
                d2 = d // d1
            if d1 * d2 != d:
                raise MXNetError(f"reshape -4 split {d1}x{d2} != input "
                                 f"dim {d}")
            out.extend([d1, d2])
            src += 1
            i += 2
        else:
            raise MXNetError(f"invalid reshape code {s}")
        i += 1
    total = 1
    for c in cur:
        total *= c
    if -1 in out:
        known = 1
        for o in out:
            if o != -1:
                known *= o
        if known == 0 or total % known:
            raise MXNetError(f"cannot infer -1 in reshape {shape} of {cur}")
        out[out.index(-1)] = total // known
    size = 1
    for o in out:
        size *= o
    if size != total:
        raise MXNetError(f"reshape {shape} of {cur}: target size {size} "
                         f"!= input size {total}")
    return tuple(out)


def _convert_index(key):
    if isinstance(key, NDArray):
        if key._data.dtype == jnp.bool_:
            # boolean-mask indexing (reference NDArray supports it via
            # np-compat semantics): keep the mask a mask — casting it to
            # int32 would silently reinterpret it as integer indices.
            # The result shape is data-dependent (number of True
            # entries), legal eagerly but not under a jit trace.
            import jax.core as _core
            if isinstance(key._data, _core.Tracer):
                raise MXNetError(
                    "boolean-mask indexing has a data-dependent result "
                    "shape and cannot appear inside a jit-traced "
                    "function; use nd.where / contrib.boolean_mask with "
                    "static shapes instead")
            return key._data
        return key._data.astype(jnp.int32)
    if isinstance(key, tuple):
        return tuple(_convert_index(k) for k in key)
    return key


# ----------------------------------------------------------------------
# creation functions (reference: python/mxnet/ndarray/ndarray.py +
# src/operator/tensor/init_op.cc)
# ----------------------------------------------------------------------

def _put(data, ctx):
    ctx = Context(ctx) if ctx is not None and not isinstance(ctx, Context) else ctx
    ctx = ctx or current_context()
    return NDArray(jax.device_put(data, ctx.jax_device), ctx)


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        data = source_array._data
        if dtype is not None:
            data = data.astype(_dtype_of(dtype))
        return _put(data, ctx)
    is_np_src = isinstance(source_array, _np.ndarray)
    np_arr = _np.asarray(source_array)
    if np_arr.dtype in (_np.int64, _np.uint64):
        _check_narrowing(np_arr)
    if dtype is None:
        # reference semantics (python/mxnet/ndarray/ndarray.py array()):
        # keep the dtype of ndarray sources, default float32 for lists etc.
        if is_np_src and np_arr.dtype != _np.float64:
            dtype = np_arr.dtype
        else:
            dtype = jnp.float32
    return _put(jnp.asarray(np_arr, dtype=_dtype_of(dtype)), ctx)


def from_jax(data, ctx=None):
    return NDArray(data, ctx or current_context())


def zeros(shape, ctx=None, dtype=None):
    shape = (shape,) if isinstance(shape, integer_types) else tuple(shape)
    return _put(jnp.zeros(shape, _dtype_of(dtype)), ctx)


def ones(shape, ctx=None, dtype=None):
    shape = (shape,) if isinstance(shape, integer_types) else tuple(shape)
    return _put(jnp.ones(shape, _dtype_of(dtype)), ctx)


def full(shape, val, ctx=None, dtype=None):
    shape = (shape,) if isinstance(shape, integer_types) else tuple(shape)
    return _put(jnp.full(shape, val, _dtype_of(dtype)), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    data = jnp.arange(start, stop, step, _dtype_of(dtype))
    if repeat > 1:
        data = jnp.repeat(data, repeat)
    return _put(data, ctx)


def eye(N, M=0, k=0, ctx=None, dtype=None):
    return _put(jnp.eye(N, M if M else N, k, dtype=_dtype_of(dtype)), ctx)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype=None):
    return _put(jnp.linspace(start, stop, num, endpoint=endpoint,
                             dtype=_dtype_of(dtype)), ctx)


def concat(*arrays, dim=1):
    from . import ops as _ops
    return _ops.concat(*arrays, dim=dim)


def concatenate(arrays, axis=0):
    from . import ops as _ops
    return _ops.concat(*arrays, dim=axis)


def stack(*arrays, axis=0):
    from . import ops as _ops
    return _ops.stack(*arrays, axis=axis)


def waitall():
    """Reference: MXNDArrayWaitAll — engine WaitForAll.

    Blocks on every live NDArray's device buffer (weak registry), the
    real equivalent of draining the reference's dependency engine."""
    handles = []
    for arr in list(_LIVE_ARRAYS):
        for h in arr._sync_handles():
            if h is not None and hasattr(h, "block_until_ready"):
                handles.append(h)
    if handles:
        jax.block_until_ready(handles)
