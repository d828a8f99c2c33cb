"""``mx.amp`` — automatic mixed precision.

Reference: python/mxnet/contrib/amp/amp.py (SURVEY.md §2.2 "AMP"): op-list
driven low-precision casting + dynamic loss scaling, `amp.init()`,
`amp.init_trainer()`, `amp.scale_loss()`.

TPU-first: the default target dtype is **bfloat16** (MXU-native; same
exponent range as fp32, so no loss scaling needed — the scaler pins to 1).
`init()` wraps the op-registry functions (the `mx.nd.*` the reference would
rewrite at the symbol-graph level): TARGET_DTYPE_OPS cast inputs down to
bf16 before dispatch, FP32_OPS cast up to fp32, WIDEST_TYPE_CASTS promote
to the widest input dtype. Under `hybridize()` the casts trace into the
jitted XLA program, so mixed precision is compiled, not interpreted.
"""
from __future__ import annotations

import functools
import inspect
from contextlib import contextmanager

import numpy as _np

from ..base import MXNetError
from . import lists
from .loss_scaler import LossScaler

__all__ = ["init", "init_trainer", "scale_loss", "unscale",
           "list_lp16_ops", "list_fp32_ops", "convert_model",
           "convert_hybrid_block", "LossScaler"]

_initialized = False
_target_dtype = None
_originals = {}


def _cast_arrays(args, kwargs, dtype, keep=()):
    """Cast every floating NDArray among the arguments to ``dtype``; ``keep``
    names those left alone, by position and by keyword."""
    import jax.numpy as jnp
    from ..ndarray.ndarray import NDArray

    def cast(x):
        # jnp.issubdtype knows the ml_dtypes (bfloat16), numpy's does not
        if isinstance(x, NDArray) and jnp.issubdtype(x.data.dtype,
                                                     jnp.floating):
            if str(x.data.dtype) != dtype:
                return x.astype(dtype)
        return x

    return ([a if i in keep else cast(a) for i, a in enumerate(args)],
            {k: v if k in keep else cast(v) for k, v in kwargs.items()})


def _widest_dtype(args, kwargs):
    import jax.numpy as jnp
    from ..ndarray.ndarray import NDArray
    widest = None
    for x in list(args) + list(kwargs.values()):
        if isinstance(x, NDArray) and jnp.issubdtype(x.data.dtype,
                                                     jnp.floating):
            widest = x.data.dtype if widest is None else \
                jnp.promote_types(widest, x.data.dtype)
    return None if widest is None else str(widest)


def _wrap(fn, mode, target_dtype, keep=()):
    if keep:        # the names, and where they stand in the signature
        order = list(inspect.signature(fn).parameters)
        keep = set(keep) | {order.index(name) for name in keep}

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if mode == "low":
            args, kwargs = _cast_arrays(args, kwargs, target_dtype, keep)
        elif mode == "fp32":
            args, kwargs = _cast_arrays(args, kwargs, "float32")
        elif mode == "widest":
            w = _widest_dtype(args, kwargs)
            if w is not None:
                args, kwargs = _cast_arrays(args, kwargs, w)
        return fn(*args, **kwargs)

    wrapper._amp_original = fn
    return wrapper


def init(target_dtype="bfloat16", target_precision_ops=None,
         conditional_fp32_ops=None, fp32_ops=None):
    """Patch the op registry for mixed precision.

    target_dtype: 'bfloat16' (TPU default) or 'float16' (API compat).
    """
    global _initialized, _target_dtype
    if _initialized:
        return
    if target_dtype not in ("bfloat16", "float16"):
        raise MXNetError("target_dtype must be bfloat16 or float16")
    _target_dtype = target_dtype

    from .. import ndarray as nd_ns
    from ..ndarray import ops as ops_mod

    low = set(lists.TARGET_DTYPE_OPS) | set(target_precision_ops or [])
    fp32 = (set(lists.FP32_OPS) | set(fp32_ops or [])) - low
    widest = set(lists.WIDEST_TYPE_CASTS) - low - fp32

    for name_set, mode in ((low, "low"), (fp32, "fp32"), (widest, "widest")):
        for name in name_set:
            fn = getattr(ops_mod, name, None)
            if fn is None or not callable(fn):
                continue
            wrapped = _wrap(fn, mode, target_dtype,
                            lists.KEEP_DTYPE_ARGS.get(name, ()))
            _originals[name] = fn
            setattr(ops_mod, name, wrapped)
            # the gluon F namespace is the `mxnet_tpu.ndarray` module
            if getattr(nd_ns, name, None) is fn:
                setattr(nd_ns, name, wrapped)
    _initialized = True


def _deinit_for_tests():
    """Undo init() — test helper, not part of the reference API."""
    global _initialized, _target_dtype
    from .. import ndarray as nd_ns
    from ..ndarray import ops as ops_mod
    for name, fn in _originals.items():
        setattr(ops_mod, name, fn)
        if hasattr(nd_ns, name):
            setattr(nd_ns, name, fn)
    _originals.clear()
    _initialized = False
    _target_dtype = None


def init_trainer(trainer):
    """Attach a loss scaler to a Trainer (reference: amp.init_trainer).

    bf16 needs no scaling -> static scale 1; fp16 gets the dynamic scaler.
    """
    if not _initialized:
        raise MXNetError("call amp.init() before amp.init_trainer()")
    if _target_dtype == "bfloat16":
        trainer._amp_loss_scaler = LossScaler(init_scale=1.0, dynamic=False)
    else:
        trainer._amp_loss_scaler = LossScaler()
    trainer._amp_original_step = trainer.step

    def amp_step(batch_size, ignore_stale_grad=False):
        scaler = trainer._amp_loss_scaler
        trainer._optimizer.rescale_grad = \
            trainer._scale / batch_size / scaler.loss_scale
        trainer._all_reduce_grads()
        # dynamic (fp16) scaling always checks for overflow — the scale can
        # sit at its 1.0 floor and grads still be inf; the static bf16
        # scaler skips the check (bf16 has fp32's exponent range).
        # Checked AFTER the grad sync: reduced grads are identical on every
        # worker (inf/nan propagates through the sum), so all workers take
        # the same skip decision — a pre-sync local check could desync the
        # collective schedule under a dist kvstore.
        overflow = scaler._dynamic and scaler.has_overflow(trainer._params)
        if not overflow:
            trainer._update(ignore_stale_grad)
        else:   # skip step, drop stale grads
            for p in trainer._params:
                if p._data is not None:
                    p._data._grad_fresh = False
        scaler.update_scale(overflow)

    def step(batch_size, ignore_stale_grad=False):
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        amp_step(batch_size, ignore_stale_grad)

    trainer.step = step


@contextmanager
def scale_loss(loss, trainer):
    """Scale the loss before backward (reference: amp.scale_loss)."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None or scaler.loss_scale == 1.0:
        yield loss
        return
    if isinstance(loss, (list, tuple)):
        yield [l * scaler.loss_scale for l in loss]
    else:
        yield loss * scaler.loss_scale


def unscale(trainer):
    """Divide current grads by the loss scale (reference: amp.unscale)."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None or scaler.loss_scale == 1.0:
        return
    inv = 1.0 / scaler.loss_scale
    for p in trainer._params:
        if p._data is not None and p._data._grad is not None:
            p._data._grad = p._data._grad * inv


def convert_hybrid_block(block, target_dtype="bfloat16"):
    """Cast a HybridBlock's parameters to the target dtype in place and
    return it (reference: amp.convert_hybrid_block returns a converted
    block; here parameters are cast and activations follow op lists)."""
    block.cast(target_dtype)
    return block


def list_lp16_ops(target_dtype="bfloat16"):
    """Reference amp.list_lp16_ops: op names cast to the low-precision
    dtype under AMP (the list is dtype-independent here: one policy
    table serves bf16 and fp16)."""
    return list(lists.TARGET_DTYPE_OPS)


def list_fp32_ops(target_dtype="bfloat16"):
    """Reference amp.list_fp32_ops: op names pinned to fp32 under AMP
    (dtype-independent, see list_lp16_ops)."""
    return list(lists.FP32_OPS)


def convert_model(sym, arg_params, aux_params, target_dtype="bfloat16",
                  target_dtype_ops=None, fp32_ops=None,
                  conditional_fp32_ops=None, excluded_sym_names=None,
                  cast_optional_params=False):
    """Reference amp.convert_model(sym, args, aux): Module-API mixed
    precision. Under XLA the cast policy is applied at DISPATCH (amp.init
    wraps the op table), not by graph surgery, so the symbol is returned
    unchanged; floating-point parameters are cast when
    cast_optional_params is set. conditional_fp32_ops/excluded_sym_names
    are accepted for reference-API compatibility (per-node graph surgery
    does not exist here; exclude at the op level via fp32_ops)."""
    import jax.numpy as jnp
    if _initialized:
        if target_dtype != _target_dtype:
            raise MXNetError(
                f"amp already initialized with target_dtype="
                f"{_target_dtype}; convert_model(target_dtype="
                f"{target_dtype}) cannot change the dispatch policy "
                "mid-process")
        if target_dtype_ops or fp32_ops:
            # init() would silently drop these on its already-initialized
            # fast path — refuse rather than pretend the pins applied
            raise MXNetError(
                "amp already initialized; convert_model cannot add "
                "target_dtype_ops/fp32_ops to an installed policy — pass "
                "them to the FIRST amp.init/convert_model call")
    init(target_dtype=target_dtype, target_precision_ops=target_dtype_ops,
         fp32_ops=fp32_ops)
    aux_params = aux_params or {}
    if cast_optional_params:
        dt = "bfloat16" if target_dtype == "bfloat16" else "float16"
        norm_suffixes = ("gamma", "beta", "running_mean", "running_var",
                         "moving_mean", "moving_var")

        def cast(name, v):
            # float params only (integer counters/index tables keep their
            # dtype), and norm-family params stay fp32 — their ops are
            # FP32_OPS and the reference keeps fp32-op params in fp32
            # (a bf16 round-trip would truncate running stats for good)
            if name.endswith(norm_suffixes):
                return v
            if jnp.issubdtype(v.data.dtype, jnp.floating):
                return v.astype(dt)
            return v

        arg_params = {k: cast(k, v) for k, v in arg_params.items()}
        aux_params = {k: cast(k, v) for k, v in aux_params.items()}
    return sym, arg_params, aux_params
