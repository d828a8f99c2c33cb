"""Random samplers with MXNet's global-seed semantics over JAX explicit keys.

Reference: ``python/mxnet/random.py`` + ``src/operator/random/`` +
``src/common/random_generator.*`` (per-device PRNG pools). SURVEY.md §2.1
disposition: "JAX explicit PRNG keys; compat shim for mx.random.seed".

A module-level key is split on every sample — stateful facade, functional
engine. Inside jit traces (hybridized blocks) sampling uses ``next_key()``
captured at trace time; for reproducible jitted dropout use the Gluon layer,
which threads keys explicitly.
"""
from __future__ import annotations

import threading

import numpy as _np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from .ndarray import NDArray, _put, _dtype_of

__all__ = ["seed", "uniform", "normal", "randn", "randint", "gamma",
           "exponential", "poisson", "shuffle", "multinomial", "bernoulli",
           "negative_binomial", "generalized_negative_binomial",
           "next_key", "current_key", "get_key_data", "set_key_data"]


class _RandState(threading.local):
    def __init__(self):
        # the key is built on first use: making one initializes the JAX
        # backend, and ``import mxnet_tpu`` must not take the chip
        self._key = None
        self.trace_stack = []   # [(key, counter-box)] while tracing CachedOps

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(0)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_STATE = _RandState()


def seed(seed_state, ctx="all"):
    """mx.random.seed — reference python/mxnet/random.py."""
    _STATE.key = jax.random.key(int(seed_state))
    from .. import debug as _debug
    if _debug.determinism_enabled():
        # samplers and image augmenters draw from numpy's global RNG; under
        # MXTPU_ENFORCE_DETERMINISM one seed pins the whole input pipeline
        _np.random.seed(int(seed_state) % (2 ** 32))


def next_key():
    """Split a fresh key from the global stream; inside a CachedOp/jit trace
    derive deterministically from the per-call trace key instead (so replays
    get fresh randomness via the key argument, not baked-in constants)."""
    if _STATE.trace_stack:
        key, box = _STATE.trace_stack[-1]
        box[0] += 1
        return jax.random.fold_in(key, box[0])
    _STATE.key, sub = jax.random.split(_STATE.key)
    return sub


class trace_key_scope:
    """Scope used by CachedOp: all next_key() calls derive from this key."""

    def __init__(self, key):
        self._key = key

    def __enter__(self):
        _STATE.trace_stack.append((self._key, [0]))
        return self

    def __exit__(self, *exc):
        _STATE.trace_stack.pop()
        return False


def current_key():
    return _STATE.key


def get_key_data():
    """Serializable uint32 view of the global PRNG key (checkpointing:
    ``mx.checkpoint.CheckpointManager`` snapshots the RNG stream so a
    resumed run replays the exact draws an uninterrupted one makes)."""
    return jax.random.key_data(_STATE.key)


def set_key_data(data):
    """Inverse of :func:`get_key_data`: restore the global PRNG key."""
    _STATE.key = jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32))


def _shape(shape):
    if shape is None:
        return ()
    if isinstance(shape, int):
        return (shape,)
    return tuple(shape)


def uniform(low=0.0, high=1.0, shape=None, dtype=None, ctx=None, out=None):
    data = jax.random.uniform(next_key(), _shape(shape),
                              _dtype_of(dtype), low, high)
    return _wrap(data, ctx, out)


def normal(loc=0.0, scale=1.0, shape=None, dtype=None, ctx=None, out=None):
    data = loc + scale * jax.random.normal(next_key(), _shape(shape),
                                           _dtype_of(dtype))
    return _wrap(data, ctx, out)


def randn(*shape, loc=0.0, scale=1.0, dtype=None, ctx=None):
    return normal(loc, scale, shape, dtype, ctx)


def randint(low, high=None, shape=None, dtype="int32", ctx=None, out=None):
    if high is None:
        low, high = 0, low
    data = jax.random.randint(next_key(), _shape(shape), low, high,
                              _dtype_of(dtype) if dtype else jnp.int32)
    return _wrap(data, ctx, out)


def gamma(alpha=1.0, beta=1.0, shape=None, dtype=None, ctx=None, out=None):
    data = jax.random.gamma(next_key(), alpha, _shape(shape),
                            _dtype_of(dtype)) * beta
    return _wrap(data, ctx, out)


def exponential(scale=1.0, shape=None, dtype=None, ctx=None, out=None):
    data = jax.random.exponential(next_key(), _shape(shape),
                                  _dtype_of(dtype)) * scale
    return _wrap(data, ctx, out)


def poisson(lam=1.0, shape=None, dtype=None, ctx=None, out=None):
    data = jax.random.poisson(next_key(), lam, _shape(shape)).astype(
        _dtype_of(dtype))
    return _wrap(data, ctx, out)


def negative_binomial(k=1, p=1, shape=None, dtype=None, ctx=None, out=None):
    """NB(k, p) draws via the Gamma-Poisson mixture (reference
    mx.nd.random.negative_binomial); failures before the k-th success."""
    from .ops import _gamma_poisson   # single home for the mixture math
    data = _gamma_poisson(next_key(), next_key(), float(k),
                          (1.0 - p) / max(p, 1e-12), _shape(shape), dtype)
    return _wrap(data, ctx, out)


def generalized_negative_binomial(mu=1.0, alpha=1.0, shape=None, dtype=None,
                                  ctx=None, out=None):
    """Generalized NB(mean mu, dispersion alpha) — NB with k=1/alpha,
    p=1/(1+mu*alpha) (reference mx.nd.random.generalized_negative_binomial).
    alpha=0 degenerates to Poisson(mu)."""
    from .ops import _gamma_poisson
    a = max(float(alpha), 1e-12)
    data = _gamma_poisson(next_key(), next_key(), 1.0 / a, mu * a,
                          _shape(shape), dtype)
    return _wrap(data, ctx, out)


def bernoulli(p=0.5, shape=None, dtype=None, ctx=None):
    data = jax.random.bernoulli(next_key(), p, _shape(shape)).astype(
        _dtype_of(dtype))
    return _wrap(data, ctx, None)


def multinomial(data, shape=None, get_prob=False, dtype="int32"):
    """Sample from categorical distributions (rows of ``data`` are pmfs).
    Reference: src/operator/random/sample_multinomial_op.cc."""
    n = 1
    if shape:
        n = int(_np.prod(_shape(shape)))
    logits = jnp.log(jnp.maximum(data.data, 1e-37))
    samples = jax.random.categorical(next_key(), logits, axis=-1,
                                     shape=(n,) + logits.shape[:-1] if logits.ndim > 1
                                     else (n,))
    if logits.ndim > 1:
        samples = jnp.moveaxis(samples, 0, -1)
    if not shape:
        samples = samples.squeeze(-1) if logits.ndim > 1 else samples[0]
    out = NDArray(samples.astype(_dtype_of(dtype)), data.context)
    if get_prob:
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1),
            samples.astype(jnp.int32).reshape(logits.shape[:-1] + (-1,)),
            axis=-1)
        return out, NDArray(logp, data.context)
    return out


def shuffle(data, **kwargs):
    perm = jax.random.permutation(next_key(), data.shape[0])
    return NDArray(jnp.take(data.data, perm, axis=0), data.context)


def _wrap(data, ctx, out):
    arr = _put(data, ctx)
    if out is not None:
        out._set_data(arr._data)
        return out
    return arr
