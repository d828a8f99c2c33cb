"""Fused LayerNorm + residual (+dropout): Pallas TPU kernel + XLA fallback.

Transformer blocks pay LayerNorm twice per layer, and in the reference
each one lowers to a chain of mean/var/normalize/scale HLOs with the
residual add materialized separately.  This op fuses
``LayerNorm(x [+ residual]) * gamma + beta`` into ONE pass over the
activation: each (rows, D) tile is read from HBM once, the row
statistics are computed in f32 in VMEM, and the normalized output is
written once — no mean/var/centered intermediates round-trip through
HBM.  The backward is fused the same way (dx plus dgamma/dbeta partials
accumulated across sequential grid steps), recomputing the row
statistics from the saved inputs instead of storing them
(flash-attention's recompute-in-backward discipline, ops/flash_attention.py).

Layout: ``x`` is (..., D), normalized over the LAST axis; ``gamma`` /
``beta`` are (D,).  On TPU with D a multiple of 128 and the flattened
row count a multiple of 8 the Pallas kernels run; everything else takes
a jnp fallback with identical f32 accumulation semantics — the fallback
is the numerics reference the kernel is gated against
(tests/test_fused_kernels.py).

``dropout`` (optional) is applied to ``x`` *before* the residual add —
the post-attention ``LayerNorm(residual + dropout(x))`` shape — using
the standard inverted scaling; the dropout mask itself is XLA-side (the
kernel fuses the add+normalize that dominates the HBM traffic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .kernel_mode import kernel_mode

__all__ = ["fused_layer_norm"]

_LANE = 128
_SUBLANE = 8


def _pick_rows(rows, sublane=_SUBLANE, preferred=256):
    """Largest multiple-of-``sublane`` divisor of ``rows`` up to
    ``preferred``; None when rows is not a multiple of it (fallback
    path then runs)."""
    if rows % sublane:
        return None
    b = min(preferred, rows)
    b -= b % sublane
    while b >= sublane:
        if rows % b == 0:
            return b
        b -= sublane
    return None


def _use_pallas(rows, d, dtype=jnp.float32):
    import os
    if kernel_mode() is None:
        return None
    if os.environ.get("MXTPU_FUSED_LN", "1") == "0":
        return None
    if d % _LANE:
        return None
    # sublane tiling granularity depends on dtype (pallas guide): f32
    # tiles are (8, 128), bf16 (16, 128); anything else falls back
    if dtype == jnp.float32:
        sublane = _SUBLANE
    elif dtype == jnp.bfloat16:
        sublane = 2 * _SUBLANE
    else:
        return None
    return _pick_rows(rows, sublane)


# ---------------------------------------------------------------------------
# Pallas kernels (rows = flattened leading dims, D = normalized axis)
# ---------------------------------------------------------------------------

def _forward_kernel(eps, has_res):
    def kernel(x_ref, *refs):
        if has_res:
            res_ref, gamma_ref, beta_ref, y_ref = refs
            h = x_ref[:].astype(jnp.float32) \
                + res_ref[:].astype(jnp.float32)
        else:
            gamma_ref, beta_ref, y_ref = refs
            h = x_ref[:].astype(jnp.float32)
        mean = jnp.mean(h, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(h - mean), axis=1, keepdims=True)
        xhat = (h - mean) * lax.rsqrt(var + eps)
        y = xhat * gamma_ref[:].astype(jnp.float32) \
            + beta_ref[:].astype(jnp.float32)
        y_ref[:] = y.astype(y_ref.dtype)
    return kernel


def _backward_kernel(eps, has_res):
    from jax.experimental import pallas as pl

    def kernel(x_ref, *refs):
        if has_res:
            res_ref, gamma_ref, dy_ref, dx_ref, dg_ref, db_ref = refs
            h = x_ref[:].astype(jnp.float32) \
                + res_ref[:].astype(jnp.float32)
        else:
            gamma_ref, dy_ref, dx_ref, dg_ref, db_ref = refs
            h = x_ref[:].astype(jnp.float32)
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            dg_ref[:] = jnp.zeros_like(dg_ref)
            db_ref[:] = jnp.zeros_like(db_ref)

        mean = jnp.mean(h, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(h - mean), axis=1, keepdims=True)
        rstd = lax.rsqrt(var + eps)
        xhat = (h - mean) * rstd
        dy = dy_ref[:].astype(jnp.float32)
        a = dy * gamma_ref[:].astype(jnp.float32)
        c1 = jnp.mean(a * xhat, axis=1, keepdims=True)
        c2 = jnp.mean(a, axis=1, keepdims=True)
        dx_ref[:] = ((a - c2 - xhat * c1) * rstd).astype(dx_ref.dtype)
        # dgamma/dbeta partials: the grid is sequential on TPU, so
        # accumulating into the single shared (1, D) output block is the
        # standard reduction-across-grid pattern
        dg_ref[:] = dg_ref[:] + jnp.sum(dy * xhat, axis=0, keepdims=True)
        db_ref[:] = db_ref[:] + jnp.sum(dy, axis=0, keepdims=True)
    return kernel


def _pallas_forward(x2, res2, gamma, beta, eps, br, interpret=False):
    from jax.experimental import pallas as pl
    rows, d = x2.shape
    has_res = res2 is not None
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    ins = [x2] + ([res2] if has_res else []) \
        + [gamma.reshape(1, d), beta.reshape(1, d)]
    return pl.pallas_call(
        _forward_kernel(eps, has_res),
        grid=(rows // br,),
        in_specs=[row_spec] + ([row_spec] if has_res else [])
        + [vec_spec, vec_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        name="mxtpu_fused_ln_fwd",
        interpret=interpret,
    )(*ins)


def _pallas_backward(x2, res2, gamma, dy2, eps, br, interpret=False):
    from jax.experimental import pallas as pl
    rows, d = x2.shape
    has_res = res2 is not None
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    ins = [x2] + ([res2] if has_res else []) \
        + [gamma.reshape(1, d), dy2]
    dx, dg, db = pl.pallas_call(
        _backward_kernel(eps, has_res),
        grid=(rows // br,),
        in_specs=[row_spec] + ([row_spec] if has_res else [])
        + [vec_spec, row_spec],
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, d), x2.dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        name="mxtpu_fused_ln_bwd",
        interpret=interpret,
    )(*ins)
    return dx, dg[0], db[0]


# ---------------------------------------------------------------------------
# XLA fallback (identical f32 accumulation; the numerics reference)
# ---------------------------------------------------------------------------

def _fallback_forward(x, res, gamma, beta, eps):
    h = x.astype(jnp.float32)
    if res is not None:
        h = h + res.astype(jnp.float32)
    mean = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mean), axis=-1, keepdims=True)
    xhat = (h - mean) * lax.rsqrt(var + eps)
    y = xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(x.dtype)


def _fallback_backward(x, res, gamma, dy, eps):
    h = x.astype(jnp.float32)
    if res is not None:
        h = h + res.astype(jnp.float32)
    mean = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mean), axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    xhat = (h - mean) * rstd
    dyf = dy.astype(jnp.float32)
    a = dyf * gamma.astype(jnp.float32)
    c1 = jnp.mean(a * xhat, axis=-1, keepdims=True)
    c2 = jnp.mean(a, axis=-1, keepdims=True)
    dx = ((a - c2 - xhat * c1) * rstd).astype(x.dtype)
    reduce_axes = tuple(range(x.ndim - 1))
    dgamma = jnp.sum(dyf * xhat, axis=reduce_axes)
    dbeta = jnp.sum(dyf, axis=reduce_axes)
    return dx, dgamma, dbeta


# ---------------------------------------------------------------------------
# custom VJP core
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_ln(x, res, gamma, beta, eps):
    return _fused_ln_fwd(x, res, gamma, beta, eps)[0]


def _fused_ln_fwd(x, res, gamma, beta, eps):
    d = x.shape[-1]
    rows = x.size // d
    br = _use_pallas(rows, d, x.dtype)
    if br is not None:
        x2 = x.reshape(rows, d)
        res2 = None if res is None else res.reshape(rows, d)
        y = _pallas_forward(
            x2, res2, gamma, beta, eps, br,
            interpret=kernel_mode() == "interpret").reshape(x.shape)
    else:
        y = _fallback_forward(x, res, gamma, beta, eps)
    return y, (x, res, gamma)


def _fused_ln_bwd(eps, saved, dy):
    x, res, gamma = saved
    d = x.shape[-1]
    rows = x.size // d
    br = _use_pallas(rows, d, x.dtype)
    if br is not None:
        x2 = x.reshape(rows, d)
        res2 = None if res is None else res.reshape(rows, d)
        dx2, dgamma, dbeta = _pallas_backward(
            x2, res2, gamma, dy.reshape(rows, d), eps, br,
            interpret=kernel_mode() == "interpret")
        dx = dx2.reshape(x.shape)
    else:
        dx, dgamma, dbeta = _fallback_backward(x, res, gamma, dy, eps)
    dres = None if res is None else dx.astype(res.dtype)
    return (dx, dres, dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


# ---------------------------------------------------------------------------
# public op (NDArray tape-aware, like ops.flash_attention)
# ---------------------------------------------------------------------------

def fused_layer_norm(x, gamma, beta, residual=None, eps=1e-5,
                     dropout=0.0, training=None):
    """``LayerNorm(dropout(x) + residual) * gamma + beta`` in one fused
    pass over the activation (last-axis normalization, f32 statistics).

    ``x``: (..., D); ``gamma``/``beta``: (D,); ``residual``: optional
    (..., D) added before normalization (the transformer post-sublayer
    shape).  ``dropout`` > 0 applies inverted dropout to ``x`` before
    the residual add when training (``mx.autograd`` recording state by
    default).  Differentiable (custom VJP, fused backward) and
    tape-aware: NDArray inputs under ``autograd.record()`` record one
    tape node.  On TPU with D % 128 == 0 the core runs as a Pallas
    kernel; otherwise an identical-semantics XLA fallback.
    """
    from ..ndarray.ndarray import NDArray, apply_nary
    from .. import _tape

    if training is None:
        training = _tape.is_training()
    rate = float(dropout)

    def core(*raw):
        if residual is not None:
            xd, gd, bd, rd = raw
        else:
            (xd, gd, bd), rd = raw, None
        if xd.ndim < 1 or gd.shape != (xd.shape[-1],):
            raise ValueError(
                f"fused_layer_norm: x (..., D) with gamma/beta (D,); got "
                f"x {xd.shape}, gamma {gd.shape}")
        if rate > 0.0 and training:
            from ..ndarray import random as _rnd
            keep = 1.0 - rate
            mask = jax.random.bernoulli(_rnd.next_key(), keep, xd.shape)
            xd = jnp.where(mask, xd / keep, 0.0).astype(xd.dtype)
        return _fused_ln(xd, rd, gd, bd, float(eps))

    inputs = [x, gamma, beta] + ([residual] if residual is not None
                                 else [])
    if isinstance(x, NDArray):
        inputs = [a if isinstance(a, NDArray) else NDArray(jnp.asarray(a))
                  for a in inputs]
        return apply_nary(core, inputs, name="fused_layer_norm")
    return core(*[jnp.asarray(a) for a in inputs])
