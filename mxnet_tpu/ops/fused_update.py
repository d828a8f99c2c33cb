"""Fused multi-tensor optimizer update: one Pallas kernel per flat bucket.

The ZeRO-1 pipeline (parallel/zero.py) already flattens gradients into a
few large f32 buckets; the per-bucket optimizer update, however, still
lowers to a chain of separate XLA elementwise HLOs.  On TPU this module
replaces that chain with ONE Pallas kernel that streams the flat bucket
through VMEM once — read p/g/state, do the whole update math per
element, write p'/state' — instead of materializing each intermediate in
HBM (the reference's ``multi_sgd_mom_update`` / ``multi_mp_sgd`` fused
CUDA kernels, src/operator/optimizer_op.cc, rebuilt as Pallas).

Entry points:

``fused_bucket_rule(name, clip_gradient=None, **hyper)``
    same contract as ``optimizer.fused_rule`` — ``(init, apply)`` with
    ``apply(p, g, s, lr, wd) -> (new_p, new_state)`` — but ``apply``
    routes eligible flat f32 payloads through the Pallas kernel on TPU
    and otherwise falls back to the *exact* ``fused_rule`` kernel (same
    function object), so CPU numerics are bitwise-unchanged.

Eligibility: rule in {sgd, nag, adam, adamw}, f32 payload, TPU backend,
``MXTPU_PALLAS_UPDATE`` not ``0``.  Everything else takes the XLA rule;
an eligible bucket whose kernel fails to lower raises.

The gluon ``Trainer`` fused group update concatenates its whole
parameter group into one flat bucket per state-layout (trainer.py
``_fused_jit_update``) and calls this rule once — "one kernel walks the
bucket" instead of one update chain per parameter.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .kernel_mode import kernel_mode

__all__ = ["fused_bucket_rule", "pallas_update_enabled", "PALLAS_RULES"]

#: rules with a Pallas bucket kernel; the rest always use the XLA chain
PALLAS_RULES = frozenset({"sgd", "nag", "adam", "adamw"})

_LANE = 128
_SUBLANE = 8


def pallas_update_enabled():
    """``MXTPU_PALLAS_UPDATE=0`` kills the Pallas bucket kernels (XLA
    fallback everywhere); default on — the TPU-backend gate still
    applies."""
    return os.environ.get("MXTPU_PALLAS_UPDATE", "1") != "0"


def _block_rows(n_rows, preferred=256):
    """Largest multiple-of-8 divisor of ``n_rows`` up to ``preferred``;
    None if n_rows is not a multiple of 8 (caller pads to avoid that)."""
    b = min(preferred, n_rows)
    b -= b % _SUBLANE
    while b >= _SUBLANE:
        if n_rows % b == 0:
            return b
        b -= _SUBLANE
    return None


def _pad_to_grid(flat, preferred=256):
    """(padded_2d, rows, block_rows, pad): reshape a flat f32 vector to
    (rows, 128) padded so a multiple-of-8 row block divides it."""
    n = flat.shape[0]
    rows = -(-n // _LANE)
    rows += (-rows) % _SUBLANE           # full (8, 128) tiles
    br = _block_rows(rows, preferred)    # rows % 8 == 0 => br >= 8
    pad = rows * _LANE - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANE), rows, br, pad


def _scalar_spec():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec((1, 1), lambda i: (0, 0),
                        memory_space=pltpu.SMEM)


def _vec_spec(br):
    from jax.experimental import pallas as pl
    return pl.BlockSpec((br, _LANE), lambda i: (i, 0))


# ---------------------------------------------------------------------------
# kernels (one grid step = one (block_rows, 128) tile of the bucket)
# ---------------------------------------------------------------------------

def _sgd_kernel(momentum, nesterov, clip):
    def kernel(lr_ref, wd_ref, p_ref, g_ref, *refs):
        lr = lr_ref[0, 0]
        wd = wd_ref[0, 0]
        p = p_ref[:]
        g = g_ref[:]
        if clip is not None:
            g = jnp.clip(g, -clip, clip)
        g = g + wd * p
        if not momentum:
            refs[0][:] = p - lr * g
            return
        m_ref, out_p, out_m = refs
        if nesterov:
            m = momentum * m_ref[:] + g
            out_p[:] = p - lr * (g + momentum * m)
        else:
            m = momentum * m_ref[:] - lr * g
            out_p[:] = p + m
        out_m[:] = m
    return kernel


def _adam_kernel(beta1, beta2, epsilon, decoupled_wd, clip):
    def kernel(lr_ref, wd_ref, lr_t_ref, p_ref, g_ref, m_ref, v_ref,
               out_p, out_m, out_v):
        lr = lr_ref[0, 0]
        wd = wd_ref[0, 0]
        lr_t = lr_t_ref[0, 0]
        p = p_ref[:]
        g = g_ref[:]
        if clip is not None:
            g = jnp.clip(g, -clip, clip)
        if not decoupled_wd:
            g = g + wd * p
        m = beta1 * m_ref[:] + (1 - beta1) * g
        v = beta2 * v_ref[:] + (1 - beta2) * jnp.square(g)
        new_p = p - lr_t * m / (jnp.sqrt(v) + epsilon)
        if decoupled_wd:
            new_p = new_p - lr * wd * p
        out_p[:] = new_p
        out_m[:] = m
        out_v[:] = v
    return kernel


def _run_pallas(kernel, name, scalars, tensors, n_out, br, rows,
                interpret=False):
    from jax.experimental import pallas as pl
    out = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[_scalar_spec() for _ in scalars]
        + [_vec_spec(br) for _ in tensors],
        out_specs=[_vec_spec(br) for _ in range(n_out)],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), jnp.float32)
                   for _ in range(n_out)],
        name=name,
        interpret=interpret,
    )(*[jnp.asarray(s, jnp.float32).reshape(1, 1) for s in scalars],
      *tensors)
    return out


def _pallas_sgd(p, g, s, lr, wd, momentum, nesterov, clip,
                interpret=False):
    n = p.shape[0]
    p2, rows, br, _ = _pad_to_grid(p)
    g2 = _pad_to_grid(g)[0]
    kernel = _sgd_kernel(momentum, nesterov, clip)
    if momentum:
        m2 = _pad_to_grid(s["mom"])[0]
        new_p, new_m = _run_pallas(kernel, "mxtpu_bucket_sgd", (lr, wd),
                                   (p2, g2, m2), 2, br, rows, interpret)
        return (new_p.reshape(-1)[:n],
                {"mom": new_m.reshape(-1)[:n]})
    (new_p,) = _run_pallas(kernel, "mxtpu_bucket_sgd", (lr, wd),
                           (p2, g2), 1, br, rows, interpret)
    return new_p.reshape(-1)[:n], dict(s)


def _pallas_adam(p, g, s, lr, wd, beta1, beta2, epsilon, decoupled_wd,
                 clip, interpret=False):
    n = p.shape[0]
    p2, rows, br, _ = _pad_to_grid(p)
    g2 = _pad_to_grid(g)[0]
    m2 = _pad_to_grid(s["m"])[0]
    v2 = _pad_to_grid(s["v"])[0]
    t = s["t"] + 1
    # the bias-corrected rate is scalar math on the step count; it stays
    # outside the kernel because Mosaic has no scalar powf ("failed to
    # legalize operation 'math.powf'", libtpu 0.0.34)
    tf = jnp.asarray(t, jnp.float32)
    lr_t = lr * jnp.sqrt(1 - beta2 ** tf) / (1 - beta1 ** tf)
    kernel = _adam_kernel(beta1, beta2, epsilon, decoupled_wd, clip)
    new_p, new_m, new_v = _run_pallas(
        kernel, "mxtpu_bucket_adam", (lr, wd, lr_t), (p2, g2, m2, v2), 3,
        br, rows, interpret)
    return (new_p.reshape(-1)[:n],
            {"m": new_m.reshape(-1)[:n], "v": new_v.reshape(-1)[:n],
             "t": t})


def _pallas_apply(name, hyper, clip, p, g, s, lr, wd, interpret=False):
    """Dispatch one flat f32 bucket through the rule's Pallas kernel."""
    if name in ("sgd", "nag"):
        momentum = float(hyper.get("momentum", 0.0))
        return _pallas_sgd(p, g, s, lr, wd, momentum, name == "nag",
                           clip, interpret)
    return _pallas_adam(p, g, s, lr, wd,
                        float(hyper.get("beta1", 0.9)),
                        float(hyper.get("beta2", 0.999)),
                        float(hyper.get("epsilon", 1e-8)),
                        name == "adamw", clip, interpret)


def _eligible(name, p):
    return (name in PALLAS_RULES
            and pallas_update_enabled()
            and kernel_mode() is not None
            and getattr(p, "ndim", 0) == 1
            and p.dtype == jnp.float32)


def fused_bucket_rule(name, clip_gradient=None, **hyper):
    """``optimizer.fused_rule`` contract with the Pallas fast path: the
    returned ``apply`` runs the flat-bucket Pallas kernel when eligible
    (TPU + flat f32 + supported rule) and the exact ``fused_rule``
    kernel — the identical function — everywhere else."""
    from ..optimizer.optimizer import fused_rule
    init, base_apply = fused_rule(name, clip_gradient=clip_gradient,
                                  **hyper)

    @functools.wraps(base_apply)
    def apply(p, g, s, lr, wd):
        if _eligible(name, p):
            # an eligible bucket that cannot lower is an error, not a
            # reason to take the XLA chain quietly
            return _pallas_apply(name, hyper, clip_gradient,
                                 p, g, s, lr, wd,
                                 interpret=kernel_mode() == "interpret")
        return base_apply(p, g, s, lr, wd)

    return init, apply
