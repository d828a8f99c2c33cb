"""RMSNorm and interleaved-pair RoPE on raw jax arrays: the one source that
the registered ops (``nd.rms_norm``, ``nd.mla_attention``), the model zoo's
decoders (``llama``, ``deepseek_v3``) and the serving engine's decode steps
share, so that none of them can drift from the others."""
from __future__ import annotations

import jax.numpy as jnp


def rms_norm(d, w, eps):
    """``d / sqrt(mean(d^2) + eps) * w`` over the last axis, reduced in
    float32 for bf16 inputs (standard practice)."""
    d32 = d.astype(jnp.float32)
    var = jnp.mean(d32 * d32, axis=-1, keepdims=True)
    return (d32 / jnp.sqrt(var + eps)).astype(d.dtype) * w


def rope_interleaved(u, cos, sin):
    """Rotate the pairs ``(u[2i], u[2i + 1])``; cos/sin broadcast against
    ``u[..., 0::2]`` ((t, d/2) in a forward, (d/2,) at a decode step)."""
    u1, u2 = u[..., 0::2], u[..., 1::2]
    return jnp.stack([u1 * cos - u2 * sin,
                      u2 * cos + u1 * sin], axis=-1).reshape(u.shape)
