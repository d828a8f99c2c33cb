"""RMSNorm and RoPE (interleaved pairs; half-split pairs with sectioned
positions) on raw jax arrays: the one source that the registered ops
(``nd.rms_norm``, ``nd.mla_attention``, ``nd.sparse_gq_attention``), the
model zoo's decoders (``llama``, ``deepseek_v3``, ``keye_vl2``) and the
serving engine's decode steps share, so that none of them can drift from
the others."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


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


def sectioned_angles(positions, dim, theta, sections=None):
    """Rotary angles (..., T, dim / 2) float32 of the ``dim / 2`` frequencies
    ``theta^(-2i / dim)``.  ``positions``: (..., T) for one position stream,
    or (S, ..., T) with ``sections`` a tuple of S counts that add up to
    ``dim / 2``: the first ``sections[0]`` frequencies take stream 0's
    positions, the next ``sections[1]`` stream 1's, and so on (multimodal
    rotary: temporal, height, width; for text the streams are equal)."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    positions = positions.astype(jnp.float32)
    if sections is None:
        return positions[..., None] * inv
    if sum(sections) != dim // 2 or len(sections) != positions.shape[0]:
        raise ValueError(f"sections {sections} for {positions.shape[0]} "
                         f"position streams and {dim // 2} frequencies")
    # (S, ..., T): every frequency reads its own stream's positions
    stream = np.repeat(np.arange(len(sections)), sections)
    return jnp.moveaxis(positions[stream], 0, -1) * inv


def rope_half_split(u, cos, sin):
    """Rotate the pairs ``(u[i], u[i + d / 2])``; cos/sin (..., T, d / 2)
    broadcast against ``u[..., :d / 2]``."""
    half = u.shape[-1] // 2
    u1, u2 = u[..., :half], u[..., half:]
    return jnp.concatenate([u1 * cos - u2 * sin, u2 * cos + u1 * sin],
                           axis=-1)
