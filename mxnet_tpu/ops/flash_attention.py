"""Flash attention: Pallas TPU kernel + blockwise-XLA fallback.

Reference capability: the fused ``contrib`` multi-head attention ops
(src/operator/contrib/transformer.cc [>=1.6]) — but those materialize the
(Lq, Lk) score matrix; this is the online-softmax streaming algorithm, so
HBM traffic is O(L*D) not O(L^2) (SURVEY.md §5.7 TPU plan).

Layout: (B, H, L, D). The Pallas path tiles Lq into BQ-row blocks and
streams Lk in BK-column blocks through VMEM, with a float32 accumulator
and running (max, denom) per query row; the MXU sees two
(BQ, D) x (D, BK) / (BQ, BK) x (BK, D) matmuls per step. The fallback is
the same algorithm as a ``lax.scan`` over KV blocks, which XLA fuses
adequately on CPU and keeps memory O(L*BK).

Gradients: custom VJP; the backward pass recomputes scores blockwise from
the saved logsumexp (standard flash-attention backward), also as a scan —
no O(L^2) residuals are ever stored.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .kernel_mode import kernel_mode

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _pick_block(n, preferred=512):
    """Largest multiple-of-128 divisor of n up to `preferred`; None if n
    is not a multiple of 128 (pallas path then declines)."""
    if n % 128:
        return None
    b = min(preferred, n)
    b -= b % 128
    while b >= 128:
        if n % b == 0:
            return b
        b -= 128
    return None


# ---------------------------------------------------------------------------
# Pallas TPU forward
# ---------------------------------------------------------------------------

def _pallas_forward(q, k, v, causal, sm_scale, bq, bk, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, lq, d = q.shape
    lk = k.shape[1]
    nq, nk = lq // bq, lk // bk

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_i, l_i):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_i[:] = jnp.full_like(m_i, _NEG_INF)
            l_i[:] = jnp.zeros_like(l_i)
            acc[:] = jnp.zeros_like(acc)

        # Causal: the whole KV block is in the future of the whole Q block
        # when j*bk > i*bq + bq - 1 — skip its compute entirely.
        live = (i + 1) * bq > j * bk if causal else True

        @pl.when(live)
        def _step():
            qb = q_ref[0]                       # (bq, d)
            kb = k_ref[0]                       # (bk, d)
            vb = v_ref[0]                       # (bk, d)
            s = lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if causal:
                qpos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, _NEG_INF)
            m_new = jnp.maximum(m_i[:], jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)              # (bq, bk) f32
            alpha = jnp.exp(m_i[:] - m_new)     # (bq, 1)
            l_i[:] = l_i[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc[:] = acc[:] * alpha + lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_i[:] = m_new

        @pl.when(j == nk - 1)
        def _fin():
            denom = jnp.maximum(l_i[:], 1e-30)
            o_ref[0] = (acc[:] / denom).astype(o_ref.dtype)
            # lse is (bq,) but mosaic tiling wants an (8, 128k) block, so
            # the output carries a broadcast sublane dim (sliced off by the
            # wrapper)
            lse = (m_i[:] + jnp.log(denom))[:, 0]
            lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, bq))

    grid = (bh, nq, nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="mxtpu_flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse[:, 0, :]


# ---------------------------------------------------------------------------
# Blockwise XLA fallback (same algorithm, lax.scan over KV blocks)
# ---------------------------------------------------------------------------

def _scan_forward(q, k, v, causal, sm_scale, bk):
    bh, lq, d = q.shape
    lk = k.shape[1]
    nk = lk // bk
    kb = k.reshape(bh, nk, bk, d).transpose(1, 0, 2, 3)   # (nk, bh, bk, d)
    vb = v.reshape(bh, nk, bk, d).transpose(1, 0, 2, 3)
    qpos = lax.broadcasted_iota(jnp.int32, (lq, bk), 0)

    def step(carry, blk):
        acc, m_i, l_i, j = carry
        kj, vj = blk
        s = jnp.einsum("bqd,bkd->bqk", q, kj,
                       preferred_element_type=jnp.float32) * sm_scale
        if causal:
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (lq, bk), 1)
            s = jnp.where((qpos >= kpos)[None], s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bqk,bkd->bqd", p.astype(v.dtype), vj,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l_new, j + 1), None

    init = (jnp.zeros((bh, lq, d), jnp.float32),
            jnp.full((bh, lq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((bh, lq, 1), jnp.float32),
            jnp.int32(0))
    (acc, m_i, l_i, _), _ = lax.scan(step, init, (kb, vb))
    denom = jnp.maximum(l_i, 1e-30)
    out = (acc / denom).astype(q.dtype)
    lse = (m_i + jnp.log(denom))[..., 0]
    return out, lse


# ---------------------------------------------------------------------------
# Backward (blockwise, shared by both paths)
# ---------------------------------------------------------------------------

def _scan_backward(q, k, v, out, lse, g, causal, sm_scale, bk):
    bh, lq, d = q.shape
    lk = k.shape[1]
    nk = lk // bk
    kb = k.reshape(bh, nk, bk, d).transpose(1, 0, 2, 3)
    vb = v.reshape(bh, nk, bk, d).transpose(1, 0, 2, 3)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # (bh, lq, 1)
    qpos = lax.broadcasted_iota(jnp.int32, (lq, bk), 0)

    def step(dq, blk):
        kj, vj, j = blk
        s = jnp.einsum("bqd,bkd->bqk", q, kj,
                       preferred_element_type=jnp.float32) * sm_scale
        if causal:
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (lq, bk), 1)
            s = jnp.where((qpos >= kpos)[None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                     # (bh, lq, bk)
        dv_j = jnp.einsum("bqk,bqd->bkd", p, g.astype(jnp.float32))
        dp = jnp.einsum("bqd,bkd->bqk", g.astype(jnp.float32),
                        vj.astype(jnp.float32))
        ds = p * (dp - delta) * sm_scale
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kj.astype(jnp.float32))
        return dq, (dk_j, dv_j)

    steps = (kb, vb, jnp.arange(nk, dtype=jnp.int32))
    dq, (dk, dv) = lax.scan(step, jnp.zeros((bh, lq, d), jnp.float32), steps)
    dk = dk.transpose(1, 0, 2, 3).reshape(bh, lk, d)
    dv = dv.transpose(1, 0, 2, 3).reshape(bh, lk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------

def _use_pallas(lq, lk, d):
    if kernel_mode() is None:
        return None
    import os

    def _pref(var, legacy):
        # tuning knobs (MXTPU_FLASH_BLOCK_Q/KV, legacy alias
        # MXTPU_FLASH_BQ/BK): preferred block sizes for the kernel
        # autotune sweep (tools/flash_long_seq.py --block-sweep);
        # clamped to >=128 so a too-small value still falls back to a
        # valid divisor instead of silently disabling the kernel, and
        # malformed values are named
        raw = os.environ.get(var)
        if raw is None:
            raw = os.environ.get(legacy, "512")
            var = legacy
        try:
            return max(int(raw), 128)
        except ValueError as e:
            from ..base import MXNetError
            raise MXNetError(
                f"{var}={raw!r} is not an integer block size") from e

    pref_q = _pref("MXTPU_FLASH_BLOCK_Q", "MXTPU_FLASH_BQ")
    pref_k = _pref("MXTPU_FLASH_BLOCK_KV", "MXTPU_FLASH_BK")
    bq = _pick_block(lq, pref_q)
    bk = _pick_block(lk, pref_k)
    # d=64 is fine: Mosaic pads the lane dim; BERT-base heads (768/12) hit
    # this. Verified on TPU v5e vs the scan path (max abs diff 1.8e-7 f32).
    if bq is None or bk is None or d % 64:
        return None
    return bq, bk


def _per_batch_shard(kernel, q):
    """``kernel``, wrapped in a shard_map over the data axis when this call
    is being traced into a step that XLA partitions over that axis by itself
    (the trainer's plain dp path: a jit over batch-sharded inputs).

    XLA refuses to partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    there each chip runs the kernel on its own (batch x heads) rows.  The
    step says how it is split through the ambient mesh
    (``parallel.mesh_scope``); a step that is already inside a shard_map
    (ZeRO-1) masks it with ``mesh_scope(None)``.  A mesh with a second axis
    of size > 1 is left to fail as before: only the leading dimension is
    known to be splittable."""
    from ..parallel.mesh import AXIS_DP, current_mesh
    mesh = current_mesh()
    if mesh is None or not isinstance(q, jax.core.Tracer):
        return kernel
    n = mesh.shape.get(AXIS_DP, 1)
    if n == 1 or n != mesh.size or q.shape[0] % n:
        return kernel
    rows = P(AXIS_DP)
    return shard_map(kernel, mesh=mesh, in_specs=(rows,) * 3,
                     out_specs=(rows, rows), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, sm_scale):
    return _flash_fwd(q, k, v, causal, sm_scale)[0]


def _flash_fwd(q, k, v, causal, sm_scale):
    blocks = _use_pallas(q.shape[1], k.shape[1], q.shape[2])
    if blocks is not None:
        kernel = functools.partial(
            _pallas_forward, causal=causal, sm_scale=sm_scale, bq=blocks[0],
            bk=blocks[1], interpret=kernel_mode() == "interpret")
        out, lse = _per_batch_shard(kernel, q)(q, k, v)
    else:
        bk = _pick_block(k.shape[1], 256) or k.shape[1]
        out, lse = _scan_forward(q, k, v, causal, sm_scale, bk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, res, g):
    q, k, v, out, lse = res
    bk = _pick_block(k.shape[1], 256) or k.shape[1]
    return _scan_backward(q, k, v, out, lse, g, causal, sm_scale, bk)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(query, key, value, causal=False, sm_scale=None):
    """softmax(QK^T * sm_scale [+ causal mask]) V without materializing the
    score matrix. query/key/value: (B, H, L, D) NDArrays or jax arrays.

    Differentiable (custom VJP, blockwise backward) and tape-aware: with
    NDArray inputs under ``autograd.record()`` it records one tape node.
    On TPU with 128-aligned L and D the core runs as a Pallas kernel;
    otherwise a blockwise-scan XLA fallback with identical semantics.
    """
    from ..ndarray.ndarray import NDArray, apply_nary

    def core(qd, kd, vd):
        if qd.ndim != 4:
            raise ValueError("flash_attention expects (B, H, L, D) inputs, "
                             f"got shape {qd.shape}")
        b, h, lq, d = qd.shape
        lk = kd.shape[2]
        scale = 1.0 / math.sqrt(d) if sm_scale is None else float(sm_scale)
        out = _flash(qd.reshape(b * h, lq, d), kd.reshape(b * h, lk, d),
                     vd.reshape(b * h, lk, d), bool(causal), scale)
        return out.reshape(b, h, lq, d)

    if isinstance(query, NDArray):
        key = key if isinstance(key, NDArray) else NDArray(jnp.asarray(key))
        value = value if isinstance(value, NDArray) else \
            NDArray(jnp.asarray(value))
        return apply_nary(core, [query, key, value], name="flash_attention")
    return core(jnp.asarray(query), jnp.asarray(key), jnp.asarray(value))
