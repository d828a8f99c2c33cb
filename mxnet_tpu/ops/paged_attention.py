"""Paged decode attention: block-table gather kernel for serving.

The serving decode step attends one query token per sequence against a
block-table paged KV cache (``serving.kv_cache.PagedKVCache``).  The
engine's original formulation gathers the sequence's blocks into a
dense ``(B, L, KVH, D)`` view with a jnp fancy-index and runs the
shared ``llama._cache_attention`` math — correct, but on TPU the
gather materializes the full context width per step in HBM traffic.

This module packages that step as one op with two interchangeable
bodies (the ``ops.flash_attention`` discipline):

- **Pallas path** (TPU only): a ``PrefetchScalarGridSpec`` kernel whose
  K/V BlockSpec index maps read the BLOCK TABLE itself — grid step
  ``(b, j)`` DMAs physical block ``table[b, j]`` straight from the pool
  into VMEM and folds it into a per-sequence online-softmax
  accumulator.  Only the sequence's own blocks ever move; there is no
  dense gather.  Blocks wholly past ``pos`` are masked per-position
  (write-ahead garbage and table padding contribute exactly 0).
- **XLA fallback** (CPU, or any geometry the kernel declines): the
  engine's original gather + ``_cache_attention``, op-for-op — so on
  the fallback path this function is BITWISE the inline formulation it
  replaces (the parity gate in tests/test_paged_attention.py), and
  ``MXTPU_PAGED_ATTN`` is a bitwise-inert routing knob on CPU hosts.

Low-precision pools (ISSUE 20): when the engine stores the KV pool in
fp8 (``MXTPU_KV_DTYPE=fp8``) it passes the per-token-row amax scale
planes (``k_scale`` / ``v_scale``, one f32 scalar per written cache
row) and both bodies dequantize AFTER the block-table gather — the
gathered rows are codes × their row scales, so HBM traffic stays at
fp8 width and only VMEM-resident tiles widen to f32.  A bf16 pool
passes no scales; codes are upcast directly.  ``k_scale=None`` on an
f32 pool is the original op, untouched.

The Pallas body compiles only on TPU backends (``_use_pallas`` gate,
like flash); structure tests assert its shape and skip execution
elsewhere.  TPU-vs-fallback numerics are checked on the chip by
``chip_smoke.py``, not claimed here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .kernel_mode import kernel_mode

__all__ = ["paged_decode_attention"]

_NEG_INF = -1e30


def _use_pallas(block_size, kv_heads, head_dim):
    """Pallas only on TPU backends, and only for geometries Mosaic
    tiles well (lane dim = head_dim multiple of 64, sublane = block
    rows multiple of 8).  Anything else: the bitwise fallback."""
    if kernel_mode() is None:
        return False
    return head_dim % 64 == 0 and block_size % 8 == 0


def _fallback(q, k_pool, v_pool, block_tables, pos, scale,
              k_scale=None, v_scale=None):
    """The engine's original decode attention, verbatim on an f32
    pool: dense gather through the block table, then the shared
    single-block online-softmax (one source with the full forward, so
    decode parity cannot drift — llama._cache_attention).  Quantized
    pools dequantize the gathered view first: codes upcast to f32 and,
    when scale planes ride along (fp8), multiply by the per-row amax
    scales gathered through the SAME block table."""
    from ..gluon.model_zoo.nlp.llama import _cache_attention
    from .quant_kv import kv_dequantize
    B = q.shape[0]
    nbl = block_tables.shape[1]
    bs, kvh, d = k_pool.shape[1:]
    L = nbl * bs
    ck = k_pool[block_tables].reshape(B, L, kvh, d)
    cv = v_pool[block_tables].reshape(B, L, kvh, d)
    if k_scale is not None:
        ck = kv_dequantize(ck, k_scale[block_tables].reshape(B, L))
        cv = kv_dequantize(cv, v_scale[block_tables].reshape(B, L))
    elif k_pool.dtype != jnp.float32:
        ck = kv_dequantize(ck)
        cv = kv_dequantize(cv)
    ck = ck.transpose(0, 2, 1, 3)
    cv = cv.transpose(0, 2, 1, 3)
    valid = jnp.arange(L)[None, :] <= pos[:, None]
    return _cache_attention(q, ck, cv, valid, scale)


def _pallas_paged(q, k_pool, v_pool, block_tables, pos, scale,
                  k_scale=None, v_scale=None, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, h, d = q.shape
    bs, kvh, _d = k_pool.shape[1:]
    nbl = block_tables.shape[1]
    rep = h // kvh
    scaled = k_scale is not None
    lowp = k_pool.dtype != jnp.float32

    def kernel(bt_ref, pos_ref, *refs):
        # refs layout: q, k, v[, ks, vs], o, acc, m_i, l_i — the scale
        # rows ride as extra block-table-gathered inputs when present
        if scaled:
            (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             acc, m_i, l_i) = refs
        else:
            q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i = refs
        b = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_i[:] = jnp.full_like(m_i, _NEG_INF)
            l_i[:] = jnp.zeros_like(l_i)
            acc[:] = jnp.zeros_like(acc)

        p = pos_ref[b]
        # a block wholly past the query position contributes nothing —
        # skip its compute (table padding points at the null block and
        # lands here too, since padded indices start past pos)
        @pl.when(j * bs <= p)
        def _step():
            qg = q_ref[0].reshape(kvh, rep, d)        # grouped queries
            kb = k_ref[0]                             # (bs, kvh, d)
            vb = v_ref[0]
            if lowp:
                kb = kb.astype(jnp.float32)
                vb = vb.astype(jnp.float32)
            if scaled:
                # per-token-row amax scales: one f32 scalar per cache
                # row, broadcast over (kvh, d)
                kb = kb * ks_ref[0][:, None, None]
                vb = vb * vs_ref[0][:, None, None]
            s = jnp.einsum("grd,tgd->grt", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * bs + lax.broadcasted_iota(
                jnp.int32, (kvh, rep, bs), 2)
            s = jnp.where(kpos <= p, s, _NEG_INF)
            m_new = jnp.maximum(m_i[:], jnp.max(s, axis=-1,
                                                keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_i[:] - m_new)
            l_i[:] = l_i[:] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            acc[:] = acc[:] * alpha + jnp.einsum(
                "grt,tgd->grd", pr.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            m_i[:] = m_new

        @pl.when(j == nbl - 1)
        def _fin():
            out = acc[:] / jnp.maximum(l_i[:], 1e-30)
            o_ref[0] = out.reshape(h, d).astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((1, h, d), lambda b, j, bt, ps: (b, 0, 0)),
        # gather-by-block-table: the index map reads the prefetched
        # table, so grid step (b, j) DMAs physical block bt[b, j]
        pl.BlockSpec((1, bs, kvh, d),
                     lambda b, j, bt, ps: (bt[b, j], 0, 0, 0)),
        pl.BlockSpec((1, bs, kvh, d),
                     lambda b, j, bt, ps: (bt[b, j], 0, 0, 0)),
    ]
    operands = [q, k_pool, v_pool]
    if scaled:
        # scale planes gather through the same table: step (b, j)
        # DMAs the matching (block_size,) row of per-token scales
        in_specs += [
            pl.BlockSpec((1, bs), lambda b, j, bt, ps: (bt[b, j], 0)),
            pl.BlockSpec((1, bs), lambda b, j, bt, ps: (bt[b, j], 0)),
        ]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block tables + positions
        grid=(B, nbl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d),
                               lambda b, j, bt, ps: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rep, d), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, d), q.dtype),
        name="mxtpu_paged_decode",
        interpret=interpret,
    )(block_tables, pos, *operands)
    return out.reshape(B, h * d)


def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, scale,
                           k_scale=None, v_scale=None):
    """One decode step of attention against a paged KV cache.

    q : (B, H, D) current-position queries, already rotated.
    k_pool / v_pool : (num_blocks, block_size, KVH, D) — ONE layer's
        slice of the engine's pool; f32, bf16, or fp8 codes.
    block_tables : (B, n_blocks_bucket) int32 physical block ids per
        sequence (null-block padded).
    pos : (B,) int32 position being written this step; cache positions
        ``<= pos`` participate, everything later (write-ahead garbage,
        padding) is masked.
    scale : softmax scale (1/sqrt(D)).
    k_scale / v_scale : (num_blocks, block_size) f32 per-token-row
        amax scales for an fp8 pool (ONE layer's plane), or None for
        f32/bf16 pools.  Gathered by the same block table and applied
        after the gather in both bodies.

    Returns (B, H*D).  Traced inside the engine's compiled decode /
    verify graphs — both bodies are pure jnp/pallas on jax arrays.
    """
    bs, kvh, d = k_pool.shape[1:]
    if _use_pallas(bs, kvh, d):
        return _pallas_paged(q, k_pool, v_pool, block_tables, pos,
                             scale, k_scale=k_scale, v_scale=v_scale,
                             interpret=kernel_mode() == "interpret")
    return _fallback(q, k_pool, v_pool, block_tables, pos, scale,
                     k_scale=k_scale, v_scale=v_scale)
