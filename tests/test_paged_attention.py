"""Paged decode attention (mxnet_tpu.ops.paged_attention), ISSUE 17.

The gate that matters on CPU: the XLA fallback is BITWISE the engine's
original inline formulation (dense gather through the block table +
``llama._cache_attention``) — so ``MXTPU_PAGED_ATTN`` is a bitwise-inert
routing knob anywhere the Pallas body doesn't engage.  The Pallas body
itself compiles only on TPU backends; here we assert its ROUTING
(``_use_pallas`` geometry gate) and skip execution off-TPU, the
flash_attention discipline.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import paged_decode_attention
from mxnet_tpu.ops.paged_attention import _fallback, _use_pallas

_ON_TPU = jax.default_backend() == "tpu"


def _geometry(rng, B=3, h=4, kvh=2, d=8, num_blocks=12, bs=4, nbl=3):
    """Random pools + per-sequence block tables with DISTINCT physical
    blocks and ragged positions (some sequences mid-block, write-ahead
    garbage past pos)."""
    q = jnp.asarray(rng.randn(B, h, d), jnp.float32)
    k_pool = jnp.asarray(rng.randn(num_blocks, bs, kvh, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(num_blocks, bs, kvh, d), jnp.float32)
    # non-trivial tables: out-of-order physical blocks, 0 as null pad
    tables = np.zeros((B, nbl), np.int32)
    perm = rng.permutation(np.arange(1, num_blocks))
    tables[0] = perm[:nbl]                      # full context
    tables[1, :2] = perm[nbl:nbl + 2]           # 2 blocks + null pad
    tables[2, :1] = perm[nbl + 2:nbl + 3]       # mid-first-block
    tables = jnp.asarray(tables)
    pos = jnp.asarray([nbl * bs - 1, bs + 1, 1], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    return q, k_pool, v_pool, tables, pos, scale


def _inline_reference(q, k_pool, v_pool, tables, pos, scale):
    """The engine's pre-ISSUE-17 decode attention, hand-inlined (the
    exact expression the fallback replaced)."""
    from mxnet_tpu.gluon.model_zoo.nlp.llama import _cache_attention
    B = q.shape[0]
    nbl = tables.shape[1]
    bs, kvh, d = k_pool.shape[1:]
    L = nbl * bs
    ck = k_pool[tables].reshape(B, L, kvh, d).transpose(0, 2, 1, 3)
    cv = v_pool[tables].reshape(B, L, kvh, d).transpose(0, 2, 1, 3)
    valid = jnp.arange(L)[None, :] <= pos[:, None]
    return _cache_attention(q, ck, cv, valid, scale)


def test_fallback_bitwise_matches_inline_gather():
    rng = np.random.RandomState(0)
    args = _geometry(rng)
    out = _fallback(*args)
    ref = _inline_reference(*args)
    assert out.shape == ref.shape == (3, 4 * 8)
    # BITWISE, not allclose: same ops in the same order
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_public_entry_routes_to_fallback_off_tpu():
    if _ON_TPU:
        pytest.skip("TPU backend: the Pallas body engages")
    rng = np.random.RandomState(1)
    args = _geometry(rng)
    out = paged_decode_attention(*args)
    assert np.array_equal(np.asarray(out),
                          np.asarray(_inline_reference(*args)))


def test_fallback_masks_write_ahead_garbage():
    """Positions past ``pos`` (verify write-ahead, table padding) must
    contribute exactly nothing: poisoning them cannot move the output."""
    rng = np.random.RandomState(2)
    q, k_pool, v_pool, tables, pos, scale = _geometry(rng)
    out = _fallback(q, k_pool, v_pool, tables, pos, scale)
    # poison every pool position a sequence is NOT allowed to see; the
    # null block 0 is shared as padding, so poison a row 1's pad target
    kp = np.asarray(k_pool).copy()
    vp = np.asarray(v_pool).copy()
    poison_blk = int(np.asarray(tables)[1, 2])   # the null pad block
    kp[poison_blk] = 1e6
    vp[poison_blk] = -1e6
    # row 2 sees only positions 0..1 of its first block: poison the rest
    blk2 = int(np.asarray(tables)[2, 0])
    kp[blk2, 2:] = 1e6
    vp[blk2, 2:] = -1e6
    out2 = _fallback(q, jnp.asarray(kp), jnp.asarray(vp), tables, pos,
                     scale)
    # row 0 attends everything it owns — untouched rows stay bitwise;
    # rows 1 and 2 must not see the poison
    assert np.array_equal(np.asarray(out2[1]), np.asarray(out[1]))
    assert np.array_equal(np.asarray(out2[2]), np.asarray(out[2]))


def test_use_pallas_geometry_gate():
    if _ON_TPU:
        # on TPU the gate is geometric only
        assert _use_pallas(block_size=8, kv_heads=2, head_dim=64)
    else:
        assert not _use_pallas(block_size=8, kv_heads=2, head_dim=64)
    # geometries Mosaic can't tile decline everywhere
    assert not _use_pallas(block_size=8, kv_heads=2, head_dim=48)
    assert not _use_pallas(block_size=6, kv_heads=2, head_dim=64)


def test_pallas_body_matches_fallback():
    """The kernel body against the gather fallback: compiled by Mosaic
    on a TPU, in the Pallas interpreter anywhere else."""
    from mxnet_tpu.ops.paged_attention import _pallas_paged
    rng = np.random.RandomState(3)
    # a Mosaic-tileable geometry: d=64, bs=8
    q = jnp.asarray(rng.randn(2, 4, 64), jnp.float32)
    k_pool = jnp.asarray(rng.randn(8, 8, 2, 64), jnp.float32)
    v_pool = jnp.asarray(rng.randn(8, 8, 2, 64), jnp.float32)
    tables = jnp.asarray([[3, 1, 0], [5, 0, 0]], jnp.int32)
    pos = jnp.asarray([13, 4], jnp.int32)
    out = _pallas_paged(q, k_pool, v_pool, tables, pos, 0.125,
                        interpret=not _ON_TPU)
    ref = _fallback(q, k_pool, v_pool, tables, pos, 0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
