"""Flash attention with a value head dim of its own (latent attention:
Q.K over 192, V at 128).  Scan path and interpreted kernel, forward and
gradients, causal and not, against plain softmax(QK^T)V."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import flash_attention
from mxnet_tpu.ops.kernel_mode import interpret_kernels

mod = importlib.import_module("mxnet_tpu.ops.flash_attention")


def _naive(q, k, v, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(np.tril(np.ones(s.shape[-2:], bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _qkv(seed, b, h, seq, d, dv):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, seq, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, seq, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, seq, dv), jnp.float32))


# float32 throughout: both sides sum the same products in another order,
# so 2e-5 (values) and 1e-4 (gradients, one more reduction) as in
# test_flash_attention.py
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d,dv", [(24, 16), (16, 24)])
def test_scan_forward_and_gradients_with_unequal_head_dims(causal, d, dv):
    q, k, v = _qkv(0, 2, 3, 64, d, dv)
    out = flash_attention(q, k, v, causal=causal)
    assert out.shape == (2, 3, 64, dv)
    np.testing.assert_allclose(out, _naive(q, k, v, causal),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_naive(*a, causal) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,blocks", [(128, "one_pass"),
                                        (256, "streaming"),
                                        (384, "streaming_three_classes")])
def test_interpreted_kernel_with_unequal_head_dims(causal, seq, blocks):
    """The Pallas body itself (192/128, the MLA shape), one-pass and
    streaming (at 384 a causal row holds a visible, a cut and a dead
    block), through the public op: forward, log-sum-exp and gradients."""
    q, k, v = _qkv(1, 1, 2, seq, 192, 128)
    with interpret_kernels():
        assert mod._use_pallas(seq, seq, 192, 128) is not None
        out3, lse = mod._pallas_forward(
            q[0], k[0], v[0], causal, 192 ** -0.5, 128, 128, interpret=True)
        got = jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    assert out3.shape == (2, seq, 128)
    ref, ref_lse = mod._scan_forward(q[0], k[0], v[0], causal,
                                     192 ** -0.5, 128)
    np.testing.assert_allclose(out3, ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out3, _naive(q, k, v, causal)[0],
                               rtol=2e-5, atol=2e-5)
    want = jax.grad(lambda *a: jnp.sum(_naive(*a, causal) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_rows_per_program_counts_both_head_dims():
    """With dv == d the rule is what it was; a narrower V takes fewer
    bytes, so no fewer rows fit."""
    same = mod._rows_per_program(1536, 128, 128, 64, 2, False)
    assert same == mod._rows_per_program(1536, 128, 128, 64, 2, False, 64) \
        == 32
    assert mod._program_vmem_bytes(4, 512, 512, 64, 2, True) == \
        mod._program_vmem_bytes(4, 512, 512, 64, 2, True, 64)
    assert mod._program_vmem_bytes(2, 512, 512, 192, 2, True, 128) < \
        mod._program_vmem_bytes(2, 512, 512, 192, 2, True, 192)
    # the MLA call of the kanana cell: 64 rows, L = 4096 streaming
    g = mod._rows_per_program(64, 512, 512, 192, 2, True, 128)
    assert 64 % g == 0 and mod._program_vmem_bytes(
        g, 512, 512, 192, 2, True, 128) <= mod._VMEM_BUDGET


def test_kernel_declined_for_a_value_dim_it_does_not_tile():
    with interpret_kernels():
        assert mod._use_pallas(128, 128, 192, 128) is not None
        assert mod._use_pallas(128, 128, 192, 48) is None
        assert mod._use_pallas(128, 128, 64) is not None
