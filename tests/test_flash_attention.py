"""Flash attention (mxnet_tpu.ops.flash_attention) vs naive reference.

The kernel must match softmax(QK^T/sqrt(d))V exactly (same algorithm,
different memory schedule) in both values and gradients — the reference's
check_consistency idea (SURVEY.md §4.2) applied CPU-scan vs naive-XLA.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import flash_attention


def _naive(q, k, v, causal=False, scale=None):
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        lq, lk = s.shape[-2:]
        mask = np.tril(np.ones((lq, lk), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 3, 64, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 3, 64, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 3, 64, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    ref = _naive(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_cross_attention_shapes():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 48, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 96, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 96, 8), jnp.float32)
    out = flash_attention(q, k, v)
    ref = _naive(q, k, v)
    assert out.shape == (1, 2, 48, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_naive(causal):
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ndarray_tape_integration():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    rng = np.random.RandomState(3)
    q = mx.nd.array(rng.randn(1, 2, 16, 8).astype("float32"))
    k = mx.nd.array(rng.randn(1, 2, 16, 8).astype("float32"))
    v = mx.nd.array(rng.randn(1, 2, 16, 8).astype("float32"))
    q.attach_grad()
    with autograd.record():
        out = flash_attention(q, k, v)
        loss = (out * out).sum()
    loss.backward()
    ref = jax.grad(lambda q_, k_, v_: jnp.sum(
        _naive(q_, k_, v_) ** 2))(q.data, k.data, v.data)
    np.testing.assert_allclose(np.asarray(q.grad.data), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_mha_use_flash_matches_einsum_path():
    from mxnet_tpu.gluon.model_zoo.nlp.attention import MultiHeadAttention
    import mxnet_tpu as mx
    rng = np.random.RandomState(4)
    x = mx.nd.array(rng.randn(2, 12, 16).astype("float32"))
    cell = MultiHeadAttention(units=16, num_heads=4, use_flash=True)
    cell.initialize()
    out_flash = cell(x)                          # eval mode -> flash path
    cell._use_flash = False
    out_ref = cell(x)
    np.testing.assert_allclose(out_flash.asnumpy(), out_ref.asnumpy(),
                               rtol=2e-5, atol=2e-5)


def test_pallas_kernel_structure_compiles_in_interpret_mode():
    """Exercise the Pallas kernel itself (interpret=True on CPU)."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 128, 128), jnp.float32)
    k = jnp.asarray(rng.randn(2, 128, 128), jnp.float32)
    v = jnp.asarray(rng.randn(2, 128, 128), jnp.float32)
    # the package re-exports the op under the submodule's own name
    import importlib
    mod = importlib.import_module("mxnet_tpu.ops.flash_attention")

    def interp_forward(q, k, v, causal, sm_scale, bq, bk):
        return mod._pallas_forward(q, k, v, causal, sm_scale, bq, bk,
                                   interpret=True)

    for causal in (False, True):
        out, lse = interp_forward(q, k, v, causal, 1.0 / np.sqrt(128.0),
                                  128, 128)
        ref, ref_lse = mod._scan_forward(q, k, v, causal,
                                         1.0 / np.sqrt(128.0), 128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_is_wrapped_in_shard_map_under_a_partitioned_step():
    """XLA cannot partition a Mosaic call by itself (on the chip: "Mosaic
    kernels cannot be automatically partitioned"), so under an ambient dp
    mesh — what DataParallelTrainer's psum path sets while it traces —
    the kernel goes inside a shard_map over 'dp'; with no ambient mesh
    (one chip, or a trace that is already per chip inside ZeRO-1's
    shard_map) it is called bare.  Results agree either way."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    from mxnet_tpu.parallel import make_mesh, mesh_scope
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(8, 2, 128, 64), jnp.float32)
               for _ in range(3))

    def fresh():
        # the ambient mesh is read while tracing and is no part of any
        # jax cache key: every trace below gets a function of its own
        return lambda q, k, v: flash_attention(q, k, v, causal=True)

    with interpret_kernels():
        with mesh_scope(mesh):
            wrapped = str(jax.make_jaxpr(fresh())(q, k, v))
            sharded = [jax.device_put(a, NamedSharding(mesh, P("dp")))
                       for a in (q, k, v)]
            out = jax.jit(fresh())(*sharded)
        with mesh_scope(None):
            bare = str(jax.make_jaxpr(fresh())(q, k, v))
    assert "shard_map" in wrapped and "pallas_call" in wrapped
    assert "shard_map" not in bare and "pallas_call" in bare
    assert out.sharding.spec[0] == "dp"
    np.testing.assert_allclose(np.asarray(out), np.asarray(fresh()(q, k, v)),
                               rtol=2e-5, atol=2e-5)
