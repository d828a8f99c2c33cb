"""Block-diffusion attention (``ops.flash_attention``): the flash kernels'
block rule ``q // beta >= k // beta + offset`` against a dense mask over
positions, the kernels under it in the Pallas interpreter against a dense
masked softmax (a query that sees no key gives 0 and a log-sum-exp of
-inf, forward and backward), and ``block_diffusion_attention`` — clean
block-causal, noisy over the clean blocks before its own and its own noisy
block — with its gradients, against the same softmax over the (2L)^2 mask
written out."""
import contextlib
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops.kernel_mode import interpret_kernels

mod = importlib.import_module("mxnet_tpu.ops.flash_attention")

# (lq, lk, bq, bk): one block, square blocks, Q blocks over and under the KV
# blocks, and lengths that differ
_SHAPES = [(256, 256, 256, 256), (512, 512, 128, 128), (1024, 512, 256, 128),
           (512, 1024, 128, 256), (384, 384, 128, 128)]


def _rule_mask(lq, lk, beta, offset):
    return (np.arange(lq)[:, None] // beta) >= \
        (np.arange(lk)[None, :] // beta) + offset


@pytest.mark.parametrize("beta,offset", [(1, 0), (4, 0), (4, 1), (128, 1),
                                         (2, 3)])
@pytest.mark.parametrize("shape", _SHAPES)
def test_block_rule_classes_extent_and_fetch_against_the_mask(shape, beta,
                                                              offset):
    """Every (Q block, KV block) pair sorted by the kernels' predicates
    against the mask over positions: live where any score is visible, cut
    where some but not all are; the walk's extent counts the same; a dead
    step names the row's last live K / V block and the backward's skipped
    steps the first live Q block; the counts follow."""
    lq, lk, bq, bk = shape
    nq, nk = lq // bq, lk // bk
    visible = _rule_mask(lq, lk, beta, offset)
    counts = [0, 0]
    for i in range(nq):
        block = [visible[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
                 for j in range(nk)]
        live_blocks = [j for j in range(nk) if block[j].any()]
        assert live_blocks == list(range(len(live_blocks)))
        for j in range(nk):
            live, cut = mod._causal_block(i, j, bq, bk, beta, offset)
            assert live == block[j].any()
            assert (live and not cut) == block[j].all()
            fetched = int(mod._kv_block_fetched(i, j, bq, bk, beta, offset))
            assert fetched == (j if live else max(live_blocks or [0]))
            counts[0] += live
            counts[1] += live and cut
        shown, reached = mod._causal_extent(i, bq, bk, beta, offset)
        assert [mod._causal_block(i, j, bq, bk, beta, offset)[0]
                for j in range(nk)] == [j < reached for j in range(nk)]
        assert [not mod._causal_block(i, j, bq, bk, beta, offset)[1]
                for j in range(nk)] == [j < shown for j in range(nk)]
    for j in range(nk):
        first = next((i for i in range(nq)
                      if visible[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
                      .any()), None)
        if first is not None:
            assert mod._first_live_q_block(j, bq, bk, beta, offset) == first
    assert mod._forward_block_counts(lq, lk, bq, bk, (beta, offset)) == \
        tuple(counts)
    if (beta, offset) == (1, 0):        # the causal call's own counts
        assert mod._forward_block_counts(lq, lk, bq, bk, True) == \
            tuple(counts)


def _dense(q, k, v, mask, scale):
    """``(out, lse)`` of softmax over the visible keys; a query that sees
    none gives 0 and -inf.  q (rows, Lq, d), k, v (rows, Lk, d)."""
    s = jnp.where(mask, jnp.einsum("bqd,bkd->bqk", q, k) * scale, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * mask
    total = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bqk,bkd->bqd", p / jnp.maximum(total, 1e-30), v)
    return out, jnp.where(mask.any(-1), (m + jnp.log(total))[..., 0],
                          -jnp.inf)


@pytest.mark.parametrize("beta,offset,lq,bq", [
    (4, 0, 512, 128), (4, 1, 512, 128), (4, 1, 128, 128), (8, 1, 256, 128)])
def test_kernels_under_the_block_rule_match_a_dense_masked_softmax(
        beta, offset, lq, bq):
    """The forward kernel's output and log-sum-exp (streaming and one-pass)
    and the backward kernel's three gradients, in the interpreter; the
    first block's queries under an offset see nothing: 0, -inf, and no
    gradient through them."""
    rng = np.random.RandomState(beta + offset + lq)
    q, k, v, do = (jnp.asarray(rng.randn(2, lq, 64), jnp.float32)
                   for _ in range(4))
    mask = _rule_mask(lq, lq, beta, offset)
    scale = 0.125
    out, lse = mod._pallas_forward(q, k, v, (beta, offset), scale, bq, bq,
                                   interpret=True)
    want, want_lse = _dense(q, k, v, mask, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.isneginf(np.asarray(lse)),
                                  ~mask.any(1)[None].repeat(2, 0))
    seen = mask.any(1)
    np.testing.assert_allclose(np.asarray(lse)[:, seen],
                               np.asarray(want_lse)[:, seen], rtol=2e-5,
                               atol=2e-5)
    ref_out, ref_lse = mod._scan_forward(q, k, v, (beta, offset), scale, 128)
    np.testing.assert_allclose(np.asarray(ref_out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ref_lse) == -np.inf,
                                  np.asarray(lse) == -np.inf)

    _, vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, mask, scale)[0], q, k, v)
    with interpret_kernels():
        got = mod._flash_bwd((beta, offset), scale, None,
                             (q, k, v, out, lse, None), do)
    for a, b in zip(got, vjp(do)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    if offset:
        assert not np.asarray(got[0])[:, :beta * offset].any()


@pytest.mark.parametrize("offset", [0, 1])
def test_grouped_backward_reads_each_kv_head_in_place(offset):
    """The backward kernel under the block rule with K / V at their own
    heads (2 kv heads, 4 query heads each): a program of a kv head's query
    heads, Q blocks of 512 outer over two KV blocks, dK / dV summed over the
    heads in VMEM — against the same backward with K / V repeated to the
    query heads (dK / dV summed back) and against the dense masked softmax.
    Under the offset the first query block's first 4 queries see no key:
    their log-sum-exp is -inf and their gradients 0."""
    rows, group, lq, beta, d = 2, 4, 1024, 4, 64
    rng = np.random.RandomState(7 + offset)
    q, do = (jnp.asarray(rng.randn(rows * group, lq, d), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(rng.randn(rows, lq, d), jnp.float32)
            for _ in range(2))
    kr, vr = (jnp.repeat(a, group, axis=0) for a in (k, v))
    mask = _rule_mask(lq, lq, beta, offset)
    scale = 0.125
    out, lse = mod._scan_forward(q, kr, vr, (beta, offset), scale, 128)
    assert np.isneginf(np.asarray(lse)).any() == bool(offset)
    telemetry.reset()
    with interpret_kernels():
        got = mod._flash_bwd((beta, offset), scale, None,
                             (q, k, v, out, lse, None), do)
        assert telemetry.value("flash.bwd.heads_per_kv_block") == group
        assert telemetry.value("flash.bwd.rows_per_program") == group
        rep = mod._flash_bwd((beta, offset), scale, None,
                             (q, kr, vr, out, lse, None), do)
        assert telemetry.value("flash.bwd.heads_per_kv_block") == 1
    assert telemetry.value("bd.attn.bwd.pallas") == 2
    rep = (rep[0],) + tuple(a.reshape(rows, group, lq, d).sum(1)
                            for a in rep[1:])
    _, vjp = jax.vjp(lambda q, k, v: _dense(
        q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0),
        mask, scale)[0], q, k, v)
    for name, a, b, c in zip("qkv", got, rep, vjp(do)):
        assert a.shape == c.shape, name
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-4,
                                   atol=1e-4, err_msg=name)
    if offset:
        assert not np.asarray(got[0])[:, :beta * offset].any()


def _bd_mask(length, beta):
    """The (2L, 2L) mask, the noisy half first."""
    blk = np.arange(length) // beta
    clean = blk[None, :] <= blk[:, None]
    offset = blk[None, :] < blk[:, None]
    diagonal = blk[None, :] == blk[:, None]
    none = np.zeros_like(clean)
    return np.block([[diagonal, offset], [none, clean]])


def _bd_dense(q, k, v, beta, scale):
    rows = k.shape[0]
    group = q.shape[0] // rows
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    return _dense(q, k, v, _bd_mask(q.shape[1] // 2, beta), scale)[0]


@pytest.mark.parametrize("kernels", [True, False], ids=["pallas", "scan"])
@pytest.mark.parametrize("rows,group", [(2, 1), (1, 4)],
                         ids=["mha", "gqa4"])
def test_block_diffusion_attention_and_gradients_match_the_dense_mask(
        kernels, rows, group):
    """Both halves' outputs and the gradients of q, k and v against the
    dense (2L)^2 mask: in the interpreter at one 256-block a half (the
    offset call leaves its first 4 queries without a key; the streaming
    body is the test above's) and through the XLA fallback at a length no
    kernel takes."""
    length, beta, d = (256 if kernels else 40), 4, 64
    rng = np.random.RandomState(rows * group)
    q = jnp.asarray(rng.randn(rows * group, 2 * length, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(rows, 2 * length, d), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.randn(rows * group, 2 * length, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)
    telemetry.reset()
    # without the interpreter the CPU has no kernel mode: the scans run
    with interpret_kernels() if kernels else contextlib.nullcontext():
        out = mod.block_diffusion_attention(q, k, v, beta, d ** -0.5)
        grads = jax.grad(loss(lambda q, k, v: mod.block_diffusion_attention(
            q, k, v, beta, d ** -0.5)), argnums=(0, 1, 2))(q, k, v)
    want = _bd_dense(q, k, v, beta, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    wants = jax.grad(loss(lambda q, k, v: _bd_dense(q, k, v, beta,
                                                    d ** -0.5)),
                     argnums=(0, 1, 2))(q, k, v)
    for got, ref in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
    suffix = ".pallas" if kernels else ".xla"
    assert telemetry.value("bd.attn.fwd" + suffix) >= 2
    assert telemetry.value("bd.attn.bwd" + suffix) == 2
    assert not telemetry.value("flash.fwd.pallas")
    assert telemetry.value("bd.block_length") == beta
    assert telemetry.value("bd.offset_rows_empty") == beta


def test_block_diffusion_kernels_carry_names_of_their_own():
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(1, 512, 64), jnp.float32)
               for _ in range(3))
    with interpret_kernels():
        text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
            mod.block_diffusion_attention(q, k, v, 4, 0.125))))(q))
        causal = str(jax.make_jaxpr(lambda q: mod._flash_on(
            q, k, v, True, 0.125, None))(q))
    assert text.count("mxtpu_bd_attn_fwd") == 2
    assert text.count("mxtpu_bd_attn_bwd") == 2
    assert "mxtpu_flash" not in text
    assert "mxtpu_flash_fwd" in causal and "mxtpu_bd" not in causal


def test_block_diffusion_refuses_lengths_it_cannot_split():
    x = jnp.zeros((1, 12, 64))
    with pytest.raises(ValueError, match="blocks of 4"):
        mod.block_diffusion_attention(jnp.zeros((1, 10, 64)),
                                      jnp.zeros((1, 10, 64)),
                                      jnp.zeros((1, 10, 64)), 4, 0.125)
    with pytest.raises(ValueError, match="power of two"):
        mod.block_diffusion_attention(x, x, x, 3, 0.125)
