"""``ops.grouped_matmul``: the interpreted ``mxtpu_gmm`` kernels and the
``lax.ragged_dot`` fallback against a Python loop over the groups."""
import contextlib
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops.grouped_matmul import grouped_matmul
from mxnet_tpu.ops.kernel_mode import interpret_kernels

mod = importlib.import_module("mxnet_tpu.ops.grouped_matmul")


def _loop(lhs, rhs, sizes):
    """The definition: one plain product per group, zeros past the total."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float64)
    at = 0
    for g, size in enumerate(sizes):
        out[at:at + size] = np.asarray(lhs[at:at + size], np.float64) @ \
            np.asarray(rhs[g], np.float64)
        at += size
    return out


def _loop_grads(lhs, rhs, sizes, dout):
    dlhs = np.zeros(lhs.shape, np.float64)
    drhs = np.zeros(rhs.shape, np.float64)
    at = 0
    for g, size in enumerate(sizes):
        rows = slice(at, at + size)
        dlhs[rows] = np.asarray(dout[rows], np.float64) @ \
            np.asarray(rhs[g], np.float64).T
        drhs[g] = np.asarray(lhs[rows], np.float64).T @ \
            np.asarray(dout[rows], np.float64)
        at += size
    return dlhs, drhs


CASES = {
    # rows 384 = three tiles of 128
    "tile_multiples": [128, 0, 256, 0],
    "ragged": [37, 91, 5, 130],                 # no multiple of the tile
    "empty_first_and_last": [0, 200, 0, 100, 0],
    "rows_past_the_total": [10, 0, 150],        # 160 of 384 rows routed
    "nothing_routed": [0, 0, 0],
    "one_group_fills_it": [384],
}


def _operands(sizes, k=256, n=128, rows=384, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(rows, k), jnp.float32),
            jnp.asarray(rng.randn(len(sizes), k, n), jnp.float32),
            jnp.asarray(sizes, jnp.int32),
            jnp.asarray(rng.randn(rows, n), jnp.float32))


# float32 operands, float32 accumulation over 256 (forward), 128 (dlhs) or
# up to 384 (drhs) products of unit-variance numbers, against a float64
# loop: 1e-4 absolute is ten times the float32 rounding of such a sum.
@pytest.mark.parametrize("mode", ["interpret", "xla"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_both_backward_products_match_a_loop(case, mode):
    sizes = CASES[case]
    lhs, rhs, gs, dout = _operands(sizes)
    total = sum(sizes)
    with interpret_kernels() if mode == "interpret" else contextlib.nullcontext():
        assert (mod._use_pallas(384, 256, 128)) == (mode == "interpret")
        out, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, gs), lhs, rhs)
        dlhs, drhs = vjp(dout)
    np.testing.assert_allclose(out, _loop(lhs, rhs, sizes), atol=1e-4)
    want_dlhs, want_drhs = _loop_grads(lhs, rhs, sizes, dout)
    np.testing.assert_allclose(dlhs, want_dlhs, atol=1e-4)
    np.testing.assert_allclose(drhs, want_drhs, atol=1e-4)
    # rows past the total: zeros out, zero gradient back
    assert not np.asarray(out[total:]).any()
    assert not np.asarray(dlhs[total:]).any()


def test_rows_past_the_total_are_never_read():
    """NaNs in the unrouted rows of lhs and of the cotangent reach neither
    the result nor a gradient: the buffer's tail is free to hold anything."""
    sizes = CASES["rows_past_the_total"]
    lhs, rhs, gs, dout = _operands(sizes, seed=1)
    total = sum(sizes)
    bad_lhs = lhs.at[total:].set(jnp.nan)
    bad_dout = dout.at[total:].set(jnp.nan)
    with interpret_kernels():
        out, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, gs), bad_lhs,
                           rhs)
        dlhs, drhs = vjp(bad_dout)
    np.testing.assert_allclose(out, _loop(lhs, rhs, sizes), atol=1e-4)
    want_dlhs, want_drhs = _loop_grads(lhs, rhs, sizes, dout)
    np.testing.assert_allclose(dlhs, want_dlhs, atol=1e-4)
    np.testing.assert_allclose(drhs, want_drhs, atol=1e-4)


def test_bfloat16_operands_float32_accumulation():
    """bf16 in, bf16 out, as amp feeds it: against the loop on the same
    bf16-rounded operands the only error is the result's own rounding
    (2^-8 relative of values up to ~3 sqrt(256))."""
    sizes = CASES["ragged"]
    lhs, rhs, gs, _ = _operands(sizes, seed=2)
    lhs16, rhs16 = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    with interpret_kernels():
        out = grouped_matmul(lhs16, rhs16, gs)
    assert out.dtype == jnp.bfloat16
    want = _loop(lhs16.astype(jnp.float32), rhs16.astype(jnp.float32), sizes)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=2 ** -7, atol=2 ** -7)


def test_counters_say_which_path_was_traced():
    lhs, rhs, gs, _ = _operands(CASES["ragged"])
    before = {k: telemetry.value(k) or 0
              for k in ("moe.gmm.pallas", "moe.gmm.xla")}
    grouped_matmul(lhs, rhs, gs)
    with interpret_kernels():
        grouped_matmul(lhs, rhs, gs)
        # shapes the kernel does not tile take the fallback there too
        grouped_matmul(lhs[:100], rhs, jnp.asarray([50, 20, 0, 30]))
    assert telemetry.value("moe.gmm.pallas") - before["moe.gmm.pallas"] == 1
    assert telemetry.value("moe.gmm.xla") - before["moe.gmm.xla"] == 2


def test_group_metadata_visits_each_routed_tile_once_per_group():
    offsets, group_ids, m_tile_ids, tiles = mod._group_metadata(
        jnp.asarray([37, 91, 5, 130], jnp.int32), 384, 128, False)
    # rows 0..262: groups 0 and 1 share tile 0 (group 1 ends with it),
    # 2 and 3 share tile 1, and 3 goes on into tile 2
    assert int(tiles) == 5
    assert list(np.asarray(group_ids[:5])) == [0, 1, 2, 3, 3]
    assert list(np.asarray(m_tile_ids[:5])) == [0, 0, 1, 1, 2]
    assert list(np.asarray(offsets)) == [0, 37, 128, 133, 263]
    _, group_ids, m_tile_ids, tiles = mod._group_metadata(
        jnp.asarray([0, 200, 0], jnp.int32), 384, 128, True)
    assert int(tiles) == 4          # the empty groups get a step each
    assert list(np.asarray(group_ids[:4])) == [0, 1, 1, 2]

