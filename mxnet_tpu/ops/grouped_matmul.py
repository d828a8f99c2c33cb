"""Grouped matrix product: Pallas TPU kernel + ``lax.ragged_dot`` fallback.

``grouped_matmul(lhs (R, K), rhs (G, K, N), group_sizes (G,))`` multiplies
the first ``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next
``group_sizes[1]`` by ``rhs[1]`` and so on: the expert products of a
dropless mixture-of-experts layer (``parallel/moe.py``), whose rows arrive
sorted by expert in a buffer sized for the worst case.  The groups need not
fill the buffer: rows past ``sum(group_sizes)`` are never read, cost no
work, and come back as zeros.

Three ways to run, as in ``flash_attention``: on a TPU the Pallas kernel
``mxtpu_gmm`` (derived from JAX's ``pallas.ops.tpu.megablox``: row tiles of
``_TM`` are visited in order, each with the group it belongs to from a
scalar-prefetched table, a tile that straddles two groups once for each,
and the grid ends at the last routed row); inside
``kernel_mode.interpret_kernels()`` the same kernel in the interpreter;
otherwise ``lax.ragged_dot`` (which XLA differentiates itself), its
unrouted rows masked: on a TPU it leaves them unspecified.  The kernel
path has a custom VJP of two more kernels: ``mxtpu_gmm_dlhs`` (the same body
with ``rhs`` transposed) and ``mxtpu_gmm_drhs`` (per group
``lhs[rows].T @ dout[rows]``).  Counters ``moe.gmm.pallas`` /
``moe.gmm.xla`` say which was traced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telem
from .kernel_mode import kernel_mode

__all__ = ["grouped_matmul"]

_TM = 128                       # rows of lhs a grid step takes
# what one step's double-buffered blocks and float32 accumulator may take of
# VMEM: under Mosaic's default scoped limit of 16 MiB (flash_attention.py)
_VMEM_BUDGET = 12 * 2 ** 20


def _tile(n, step_bytes):
    """The largest divisor of ``n`` that is a multiple of 128 (or ``n``
    itself) at which a grid step's blocks, ``step_bytes(t)``, fit the
    budget; the smallest such divisor where none does."""
    divisors = [t for t in range(n, 0, -1)
                if n % t == 0 and (t % 128 == 0 or t == n)]
    for t in divisors:
        if step_bytes(t) <= _VMEM_BUDGET:
            return t
    return divisors[-1]


def _group_metadata(group_sizes, m, tm, visit_empty_groups):
    """``(group_offsets (G+1,), group_ids, m_tile_ids, num_tiles)``: for each
    grid step which group and which row tile it works on.  A tile that holds
    rows of two groups appears once for each, consecutively, so an output
    tile is only ever revisited at once.  Only the first ``num_tiles`` steps
    exist: tiles past the last routed row are not in the grid.  With
    ``visit_empty_groups`` an empty group still gets one step (the drhs
    kernel zeroes its output there)."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    steps = tiles_m + g - 1
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    rounded = (ends + tm - 1) // tm * tm - starts // tm * tm
    group_tiles = jnp.where(group_sizes == 0, 0, rounded // tm)
    if visit_empty_groups:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), group_tiles,
                           total_repeat_length=steps)
    # a tile is visited once by the group that owns its first row and once
    # more for every other group that starts inside it
    aligned = (starts % tm == 0) | (group_sizes == 0)
    if visit_empty_groups:
        aligned = jnp.where(group_sizes == 0, False, aligned)
    extra = jnp.where(aligned, tiles_m, starts // tm)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[extra].add(1)[:tiles_m] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=steps)
    return offsets, group_ids, m_tile_ids, jnp.sum(group_tiles)


def _rows_of_group(offsets, group_ids, m_tile_ids, step, tm, width):
    """(tm, width) mask: the rows of this step's tile that are its group's."""
    group = group_ids[step]
    rows = m_tile_ids[step] * tm + lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return (rows >= offsets[group]) & (rows < offsets[group + 1])


def _gmm(lhs, rhs, group_sizes, transpose_rhs, name, interpret):
    """``out[rows of g] = lhs[rows of g] @ rhs[g]`` (``rhs[g].T`` when
    ``transpose_rhs``); rows past the total are zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = _TM
    isz = lhs.dtype.itemsize
    # the whole contraction in one block: a group's weights are then fetched
    # once, however many row tiles it has (the block index does not change)
    tn = _tile(n, lambda t: 2 * (tm * k + k * t + tm * t) * isz
               + tm * t * 4)
    offsets, group_ids, m_tile_ids, num_tiles = _group_metadata(
        group_sizes, m, tm, visit_empty_groups=False)

    def operand(x):         # the CPU has no bf16 x bf16 -> f32 dot
        return x.astype(jnp.float32) if interpret else x

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref):
        step = pl.program_id(1)
        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        acc = lax.dot_general(operand(lhs_ref[...]), operand(rhs_ref[...]),
                              dims, preferred_element_type=jnp.float32)
        mine = _rows_of_group(offsets, group_ids, m_tile_ids, step, tm, tn)
        # rows of the tile that are another group's keep what that group's
        # step wrote (the block stays in VMEM between the two visits)
        out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)

    def lhs_index(n_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], 0

    def rhs_index(n_i, step, offsets, group_ids, m_tile_ids):
        return (group_ids[step], n_i, 0) if transpose_rhs \
            else (group_ids[step], 0, n_i)

    def out_index(n_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], n_i

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, k), lhs_index),
                pl.BlockSpec((None, tn, k) if transpose_rhs
                             else (None, k, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(n // tn, num_tiles),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids, lhs, rhs)
    # rows past the last routed one were never written: make them zeros (an
    # elementwise select that XLA fuses into whatever reads the result)
    routed = lax.broadcasted_iota(jnp.int32, (m, 1), 0) < offsets[-1]
    return jnp.where(routed, out, jnp.zeros((), out.dtype))


def _tgmm(lhs, dout, group_sizes, out_dtype, name, interpret):
    """``out[g] = lhs[rows of g].T @ dout[rows of g]``: (G, K, N), zeros for
    an empty group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    g = group_sizes.shape[0]
    tm = _TM
    isz, osz = lhs.dtype.itemsize, jnp.dtype(out_dtype).itemsize
    tn = _tile(n, lambda t: 2 * (tm * k + tm * t) * isz
               + k * t * (4 + 2 * osz))
    offsets, group_ids, m_tile_ids, num_tiles = _group_metadata(
        group_sizes, m, tm, visit_empty_groups=True)

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, dout_ref, out_ref,
               acc):
        step = pl.program_id(1)
        last = pl.num_programs(1) - 1
        group = group_ids[step]
        before = group_ids[jnp.maximum(step - 1, 0)]
        after = group_ids[jnp.minimum(step + 1, last)]

        @pl.when((step == 0) | (before != group))
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(offsets[group + 1] > offsets[group])
        def _accumulate():
            # the rows of the tile that are another group's, or past the
            # last routed row (never written by anyone), count as zeros
            a = jnp.where(
                _rows_of_group(offsets, group_ids, m_tile_ids, step, tm, k),
                lhs_ref[...].astype(jnp.float32), 0.0)
            b = jnp.where(
                _rows_of_group(offsets, group_ids, m_tile_ids, step, tm, tn),
                dout_ref[...].astype(jnp.float32), 0.0)
            cast = jnp.float32 if interpret else lhs_ref.dtype
            acc[...] += lax.dot(a.swapaxes(0, 1).astype(cast),
                                b.astype(cast),
                                preferred_element_type=jnp.float32)

        @pl.when((step == last) | (after != group))
        def _store():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def row_tile(n_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], 0

    def dout_index(n_i, step, offsets, group_ids, m_tile_ids):
        return m_tile_ids[step], n_i

    def out_index(n_i, step, offsets, group_ids, m_tile_ids):
        return group_ids[step], 0, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, k), row_tile),
                      pl.BlockSpec((tm, tn), dout_index)],
            out_specs=pl.BlockSpec((None, k, tn), out_index),
            grid=(n // tn, num_tiles),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_gmm(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, False, "mxtpu_gmm", interpret)


def _kernel_gmm_fwd(lhs, rhs, group_sizes, interpret):
    return _kernel_gmm(lhs, rhs, group_sizes, interpret), \
        (lhs, rhs, group_sizes)


def _kernel_gmm_bwd(interpret, res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm(dout, rhs, group_sizes, True, "mxtpu_gmm_dlhs", interpret)
    drhs = _tgmm(lhs, dout, group_sizes, rhs.dtype, "mxtpu_gmm_drhs",
                 interpret)
    return dlhs, drhs, None


_kernel_gmm.defvjp(_kernel_gmm_fwd, _kernel_gmm_bwd)


def _use_pallas(m, k, n):
    """Whether the kernel takes these shapes: a kernel mode is set, the rows
    come in whole tiles and both widths fill whole lanes."""
    return kernel_mode() is not None and m % _TM == 0 and \
        k % 128 == 0 and n % 128 == 0


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs (R, K) x rhs (G, K, N)`` by ``group_sizes (G,)`` -> ``(R, N)``.

    Rows ``[sum(group_sizes[:g]), sum(group_sizes[:g + 1]))`` of ``lhs`` are
    multiplied by ``rhs[g]``; ``sum(group_sizes)`` may be less than ``R``,
    and the rows past it are not read and come back as zeros (their
    gradients too).  float32 accumulation, result in ``lhs``'s dtype.
    Differentiable in ``lhs`` and ``rhs``."""
    group_sizes = group_sizes.astype(jnp.int32)
    rhs = rhs.astype(lhs.dtype)
    m, k = lhs.shape
    if not _use_pallas(m, k, rhs.shape[2]):
        _telem.inc("moe.gmm.xla")
        # ragged_dot leaves the rows past the total unspecified (zeros on
        # the CPU, whatever was there on a TPU): mask them going in and
        # coming out, so that result and gradients keep the contract
        routed = lax.broadcasted_iota(jnp.int32, (m, 1), 0) < \
            jnp.sum(group_sizes)
        zero = jnp.zeros((), lhs.dtype)
        out = lax.ragged_dot(jnp.where(routed, lhs, zero), rhs, group_sizes)
        return jnp.where(routed, out, zero)
    _telem.inc("moe.gmm.pallas")
    return _kernel_gmm(lhs, rhs, group_sizes, kernel_mode() == "interpret")
