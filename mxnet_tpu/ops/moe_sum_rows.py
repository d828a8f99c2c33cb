"""The expert layer's token-side sum: Pallas TPU kernels + XLA fallback.

``sum_rows(rows (R, d), row_of_choice (T, k), here (T, k), scale=None)``
is, in float32,

    out[t] = sum_j where(here[t, j], rows[row_of_choice[t, j]] * scale[t, j], 0)

from 0 and in ascending ``j`` (without ``scale``, the rows alone): the
combine of a dropless expert layer (``parallel/moe.py``, ``scale`` its
routing weights) and the transpose of its dispatch.  Each token's rows are
read where they lie in the buffer: no copy of the buffer in token order.

Three ways to run, as in ``grouped_matmul``: on a TPU two Pallas kernels.
``mxtpu_moe_sum_rows_slabs`` copies the routed rows into slabs of (2, d/2),
one a row: a row of the (R, d) array shares its tiles with its neighbours,
and a DMA takes whole tiles only.  ``mxtpu_moe_sum_rows`` takes a tile of
tokens a grid step, with each token's routed rows (packed to the front in
ascending ``j``) and their count as an SMEM block; the slabs stay in HBM,
and the step starts one DMA a routed row of the *next* tile into the other
half of a VMEM buffer, waits for its own, adds each token's rows into a
float32 sum and writes the tile out as rows.  A row that is not routed is
never fetched.  Inside ``kernel_mode.interpret_kernels()`` the same kernels
in the interpreter; otherwise the formula above in XLA (which gathers T rows
for every ``j``).  Counters ``moe.sum_rows.pallas`` / ``moe.sum_rows.xla``
say which was traced.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telem
from .grouped_matmul import _VMEM_BUDGET
from .kernel_mode import kernel_mode

__all__ = ["sum_rows"]

_MAX_TILE = 256                 # tokens (rows) a grid step takes, at most
_UNROLL = 2                     # tokens a turn of the step's loop


def _xla_sum_rows(rows, row_of_choice, here, scale):
    out = jnp.zeros((row_of_choice.shape[0], rows.shape[1]), jnp.float32)
    for j in range(row_of_choice.shape[1]):
        term = rows[row_of_choice[:, j]].astype(jnp.float32)
        if scale is not None:
            term = term * scale[:, j, None]
        out = out + jnp.where(here[:, j, None], term, 0)
    return out


def _tile(n, step_bytes):
    """The largest power of two from 8 up to ``_MAX_TILE`` that divides
    ``n`` and whose step, ``step_bytes(t)``, fits the budget; 8 where none
    does."""
    tile = 8
    while tile < _MAX_TILE and n % (2 * tile) == 0 and \
            step_bytes(2 * tile) <= _VMEM_BUDGET:
        tile *= 2
    return tile


def _slabs(rows, routed, interpret):
    """``rows`` (R, d) as (R, 2, d / 2), each row a slab that a DMA can
    address by a leading index.  Only the first ``routed`` rows are copied:
    the grid's later steps fetch and write nothing, and the slabs past them
    hold whatever was there."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = rows.shape
    block = _tile(n, lambda b: 4 * b * d * rows.dtype.itemsize)
    last = jnp.maximum(routed - 1, 0) // block

    def kernel(last_ref, rows_ref, out_ref):
        @pl.when(pl.program_id(0) <= last_ref[0])
        def _copy():
            both = rows_ref[...]
            out_ref[:, 0, :] = both[:, :d // 2]
            out_ref[:, 1, :] = both[:, d // 2:]

    def index(i, last_ref):
        return jnp.minimum(i, last_ref[0]), 0

    def slab_index(i, last_ref):
        return jnp.minimum(i, last_ref[0]), 0, 0

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, 2, d // 2), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // block,),
            in_specs=[pl.BlockSpec((block, d), index)],
            out_specs=pl.BlockSpec((block, 2, d // 2), slab_index)),
        name="mxtpu_moe_sum_rows_slabs",
        interpret=interpret,
    )(last.reshape(1).astype(jnp.int32), rows)


def _pallas_sum_rows(rows, table, scale, routed, interpret):
    """The sum over ``table`` (T, k + 1): the rows of each token's choices
    that are here, packed to the front in ascending ``j``, and how many
    there are; ``scale`` (T, k) packed the same way, or None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d = rows.shape[1]
    tokens, k = table.shape[0], table.shape[1] - 1
    rows = _slabs(rows, routed, interpret)
    # two halves of k rows a token, a float32 sum a token, and the float32
    # tile written out twice
    tile = _tile(tokens, lambda t: 2 * k * t * d * rows.dtype.itemsize
                 + 3 * t * d * 4)
    weighted = scale is not None
    unroll = min(_UNROLL, tile)

    def kernel(now_ref, ahead_ref, *refs):
        scale_ref = refs[0] if weighted else None
        rows_ref, out_ref, buf, sums, sems, issued = refs[weighted:]
        step = pl.program_id(0)
        half = step % 2

        def fetch(numbers, into, t):
            """Start a DMA for each routed row of token ``t`` of the tile
            ``numbers`` names, into half ``into``; how many."""
            def start(i, carry):
                pltpu.make_async_copy(rows_ref.at[numbers[t, i]],
                                      buf.at[into, i, t],
                                      sems.at[into]).start()
                return carry
            lax.fori_loop(0, numbers[t, k], start, 0)
            return numbers[t, k]

        def add(t):
            """Token ``t``'s sum, in ascending ``j`` from 0: the choices
            that are not here would add an exact 0 to a sum that is never
            -0, and are left out."""
            count = now_ref[t, k]

            def term(i, acc):
                row = buf[half, i, t].astype(jnp.float32)
                if weighted:
                    row = row * scale_ref[t, i]
                # (a select between the product and the sum, as in the XLA
                # form, where a compiler could fuse them into one rounding)
                return acc + jnp.where(i < count, row, 0.0)
            sums[t] = lax.fori_loop(0, count, term,
                                    jnp.zeros(sums.shape[1:], jnp.float32))

        @pl.when(step == 0)
        def _first():
            issued[0] = lax.fori_loop(
                0, tile, lambda t, n: n + fetch(now_ref, 0, t), jnp.int32(0))

        def wait(i, carry):
            pltpu.make_async_copy(rows_ref.at[0], buf.at[half, 0, 0],
                                  sems.at[half]).wait()
            return carry
        lax.fori_loop(0, issued[half], wait, 0)

        def tokens(body):
            """``body(t, n)`` over the tile's tokens, ``unroll`` a turn."""
            def turn(i, n):
                for u in range(unroll):
                    n = body(i * unroll + u, n)
                return n
            return lax.fori_loop(0, tile // unroll, turn, jnp.int32(0))

        # this tile's sums, and the next tile's DMAs started beside them
        @pl.when(step + 1 < pl.num_programs(0))
        def _sum_and_fetch():
            def token(t, n):
                add(t)
                return n + fetch(ahead_ref, 1 - half, t)
            issued[1 - half] = tokens(token)

        @pl.when(step + 1 == pl.num_programs(0))
        def _sum():
            def token(t, n):
                add(t)
                return n
            tokens(token)

        # the tile's sums as rows: eight tokens' halves at a time
        def rows_of(i, carry):
            at = pl.ds(pl.multiple_of(i * 8, 8), 8)
            eight = sums[at]
            out_ref[at, :d // 2] = eight[:, 0]
            out_ref[at, d // 2:] = eight[:, 1]
            return carry
        lax.fori_loop(0, tile // 8, rows_of, 0)

    steps = tokens // tile
    in_specs = [pl.BlockSpec((tile, k + 1), lambda i: (i, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, k + 1),
                             lambda i: (jnp.minimum(i + 1, steps - 1), 0),
                             memory_space=pltpu.SMEM)]
    operands = [table, table]
    if weighted:
        in_specs.append(pl.BlockSpec((tile, k), lambda i: (i, 0),
                                     memory_space=pltpu.SMEM))
        operands.append(scale)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(rows)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        grid=(steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile, d), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((2, k, tile) + rows.shape[1:], rows.dtype),
                        pltpu.VMEM((tile,) + rows.shape[1:], jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32)],
        # a step starts the next step's DMAs: the steps run in order.  Every
        # row number it reads is a routed row's (the plan's inverse of a
        # sort), so the DMAs need no bounds check
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        name="mxtpu_moe_sum_rows",
        interpret=interpret,
    )(*operands)


def sum_rows(rows, row_of_choice, here, scale=None, routed=None):
    """``out[t] = sum_j where(here[t, j], rows[row_of_choice[t, j]] *
    scale[t, j], 0)`` in float32, from 0 and in ascending ``j``.

    rows: (R, d); row_of_choice: (T, k) int, in ``[0, routed)`` where
    ``here``; here: (T, k) bool; scale: (T, k) or None; routed: how many of
    the rows any choice names (all where None): the kernels read no other.
    Returns (T, d) float32.  Not differentiated (the expert layer's rules
    call it)."""
    n, d = rows.shape
    tokens, k = here.shape
    if kernel_mode() is None or d % 256 or n % 16 or tokens % 8:
        _telem.inc("moe.sum_rows.xla")
        return _xla_sum_rows(rows, row_of_choice, here, scale)
    _telem.inc("moe.sum_rows.pallas")
    # each token's choices that are here, packed to the front in ascending j
    to = jnp.where(here, jnp.cumsum(here, axis=1) - 1, k)
    packed = jnp.arange(k)[None, :, None] == to[:, None, :]    # (T, at, j)

    def pack(v):
        return jnp.sum(jnp.where(packed, v[:, None, :], 0), axis=2)
    table = jnp.concatenate(
        [pack(row_of_choice.astype(jnp.int32)),
         jnp.sum(here, axis=1, keepdims=True, dtype=jnp.int32)], axis=1)
    if scale is not None:
        scale = pack(scale.astype(jnp.float32))
    return _pallas_sum_rows(rows, table, scale, n if routed is None else routed,
                            kernel_mode() == "interpret")
