"""Grouped-query attention under a learned sparse-attention indexer
(DeepSeek Sparse Attention as ``model_type: KeyeVL2`` configures it): each
query attends to the ``topk`` keys of its causal past that a small indexer
scores highest, and the indexer is trained to follow the attention it steers.

For one sequence, with ``t`` a query and ``s <= t`` a key::

    I[t, s]  = scale * sum_j w[t, j] * relu(qI[t, j] . kI[s])      index score
    tau_t    = the topk-th largest of {I[t, s] : s <= t}
    S_t      = {s <= t : I[t, s] >= tau_t}       (all of the past if t < topk)
    o[t, a]  = sum_{s in S_t} softmax_{S_t}(q[t, a] . k[s, a // rep] * sm) v[s]
    L_I      = mean_t KL(pbar_t || softmax_{S_t} I[t, .]),
               pbar_t[s] = mean_a P[t, a, s], without a gradient

The selection is defined by the threshold, so a tie takes both and no
sort's order enters.  ``L_I`` is the only thing the indexer's operands get a
gradient from (attention sees the selection as a constant), and q, k, v get
none from it.

Three steps, five Pallas kernels on the chip and plain XLA elsewhere (the
XLA forms walk blocks of queries, so they hold (block, L) and never (L, L)
in float32; they are also the tests' reference):

- ``mxtpu_dsa_index_select``: a block of 128 queries against every key block of
  their past — the scores as 16 thin products a tile into a VMEM scratch of
  (L, 128) order-preserving int32 keys, then the exact k-th largest by a
  radix search over the bit pattern (32 compare-and-count passes over the
  scratch, no sort), and out go the selection as an int8 mask (keys first,
  like the flash kernels' score tiles; causality included), ``tau`` and the
  log-sum-exp of the selected scores.
- ``mxtpu_dsa_attn_fwd`` / ``mxtpu_dsa_attn_bwd``: the flash kernels of
  ``ops/flash_attention.py`` with the mask as one more operand: they stream
  every causal block and mask it by its tile (with a token-level selection
  no block is empty, so there is nothing to skip but the causal dead half).
  A kernel that gathers ``topk`` keys a query would move 1 MB a (query,
  kv head) for 8 MFLOP; the two cross near L = 67k.
- ``mxtpu_dsa_align_loss`` and ``mxtpu_dsa_align_loss_grad``: ``L_I`` as a
  custom VJP whose rules each compute only what they hand on.  The value
  kernel (the primal and the forward rule): per (256, 256) tile the 32
  heads' probabilities from the attention kernel's own log-sum-exp, their
  mean on the selection, the indexer's scores again, ``KL_t``.  The gradient
  kernel (the backward rule; the loss's cotangent is a scalar a sequence,
  so it multiplies the result): the mean probability again, each index
  head's ``relu(kI . qI)`` once, kept in VMEM between the scores and the
  three products of the indexer's backward.  The forward rule's residuals
  are its own operands, so under ``jax.grad`` of a ``jax.checkpoint`` the
  recomputation's value kernel has no reader and is removed: a
  rematerialised layer runs each kernel once.  A layer trained without
  ``jax.checkpoint`` runs both as well and so builds the mean probability
  twice, where one kernel that did both would build it once (some 1.4
  times that kernel's products); that is the price of one path — the op
  cannot see whether it is being recomputed, and a flag would be a knob.

The kernels read K and V at their own ``Hkv`` heads (gauge
``gqa.kv_repeat``: the ``H / Hkv`` query heads each kv head serves).  A
forward program takes query heads of one kv head, which share its K / V
block and the mask tile (gauge ``flash.fwd.heads_per_kv_block``); a
backward program takes all of a kv head's query heads, Q blocks outer, and
sums their dK / dV in VMEM (gauge ``flash.bwd.heads_per_kv_block``;
``ops/flash_attention.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry as _telem
from .flash_attention import _VMEM_BUDGET, _pick_block, masked_flash
from .kernel_mode import kernel_mode

__all__ = ["sparse_gq_attention", "kth_largest", "selected_share"]

_INT_MIN = np.int32(-2 ** 31)
_INDEX_BQ = 128         # queries a program of mxtpu_dsa_index_select takes (lanes)
_LOSS_BLOCK = 256       # the tile of both mxtpu_dsa_align_loss kernels, both ways


def selected_share(seq, topk):
    """The share of the causal pairs a selection of ``topk`` keeps:
    ``sum_t min(t + 1, topk)`` over ``seq (seq + 1) / 2``."""
    t = np.arange(1, seq + 1)
    return float(np.minimum(t, topk).sum() / (seq * (seq + 1) / 2))


# ---------------------------------------------------------------------------
# order-preserving keys and the exact k-th largest
# ---------------------------------------------------------------------------

def _sort_key(x):
    """float32 -> int32 with the same order (an involution: applied to a key
    it gives the float's bits back)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _from_sort_key(key):
    return lax.bitcast_convert_type(
        key ^ ((key >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def _kth_largest_key(keys, k, count):
    """The largest int32 ``T`` with ``count(keys >= T) >= k``: the k-th
    largest key itself.  Built bit by bit from the top (the sign bit of an
    int32 counts as the highest, inverted): 32 passes of compare and count,
    ``count(cand)`` summing ``keys >= cand`` over the axis searched."""
    def body(n, t):
        cand = t ^ jnp.left_shift(jnp.int32(1), jnp.int32(31) - n)
        return jnp.where(count(cand) >= k, cand, t)
    return lax.fori_loop(0, 32, body, jnp.full_like(k, _INT_MIN))


def kth_largest(x, k):
    """Exact k-th largest of each row of ``x`` (..., n) float32, ``k``
    (...,) int32 >= 1, by a radix search over the floats' bit pattern: no
    sort, and ties change nothing (the value is what is asked for)."""
    keys = _sort_key(x.astype(jnp.float32))
    t = _kth_largest_key(
        keys, k.astype(jnp.int32)[..., None],
        lambda cand: jnp.sum(keys >= cand, axis=-1, keepdims=True,
                             dtype=jnp.int32))
    return _from_sort_key(t)[..., 0]


# ---------------------------------------------------------------------------
# XLA forms (a block of queries at a time)
# ---------------------------------------------------------------------------

def _query_blocks(n, block):
    block = min(block, n)
    while n % block:
        block //= 2
    return block


def _index_scores(qi, ki, w, scale):
    """(bq, L) float32 scores of a block of queries: qi (HI, bq, dI), ki
    (L, dI), w (bq, HI); the products in the operands' dtype with float32
    accumulation, the rest float32."""
    pre = jnp.einsum("hqd,kd->hqk", qi, ki,
                     preferred_element_type=jnp.float32)
    return scale * jnp.einsum("hqk,qh->qk", jax.nn.relu(pre),
                              w.astype(jnp.float32),
                              precision=lax.Precision.HIGHEST)


def _select_block(scores, t0, topk):
    """``(selected (bq, L) bool, tau (bq,))`` of a block of queries that
    starts at position ``t0``."""
    bq, n = scores.shape
    qpos = t0 + jnp.arange(bq, dtype=jnp.int32)
    causal = jnp.arange(n, dtype=jnp.int32)[None] <= qpos[:, None]
    keys = jnp.where(causal, _sort_key(scores), _INT_MIN)
    t = _kth_largest_key(
        keys, jnp.minimum(topk, qpos + 1)[:, None],
        lambda cand: jnp.sum(keys >= cand, axis=-1, keepdims=True,
                             dtype=jnp.int32))
    return (keys >= t) & causal, _from_sort_key(t)[:, 0]


def _xla_index_select(qi, ki, w, topk, scale):
    """One sequence: qi (HI, L, dI), ki (L, dI), w (L, HI) ->
    ``(mask (L, L) int8 keys first, tau (L,), lse_i (L,))``."""
    seq = ki.shape[0]
    bq = _query_blocks(seq, 256)

    def block(n):
        t0 = n * bq
        scores = _index_scores(lax.dynamic_slice_in_dim(qi, t0, bq, 1), ki,
                               lax.dynamic_slice_in_dim(w, t0, bq, 0), scale)
        sel, tau = _select_block(scores, t0, topk)
        lse = jax.nn.logsumexp(jnp.where(sel, scores, -jnp.inf), axis=-1)
        # a 0 / 1 mask, not a narrowed tensor: nothing to scale
        return sel.T.astype(jnp.int8), tau, lse  # mxlint: disable=HB21
    mask, tau, lse = lax.map(block, jnp.arange(seq // bq, dtype=jnp.int32))
    return (mask.transpose(1, 0, 2).reshape(seq, seq), tau.reshape(seq),
            lse.reshape(seq))


def _xlogy(p, logq):
    return jnp.where(p > 0, p * logq, 0.0)


def _xla_index_loss(q, k, lse, qi, ki, w, mask, sm_scale, scale):
    """One sequence's ``sum_t KL_t``: q, k (H, L, d) (k repeated to the
    query heads), lse (H, L) the attention's log-sum-exp, the indexer's
    operands and the mask as :func:`_xla_index_select` takes and gives them.
    Differentiable in qi, ki, w (``jax.grad`` of this is the reference of
    the gradient kernel)."""
    seq = ki.shape[0]
    bq = _query_blocks(seq, 256)

    @jax.checkpoint
    def block(qi, ki, w, n):
        t0 = n * bq
        sel = lax.dynamic_slice_in_dim(mask, t0, bq, 1).T != 0    # (bq, L)
        s = jnp.einsum("hqd,hkd->hqk", lax.dynamic_slice_in_dim(q, t0, bq, 1),
                       k, preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lax.dynamic_slice_in_dim(lse, t0, bq, 1)[..., None])
        pbar = lax.stop_gradient(jnp.where(sel, jnp.mean(p, axis=0), 0.0))
        scores = _index_scores(lax.dynamic_slice_in_dim(qi, t0, bq, 1), ki,
                               lax.dynamic_slice_in_dim(w, t0, bq, 0), scale)
        logq = scores - jax.nn.logsumexp(
            jnp.where(sel, scores, -jnp.inf), axis=-1, keepdims=True)
        return jnp.sum(_xlogy(pbar, jnp.log(jnp.maximum(pbar, 1e-37)))
                       - jnp.where(sel, pbar * logq, 0.0))
    return lax.fori_loop(0, seq // bq,
                         lambda n, acc: acc + block(qi, ki, w, n),
                         jnp.float32(0))


# ---------------------------------------------------------------------------
# Pallas: index scores + selection
# ---------------------------------------------------------------------------

def _pallas_index_select(qi, ki, w, topk, scale, interpret=False):
    """qi (B, HI, dI, L), ki (B, dI, L), w (B, HI, L) float32 ->
    ``(mask (B, L, L) int8, tau (B, L), lse_i (B, L))``: the head dim on
    sublanes and the sequence on lanes, as the flash kernels take theirs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, hi, di, seq = qi.shape
    bq = _INDEX_BQ
    bk = _pick_block(seq)
    nq, nk = seq // bq, seq // bk

    def operand(x):
        return x.astype(jnp.float32) if interpret else x

    def kernel(qi_ref, ki_ref, w_ref, mask_ref, tau_ref, lse_ref, keys):
        i = pl.program_id(1)
        live = ((i + 1) * bq + bk - 1) // bk
        qpos = i * bq + lax.broadcasted_iota(jnp.int32, (1, bq), 1)

        def at(j):
            return pl.ds(pl.multiple_of(j * bk, bk), bk)

        def score(j, carry):
            kb = operand(ki_ref[0, :, at(j)])                  # (dI, bk)
            acc = jnp.zeros((bk, bq), jnp.float32)
            for h in range(hi):
                pre = lax.dot_general(
                    kb, operand(qi_ref[0, h]), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # (bk, bq)
                acc = acc + jnp.maximum(pre, 0.0) * w_ref[0, h:h + 1, :]
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            keys[at(j), :] = jnp.where(qpos >= kpos, _sort_key(acc * scale),
                                       _INT_MIN)
            return carry
        lax.fori_loop(0, live, score, 0)

        def count(cand):
            def add(j, n):
                return n + jnp.sum((keys[at(j), :] >= cand).astype(jnp.int32),
                                   axis=0, keepdims=True)
            return lax.fori_loop(0, live, add, jnp.zeros((1, bq), jnp.int32))
        t = _kth_largest_key(None, jnp.minimum(topk, qpos + 1), count)

        def largest(j, m):
            kj = keys[at(j), :]
            return jnp.maximum(m, jnp.max(jnp.where(
                kj >= t, _from_sort_key(kj), -jnp.inf), axis=0, keepdims=True))
        m = lax.fori_loop(0, live, largest,
                          jnp.full((1, bq), -jnp.inf, jnp.float32))

        def write(j, total):
            kj = keys[at(j), :]
            sel = kj >= t
            mask_ref[0, at(j), :] = sel.astype(  # mxlint: disable=HB21
                jnp.int8)
            return total + jnp.sum(jnp.where(
                sel, jnp.exp(_from_sort_key(kj) - m), 0.0), axis=0,
                keepdims=True)
        total = lax.fori_loop(0, live, write,
                              jnp.zeros((1, bq), jnp.float32))

        def dead(j, carry):
            mask_ref[0, at(j), :] = jnp.zeros((bk, bq), jnp.int8)
            return carry
        lax.fori_loop(live, nk, dead, 0)
        tau_ref[0] = _from_sort_key(t)
        lse_ref[0] = m + jnp.log(total)

    # the scratch of keys, the resident mask block and the row of kI, each
    # double-buffered where it is an operand: 17 MiB at L = 16384
    need = seq * bq * 4 + 2 * seq * bq + 2 * di * seq * qi.dtype.itemsize \
        + 2 * hi * (di * qi.dtype.itemsize + 4) * bq + 6 * bk * bq * 4
    mask, tau, lse = pl.pallas_call(
        kernel,
        grid=(nb, nq),
        in_specs=[
            pl.BlockSpec((1, hi, di, bq), lambda b, i: (b, 0, 0, i)),
            pl.BlockSpec((1, di, seq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, hi, bq), lambda b, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, seq, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((nb, seq, seq), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1, seq), jnp.float32),
                   jax.ShapeDtypeStruct((nb, 1, seq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((seq, bq), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            **({"vmem_limit_bytes": need + 4 * 2 ** 20}
               if need > _VMEM_BUDGET else {})),
        name="mxtpu_dsa_index_select",
        interpret=interpret,
    )(qi, ki, w)
    return mask, tau[:, 0], lse[:, 0]


# ---------------------------------------------------------------------------
# Pallas: the index loss, and its gradients
# ---------------------------------------------------------------------------

def _loss_tile(q_ref, k_ref, lse_ref, qi_ref, ki_ref, mask_ref, ks, sm_scale,
               operand):
    """What both kernels build of a live (blk, blk) tile, keys first:
    ``(sel, pbar, kb, relu)`` — the selection, the heads' mean probability
    on it, the tile's (dI, blk) block of index keys (the slice ``ks`` of
    the row) and a function giving ``relu(kI . qI[hh])`` (blk, blk)
    float32."""
    h, hkv, blk = q_ref.shape[1], k_ref.shape[1], q_ref.shape[3]
    rep = h // hkv
    sel = mask_ref[0].astype(jnp.int32) != 0                    # (bk, bq)

    # every head unrolled, a kv head's block of K read once for the query
    # heads that share it: the scheduler overlaps one head's exp with the
    # next one's product, where a rolled loop drains the MXU every turn
    # (25.7 ms a call rolled, 11.9 a kv head a turn, 9.9 unrolled: PERF.md)
    pbar = jnp.zeros((blk, blk), jnp.float32)
    for g in range(hkv):
        kg = operand(k_ref[0, g])                               # (d, bk)
        for a in range(g * rep, (g + 1) * rep):
            s = lax.dot_general(
                kg, operand(q_ref[0, a]), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            pbar = pbar + jnp.exp(s - lse_ref[0, a:a + 1, :])
    pbar = jnp.where(sel, pbar * (1.0 / h), 0.0)
    kb = operand(ki_ref[0, :, ks])

    def relu(hh):
        return jnp.maximum(lax.dot_general(
            kb, operand(qi_ref[0, hh]), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), 0.0)           # (bk, bq)
    return sel, pbar, kb, relu


def _loss_call(kernel, name, q, k, lse, qi, ki, w, mask, lse_i, out_specs,
               out_shape, scratch, more_vmem, interpret):
    """One of the two kernels over the grid they share: (batch, query
    block, key block), the key block innermost, a dead tile (key block past
    the query block) naming the row's last live key block again so that
    nothing is fetched for it.  ``more_vmem``: the bytes of the kernel's own
    output blocks and scratch beside the operands' blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, h, d, seq = q.shape
    hkv, hi, di = k.shape[1], qi.shape[1], qi.shape[2]
    blk = _pick_block(seq, _LOSS_BLOCK)
    n = seq // blk

    def at_k(i, j):
        return jnp.minimum(j, i)
    need = 2 * (h * d * blk + hkv * d * blk + hi * di * blk
                + di * seq) * q.dtype.itemsize + 12 * blk * blk * 4 \
        + more_vmem
    return pl.pallas_call(
        kernel,
        grid=(nb, n, n),
        in_specs=[
            pl.BlockSpec((1, h, d, blk), lambda b, i, j: (b, 0, 0, i)),
            pl.BlockSpec((1, hkv, d, blk),
                         lambda b, i, j: (b, 0, 0, at_k(i, j))),
            pl.BlockSpec((1, h, blk), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, hi, di, blk), lambda b, i, j: (b, 0, 0, i)),
            pl.BlockSpec((1, di, seq), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((1, hi, blk), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, blk, blk), lambda b, i, j: (b, at_k(i, j), i)),
            pl.BlockSpec((1, 1, blk), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            **({"vmem_limit_bytes": need + 4 * 2 ** 20}
               if need > _VMEM_BUDGET else {})),
        name=name,
        interpret=interpret,
    )(q, k, lse, qi, ki, w, mask, lse_i[:, None, :])


def _interpreter_cast(interpret):
    """The interpreter multiplies in float32 what Mosaic takes as it is."""
    return (lambda x: x.astype(jnp.float32)) if interpret else (lambda x: x)


# Both kernels are jitted so that a model's layers share one trace of the
# unrolled body (a pallas_call traces its kernel anew every time it is
# called: 2.3 s a gradient kernel on the chip's host, PERF.md §6).
_LOSS_STATIC = ("sm_scale", "scale", "interpret")


@functools.partial(jax.jit, static_argnames=_LOSS_STATIC)
def _pallas_index_loss(q, k, lse, qi, ki, w, mask, lse_i, sm_scale, scale,
                       interpret=False):
    """The value kernel.  q (B, H, d, L), k (B, Hkv, d, L), lse (B, H, L), qi
    (B, HI, dI, L), ki (B, dI, L), w (B, HI, L) float32, mask (B, L, L) int8,
    lse_i (B, L) -> ``kl (B, L)`` float32, a query's ``KL_t``: per tile the
    heads' probabilities from the attention's log-sum-exp, their mean on the
    selection, the index scores (one product an index head), and the tile's
    share of the divergence."""
    from jax.experimental import pallas as pl

    nb, seq, hi = q.shape[0], q.shape[3], qi.shape[1]
    blk = _pick_block(seq, _LOSS_BLOCK)
    operand = _interpreter_cast(interpret)

    def kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, mask_ref,
               lsei_ref, kl_ref):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            kl_ref[...] = jnp.zeros_like(kl_ref)

        @pl.when(j <= i)
        def _tile():
            _, pbar, _, relu = _loss_tile(
                q_ref, k_ref, lse_ref, qi_ref, ki_ref, mask_ref,
                pl.ds(pl.multiple_of(j * blk, blk), blk), sm_scale, operand)
            scores = jnp.zeros((blk, blk), jnp.float32)
            for hh in range(hi):
                scores = scores + relu(hh) * w_ref[0, hh:hh + 1, :]
            logq = scores * scale - lsei_ref[0]
            kl_ref[0] += jnp.sum(
                jnp.where(pbar > 0,
                          pbar * (jnp.log(jnp.maximum(pbar, 1e-37)) - logq),
                          0.0), axis=0, keepdims=True)

    return _loss_call(
        kernel, "mxtpu_dsa_align_loss", q, k, lse, qi, ki, w, mask, lse_i,
        pl.BlockSpec((1, 1, blk), lambda b, i, j: (b, 0, i)),
        jax.ShapeDtypeStruct((nb, 1, seq), jnp.float32), [], 0,
        interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=_LOSS_STATIC)
def _pallas_index_loss_grad(q, k, lse, qi, ki, w, mask, lse_i, sm_scale,
                            scale, interpret=False):
    """The gradient kernel: the value kernel's operands -> ``(dqi (B, HI,
    dI, L), dki (B, dI, L), dw (B, HI, L))`` float32, the gradients of
    ``sum_t KL_t``.  Per tile the heads' mean probability again, every index
    head's ``relu(kI . qI)`` once — kept as computed in a float32 VMEM
    scratch of (HI, blk, blk), 4 MiB at 16 heads, between the scores and
    the products that need ``d I`` (computing them again read 19.2 ms a
    call where the scratch read 17.3, PERF.md §6; rounding them to halve
    the scratch would change ``dw``) — then ``d I = softmax_S(I) - pbar``
    and the three products of the indexer's backward, ``g`` in the
    operands' dtype.  No ``log`` and no ``kl``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, seq = q.shape[0], q.shape[3]
    hi, di = qi.shape[1], qi.shape[2]
    blk = _pick_block(seq, _LOSS_BLOCK)
    operand = _interpreter_cast(interpret)

    def kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, mask_ref,
               lsei_ref, dqi_ref, dki_ref, dw_ref, relu_ref):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init_q():
            dqi_ref[...] = jnp.zeros_like(dqi_ref)
            dw_ref[...] = jnp.zeros_like(dw_ref)

        @pl.when((i == 0) & (j == 0))
        def _init_k():
            dki_ref[...] = jnp.zeros_like(dki_ref)

        @pl.when(j <= i)
        def _tile():
            ks = pl.ds(pl.multiple_of(j * blk, blk), blk)
            sel, pbar, kb, relu = _loss_tile(
                q_ref, k_ref, lse_ref, qi_ref, ki_ref, mask_ref, ks,
                sm_scale, operand)
            scores = jnp.zeros((blk, blk), jnp.float32)
            for hh in range(hi):
                r = relu(hh)
                relu_ref[hh] = r
                scores = scores + r * w_ref[0, hh:hh + 1, :]
            # d sum_t KL_t / d I, times the scale on the way to the products
            di_tile = jnp.where(
                sel, jnp.exp(scores * scale - lsei_ref[0]) - pbar, 0.0) * scale
            for hh in range(hi):
                r = relu_ref[hh]
                dw_ref[0, hh:hh + 1, :] += jnp.sum(di_tile * r, axis=0,
                                                   keepdims=True)
                g = jnp.where(r > 0, di_tile * w_ref[0, hh:hh + 1, :], 0.0)
                g = operand(g.astype(qi_ref.dtype))
                dqi_ref[0, hh] += lax.dot_general(
                    kb, g, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)         # (dI, bq)
                dki_ref[0, :, ks] += lax.dot_general(
                    operand(qi_ref[0, hh]), g, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)         # (dI, bk)

    return tuple(_loss_call(
        kernel, "mxtpu_dsa_align_loss_grad", q, k, lse, qi, ki, w, mask,
        lse_i,
        [pl.BlockSpec((1, hi, di, blk), lambda b, i, j: (b, 0, 0, i)),
         pl.BlockSpec((1, di, seq), lambda b, i, j: (b, 0, 0)),
         pl.BlockSpec((1, hi, blk), lambda b, i, j: (b, 0, i))],
        [jax.ShapeDtypeStruct((nb, hi, di, seq), jnp.float32),
         jax.ShapeDtypeStruct((nb, di, seq), jnp.float32),
         jax.ShapeDtypeStruct((nb, hi, seq), jnp.float32)],
        [pltpu.VMEM((hi, blk, blk), jnp.float32)],
        # the float32 gradient blocks, double-buffered (dki a row of the
        # whole sequence, resident), and the scratch
        2 * (hi * di * blk + di * seq + hi * blk) * 4 + hi * blk * blk * 4,
        interpret))


# ---------------------------------------------------------------------------
# the three steps, each with its two forms
# ---------------------------------------------------------------------------

def _kernels(seq, *head_dims):
    """``"mosaic"`` / ``"interpret"`` where this call's shapes take the
    Pallas kernels (a kernel mode, L a multiple of 128, head dims of 64s),
    else None: the XLA forms."""
    mode = kernel_mode()
    if mode is None or seq % 128 or any(x % 64 for x in head_dims):
        return None
    return mode


def _index_select(qi, ki, w, topk, scale):
    """qi (B, HI, L, dI), ki (B, L, dI), w (B, L, HI) -> mask (B, L, L)
    int8 (keys first), tau (B, L), lse_i (B, L); no gradient."""
    qi, ki, w = (lax.stop_gradient(a) for a in (qi, ki, w))
    mode = _kernels(ki.shape[1], qi.shape[3])
    _telem.inc("dsa.index.xla" if mode is None else "dsa.index.pallas")
    _telem.inc("dsa.select.radix")
    if mode is None:
        return jax.vmap(functools.partial(
            _xla_index_select, topk=topk, scale=scale))(qi, ki, w)
    return _pallas_index_select(
        jnp.swapaxes(qi, 2, 3), jnp.swapaxes(ki, 1, 2),
        jnp.swapaxes(w, 1, 2).astype(jnp.float32), topk, scale,
        interpret=mode == "interpret")


def _loss_layout(q, k, qi, ki, w):
    """The operands as the two loss kernels take them: the head dim on
    sublanes, the sequence on lanes."""
    return (jnp.swapaxes(q, 2, 3), jnp.swapaxes(k, 2, 3),
            jnp.swapaxes(qi, 2, 3), jnp.swapaxes(ki, 1, 2),
            jnp.swapaxes(w, 1, 2).astype(jnp.float32))


def _index_loss_value(q, k, lse, qi, ki, w, mask, lse_i, sm_scale, scale,
                      mode):
    _telem.inc("dsa.index_loss.value.pallas")
    qt, kt, qit, kit, wt = _loss_layout(q, k, qi, ki, w)
    return jnp.sum(_pallas_index_loss(
        qt, kt, lse, qit, kit, wt, mask, lse_i, sm_scale, scale,
        interpret=mode == "interpret"), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _index_loss(q, k, lse, qi, ki, w, mask, lse_i, sm_scale, scale, mode):
    """Each rule computes only what it hands on: the primal and the forward
    rule run the value kernel, and the forward rule keeps nothing but its
    own operands, so a recomputation (``jax.checkpoint``) of it under
    ``jax.grad`` has no reader and is removed; the backward rule, which runs
    once whatever recomputes, runs the gradient kernel."""
    return _index_loss_value(q, k, lse, qi, ki, w, mask, lse_i, sm_scale,
                             scale, mode)


def _index_loss_fwd(q, k, lse, qi, ki, w, mask, lse_i, sm_scale, scale,
                    mode):
    return (_index_loss_value(q, k, lse, qi, ki, w, mask, lse_i, sm_scale,
                              scale, mode),
            (q, k, lse, qi, ki, w, mask, lse_i))


def _index_loss_bwd(sm_scale, scale, mode, res, ct):
    q, k, lse, qi, ki, w, mask, lse_i = res
    _telem.inc("dsa.index_loss.grad.pallas")
    qt, kt, qit, kit, wt = _loss_layout(q, k, qi, ki, w)
    dqi, dki, dw = _pallas_index_loss_grad(
        qt, kt, lse, qit, kit, wt, mask, lse_i, sm_scale, scale,
        interpret=mode == "interpret")
    grads = (jnp.swapaxes(dqi, 2, 3), jnp.swapaxes(dki, 1, 2),
             jnp.swapaxes(dw, 1, 2))
    return (None, None, None) + tuple(
        (g * ct.reshape((-1,) + (1,) * (g.ndim - 1))).astype(a.dtype)
        for g, a in zip(grads, (qi, ki, w))) + (None, None)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def _index_loss_sum(q, k, lse, qi, ki, w, mask, lse_i, sm_scale, scale):
    """``sum_t KL_t`` a sequence, (B,): q (B, H, L, d), k (B, Hkv, L, d),
    lse (B, H, L), the rest as :func:`_index_select` takes and gives them.
    A gradient for qi, ki and w only."""
    q, k, lse = (lax.stop_gradient(a) for a in (q, k, lse))
    mode = _kernels(ki.shape[1], q.shape[3], qi.shape[3])
    _telem.inc("dsa.index_loss.xla" if mode is None
               else "dsa.index_loss.pallas")
    if mode is None:
        k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
        return jax.vmap(functools.partial(
            _xla_index_loss, sm_scale=sm_scale, scale=scale))(
                q, k, lse, qi, ki, w, mask)
    return _index_loss(q, k, lse, qi, ki, w, mask, lse_i, sm_scale, scale,
                       mode)


def sparse_gq_attention(q, k, v, qi, ki, w, topk, sm_scale=None,
                        index_scale=None):
    """Attention of every query over the ``topk`` keys of its causal past
    that the indexer scores highest, and the indexer's alignment loss.

    q: (B, H, L, d); k, v: (B, Hkv, L, d), ``H`` a multiple of ``Hkv`` (head
    ``a`` reads kv head ``a // (H / Hkv)``), rotary applied; qi: (B, HI, L,
    dI) index queries; ki: (B, L, dI) the one index key head; w: (B, L, HI)
    index weights.  ``sm_scale`` defaults to ``d ** -0.5``, ``index_scale``
    to ``HI ** -0.5 * dI ** -0.5``.  Returns ``(out (B, H, L, d), loss
    (B,))``, ``loss`` a sequence's ``mean_t KL(pbar_t || softmax_{S_t} I)``.
    Gradients: q, k, v through ``out`` with the selection a constant; qi,
    ki, w through ``loss`` with ``pbar`` a constant (module docstring).

    Counters, while tracing: ``dsa.layers``; ``dsa.index.pallas`` / ``.xla``;
    ``dsa.select.radix``; ``dsa.attn.fwd.pallas`` / ``.scan`` and
    ``dsa.attn.bwd.*`` (``ops/flash_attention.py``);
    ``dsa.index_loss.pallas`` / ``.xla`` (a layer that takes the loss's
    kernels or the XLA form), ``dsa.index_loss.value.pallas`` and
    ``dsa.index_loss.grad.pallas`` (each kernel where it is traced: the
    value kernel in every trace of the forward, the gradient kernel in the
    backward rule, once a differentiated layer).  Gauges ``dsa.topk``,
    ``dsa.index_heads``, ``dsa.selected_share``, ``gqa.kv_repeat``."""
    b, h, seq, d = q.shape
    hkv, hi, di = k.shape[1], qi.shape[1], qi.shape[3]
    sm_scale = d ** -0.5 if sm_scale is None else float(sm_scale)
    scale = hi ** -0.5 * di ** -0.5 if index_scale is None \
        else float(index_scale)
    _telem.inc("dsa.layers")
    _telem.set_gauge("dsa.topk", topk)
    _telem.set_gauge("dsa.index_heads", hi)
    _telem.set_gauge("dsa.selected_share", selected_share(seq, topk))
    _telem.set_gauge("gqa.kv_repeat", h // hkv)

    with jax.named_scope("dsa.index"), jax.named_scope("dsa.select"):
        mask, _, lse_i = _index_select(qi, ki, w, topk, scale)
    with jax.named_scope("dsa.attention"):
        out, lse = masked_flash(
            q.reshape(b * h, seq, d), k.reshape(b * hkv, seq, d),
            v.reshape(b * hkv, seq, d), mask, sm_scale)
    with jax.named_scope("dsa.index_loss"):
        loss = _index_loss_sum(q, k, lse.reshape(b, h, seq), qi, ki, w, mask,
                               lse_i, sm_scale, scale) / seq
    return out.reshape(b, h, seq, d), loss
