"""Flash attention: Pallas TPU kernel + blockwise-XLA fallback.

Reference capability: the fused ``contrib`` multi-head attention ops
(src/operator/contrib/transformer.cc [>=1.6]) — but those materialize the
(Lq, Lk) score matrix; this is the online-softmax streaming algorithm, so
HBM traffic is O(L*D) not O(L^2) (SURVEY.md §5.7 TPU plan).

Layout: (B, H, L, D) outside.  The Pallas path hands the kernel its
operands as (B*H, D, L) — L on the lanes, D on the sublanes — which is the
layout XLA itself prefers for a D of 64 (a 64-wide minor dimension is
padded to 128 lanes in HBM and twice the bytes move), and holds the scores
transposed, keys on sublanes and queries on lanes: the softmax's max and
sum then run down the sublanes, and the running max, the denominator and
the log-sum-exp are lane-major rows, which is how they are stored.  One
grid program takes G (batch x head) rows, a BQ block of queries and
streams Lk in BK blocks through VMEM with a float32 accumulator: the MXU
sees G batched (BK, D) x (D, BQ) / (D, BK) x (BK, BQ) matmuls per step.  G
comes from the shapes alone (:func:`_rows_per_program`): as many rows as
give a program a few microseconds of work and fit VMEM.  Where K and V have
fewer rows than Q (grouped-query attention, read in place) a program's G
rows are query heads of one kv head, which share its K / V block, at a
square score tile of that rule's size (:func:`_forward_tiling`).  When one
BK block holds all of Lk the body is a plain one-pass softmax.  Otherwise a
grid step holds as many KV blocks as VMEM has room for
(:func:`_kv_blocks_per_step`) and walks them in a loop of its own, a block
at a time through the same online softmax (a grid step costs about half a
microsecond whatever it computes); with few rows to a program the Q
block's columns go in two halves, the second's QK^T issued before the
first's softmax.  Under ``causal`` the walk sorts the blocks from the grid
indices (:func:`_causal_extent`), as the backward does: a block that is
wholly masked is skipped, and a grid step that holds only such blocks asks
for the row's last live K / V blocks again, so nothing is fetched for it; a
block the diagonal cuts is masked; a wholly visible one runs without the
iota, the compare and the select.  ``causal`` may also be a block rule
``(beta, offset)``, query ``a`` seeing key ``b`` where ``a // beta >= b //
beta + offset`` (:func:`_sees`): the same walk, skips and fetches, the
kernels named ``mxtpu_bd_attn_*``; :func:`block_diffusion_attention` is
built from two such calls.  Where ``sm_scale`` is a power of two and
the Q block a quarter of the score tile or less it multiplies the
(G, D, BQ) block of Q and not the (G, BK, BQ) scores: the same numbers, bit
for bit, from fewer elements.  The fallback is the same algorithm as a
``lax.scan`` over KV blocks, which XLA fuses adequately on CPU and keeps
memory O(L*BK).

Gradients: custom VJP; the backward pass recomputes scores blockwise from
the saved logsumexp (standard flash-attention backward) — no O(L^2)
residuals are ever stored.  Wherever the forward gets its kernel the
backward is a Pallas kernel too (``mxtpu_flash_bwd``,
:func:`_pallas_backward`): the same (rows, D, L) operands and transposed
scores, bf16 into the MXU and float32 accumulation, one program computing
dV, dK and dQ of a (BK, BQ) block of scores from one S and one dP — five
products, where a dK/dV kernel and a dQ kernel would take seven.  One pass
where a block holds all of L; otherwise the Q blocks are walked inside each
KV block, dK / dV accumulate over the inner walk, the row's dQ in a float32
VMEM scratch over the outer one, and causal blocks that are wholly masked
are skipped.  G is the forward's rule over the backward's own VMEM
arithmetic (:func:`_backward_rows_per_program`).  Where K and V have fewer
rows than Q a program takes every query head of one kv head instead, and
the loops turn round: Q blocks outer, KV blocks inner, the kv head's dK /
dV summed over its query heads in a float32 VMEM scratch of the whole row
and the Q block's dQ over the inner walk (:func:`_grouped_backward_blocks`;
where that does not fit VMEM, K / V are repeated to the query rows and
dK / dV summed back).  Everywhere else, and where one row's dQ does not
fit VMEM, it is the float32 scan (:func:`_scan_backward`), which is also
the tests' reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telem
from .kernel_mode import kernel_mode

__all__ = ["flash_attention", "masked_flash", "block_diffusion_attention"]

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

# What one program's blocks, scratch and temporaries may take of VMEM, as
# _program_vmem_bytes reckons them: under Mosaic's default scoped limit
# (16 MiB on the v5e), with room for what the arithmetic does not see.
_VMEM_BUDGET = 14 * 2 ** 20
_VMEM_DEFAULT_LIMIT = 16 * 2 ** 20
# HBM bytes a program should move before more rows stop paying: 2.6 us at
# the v5e's 819 GB/s against the ~0.35 us a grid step costs by itself.
_PROGRAM_HBM_BYTES = 2 * 2 ** 20


def _padded_head_dims(d, dv, itemsize):
    """``d + dv`` as a block holds them: the head dim is on sublanes, padded
    to the dtype's tile (8 float32 rows, 16 bf16)."""
    sublanes = 32 // itemsize
    return sum(-(-x // sublanes) * sublanes for x in (d, dv))


def _mask_vmem_bytes(bq, bk):
    """What a (bk, bq) tile of a selection mask adds to a program: the
    double-buffered int8 block and its int32 widening."""
    return bq * bk * (2 + 4)


def _program_vmem_bytes(g, bq, bk, d, itemsize, streaming, dv=None,
                        masked=False, kv_rows=None):
    """VMEM bytes one program of ``g`` rows holds: the double-buffered
    Q/K blocks at ``d`` and V/O blocks at ``dv`` (the head dim on sublanes,
    padded to the dtype's tile) — K and V at ``kv_rows`` rows, ``g`` where
    every row has its own and 1 where the rows are query heads of one kv
    head —, the log-sum-exp block, the float32 scratch of the streaming
    body and the (g, bk, bq) score / probability temporaries with the
    float32 P.V; ``masked``: a selection mask's tile besides, once a
    program."""
    dv = d if dv is None else dv
    kv_rows = g if kv_rows is None else kv_rows
    if masked:
        return _mask_vmem_bytes(bq, bk) + _program_vmem_bytes(
            g, bq, bk, d, itemsize, streaming, dv, kv_rows=kv_rows)
    blocks = 2 * _padded_head_dims(d, dv, itemsize) * (
        g * bq + kv_rows * bk) * itemsize
    lse = 2 * 8 * -(-g // 8) * bq * 4
    scratch = g * (dv + 2 * 8) * bq * 4 if streaming else 0
    temps = g * bq * (bk * (4 + 4 + itemsize) + dv * 4)
    return blocks + lse + scratch + temps


def _pick_rows(bh, vmem_bytes, row_bytes):
    """The divisor of ``bh`` a kernel gives each grid program: one whose
    blocks fit ``_VMEM_BUDGET`` by ``vmem_bytes(g)`` (a multiple of 8 where
    one fits), the smallest that moves ``_PROGRAM_HBM_BYTES`` at
    ``row_bytes`` a row or else the largest that fits; 1 where nothing
    fits."""
    fits = [g for g in range(1, bh + 1)
            if bh % g == 0 and vmem_bytes(g) <= _VMEM_BUDGET] or [1]
    pool = [g for g in fits if g % 8 == 0] or fits
    for g in pool:
        if g * row_bytes >= _PROGRAM_HBM_BYTES:
            return g
    return pool[-1]


def _rows_per_program(bh, bq, bk, d, itemsize, streaming, dv=None,
                      masked=False):
    """G, the (batch x head) rows one forward grid program takes: a divisor
    of ``bh`` (a multiple of 8 where one fits: the log-sum-exp block is then
    (G, bq), rows on sublanes) whose blocks fit ``_VMEM_BUDGET``, the
    smallest that moves ``_PROGRAM_HBM_BYTES`` or else the largest that
    fits.  A function of the shapes and the dtype alone; ``dv`` is the head
    dim of V and O where it is not Q's and K's ``d``."""
    dv = d if dv is None else dv
    return _pick_rows(
        bh, lambda g: _program_vmem_bytes(g, bq, bk, d, itemsize, streaming,
                                          dv, masked),
        (bq + bk) * (d + dv) * itemsize)


def _forward_tiling(heads, group, lq, lk, bq, bk, d, itemsize, streaming,
                    dv, masked=False):
    """``(G, bq, bk)`` of the forward kernel, from the shapes alone.
    ``group`` query heads share each kv head (``H / Hkv``, read from the
    operands).  A group of 1 is :func:`_rows_per_program` at the caller's
    blocks.  A larger one takes query heads of one kv head a program, so
    that one K / V block and one mask tile serve them all: as many heads of
    the group as fit VMEM with a square score tile of 128-multiples no
    larger than that rule's (G, bk, bq): of the tiles of that size the
    compiler schedules the square's body closest to the MXU's floor, and
    it leaves VMEM for several KV blocks a grid step (PERF.md §6, PR 40)."""
    g = _rows_per_program(heads, bq, bk, d, itemsize, streaming, dv, masked)
    if group == 1:
        return g, bq, bk
    tile = g * bq * bk
    for f in (f for f in range(group, 1, -1) if group % f == 0):
        side = math.isqrt(tile // f)
        blocks = _pick_block(lq, side), _pick_block(lk, side)
        if None not in blocks and _program_vmem_bytes(
                f, *blocks, d, itemsize, lk > blocks[1], dv, masked,
                1) <= _VMEM_BUDGET:
            return (f,) + blocks
    return 1, bq, bk


def _plus(x, n):
    """``x + n``, and ``x`` itself where ``n`` is 0: the token-causal
    kernels' index arithmetic stays what it was, operation for operation."""
    return x + n if n else x


def _rule(causal):
    """``(beta, offset)`` of a ``causal`` argument: ``True`` is token-causal,
    ``(1, 0)``; a pair is the block rule itself (:func:`_sees`)."""
    return (1, 0) if causal is True else tuple(causal)


def _sees(qpos, kpos, beta=1, offset=0):
    """Query position ``qpos`` sees key position ``kpos`` under the block
    rule: ``qpos // beta >= kpos // beta + offset`` (``beta`` a power of
    two).  ``(1, 0)`` is the causal mask, ``qpos >= kpos``; ``(beta, 0)``
    block-causal, every query seeing its whole block; ``(beta, 1)`` the
    blocks before its own only, so the first block's queries see nothing."""
    if beta > 1:
        shift = beta.bit_length() - 1
        qpos, kpos = qpos >> shift, kpos >> shift
    return qpos >= _plus(kpos, offset)


def _causal_block(i, j, bq, bk, beta=1, offset=0):
    """``(live, cut)`` of Q block ``i`` against KV block ``j`` under the
    block rule :func:`_sees` (``beta`` divides ``bq`` and ``bk``; the
    causal mask where ``beta`` is 1 and ``offset`` 0: query ``a`` sees key
    ``b`` where ``a >= b``, both counted from 0): live unless the block's
    first key comes after what its last query sees, cut where its last key
    comes after what its first query sees.  Live and not cut is wholly
    visible.  Grid indices in the backward kernel, plain ints in the counts
    and the tests."""
    bq, bk = bq // beta, bk // beta     # in blocks of the rule's length
    return (i + 1) * bq > _plus(j * bk, offset), \
        _plus((j + 1) * bk - 1, offset) > i * bq


def _causal_extent(i, bq, bk, beta=1, offset=0):
    """``(visible, live)``: :func:`_causal_block` as counts, for the
    forward's inner walk — how many of the KV blocks, from the first on, Q
    block ``i`` sees whole and how many it sees any of (``live`` may pass
    the last block there is, where Lq is longer than Lk; ``visible`` may be
    under 0 where the offset hides a whole Q block); the blocks between the
    two are the ones the diagonal cuts.  (Not one written from the other:
    the divisions cost the backward, which asks once a grid step, 0.9 % of
    its time on the chip.)"""
    bq, bk = bq // beta, bk // beta
    return _plus(i * bq + 1, -offset) // bk, \
        _plus((i + 1) * bq + bk - 1, -offset) // bk


def _kv_block_fetched(i, j, bq, bk, beta=1, offset=0):
    """The K / V block grid step ``(i, j)`` of a causal forward names: its
    own while it is live, then the one that holds the last key Q block ``i``
    sees, which is the row's last live one and in VMEM already, so that no
    copy is issued for a skipped step (block 0 where the block sees no key
    at all).  ``bk`` is what one grid step holds of Lk."""
    if (beta, offset) == (1, 0):
        last = (i + 1) * bq - 1
    else:
        last = jnp.maximum(((i + 1) * (bq // beta) - offset) * beta - 1, 0)
    return jnp.minimum(j, last // bk)


def _first_live_q_block(j, bq, bk, beta=1, offset=0):
    """The first Q block that sees any key of KV block ``j``: what the
    backward's skipped steps name on the Q side."""
    return _plus(j * (bk // beta), offset) // (bq // beta)


def _kv_blocks_per_step(nk, g, bq, bk, d, itemsize, dv, masked=False,
                        kv_rows=None):
    """KV blocks one grid step of the streaming forward holds and walks: the
    largest divisor of ``nk`` whose K / V blocks (at ``kv_rows`` rows: one
    where the ``g`` rows are query heads of one kv head) and mask tiles,
    double-buffered, fit what :func:`_program_vmem_bytes` leaves of
    ``_VMEM_BUDGET`` at the ``g`` already chosen.  A grid step costs about
    half a microsecond whatever it computes; the walk inside one costs
    nothing a block."""
    kv_rows = g if kv_rows is None else kv_rows
    used = _program_vmem_bytes(g, bq, bk, d, itemsize, True, dv, masked,
                               kv_rows)
    more = 2 * kv_rows * _padded_head_dims(d, dv, itemsize) * bk * itemsize \
        + (2 * bq * bk if masked else 0)
    return max([n for n in range(1, nk + 1) if nk % n == 0 and
                used + (n - 1) * more <= _VMEM_BUDGET] or [1])


def _scale_on_q(sm_scale, d, bk):
    """Whether ``sm_scale`` multiplies the (G, D, BQ) block of Q in place of
    the (G, BK, BQ) score tile: where it is a power of two, which commutes
    with every rounding on the way to the scores, so the result is the same
    bit for bit, and the Q block is at most a quarter of the tile (a bf16
    multiply costs the v5e's float32 vector unit two converts besides: at a
    half, L = 128 at d = 64, it does not pay)."""
    return math.frexp(sm_scale)[0] == 0.5 and 4 * d <= bk


def _forward_block_counts(lq, lk, bq, bk, causal):
    """``(live, masked)``: of the ``nq * nk`` (Q block, KV block) pairs of
    one (batch x head) row, those the forward kernel computes and those it
    masks, under ``causal``'s rule (:func:`_rule`).  The one-pass body
    (``nk == 1``) masks every block of a causal call; the streaming one only
    those the diagonal cuts."""
    nq, nk = lq // bq, lk // bk
    if not causal:
        return nq * nk, 0
    pairs = [_causal_block(i, j, bq, bk, *_rule(causal))
             for i in range(nq) for j in range(nk)]
    live = sum(lv for lv, _ in pairs)
    return live, live if nk == 1 else sum(lv and ct for lv, ct in pairs)


def _kernel_name(direction, masked, rule):
    """What a kernel is called in a trace: ``mxtpu_dsa_attn_*`` under a
    selection mask, ``mxtpu_bd_attn_*`` under a block rule other than the
    causal one, ``mxtpu_flash_*`` otherwise."""
    if masked:
        return "mxtpu_dsa_attn_" + direction
    return ("mxtpu_flash_" if rule == (1, 0) else "mxtpu_bd_attn_") \
        + direction


def _shared(kb, like):
    """A kv head's block inside a kernel, shared by the query heads of
    ``like``: broadcast to their rows where it has fewer."""
    if kb.shape[0] == like.shape[0]:
        return kb
    return jnp.broadcast_to(kb, like.shape[:1] + kb.shape[1:])


def _pallas_forward(q, k, v, causal, sm_scale, bq, bk, interpret=False,
                    mask=None):
    """``mask``: a selection of keys a query, (batches, Lk, Lq) int8 with
    the keys first like the score tile, nonzero where query ``t`` attends
    to key ``s`` (the causal mask is part of it: the call is ``causal``,
    which here only says which blocks hold nothing); the ``bh`` rows are
    then whole batches of ``bh / batches`` heads, one mask a batch.  Every
    live block is masked by its tile of it and the kernel is
    ``mxtpu_dsa_attn_fwd``.  K and V may have fewer rows than Q: each of
    theirs then serves ``bh / rows`` consecutive query rows (grouped-query
    attention), and a program's rows are query heads of one kv head, which
    read its K / V block and the mask tile once (:func:`_forward_tiling`:
    its own G and ``bq``).  ``causal``: False, True, or a block rule
    ``(beta, offset)`` (:func:`_sees`), whose kernel is
    ``mxtpu_bd_attn_fwd``; under an offset a query that sees no key gives
    an output of 0 and a log-sum-exp of -inf."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    masked = mask is not None
    rule = _rule(causal) if causal else (1, 0)
    itemsize = q.dtype.itemsize
    # from the rows this call sees: a chip's own, inside _per_batch_shard
    # (a program's rows share a mask tile: they are one batch's heads)
    heads = bh // mask.shape[0] if masked else bh
    g, bq, bk = _forward_tiling(heads, group, lq, lk, bq, bk, d, itemsize,
                                lk > bk, dv, masked)
    kv_rows = g if group == 1 else 1
    nq, nk = lq // bq, lk // bk
    streaming = nk > 1
    shape = (bq, bk, d, itemsize, streaming, dv, masked, kv_rows)
    _telem.set_gauge("flash.fwd.rows_per_program", g)
    _telem.set_gauge("flash.fwd.heads_per_kv_block", g // kv_rows)
    live, cut_blocks = _forward_block_counts(lq, lk, bq, bk, causal)
    _telem.set_gauge("flash.fwd.blocks_live", live)
    _telem.set_gauge("flash.fwd.blocks_masked", cut_blocks)
    scale_on_q = _scale_on_q(sm_scale, d, bk)
    # the streaming body: KV blocks a grid step walks, and the Q block's
    # columns (queries: independent of each other) in two halves where the
    # program has few rows to keep the MXU and the vector unit both busy
    # (a program of query heads that share a K / V block gains nothing
    # from halves of its heads: PERF.md §6, PR 40)
    nsub = _kv_blocks_per_step(nk, g, *shape[:4], dv, masked,
                               kv_rows) if streaming else 1
    _telem.set_gauge("flash.fwd.kv_blocks_per_step", nsub)
    halves = 2 if g <= 2 and bq % 256 == 0 else 1
    cols = [slice(c * bq // halves, (c + 1) * bq // halves)
            for c in range(halves)]
    # rows on sublanes where G fills whole tiles; otherwise mosaic's
    # (8, 128) tile is met by a broadcast sublane dim, sliced off below
    lse_rows = g % 8 == 0

    def in_hbm(x):
        # Q, K, V, O and the log-sum-exp stream between HBM and the
        # program's blocks, which is what G and the kernel's bytes are
        # reckoned from: XLA, left to choose, parks a whole operand in VMEM
        # (prefetched beside the op before), and the kernel then runs ahead
        # of the HBM rate it is held against
        # (inside a compiled program only: an eager call's operands are
        # its program's own arguments, in HBM, and the constraint is no
        # eager operation)
        if interpret or not isinstance(q, jax.core.Tracer):
            return x
        if isinstance(x, jax.ShapeDtypeStruct):
            return pltpu.HBM(x.shape, x.dtype)
        return pltpu.with_memory_space_constraint(x, pltpu.HBM)

    def operand(x):
        # the CPU backend has no batched bf16 x bf16 -> f32 dot, so the
        # interpreter multiplies in float32: the same products, exactly
        return x.astype(jnp.float32) if interpret else x

    def selected(keep):
        # the (bk, cols) int8 tile of the selection mask as a condition
        return keep.astype(jnp.int32) != 0

    def scores(qb, kb, i, j, mask, col=0, keep=None):
        # of Q block i from its column `col` on against KV block j; `keep`:
        # the (bk, cols) tile of the selection mask as a condition, in the
        # iota's place
        if scale_on_q:
            qb = qb * sm_scale
        # (g, d, bk) x (g, d, cols) over d -> (g, bk, cols): keys on sublanes
        s = lax.dot_general(
            operand(_shared(kb, qb)), operand(qb),
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if not scale_on_q:
            s = s * sm_scale
        if keep is not None:
            s = jnp.where(keep[None], s, _NEG_INF)
        elif mask:
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, s.shape[1:], 0)
            qpos = i * bq + col + lax.broadcasted_iota(jnp.int32,
                                                       s.shape[1:], 1)
            s = jnp.where(_sees(qpos, kpos, *rule)[None], s, _NEG_INF)
        return s

    def p_dot_v(vb, p):
        # (g, dv, bk) x (g, bk, cols) -> (g, dv, cols)
        return lax.dot_general(
            operand(_shared(vb, p)), operand(p.astype(vb.dtype)),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    def finish(o_ref, lse_ref, o, lse, m):
        # o (g, dv, bq) float32; lse and m give (g, 1, bq) when called, in
        # the order the stores had before there were offsets.  Under an
        # offset a query whose rule hides every key kept m's -1e30
        if not rule[1]:
            o_ref[...] = o.astype(o_ref.dtype)
            store_lse(lse_ref, lse())
            return
        seen = m() > _NEG_INF
        o_ref[...] = jnp.where(seen, o, 0.0).astype(o_ref.dtype)
        store_lse(lse_ref, jnp.where(seen, lse(), -jnp.inf))

    def store_lse(lse_ref, lse):                # lse (g, 1, bq)
        if lse_rows:
            lse_ref[...] = lse[:, 0, :]
        else:
            lse_ref[...] = jnp.broadcast_to(lse, (g, 8, bq))

    def one_pass(q_ref, k_ref, v_ref, *refs):
        # all of Lk is in the block: nothing to stream, no running state
        o_ref, lse_ref = refs[-2:]
        s = scores(q_ref[...], k_ref[...], pl.program_id(1), 0, causal,
                   keep=selected(refs[0][0]) if masked else None)
        m = jnp.max(s, axis=1, keepdims=True)   # (g, 1, bq)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)   # >= 1: the max's own term
        finish(o_ref, lse_ref, p_dot_v(v_ref[...], p) / l,
               lambda: m + jnp.log(l), lambda: m)

    def streaming(q_ref, k_ref, v_ref, *refs):
        o_ref, lse_ref, acc, m_i, l_i = refs[-5:]
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_i[...] = jnp.full_like(m_i, _NEG_INF)
            l_i[...] = jnp.zeros_like(l_i)
            acc[...] = jnp.zeros_like(acc)

        def walk(mask):
            def block(sub, carry):
                ks = pl.ds(pl.multiple_of(sub * bk, bk), bk)
                # every half's QK^T before the first's softmax: the MXU
                # then has the next product to do under the vector unit;
                # a half's mask tile is widened and compared once for all
                # of the program's heads
                tiles = [scores(q_ref[:, :, cs], k_ref[:, :, ks], i,
                                j * nsub + sub, mask, cs.start,
                                selected(refs[0][0, ks, cs]) if masked
                                else None)
                         for cs in cols]
                for cs, s in zip(cols, tiles):
                    m_old = m_i[:, :, cs]
                    m_new = jnp.maximum(
                        m_old, jnp.max(s, axis=1, keepdims=True))
                    p = jnp.exp(s - m_new)          # (g, bk, cols) f32
                    alpha = jnp.exp(m_old - m_new)  # (g, 1, cols)
                    l_i[:, :, cs] = l_i[:, :, cs] * alpha + jnp.sum(
                        p, axis=1, keepdims=True)
                    acc[:, :, cs] = acc[:, :, cs] * alpha + p_dot_v(
                        v_ref[:, :, ks], p)
                    m_i[:, :, cs] = m_new
                return carry
            return block

        if causal:
            # of this step's KV blocks: the wholly visible run plain, the
            # ones the diagonal cuts masked, the wholly masked not at all
            # (and nothing is fetched for a step that has only those: at_kv)
            visible, live = (jnp.clip(n - j * nsub, 0, nsub)
                             for n in _causal_extent(i, bq, bk, *rule))
            if masked:          # no block of a learned selection is whole
                visible = 0
            else:
                lax.fori_loop(0, visible, walk(False), 0)
            lax.fori_loop(visible, live, walk(True), 0)
        else:
            lax.fori_loop(0, nsub, walk(False), 0)

        @pl.when(j == nk // nsub - 1)
        def _fin():
            denom = jnp.maximum(l_i[...], 1e-30)
            finish(o_ref, lse_ref, acc[...] / denom,
                   lambda: m_i[...] + jnp.log(denom), lambda: m_i[...])

    if lse_rows:
        lse_spec = pl.BlockSpec((g, bq), lambda b, i, j: (b, i))
        lse_shape = jax.ShapeDtypeStruct((bh, lq), jnp.float32)
    else:
        lse_spec = pl.BlockSpec((g, 8, bq), lambda b, i, j: (b, 0, i))
        lse_shape = jax.ShapeDtypeStruct((bh, 8, lq), jnp.float32)

    def at_kv(b, i, j):
        # a skipped step asks for the row's last live K / V blocks again,
        # which are in VMEM already: no copy is issued for it; a program of
        # query heads reads their kv head's
        if causal:
            j = _kv_block_fetched(i, j, bq, bk * nsub, *rule)
        return (b if group == 1 else b * g // group, 0, j)

    # where one row at the caller's blocks does not fit the budget, mosaic's
    # limit is raised by what it is over (nsub is 1 there)
    over = _program_vmem_bytes(g, *shape) - _VMEM_BUDGET
    mask_spec = [pl.BlockSpec(
        (1, bk * nsub, bq),
        lambda b, i, j: (b * g // heads, at_kv(b, i, j)[2], i))] \
        if masked else []
    out_t, lse = pl.pallas_call(
        one_pass if nk == 1 else streaming,
        grid=(bh // g, nq, nk // nsub),
        in_specs=[
            pl.BlockSpec((g, d, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((kv_rows, d, bk * nsub), at_kv),
            pl.BlockSpec((kv_rows, dv, bk * nsub), at_kv),
        ] + mask_spec,
        out_specs=[
            pl.BlockSpec((g, dv, bq), lambda b, i, j: (b, 0, i)),
            lse_spec,
        ],
        out_shape=[in_hbm(jax.ShapeDtypeStruct((bh, dv, lq), q.dtype)),
                   in_hbm(lse_shape)],
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((g, dv, bq), jnp.float32),
            pltpu.VMEM((g, 1, bq), jnp.float32),
            pltpu.VMEM((g, 1, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_DEFAULT_LIMIT + over if over > 0 else None),
        name=_kernel_name("fwd", masked, rule),
        interpret=interpret,
    )(*(in_hbm(jnp.swapaxes(a, 1, 2)) for a in (q, k, v)),
      *([in_hbm(mask)] if masked else []))
    return jnp.swapaxes(out_t, 1, 2), lse if lse_rows else lse[:, 0, :]


# ---------------------------------------------------------------------------
# Blockwise XLA fallback (same algorithm, lax.scan over KV blocks)
# ---------------------------------------------------------------------------

def _mask_blocks(mask, bh, nk, bk):
    """A selection mask (batches, Lk, Lq) as the scans walk it: (nk, bh, Lq,
    bk) bool, a batch's mask repeated for its heads (the scans are the
    fallback and the tests' reference: the copy is theirs alone)."""
    nb, _, lq = mask.shape
    blocks = (mask != 0).reshape(nb, nk, bk, lq).transpose(1, 0, 3, 2)
    return jnp.repeat(blocks, bh // nb, axis=1)


def _scan_forward(q, k, v, causal, sm_scale, bk, mask=None):
    bh, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[2]
    nk = lk // bk
    kb = k.reshape(bh, nk, bk, d).transpose(1, 0, 2, 3)   # (nk, bh, bk, d)
    vb = v.reshape(bh, nk, bk, dv).transpose(1, 0, 2, 3)
    qpos = lax.broadcasted_iota(jnp.int32, (lq, bk), 0)

    def step(carry, blk):
        acc, m_i, l_i, j = carry
        kj, vj = blk[:2]
        s = jnp.einsum("bqd,bkd->bqk", q, kj,
                       preferred_element_type=jnp.float32) * sm_scale
        if mask is not None:
            s = jnp.where(blk[2], s, _NEG_INF)
        elif causal:
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (lq, bk), 1)
            s = jnp.where(_sees(qpos, kpos, *_rule(causal))[None], s,
                          _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bqk,bkd->bqd", p.astype(v.dtype), vj,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l_new, j + 1), None

    init = (jnp.zeros((bh, lq, dv), jnp.float32),
            jnp.full((bh, lq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((bh, lq, 1), jnp.float32),
            jnp.int32(0))
    (acc, m_i, l_i, _), _ = lax.scan(
        step, init, (kb, vb) if mask is None
        else (kb, vb, _mask_blocks(mask, bh, nk, bk)))
    denom = jnp.maximum(l_i, 1e-30)
    out, lse = acc / denom, m_i + jnp.log(denom)
    if causal and _rule(causal)[1]:     # a query that sees no key: 0, -inf
        seen = m_i > _NEG_INF
        out, lse = jnp.where(seen, out, 0.0), jnp.where(seen, lse, -jnp.inf)
    return out.astype(q.dtype), lse[..., 0]


# ---------------------------------------------------------------------------
# Backward as a scan (where there is no kernel; the tests' reference)
# ---------------------------------------------------------------------------

def _scan_backward(q, k, v, out, lse, g, causal, sm_scale, bk, mask=None):
    bh, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[2]
    nk = lk // bk
    kb = k.reshape(bh, nk, bk, d).transpose(1, 0, 2, 3)
    vb = v.reshape(bh, nk, bk, dv).transpose(1, 0, 2, 3)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # (bh, lq, 1)
    qpos = lax.broadcasted_iota(jnp.int32, (lq, bk), 0)

    def step(dq, blk):
        kj, vj, j = blk[:3]
        s = jnp.einsum("bqd,bkd->bqk", q, kj,
                       preferred_element_type=jnp.float32) * sm_scale
        if mask is not None:
            s = jnp.where(blk[3], s, _NEG_INF)
        elif causal:
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (lq, bk), 1)
            s = jnp.where(_sees(qpos, kpos, *_rule(causal))[None], s,
                          _NEG_INF)
        p = jnp.exp(s - lse[..., None])                     # (bh, lq, bk)
        dv_j = jnp.einsum("bqk,bqd->bkd", p, g.astype(jnp.float32))
        dp = jnp.einsum("bqd,bkd->bqk", g.astype(jnp.float32),
                        vj.astype(jnp.float32))
        ds = p * (dp - delta) * sm_scale
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kj.astype(jnp.float32))
        return dq, (dk_j, dv_j)

    steps = (kb, vb, jnp.arange(nk, dtype=jnp.int32))
    if mask is not None:
        steps += (_mask_blocks(mask, bh, nk, bk),)
    dq, (dk, dvs) = lax.scan(step, jnp.zeros((bh, lq, d), jnp.float32),
                             steps)
    dk = dk.transpose(1, 0, 2, 3).reshape(bh, lk, d)
    dvs = dvs.transpose(1, 0, 2, 3).reshape(bh, lk, dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dvs.astype(v.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU backward
# ---------------------------------------------------------------------------

def _backward_vmem_bytes(g, bq, bk, lq, d, itemsize, streaming, dv=None,
                         masked=False):
    """VMEM bytes one backward program of ``g`` rows holds: the
    double-buffered Q / dQ and O / dO blocks at ``bq``, K / dK and V / dV at
    ``bk`` (twice the forward's), the log-sum-exp block, the float32
    dK / dV accumulators and the row's whole dQ of the streaming body, and
    the (g, bk, bq) S / P and dP / dS temporaries in float32 with their
    casts, delta and the three float32 products."""
    dv = d if dv is None else dv
    if masked:
        return _mask_vmem_bytes(bq, bk) + _backward_vmem_bytes(
            g, bq, bk, lq, d, itemsize, streaming, dv)
    blocks = 4 * g * _padded_head_dims(d, dv, itemsize) * (bq + bk) * itemsize
    lse = 2 * g * 8 * bq * 4
    scratch = g * ((d + dv) * bk + d * lq) * 4 if streaming else 0
    temps = g * (bk * bq * (4 + 4 + 2 * itemsize) + 8 * bq * 4
                 + ((d + dv) * bk + d * bq) * 4)
    return blocks + lse + scratch + temps


def _backward_rows_per_program(bh, bq, bk, lq, d, itemsize, streaming,
                               dv=None, masked=False):
    """G of the backward kernel, by the forward's rule (:func:`_pick_rows`)
    over :func:`_backward_vmem_bytes`; a row moves eight blocks where the
    forward moves four."""
    dv = d if dv is None else dv
    return _pick_rows(
        bh, lambda g: _backward_vmem_bytes(g, bq, bk, lq, d, itemsize,
                                           streaming, dv, masked),
        2 * (bq + bk) * (d + dv) * itemsize)


# What a backward program of a kv head's query heads may take of VMEM with
# its limit raised: the v5e has 128 MiB, and XLA keeps some for itself.
_VMEM_GROUPED_CEILING = 96 * 2 ** 20


def _grouped_backward_vmem_bytes(g, bq, bk, lk, d, itemsize, dv, masked):
    """VMEM bytes of a backward program that takes the ``g`` query heads of
    one kv head (:func:`_grouped_backward_blocks`): the double-buffered
    Q / dQ and O / dO blocks of ``g`` heads and the K / dK and V / dV blocks
    of one, the log-sum-exp block, the float32 scratch — the Q block's dQ
    and delta, the kv head's whole-row dK and dV — and
    :func:`_backward_vmem_bytes`'s temporaries at ``g`` heads."""
    blocks = 4 * _padded_head_dims(d, dv, itemsize) * (g * bq + bk) * itemsize
    lse = 2 * g * 8 * bq * 4
    scratch = (g * (d + 8) * bq + (d + dv) * lk) * 4
    temps = g * (bk * bq * (4 + 4 + 2 * itemsize) + 8 * bq * 4
                 + ((d + dv) * bk + d * bq) * 4)
    return blocks + lse + scratch + temps + (
        _mask_vmem_bytes(bq, bk) if masked else 0)


def _grouped_backward_blocks(group, lq, lk, bq, bk, d, itemsize, dv,
                             masked):
    """``(bq, bk)`` of a backward program that takes all ``group`` query
    heads of one kv head (K / V at fewer rows than Q), or None where none
    fits ``_VMEM_GROUPED_CEILING`` (its float32 dK / dV hold the kv head's
    whole row): square blocks of the caller's side, halved until the
    program fits.  The body has no walk of its own, so a grid step's fixed
    cost is paid once a tile: on a TPU v5e the whole backward rule at 32
    query heads over 4 kv heads, L = 16384, d = 128 takes 34.1 ms a call at
    (512, 512), 35.2 at (256, 512), 37.3 at (256, 256) and 43.8 with K / V
    repeated (PERF.md §6)."""
    side = max(bq, bk)
    while side >= 128:
        bq, bk = _pick_block(lq, side), _pick_block(lk, side)
        if _grouped_backward_vmem_bytes(group, bq, bk, lk, d, itemsize, dv,
                                        masked) <= _VMEM_GROUPED_CEILING:
            return bq, bk
        side //= 2
    return None


def _pallas_backward(q, k, v, out, lse, do, causal, sm_scale, bq, bk,
                     interpret=False, mask=None):
    """dQ, dK, dV of the kernel's forward, operands as (rows, D, L) and the
    scores transposed like the forward's (keys on sublanes, queries on
    lanes; ``lse`` and ``delta = rowsum(O dO)`` are lane-major rows).  One
    program takes ``g`` rows and a (bk, bq) block of scores: S and dP from
    two products, dV, dK and dQ from three more, bf16 operands and float32
    accumulation.  Where one block holds all of Lq and Lk that is the whole
    kernel; otherwise the grid walks the Q blocks inside each KV block,
    dK / dV accumulate over the inner walk and the row's dQ over the outer
    one (a float32 scratch of the whole row, written out on the last KV
    block), and a causal block that is wholly masked is skipped: it would
    add exact zeros.  ``mask``: the forward's selection mask; every live
    block is then masked by its tile, the kernel is ``mxtpu_dsa_attn_bwd``,
    and where a row's dQ is over the VMEM rule (L = 16384 at d = 128: 8 MiB)
    Mosaic's limit is raised by what it is over, as the forward's is.
    ``causal`` a block rule (:func:`_sees`): the kernel is
    ``mxtpu_bd_attn_bwd``, and ``lse`` has no -inf (:func:`_flash_bwd`).

    K and V may have fewer rows than Q (grouped-query attention, read in
    place): a program then takes the ``group`` query heads of one kv head
    at :func:`_grouped_backward_blocks`' blocks, which share its K / V block
    and the mask tile, and the loops turn the other way round — the Q
    blocks outer, the KV blocks inner — so that what is held whole in VMEM
    is the kv head's float32 dK / dV row, summed over its query heads and
    every Q block and written out on the last Q block's walk, while dQ and
    ``delta`` are the current Q block's alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    masked = mask is not None
    itemsize = q.dtype.itemsize
    if group > 1:
        bq, bk = _grouped_backward_blocks(group, lq, lk, bq, bk, d, itemsize,
                                          dv, masked)
    nq, nk = lq // bq, lk // bk
    one = nq == 1 and nk == 1 and group == 1
    rule = _rule(causal) if causal else (1, 0)
    shape = (bq, bk, lq, d, itemsize, not one, dv) + \
        ((True,) if masked else ())
    # from the rows this call sees: a chip's own, inside _per_batch_shard
    # (a program's rows share a mask tile: they are one batch's heads)
    heads = bh // mask.shape[0] if masked else bh
    g = _backward_rows_per_program(heads, *shape) if group == 1 else group
    kv_rows = g if group == 1 else 1
    _telem.set_gauge("flash.bwd.rows_per_program", g)
    _telem.set_gauge("flash.bwd.heads_per_kv_block", g // kv_rows)

    def dot(a, b, contract):
        # batched over the g rows; float32 in the interpreter, as the forward
        if interpret:
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return lax.dot_general(a, b, (contract, ((0,), (0,))),
                               preferred_element_type=jnp.float32)

    def per_kv(x):
        # a kv head's dK / dV: the sum of its query heads'
        return x if kv_rows == g else jnp.sum(x, axis=0, keepdims=True)

    def products(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, i, j, mask,
                 keep=None, delta=None):
        """The block's dV, dK and dQ in float32, dK and dQ still without
        ``sm_scale`` (applied to the small results, not to the scores);
        ``delta``: the Q block's rowsum(O dO) where it is held, else it is
        computed here."""
        qb, kb, vb, dob = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        # (g, d, bk) x (g, d, bq) over d -> (g, bk, bq)
        s = dot(_shared(kb, qb), qb, ((1,), (1,))) * sm_scale
        if keep is not None:
            s = jnp.where((keep[0].astype(jnp.int32) != 0)[None], s,
                          _NEG_INF)
        elif mask:
            kpos = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            qpos = i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            s = jnp.where(_sees(qpos, kpos, *rule)[None], s, _NEG_INF)
        p = jnp.exp(s - lse_ref[...])                      # lse (g, 1, bq)
        if delta is None:
            delta = jnp.sum(o_ref[...].astype(jnp.float32) *
                            dob.astype(jnp.float32), axis=1, keepdims=True)
        ds = p * (dot(_shared(vb, dob), dob, ((1,), (1,))) - delta)
        p, ds = p.astype(qb.dtype), ds.astype(qb.dtype)
        return (per_kv(dot(dob, p, ((2,), (2,)))),        # (kv, dv, bk)
                per_kv(dot(qb, ds, ((2,), (2,)))),        # (kv, d, bk)
                dot(_shared(kb, ds), ds, ((2,), (1,))))       # (g, d, bq)

    def walk(step, i, j):
        # one (Q block i, KV block j) pair of the causal walk, or all
        if causal:
            # wholly masked (its first key after the block's last query):
            # skipped; cut by the diagonal: masked; wholly visible: plain
            live, cut = _causal_block(i, j, bq, bk, *rule)
            if masked:          # no block of a learned selection is whole
                pl.when(live)(lambda: step(True))
            else:
                pl.when(live & cut)(lambda: step(True))
                pl.when(live & jnp.logical_not(cut))(lambda: step(False))
        else:
            step(False)

    def one_pass(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *refs):
        dq_ref, dk_ref, dv_ref = refs[-3:]
        dvb, dkb, dqb = products(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                                 0, 0, causal, refs[0] if masked else None)
        dv_ref[...] = dvb.astype(dv_ref.dtype)
        dk_ref[...] = (dkb * sm_scale).astype(dk_ref.dtype)
        dq_ref[...] = (dqb * sm_scale).astype(dq_ref.dtype)

    def streaming(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *refs):
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[-6:]
        j = pl.program_id(1)                    # the KV block, outer
        i = pl.program_id(2)                    # the Q block, inner

        @pl.when(i == 0)
        def _init_kv():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        @pl.when(j == 0)
        def _init_q():
            dq_acc[i] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

        def step(mask):
            dvb, dkb, dqb = products(q_ref, k_ref, v_ref, o_ref, lse_ref,
                                     do_ref, i, j, mask,
                                     refs[0] if masked else None)
            dv_acc[...] += dvb
            dk_acc[...] += dkb
            dq_acc[i] += dqb

        walk(step, i, j)

        @pl.when(i == nq - 1)
        def _fin_kv():
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
            dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)

        @pl.when(j == nk - 1)
        def _fin_q():
            dq_ref[...] = (dq_acc[i] * sm_scale).astype(dq_ref.dtype)

    def q_outer(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *refs):
        # a kv head's query heads: Q block i outer, KV block j inner; the
        # kv head's dK / dV rows whole in VMEM, the Q block's dQ and delta
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, delta = refs[-7:]
        i = pl.program_id(1)                    # the Q block, outer
        j = pl.program_id(2)                    # the KV block, inner

        @pl.when(j == 0)
        def _init_q():
            dq_acc[...] = jnp.zeros_like(dq_acc)
            delta[...] = jnp.sum(o_ref[...].astype(jnp.float32) *
                                 do_ref[...].astype(jnp.float32), axis=1,
                                 keepdims=True)

        @pl.when(i == 0)
        def _init_kv():
            dk_acc[j] = jnp.zeros(dk_acc.shape[1:], dk_acc.dtype)
            dv_acc[j] = jnp.zeros(dv_acc.shape[1:], dv_acc.dtype)

        def step(mask):
            dvb, dkb, dqb = products(q_ref, k_ref, v_ref, o_ref, lse_ref,
                                     do_ref, i, j, mask,
                                     refs[0] if masked else None, delta[...])
            dv_acc[j] += dvb[0]
            dk_acc[j] += dkb[0]
            dq_acc[...] += dqb

        walk(step, i, j)

        @pl.when(i == nq - 1)
        def _fin_kv():
            dv_ref[...] = dv_acc[j][None].astype(dv_ref.dtype)
            dk_ref[...] = (dk_acc[j][None] * sm_scale).astype(dk_ref.dtype)

        @pl.when(j == nk - 1)
        def _fin_q():
            dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)

    if group == 1:
        def at_q(b, j, i):
            # a skipped block asks for the first live one's Q-side blocks
            # again (the last there is, where Lq ends before this KV block),
            # so nothing is fetched for it
            if causal:
                i = jnp.minimum(jnp.maximum(
                    i, _first_live_q_block(j, bq, bk, *rule)), nq - 1)
            return (b, 0, i)

        def at_kv(b, j, i):
            return (b, 0, j)

        def at_dq(b, j, i):
            # parked on block 0 until the last KV block, when each Q block's
            # sum is complete and is written out once
            return (b, 0, jnp.where(j == nk - 1, i, 0))

        def at_mask(b, j, i):
            return (b * g // heads, j, at_q(b, j, i)[2])
        at_dkv = at_kv
        grid = (bh // g, nk, nq)
        scratch = [] if one else [
            pltpu.VMEM((nq, g, d, bq), jnp.float32),
            pltpu.VMEM((g, d, bk), jnp.float32),
            pltpu.VMEM((g, dv, bk), jnp.float32),
        ]
        over = _backward_vmem_bytes(g, *shape) - _VMEM_BUDGET if masked \
            else 0
    else:
        def at_q(b, i, j):
            return (b, 0, i)

        def at_kv(b, i, j):
            # a skipped step asks for the Q block's last live K / V blocks
            # again, which are in VMEM already: no copy is issued for it
            if causal:
                j = _kv_block_fetched(i, j, bq, bk, *rule)
            return (b, 0, j)

        def at_dkv(b, i, j):
            # parked on block 0 until the last Q block, when each KV block's
            # sum is complete and is written out once
            return (b, 0, jnp.where(i == nq - 1, j, 0))

        def at_mask(b, i, j):
            return (b * g // heads, at_kv(b, i, j)[2], i)
        at_dq = at_q
        grid = (bh // g, nq, nk)
        scratch = [
            pltpu.VMEM((g, d, bq), jnp.float32),
            pltpu.VMEM((nk, d, bk), jnp.float32),
            pltpu.VMEM((nk, dv, bk), jnp.float32),
            pltpu.VMEM((g, 1, bq), jnp.float32),
        ]
        over = _grouped_backward_vmem_bytes(g, bq, bk, lk, d, itemsize, dv,
                                            masked) - _VMEM_BUDGET

    mask_spec = [pl.BlockSpec((1, bk, bq), at_mask)] if masked else []
    dq_t, dk_t, dv_t = pl.pallas_call(
        q_outer if group > 1 else one_pass if one else streaming,
        grid=grid,
        in_specs=[
            pl.BlockSpec((g, d, bq), at_q),
            pl.BlockSpec((kv_rows, d, bk), at_kv),
            pl.BlockSpec((kv_rows, dv, bk), at_kv),
            pl.BlockSpec((g, dv, bq), at_q),
            pl.BlockSpec((g, 1, bq), at_q),
            pl.BlockSpec((g, dv, bq), at_q),
        ] + mask_spec,
        out_specs=[
            pl.BlockSpec((g, d, bq), at_dq),
            pl.BlockSpec((kv_rows, d, bk), at_dkv),
            pl.BlockSpec((kv_rows, dv, bk), at_dkv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, d, lq), q.dtype),
            jax.ShapeDtypeStruct((bh // group, d, lk), k.dtype),
            jax.ShapeDtypeStruct((bh // group, dv, lk), v.dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            **({"vmem_limit_bytes": _VMEM_DEFAULT_LIMIT + over}
               if over > 0 else {})),
        name=_kernel_name("bwd", masked, rule),
        interpret=interpret,
    )(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
      jnp.swapaxes(out, 1, 2), lse[:, None, :], jnp.swapaxes(do, 1, 2),
      *([mask] if masked else []))
    return tuple(jnp.swapaxes(a, 1, 2) for a in (dq_t, dk_t, dv_t))


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------

def _use_pallas(lq, lk, d, dv=None):
    """``(bq, bk)`` for :func:`_pallas_forward`, or None where the scan
    runs: no kernel mode, or shapes the kernel does not tile (``dv``: the
    head dim of V where it is not ``d``)."""
    if kernel_mode() is None:
        return None
    bq = _pick_block(lq)
    bk = _pick_block(lk)
    # d=64 is fine: Mosaic pads the lane dim; BERT-base heads (768/12) hit
    # this. Verified on TPU v5e vs the scan path (max abs diff 1.8e-7 f32).
    if bq is None or bk is None or d % 64 or (dv or d) % 64:
        return None
    return bq, bk


def _dp_mesh(q, *kv):
    """The mesh whose data axis this call's kernels are split over by hand,
    or None: set when the call is being traced into a step that XLA
    partitions over that axis by itself (the trainer's plain dp path: a jit
    over batch-sharded inputs).

    XLA refuses to partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    there each chip runs the kernel on its own (batch x heads) rows.  The
    step says how it is split through the ambient mesh
    (``parallel.mesh_scope``); a step that is already inside a shard_map
    (ZeRO-1) masks it with ``mesh_scope(None)``.  The split keeps a chip's
    query rows with the kv rows they read where the leading dimensions of
    q and of ``kv`` (fewer rows: grouped-query attention) all divide by
    the data axis.  A mesh with a second axis
    of size > 1 is left to fail as before: only the leading dimension is
    known to be splittable.  Read once, where the op is called: the scope
    covers the step's forward trace, and the backward, traced after it has
    closed, is handed what the forward saw."""
    from ..parallel.mesh import AXIS_DP, current_mesh
    mesh = current_mesh()
    if mesh is None or not isinstance(q, jax.core.Tracer):
        return None
    n = mesh.shape.get(AXIS_DP, 1)
    if n == 1 or n != mesh.size or any(x.shape[0] % n for x in (q,) + kv):
        return None
    return mesh


def _per_batch_shard(kernel, mesh):
    """``kernel``, inside a shard_map over the data axis of ``mesh``
    (:func:`_dp_mesh`) with every operand and result split over its leading
    (batch x head) dimension; ``kernel`` itself where there is none."""
    if mesh is None:
        return kernel
    from ..parallel.mesh import AXIS_DP
    return shard_map(kernel, mesh=mesh, in_specs=P(AXIS_DP),
                     out_specs=P(AXIS_DP), check_vma=False)


def _flash(q, k, v, causal, sm_scale):
    return _flash_on(q, k, v, causal, sm_scale, _dp_mesh(q))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_on(q, k, v, causal, sm_scale, mesh):
    return _forward(q, k, v, None, causal, sm_scale, mesh)[0]


def _flash_fwd(q, k, v, causal, sm_scale, mesh):
    return _forward(q, k, v, None, causal, sm_scale, mesh)


def _forward(q, k, v, mask, causal, sm_scale, mesh):
    """``(out, residuals)``; ``mask``: a selection mask
    (:func:`_pallas_forward`) or None."""
    blocks = _use_pallas(q.shape[1], k.shape[1], q.shape[2], v.shape[2])
    # counted while tracing: one per attention layer of a compiled program
    # (under a selection mask or a block rule by those ops' own names)
    _telem.inc(_counter("fwd", mask, causal, blocks))
    if blocks is not None:
        kernel = functools.partial(
            _pallas_forward, causal=causal, sm_scale=sm_scale, bq=blocks[0],
            bk=blocks[1], interpret=kernel_mode() == "interpret")
        if mask is None:
            out, lse = _per_batch_shard(kernel, mesh)(q, k, v)
        else:
            out, lse = _per_batch_shard(
                lambda q, k, v, mask: kernel(q, k, v, mask=mask), mesh)(
                    q, k, v, mask)
    else:
        bk = _pick_block(k.shape[1], 256) or k.shape[1]
        group = q.shape[0] // k.shape[0]
        out, lse = _scan_forward(q, jnp.repeat(k, group, axis=0),
                                 jnp.repeat(v, group, axis=0), causal,
                                 sm_scale, bk, mask)
    return out, (q, k, v, out, lse, mask)


def _counter(direction, mask, causal, blocks):
    """The counter a traced call adds to: ``flash.<direction>``,
    ``dsa.attn.<direction>`` under a selection mask, ``bd.attn.<direction>``
    under a block rule, each ``.pallas`` or the fallback's."""
    if mask is not None:
        name, fallback = "dsa.attn.", ".scan"
    elif causal and _rule(causal) != (1, 0):
        name, fallback = "bd.attn.", ".xla"
    else:
        name, fallback = "flash.", ".scan"
    return name + direction + (fallback if blocks is None else ".pallas")


def _at_query_rows(backward, group):
    """``backward`` (q, k, v, ...) -> (dq, dk, dv) at as many K / V rows as
    query rows, taking K / V at ``1 / group`` of them: each kv row repeated
    to the ``group`` query rows it serves, dK / dV summed back by the
    repeat's own transpose."""
    if group == 1:
        return backward

    def grouped(q, k, v, *rest):
        kv, summed = jax.vjp(lambda k, v: (jnp.repeat(k, group, axis=0),
                                           jnp.repeat(v, group, axis=0)),
                             k, v)
        dq, dk, dv = backward(q, *kv, *rest)
        return (dq,) + summed((dk, dv))
    return grouped


def _flash_bwd(causal, sm_scale, mesh, res, do):
    q, k, v, out, lse, mask = res
    lq, lk, d, dv = q.shape[1], k.shape[1], q.shape[2], v.shape[2]
    blocks = _use_pallas(lq, lk, d, dv)
    # K / V at fewer rows than Q: the kernel reads them in place where a
    # program of a kv head's query heads fits VMEM (its dK / dV rows whole);
    # otherwise the backward takes K / V repeated to the query rows
    group = q.shape[0] // k.shape[0]
    in_place = blocks is not None and group > 1 and \
        _grouped_backward_blocks(group, lq, lk, *blocks, d,
                                 q.dtype.itemsize, dv, mask is not None)
    # a streamed row's float32 dQ is held whole in VMEM: where one row is
    # over the budget (L = 16384 at d = 192) the scan runs; under a
    # selection mask the kernel runs with Mosaic's limit raised instead
    # (the scan at L = 16384 holds (rows, L, bk) float32 scores)
    if mask is None and blocks is not None and not in_place and \
            _backward_vmem_bytes(1, *blocks, lq, d, q.dtype.itemsize,
                                 (lq, lk) != blocks, dv) > _VMEM_BUDGET:
        blocks = None
    # counted while tracing, as the forward's
    _telem.inc(_counter("bwd", mask, causal, blocks))
    if causal and _rule(causal)[1]:
        # a query its offset hides every key from has a log-sum-exp of
        # -inf: as +inf its probabilities come out 0, not exp(inf)
        lse = jnp.where(jnp.isneginf(lse), jnp.inf, lse)
    if blocks is None:
        bk = _pick_block(lk, 256) or lk
        return _at_query_rows(functools.partial(
            _scan_backward, causal=causal, sm_scale=sm_scale, bk=bk,
            mask=mask), group)(q, k, v, out, lse, do)
    kernel = functools.partial(
        _pallas_backward, causal=causal, sm_scale=sm_scale, bq=blocks[0],
        bk=blocks[1], interpret=kernel_mode() == "interpret")
    group = 1 if in_place else group
    if mask is None:
        return _per_batch_shard(_at_query_rows(kernel, group), mesh)(
            q, k, v, out, lse, do)
    return _per_batch_shard(_at_query_rows(
        lambda *a: kernel(*a[:6], mask=a[6]), group), mesh)(
            q, k, v, out, lse, do, mask)


_flash_on.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _masked_flash_on(q, k, v, mask, sm_scale, mesh):
    return _masked_flash_fwd(q, k, v, mask, sm_scale, mesh)[0]


def _masked_flash_fwd(q, k, v, mask, sm_scale, mesh):
    out, res = _forward(q, k, v, mask, True, sm_scale, mesh)
    return (out, res[4]), res


def _masked_flash_bwd(sm_scale, mesh, res, cts):
    # the log-sum-exp's cotangent is not read; the mask gets none
    return _flash_bwd(True, sm_scale, mesh, res, cts[0]) + (None,)


_masked_flash_on.defvjp(_masked_flash_fwd, _masked_flash_bwd)


def masked_flash(q, k, v, mask, sm_scale):
    """``(out, lse)`` of attention over a per-query selection of the causal
    past: q (B·H, L, d) and k, v (B·Hkv, L, d), each the rows of whole
    batches of heads, query head ``a`` of a batch reading its kv head
    ``a // (H / Hkv)`` in place (grouped-query attention; ``Hkv = H`` is
    plain attention), mask (B, L, L) int8, keys first, causality included
    (:func:`_pallas_forward`).  Differentiable in q, k, v by the kernels
    :func:`flash_attention` has, dK / dV summed over each kv head's query
    heads; the log-sum-exp (B·H, L) comes without a gradient (the index
    loss reads it detached)."""
    return _masked_flash_on(q, k, v, mask, sm_scale, _dp_mesh(q, k))


def _in_block(q, k, v, beta, sm_scale):
    """``(out, lse)`` of each query over the ``beta`` keys of its own block
    alone, in float32 over (rows, group, L / beta, beta, beta) score tiles:
    q (rows * group, L, d), k, v (rows, L, d)."""
    rows, lk, d = k.shape
    group, n = q.shape[0] // rows, lk // beta
    f32 = jnp.float32
    s = jnp.einsum("rgnqd,rnkd->rgnqk",
                   q.reshape(rows, group, n, beta, d).astype(f32),
                   k.reshape(rows, n, beta, d).astype(f32)) * sm_scale
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    total = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("rgnqk,rnkd->rgnqd", p / total,
                     v.reshape(rows, n, beta, -1).astype(f32))
    return out.reshape(q.shape[0], lk, -1), \
        (m + jnp.log(total)).reshape(q.shape[0], lk)


def _in_block_bwd(q, k, v, out, lse, do, beta, sm_scale):
    """dQ, dK, dV of :func:`_in_block`'s keys under the merged ``out`` and
    ``lse`` of every key a query sees, float32."""
    rows, lk, d = k.shape
    group, n = q.shape[0] // rows, lk // beta
    f32 = jnp.float32

    def tiles(a, kv=False):
        shape = (rows, n, beta, -1) if kv else (rows, group, n, beta, -1)
        return a.reshape(shape).astype(f32)
    qb, kb, vb, dob = tiles(q), tiles(k, True), tiles(v, True), tiles(do)
    s = jnp.einsum("rgnqd,rnkd->rgnqk", qb, kb) * sm_scale
    p = jnp.exp(s - tiles(lse))
    delta = jnp.sum(tiles(out) * dob, axis=-1, keepdims=True)
    ds = p * (jnp.einsum("rgnqd,rnkd->rgnqk", dob, vb) - delta)
    dq = jnp.einsum("rgnqk,rnkd->rgnqd", ds, kb) * sm_scale
    dk = jnp.einsum("rgnqk,rgnqd->rnkd", ds, qb) * sm_scale
    dv = jnp.einsum("rgnqk,rgnqd->rnkd", p, dob)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _bd_noisy_on(q, k0, v0, kt, vt, beta, sm_scale, mesh):
    return _bd_noisy_fwd(q, k0, v0, kt, vt, beta, sm_scale, mesh)[0]


def _bd_noisy_fwd(q, k0, v0, kt, vt, beta, sm_scale, mesh):
    # the clean keys of the blocks before the query's own: the kernel under
    # the rule (beta, 1); the noisy keys of its own block: XLA; one softmax
    # over both, merged by their log-sum-exps
    out_a, res = _forward(q, k0, v0, None, (beta, 1), sm_scale, mesh)
    out_b, lse_b = _in_block(q, kt, vt, beta, sm_scale)
    with jax.named_scope("bd.merge"):
        lse = jnp.logaddexp(res[4], lse_b)
        out = (jnp.exp(res[4] - lse)[..., None] * out_a.astype(jnp.float32)
               + jnp.exp(lse_b - lse)[..., None] * out_b).astype(q.dtype)
    return out, (q, k0, v0, kt, vt, out, lse)


def _bd_noisy_bwd(beta, sm_scale, mesh, res, do):
    # each part's backward under the merged out and lse: the probabilities
    # it recomputes are then the merged softmax's own
    q, k0, v0, kt, vt, out, lse = res
    dq_a, dk0, dv0 = _flash_bwd((beta, 1), sm_scale, mesh,
                                (q, k0, v0, out, lse, None), do)
    with jax.named_scope("bd.noisy"):
        dq_b, dkt, dvt = _in_block_bwd(q, kt, vt, out, lse, do, beta,
                                       sm_scale)
    return ((dq_a.astype(jnp.float32) + dq_b).astype(q.dtype), dk0, dv0,
            dkt.astype(kt.dtype), dvt.astype(vt.dtype))


_bd_noisy_on.defvjp(_bd_noisy_fwd, _bd_noisy_bwd)


def block_diffusion_attention(q, k, v, block_length, sm_scale):
    """Attention of a block-diffusion training sequence, the noisy copy
    ``x_t`` beside the clean ``x_0``: q (B·H, 2L, d), k, v (B·Hkv, 2L, d),
    each row's first L positions the noisy half and its last L the clean
    one, both counted from 0 within their half, query head ``a`` of a batch
    reading kv head ``a // (H / Hkv)`` in place.  With ``beta =
    block_length`` (a power of two dividing L) and ``blk(a) = a // beta``:

    - a clean query sees the clean keys with ``blk(b) <= blk(a)``
      (block-causal): the flash kernels under the rule ``(beta, 0)``;
    - a noisy query sees the clean keys with ``blk(b) < blk(a)`` — the
      kernels under ``(beta, 1)``, whose first block of queries sees none —
      and the noisy keys of its own block, ``blk(b) == blk(a)``: ``beta``
      keys a query, in XLA; one softmax over both, merged by their
      log-sum-exps, each part's backward taken under the merged result;
    - a clean query never sees a noisy key.

    No (2L)² mask exists anywhere; dead tiles are skipped as the causal
    kernels skip them.  Differentiable in q, k, v; returns (B·H, 2L, dv).
    Gauges ``bd.block_length`` and ``bd.offset_rows_empty`` (the queries of
    a row that the offset call leaves without a key: one block's)."""
    lq = q.shape[1] // 2
    if q.shape[1] % 2 or lq % block_length or \
            block_length & (block_length - 1):
        raise ValueError(f"block_diffusion_attention: {q.shape[1]} positions "
                         f"are not two halves of blocks of {block_length} "
                         f"(a power of two)")
    _telem.set_gauge("bd.block_length", block_length)
    _telem.set_gauge("bd.offset_rows_empty", block_length)
    mesh = _dp_mesh(q, k)

    def half(a, i):
        return a[:, i * lq:(i + 1) * lq]
    with jax.named_scope("bd.clean"):
        clean = _flash_on(half(q, 1), half(k, 1), half(v, 1),
                          (block_length, 0), sm_scale, mesh)
    with jax.named_scope("bd.noisy"):
        noisy = _bd_noisy_on(half(q, 0), half(k, 1), half(v, 1), half(k, 0),
                             half(v, 0), block_length, sm_scale, mesh)
    return jnp.concatenate([noisy, clean], axis=1)


def flash_attention(query, key, value, causal=False, sm_scale=None):
    """softmax(QK^T * sm_scale [+ causal mask]) V without materializing the
    score matrix. query/key: (B, H, L, D), value: (B, H, L, Dv) NDArrays or
    jax arrays; Dv may differ from D (latent attention: 192 / 128) and the
    result is (B, H, L, Dv).  ``sm_scale`` defaults to D ** -0.5.

    Differentiable (custom VJP, blockwise backward) and tape-aware: with
    NDArray inputs under ``autograd.record()`` it records one tape node.
    On TPU, with Lq and Lk multiples of 128 and D a multiple of 64, the
    core runs as a Pallas kernel, forward and backward (G batch x head rows
    to a grid program, G from the shapes); otherwise a blockwise-scan XLA
    fallback with identical semantics.  Counters ``flash.fwd.pallas`` /
    ``flash.fwd.scan`` and ``flash.bwd.pallas`` / ``flash.bwd.scan`` say
    which was traced, gauges ``flash.fwd.rows_per_program`` and
    ``flash.bwd.rows_per_program`` the last G,
    ``flash.fwd.heads_per_kv_block`` and ``flash.bwd.heads_per_kv_block``
    the query heads one K / V block serves in a program (1 where every row
    has its own), ``flash.fwd.blocks_live`` /
    ``flash.fwd.blocks_masked`` the (Q block, KV block) pairs a row of the
    last forward kernel computes and those it masks,
    ``flash.fwd.kv_blocks_per_step`` the KV blocks one of its grid steps
    walks.
    """
    from ..ndarray.ndarray import NDArray, apply_nary

    def core(qd, kd, vd):
        if qd.ndim != 4:
            raise ValueError("flash_attention expects (B, H, L, D) inputs, "
                             f"got shape {qd.shape}")
        b, h, lq, d = qd.shape
        lk, dv = kd.shape[2], vd.shape[3]
        scale = 1.0 / math.sqrt(d) if sm_scale is None else float(sm_scale)
        out = _flash(qd.reshape(b * h, lq, d), kd.reshape(b * h, lk, d),
                     vd.reshape(b * h, lk, dv), bool(causal), scale)
        return out.reshape(b, h, lq, dv)

    if isinstance(query, NDArray):
        key = key if isinstance(key, NDArray) else NDArray(jnp.asarray(key))
        value = value if isinstance(value, NDArray) else \
            NDArray(jnp.asarray(value))
        return apply_nary(core, [query, key, value], name="flash_attention")
    return core(jnp.asarray(query), jnp.asarray(key), jnp.asarray(value))
