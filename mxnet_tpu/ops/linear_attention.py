"""Kimi Delta Attention's token mixer: the gated delta rule with a decay of its
own for every key channel, as a chunked scan with a backward.

The specification is the recurrence, a head at a time, ``S`` (dk, dv) float32,
``alpha_t = exp(g_t)`` in (0, 1]^dk, ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

What runs is its chunked form.  Inside a chunk of ``C`` tokens, with ``G`` the
running sum of ``g`` from the chunk's first token (so every factor below is
``exp(G_i - G_j)`` with ``j <= i``: a decay, never over 1) and ``S`` the state
that enters the chunk::

    A[i, j]   = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)        (j <  i)
    Aqk[i, j] =        sum_c q_ic k_jc exp(G_ic - G_jc)        (j <= i)
    T  = (I + A)^-1                     the "WY" form of the C rank-one factors
    W  = T (beta k exp(G)),  U = T (beta v) - W S
    O  = (q exp(G)) S + Aqk U
    S' = Diag(exp(G_C)) S + (k exp(G_C - G))^T U

**The decay is never divided by.**  ``exp(G_i - G_j)`` cannot be written as
``exp(G_i) / exp(G_j)`` over a chunk: at ``A = 16``, ``dt = 0.1`` the running
product over 64 tokens is e^-100 and its inverse is no float32.  The pair
products are taken a block of ``_SUB`` = 16 rows at a time against the block's
first row ``n``: ``(x_i exp(G_i - G_n)) . (k_j exp(G_n - G_j))``; for ``j``
before the block both factors are decays, inside the block the second is at
most ``exp(15 |g|)``, which float32 holds while ``|g| < 5.8``.  So a
log-decay under ``_MIN_LOG_DECAY`` = -5.5 is taken as -5.5 (and hands back no
gradient): a channel keeps at least e^-5.5 = 0.4 % of its state a token, where
the configuration's draws reach e^-1.6.  Cutting the exponent of a pair
instead would not do: one token of strong decay at a block's start pushes
every later row's factor under float32's range, and with it the pairs behind
that token, whose true weight is near 1.

``T`` comes from matrix products alone: the 16 x 16 diagonal blocks by
``(I - D)(I + D^2)(I + D^4)(I + D^8)`` (``D^16 = 0``; the terms grow like
binomials of 15, which float32 carries, where the same product over all 64 rows
would not), then two merges ``T - T M T`` with ``M`` the blocks a merge adds.
Its terms cancel, so its products keep float32's operands (:func:`_dot_exact`);
every other product takes its operands in the dtype q arrives in (bfloat16
under ``amp``) and accumulates in float32.  The decays, the running sum, the
norms and the carried state are float32 always.

**What a tile does to its operands itself**, because XLA does each of them
badly around a kernel that wants (rows, 128 lanes) a head (the kimi cell's
step, PERF.md §6, PR 35): the running sum ``G`` (a product with a triangle of
ones, ``g`` in three bfloat16 pieces), ``beta`` times k and v (a value a head
and token is a column inside the tile and a transpose outside), q's scale, and
the division of each head's q and k by its 2-norm (``x / sqrt(sum x^2 +
1e-6)``, the architecture's L2 norm).  The backward hands back the gradients
of what came in: q, k, v, g, beta.

The backward is written out by hand over the same tiles (:func:`_chunk_bwd`),
walks the chunks from the last to the first and reads the state that entered
each chunk, which is all the forward keeps beside its operands: one (dv, dk)
float32 state a chunk and head, not one a token.

On the chip both directions are one Pallas kernel each (``mxtpu_kda_fwd``,
``mxtpu_kda_bwd``): grid (batch, heads / heads a program, chunks), the chunks
in order with the states in a VMEM scratch, operands read in place from (B, T,
H d) — a head is a block of 128 lanes — so nothing is transposed on the way in
or out.  A *unit* is ``_HEADS`` = 2 heads that go through a tile one under
another: ``A``, ``T`` and ``Aqk`` are then block diagonal (128, 128), which
fills the MXU where one head's (64, 64) would leave three quarters of it idle.
A unit's chunk is a chain of dependent products (the inverse alone is ten in
series), each a wait of some 130 cycles on an MXU that runs its pushes and
pops in program order: written one unit after another the chains do not
overlap, whatever the scheduler would like.  So a program takes 4, 2 or 1
units (:func:`_kernel_heads`, from the head count alone; one head where it is
odd), and :func:`_interleaved` traces a unit's tile function once and issues
its equations a stage at a time, every unit's stage before any unit's next (a
stage ends where a product waits for a product of the stage): one body, a
unit's arithmetic untouched, and in program order a unit's product sits in
the shadow of another's latency (the compiler's schedule at the kimi cell's
shape, 32 heads: 2074 -> 883 bundles a head and chunk forward, 2915 -> 1435
backward; PERF.md §6, PR 38).  Elsewhere the same tile functions run a head
at a time under ``lax.scan`` (the XLA form, also what the kernels are tested
against, beside the recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.core import Literal

from .. import telemetry as _telem
from .kernel_mode import kernel_mode

__all__ = ["kda_attention", "chunk_size"]

_CHUNK = 64     # tokens a chunk
_SUB = 16       # rows that share a reference row in the pair products
_HEADS = 2      # heads a unit: one under another in every tile
_UNITS = (4, 2, 1)  # units a grid program of the kernels may take

_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))
_EXACT = lax.Precision.HIGHEST
_NORM_EPS = 1e-6
_MIN_LOG_DECAY = -5.5   # 15 of them in a sub-block: exp(82.5), a float32


def chunk_size(seq):
    """Tokens a chunk for a sequence of ``seq``: 64, or the whole of a shorter
    sequence rounded up to the 16 rows of a sub-block."""
    return _CHUNK if seq >= _CHUNK else -(-seq // _SUB) * _SUB


# ---------------------------------------------------------------------------
# one chunk of some heads: tiles in, tiles out (XLA and kernel bodies alike)
# ---------------------------------------------------------------------------

def _dot(a, b, dims, dtype):
    return lax.dot_general(a.astype(dtype), b.astype(dtype), (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def _dot_exact(a, b, dims, dtype):
    """A product of float32 operands that keeps them: float32 at ``HIGHEST``
    where the products are float32, else three bfloat16 products of the
    operands' high and low halves (16 bits of mantissa: the inverse's terms
    cancel, and plain bfloat16 loses them all where the keys repeat)."""
    if dtype == jnp.float32:
        return lax.dot_general(a, b, (dims, ((), ())), precision=_EXACT,
                               preferred_element_type=jnp.float32)
    a_hi, b_hi = a.astype(dtype), b.astype(dtype)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(dtype)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(dtype)
    return _dot(a_hi, b_hi, dims, dtype) + _dot(a_hi, b_lo, dims, dtype) \
        + _dot(a_lo, b_hi, dims, dtype)


def _dot_sum(ones, b, dims, dtype):
    """``ones`` (zeros and ones) times float32 ``b``, to float32's own
    precision: the running sum of the log-decays and its transpose.  Where
    the products are bfloat16, ``b`` goes in as three bfloat16 pieces (24
    bits of mantissa) and ``ones`` as it is."""
    if dtype == jnp.float32:
        return lax.dot_general(ones, b, (dims, ((), ())), precision=_EXACT,
                               preferred_element_type=jnp.float32)
    total = None
    for _ in range(3):
        piece = b.astype(dtype)
        part = _dot(ones, piece, dims, dtype)
        total = part if total is None else total + part
        b = b - piece.astype(jnp.float32)
    return total


def _iota2(shape):
    return (lax.broadcasted_iota(jnp.int32, shape, 0),
            lax.broadcasted_iota(jnp.int32, shape, 1))


def _own_head(r, c, size, heads):
    """Whether row ``r`` and column ``c`` of a (heads C, heads C) tile lie
    in the same head's (C, C) block."""
    same = None
    for j in range(heads):
        inside = (r >= j * size) & (r < (j + 1) * size) \
            & (c >= j * size) & (c < (j + 1) * size)
        same = inside if same is None else same | inside
    return same


def _decays(G, size):
    """For each block of ``_SUB`` rows from ``n`` on: ``(n, exp(G_i - G_n))``
    for its rows, and for every row ``j`` of the same head up to the block's
    end ``exp(G_n - G_j)``, 0 elsewhere."""
    rows = lax.broadcasted_iota(jnp.int32, G.shape, 0)
    out = []
    for n in range(0, G.shape[0], _SUB):
        ref = G[n:n + 1]
        seen = (rows >= n // size * size) & (rows < n + _SUB)
        out.append((n, jnp.exp(G[n:n + _SUB] - ref),
                    jnp.exp(jnp.where(seen, ref - G, -jnp.inf))))
    return out


def _stages(jaxpr):
    """The equations of ``jaxpr`` in order, cut before each product that
    waits for a product of its own stage."""
    stages, waits = [[]], set()
    for eqn in jaxpr.eqns:
        behind = any(v in waits for v in eqn.invars
                     if not isinstance(v, Literal))
        if behind and eqn.primitive is lax.dot_general_p:
            stages.append([])
            waits, behind = set(), False
        if behind or eqn.primitive is lax.dot_general_p:
            waits.update(eqn.outvars)
        stages[-1].append(eqn)
    return stages


def _interleaved(fn, units):
    """``fn(*unit)`` for each of ``units`` (the same shapes), traced once and
    issued a stage at a time, every unit's stage before any unit's next ->
    what each returned.  In a kernel that is the order of the program, and an
    MXU runs in program order: a unit's product then sits in the shadow of
    another's latency."""
    closed, shape = jax.make_jaxpr(fn, return_shape=True)(*units[0])
    jaxpr = closed.jaxpr

    def read(env, v):
        return v.val if isinstance(v, Literal) else env[v]
    envs = [dict(zip(jaxpr.constvars + jaxpr.invars,
                     closed.consts + jax.tree.leaves(unit)))
            for unit in units]
    for stage in _stages(jaxpr):
        for env in envs:
            for eqn in stage:
                subfuns, params = eqn.primitive.get_bind_params(eqn.params)
                out = eqn.primitive.bind(
                    *subfuns, *(read(env, v) for v in eqn.invars), **params)
                env.update(zip(eqn.outvars, out
                               if eqn.primitive.multiple_results else [out]))
    return [jax.tree.unflatten(jax.tree.structure(shape),
                               [read(env, v) for v in jaxpr.outvars])
            for env in envs]


def _unit_lower_inverse(A, size, md):
    """``(I + A)^-1`` for ``A`` strictly lower triangular, float32, (C, C) or
    the block diagonal of several heads' (C, C)."""
    r, c = _iota2(A.shape)
    eye = (r == c).astype(jnp.float32)
    shift = _SUB.bit_length() - 1
    D = jnp.where((r >> shift) == (c >> shift), A, 0.0)
    inv, power = eye - D, D
    for _ in range(shift - 1):
        power = _dot_exact(power, power, _NN, md)
        inv = inv + _dot_exact(inv, power, _NN, md)
    # blocks that are a power of two merge up to a head; others (a short
    # sequence's one chunk) up to the whole tile, across heads' zeros
    limit = size if size & (size - 1) == 0 else A.shape[0]
    while (1 << shift) < limit:
        added = jnp.where(((r >> shift) != (c >> shift))
                          & ((r >> (shift + 1)) == (c >> (shift + 1))), A, 0.0)
        inv = inv - _dot_exact(inv, _dot_exact(added, inv, _NN, md), _NN, md)
        shift += 1
    return inv


def _by_head(fn, size, *tiles):
    """``fn`` on each head's rows of the stacked ``tiles``, stacked again."""
    heads = tiles[0].shape[0] // size
    return jnp.concatenate([fn(j, *(a[j * size:(j + 1) * size]
                                    for a in tiles)) for j in range(heads)])


def _unit(a):
    """Rows of ``a`` over their 2-norm (``_NORM_EPS`` under the root), and
    the factor."""
    factor = lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + _NORM_EPS)
    return a * factor, factor


def _chunk_parts(q, k, v, g, beta, states, md):
    """What the forward computes and the backward computes again.  The
    tiles hold the chunk's rows of ``len(states)`` heads one under another:
    the heads share every product whose left operand is block diagonal (the
    pair products, ``T`` and what it multiplies), which fills the MXU where
    one head's (64, 64) would leave three quarters of it idle."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    (q, rq), (k, rk) = _unit(q), _unit(k)
    scale = q.shape[1] ** -0.5
    q = q * scale
    kb, vb = k * beta, v * beta
    heads = len(states)
    size = g.shape[0] // heads
    r, c = _iota2((heads * size,) * 2)
    own = _own_head(r, c, size, heads)
    # G: the running sum of g from the chunk's first token, a head at a time
    upto = jnp.where(own & (r >= c), 1.0, 0.0)
    G = _dot_sum(upto, g, _NN, md)
    dec = _decays(G, size)
    pairs = [_dot(jnp.concatenate([q[n:n + _SUB] * row, kb[n:n + _SUB] * row]),
                  k * col, _NT, md) for n, row, col in dec]
    Aqk = jnp.where(own & (r >= c),
                    jnp.concatenate([b[:_SUB] for b in pairs]), 0.0)
    A = jnp.where(own & (r > c),
                  jnp.concatenate([b[_SUB:] for b in pairs]), 0.0)
    T = _unit_lower_inverse(A, size, md)
    eG = jnp.exp(G)
    Kg, Qg = kb * eG, q * eG
    W = _dot(T, Kg, _NN, md)
    U = _dot(T, vb, _NN, md) - _by_head(
        lambda j, w: _dot(w, states[j], _NT, md), size, W)
    last = [G[(j + 1) * size - 1:(j + 1) * size] for j in range(heads)]
    eD = _by_head(lambda j, g: jnp.exp(last[j] - g), size, G)
    return dict(q=q, k=k, v=v, kb=kb, vb=vb, rq=rq, rk=rk, scale=scale,
                dec=dec, own=own,
                upto=upto,
                G=G, Aqk=Aqk, T=T, eG=eG, Kg=Kg, Qg=Qg, W=W, U=U, eD=eD, Kd=k * eD, size=size,
                eC=[jnp.exp(g) for g in last])


def _chunk_fwd(q, k, v, g, beta, states):
    """q, k (n C, dk), v (n C, dv), g (n C, dk) and beta (n C, 1) float32: a
    chunk's rows of ``n`` heads one under another; ``states`` their ``n``
    states (dv, dk) float32, transposed -> ``(o (n C, dv) float32, the n new
    states)``."""
    md = q.dtype
    p = _chunk_parts(q, k, v, g, beta, states, md)
    size = p["size"]
    o = _by_head(lambda j, qg: _dot(qg, states[j], _NT, md), size, p["Qg"]) \
        + _dot(p["Aqk"], p["U"], _NN, md)
    new = [states[j] * p["eC"][j]
           + _dot(p["U"][j * size:(j + 1) * size],
                  p["Kd"][j * size:(j + 1) * size], _TN, md)
           for j in range(len(states))]
    return o, new


def _chunk_bwd(q, k, v, g, beta, states, do, dnext):
    """The chunk's operands, the states that entered it, ``do`` (n C, dv) and
    the cotangents ``dnext`` of the states it left -> ``(dq, dk, dv, dg,
    dbeta (n C, 1), the n cotangents of the states that entered)``,
    float32."""
    md = q.dtype
    p = _chunk_parts(q, k, v, g, beta, states, md)
    q, k, v, kb, vb, G = p["q"], p["k"], p["v"], p["kb"], p["vb"], p["G"]
    T, U, W, Kd, Kg, Qg = p["T"], p["U"], p["W"], p["Kd"], p["Kg"], p["Qg"]
    size, heads = p["size"], len(states)
    r, c = _iota2(T.shape)
    do = do.astype(jnp.float32)

    def rows(a, j):
        return a[j * size:(j + 1) * size]
    dU = _dot(p["Aqk"], do, _TN, md) + _by_head(
        lambda j, kd: _dot(kd, dnext[j], _NT, md), size, Kd)
    dAqk = jnp.where(p["own"] & (r >= c), _dot(do, U, _NT, md), 0.0)
    dQg = _by_head(lambda j, d: _dot(d, states[j], _NN, md), size, do)
    dKd = _by_head(lambda j, u: _dot(u, dnext[j], _NN, md), size, U)
    dstates = [dnext[j] * p["eC"][j] + _dot(rows(do, j), rows(Qg, j), _TN, md)
               - _dot(rows(dU, j), rows(W, j), _TN, md) for j in range(heads)]
    dlast = [jnp.sum(dnext[j] * states[j], axis=0, keepdims=True) * p["eC"][j]
             + jnp.sum(rows(dKd, j) * rows(Kd, j), axis=0, keepdims=True)
             for j in range(heads)]
    dW = -_by_head(lambda j, d: _dot(d, states[j], _NN, md), size, dU)
    dKg = _dot(T, dW, _TN, md)
    dvb = _dot(T, dU, _TN, md)
    dT = _dot(dW, Kg, _NT, md) + _dot(dU, vb, _NT, md)
    dA = jnp.where(p["own"] & (r > c), -_dot_exact(
        _dot_exact(T, dT, _TN, md), T, _NT, md), 0.0)

    dq_rows, dkb_rows, dk_pairs = [], [], jnp.zeros_like(k)
    for n, row, col in p["dec"]:
        weights = jnp.concatenate([dAqk[n:n + _SUB], dA[n:n + _SUB]])
        by_row = _dot(weights, k * col, _NN, md)
        dq_rows.append(by_row[:_SUB] * row)
        dkb_rows.append(by_row[_SUB:] * row)
        dk_pairs = dk_pairs + col * _dot(
            weights, jnp.concatenate([q[n:n + _SUB] * row,
                                      kb[n:n + _SUB] * row]), _TN, md)
    dq_pairs, dkb_pairs = jnp.concatenate(dq_rows), jnp.concatenate(dkb_rows)

    dG = dKg * Kg + dQg * Qg - dKd * Kd \
        + q * dq_pairs + kb * dkb_pairs - k * dk_pairs
    at = lax.broadcasted_iota(jnp.int32, G.shape, 0)
    for j in range(heads):
        dG = dG + jnp.where(at == (j + 1) * size - 1, dlast[j], 0.0)
    dkb = dKg * p["eG"] + dkb_pairs
    dbeta = jnp.sum(k * dkb, axis=1, keepdims=True) \
        + jnp.sum(v * dvb, axis=1, keepdims=True)
    dq = (dQg * p["eG"] + dq_pairs) * p["scale"]
    dk = dKd * p["eD"] + dk_pairs + beta * dkb
    # through x / |x|: the part of the cotangent across x
    unit_q = q * (1.0 / p["scale"])
    dq = p["rq"] * (dq - unit_q * jnp.sum(unit_q * dq, 1, keepdims=True))
    dk = p["rk"] * (dk - k * jnp.sum(k * dk, 1, keepdims=True))
    return (dq, dk, beta * dvb, _dot_sum(p["upto"], dG, _TN, md), dbeta,
            dstates)


# ---------------------------------------------------------------------------
# the XLA form: the tile functions under lax.scan
# ---------------------------------------------------------------------------

def _chunks_first(a, heads, size):
    """(B, T, H d) -> (T / C, B, H, C, d)."""
    b, t, _ = a.shape
    return a.reshape(b, t // size, size, heads, -1).transpose(1, 0, 3, 2, 4)


def _tokens_first(a):
    """(T / C, B, H, C, d) -> (B, T, H d)."""
    n, b, h, size, d = a.shape
    return a.transpose(1, 0, 3, 2, 4).reshape(b, n * size, h * d)


def _over_heads(fn):
    return jax.vmap(jax.vmap(fn))


def _xla_forward(q, k, v, g, beta, heads, size):
    tiles = [_chunks_first(a, heads, size) for a in (q, k, v, g, beta)]
    dk, dv = q.shape[2] // heads, v.shape[2] // heads

    def one(*a):
        o, (new,) = _chunk_fwd(*a[:5], [a[5]])
        return o, new

    def step(St, chunk):
        o, new = _over_heads(one)(*chunk, St)
        return new, (o, St)
    final, (o, states) = lax.scan(
        step, jnp.zeros((q.shape[0], heads, dv, dk), jnp.float32),
        tuple(tiles))
    return _tokens_first(o).astype(q.dtype), \
        states.transpose(1, 2, 0, 3, 4), final


def _xla_backward(q, k, v, g, beta, states, do, dfinal, size):
    heads = states.shape[1]
    tiles = [_chunks_first(a, heads, size) for a in (q, k, v, g, beta, do)]

    def one(*a):
        *grads, (dSt,) = _chunk_bwd(*a[:5], [a[5]], a[6], [a[7]])
        return (*grads, dSt)

    def step(dSn, chunk):
        *grads, dSt = _over_heads(one)(*chunk, dSn)
        return dSt, tuple(grads)
    chunks = tuple(tiles[:5]) + (states.transpose(2, 0, 1, 3, 4), tiles[5])
    _, grads = lax.scan(step, dfinal, chunks, reverse=True)
    dq, dk, dv, dg, dbeta = (_tokens_first(a) for a in grads)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dg, \
        dbeta


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _kernel_heads(heads):
    """Heads a grid program takes: the most of ``_UNITS`` units of
    ``_HEADS`` that divide ``heads``, one head where ``heads`` is odd."""
    return next((n * _HEADS for n in _UNITS if heads % (n * _HEADS) == 0), 1)


def _token_spec(pl, size, width, hb, reverse, chunks):
    if reverse:
        return pl.BlockSpec((None, size, hb * width),
                            lambda b, h, c: (b, chunks - 1 - c, h))
    return pl.BlockSpec((None, size, hb * width), lambda b, h, c: (b, c, h))


def _head_spec(pl, size, hb, reverse, chunks):
    """A value a token and head, laid out (B, H / hb, T, hb): a program's
    heads are the block's ``hb`` lanes, a head's column broadcasts over its
    rows' lanes inside the kernel."""
    if reverse:
        return pl.BlockSpec((None, None, size, hb),
                            lambda b, h, c: (b, h, chunks - 1 - c, 0))
    return pl.BlockSpec((None, None, size, hb), lambda b, h, c: (b, h, c, 0))


def _by_program(beta, hb):
    """(B, T, H) -> (B, H / hb, T, hb)."""
    b, t, h = beta.shape
    return beta.reshape(b, t, h // hb, hb).transpose(0, 2, 1, 3)


def _stacked(ref, heads, width):
    """Heads ``heads`` of a (C, hb d) block as (n C, d): a unit's heads one
    under another."""
    return jnp.concatenate([ref[:, j * width:(j + 1) * width]
                            for j in heads])


def _units(hb):
    """The heads of each unit of a program of ``hb`` heads."""
    n = min(hb, _HEADS)
    return [range(u, u + n) for u in range(0, hb, n)]


# Jitted so that a model's layers share one trace of the body (a pallas_call
# traces its kernel anew every time it is called: PERF.md §6, PR 34).
@functools.partial(jax.jit, static_argnames=("heads", "size", "interpret"))
def _pallas_forward(q, k, v, g, beta, heads, size, interpret=False):
    """q, k (B, T, H dk), v (B, T, H dv), g (B, T, H dk) and beta (B, T, H)
    float32 -> ``(o (B, T, H dv), states (B, H, T / C, dv, dk), final (B, H,
    dv, dk))``: ``states[:, :, c]`` the state that entered chunk ``c``, zero
    for the first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, seq, _ = q.shape
    dk, dv = q.shape[2] // heads, v.shape[2] // heads
    hb, chunks = _kernel_heads(heads), seq // size
    units = _units(hb)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, fin_ref,
               state):
        c = pl.program_id(2)

        @pl.when(c == 0)
        def _start():
            state[...] = jnp.zeros_like(state)

        st_ref[...] = state[...]
        # every unit's loads before the first store of any
        done = _interleaved(_chunk_fwd, [(
            _stacked(q_ref, unit, dk), _stacked(k_ref, unit, dk),
            _stacked(v_ref, unit, dv), _stacked(g_ref, unit, dk),
            _stacked(b_ref, unit, 1), [state[j] for j in unit])
            for unit in units])
        for unit, (o, new) in zip(units, done):
            for i, j in enumerate(unit):
                o_ref[:, j * dv:(j + 1) * dv] = \
                    o[i * size:(i + 1) * size].astype(o_ref.dtype)
                state[j] = new[i]

        @pl.when(c == chunks - 1)
        def _end():
            fin_ref[...] = state[...]

    tokens = functools.partial(_token_spec, pl, size, hb=hb, reverse=False,
                               chunks=chunks)
    return pl.pallas_call(
        kernel, grid=(nb, heads // hb, chunks),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                  _head_spec(pl, size, hb, False, chunks)],
        out_specs=[tokens(dv),
                   pl.BlockSpec((None, hb, None, dv, dk),
                                lambda b, h, c: (b, h, c, 0, 0)),
                   pl.BlockSpec((None, hb, dv, dk),
                                lambda b, h, c: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, seq, heads * dv), q.dtype),
                   jax.ShapeDtypeStruct((nb, heads, chunks, dv, dk),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((nb, heads, dv, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="mxtpu_kda_fwd",
    )(q, k, v, g, _by_program(beta, hb))


@functools.partial(jax.jit, static_argnames=("size", "interpret"))
def _pallas_backward(q, k, v, g, beta, states, do, dfinal, size,
                     interpret=False):
    """The forward's operands and ``states``, ``do`` (B, T, H dv) and the
    final state's cotangent -> ``(dq, dk, dv, dg, dbeta)``; the chunks from
    the last to the first, the state's cotangent in a VMEM scratch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, seq, _ = q.shape
    heads, _, dv, dk = states.shape[1:]
    hb, chunks = _kernel_heads(heads), seq // size
    units = _units(hb)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref, dfin_ref,
               dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate):
        c = pl.program_id(2)

        @pl.when(c == 0)
        def _start():
            dstate[...] = dfin_ref[...]

        done = _interleaved(_chunk_bwd, [(
            _stacked(q_ref, unit, dk), _stacked(k_ref, unit, dk),
            _stacked(v_ref, unit, dv), _stacked(g_ref, unit, dk),
            _stacked(b_ref, unit, 1), [st_ref[j] for j in unit],
            _stacked(do_ref, unit, dv), [dstate[j] for j in unit])
            for unit in units])
        for unit, (*grads, dstates) in zip(units, done):
            for i, j in enumerate(unit):
                for ref, grad, width in zip(
                        (dq_ref, dk_ref, dv_ref, dg_ref, db_ref), grads,
                        (dk, dk, dv, dk, 1)):
                    ref[:, j * width:(j + 1) * width] = \
                        grad[i * size:(i + 1) * size].astype(ref.dtype)
                dstate[j] = dstates[i]

    tokens = functools.partial(_token_spec, pl, size, hb=hb, reverse=True,
                               chunks=chunks)
    by_head = _head_spec(pl, size, hb, True, chunks)
    wide = jax.ShapeDtypeStruct((nb, seq, heads * dk), q.dtype)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        kernel, grid=(nb, heads // hb, chunks),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk), by_head,
                  pl.BlockSpec((None, hb, None, dv, dk),
                               lambda b, h, c: (b, h, chunks - 1 - c, 0, 0)),
                  tokens(dv),
                  pl.BlockSpec((None, hb, dv, dk),
                               lambda b, h, c: (b, h, 0, 0))],
        out_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk), by_head],
        out_shape=[wide, wide,
                   jax.ShapeDtypeStruct((nb, seq, heads * dv), v.dtype),
                   jax.ShapeDtypeStruct((nb, seq, heads * dk), jnp.float32),
                   jax.ShapeDtypeStruct((nb, heads // hb, seq, hb),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="mxtpu_kda_bwd",
    )(q, k, v, g, _by_program(beta, hb), states, do, dfinal)
    return dq, dk_, dv_, dg, \
        dbeta.transpose(0, 2, 1, 3).reshape(nb, seq, heads)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def _kernels(dk, dv):
    """``"mosaic"`` / ``"interpret"`` where this call's shapes take the
    kernels (a kernel mode; on the chip a head a whole number of 128-lane
    tiles), else None: the XLA form."""
    mode = kernel_mode()
    if mode == "mosaic" and (dk % 128 or dv % 128):
        return None
    return mode


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(q, k, v, g, beta, heads, size, mode):
    return _scan_fwd(q, k, v, g, beta, heads, size, mode)[0]


def _scan_fwd(q, k, v, g, beta, heads, size, mode):
    _telem.inc("kda.fwd.xla" if mode is None else "kda.fwd.pallas")
    if mode is None:
        o, states, final = _xla_forward(q, k, v, g, beta, heads, size)
    else:
        o, states, final = _pallas_forward(q, k, v, g, beta, heads, size,
                                           interpret=mode == "interpret")
    return (o, final), (q, k, v, g, beta, states)


def _scan_bwd(heads, size, mode, residuals, cotangents):
    _telem.inc("kda.bwd.xla" if mode is None else "kda.bwd.pallas")
    q, k, v, g, beta, states = residuals
    do, dfinal = cotangents
    if mode is None:
        return _xla_backward(q, k, v, g, beta, states, do, dfinal, size)
    return _pallas_backward(q, k, v, g, beta, states, do, dfinal, size,
                            interpret=mode == "interpret")


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_attention(q, k, v, g, beta):
    """The gated delta rule with a decay a key channel, from a zero state
    (module docstring).

    q, k: (B, T, H, dk), each head divided by its 2-norm inside the tiles and
    q scaled by dk^-1/2; v: (B, T, H, dv); g: (B, T, H, dk), the log of the
    decay (<= 0; under ``_MIN_LOG_DECAY`` taken as it), and beta: (B, T, H),
    both taken in float32.  Returns ``(o (B, T, H, dv) in q's dtype,
    final_state (B, H, dk, dv) float32)``.  A sequence that is no whole
    number of chunks is padded behind its end with tokens that change
    nothing (k = 0)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    size = chunk_size(t)
    pad = -t % size
    _telem.set_gauge("kda.heads", h)
    _telem.set_gauge("kda.chunk", size)
    _telem.set_gauge("kda.chunks_per_seq", (t + pad) // size)
    mode = _kernels(dk, dv)
    _telem.set_gauge("kda.heads_per_program", _kernel_heads(h) if mode else 1)

    f32 = jnp.float32
    operands = [q, k, v, jnp.maximum(g.astype(f32), _MIN_LOG_DECAY),
                beta.astype(f32)]
    if pad:
        operands = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                    for a in operands]
    o, final = _scan(*(a.reshape(b, t + pad, -1) for a in operands), h, size,
                     mode)
    return o[:, :t].reshape(b, t, h, dv), jnp.swapaxes(final, 2, 3)
