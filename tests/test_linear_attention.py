"""``ops/linear_attention.py`` — the gated delta rule with a decay a key
channel as a chunked scan — against the per-token recurrence of
``benchmark/reference/kimi_linear.py`` (``delta_rule``: no chunks, no
kernel): the XLA form and the kernels under the interpreter, outputs, the
state handed on and the gradients of q, k, v, g and beta.  The op divides q
and k by their 2-norms itself, so the recurrence is fed ``_unit`` of both.
And the causal convolution of ``nd.causal_conv1d``."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import linear_attention
from mxnet_tpu.ops.kernel_mode import interpret_kernels

from references import kimi_linear as ref


def _draw(seed, b, t, h, dk, dv, rate, dt):
    """q and k of lengths between 0.3 and 3 a head and token, ``g = -rate dt
    u`` with ``u`` in [0.5, 1] a channel, beta in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    lengths = jax.random.uniform(ks[5], (b, t, h, 1), minval=0.3, maxval=3.0)
    q, k = _unit(q) * lengths, _unit(k) / lengths
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -rate * dt * jax.random.uniform(ks[3], (b, t, h, dk), minval=0.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def _unit(a):
    return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)


def _recurrence(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return ref.delta_rule(_unit(q) * q.shape[-1] ** -0.5, _unit(k), v, g,
                              beta, None)


def _chunked(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return linear_attention.kda_attention(q, k, v, g, beta)


def _value_and_grads(fn, operands, weights):
    def scalar(*a):
        o, state = fn(*a)
        return jnp.sum(o * weights[0]) + jnp.sum(state * weights[1]), \
            (o, state)
    (_, outs), grads = jax.value_and_grad(
        scalar, argnums=tuple(range(5)), has_aux=True)(*operands)
    return outs, grads


def _weights(b, t, h, dk, dv):
    return (jax.random.normal(jax.random.key(9), (b, t, h, dv)),
            jax.random.normal(jax.random.key(8), (b, h, dk, dv)))


def _close(got, want, tol):
    return float(jnp.linalg.norm(got - want)) <= \
        tol * float(jnp.linalg.norm(want))


# lengths: under a chunk, a chunk, no whole number of sub-blocks or chunks,
# several chunks; decays: the configuration's strongest (rate 16, dt 0.1: the
# running product over a chunk is e^-100, the underflow case), its weakest
# (alpha near 1), and one in between
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "interpret"])
@pytest.mark.parametrize("t,rate,dt", [
    (16, 1.0, 0.05), (40, 1.0, 0.05), (64, 4.0, 0.05), (200, 1.0, 0.05),
    (128, 16.0, 0.1), (192, 1.0, 1e-3),
])
def test_chunked_scan_is_the_recurrence(kernels, t, rate, dt):
    operands = _draw(1, 2, t, 2, 32, 16, rate, dt)
    weights = _weights(2, t, 2, 32, 16)
    (o, state), want = _value_and_grads(_recurrence, operands, weights)
    with interpret_kernels() if kernels else contextlib.nullcontext():
        (o2, state2), got = _value_and_grads(_chunked, operands, weights)
    # float32 on both sides, the sums in another order
    assert _close(o2, o, 5e-6) and _close(state2, state, 5e-6)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert float(jnp.linalg.norm(b)) > 0, name
        assert _close(a, b, 2e-5), name


# a grid program of the kernels takes 4, 2 or 1 units of two heads (8, 4, 2
# heads) and runs them stage by stage, or one head where the count is odd.
# Whatever else shares the program, a unit's arithmetic is its own: the same
# bits as the unit alone.  Against each head alone a pair differs by the
# zeros of its block-diagonal tiles, and the XLA form by its batch of heads
# (a head at a time, the products' sums in XLA's order for the batch):
# float32's rounding.
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "interpret"])
@pytest.mark.parametrize("heads,a_program", [(2, 2), (4, 4), (8, 8), (3, 1)])
def test_a_head_gets_what_it_gets_alone(kernels, heads, a_program):
    t, dk, dv = 128, 32, 16
    operands = _draw(21, 1, t, heads, dk, dv, 2.0, 0.05)
    weights = _weights(1, t, heads, dk, dv)

    # jitted: the units alone share one compiled program
    both = jax.jit(lambda *a: _value_and_grads(_chunked, a[:5], a[5:]))

    def run(part):
        with interpret_kernels() if kernels else contextlib.nullcontext():
            (o, state), grads = both(*(a[:, :, part] for a in operands),
                                     weights[0][:, :, part],
                                     weights[1][:, part])
        return [o, jnp.swapaxes(state, 1, 2), *grads]

    whole = run(slice(None))
    assert mx.telemetry.value("kda.heads_per_program") == \
        (a_program if kernels else 1)
    unit = 2 if kernels and heads % 2 == 0 else 1
    for first in range(0, heads, unit):
        part = slice(first, first + unit)
        for got, want in zip(whole, run(part)):
            if kernels:
                assert bool((got[:, :, part] == want).all()), first
            else:
                assert _close(got[:, :, part], want, 2e-6), first
    if unit == 2:
        for got, want in zip(whole, run(slice(heads - 1, heads))):
            assert _close(got[:, :, heads - 1:], want, 2e-6)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "interpret"])
def test_the_tiles_norm_sees_only_directions(kernels):
    """q and k go in at any length: the tiles divide each head's row by its
    2-norm, so a row twice as long gives the same output, and the gradient
    that comes back through the division is across the row and half as
    large."""
    q, k, v, g, beta = _draw(11, 2, 80, 2, 32, 16, 2.0, 0.05)
    weights = _weights(2, 80, 2, 32, 16)
    with interpret_kernels() if kernels else contextlib.nullcontext():
        (o, _), got = _value_and_grads(_chunked, (q, k, v, g, beta), weights)
        (o2, _), got2 = _value_and_grads(_chunked, (2 * q, 2 * k, v, g, beta),
                                         weights)
    # the 1e-6 under the root is 1e-5 of the shortest row's square (0.3^2)
    assert _close(o2, o, 3e-5)
    for a, a2, x in zip(got[:2], got2[:2], (q, k)):
        assert _close(2 * a2, a, 3e-5)
        # across the row, up to what the 1e-6 under the root lets through
        assert float(jnp.abs(jnp.sum(a * x, -1)).max()) < \
            1e-4 * float(jnp.abs(a).max())


# a log-decay the scan's sub-blocks cannot carry (15 of them pass float32's
# exponent under -5.8 a token) is taken as _MIN_LOG_DECAY: finite, the
# recurrence's result at that decay, and no gradient to the g that was cut.
# "bursts": every channel of a block's first tokens and of single tokens;
# "channels": every fifth channel of every token, whose running sum over a
# chunk reaches 350, where float32's spacing (3e-5) is the relative error of
# every exp of a difference
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "interpret"])
@pytest.mark.parametrize("where,tol", [("bursts", 1e-4), ("channels", 1e-2)])
@pytest.mark.parametrize("strongest", [-5.4, -200.0])
def test_a_decay_past_the_floor_is_taken_at_the_floor(kernels, where, tol,
                                                      strongest):
    floor = linear_attention._MIN_LOG_DECAY
    # the shapes of a case above, whose compiled scans these cases find
    q, k, v, g, beta = _draw(13, 2, 128, 2, 32, 16, 1.0, 0.05)
    cut = jnp.zeros(g.shape, bool)
    cut = cut.at[:, 16:19].set(True).at[:, 40::7].set(True) \
        if where == "bursts" else cut.at[:, :, :, ::5].set(True)
    g = jnp.where(cut, strongest, g)
    weights = _weights(2, 128, 2, 32, 16)
    (o, state), want = _value_and_grads(
        _recurrence, (q, k, v, jnp.maximum(g, floor), beta), weights)
    with interpret_kernels() if kernels else contextlib.nullcontext():
        (o2, state2), got = _value_and_grads(_chunked, (q, k, v, g, beta),
                                             weights)
    assert _close(o2, o, tol) and _close(state2, state, tol)
    dg = jnp.where(g < floor, 0.0, want[3])
    assert (strongest >= floor) == bool((dg == want[3]).all())
    assert bool((got[3] == 0.0)[g < floor].all())
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got,
                          (*want[:3], dg, want[4])):
        assert _close(a, b, tol), name


def test_the_strongest_decay_underflows_a_chunk_and_the_scan_does_not():
    """At rate 16, dt 0.1 the running decay over 64 tokens leaves float32's
    range for its inverse; the chunked form never takes it."""
    q, k, v, g, beta = _draw(3, 1, 128, 1, 32, 16, 16.0, 0.1)
    g = jnp.full_like(g, -1.6)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.float32(g[0, :64, 0].sum(0))).max())
    o, _ = _chunked(q, k, v, g, beta)
    want, _ = _recurrence(q, k, v, g, beta)
    assert bool(jnp.isfinite(o).all()) and _close(o, want, 5e-6)


def test_bfloat16_operands_keep_the_state_float32():
    """As ``amp`` hands them over: q, k, v bfloat16, g and beta float32."""
    q, k, v, g, beta = _draw(5, 1, 128, 2, 32, 32, 4.0, 0.05)
    low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    o, state = linear_attention.kda_attention(*low, g, beta)
    want, want_state = _recurrence(*(a.astype(jnp.float32) for a in low),
                                   g, beta)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    # bfloat16 products: 2^-8 an operand
    assert _close(o.astype(jnp.float32), want, 2e-2)
    assert _close(state, want_state, 2e-2)


def test_chunk_size():
    assert [linear_attention.chunk_size(t) for t in (5, 16, 40, 64, 8192)] \
        == [16, 16, 48, 64, 64]


def test_nd_kda_attention_normalises_and_scales():
    """The registered op: heads side by side in the last axis, q and k
    divided by their 2-norm a head, q scaled by dk^-1/2."""
    q, k, v, g, beta = _draw(7, 2, 32, 2, 16, 16, 1.0, 0.05)
    scale = jnp.arange(1, 3, dtype=jnp.float32)[:, None]

    def flat(a):
        return mx.nd.array(np.asarray(a.reshape(2, 32, -1)))
    got = mx.nd.kda_attention(flat(q * 3.0), flat(k * scale), flat(v),
                              flat(g), mx.nd.array(np.asarray(beta)),
                              num_heads=2).asnumpy()
    want, _ = _recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(got, want.reshape(2, 32, -1), atol=2e-5)


def test_nd_kda_gate():
    f = np.random.RandomState(0).randn(2, 8, 6).astype(np.float32)
    b = np.random.RandomState(1).randn(2, 8, 2).astype(np.float32)
    a_log = np.log(np.array([2.0, 16.0], np.float32))
    dt_bias = np.random.RandomState(2).randn(6).astype(np.float32)
    g, beta = mx.nd.kda_gate(*(mx.nd.array(a) for a in
                               (f, b, a_log, dt_bias)))
    want = -np.repeat([2.0, 16.0], 3) * np.log1p(np.exp(f + dt_bias))
    np.testing.assert_allclose(g.asnumpy(), want, rtol=1e-5)
    np.testing.assert_allclose(beta.asnumpy(), 1 / (1 + np.exp(-b)),
                               rtol=1e-5)
    assert g.asnumpy().max() < 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_causal_conv_sees_no_later_token(dtype):
    """The input as the layer hands it over (bfloat16 under ``amp``), the
    taps float32."""
    rng = np.random.RandomState(0)
    x = np.asarray(jnp.asarray(rng.randn(2, 12, 6), dtype).astype(jnp.float32))
    w = rng.randn(6, 4).astype(np.float32)

    def conv(x):
        return mx.nd.causal_conv1d(mx.nd.array(x).astype(dtype),
                                   mx.nd.array(w)).asnumpy()
    y = conv(x)
    want = np.asarray(ref.causal_conv(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_allclose(y, want / (1 + np.exp(-want)), atol=1e-6)
    # token t is w[:, 3] x[t] + w[:, 2] x[t - 1] + ..., zeros before the start
    np.testing.assert_allclose(
        np.asarray(ref.causal_conv(jnp.asarray(x), jnp.asarray(w)))[:, 1],
        w[:, 3] * x[:, 1] + w[:, 2] * x[:, 0], atol=1e-6)
    # changing tokens 7.. leaves tokens 0..6 as they were, to the bit
    later = x.copy()
    later[:, 7:] += rng.randn(2, 5, 6).astype(np.float32)
    y2 = conv(later)
    assert (y2[:, :7] == y[:, :7]).all() and (y2[:, 7] != y[:, 7]).any()
