"""The dropless expert layer's buffers (``parallel/moe.py``): the layer at
every fill — one buffer of a quarter of the worst case, or as many as the
routed rows need, up to the worst case — against the worst-case formulation
it had before, kept here as the plain reference; the rows a routed count
runs through; what the layer's program holds with and without ``remat``."""
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu import telemetry
from mxnet_tpu.ops.kernel_mode import interpret_kernels
from mxnet_tpu.parallel import moe

# 256 tokens choose 4 of 16 experts, 4 of them held from expert 5 on: a
# worst case of 1024 rows in buffers of 256
T, K, HELD, EXPERTS, OFFSET = 256, 4, 4, 16, 5
FULL = T * K
BUFFER = 256
# (rows routed, the buffer rows they run through): nothing, one row, exactly
# a buffer, a buffer and one, three buffers, and every choice of every token
FILLS = [(0, 0), (1, 256), (256, 256), (257, 512), (600, 768), (FULL, FULL)]


def _choices(routed, seed=0, tokens=T):
    """(tokens, K) expert ids of which exactly ``routed`` are held: the
    tokens in a shuffled order send all their choices here until the count
    is reached, the others choose among the absent experts."""
    rng = np.random.RandomState(seed)
    t, j = np.meshgrid(np.arange(tokens), np.arange(K), indexing="ij")
    absent = (OFFSET + HELD + (t + j) % (EXPERTS - HELD)) % EXPERTS
    held = OFFSET + (t + j) % HELD
    rank = np.empty(tokens, np.int64)
    rank[rng.permutation(tokens)] = np.arange(tokens)
    return np.where(rank[:, None] * K + j < routed, held, absent
                    ).astype(np.int32)


def _operands(routed, seed=0, d=32, h=16, dtype=jnp.float32, tokens=T):
    rng = np.random.RandomState(seed + 1)
    x = rng.randn(tokens, d)
    weights = rng.rand(tokens, K) + 0.1
    stacks = [rng.randn(HELD, d, h) * d ** -0.5,
              rng.randn(HELD, d, h) * d ** -0.5,
              rng.randn(HELD, h, d) * h ** -0.5]
    g = rng.randn(tokens, d)
    return (jnp.asarray(_choices(routed, seed, tokens)),
            [jnp.asarray(x, dtype), jnp.asarray(weights, jnp.float32)]
            + [jnp.asarray(s, dtype) for s in stacks], jnp.asarray(g, dtype))


def _worst_case(x, experts, weights, w_gate, w_up, w_down, *, expert_offset):
    """The layer as it stood before: one buffer of ``tokens * min(top_k,
    held)`` rows whatever is routed, a gather of a row a choice back to the
    tokens, and autodiff's own transposes."""
    tokens, k = experts.shape
    held = w_gate.shape[0]
    rows = tokens * min(k, held)
    local = experts - expert_offset
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None], axis=0,
                    dtype=jnp.int32)
    row_of_choice = jnp.where(is_held,
                              jnp.argsort(order).reshape(tokens, k), 0)
    routed = (jnp.arange(rows) < jnp.sum(sizes))[:, None]

    def product(lhs, rhs):
        return jnp.where(routed, lax.ragged_dot(
            jnp.where(routed, lhs, 0), rhs, sizes), 0)

    buffer = jnp.where(routed, x[order[:rows] // k], 0)
    out = product(jax.nn.silu(product(buffer, w_gate))
                  * product(buffer, w_up), w_down)
    picked = out[row_of_choice].astype(jnp.float32)
    w = jnp.where(is_held, weights, 0)
    return jnp.sum(picked * w[..., None], axis=1).astype(x.dtype)


def _value_and_grads(layer, experts, floats, g):
    """Output and the five gradients (x, weights, the three stacks) of
    ``sum(layer(...) * g)``."""
    def loss(*floats):
        out = layer(floats[0], experts, *floats[1:], expert_offset=OFFSET)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(5)), has_aux=True))(*floats)
    return (out,) + grads


NAMES = ("out", "dx", "dweights", "dgate", "dup", "ddown")
# float32, values O(1) to O(10), sums of up to 32 + 16 + 4 terms reordered
TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("routed,rows", FILLS)
def test_every_fill_agrees_with_the_worst_case_buffer(routed, rows):
    assert moe.window_rows(T, K, HELD) == BUFFER
    assert moe.rung_rows(routed, T, K, HELD) == rows
    experts, floats, g = _operands(routed, seed=routed)
    assert int(((experts >= OFFSET) & (experts < OFFSET + HELD)).sum()) \
        == routed
    got = _value_and_grads(moe.dropless_moe_apply, experts, floats, g)
    want = _value_and_grads(_worst_case, experts, floats, g)
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(a, b, err_msg=name, **TOL)
    if routed:
        assert all(float(jnp.abs(a).max()) > 1e-3 for a in got)
    else:
        assert not any(float(jnp.abs(a).max()) for a in got)


@pytest.mark.parametrize("fixed,routed,rows", [
    (512, 600, 1024),               # two buffers of half the worst case
    (300, 10, 384),                 # rounded up to whole tiles
    (4096, 700, FULL)])             # never past the worst case
def test_a_fixed_amount_of_work(monkeypatch, fixed, routed, rows):
    """``fixed_rows``: buffers of that many rows, and the products over
    every row of each whatever is routed; the layer still agrees with the
    worst-case buffer, the zero rows adding nothing."""
    gmm = importlib.import_module("mxnet_tpu.ops.grouped_matmul")
    assert moe.rung_rows(routed, T, K, HELD, fixed) == rows
    seen = []
    plain = gmm.grouped_matmul

    def counting(lhs, rhs, group_sizes):
        jax.debug.callback(lambda n: seen.append(int(n)),
                           jnp.sum(group_sizes))
        return plain(lhs, rhs, group_sizes)
    monkeypatch.setattr(gmm, "grouped_matmul", counting)
    experts, floats, g = _operands(routed, seed=routed + 1)
    layer = functools.partial(moe.dropless_moe_apply, fixed_rows=fixed)
    got = _value_and_grads(layer, experts, floats, g)
    buffer = moe.window_rows(T, K, HELD, fixed)
    assert telemetry.value("moe.rows_ladder") == buffer
    assert seen and set(seen) == {buffer}
    want = _value_and_grads(_worst_case, experts, floats, g)
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(a, b, err_msg=name, **TOL)


def test_dropless_at_the_worst_case_against_a_layer_without_a_buffer():
    """Every token sends all its choices here, the worst case to its last
    row: four buffers take them all, and the result is what a dense loop
    over the held experts gives, which has no buffer to overflow."""
    experts, (x, weights, w_gate, w_up, w_down), _ = _operands(FULL, seed=3)
    got = jax.jit(functools.partial(
        moe.dropless_moe_apply, expert_offset=OFFSET))(
            x, experts, weights, w_gate, w_up, w_down)
    want = np.zeros_like(x)
    for e in range(HELD):
        y = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        share = jnp.sum(jnp.where(experts == OFFSET + e, weights, 0), axis=1)
        want = want + share[:, None] * y
    assert float(jnp.sum(experts == OFFSET) > 0)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("routed", [129, 600])
def test_the_kernels_under_a_buffer(routed):
    """The same through ``mxtpu_gmm`` and its backward kernels (in the
    interpreter), which take a buffer's rows in whole tiles of 128: one
    buffer, and three (an expert's rows in two of them)."""
    experts, floats, g = _operands(routed, seed=7, d=128, h=128)
    with interpret_kernels():
        got = _value_and_grads(moe.dropless_moe_apply, experts, floats, g)
    want = _value_and_grads(_worst_case, experts, floats, g)
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(a, b, err_msg=name, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("routed", [129, 600])
def test_the_kernels_over_a_fixed_buffer(routed):
    """``mxtpu_gmm`` and its backward kernels over every row of buffers of
    512, the last expert's group holding the zero rows: one buffer, and
    two."""
    experts, floats, g = _operands(routed, seed=7, d=128, h=128)
    layer = functools.partial(moe.dropless_moe_apply, fixed_rows=512)
    with interpret_kernels():
        got = _value_and_grads(layer, experts, floats, g)
    want = _value_and_grads(_worst_case, experts, floats, g)
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(a, b, err_msg=name, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("routed", [200, 700])
def test_bfloat16_rows_float32_sums(routed):
    """bf16 rows in and out as ``amp`` feeds the layer; a token's sum and
    the weights' gradient accumulate in float32, so the layer reads what
    the worst-case buffer reads on the same operands but for the order of
    the sums (a bf16 ulp of values up to ~8).  Over several buffers the
    sums between them are float32 too, but an expert whose rows a buffer's
    edge cuts adds its two parts in bf16."""
    experts, floats, g = _operands(routed, seed=9, dtype=jnp.bfloat16)
    got = _value_and_grads(moe.dropless_moe_apply, experts, floats, g)
    want = _value_and_grads(_worst_case, experts, floats, g)
    assert got[0].dtype == got[1].dtype == got[3].dtype == jnp.bfloat16
    assert got[2].dtype == jnp.float32
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), err_msg=name,
                                   rtol=2 ** -6, atol=2 ** -5)


# (tokens, top_k, held) of the three benchmark cells that run the layer,
# the rows of a buffer, and (routed, rows run through) as the runner counts
# them before the first step and after the window
CELLS = {"kimi": ((8192, 8, 8), 16384, [(2079, 16384), (2498, 16384)]),
         "kanana": ((8192, 6, 16), 12288, [(5688, 12288), (11114, 12288)]),
         "keye": ((16384, 8, 16), 32768, [(16504, 32768), (69054, 98304)])}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rung_rows_at_its_boundaries(cell):
    shape, buffer, counted = CELLS[cell]
    full = moe.buffer_rows(*shape)
    assert moe.window_rows(*shape) == buffer
    assert buffer % 128 == 0 and 4 * buffer == full
    assert moe.rung_rows(0, *shape) == 0
    for n in range(1, 5):
        assert moe.rung_rows((n - 1) * buffer + 1, *shape) == n * buffer
        assert moe.rung_rows(n * buffer, *shape) == n * buffer
    for routed, rows in counted:
        assert moe.rung_rows(routed, *shape) == rows


@pytest.mark.parametrize("shape,buffer", [
    ((8, 2, 2), 16),                # under a tile: the worst case is one
    ((64, 2, 2), 128),              # one tile
    ((64, 4, 2), 128),              # held < top_k: a token sends 2 at most
    ((128, 2, 2), 128),
    ((1024, 6, 16), 1536),
    ((100, 3, 8), 128)])            # whole tiles under 300 rows: 3 buffers
def test_buffers_of_small_and_ragged_layers(shape, buffer):
    full = moe.buffer_rows(*shape)
    assert moe.window_rows(*shape) == moe.rung_rows(1, *shape) == buffer
    assert full <= moe.rung_rows(full, *shape) < full + buffer


def test_the_sdar_cell_computes_one_fixed_buffer():
    """The SDAR cell's ``moe_fixed_rows`` (65536, half the worst case): the
    most a layer routed by the end of a window over twelve seeds (52222)
    takes one buffer, where the quarter (32768) takes two."""
    shape = (16384, 8, 16)
    assert 2 * moe.window_rows(*shape, 65536) == moe.buffer_rows(*shape)
    assert moe.rung_rows(52222, *shape) == 2 * 32768
    assert moe.rung_rows(52222, *shape, 65536) == 65536


def _plan_of(experts, floats):
    """The plan ``dropless_moe_apply`` hands its buffers."""
    return moe._make_plan(experts, floats[2].shape[0], OFFSET)


@pytest.mark.parametrize("routed,rows", FILLS)
def test_the_buffers_are_counted_on_the_device(routed, rows):
    """The trip count of the two loops, from the routed count the plan
    carries (a traced scalar: no host sync)."""
    experts, floats, _ = _operands(routed)

    def counted(experts, floats):
        plan = _plan_of(experts, floats)
        return plan.routed, moe._over_windows(
            plan, BUFFER, lambda start: jnp.ones((), jnp.int32),
            jnp.zeros((), jnp.int32))
    count, buffers = jax.jit(counted)(experts, floats)
    assert int(count) == routed
    assert int(buffers) * BUFFER == rows


def test_the_layer_holds_no_array_of_the_worst_case():
    """Forward and backward of the layer, lowered: index vectors of
    ``T * k`` scalars, but no array of floats has the worst case's rows,
    where the worst-case formulation (the control) has."""
    experts, floats, g = _operands(100)
    wide = re.compile(rf"tensor<{FULL}x\d+x(f32|bf16)>")

    def lowered(layer):
        return jax.jit(functools.partial(_value_and_grads, layer)).lower(
            experts, floats, g).as_text()
    text = lowered(moe.dropless_moe_apply)
    assert f"tensor<{FULL}xi32>" in text
    assert f"tensor<{BUFFER}x32xf32>" in text
    assert not wide.search(text)
    assert wide.search(lowered(_worst_case))


def _two_layers(remat):
    def layer(x, experts, weights, w_gate, w_up, w_down):
        h = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return x + moe.dropless_moe_apply(h, experts, weights, w_gate, w_up,
                                          w_down, expert_offset=OFFSET)
    if remat:
        layer = jax.checkpoint(layer)

    def loss(floats, first, second):
        y = layer(floats[0], first, *floats[1:])
        y = layer(y, second, *floats[1:])
        return jnp.sum(y * y)
    return jax.jit(jax.grad(loss))


@pytest.mark.parametrize("remat", [False, True])
def test_a_compiled_layer_holds_two_loops_and_no_scatter_of_rows(remat):
    """Two layers' gradient, compiled: a forward and a backward loop a
    layer — under ``jax.checkpoint`` too, where the recomputed forward loop
    has no reader and is dropped — and the only scatters are the plan's,
    over ``T * k`` int32 (dispatch and combine transpose to gathers, not to
    a scatter-add of rows of d)."""
    first, floats, _ = _operands(100, seed=1)
    second = jnp.asarray(_choices(300, seed=2))
    text = _two_layers(remat).lower(floats, first, second).compile().as_text()
    assert len(re.findall(r"\swhile\(", text)) == 4
    assert not re.search(r"\sconditional\(", text)
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert scatters and all(s.startswith(f"s32[{FULL}]") for s in scatters)


def _buffer_and_its_vjp(rows, g, floats, plan):
    out, vjp = jax.vjp(lambda *f: moe._experts_at(rows, 0, *f, plan), *floats)
    return out, vjp(g)


def test_twelve_grouped_products_a_layer():
    """What the two loops run for a buffer: the forward's body 3 grouped
    products, the backward's the same 3 again and their 6 transposes —
    ``mxtpu_gmm`` x 3, then x 3 with ``_dlhs`` x 3 and ``_drhs`` x 3 — and
    a layer's program holds no other."""
    experts, floats, g = _operands(100, d=128, h=128)
    plan = _plan_of(experts, floats)
    with interpret_kernels():
        forward = str(jax.make_jaxpr(functools.partial(
            moe._experts_at, BUFFER, 0))(*floats, plan))
        both = str(jax.make_jaxpr(functools.partial(
            _buffer_and_its_vjp, BUFFER))(g.astype(jnp.float32), floats,
                                          plan))
        layer = str(jax.make_jaxpr(functools.partial(
            _value_and_grads, moe.dropless_moe_apply))(experts, floats, g))

    def calls(text):
        return {name: len(re.findall(rf"name={name}\b", text))
                for name in ("mxtpu_gmm", "mxtpu_gmm_dlhs", "mxtpu_gmm_drhs")}
    assert calls(forward) == {"mxtpu_gmm": 3, "mxtpu_gmm_dlhs": 0,
                              "mxtpu_gmm_drhs": 0}
    assert calls(both) == {"mxtpu_gmm": 3, "mxtpu_gmm_dlhs": 3,
                           "mxtpu_gmm_drhs": 3}
    assert calls(layer) == {"mxtpu_gmm": 6, "mxtpu_gmm_dlhs": 3,
                            "mxtpu_gmm_drhs": 3}


@pytest.mark.parametrize("remat", [False, True])
def test_grad_through_two_layers_at_different_fills(remat):
    """A layer in one buffer under one in three: the gradient of the pair
    is that of two worst-case layers."""
    first, floats, _ = _operands(100, seed=4)
    second = jnp.asarray(_choices(700, seed=5))
    got = _two_layers(remat)(floats, first, second)

    def loss(floats, first, second):
        y = floats[0]
        for experts in (first, second):
            h = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
            y = y + _worst_case(h, experts, *floats[1:],
                                expert_offset=OFFSET)
        return jnp.sum(y * y)
    want = jax.jit(jax.grad(loss))(floats, first, second)
    for name, a, b in zip(NAMES[1:], got, want):
        np.testing.assert_allclose(a, b, err_msg=name, rtol=1e-4, atol=1e-4)


def test_layers_of_one_shape_share_a_trace():
    """The two directions are jitted, so a second layer of the same shapes
    traces no grouped product (``moe.gmm.*`` count traces), and the kernel
    mode is part of the cache's key."""
    experts, floats, g = _operands(50, seed=11, d=48, h=24)

    def traced():
        before = telemetry.value("moe.gmm.xla") or 0
        _value_and_grads(moe.dropless_moe_apply, experts, floats, g)
        return (telemetry.value("moe.gmm.xla") or 0) - before
    assert traced() == 6            # 3 forward, 3 in the backward's own
    assert traced() == 0
    with interpret_kernels():       # shapes the kernels do not tile
        assert traced() == 6


def test_the_buffer_gauges():
    experts, floats, _ = _operands(10)
    moe.dropless_moe_apply(floats[0], experts, *floats[1:],
                           expert_offset=OFFSET)
    assert telemetry.value("moe.rows_buffer") == FULL
    assert telemetry.value("moe.rows_ladder") == BUFFER
