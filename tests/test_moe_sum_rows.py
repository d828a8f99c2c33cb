"""The expert layer's token-side sum (``ops/moe_sum_rows.py``): the Pallas
kernel (in the interpreter) and the XLA form against the sum the layer took
before — a gather into slot order, ``k`` shifted sums, a gather of T — kept
here as the reference (bit for bit where nothing is multiplied; see
``_same_sum``); and the layer against itself with that sum put back."""
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import moe_sum_rows
from mxnet_tpu.ops.kernel_mode import interpret_kernels
from mxnet_tpu.parallel import moe

# 64 tokens, token t sending t % (k + 1) of its k choices to the 8 experts
# held here: every count from 0 to k, 256 rows routed for k = 8, in windows
# of 128 rows
T, HELD, EXPERTS, D = 64, 8, 16, 256


def _slots(row_of_choice, here):
    """The slot order the layer used to keep: the choices in a window in
    token order, packed to the front."""
    tokens, k = here.shape
    choices = jnp.arange(tokens * k, dtype=jnp.int32)
    slots = jnp.cumsum(here.reshape(-1), dtype=jnp.int32)
    choice_of_slot = jnp.zeros(tokens * k, jnp.int32).at[
        jnp.where(here.reshape(-1), slots - 1, slots[-1] + choices - slots)
    ].set(choices, unique_indices=True)
    return (choice_of_slot, row_of_choice.reshape(-1)[choice_of_slot],
            jnp.maximum(slots.reshape(tokens, k)[:, -1] - 1, 0),
            jnp.any(here, axis=1))


def _reference(rows, row_of_choice, here, scale=None):
    """The token-side sum as the layer took it before: one gather brings the
    rows into slot order, where a token's rows are neighbours; each slot
    adds the up to ``k - 1`` slots before it that carry its token (shifted
    slices), and the last slot of each token's run is the token's sum."""
    k, n = here.shape[1], rows.shape[0]
    choice_of_slot, row_of_slot, last_slot, token_is_here = _slots(
        row_of_choice, here)
    choice_of_slot, row_of_slot = choice_of_slot[:n], row_of_slot[:n]
    if scale is not None:
        scale = scale.reshape(-1)[choice_of_slot].astype(jnp.float32)
    front = jnp.zeros(k - 1, jnp.int32)
    token = jnp.concatenate([front - 1, choice_of_slot // k])
    ordered = rows[jnp.concatenate([front, row_of_slot])]
    if scale is not None:
        scale = jnp.concatenate([front.astype(scale.dtype), scale])

    def shifted(s):
        term = ordered[s:s + n].astype(jnp.float32)
        if scale is not None:
            term = term * scale[s:s + n, None]
        return jnp.where((token[s:s + n] == token[k - 1:])[:, None], term, 0)

    runs = sum(shifted(s) for s in range(k))
    return jnp.where(token_is_here[:, None], runs[last_slot], 0)


def _window(k, start, seed=0):
    """The layer's window of 128 rows from ``start``: (row_of_choice, here)
    as ``parallel.moe`` hands them to the sum."""
    rng = np.random.RandomState(seed)
    t, j = np.meshgrid(np.arange(T), np.arange(k), indexing="ij")
    held = (t + j) % HELD
    absent = HELD + (t + j) % (EXPERTS - HELD)
    experts = np.where(j < t % (k + 1), held, absent)
    experts = np.take_along_axis(experts, rng.rand(T, k).argsort(1), 1)
    plan = moe._make_plan(jnp.asarray(experts, jnp.int32), HELD, 0)
    window = moe._window(plan, 128, start)
    return window.row_of_choice, window.choice_is_here, plan


def _rows(dtype, tail, routed, seed=1):
    """128 buffer rows; those past the window's routed ones are zeros (the
    products' ``whole`` mode) or NaN (rows no choice names: never read)."""
    rows = np.random.RandomState(seed).randn(128, D)
    rows[routed:] = 0.0 if tail == "zeros" else np.nan
    return jnp.asarray(rows, dtype)


@pytest.mark.parametrize("k", [6, 8])
@pytest.mark.parametrize("start", [0, 128], ids=["first", "second"])
@pytest.mark.parametrize("tail", ["zeros", "nan"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["dispatch", "combine"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_kernel_and_the_xla_form_are_the_old_sum_bit_for_bit(
        k, start, tail, weighted, dtype):
    """Tokens with 0 to k choices here, a window from the first row and one
    from the 128th, scales absent and given, bf16 and float32 rows: the same
    float32 sum in the same order (``_same_sum``)."""
    row_of_choice, here, plan = _window(k, start)
    routed = min(128, int(plan.routed) - start)
    assert routed > 0 and int(jnp.sum(here)) == routed
    counts = set(np.asarray(jnp.sum(here, axis=1)).tolist())
    assert 0 in counts and len(counts) > 3
    rows = _rows(dtype, tail, routed)
    scale = (jnp.asarray(np.random.RandomState(2).rand(T, k) + 0.1,
                         jnp.float32) if weighted else None)
    want = jax.jit(_reference)(rows, row_of_choice, here, scale)
    xla = jax.jit(moe_sum_rows._xla_sum_rows)(rows, row_of_choice, here,
                                               scale)
    with interpret_kernels():       # (a fresh function: see below)
        kernel = jax.jit(lambda *a: moe_sum_rows.sum_rows(*a))(
            rows, row_of_choice, here, scale, jnp.int32(routed))
    assert bool(jnp.all(jnp.isfinite(want)))
    for got in (xla, kernel):
        assert got.dtype == jnp.float32 and got.shape == (T, D)
        _same_sum(got, want, weighted)


def _same_sum(got, want, weighted):
    """Equal to the bit where nothing is multiplied.  With scales, the CPU
    compiler contracts a product and the sum it joins into one fused
    multiply-add in some programs and not in others (each of the three forms
    here differs from a plain sequential float32 sum in some elements), so
    a term may be rounded once where another form rounds it twice: at most
    1e-6 of the sum's scale."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not weighted:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-6 * np.abs(want).max())


def test_the_kernel_is_what_a_tpu_traces():
    """``sum_rows`` under the interpreter takes the kernels (the sum, and
    the copy of the routed rows into slabs before it; counted
    ``moe.sum_rows.pallas``); without a kernel mode, or at a width the
    kernel does not take, the XLA form (``moe.sum_rows.xla``)."""
    row_of_choice, here, _ = _window(8, 0)

    def traced(rows):
        before = {name: telemetry.value(name) or 0 for name in
                  ("moe.sum_rows.pallas", "moe.sum_rows.xla")}
        # (a fresh function: make_jaxpr caches a function's trace by its
        # operands, and the kernel mode is not among them)
        text = str(jax.make_jaxpr(lambda *a: moe_sum_rows.sum_rows(*a))(
            rows, row_of_choice, here))
        return (sorted(re.findall(r"name=(mxtpu_moe_sum_rows\w*)", text)),
                {name: (telemetry.value(name) or 0) - n
                 for name, n in before.items()})
    rows = _rows(jnp.bfloat16, "zeros", 128)
    assert traced(rows) == ([], {"moe.sum_rows.pallas": 0,
                                 "moe.sum_rows.xla": 1})
    with interpret_kernels():
        assert traced(rows) == (
            ["mxtpu_moe_sum_rows", "mxtpu_moe_sum_rows_slabs"],
            {"moe.sum_rows.pallas": 1, "moe.sum_rows.xla": 0})
        assert traced(rows[:, :128]) == ([], {"moe.sum_rows.pallas": 0,
                                              "moe.sum_rows.xla": 1})


def _old_sum_rows(rows, window, scale=None):
    """``parallel.moe._sum_rows`` as it was: the reference above over the
    window's own slot order."""
    return _reference(rows, window.row_of_choice, window.choice_is_here,
                      scale)


def _layer_value_and_grads(experts, floats, g, remat):
    def loss(*floats):
        def layer(x, weights, *stacks):
            return moe.dropless_moe_apply(x, experts, weights, *stacks,
                                          expert_offset=0)
        if remat:
            layer = jax.checkpoint(layer)
        out = layer(*floats)
        return jnp.sum(out.astype(jnp.float32) * g), out
    jax.clear_caches()          # the layer's two directions are jitted
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(5)), has_aux=True))(*floats)
    return (out,) + grads


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_the_layer_and_its_four_gradients_are_unchanged(monkeypatch, remat,
                                                        kernels):
    """``dropless_moe_apply``'s output and its gradients in x, the weights
    and the three stacks, with the old sum put back in its place: equal to
    the bit, with and without ``remat``, through the XLA form and through
    the kernels (the grouped products' and this one, in the interpreter)."""
    rng = np.random.RandomState(3)
    t, j = np.meshgrid(np.arange(T), np.arange(8), indexing="ij")
    experts = jnp.asarray(np.where(j < t % 9, (t + j) % HELD,
                                   HELD + (t + j) % HELD), jnp.int32)
    h = 128
    floats = [jnp.asarray(rng.randn(T, D), jnp.bfloat16),
              jnp.asarray(rng.rand(T, 8) + 0.1, jnp.float32),
              jnp.asarray(rng.randn(HELD, D, h) * D ** -0.5, jnp.bfloat16),
              jnp.asarray(rng.randn(HELD, D, h) * D ** -0.5, jnp.bfloat16),
              jnp.asarray(rng.randn(HELD, h, D) * h ** -0.5, jnp.bfloat16)]
    g = jnp.asarray(rng.randn(T, D), jnp.float32)
    run = functools.partial(_layer_value_and_grads, experts, floats, g,
                            remat)
    with interpret_kernels() if kernels else contextlib.nullcontext():
        got = run()
        monkeypatch.setattr(moe, "_sum_rows", _old_sum_rows)
        want = run()
    jax.clear_caches()
    assert all(float(jnp.max(jnp.abs(a.astype(jnp.float32)))) > 0
               for a in got)
    for name, a, b in zip(("out", "dx", "dweights", "dgate", "dup", "ddown"),
                          got, want):
        _same_sum(a, b, weighted=True)


def test_a_step_traces_three_sums():
    """The layers of one shape share the two directions' traces: the
    forward rule's combine, and in the backward rule the combine's forward
    again (which the compiler removes) and the dispatch's transpose — 3,
    and 0 for a second layer."""
    rng = np.random.RandomState(4)
    experts = jnp.asarray(rng.randint(0, EXPERTS, (T, 4)), jnp.int32)
    floats = [jnp.asarray(rng.randn(T, 48), jnp.float32),
              jnp.asarray(rng.rand(T, 4), jnp.float32),
              jnp.asarray(rng.randn(HELD, 48, 24), jnp.float32),
              jnp.asarray(rng.randn(HELD, 48, 24), jnp.float32),
              jnp.asarray(rng.randn(HELD, 24, 48), jnp.float32)]

    def traced():
        before = telemetry.value("moe.sum_rows.xla") or 0
        jax.grad(lambda *f: jnp.sum(moe.dropless_moe_apply(
            f[0], experts, *f[1:], expert_offset=0)),
            argnums=tuple(range(5)))(*floats)
        return (telemetry.value("moe.sum_rows.xla") or 0) - before
    assert traced() == 3
    assert traced() == 0
