"""Flash attention (mxnet_tpu.ops.flash_attention) vs naive reference.

The kernel must match softmax(QK^T/sqrt(d))V exactly (same algorithm,
different memory schedule) in both values and gradients — the reference's
check_consistency idea (SURVEY.md §4.2) applied CPU-scan vs naive-XLA.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import flash_attention


def _naive(q, k, v, causal=False, scale=None):
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        lq, lk = s.shape[-2:]
        mask = np.tril(np.ones((lq, lk), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 3, 64, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 3, 64, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 3, 64, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    ref = _naive(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_cross_attention_shapes():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 48, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 96, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 96, 8), jnp.float32)
    out = flash_attention(q, k, v)
    ref = _naive(q, k, v)
    assert out.shape == (1, 2, 48, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_naive(causal):
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ndarray_tape_integration():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    rng = np.random.RandomState(3)
    q = mx.nd.array(rng.randn(1, 2, 16, 8).astype("float32"))
    k = mx.nd.array(rng.randn(1, 2, 16, 8).astype("float32"))
    v = mx.nd.array(rng.randn(1, 2, 16, 8).astype("float32"))
    q.attach_grad()
    with autograd.record():
        out = flash_attention(q, k, v)
        loss = (out * out).sum()
    loss.backward()
    ref = jax.grad(lambda q_, k_, v_: jnp.sum(
        _naive(q_, k_, v_) ** 2))(q.data, k.data, v.data)
    np.testing.assert_allclose(np.asarray(q.grad.data), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_mha_use_flash_matches_einsum_path():
    from mxnet_tpu.gluon.model_zoo.nlp.attention import MultiHeadAttention
    import mxnet_tpu as mx
    rng = np.random.RandomState(4)
    x = mx.nd.array(rng.randn(2, 12, 16).astype("float32"))
    cell = MultiHeadAttention(units=16, num_heads=4, use_flash=True)
    cell.initialize()
    out_flash = cell(x)                          # eval mode -> flash path
    cell._use_flash = False
    out_ref = cell(x)
    np.testing.assert_allclose(out_flash.asnumpy(), out_ref.asnumpy(),
                               rtol=2e-5, atol=2e-5)


def test_pallas_kernel_structure_compiles_in_interpret_mode():
    """Exercise the Pallas kernel itself (interpret=True on CPU)."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 128, 128), jnp.float32)
    k = jnp.asarray(rng.randn(2, 128, 128), jnp.float32)
    v = jnp.asarray(rng.randn(2, 128, 128), jnp.float32)
    # the package re-exports the op under the submodule's own name
    import importlib
    mod = importlib.import_module("mxnet_tpu.ops.flash_attention")

    def interp_forward(q, k, v, causal, sm_scale, bq, bk):
        return mod._pallas_forward(q, k, v, causal, sm_scale, bq, bk,
                                   interpret=True)

    for causal in (False, True):
        out, lse = interp_forward(q, k, v, causal, 1.0 / np.sqrt(128.0),
                                  128, 128)
        ref, ref_lse = mod._scan_forward(q, k, v, causal,
                                         1.0 / np.sqrt(128.0), 128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_is_wrapped_in_shard_map_under_a_partitioned_step():
    """XLA cannot partition a Mosaic call by itself (on the chip: "Mosaic
    kernels cannot be automatically partitioned"), so under an ambient dp
    mesh — what DataParallelTrainer's psum path sets while it traces —
    the kernel goes inside a shard_map over 'dp'; with no ambient mesh
    (one chip, or a trace that is already per chip inside ZeRO-1's
    shard_map) it is called bare.  Results agree either way."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    from mxnet_tpu.parallel import make_mesh, mesh_scope
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(8, 2, 128, 64), jnp.float32)
               for _ in range(3))

    def fresh():
        # the ambient mesh is read while tracing and is no part of any
        # jax cache key: every trace below gets a function of its own
        return lambda q, k, v: flash_attention(q, k, v, causal=True)

    def fresh_grad(scope):
        # as DataParallelTrainer's loss_of: the scope covers the forward's
        # trace and has closed when value_and_grad traces the backward
        def loss(q, k, v):
            with mesh_scope(scope):
                return jnp.sum(fresh()(q, k, v) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))

    with interpret_kernels():
        with mesh_scope(mesh):
            wrapped = str(jax.make_jaxpr(fresh())(q, k, v))
            sharded = [jax.device_put(a, NamedSharding(mesh, P("dp")))
                       for a in (q, k, v)]
            out = jax.jit(fresh())(*sharded)
        with mesh_scope(None):
            bare = str(jax.make_jaxpr(fresh())(q, k, v))
        wrapped_grad = str(jax.make_jaxpr(fresh_grad(mesh))(q, k, v))
        grads = jax.jit(fresh_grad(mesh))(*sharded)
        bare_grad = str(jax.make_jaxpr(fresh_grad(None))(q, k, v))
    assert "shard_map" in wrapped and "pallas_call" in wrapped
    assert "shard_map" not in bare and "pallas_call" in bare
    # the gradient: the forward's kernel and the backward's (six operands,
    # three results), each in a shard_map of its own
    assert wrapped_grad.count("shard_map") == 2
    assert wrapped_grad.count("mxtpu_flash_bwd") == 1
    assert "shard_map" not in bare_grad and "mxtpu_flash_bwd" in bare_grad
    assert out.sharding.spec[0] == "dp"
    np.testing.assert_allclose(np.asarray(out), np.asarray(fresh()(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(grads, fresh_grad(None)(q, k, v)):
        assert got.sharding.spec[0] == "dp"
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the kernel's grid: G (batch x head) rows per program, chosen from shapes
# ---------------------------------------------------------------------------

def _flash_module():
    # the package re-exports the op under the submodule's own name
    import importlib
    return importlib.import_module("mxnet_tpu.ops.flash_attention")


# (lq, lk, bq, bk) of the streaming forward's cases below: every way a causal
# row can mix wholly visible, cut and wholly masked (dead) blocks
_BLOCK_SHAPES = {
    "square": (256, 256, 128, 128),
    "visible-cut-dead-in-one-row": (384, 384, 128, 128),
    "bq-over-bk": (1024, 384, 512, 128),
    "bq-under-bk": (384, 1024, 128, 512),
    "lq-over-lk": (512, 256, 128, 128),
    "lq-under-lk": (256, 512, 128, 128),
    "column-halves": (512, 512, 256, 256),
    "kanana": (4096, 4096, 512, 512),
}


def _streaming(bh, shape, d=64, dv=64, note=""):
    """A case of ``shape``'s blocks; every row fits one program at these
    sizes, so G is ``bh``."""
    lq, lk, bq, bk = _BLOCK_SHAPES[shape]
    return pytest.param(bh, lq, lk, d, dv, bq, bk, bh,
                        id=f"streaming-{shape}{note}")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bh,lq,lk,d,dv,bq,bk,rows", [
    pytest.param(24, 128, 128, 64, 64, 128, 128, 24,
                 id="one-pass-lse-rows"),
    pytest.param(6, 128, 128, 64, 64, 128, 128, 6,
                 id="one-pass-lse-broadcast"),
    _streaming(16, "square"),
    pytest.param(3, 256, 256, 128, 128, 256, 256, 3,
                 id="one-pass-d128-block256"),
    _streaming(2, "visible-cut-dead-in-one-row"),
    _streaming(2, "bq-over-bk"),
    _streaming(2, "bq-under-bk"),
    _streaming(8, "lq-over-lk", note="-G8"),
    _streaming(3, "lq-under-lk", note="-G3"),
    _streaming(4, "visible-cut-dead-in-one-row", 192, 128,
               note="-mla-192-128-G4"),
    _streaming(2, "column-halves"),
    pytest.param(2, 1024, 384, 64, 64, 512, 384, 2,
                 id="one-pass-two-q-blocks"),
])
def test_pallas_forward_rows_per_program_matches_scan(bh, lq, lk, d, dv, bq,
                                                      bk, rows, causal):
    """The kernel's output and log-sum-exp (one block of keys: the one-pass
    body; more: the streaming one, which walks the KV blocks a grid step
    holds and under ``causal`` skips the dead ones, masks the cut ones and
    runs the visible ones plain; blocks of 256 queries and up go in two
    column halves at these G) against the scan and against plain
    softmax(QK^T)V."""
    from mxnet_tpu import telemetry
    mod = _flash_module()
    rng = np.random.RandomState(bh + lq + lk)
    q = jnp.asarray(rng.randn(bh, lq, d), jnp.float32)
    k = jnp.asarray(rng.randn(bh, lk, d), jnp.float32)
    v = jnp.asarray(rng.randn(bh, lk, dv), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    out, lse = mod._pallas_forward(q, k, v, causal, scale, bq, bk,
                                   interpret=True)
    assert telemetry.value("flash.fwd.rows_per_program") == rows
    # at these sizes every KV block fits VMEM: one grid step walks them all
    assert telemetry.value("flash.fwd.kv_blocks_per_step") == \
        (lk // bk if lk > bk else 1)
    ref, ref_lse = mod._scan_forward(q, k, v, causal, scale, bk)
    assert out.shape == (bh, lq, dv) and lse.shape == (bh, lq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_naive(q[None], k[None], v[None], causal,
                                           scale)[0]), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", sorted(_BLOCK_SHAPES))
def test_causal_block_classes_and_kv_clamp_against_the_mask_itself(shape):
    """Every (Q block, KV block) pair of the shapes above, sorted by the
    kernels' predicates, against the causal mask over positions: live where
    any score is visible, cut where some but not all are; a dead step names
    the row's last live K / V block (so nothing is fetched for it) and no
    live step is redirected."""
    mod = _flash_module()
    lq, lk, bq, bk = _BLOCK_SHAPES[shape]
    nq, nk = lq // bq, lk // bk
    visible = np.arange(lq)[:, None] >= np.arange(lk)[None, :]
    counts = [0, 0]
    for i in range(nq):
        block = [visible[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
                 for j in range(nk)]
        live_blocks = [j for j in range(nk) if block[j].any()]
        assert live_blocks and live_blocks == list(range(len(live_blocks)))
        for j in range(nk):
            live, cut = mod._causal_block(i, j, bq, bk)
            assert live == block[j].any()
            assert (live and cut) == (block[j].any() and not block[j].all())
            assert (live and not cut) == block[j].all()
            fetched = int(mod._kv_block_fetched(i, j, bq, bk))
            assert fetched == (j if live else live_blocks[-1])
            counts[0] += live
            counts[1] += live and cut
        # the same sorting as counts from the first block on (the forward's
        # inner walk): visible blocks, then cut ones, then dead ones
        visible_run, live_run = mod._causal_extent(i, bq, bk)
        assert [mod._causal_block(i, j, bq, bk) for j in range(nk)] == \
            [(j < live_run, j >= visible_run) for j in range(nk)]
        # a grid step that holds several KV blocks: one with a live block
        # names itself, a later one the last that has one
        for held in (n for n in range(2, nk + 1) if nk % n == 0):
            for step in range(nk // held):
                has_live = step * held <= live_blocks[-1]
                assert int(mod._kv_block_fetched(i, step, bq, bk * held)) \
                    == (step if has_live else live_blocks[-1] // held)
    assert mod._forward_block_counts(lq, lk, bq, bk, True) == tuple(counts)
    assert mod._forward_block_counts(lq, lk, bq, bk, False) == (nq * nk, 0)


@pytest.mark.parametrize("lq,lk,bq,bk,causal,live,masked", [
    pytest.param(*_BLOCK_SHAPES["kanana"], True, 36, 8, id="kanana"),
    pytest.param(*_BLOCK_SHAPES["kanana"], False, 64, 0,
                 id="kanana-non-causal"),
    pytest.param(512, 512, 512, 512, True, 1, 1, id="one-pass-causal"),
    pytest.param(512, 512, 512, 512, False, 1, 0, id="one-pass-bert-s512"),
    pytest.param(1024, 384, 512, 384, True, 2, 2,
                 id="one-pass-masks-every-block"),
    pytest.param(*_BLOCK_SHAPES["visible-cut-dead-in-one-row"], True, 6, 3,
                 id="three-classes"),
])
def test_forward_gauges_count_the_blocks_a_row_computes_and_masks(
        lq, lk, bq, bk, causal, live, masked):
    """Set while tracing (nothing runs here): of the last forward kernel,
    the (Q block, KV block) pairs a row computes and those it masks."""
    from mxnet_tpu import telemetry
    mod = _flash_module()
    for name in ("flash.fwd.blocks_live", "flash.fwd.blocks_masked"):
        telemetry.set_gauge(name, -1)
    q = jax.ShapeDtypeStruct((2, lq, 192), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, lk, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, lk, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: mod._pallas_forward(
        q, k, v, causal, 192 ** -0.5, bq, bk, interpret=True), q, k, v)
    assert telemetry.value("flash.fwd.blocks_live") == live
    assert telemetry.value("flash.fwd.blocks_masked") == masked
    # two rows here: the kanana call's G, so its four KV blocks a grid step
    assert telemetry.value("flash.fwd.kv_blocks_per_step") == \
        {8: 4, 3: 3, 1: 1}[lk // bk]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,held", [
    (name, held) for name in sorted(_BLOCK_SHAPES) if name != "kanana"
    for held in (1, 2, 3, 4, 8)
    if (_BLOCK_SHAPES[name][1] // _BLOCK_SHAPES[name][3]) % held == 0
    and held <= _BLOCK_SHAPES[name][1] // _BLOCK_SHAPES[name][3]])
def test_streaming_forward_is_the_same_whatever_a_grid_step_holds(
        monkeypatch, shape, held, causal):
    """One KV block a grid step (the grid walks them all), several, or all
    of Lk in one step: the same online softmax block by block, so the same
    output and log-sum-exp bit for bit, and the scan's within rounding."""
    from mxnet_tpu import telemetry
    mod = _flash_module()
    lq, lk, bq, bk = _BLOCK_SHAPES[shape]
    rng = np.random.RandomState(lq + lk + held)
    q = jnp.asarray(rng.randn(2, lq, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, lk, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, lk, 64), jnp.float32)

    def run(n):
        monkeypatch.setattr(mod, "_kv_blocks_per_step", lambda *a: n)
        got = mod._pallas_forward(q, k, v, causal, 0.125, bq, bk,
                                  interpret=True)
        assert telemetry.value("flash.fwd.kv_blocks_per_step") == n
        return got
    out, lse = run(held)
    if held > 1:
        one_out, one_lse = run(1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(one_out))
        np.testing.assert_array_equal(np.asarray(lse), np.asarray(one_lse))
    ref, ref_lse = mod._scan_forward(q, k, v, causal, 0.125, bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)


def test_kv_blocks_per_step_fill_what_the_rows_leave_of_vmem():
    mod = _flash_module()
    # the kanana cell's call (G = 2): four of a row's eight KV blocks
    assert mod._rows_per_program(64, 512, 512, 192, 2, True, 128) == 2
    assert mod._kv_blocks_per_step(8, 2, 512, 512, 192, 2, 128) == 4
    # chip_smoke's long shape: all four
    assert mod._kv_blocks_per_step(4, 2, 512, 512, 128, 2, 128) == 4
    # always a divisor of nk, within the budget, and no fewer with fewer rows
    for nk, g, d, dv in [(8, 2, 192, 128), (32, 2, 128, 128),
                         (8, 4, 64, 64), (6, 1, 64, 64), (7, 2, 128, 128)]:
        n = mod._kv_blocks_per_step(nk, g, 512, 512, d, 2, dv)
        assert nk % n == 0
        more = 2 * g * mod._padded_head_dims(d, dv, 2) * 512 * 2
        assert n == 1 or mod._program_vmem_bytes(
            g, 512, 512, d, 2, True, dv) + (n - 1) * more <= mod._VMEM_BUDGET
        assert n <= mod._kv_blocks_per_step(nk, 1, 512, 512, d, 2, dv)
    # blocks that are over the budget by themselves: one a step
    assert mod._kv_blocks_per_step(4, 1, 2048, 2048, 128, 2, 128) == 1


def _kernel_calls(jaxpr):
    """The params of every ``pallas_call`` in ``jaxpr``, nested ones too."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    found += _kernel_calls(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    found += _kernel_calls(sub)
    return found


# what each group-1 shape traced before the backward could read K / V in
# place, forward then backward: the kernel's grid and blocks, and a hash of
# its jaxpr, grid mapping and compiler parameters (the text without source
# locations, from which Mosaic's module is lowered)
_PARENTS_KERNELS = {
    "bert-s128": [
        ((48, 1, 1), [(32, 64, 128)] * 4 + [(32, 128)], "8bd08293699501b5"),
        ((96, 1, 1), [(16, 64, 128)] * 4 + [(16, 1, 128)]
         + [(16, 64, 128)] * 4, "39f9112012260790")],
    "bert-s512": [
        ((96, 1, 1), [(4, 64, 512)] * 4 + [(4, 8, 512)], "0c2e75e448465df3"),
        ((128, 1, 1), [(3, 64, 512)] * 4 + [(3, 1, 512)]
         + [(3, 64, 512)] * 4, "2598f49dc3b99c35")],
    "kanana-mla": [
        ((32, 8, 2), [(2, 192, 512), (2, 192, 2048), (2, 128, 2048),
                      (2, 128, 512), (2, 8, 512)], "f9445e1922d1fdc1"),
        ((64, 8, 8), [(1, 192, 512), (1, 192, 512), (1, 128, 512),
                      (1, 128, 512), (1, 1, 512), (1, 128, 512),
                      (1, 192, 512), (1, 192, 512), (1, 128, 512)],
         "926287c5acbe381c")],
    "kimi-mla": [
        ((16, 16, 4), [(2, 192, 512), (2, 192, 2048), (2, 128, 2048),
                       (2, 128, 512), (2, 8, 512)], "5e8b9e7dbc31905e"),
        ((32, 16, 16), [(1, 192, 512), (1, 192, 512), (1, 128, 512),
                        (1, 128, 512), (1, 1, 512), (1, 128, 512),
                        (1, 192, 512), (1, 192, 512), (1, 128, 512)],
         "21105af07da86bd0")],
}


@pytest.mark.parametrize("bh,seq,d,dv,causal,want", [
    # (G, bq, bk, KV blocks a grid step) as the parent (PR 39) chose them
    pytest.param(1536, 128, 64, 64, False, (32, 128, 128, 1),
                 id="bert-s128"),
    pytest.param(384, 512, 64, 64, False, (4, 512, 512, 1), id="bert-s512"),
    pytest.param(64, 4096, 192, 128, True, (2, 512, 512, 4),
                 id="kanana-mla"),
    pytest.param(32, 8192, 192, 128, True, (2, 512, 512, 4), id="kimi-mla"),
])
def test_a_group_of_one_keeps_the_parents_program(request, bh, seq, d, dv,
                                                  causal, want):
    """Where every row has its own K / V (every call but grouped-query
    attention) the rule gives the forward program it gave before K / V
    could be read in place: the same G, blocks and KV blocks a step; and
    both kernels a differentiated call traces for the chip are the
    parent's, forward and backward — grid, blocks, and the kernel's jaxpr
    with its grid mapping and compiler parameters, hashed."""
    import hashlib
    from unittest import mock
    mod = _flash_module()
    block = mod._pick_block(seq)               # what _use_pallas gives
    g, bq, bk = mod._forward_tiling(bh, 1, seq, seq, block, block, d, 2,
                                    seq > block, dv)
    nsub = mod._kv_blocks_per_step(seq // bk, g, bq, bk, d, 2, dv) \
        if seq > bk else 1
    assert (g, bq, bk, nsub) == want

    def grads(q, k, v):
        return jax.grad(lambda *a: mod._flash(*a, causal, d ** -0.5).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    def rows(x):
        return jax.ShapeDtypeStruct((bh, seq, x), jnp.bfloat16)
    # traced as for the chip (nothing is lowered: no chip needed)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        calls = _kernel_calls(jax.make_jaxpr(grads)(rows(d), rows(d),
                                                    rows(dv)).jaxpr)
    got = []
    for p in calls:
        gm = p["grid_mapping"]
        text = str(p["jaxpr"]) + str(gm) + str(p["compiler_params"])
        got.append((tuple(gm.grid),
                    [tuple(getattr(x, "block_size", x) for x in b.block_shape)
                     for b in gm.block_mappings],
                    hashlib.sha256(text.encode()).hexdigest()[:16]))
    assert [str(p["name"]) for p in calls] == ["mxtpu_flash_fwd",
                                               "mxtpu_flash_bwd"]
    assert got == _PARENTS_KERNELS[request.node.callspec.id]


def test_the_keye_query_heads_share_a_kv_block_within_the_budget():
    """The keye cell's masked forward (32 query heads over 4 kv heads,
    L = 16384, d = 128): a program takes the 8 query heads of one kv head
    at square 256-blocks — the parent's score tile of 2 x 512 x 512
    elements — and walks 8 KV blocks a grid step, K / V and the mask tile
    counted once; all of it within the budget."""
    mod = _flash_module()
    args = (16384, 16384, 512, 512, 128, 2, True, 128, True)
    assert mod._forward_tiling(32, 1, *args) == (2, 512, 512)
    g, bq, bk = mod._forward_tiling(32, 8, *args)
    assert (g, bq, bk) == (8, 256, 256)
    assert g * bq * bk == 2 * 512 * 512
    nsub = mod._kv_blocks_per_step(16384 // bk, g, bq, bk, 128, 2, 128,
                                   True, 1)
    assert nsub == 8
    used = mod._program_vmem_bytes(g, bq, bk, 128, 2, True, 128, True, 1)
    more = 2 * mod._padded_head_dims(128, 128, 2) * bk * 2 + 2 * bq * bk
    assert used + (nsub - 1) * more <= mod._VMEM_BUDGET
    # the K / V blocks are one row's, not G rows'
    assert used < mod._program_vmem_bytes(g, bq, bk, 128, 2, True, 128,
                                          True)
    # four heads a kv head: four a program
    assert mod._forward_tiling(32, 4, *args)[0] == 4


def test_heads_per_kv_block_gauge_reads_the_group_where_one_is_read():
    """Set while tracing: 1 for a BERT-shaped call, the group for a masked
    call whose K / V are at their own heads (the keye shape)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    mod = _flash_module()

    def rows(n, seq, d):
        return jax.ShapeDtypeStruct((n, seq, d), jnp.bfloat16)
    with interpret_kernels():
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v), *(
            jax.ShapeDtypeStruct((128, 12, 128, 64), jnp.bfloat16),) * 3)
        assert telemetry.value("flash.fwd.heads_per_kv_block") == 1
        jax.eval_shape(lambda q, k, v, m: mod.masked_flash(
            q, k, v, m, 128 ** -0.5), rows(32, 16384, 128),
            rows(4, 16384, 128), rows(4, 16384, 128),
            jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8))
        assert telemetry.value("flash.fwd.heads_per_kv_block") == 8
        assert telemetry.value("flash.fwd.rows_per_program") == 8
        assert telemetry.value("flash.fwd.kv_blocks_per_step") == 8


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("seq,causal", [(256, False), (512, False),
                                        (768, True)])
def test_power_of_two_scale_on_q_is_bit_identical_to_the_tile(
        monkeypatch, seq, causal, dtype):
    """d = 64 at 256-blocks: 0.125 multiplies the (G, D, BQ) block of Q; on
    the (G, BK, BQ) score tile, where any other scale stays, it gives the
    same output and log-sum-exp bit for bit (one pass and streaming)."""
    mod = _flash_module()
    assert all(mod._scale_on_q(s, 64, 512) for s in (0.125, 1 / 16, 1.0,
                                                     64 ** -0.5))
    assert not any(mod._scale_on_q(s, 64, 512) for s in (
        192 ** -0.5, 128 ** -0.5, 0.3, 0.75))
    # the Q block over a quarter of the tile (BERT at L = 128): left alone
    assert mod._scale_on_q(0.125, 64, 256)
    assert not mod._scale_on_q(0.125, 64, 128)
    assert not mod._scale_on_q(1 / 16, 256, 512)
    rng = np.random.RandomState(seq)
    q, k, v = (jnp.asarray(rng.randn(4, seq, 64), dtype) for _ in range(3))

    def run():
        return jax.jit(lambda q, k, v: mod._pallas_forward(
            q, k, v, causal, 0.125, 256, 256, interpret=True))(q, k, v)
    on_q = run()
    monkeypatch.setattr(mod, "_scale_on_q", lambda *a: False)
    on_tile = run()
    for a, b in zip(on_q, on_tile):
        assert a.dtype == b.dtype and np.isfinite(np.asarray(
            a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_pallas_forward_bf16_under_jit_in_interpret_mode():
    """bf16 operands inside a jit: what the benchmark's CPU rehearsal and
    chip_smoke's run (the CPU backend has no batched bf16 dot)."""
    mod = _flash_module()
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(16, 128, 64), jnp.bfloat16)
               for _ in range(3))
    out, lse = jax.jit(lambda q, k, v: mod._pallas_forward(
        q, k, v, False, 0.125, 128, 128, interpret=True))(q, k, v)
    ref, ref_lse = mod._scan_forward(q, k, v, False, 0.125, 128)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-2)


def test_forward_operands_are_held_to_hbm_inside_a_compiled_program_only():
    """Traced for the chip (no interpreter), the forward pins Q, K and V to
    HBM; an eager call must not try to: the constraint is no eager
    operation (it raised on the v5e in the model's first eager forward),
    so the call gets as far as this backend's own refusal of Mosaic."""
    mod = _flash_module()
    q = jnp.ones((8, 128, 64), jnp.bfloat16)

    def forward(q):
        return mod._pallas_forward(q, q, q, False, 0.125, 128, 128)

    assert str(jax.make_jaxpr(forward)(q)).count(
        "with_memory_space_constraint") == 3
    with pytest.raises(ValueError, match="interpret mode"):
        forward(q)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_through_the_kernel_match_the_scan_path(causal):
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    mod = _flash_module()
    rng = np.random.RandomState(8)
    q, k, v = (jnp.asarray(rng.randn(8, 256, 64), jnp.float32)
               for _ in range(3))

    def grads():
        # the mode is read while tracing: a fresh function each time
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(mod._flash(q, k, v, causal, 0.125) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    with interpret_kernels():
        assert mod._use_pallas(256, 256, 64) is not None
        val, got = grads()
    assert mod._use_pallas(256, 256, 64) is None
    ref_val, want = grads()
    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bh,seq,d,itemsize", [
    (1536, 128, 64, 2),     # BERT-base b128 s128, a chip's rows
    (384, 512, 64, 2),      # BERT-base b32 s512
    (8, 2048, 128, 2),      # chip_smoke's long shape (512-blocks, streamed)
    (7, 128, 64, 2),        # a prime number of rows
    (1536, 128, 64, 4),     # float32 operands
])
def test_rows_per_program_is_a_pure_function_of_the_shapes(
        bh, seq, d, itemsize):
    mod = _flash_module()
    block = mod._pick_block(seq, 512)
    streaming = seq > block
    g = mod._rows_per_program(bh, block, block, d, itemsize, streaming)
    assert g >= 1 and bh % g == 0
    assert mod._program_vmem_bytes(g, block, block, d, itemsize,
                                   streaming) <= mod._VMEM_BUDGET
    assert mod._VMEM_BUDGET <= mod._VMEM_DEFAULT_LIMIT
    # no more rows than give a program its work, unless fewer do not exist
    row_bytes = 4 * block * d * itemsize
    smaller = [x for x in range(1, g) if bh % x == 0 and
               (x % 8 == 0) == (g % 8 == 0)]
    assert all(x * row_bytes < mod._PROGRAM_HBM_BYTES for x in smaller)


def test_rows_per_program_at_the_benchmark_shapes():
    mod = _flash_module()
    assert mod._rows_per_program(1536, 128, 128, 64, 2, False) == 32
    assert mod._rows_per_program(384, 512, 512, 64, 2, False) == 4
    assert mod._rows_per_program(8, 512, 512, 128, 2, True) == 2
    assert mod._rows_per_program(7, 128, 128, 64, 2, False) == 7
    # a block that no budget holds still gets one row, and its limit
    assert mod._rows_per_program(8, 2048, 2048, 128, 2, False) == 1
    assert mod._program_vmem_bytes(1, 2048, 2048, 128, 2, False) \
        > mod._VMEM_BUDGET


@pytest.mark.parametrize("direction,g", [("fwd", 16), ("bwd", 8)])
def test_flash_counters_say_which_path_was_traced(direction, g):
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    mod = _flash_module()
    q = jnp.zeros((16, 128, 64), jnp.float32)

    def trace():
        # the mode is read while tracing: a fresh function each time
        def flash(q):
            return mod._flash(q, q, q, False, 0.125)
        jax.make_jaxpr(flash if direction == "fwd" else
                       jax.grad(lambda q: flash(q).sum()))(q)

    scan, pallas, rows = (f"flash.{direction}.{n}" for n in
                          ("scan", "pallas", "rows_per_program"))
    scan0 = telemetry.value(scan) or 0
    pallas0 = telemetry.value(pallas) or 0
    trace()
    assert telemetry.value(scan) == scan0 + 1
    assert (telemetry.value(pallas) or 0) == pallas0
    telemetry.set_gauge(rows, 0)
    with interpret_kernels():
        trace()
    assert telemetry.value(pallas) == pallas0 + 1
    assert telemetry.value(scan) == scan0 + 1
    assert telemetry.value(rows) == g


# ---------------------------------------------------------------------------
# the backward kernel: dQ, dK, dV from one program of G rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bh,lq,lk,d,dv,rows", [
    pytest.param(16, 128, 128, 64, 64, 8, id="one-pass-G8"),
    pytest.param(1, 128, 128, 64, 64, 1, id="one-pass-G1"),
    pytest.param(8, 256, 256, 64, 64, 8, id="streaming-G8"),
    pytest.param(1, 256, 256, 64, 64, 1, id="streaming-G1"),
    pytest.param(8, 128, 128, 192, 128, 4, id="one-pass-mla-G4"),
    pytest.param(1, 128, 128, 192, 128, 1, id="one-pass-mla-G1"),
    pytest.param(2, 256, 256, 192, 128, 2, id="streaming-mla-G2"),
    pytest.param(1, 256, 256, 192, 128, 1, id="streaming-mla-G1"),
    pytest.param(2, 128, 256, 64, 64, 2, id="lq-under-lk"),
    pytest.param(2, 384, 128, 64, 64, 2, id="lq-over-lk"),
])
def test_pallas_backward_matches_scan_and_naive(bh, lq, lk, d, dv, rows,
                                                causal):
    """The kernel's dQ / dK / dV at 128-blocks (one block: the one-pass
    body; more: the streaming one with its causal skip) against the scan
    from the same residuals and against jax.grad of plain softmax(QK^T)V."""
    from mxnet_tpu import telemetry
    mod = _flash_module()
    rng = np.random.RandomState(bh + lq + d)
    q = jnp.asarray(rng.randn(bh, lq, d), jnp.float32)
    k = jnp.asarray(rng.randn(bh, lk, d), jnp.float32)
    v = jnp.asarray(rng.randn(bh, lk, dv), jnp.float32)
    do = jnp.asarray(rng.randn(bh, lq, dv), jnp.float32)
    scale = d ** -0.5
    out, lse = mod._scan_forward(q, k, v, causal, scale, 128)
    got = mod._pallas_backward(q, k, v, out, lse, do, causal, scale, 128,
                               128, interpret=True)
    assert telemetry.value("flash.bwd.rows_per_program") == rows
    scan = mod._scan_backward(q, k, v, out, lse, do, causal, scale, 128)
    naive = jax.vjp(lambda *a: _naive(*(x[None] for x in a), causal,
                                      scale)[0], q, k, v)[1](do)
    for a, b, c in zip(got, scan, naive):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert float(jnp.abs(c).max()) > 0.1        # a gradient to speak of
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_bf16_under_jit_in_interpret_mode(causal):
    """bf16 operands inside a jit, as the benchmark's CPU rehearsal and
    chip_smoke's run them: results in bf16, the scan's to bf16 rounding."""
    mod = _flash_module()
    rng = np.random.RandomState(9)
    q, k, v, do = (jnp.asarray(rng.randn(8, 256, 64), jnp.bfloat16)
                   for _ in range(4))
    out, lse = mod._scan_forward(q, k, v, causal, 0.125, 128)
    got = jax.jit(lambda *a: mod._pallas_backward(
        *a, causal, 0.125, 128, 128, interpret=True))(q, k, v, out, lse, do)
    want = mod._scan_backward(q, k, v, out, lse, do, causal, 0.125, 128)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=2e-2 * np.abs(b).max())


@pytest.mark.parametrize("bh,seq,d,dv,itemsize", [
    (1536, 128, 64, 64, 2),     # BERT-base b128 s128, a chip's rows
    (384, 512, 64, 64, 2),      # BERT-base b32 s512
    (64, 4096, 192, 128, 2),    # the kanana cell's latent attention
    (8, 2048, 128, 128, 2),     # chip_smoke's long shape
    (7, 128, 64, 64, 2),        # a prime number of rows
    (1536, 128, 64, 64, 4),     # float32 operands
])
def test_backward_rows_per_program_is_a_pure_function_of_the_shapes(
        bh, seq, d, dv, itemsize):
    mod = _flash_module()
    block = mod._pick_block(seq, 512)
    streaming = seq > block
    shape = (block, block, seq, d, itemsize, streaming, dv)
    g = mod._backward_rows_per_program(bh, *shape)
    assert g >= 1 and bh % g == 0
    assert mod._backward_vmem_bytes(g, *shape) <= mod._VMEM_BUDGET
    # the backward holds more a row than the forward, so never more rows
    assert mod._backward_vmem_bytes(g, *shape) > mod._program_vmem_bytes(
        g, block, block, d, itemsize, streaming, dv)
    assert g <= mod._rows_per_program(bh, block, block, d, itemsize,
                                      streaming, dv)


def test_backward_rows_per_program_at_the_benchmark_shapes():
    mod = _flash_module()
    rows = mod._backward_rows_per_program
    assert rows(1536, 128, 128, 128, 64, 2, False) == 16     # the s128 cells
    assert rows(384, 128, 128, 128, 64, 2, False) == 16      # dp4: as one chip
    assert rows(384, 512, 512, 512, 64, 2, False) == 3       # s512
    assert rows(64, 512, 512, 4096, 192, 2, True, 128) == 1  # kanana
    assert rows(8, 512, 512, 2048, 128, 2, True) == 1
    # a streamed row's float32 dQ is held whole: at L = 16384 and d = 192
    # one row is over the budget, and _flash_bwd keeps the scan
    assert mod._backward_vmem_bytes(1, 512, 512, 8192, 192, 2, True, 128) \
        <= mod._VMEM_BUDGET
    assert mod._backward_vmem_bytes(1, 512, 512, 16384, 192, 2, True, 128) \
        > mod._VMEM_BUDGET


def test_backward_keeps_the_scan_where_a_row_is_over_the_budget(monkeypatch):
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    mod = _flash_module()
    q = jnp.zeros((1, 256, 64), jnp.float32)

    def trace():
        jax.make_jaxpr(jax.grad(
            lambda q: mod._flash(q, q, q, True, 0.125).sum()))(q)

    monkeypatch.setattr(mod, "_VMEM_BUDGET", 2 ** 19)
    fwd0 = telemetry.value("flash.fwd.pallas") or 0
    scan0 = telemetry.value("flash.bwd.scan") or 0
    with interpret_kernels():
        trace()
    assert telemetry.value("flash.fwd.pallas") == fwd0 + 1
    assert telemetry.value("flash.bwd.scan") == scan0 + 1


def test_grouped_backward_blocks_at_the_cell_shapes():
    """A backward program of a kv head's query heads takes the caller's
    512-blocks at the keye shape (8 heads a kv head, L = 16384, d = 128,
    masked) and the SDAR one (L = 8192, unmasked), the kv head's float32
    dK / dV rows included, within the ceiling; a group of 16 at L = 16384
    halves them; a row so long that its dK / dV alone are over the ceiling
    has none."""
    mod = _flash_module()
    blocks = mod._grouped_backward_blocks
    keye = (16384, 16384, 512, 512, 128, 2, 128, True)
    assert blocks(8, *keye) == (512, 512)
    assert mod._grouped_backward_vmem_bytes(
        8, 512, 512, 16384, 128, 2, 128, True) <= mod._VMEM_GROUPED_CEILING
    assert blocks(8, 8192, 8192, 512, 512, 128, 2, 128, False) == (512, 512)
    assert blocks(16, *keye) == (256, 256)
    assert blocks(8, 2 ** 18, 2 ** 18, 512, 512, 128, 2, 128, True) is None


def test_grouped_backward_repeats_kv_where_a_program_is_over_the_ceiling(
        monkeypatch):
    """Where no program of a kv head's query heads fits, the backward takes
    K / V repeated to the query rows (the parent's program, a head a row)
    and sums dK / dV back: the same gradients as in place."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    mod = _flash_module()
    rng = np.random.RandomState(3)
    q, do = (jnp.asarray(rng.randn(8, 256, 64), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
            for _ in range(2))
    out, lse = mod._scan_forward(q, jnp.repeat(k, 4, axis=0),
                                 jnp.repeat(v, 4, axis=0), True, 0.125, 128)

    def grads():
        return mod._flash_bwd(True, 0.125, None, (q, k, v, out, lse, None),
                              do)
    with interpret_kernels():
        in_place = grads()
        assert telemetry.value("flash.bwd.heads_per_kv_block") == 4
        monkeypatch.setattr(mod, "_VMEM_GROUPED_CEILING", 2 ** 20)
        repeated = grads()
        assert telemetry.value("flash.bwd.heads_per_kv_block") == 1
    for a, b in zip(in_place, repeated):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
