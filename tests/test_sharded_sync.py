"""Sharded gradient sync (ISSUE 3): reduce-scatter + ZeRO-1 parity.

On the virtual 8-device CPU mesh: the bucketed reduce-scatter ->
sharded-update -> all-gather pipeline must match the legacy full-psum
path to float eps in fp32 (plain step AND the step_accum scan path);
the quantized wire modes (bf16 / stochastic-rounding int8) report their
MEASURED per-bucket error; the eager fused kvstore pushpull matches the
in-graph traced path and the push-then-pull composition bit-for-bit.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.parallel import make_mesh, mesh_scope
from jax import shard_map
from mxnet_tpu.parallel import zero
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

nd = mx.nd

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 virtual devices")


# ----------------------------------------------------------------------
# BucketPlan — host-side coalescing
# ----------------------------------------------------------------------

def test_bucket_plan_bounds_and_padding():
    shapes = [(100,), (300,), (50, 2), (1000,), (7,)]
    plan = zero.BucketPlan(shapes, dp=8, bound_bytes=400 * 4)
    # fill order respected, no bucket exceeds the bound except a single
    # oversized tensor, every padded length divides dp
    for b, idxs in enumerate(plan.buckets):
        payload = sum(plan.sizes[i] for i in idxs)
        assert len(idxs) == 1 or payload <= 400
        assert plan.lengths[b] % 8 == 0
        assert 0 <= plan.lengths[b] - payload < 8
    # every param lands in exactly one bucket at a consistent offset
    seen = set()
    for i, (b, off) in enumerate(plan.offsets):
        assert off + plan.sizes[i] <= plan.lengths[b]
        seen.add(i)
    assert seen == set(range(len(shapes)))
    # the oversized (1000,) tensor got its own bucket
    assert [plan.offsets[3][0]] == [b for b, idxs in
                                    enumerate(plan.buckets) if 3 in idxs]


def test_bucket_plan_flatten_roundtrip():
    rng = np.random.RandomState(0)
    shapes = [(13,), (4, 7), (2, 3, 5), (111,)]
    arrays = [jnp.asarray(rng.randn(*s).astype(np.float32))
              for s in shapes]
    plan = zero.BucketPlan(shapes, dp=8, bound_bytes=64 * 4)
    flats = plan.flatten(arrays)
    assert [f.shape[0] for f in flats] == plan.lengths
    back = plan.unflatten(flats, arrays)
    for a, b in zip(arrays, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucket_plan_wire_accounting():
    plan = zero.BucketPlan([(100,), (200,)], dp=4, bound_bytes=1 << 20)
    total = sum(plan.lengths)
    assert plan.grad_bytes_fp32() == 4 * total
    assert plan.wire_bytes("fp32") == 4 * total
    assert plan.wire_bytes("bf16") == 2 * total
    # int8 pays 1 B/elem + one f32 scale per bucket
    assert plan.wire_bytes("int8") == total + 4 * plan.n_buckets


def test_comm_dtype_env(monkeypatch):
    monkeypatch.delenv("MXTPU_COMM_DTYPE", raising=False)
    assert zero.comm_dtype() == "fp32"
    monkeypatch.setenv("MXTPU_COMM_DTYPE", "bfloat16")
    assert zero.comm_dtype() == "bf16"
    monkeypatch.setenv("MXTPU_COMM_DTYPE", "int8")
    assert zero.comm_dtype() == "int8"
    monkeypatch.setenv("MXTPU_COMM_DTYPE", "fp8")
    with pytest.raises(mx.MXNetError, match="MXTPU_COMM_DTYPE"):
        zero.comm_dtype()


# ----------------------------------------------------------------------
# reduce_scatter_bucket vs psum — the collective itself
# ----------------------------------------------------------------------

def _gather_rs(x, mode):
    """Run reduce_scatter_bucket under shard_map on the dp=8 mesh and
    all-gather the shards back: every row of the result is the mean
    bucket as the sharded pipeline computed it."""
    mesh = make_mesh({"dp": 8})

    def body(xs, key):
        shard = zero.reduce_scatter_bucket(xs.reshape(-1), key[0], 8, mode)
        return jax.lax.all_gather(shard, "dp", tiled=True)[None]

    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    return np.asarray(shard_map(
        body, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=P("dp"), check_vma=False)(x, keys))


@needs8
def test_reduce_scatter_fp32_matches_mean_to_eps():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 1, 512).astype(np.float32))
    out = _gather_rs(x, "fp32")
    expect = np.asarray(x).mean(axis=0)
    for row in out:
        np.testing.assert_allclose(row, expect[0], rtol=1e-6, atol=1e-7)


@needs8
@pytest.mark.parametrize("mode,tol", [("bf16", 1e-2), ("int8", 1e-2)])
def test_quantized_reduce_scatter_measured_error(mode, tol):
    """Acceptance criterion: the quantized wire's per-bucket max
    relative error is MEASURED against the exact fp32 mean and stays
    <= 1e-2.  Gradients are data-parallel-shaped (shared signal + small
    per-chip noise), so the denominator is a real gradient magnitude."""
    rng = np.random.RandomState(2)
    base = rng.randn(1, 1, 2048).astype(np.float32)
    x = jnp.asarray(base + 0.05 * rng.randn(8, 1, 2048).astype(np.float32))
    out = _gather_rs(x, mode)
    expect = np.asarray(x).mean(axis=0)
    denom = np.max(np.abs(expect))
    err = max(float(np.max(np.abs(row - expect[0])) / denom)
              for row in out)
    print(f"{mode} per-bucket max rel err (measured): {err:.5f}")
    assert err <= tol, f"{mode} wire error {err} above {tol}"
    assert err > 0, "quantized wire produced exact values (mode not used?)"


def test_int8_roundtrip_unbiased_and_bounded():
    rng = np.random.RandomState(4)
    flat = jnp.asarray(rng.randn(4096).astype(np.float32))
    err = float(zero.int8_roundtrip_error(flat, jax.random.PRNGKey(0)))
    # one stochastic-rounding step errs by at most 1 code ~= max|x|/127
    assert err <= 1.5 / 127
    # unbiased: averaging many independent roundings converges on x.
    # The per-element max deviation shrinks as 1/sqrt(K); the MEAN
    # signed error (averaged over elements too) isolates systematic
    # bias, which must sit far inside one code step.
    keys = jax.random.split(jax.random.PRNGKey(1), 64)
    deq = jnp.mean(jnp.stack([
        zero.dequantize_int8(*zero.quantize_int8(flat, k))
        for k in keys]), axis=0)
    scale = float(jnp.max(jnp.abs(flat))) / 127.0
    bias = float(jnp.abs(jnp.mean(deq - flat)))
    assert bias < 0.02 * scale, f"stochastic rounding biased: {bias}"
    assert float(jnp.max(jnp.abs(deq - flat))) < scale


# ----------------------------------------------------------------------
# trainer parity: sharded (ZeRO-1) step vs the legacy psum step
# ----------------------------------------------------------------------

def _build_net(in_dim=16, hidden=32, classes=8):
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, activation="relu"),
            gluon.nn.Dense(classes))
    net.initialize()
    net(nd.zeros((2, in_dim)))
    rs = np.random.RandomState(7)
    for _, p in sorted(net.collect_params().items()):
        p.set_data(nd.array(rs.randn(*p.shape).astype(np.float32)))
    return net


def _run_steps(shard, n_steps=3, n_micro=None, optimizer="adam",
               batch=32, bucket_mb=None):
    if bucket_mb is not None:
        os.environ["MXTPU_COMM_BUCKET_MB"] = bucket_mb
    try:
        net = _build_net()
        mesh = make_mesh({"dp": 8})
        tr = DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
            {"learning_rate": 0.1}, mesh=mesh, shard_updates=shard)
        rs = np.random.RandomState(11)
        losses = []
        for i in range(n_steps):
            x = nd.array(rs.randn(batch, 16).astype(np.float32))
            y = nd.array(rs.randint(0, 8, (batch,)))
            if n_micro is None:
                losses.append(float(tr.step(x, y).asnumpy()))
            else:
                losses.append(float(
                    tr.step_accum(x, y, n_micro=n_micro).asnumpy()))
        # positional (sorted-key) order: gluon auto-naming counters are
        # global, so NAMES differ between two builds in one process
        params = [p.data().asnumpy()
                  for _, p in sorted(net.collect_params().items())]
        return tr, losses, params
    finally:
        if bucket_mb is not None:
            del os.environ["MXTPU_COMM_BUCKET_MB"]


@needs8
def test_sharded_step_matches_psum_to_float_eps():
    """The tentpole acceptance bar: fp32 RS+AG+sharded-update == full
    psum + replicated update to float eps, multi-step, Adam."""
    tr_s, loss_s, p_s = _run_steps(shard=True)
    tr_r, loss_r, p_r = _run_steps(shard=False)
    assert tr_s._zero1_active() and not tr_r._zero1_active()
    np.testing.assert_allclose(loss_s, loss_r, rtol=1e-6)
    for a, b in zip(p_s, p_r):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


@needs8
def test_sharded_step_accum_matches_psum():
    """The in-graph microbatch scan under shard_map: same numerics as
    the replicated accumulating step."""
    _, loss_s, p_s = _run_steps(shard=True, n_micro=4, batch=64)
    _, loss_r, p_r = _run_steps(shard=False, n_micro=4, batch=64)
    np.testing.assert_allclose(loss_s, loss_r, rtol=1e-6)
    for a, b in zip(p_s, p_r):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


@needs8
def test_sharded_multi_bucket_parity():
    """A tiny MXTPU_COMM_BUCKET_MB forces several buckets; parity must
    hold across bucket boundaries (offset/padding bookkeeping)."""
    tr_s, loss_s, p_s = _run_steps(shard=True, bucket_mb="0.001")
    _, loss_r, p_r = _run_steps(shard=False)
    assert tr_s._plan.n_buckets >= 2, "bound did not split the params"
    np.testing.assert_allclose(loss_s, loss_r, rtol=1e-6)
    for a, b in zip(p_s, p_r):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


@needs8
def test_int8_mode_trainer_measured_error(monkeypatch):
    """MXTPU_COMM_DTYPE=int8: the step runs on the quantized wire; the
    parameter deviation from the exact-psum reference is MEASURED and
    reported, bounded by lr * (quantization step) per update."""
    monkeypatch.setenv("MXTPU_COMM_DTYPE", "int8")
    tr_q, _, p_q = _run_steps(shard=True, n_steps=1, optimizer="sgd")
    monkeypatch.delenv("MXTPU_COMM_DTYPE")
    _, _, p_r = _run_steps(shard=False, n_steps=1, optimizer="sgd")
    assert tr_q._comm_dtype == "int8"
    assert tr_q.comm_stats()["wire_dtype"] == "int8"
    worst = 0.0
    for a, b in zip(p_q, p_r):
        scale = max(np.max(np.abs(b)), 1e-6)
        worst = max(worst, float(np.max(np.abs(a - b)) / scale))
    print(f"int8 wire: max param rel deviation after 1 step "
          f"(measured): {worst:.5f}")
    assert 0 < worst <= 1e-2


@needs8
def test_kill_switch_restores_psum_path(monkeypatch):
    monkeypatch.setenv("MXTPU_SHARDED_SYNC", "0")
    tr, _, p_k = _run_steps(shard=True, n_steps=1)
    assert not tr._zero1_active()
    assert tr._jitted is not None and not tr._jit_zero1_cache
    monkeypatch.delenv("MXTPU_SHARDED_SYNC")
    _, _, p_r = _run_steps(shard=False, n_steps=1)
    for a, b in zip(p_k, p_r):
        np.testing.assert_array_equal(a, b)


@needs8
def test_zero1_state_shards_and_comm_stats_measure():
    """Acceptance criterion: optimizer-state bytes per chip shrink by
    (N-1)/N on the 8-device mesh, and the comm block's collective time
    is measured (not assumed) via the RS+AG-only probe program."""
    tr, _, _ = _run_steps(shard=True, n_steps=1)
    stats = tr.comm_stats(measure=True, iters=3, step_ms=50.0)
    assert stats["zero1"] and stats["dp"] == 8
    # the VECTOR state (Adam m/v) shards exactly 1/8 per chip; the
    # per-bucket scalar step counters replicate, so the overall ratio
    # approaches 1/8 rather than hitting it exactly
    ratio = stats["state_bytes_per_chip"] / stats["state_bytes_replicated"]
    assert abs(ratio - 1 / 8) < 0.02, ratio
    assert stats["bytes_reduced_per_step"] > 0
    assert stats["bytes_gathered_per_step"] == stats["grad_bytes_fp32"]
    assert stats["collective_ms"] > 0
    # GB/s rounds to 2 decimals: a few-KB CPU probe legitimately reads
    # 0.0; the field just has to be present and sane
    assert stats["est_ici_gb_s"] >= 0
    assert 0 <= stats["overlap_efficiency"] <= 1


@needs8
def test_lamb_falls_back_to_psum():
    """Non-elementwise rules (per-param norms) must keep the replicated
    path rather than shard a norm across chips."""
    net = _build_net()
    mesh = make_mesh({"dp": 8})
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "lamb", {"learning_rate": 0.01},
                             mesh=mesh, shard_updates=True)
    x = nd.array(np.random.RandomState(0).randn(16, 16).astype(np.float32))
    y = nd.array(np.random.RandomState(1).randint(0, 8, (16,)))
    tr.step(x, y)
    assert not tr._zero1_active()
    assert not tr._jit_zero1_cache


@needs8
def test_sharded_batch_divisibility_error():
    net = _build_net()
    mesh = make_mesh({"dp": 8})
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "sgd", {"learning_rate": 0.1},
                             mesh=mesh, shard_updates=True)
    x = nd.array(np.zeros((12, 16), np.float32))   # 12 % 8 != 0
    y = nd.array(np.zeros((12,), np.float32))
    with pytest.raises(mx.MXNetError, match="divisible by dp"):
        tr.step(x, y)


# ----------------------------------------------------------------------
# gluon.Trainer: the eager-side weight-update sharding
# ----------------------------------------------------------------------

def _gluon_train(under_mesh, n_steps=2):
    import contextlib
    net = _build_net()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.1})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(21)
    ctx = mesh_scope(make_mesh({"dp": 8})) if under_mesh \
        else contextlib.nullcontext()
    with ctx:
        for _ in range(n_steps):
            x = nd.array(rs.randn(16, 16).astype(np.float32))
            y = nd.array(rs.randint(0, 8, (16,)))
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            tr.step(1)
    params = [p.data().asnumpy()
              for _, p in sorted(net.collect_params().items())]
    return tr, params


@needs8
def test_gluon_trainer_sharded_update_matches_replicated():
    """Under an ambient dp mesh the fused group update computes each
    param's new value on a 1/8 shard (state resident sharded); numerics
    must match the no-mesh replicated update to float eps."""
    tr_s, p_s = _gluon_train(under_mesh=True)
    _, p_r = _gluon_train(under_mesh=False)
    for a, b in zip(p_s, p_r):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
    # optimizer state stayed resident dp-sharded across steps (the
    # leaves are (m, v) NDArray tuples wrapping sharded jax.Arrays)
    from jax.sharding import NamedSharding
    sharded = 0
    for st in tr_s._states.values():
        for v in (st if isinstance(st, (tuple, list)) else [st]):
            sh = getattr(getattr(v, "_data", None), "sharding", None)
            if isinstance(sh, NamedSharding) and sh.spec and \
                    sh.spec[0] == "dp":
                sharded += 1
    assert sharded > 0, "no optimizer-state leaf ended up dp-sharded"


@needs8
def test_gluon_trainer_sharded_kill_switch(monkeypatch):
    monkeypatch.setenv("MXTPU_SHARDED_SYNC", "0")
    tr, p_k = _gluon_train(under_mesh=True)
    assert tr._sharded_update_mesh() is None
    monkeypatch.delenv("MXTPU_SHARDED_SYNC")
    _, p_r = _gluon_train(under_mesh=False)
    for a, b in zip(p_k, p_r):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# kvstore: fused eager pushpull vs in-graph vs push-then-pull
# ----------------------------------------------------------------------

def _per_device_grads():
    rng = np.random.RandomState(5)
    return rng.randn(8, 4).astype(np.float32)


@needs8
def test_eager_vs_ingraph_pushpull_parity():
    """The same 8 per-chip gradients through (a) the fused eager
    pushpull (ONE jitted reduce) and (b) the in-graph traced pushpull
    (psum inside shard_map) must agree bit-for-bit."""
    g = _per_device_grads()

    kv_e = mx.kv.create("tpu_sync")
    kv_e.init(0, nd.zeros((4,)))
    out = nd.zeros((4,))
    kv_e.pushpull(0, [nd.array(row) for row in g], out=out)
    eager = out.asnumpy()

    mesh = make_mesh({"dp": 8})
    kv_t = mx.kv.create("tpu_sync")
    kv_t.init(0, nd.zeros((4,)))
    from mxnet_tpu.ndarray.ndarray import NDArray

    def step(x):
        gn = NDArray(x[0])
        kv_t.pushpull(0, gn, out=gn)
        return gn.data[None]

    y = np.asarray(jax.jit(shard_map(
        step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(
            jnp.asarray(g)))
    expect = g.sum(axis=0)
    np.testing.assert_array_equal(eager, expect)
    for row in y:
        np.testing.assert_allclose(row, expect, rtol=1e-6)


def test_fused_pushpull_matches_push_then_pull():
    g = _per_device_grads()
    kv_a = mx.kv.create("tpu_sync")
    kv_a.init("w", nd.zeros((4,)))
    out_a = nd.zeros((4,))
    kv_a.pushpull("w", [nd.array(r) for r in g], out=out_a)

    kv_b = mx.kv.create("tpu_sync")
    kv_b.init("w", nd.zeros((4,)))
    out_b = nd.zeros((4,))
    kv_b.push("w", [nd.array(r) for r in g])
    kv_b.pull("w", out=out_b)
    np.testing.assert_array_equal(out_a.asnumpy(), out_b.asnumpy())
    # the store itself holds the reduced value (pull-after-pushpull)
    again = nd.zeros((4,))
    kv_a.pull("w", out=again)
    np.testing.assert_array_equal(again.asnumpy(), out_b.asnumpy())


def test_fused_pushpull_multi_key_and_out_default():
    kv = mx.kv.create("tpu_sync")
    kv.init(["a", "b"], [nd.zeros((2,)), nd.zeros((3,))])
    va, vb = nd.ones((2,)) * 2, nd.ones((3,)) * 3
    kv.pushpull(["a", "b"], [va, vb])       # out=None -> values updated
    np.testing.assert_array_equal(va.asnumpy(), np.full(2, 2.0))
    np.testing.assert_array_equal(vb.asnumpy(), np.full(3, 3.0))
    va2 = nd.zeros((2,))
    kv.pull("a", out=va2)
    np.testing.assert_array_equal(va2.asnumpy(), np.full(2, 2.0))


@needs8
def test_pushpull_scatter_ingraph_shards_the_sum():
    """The reduce-scatter-aware in-graph path: inside shard_map each
    chip receives its contiguous 1/8 shard of the cross-chip sum;
    gathering the shards reproduces the full psum result."""
    g = np.random.RandomState(6).randn(8, 16).astype(np.float32)
    mesh = make_mesh({"dp": 8})
    kv = mx.kv.create("tpu_sync")
    kv.init(0, nd.zeros((16,)))
    from mxnet_tpu.ndarray.ndarray import NDArray

    def step(x):
        shard = kv.pushpull_scatter(0, NDArray(x[0]))
        return shard.data[None]

    y = np.asarray(jax.jit(shard_map(
        step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(
            jnp.asarray(g)))
    assert y.shape == (8, 2)            # 16 elems / 8 chips per shard
    np.testing.assert_allclose(y.reshape(-1), g.sum(axis=0), rtol=1e-6)
    # the lowered program must contain a reduce-scatter, not a psum
    jaxpr = str(jax.make_jaxpr(shard_map(
        step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(
            jnp.asarray(g)))
    assert "psum_scatter" in jaxpr or "reduce_scatter" in jaxpr


@needs8
def test_pushpull_scatter_eager_path_unchanged():
    """Outside a trace there is no mesh axis: the eager call falls back
    to the fused full pushpull (full reduced value, store updated)."""
    g = _per_device_grads()
    kv = mx.kv.create("tpu_sync")
    kv.init(0, nd.zeros((4,)))
    out = kv.pushpull_scatter(0, [nd.array(r) for r in g])
    np.testing.assert_array_equal(out.asnumpy(), g.sum(axis=0))
    stored = nd.zeros((4,))
    kv.pull(0, out=stored)
    np.testing.assert_array_equal(stored.asnumpy(), g.sum(axis=0))


@needs8
def test_pushpull_scatter_indivisible_raises():
    mesh = make_mesh({"dp": 8})
    kv = mx.kv.create("tpu_sync")
    kv.init(0, nd.zeros((5,)))
    from mxnet_tpu.ndarray.ndarray import NDArray

    def step(x):
        return kv.pushpull_scatter(0, NDArray(x[0])).data[None]

    with pytest.raises(mx.MXNetError, match="not divisible"):
        jax.make_jaxpr(shard_map(
            step, mesh=mesh, in_specs=P("dp"), out_specs=P(None)))(
                jnp.ones((8, 5), jnp.float32))


def test_fused_pushpull_updater_falls_back():
    """update-on-kvstore is a host-side path; the fused reduce must not
    bypass the updater."""
    kv = mx.kv.create("tpu_sync")
    kv.init(3, nd.ones((4,)))

    def update(key, grad, weight):
        weight -= 0.5 * grad

    kv._set_updater(update)
    out = nd.zeros((4,))
    kv.pushpull(3, nd.ones((4,)), out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.5)


# ----------------------------------------------------------------------
# all_reduce_gradients: one implementation, reduce-once per accum cycle
# ----------------------------------------------------------------------

class _CountingKV:
    """pushpull spy: identity reduce, counts wire rounds."""

    def __init__(self):
        self.calls = 0
        self.keys_seen = []

    def pushpull(self, keys, grads, out=None, priority=0):
        self.calls += 1
        self.keys_seen.append(list(keys))


def test_all_reduce_gradients_reduces_once_per_accum_cycle():
    """The grad_req='add' contract (ISSUE 3 satellite): the reference's
    documented split flow — allreduce_grads() then step() — must not
    double-count the cross-worker sum, and a fresh backward (or
    zero_grad) re-arms the reduction."""
    from mxnet_tpu import autograd
    from mxnet_tpu.parallel import all_reduce_gradients

    net = gluon.nn.Dense(4)
    net.initialize()
    net(nd.zeros((2, 8)))
    params = list(net.collect_params().values())
    for p in params:
        p.grad_req = "add"
    x = nd.array(np.random.RandomState(0).randn(2, 8).astype(np.float32))

    kv = _CountingKV()
    with autograd.record():
        net(x).sum().backward()
    all_reduce_gradients(params, kvstore=kv)
    assert kv.calls == 1 and len(kv.keys_seen[0]) == len(params)
    # second call in the same cycle: nothing fresh to reduce
    all_reduce_gradients(params, kvstore=kv)
    assert kv.calls == 1
    # accumulating another backward re-arms every gradient
    with autograd.record():
        net(x).sum().backward()
    all_reduce_gradients(params, kvstore=kv)
    assert kv.calls == 2
    # zero_grad starts a new cycle too
    for p in params:
        p.zero_grad()
    with autograd.record():
        net(x).sum().backward()
    all_reduce_gradients(params, kvstore=kv)
    assert kv.calls == 3


def test_trainer_allreduce_grads_shares_the_implementation():
    """Trainer._allreduce_grads must be the same code path (the two
    used to be drifting copies)."""
    import inspect
    from mxnet_tpu.gluon.trainer import Trainer
    src = inspect.getsource(Trainer._all_reduce_grads)
    assert "all_reduce_gradients" in src


# ----------------------------------------------------------------------
# the `comm` block schema (ISSUE 3): regression-tested on CPU — the
# sharded-sync observability must ship with every field present (zeros
# are fine) so a TPU round can't discover a broken schema
# ----------------------------------------------------------------------

_COMM_KEYS = {
    "zero1", "dp", "wire_dtype", "buckets", "bucket_mb",
    "bytes_reduced_per_step", "bytes_gathered_per_step",
    "grad_bytes_fp32", "collective_ms", "est_ici_gb_s",
    "overlap_efficiency", "overlap_comm", "exposed_comm_ms",
    "overlap_frac", "state_bytes_per_chip",
    "state_bytes_replicated",
}


def test_comm_block_schema_is_stable():
    from mxnet_tpu.parallel import zero
    blk = zero.comm_block()
    assert set(blk) == _COMM_KEYS
    # static accounting defaults are zeros / fp32 — the CPU shape
    assert blk["dp"] == 1 and not blk["zero1"]
    assert blk["wire_dtype"] == "fp32"
    # MEASURED fields are null when nothing measured (ISSUE 6 honesty
    # fix: a CPU zero must not read as "measured: comm is free")
    for k in ("collective_ms", "est_ici_gb_s", "overlap_efficiency",
              "exposed_comm_ms", "overlap_frac"):
        assert blk[k] is None, k
    assert blk["overlap_comm"] is False
    # measured values still round-trip as numbers
    blk2 = zero.comm_block(collective_ms=1.8444, overlap_frac=0.51234)
    assert blk2["collective_ms"] == 1.844
    assert blk2["overlap_frac"] == 0.5123
    assert json.loads(json.dumps(blk)) == blk
