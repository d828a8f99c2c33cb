"""Backward-overlapped gradient communication (ISSUE 5).

Covers the tentpole end to end on the virtual 8-device CPU mesh:

- tape grad-ready hooks fire per variable, in backward order, with the
  FINAL gradient already applied;
- ``zero.BucketPlan(fill_order=...)`` builds backward-ordered buckets
  whose flatten/unflatten bookkeeping survives the permutation;
- the ZeRO-1 trainer plans its buckets in backward order when overlap
  is on, and ``MXTPU_OVERLAP_COMM=0`` restores the PR 3 declaration
  order — with fp32 results BITWISE identical either way (psum_scatter
  sums the same per-chip values element-by-element regardless of bucket
  layout) and the quantized wire modes bounded against the exact psum
  reference;
- the eager ``OverlapScheduler`` dispatches per-bucket kvstore rounds
  from inside ``backward()`` (second cycle onward), reduces exactly
  once per accumulation cycle, and composes with ``gluon.Trainer``;
- the prefetch-depth plumbing (``MXTPU_PREFETCH_DEPTH``, DataLoader /
  estimator.fit kwargs).
"""
import os

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.parallel import make_mesh, OverlapScheduler
from mxnet_tpu.parallel import zero
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

nd = mx.nd

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 virtual devices")


# ----------------------------------------------------------------------
# tape grad-ready hooks
# ----------------------------------------------------------------------

def _chain_net(widths=(16, 8, 4)):
    net = gluon.nn.HybridSequential()
    for w in widths[:-1]:
        net.add(gluon.nn.Dense(w, activation="relu"))
    net.add(gluon.nn.Dense(widths[-1]))
    net.initialize()
    net(nd.zeros((2, 6)))
    return net


def test_grad_ready_hooks_fire_in_backward_order():
    net = _chain_net()
    params = sorted(net.collect_params().items())
    fired = []
    for name, p in params:
        autograd.register_grad_ready_hook(
            p, lambda arr, n=name: fired.append(n))
    x = nd.array(np.random.RandomState(0).randn(2, 6).astype(np.float32))
    with autograd.record():
        net(x).sum().backward()
    assert len(fired) == len(params)
    # layers fire last-to-first: all dense2 params before all dense1
    # params before all dense0 params
    layers = [n.split("_")[0] for n in fired]
    assert max(i for i, l in enumerate(layers) if l == "dense2") < \
        min(i for i, l in enumerate(layers) if l == "dense1")
    assert max(i for i, l in enumerate(layers) if l == "dense1") < \
        min(i for i, l in enumerate(layers) if l == "dense0")


def test_hook_sees_final_grad_and_remove_works():
    w = nd.array(np.ones((3,), np.float32))
    w.attach_grad()
    seen = []
    handle = autograd.register_grad_ready_hook(
        w, lambda arr: seen.append(np.asarray(arr.grad.data).copy()))
    with autograd.record():
        ((w * w).sum() + w.sum()).backward()
    # d(x^2 + x)/dx at x=1 is 3: the hook fired ONCE, after BOTH
    # contributions were accumulated — never on a partial gradient
    assert len(seen) == 1
    np.testing.assert_allclose(seen[0], 3.0, rtol=1e-6)
    handle.remove()
    with autograd.record():
        (w * w).sum().backward()
    assert len(seen) == 1, "removed hook fired again"


def test_hooks_fire_once_per_backward_under_grad_add():
    w = nd.array(np.ones((2,), np.float32))
    w.attach_grad("add")
    count = [0]
    autograd.register_grad_ready_hook(
        w, lambda arr: count.__setitem__(0, count[0] + 1))
    for _ in range(3):
        with autograd.record():
            (w * w).sum().backward()
    # one firing per backward — accumulation-cycle counting is the
    # OverlapScheduler's job, the tape just reports readiness
    assert count[0] == 3
    np.testing.assert_allclose(np.asarray(w.grad), 6.0, rtol=1e-6)


def test_autograd_grad_does_not_fire_hooks():
    w = nd.array(np.ones((2,), np.float32))
    w.attach_grad()
    count = [0]
    autograd.register_grad_ready_hook(
        w, lambda arr: count.__setitem__(0, count[0] + 1))
    with autograd.record():
        y = (w * w).sum()
    g = autograd.grad(y, [w], retain_graph=False)
    np.testing.assert_allclose(np.asarray(g[0].data), 2.0, rtol=1e-6)
    assert count[0] == 0, "autograd.grad leaked a hook firing"


# ----------------------------------------------------------------------
# BucketPlan fill_order / ready_order
# ----------------------------------------------------------------------

def test_bucket_plan_fill_order_roundtrip():
    rng = np.random.RandomState(3)
    shapes = [(13,), (4, 7), (2, 3, 5), (111,), (9,)]
    arrays = [np.asarray(rng.randn(*s), np.float32) for s in shapes]
    order = [4, 2, 0, 3, 1]
    plan = zero.BucketPlan(shapes, dp=8, bound_bytes=64 * 4,
                           fill_order=order)
    assert plan.fill_order == tuple(order)
    assert plan.ready_order == tuple(range(plan.n_buckets))
    # buckets hold param indices in fill order
    flat_fill = [i for idxs in plan.buckets for i in idxs]
    assert flat_fill == order
    # span bookkeeping survives the permutation
    for i in range(len(shapes)):
        b, off, n = plan.param_span(i)
        assert n == plan.sizes[i] and off + n <= plan.lengths[b]
    import jax.numpy as jnp
    flats = plan.flatten([jnp.asarray(a) for a in arrays])
    assert [f.shape[0] for f in flats] == plan.lengths
    back = plan.unflatten(flats, [jnp.asarray(a) for a in arrays])
    for a, b in zip(arrays, back):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_bucket_plan_rejects_bad_fill_order():
    with pytest.raises(mx.MXNetError, match="permutation"):
        zero.BucketPlan([(4,), (4,)], dp=2, fill_order=[0, 0])
    with pytest.raises(mx.MXNetError, match="permutation"):
        zero.BucketPlan([(4,), (4,)], dp=2, fill_order=[1])


def test_bucket_plan_identity_order_matches_default():
    shapes = [(100,), (300,), (50, 2)]
    a = zero.BucketPlan(shapes, dp=8, bound_bytes=400 * 4)
    b = zero.BucketPlan(shapes, dp=8, bound_bytes=400 * 4,
                        fill_order=[0, 1, 2])
    assert a.buckets == b.buckets and a.lengths == b.lengths
    assert a.offsets == b.offsets
    assert a.fill_order is None and b.fill_order == (0, 1, 2)


# ----------------------------------------------------------------------
# in-graph trainer: backward-ordered plan, kill switch, parity
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
               batch=32, env=None):
    old = {k: os.environ.get(k) for k in (env or {})}
    os.environ.update(env or {})
    try:
        net = _build_net()
        tr = DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
            {"learning_rate": 0.1}, mesh=make_mesh({"dp": 8}),
            shard_updates=shard)
        rs = np.random.RandomState(11)
        losses = []
        for _ in range(n_steps):
            x = nd.array(rs.randn(batch, 16).astype(np.float32))
            y = nd.array(rs.randint(0, 8, (batch,)))
            if n_micro is None:
                losses.append(float(tr.step(x, y).asnumpy()))
            else:
                losses.append(float(
                    tr.step_accum(x, y, n_micro=n_micro).asnumpy()))
        params = [p.data().asnumpy()
                  for _, p in sorted(net.collect_params().items())]
        return tr, losses, params
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@needs8
def test_zero1_plan_is_backward_ordered_with_overlap_on():
    tr, _, _ = _run_steps(shard=True, n_steps=1)
    assert tr._overlap_comm
    # sorted params: [d0_bias, d0_weight, d1_bias, d1_weight]; backward
    # readiness puts the LAST layer's params (indices 2, 3) first
    assert tr._plan.fill_order is not None
    assert set(tr._plan.fill_order[:2]) == {2, 3}
    assert tr._plan.ready_order == tuple(range(tr._plan.n_buckets))


@needs8
def test_kill_switch_restores_declaration_order_plan():
    tr, _, _ = _run_steps(shard=True, n_steps=1,
                          env={"MXTPU_OVERLAP_COMM": "0"})
    assert not tr._overlap_comm
    assert tr._plan.fill_order is None       # the PR 3 layout, bitwise
    assert not tr.comm_stats()["overlap_comm"]


@needs8
@pytest.mark.parametrize("n_micro", [None, 4])
def test_overlap_vs_killswitch_bitwise_fp32(n_micro):
    """fp32 wire: overlapped (backward-ordered buckets) and monolithic
    (declaration-ordered) plans must be BITWISE identical — the
    reduce-scatter sums the same eight per-chip values for every
    element whatever bucket it lands in, and the update is elementwise.
    This is the kill-switch acceptance bar: MXTPU_OVERLAP_COMM=0
    reproduces PR 3 exactly, overlap changes scheduling, not values."""
    batch = 64 if n_micro else 32
    _, loss_o, p_o = _run_steps(shard=True, n_micro=n_micro, batch=batch)
    _, loss_k, p_k = _run_steps(shard=True, n_micro=n_micro, batch=batch,
                                env={"MXTPU_OVERLAP_COMM": "0"})
    np.testing.assert_array_equal(loss_o, loss_k)
    for a, b in zip(p_o, p_k):
        np.testing.assert_array_equal(a, b)


@needs8
@pytest.mark.parametrize("n_micro", [None, 4])
def test_overlap_matches_psum_to_float_eps(n_micro):
    batch = 64 if n_micro else 32
    tr, loss_s, p_s = _run_steps(shard=True, n_micro=n_micro, batch=batch)
    assert tr._plan.fill_order is not None
    _, loss_r, p_r = _run_steps(shard=False, n_micro=n_micro, batch=batch)
    np.testing.assert_allclose(loss_s, loss_r, rtol=1e-6)
    for a, b in zip(p_s, p_r):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


@needs8
@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_overlap_quantized_wire_bounded(wire):
    """Quantized wires under the backward-ordered plan: bucket
    composition differs from the declaration-ordered plan, so bitwise
    comparison is meaningless (different rounding groups); the bar is
    the SAME one PR 3 set — measured deviation from the exact psum
    reference stays <= 1e-2 after a step."""
    tr, _, p_q = _run_steps(shard=True, n_steps=1, optimizer="sgd",
                            env={"MXTPU_COMM_DTYPE": wire})
    assert tr._comm_dtype == wire and tr._plan.fill_order is not None
    _, _, p_r = _run_steps(shard=False, n_steps=1, optimizer="sgd")
    worst = 0.0
    for a, b in zip(p_q, p_r):
        scale = max(np.max(np.abs(b)), 1e-6)
        worst = max(worst, float(np.max(np.abs(a - b)) / scale))
    print(f"{wire} wire under overlap: max param rel deviation "
          f"(measured): {worst:.5f}")
    assert 0 < worst <= 1e-2


@needs8
def test_overlap_probe_and_comm_stats_fields():
    tr, _, _ = _run_steps(shard=True, n_steps=1)
    rs = np.random.RandomState(2)
    x = nd.array(rs.randn(32, 16).astype(np.float32))
    y = nd.array(rs.randint(0, 8, (32,)))
    probe = tr.overlap_probe(x, y, iters=2)
    for k in ("overlapped_step_ms", "monolithic_step_ms",
              "compute_only_step_ms"):
        assert probe[k] > 0
    assert probe["exposed_comm_ms"] >= 0
    assert 0 <= probe["overlap_frac"] <= 1
    stats = tr.comm_stats(overlap_stats=probe)
    assert stats["overlap_comm"] is True
    assert stats["exposed_comm_ms"] == probe["exposed_comm_ms"]
    assert stats["overlap_frac"] == probe["overlap_frac"]
    # the probe compiled non-donated variants: trainer state must still
    # be usable for a real step afterwards
    _ = tr.step(x, y)


@needs8
def test_probe_survives_batchnorm_aux_state():
    """Regression: nets with batch-stat aux state (BatchNorm running
    mean/var) WRITE into parameter buffers during tracing; the plan
    probe (jax.eval_shape) and overlap_probe discard their results, so
    without buffer restore the leaked tracers blew up the next
    device_put (UnexpectedTracerError — found with ResNet-50 over a
    dp=8 mesh)."""
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16), gluon.nn.BatchNorm(),
            gluon.nn.Dense(8))
    net.initialize()
    net(nd.zeros((2, 16)))
    tr = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=make_mesh({"dp": 8}),
        shard_updates=True)
    rs = np.random.RandomState(5)
    x = nd.array(rs.randn(32, 16).astype(np.float32))
    y = nd.array(rs.randint(0, 8, (32,)))
    l1 = float(tr.step(x, y).asnumpy())        # plan probe ran here
    probe = tr.overlap_probe(x, y, iters=1)
    assert probe["overlapped_step_ms"] > 0
    l2 = float(tr.step(x, y).asnumpy())        # state still usable
    assert np.isfinite(l1) and np.isfinite(l2)
    # no parameter buffer is left holding a tracer
    import jax.core
    for p in tr._param_objs:
        assert not isinstance(p._data._data, jax.core.Tracer)


# ----------------------------------------------------------------------
# eager OverlapScheduler (gluon.Trainer path)
# ----------------------------------------------------------------------

class _SpyKV:
    """Identity-reduce kvstore spy that records dispatch order."""

    num_workers = 2

    def __init__(self):
        self.calls = []          # list of key-lists, in dispatch order

    def init(self, keys, values):
        pass

    def pushpull(self, keys, grads, out=None, priority=0):
        self.calls.append(list(keys))


def _eager_net():
    net = _chain_net(widths=(16, 8, 4))
    params = [p for _, p in sorted(net.collect_params().items())]
    return net, params


def _backward(net, x):
    with autograd.record():
        net(x).sum().backward()


def test_overlap_scheduler_dispatches_per_bucket_during_backward():
    net, params = _eager_net()
    kv = _SpyKV()
    # tiny bound: one bucket per few params -> several dispatch rounds
    sched = OverlapScheduler(params, kvstore=kv, bound_bytes=4 * 8).install()
    x = nd.array(np.random.RandomState(0).randn(2, 6).astype(np.float32))
    # cycle 1: order discovery — nothing dispatches until finish()
    _backward(net, x)
    assert kv.calls == []
    sched.finish()
    n_buckets = sched.plan.n_buckets
    assert n_buckets >= 2 and len(kv.calls) == n_buckets
    # observed backward order: the LAST layer's params lead the plan
    first_bucket_params = [params[sched._order[k]].name
                           for k in sched.plan.buckets[0]]
    assert all(n.startswith("dense2") for n in first_bucket_params)
    # cycle 2: every bucket goes out DURING backward; finish adds none
    kv.calls.clear()
    _backward(net, x)
    assert len(kv.calls) == n_buckets, \
        "buckets did not dispatch from the grad-ready hooks"
    sched.finish()
    assert len(kv.calls) == n_buckets
    # reduced grads are marked: the batched fallback must skip them
    assert all(p._data._grad_reduced for p in params)
    sched.remove()


def test_overlap_scheduler_reduces_on_final_microbatch_only():
    net, params = _eager_net()
    for p in params:
        p.grad_req = "add"
        p._data.attach_grad("add")
    kv = _SpyKV()
    sched = OverlapScheduler(params, kvstore=kv, n_accum=3).install()
    x = nd.array(np.random.RandomState(1).randn(2, 6).astype(np.float32))
    # cycle 1 (discovery): micro 1..2 silent, finish after micro 3
    for _ in range(3):
        _backward(net, x)
    sched.finish()
    base = len(kv.calls)
    assert base == sched.plan.n_buckets
    # cycle 2: only the THIRD backward may dispatch
    kv.calls.clear()
    _backward(net, x)
    _backward(net, x)
    assert kv.calls == [], "reduced before the final microbatch"
    _backward(net, x)
    assert len(kv.calls) == sched.plan.n_buckets
    sched.finish()
    assert len(kv.calls) == sched.plan.n_buckets
    sched.remove()


def test_trainer_installs_and_finishes_overlap():
    net, params = _eager_net()
    kv = _SpyKV()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01}, kvstore=kv)
    x = nd.array(np.random.RandomState(2).randn(2, 6).astype(np.float32))
    _backward(net, x)
    tr.step(2)
    assert tr._overlap is not None
    assert len(kv.calls) >= 1          # cycle 1 dispatched from finish()
    n1 = len(kv.calls)
    _backward(net, x)
    mid = len(kv.calls)
    tr.step(2)
    # cycle 2 dispatched during backward, before step() ran
    assert mid > n1
    assert len(kv.calls) == mid, "step() re-reduced overlap buckets"


def test_trainer_overlap_kill_switch(monkeypatch):
    monkeypatch.setenv("MXTPU_OVERLAP_COMM", "0")
    net, params = _eager_net()
    kv = _SpyKV()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01}, kvstore=kv)
    x = nd.array(np.random.RandomState(3).randn(2, 6).astype(np.float32))
    _backward(net, x)
    tr.step(2)
    assert tr._overlap is None
    # PR 3 behavior: ONE batched pushpull from step(), nothing earlier
    assert len(kv.calls) == 1
    assert sorted(kv.calls[0]) == list(range(len(params)))


# ----------------------------------------------------------------------
# runtime: latency-hiding-scheduler flag plumbing (MXTPU_LHS)
# ----------------------------------------------------------------------

def test_lhs_flags_apply_and_idempotence():
    from mxnet_tpu import runtime
    flags = runtime.lhs_flags()
    assert any("latency_hiding_scheduler" in f for f in flags)
    env = {"JAX_PLATFORMS": "tpu"}
    out = runtime.apply_lhs_flags(env)
    assert env["XLA_FLAGS"] == out
    for f in flags:
        assert f in env["XLA_FLAGS"]
    # second apply adds nothing (prefix-matched, no duplicates)
    again = runtime.apply_lhs_flags(env)
    assert again == out
    # user flags survive, and a user-set LHS value is NOT overridden
    env2 = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
                         "--xla_tpu_enable_latency_hiding_scheduler=false"}
    runtime.apply_lhs_flags(env2, force=True)
    assert "--xla_force_host_platform_device_count=8" in env2["XLA_FLAGS"]
    assert env2["XLA_FLAGS"].count("latency_hiding_scheduler") == 1


def test_lhs_flags_noop_on_non_tpu_host():
    """The TPU-only gate is load-bearing: CPU/GPU XLA builds FATALLY
    abort on unknown --xla_tpu_* flags, so on a non-TPU host (this CI)
    MXTPU_LHS must leave XLA_FLAGS alone."""
    from mxnet_tpu import runtime
    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--keep=1"}
    assert runtime.apply_lhs_flags(env) == "--keep=1"
    assert env["XLA_FLAGS"] == "--keep=1"
    env = {"JAX_PLATFORMS": "cpu"}
    assert runtime.apply_lhs_flags(env) == ""
    assert "XLA_FLAGS" not in env


def test_lhs_env_gate_at_import():
    """MXTPU_LHS=1 on a cpu-pinned process: import must survive (the
    gate keeps the TPU-only flags out) and XLA_FLAGS stays clean."""
    import subprocess, sys
    code = ("import os; os.environ['MXTPU_LHS']='1'; "
            "import mxnet_tpu; "
            "assert 'latency_hiding_scheduler' not in "
            "os.environ.get('XLA_FLAGS', ''); "
            "import jax; jax.numpy.zeros(1); print('ok')")
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert res.returncode == 0 and "ok" in res.stdout, res.stderr


# ----------------------------------------------------------------------
# prefetch-depth plumbing (satellite)
# ----------------------------------------------------------------------

def test_device_prefetcher_env_depth(monkeypatch):
    from mxnet_tpu.io import DevicePrefetcher, default_prefetch_depth
    monkeypatch.setenv("MXTPU_PREFETCH_DEPTH", "5")
    assert default_prefetch_depth() == 5
    pf = DevicePrefetcher(iter([]))
    assert pf._depth == 5
    pf.close()
    assert DevicePrefetcher(iter([]), depth=3)._depth == 3
    monkeypatch.setenv("MXTPU_PREFETCH_DEPTH", "0")
    with pytest.raises(mx.MXNetError, match="PREFETCH_DEPTH"):
        default_prefetch_depth()


def test_dataloader_prefetch_depth_kwarg(monkeypatch):
    import mxnet_tpu.io as mio
    seen = {}
    real = mio.DevicePrefetcher

    class Recorder(real):
        def __init__(self, source, depth=None, **kw):
            seen["depth"] = depth
            super().__init__(source, depth=depth, **kw)

    monkeypatch.setattr(mio, "DevicePrefetcher", Recorder)
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    ds = ArrayDataset(nd.arange(16).reshape((8, 2)), nd.arange(8))
    loader = DataLoader(ds, batch_size=4, prefetch_to_device=True,
                        prefetch_depth=4)
    batches = list(loader)
    assert seen["depth"] == 4 and len(batches) == 2


def test_estimator_fit_prefetch_depth():
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    net = gluon.nn.Dense(2)
    net.initialize()
    net(nd.zeros((2, 3)))
    rs = np.random.RandomState(0)
    data = [(nd.array(rs.randn(4, 3).astype(np.float32)),
             nd.array(rs.randint(0, 2, (4,))))
            for _ in range(3)]
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    train_metrics=[mx.metric.Loss()])
    est.fit(data, epochs=2, prefetch_depth=3)
    assert est.current_epoch == 2 and est.global_step == 6
