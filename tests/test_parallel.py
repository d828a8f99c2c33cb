"""Mesh / collective / parallel-training semantics on the virtual 8-device
CPU mesh (SURVEY.md §4 technique 3: the reference faked clusters with local
processes; we fake a pod with host devices)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.parallel import make_mesh, mesh_scope, current_mesh

nd = mx.nd

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 virtual devices")


@needs8
def test_make_mesh_shapes():
    mesh = make_mesh({"dp": 4, "tp": 2})
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    with mesh_scope(mesh):
        assert current_mesh() is mesh
    assert current_mesh() is None or current_mesh() is not mesh


@needs8
def test_psum_over_mesh():
    mesh = make_mesh({"dp": 8})
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def f(x):
        return jax.lax.psum(x, "dp")

    x = jnp.arange(8.0)
    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


@needs8
def test_data_parallel_trainer_matches_single_device():
    """The fused dp step must produce the same weights as plain Trainer."""
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    def build():
        np.random.seed(0)
        net = gluon.nn.Dense(4)
        net.initialize()
        net(nd.zeros((2, 8)))       # materialize params
        for p in net.collect_params().values():
            p.set_data(nd.array(np.random.RandomState(1)
                                .randn(*p.shape).astype(np.float32)))
        return net

    x = nd.array(np.random.RandomState(2).randn(8, 8).astype(np.float32))
    y = nd.array(np.random.RandomState(3).randint(0, 4, (8,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # single-device reference
    ref = build()
    tr = gluon.Trainer(ref.collect_params(), "sgd", {"learning_rate": 0.1})
    with autograd.record():
        loss = loss_fn(ref(x), y).mean()
    loss.backward()
    tr.step(1)      # rescale 1: loss already meaned

    # 8-way dp fused step
    net = build()
    mesh = make_mesh({"dp": 8})
    with mesh_scope(mesh):
        dpt = DataParallelTrainer(net, loss_fn, "sgd",
                                  {"learning_rate": 0.1}, mesh=mesh)
        dpt.step(x, y)

    for (_, pr), (_, pn) in zip(sorted(ref.collect_params().items()),
                                sorted(net.collect_params().items())):
        np.testing.assert_allclose(pr.data().asnumpy(),
                                   pn.data().asnumpy(), rtol=1e-4,
                                   atol=1e-5)


@needs8
def test_tensor_parallel_dense_matches_serial():
    from mxnet_tpu.parallel.tensor_parallel import ParallelDense
    mesh = make_mesh({"dp": 1, "tp": 8})
    np.random.seed(0)
    x = nd.array(np.random.randn(4, 16).astype(np.float32))

    serial = gluon.nn.Dense(32)
    serial.initialize()
    serial(x)
    w = serial.weight.data().asnumpy()
    b = serial.bias.data().asnumpy()

    with mesh_scope(mesh):
        par = ParallelDense(32, parallel_mode="column")
        par.initialize()
        par(x)
        par.weight.set_data(nd.array(w))
        par.bias.set_data(nd.array(b))
        out = par(x).asnumpy()
    np.testing.assert_allclose(out, serial(x).asnumpy(), rtol=1e-4,
                               atol=1e-5)


@needs8
def test_split_and_load():
    parts = gluon.utils.split_and_load(nd.arange(8), [mx.cpu(i)
                                                      for i in range(4)])
    assert len(parts) == 4
    np.testing.assert_allclose(parts[0].asnumpy(), [0, 1])


@needs8
def test_sync_batchnorm_cross_device_stats():
    """SyncBatchNorm must normalize with GLOBAL batch stats under dp."""
    from mxnet_tpu.gluon.contrib.nn import SyncBatchNorm
    sbn = SyncBatchNorm(in_channels=2)
    sbn.initialize()
    x = nd.array(np.random.RandomState(0).randn(8, 2, 4, 4)
                 .astype(np.float32))
    from mxnet_tpu import _tape
    prev = _tape.set_training(True)
    try:
        out = sbn(x).asnumpy()
    finally:
        _tape.set_training(prev)
    xn = x.asnumpy()
    mean = xn.mean((0, 2, 3), keepdims=True)
    var = xn.var((0, 2, 3), keepdims=True)
    ref = (xn - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)


@needs8
def test_ps_embedding_store():
    """Host parameter server for sparse embeddings (parallel/ps.py)."""
    from mxnet_tpu.parallel import ps as ps_mod
    names = [n for n in dir(ps_mod) if not n.startswith("_")]
    assert names, "ps module must export something"


@needs8
@pytest.mark.parametrize("opt,params", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-3}),
    ("adamw", {"learning_rate": 0.01, "wd": 1e-2}),
    ("lamb", {"learning_rate": 0.01, "wd": 1e-2}),
    ("lars", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3}),
    ("rmsprop", {"learning_rate": 0.01, "wd": 1e-3}),
])
def test_fused_trainer_matches_eager_optimizer(opt, params):
    """Fused and eager paths share one kernel (optimizer.fused_rule):
    3 steps of DataParallelTrainer must equal 3 steps of gluon.Trainer
    (VERDICT r1 #6 parity contract)."""
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    def build():
        net = gluon.nn.Dense(4)
        net.initialize()
        net(nd.zeros((2, 8)))
        for p in net.collect_params().values():
            p.set_data(nd.array(np.random.RandomState(1)
                                .randn(*p.shape).astype(np.float32) * 0.1))
        return net

    rs = np.random.RandomState(2)
    xs = [nd.array(rs.randn(8, 8).astype(np.float32)) for _ in range(3)]
    ys = [nd.array(rs.randint(0, 4, (8,))) for _ in range(3)]
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    ref = build()
    tr = gluon.Trainer(ref.collect_params(), opt, dict(params))
    for x, y in zip(xs, ys):
        with autograd.record():
            loss = loss_fn(ref(x), y).mean()
        loss.backward()
        tr.step(1)

    net = build()
    mesh = make_mesh({"dp": 8})
    with mesh_scope(mesh):
        dpt = DataParallelTrainer(net, loss_fn, opt, dict(params), mesh=mesh)
        for x, y in zip(xs, ys):
            dpt.step(x, y)

    for (_, pr), (_, pn) in zip(sorted(ref.collect_params().items()),
                                sorted(net.collect_params().items())):
        np.testing.assert_allclose(pr.data().asnumpy(),
                                   pn.data().asnumpy(), rtol=2e-4,
                                   atol=2e-5)


@needs8
def test_combined_dp_tp_sp_pp_matches_oracle():
    """VERDICT r3 #10: the four-axis fused step's loss/grads equal a
    single-device sequential replay (full softmax attention oracle)."""
    import __graft_entry__ as g
    g._dryrun_combined_oracle(8)


@needs8
def test_weight_update_sharding_matches_replicated():
    """ZeRO-1 sharded sync (shard_updates=True, ISSUE 3 tentpole):
    identical numerics to the replicated psum path, optimizer state
    physically sharded 1/N per chip in bucket space, and the lowered
    step contains an explicit reduce-scatter + all-gather."""
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    def build():
        np.random.seed(0)
        net = gluon.nn.Dense(16)
        net.initialize()
        net(nd.zeros((8, 32)))
        for p in net.collect_params().values():
            p.set_data(nd.array(np.random.RandomState(1)
                                .randn(*p.shape).astype(np.float32)))
        return net

    x = nd.array(np.random.RandomState(2).randn(8, 32).astype(np.float32))
    y = nd.array(np.random.RandomState(3).randint(0, 16, (8,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 8})

    nets = {}
    for shard in (False, True):
        net = build()
        with mesh_scope(mesh):
            dpt = DataParallelTrainer(
                net, loss_fn, "sgd", {"learning_rate": 0.1,
                                      "momentum": 0.9},
                mesh=mesh, shard_updates=shard)
            for _ in range(3):
                dpt.step(x, y)
        nets[shard] = net
        if shard:
            assert dpt._zero1_active() and dpt._plan is not None
            # momentum state lives in bucket space, dp-sharded: each
            # chip's addressable shard is 1/8 of the bucket (the
            # (N-1)/N optimizer-HBM saving, acceptance criterion)
            leaves = [l for l in jax.tree.leaves(dpt._opt_state)
                      if getattr(l, "ndim", 0) >= 1]
            assert leaves, "no sharded optimizer state"
            for leaf in leaves:
                assert leaf.sharding.spec[0] == "dp", leaf.sharding
                assert leaf.addressable_shards[0].data.size == \
                    leaf.size // 8
            stats = dpt.comm_stats()
            assert stats["zero1"] and stats["buckets"] >= 1
            assert stats["state_bytes_per_chip"] * 8 == \
                stats["state_bytes_replicated"]
            # the compiled step must contain the explicit collectives
            # (cache key: kind, n_micro, n_steps, input ranks, comm
            # mode, donate)
            jitted = dpt._jit_zero1_cache[
                ("plain", None, None, (x.data.ndim, y.data.ndim),
                 "overlap", None)]
            key = jax.random.PRNGKey(0)
            hlo = jitted.lower(
                dpt._param_vals, dpt._opt_state,
                jnp.asarray(0.1, jnp.float32), key,
                jax.device_put(x.data,
                               dpt._batch_sharding(x.data)),
                jax.device_put(y.data,
                               dpt._batch_sharding(y.data,
                                                   is_label=True))
            ).compile().as_text()
            assert "reduce-scatter" in hlo, "no grad reduce-scatter"
            assert "all-gather" in hlo, "no all-gather of updated params"

    for (_, pr), (_, ps) in zip(sorted(nets[False].collect_params().items()),
                                sorted(nets[True].collect_params().items())):
        np.testing.assert_allclose(pr.data().asnumpy(),
                                   ps.data().asnumpy(), rtol=1e-4,
                                   atol=1e-5)


@needs8
def test_step_accum_matches_single_big_batch():
    """In-graph gradient accumulation: n_micro microbatches through
    lax.scan + one update == one big-batch step (for batch-independent
    models; BN would differ by design)."""
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    def build():
        np.random.seed(0)
        net = gluon.nn.Dense(8)
        net.initialize()
        net(nd.zeros((2, 16)))
        for p in net.collect_params().values():
            p.set_data(nd.array(np.random.RandomState(1)
                                .randn(*p.shape).astype(np.float32)))
        return net

    x = nd.array(np.random.RandomState(2).randn(16, 16).astype(np.float32))
    y = nd.array(np.random.RandomState(3).randint(0, 8, (16,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 8})

    with mesh_scope(mesh):
        big = DataParallelTrainer(build(), loss_fn, "sgd",
                                  {"learning_rate": 0.1}, mesh=mesh)
        loss_big = big.step(x, y)
        acc = DataParallelTrainer(build(), loss_fn, "sgd",
                                  {"learning_rate": 0.1}, mesh=mesh)
        loss_acc = acc.step_accum(x, y, n_micro=4)

    np.testing.assert_allclose(loss_acc.asnumpy(), loss_big.asnumpy(),
                               rtol=1e-5)
    for (_, pb), (_, pa) in zip(
            sorted(big.block.collect_params().items()),
            sorted(acc.block.collect_params().items())):
        np.testing.assert_allclose(pb.data().asnumpy(),
                                   pa.data().asnumpy(), rtol=1e-5,
                                   atol=1e-6)
    with pytest.raises(mx.MXNetError):
        acc.step_accum(x, y, n_micro=5)   # 16 % 5 != 0


@needs8
def test_step_accum_batch_axis_1():
    """Accumulation must split the BATCH axis, not axis 0: a time-major
    (T, B) input microbatched on axis 1 equals the big-batch step."""
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    class TimeMajorMLP(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = gluon.nn.Dense(8, flatten=False)

        def hybrid_forward(self, F, x):
            # x: (T, B, F) -> mean over time -> (B, 8)
            return self.d(x).mean(axis=0)

    def build():
        np.random.seed(0)
        net = TimeMajorMLP()
        net.initialize()
        net(nd.zeros((4, 2, 6)))
        for p in net.collect_params().values():
            p.set_data(nd.array(np.random.RandomState(1)
                                .randn(*p.shape).astype(np.float32)))
        return net

    x = nd.array(np.random.RandomState(2).randn(4, 16, 6)
                 .astype(np.float32))      # (T=4, B=16, F)
    y = nd.array(np.random.RandomState(3).randint(0, 8, (16,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh({"dp": 8})

    with mesh_scope(mesh):
        big = DataParallelTrainer(build(), loss_fn, "sgd",
                                  {"learning_rate": 0.1}, mesh=mesh,
                                  batch_axis=1)
        loss_big = big.step(x, y)
        acc = DataParallelTrainer(build(), loss_fn, "sgd",
                                  {"learning_rate": 0.1}, mesh=mesh,
                                  batch_axis=1)
        loss_acc = acc.step_accum(x, y, n_micro=2)

    np.testing.assert_allclose(loss_acc.asnumpy(), loss_big.asnumpy(),
                               rtol=1e-5)
    for (_, pb), (_, pa) in zip(
            sorted(big.block.collect_params().items()),
            sorted(acc.block.collect_params().items())):
        np.testing.assert_allclose(pb.data().asnumpy(),
                                   pa.data().asnumpy(), rtol=1e-5,
                                   atol=1e-6)


@needs8
def test_step_accum_label_batch_axis():
    """(B, C) soft labels under time-major data need label_batch_axis=0;
    the trainer must honor it rather than shredding the class axis."""
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    class TimeMajorMLP(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = gluon.nn.Dense(8, flatten=False)

        def hybrid_forward(self, F, x):
            return self.d(x).mean(axis=0)

    class SoftCE(gluon.loss.Loss):
        def __init__(self, **kw):
            super().__init__(None, 0, **kw)

        def hybrid_forward(self, F, pred, label):
            return -(label * F.log_softmax(pred, axis=-1)).sum(axis=-1)

    def build():
        np.random.seed(0)
        net = TimeMajorMLP()
        net.initialize()
        net(nd.zeros((4, 2, 6)))
        for p in net.collect_params().values():
            p.set_data(nd.array(np.random.RandomState(1)
                                .randn(*p.shape).astype(np.float32)))
        return net

    x = nd.array(np.random.RandomState(2).randn(4, 16, 6)
                 .astype(np.float32))
    soft = np.random.RandomState(3).rand(16, 8).astype(np.float32)
    soft /= soft.sum(1, keepdims=True)
    y = nd.array(soft)
    mesh = make_mesh({"dp": 8})
    with mesh_scope(mesh):
        big = DataParallelTrainer(build(), SoftCE(), "sgd",
                                  {"learning_rate": 0.1}, mesh=mesh,
                                  batch_axis=1, label_batch_axis=0)
        loss_big = big.step(x, y)
        acc = DataParallelTrainer(build(), SoftCE(), "sgd",
                                  {"learning_rate": 0.1}, mesh=mesh,
                                  batch_axis=1, label_batch_axis=0)
        loss_acc = acc.step_accum(x, y, n_micro=2)
    np.testing.assert_allclose(loss_acc.asnumpy(), loss_big.asnumpy(),
                               rtol=1e-5)
    for (_, pb), (_, pa) in zip(
            sorted(big.block.collect_params().items()),
            sorted(acc.block.collect_params().items())):
        np.testing.assert_allclose(pb.data().asnumpy(),
                                   pa.data().asnumpy(), rtol=1e-5,
                                   atol=1e-6)


@needs8
def test_amp_zero1_accum_interaction():
    """bf16 AMP + ZeRO-1 sharded updates + in-graph accumulation compose
    in one trainer: loss descends across mixed step kinds."""
    from mxnet_tpu import amp
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    amp.init(target_dtype="bfloat16")
    try:
        np.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(8))
        net.initialize()
        net.hybridize()
        # batch splits evenly over dp=8 chips x n_micro=4 microbatches
        # (the sharded pipeline needs even local shards)
        x = nd.array(np.random.randn(64, 16).astype(np.float32))
        y = nd.array(np.random.randint(0, 8, (64,)))
        mesh = make_mesh({"dp": 8})
        with mesh_scope(mesh):
            tr = DataParallelTrainer(
                net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                {"learning_rate": 1e-2}, mesh=mesh, shard_updates=True)
            l1 = float(tr.step(x, y).asnumpy())
            tr.step_accum(x, y, n_micro=4)
            l3 = float(tr.step(x, y).asnumpy())
        assert l3 < l1, (l1, l3)
    finally:
        amp._deinit_for_tests()   # restore default precision policy
