"""KVStore semantics (reference: tests/python/unittest/test_kvstore.py +
nightly dist_sync_kvstore.py --gc-type 2bit for compression)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.kvstore.kvstore import GradientCompression

nd = mx.nd


def test_init_push_pull_single():
    kv = mx.kv.create("local")
    kv.init("a", nd.ones((4,)))
    kv.push("a", nd.ones((4,)) * 3)
    out = nd.zeros((4,))
    kv.pull("a", out=out)
    np.testing.assert_allclose(out.asnumpy(), 3.0)


def test_push_list_reduces():
    kv = mx.kv.create("device")
    kv.init(0, nd.zeros((2, 2)))
    kv.push(0, [nd.ones((2, 2)), nd.ones((2, 2)) * 2])
    out = nd.zeros((2, 2))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), 3.0)


def test_list_keys():
    kv = mx.kv.create("local")
    kv.init(["x", "y"], [nd.ones((2,)), nd.ones((3,))])
    outs = [nd.zeros((2,)), nd.zeros((3,))]
    kv.pull(["x", "y"], out=outs)
    assert outs[0].shape == (2,)
    assert outs[1].shape == (3,)


def test_updater_applied_on_push():
    kv = mx.kv.create("local")
    kv.init(3, nd.ones((4, 4)))

    def update(key, grad, weight):
        weight -= 0.5 * grad

    kv._set_updater(update)
    kv.push(3, nd.ones((4, 4)))
    out = nd.zeros((4, 4))
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.5)


def test_gradient_compression_roundtrip():
    gc = GradientCompression(threshold=0.5)
    g = np.array([0.7, -0.7, 0.1, -0.1, 2.0], np.float32)
    packed, shape = gc.compress("k", mx.nd.array(g).data)
    deq = np.asarray(gc.decompress(packed, shape))
    np.testing.assert_allclose(deq, [0.5, -0.5, 0.0, 0.0, 0.5])
    # error feedback: residual carries the truncated mass
    res = np.asarray(gc._residuals["k"])
    np.testing.assert_allclose(res, [0.2, -0.2, 0.1, -0.1, 1.5], atol=1e-6)
    # second step: residual alone pushes 1.5 -> +0.5 again
    packed2, _ = gc.compress("k", mx.nd.zeros((5,)).data)
    deq2 = np.asarray(gc.decompress(packed2, shape))
    assert deq2[4] == pytest.approx(0.5)


def test_gradient_compression_packing_is_4x():
    gc = GradientCompression(threshold=1.0)
    g = mx.nd.random.uniform(-2, 2, shape=(1024,)).data
    packed, _ = gc.compress("k", g)
    assert packed.dtype.name == "uint8"
    assert packed.shape == (256,)     # 4 codes per byte


def test_kvstore_with_compression():
    # compression applies to the cross-worker hop -> dist store only
    # (single-process dist still exercises the pack/unpack path)
    kv = mx.kv.create("dist_sync")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init(0, nd.zeros((4,)))
    kv.push(0, nd.array([1.0, -1.0, 0.2, 0.0]))
    out = nd.zeros((4,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), [0.5, -0.5, 0.0, 0.0])


def test_optimizer_on_kvstore():
    kv = mx.kv.create("local")
    kv.init(0, nd.ones((4,)))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
    kv.push(0, nd.ones((4,)))
    out = nd.zeros((4,))
    kv.pull(0, out=out)
    assert not np.allclose(out.asnumpy(), 1.0)     # weight moved


def test_int8_compression_roundtrip_and_feedback():
    """EQuARX-style blockwise int8 wire quantization (PAPERS.md row 9):
    value-proportional error, ~4x wire reduction, error feedback."""
    from mxnet_tpu.kvstore.kvstore import Int8GradientCompression
    gc = Int8GradientCompression()
    rng = np.random.RandomState(0)
    g = mx.nd.array(rng.randn(1000).astype(np.float32) * 0.01).data
    packed, shape = gc.compress("k", g)
    assert packed.dtype.name == "uint8"
    # 1000 values -> 4 blocks of 256: 1024 code bytes + 16 scale bytes
    assert packed.shape == (1040,)
    deq = np.asarray(gc.decompress(packed, shape))
    scale_bound = np.abs(np.asarray(g)).max() / 127.0
    assert np.abs(deq - np.asarray(g)).max() <= scale_bound
    # error feedback: the running mean of dequantized grads converges far
    # below one quantization step
    gc2 = Int8GradientCompression()
    acc = np.zeros(1000, np.float32)
    for _ in range(30):
        p, s = gc2.compress("k", g)
        acc += np.asarray(gc2.decompress(p, s))
    assert np.abs(acc / 30 - np.asarray(g)).max() < scale_bound / 20
    # non-multiple-of-block sizes roundtrip
    g3 = mx.nd.array(rng.randn(777).astype(np.float32)).data
    p3, s3 = gc.compress("x", g3)
    d3 = np.asarray(gc.decompress(p3, s3))
    assert d3.shape == (777,)
    assert np.abs(d3 - np.asarray(g3)).max() <= \
        np.abs(np.asarray(g3)).max() / 127.0


def test_kvstore_with_int8_compression():
    kv = mx.kv.create("dist_sync")
    kv.set_gradient_compression({"type": "int8"})
    kv.init(1, nd.zeros((600,)))
    g = np.linspace(-1, 1, 600).astype(np.float32)
    kv.push(1, nd.array(g))
    out = nd.zeros((600,))
    kv.pull(1, out=out)
    np.testing.assert_allclose(out.asnumpy(), g, atol=1.0 / 127.0)


def test_compression_rejects_unknown_params():
    kv = mx.kv.create("dist_sync")
    with pytest.raises(mx.MXNetError):
        kv.set_gradient_compression({"type": "int8", "threshold": 0.1})
    with pytest.raises(mx.MXNetError):
        kv.set_gradient_compression({"type": "2bit", "block": 64})


def _mesh8(axis="dp"):
    import jax
    devs = np.array(jax.devices()[:8])
    from jax.sharding import Mesh
    return Mesh(devs, (axis,))


def test_tpu_sync_traced_push_lowers_to_psum():
    """VERDICT r3 #9: a traced push through the tpu_sync facade must stay
    in-graph as a psum over the mesh data axis — assert on the jaxpr and
    on executed numerics (every shard sees the cross-device sum)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.ndarray.ndarray import NDArray

    mesh = _mesh8()
    kv = mx.kv.create("tpu_sync")
    kv.init(3, nd.zeros((4,)))

    def step(g):
        gn = NDArray(g[0])          # shard-local (1,4) -> (4,)
        kv.push(3, gn)
        out = NDArray(jnp.zeros((4,), jnp.float32))
        kv.pull(3, out=out)
        return out.data[None, :]

    f = shard_map(step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    jaxpr = str(jax.make_jaxpr(f)(x))
    assert "psum" in jaxpr
    y = np.asarray(jax.jit(f)(x))
    expect = np.asarray(x).sum(axis=0)
    for shard in y:
        np.testing.assert_allclose(shard, expect, rtol=1e-6)


def test_dist_tpu_sync_traced_push_stays_in_graph():
    """VERDICT r3 #4b: pushpull inside a jitted step must not take the
    host-mediated bucketed-allreduce (device_put/D2H per bucket). Tracing
    succeeding is itself the no-host-sync proof (np.asarray on a tracer
    raises); also assert the collective is in the lowered jaxpr."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.ndarray.ndarray import NDArray

    mesh = _mesh8()
    kv = mx.kv.create("dist_tpu_sync")
    kv.init(7, nd.zeros((2,)))

    def step(g):
        gn = NDArray(g[0])
        kv.pushpull(7, gn, out=gn)
        return gn.data[None, :]

    f = shard_map(step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    x = jnp.ones((8, 2), jnp.float32)
    jaxpr = str(jax.make_jaxpr(f)(x))
    assert "psum" in jaxpr
    y = np.asarray(jax.jit(f)(x))
    np.testing.assert_allclose(y, np.full((8, 2), 8.0), rtol=1e-6)


def test_tpu_sync_traced_push_rejects_updater():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray

    mesh = _mesh8()
    kv = mx.kv.create("tpu_sync")
    kv.init(1, nd.zeros((2,)))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))

    def step(g):
        gn = NDArray(g[0])
        kv.push(1, gn)
        return g

    f = shard_map(step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    with pytest.raises(mx.MXNetError, match="update-on-kvstore"):
        import jax
        jax.make_jaxpr(f)(jnp.ones((8, 2), jnp.float32))


def test_tpu_sync_traced_mixed_pull_and_stale_scrub():
    """Review findings: mixed traced/eager pulls route per key; stale
    tracers from an aborted trace never leak into eager pulls."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.ndarray.ndarray import NDArray

    mesh = _mesh8()
    kv = mx.kv.create("tpu_sync")
    kv.init(1, nd.array([10.0, 20.0]))
    kv.init(2, nd.array([5.0, 6.0]))

    def step(g):
        gn = NDArray(g[0])
        kv.push(1, gn)
        o1 = NDArray(jnp.zeros((2,), jnp.float32))
        o2 = nd.zeros((2,))
        kv.pull([1, 2], out=[o1, o2])    # key 2 was never pushed traced
        return (o1.data + o2.data)[None, :]

    f = shard_map(step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    y = np.asarray(jax.jit(f)(jnp.ones((8, 2), jnp.float32)))
    np.testing.assert_allclose(y, np.full((8, 2), 8.0) + [5.0, 6.0])

    # aborted trace: push happens, pull never does -> eager pull must
    # return the stored value, not the dead tracer
    def bad_step(g):
        kv.push(1, NDArray(g[0]))
        raise ValueError("abort after push")

    fb = shard_map(bad_step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    with pytest.raises(ValueError):
        jax.make_jaxpr(fb)(jnp.ones((8, 2), jnp.float32))
    out = nd.zeros((2,))
    kv.pull(1, out=out)
    np.testing.assert_allclose(out.asnumpy(), [10.0, 20.0])


def test_tpu_sync_traced_push_guards():
    """Uninitialized keys and unbound axis names fail fast with guidance."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.ndarray.ndarray import NDArray

    kv = mx.kv.create("tpu_sync")
    kv.init(0, nd.zeros((2,)))
    mesh = _mesh8()

    def push99(g):
        kv.push(99, NDArray(g[0]))
        return g

    f = shard_map(push99, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    with pytest.raises(mx.MXNetError, match="not initialized"):
        jax.make_jaxpr(f)(jnp.ones((8, 2), jnp.float32))

    mesh_model = _mesh8(axis="model")    # no 'dp' axis in scope

    def push0(g):
        kv.push(0, NDArray(g[0]))
        return g

    fm = shard_map(push0, mesh=mesh_model,
                   in_specs=P("model"), out_specs=P("model"))
    with pytest.raises(mx.MXNetError, match="set_data_axis"):
        jax.make_jaxpr(fm)(jnp.ones((8, 2), jnp.float32))


def test_horovod_byteps_adapter_facades():
    """Reference >=1.6 kvstore/horovod.py + byteps.py adapters (VERDICT r3
    missing #5): create() accepts the names, push/pull keep allreduce
    semantics, server-side optimizer is refused like the reference."""
    for name in ("horovod", "byteps"):
        kv = mx.kv.create(name)
        assert kv.type == name
        assert kv.rank == 0 and kv.num_workers == 1
        kv.init(0, nd.zeros((3,)))
        v = nd.array([1.0, 2.0, 3.0])
        kv.pushpull(0, v, out=v)
        np.testing.assert_allclose(v.asnumpy(), [1.0, 2.0, 3.0])
        with pytest.raises(mx.MXNetError, match="server-side"):
            kv.set_optimizer(mx.optimizer.SGD())


def test_interval_sampler_and_send_command():
    """gluon.contrib.data.IntervalSampler + KVStore.send_command_to_servers
    (reference contrib/data/sampler.py, kvstore.py controller messages)."""
    from mxnet_tpu.gluon.contrib.data import IntervalSampler
    s = IntervalSampler(10, 3)
    order = list(s)
    assert sorted(order) == list(range(10)) and len(s) == 10
    assert order[:4] == [0, 3, 6, 9]
    s2 = IntervalSampler(10, 3, rollover=False)
    assert list(s2) == [0, 3, 6, 9] and len(s2) == 4
    # serverless stores: documented no-op
    mx.kv.create("local").send_command_to_servers(0, "anything")
