"""NDArray semantics tests.

Modelled on reference tests/python/unittest/test_ndarray.py (SURVEY.md §4).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal, with_seed


def test_creation_defaults():
    a = nd.array([[1, 2], [3, 4]])
    assert a.dtype == np.float32  # python lists default to float32
    assert a.shape == (2, 2)
    b = nd.array(np.arange(6, dtype=np.int32).reshape(2, 3))
    assert b.dtype == np.int32    # numpy dtype preserved
    z = nd.zeros((2, 3))
    assert z.dtype == np.float32
    assert (z.asnumpy() == 0).all()
    o = nd.ones(4)
    assert o.shape == (4,)
    f = nd.full((2, 2), 7.5)
    assert (f.asnumpy() == 7.5).all()
    r = nd.arange(0, 10, 2)
    assert_almost_equal(r, np.arange(0, 10, 2, dtype=np.float32))


def test_elementwise_arith():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([[5.0, 6.0], [7.0, 8.0]])
    assert_almost_equal(a + b, np.array([[6, 8], [10, 12]], np.float32))
    assert_almost_equal(a - b, -np.array([[4, 4], [4, 4]], np.float32))
    assert_almost_equal(a * b, np.array([[5, 12], [21, 32]], np.float32))
    assert_almost_equal(b / a, np.array([[5, 3], [7 / 3, 2]], np.float32))
    assert_almost_equal(a ** 2, np.array([[1, 4], [9, 16]], np.float32))
    assert_almost_equal(2 + a, a.asnumpy() + 2)
    assert_almost_equal(2 - a, 2 - a.asnumpy())
    assert_almost_equal(2 / a, 2 / a.asnumpy())
    assert_almost_equal(-a, -a.asnumpy())


def test_inplace_ops():
    a = nd.ones((2, 2))
    a += 1
    assert (a.asnumpy() == 2).all()
    a *= 3
    assert (a.asnumpy() == 6).all()
    a /= 2
    assert (a.asnumpy() == 3).all()
    a -= 1
    assert (a.asnumpy() == 2).all()


def test_setitem_getitem():
    a = nd.zeros((3, 4))
    a[1] = 5.0
    assert (a.asnumpy()[1] == 5).all()
    a[0, 2] = 7.0
    assert a.asnumpy()[0, 2] == 7
    a[:, 1] = 2.0
    assert (a.asnumpy()[:, 1] == 2).all()
    b = a[1:3]
    assert b.shape == (2, 4)
    # fancy index with NDArray
    idx = nd.array([0, 2], dtype="int32")
    c = a[idx]
    assert c.shape == (2, 4)


def test_reshape_codes():
    a = nd.zeros((2, 3, 4))
    assert a.reshape((6, 4)).shape == (6, 4)
    assert a.reshape((-1,)).shape == (24,)
    assert a.reshape((0, -1)).shape == (2, 12)  # 0 copies dim
    assert a.reshape(0, 0, -1).shape == (2, 3, 4)
    with pytest.raises(mx.MXNetError):
        a.reshape((-2, 4))


def test_flatten_is_mxnet_flatten():
    a = nd.zeros((2, 3, 4))
    assert a.flatten().shape == (2, 12)  # NOT numpy ravel


def test_broadcast():
    a = nd.array([[1.0], [2.0]])
    out = a.broadcast_to((2, 3))
    assert out.shape == (2, 3)
    assert_almost_equal(out, np.broadcast_to(a.asnumpy(), (2, 3)))
    with pytest.raises(mx.MXNetError):
        nd.zeros((2, 2)).broadcast_to((3, 3))


def test_reductions():
    a = nd.array(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
    npa = a.asnumpy()
    assert_almost_equal(a.sum(), npa.sum())
    assert_almost_equal(a.sum(axis=1), npa.sum(1))
    assert_almost_equal(a.mean(axis=(0, 2)), npa.mean((0, 2)))
    assert_almost_equal(a.max(axis=2, keepdims=True), npa.max(2, keepdims=True))
    assert_almost_equal(a.min(), npa.min())
    assert_almost_equal(nd.norm(a), np.sqrt((npa ** 2).sum()))
    assert_almost_equal(a.argmax(axis=1), npa.argmax(1).astype(np.float32))


def test_dot_semantics():
    # mx.nd.dot on >2d: tensordot over last/first axes, not matmul batching
    a = nd.array(np.random.rand(2, 3).astype(np.float32))
    b = nd.array(np.random.rand(3, 4).astype(np.float32))
    assert_almost_equal(nd.dot(a, b), a.asnumpy() @ b.asnumpy())
    assert_almost_equal(nd.dot(a, b, transpose_b=False, transpose_a=False),
                        a.asnumpy() @ b.asnumpy())
    c = nd.array(np.random.rand(4, 3).astype(np.float32))
    assert_almost_equal(nd.dot(a, c, transpose_b=True),
                        a.asnumpy() @ c.asnumpy().T)
    # batch_dot
    x = nd.array(np.random.rand(5, 2, 3).astype(np.float32))
    y = nd.array(np.random.rand(5, 3, 4).astype(np.float32))
    assert_almost_equal(nd.batch_dot(x, y),
                        np.matmul(x.asnumpy(), y.asnumpy()))


def test_concat_stack_split():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    c = nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    c2 = nd.concat(a, b, dim=1)
    assert c2.shape == (2, 6)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)
    parts = nd.split(c2, num_outputs=2, axis=1)
    assert parts[0].shape == (2, 3)
    parts2 = nd.split(nd.ones((4, 6)), num_outputs=2, axis=0,
                      squeeze_axis=False)
    assert parts2[1].shape == (2, 6)


def test_comparison_ops():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([3.0, 2.0, 1.0])
    assert_almost_equal(a == b, np.array([0, 1, 0], np.float32))
    assert_almost_equal(a > b, np.array([0, 0, 1], np.float32))
    assert_almost_equal(a <= b, np.array([1, 1, 0], np.float32))


def test_astype_copy_context():
    a = nd.array([1, 2, 3])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.copy()
    c += 1
    assert (a.asnumpy() == [1, 2, 3]).all()
    d = a.as_in_context(mx.cpu())
    assert d.context.device_type == "cpu"


def test_scalar_conversions():
    a = nd.array([3.5])
    assert a.asscalar() == 3.5
    assert float(a.asnumpy()) == 3.5
    with pytest.raises(mx.MXNetError):
        nd.zeros((2, 2)).asscalar()
    assert bool(nd.array([1.0]))
    assert len(nd.zeros((5, 2))) == 5


def test_save_load_roundtrip(tmp_path):
    fname = str(tmp_path / "arrays.params")
    a = nd.array(np.random.rand(3, 4).astype(np.float32))
    b = nd.array(np.arange(5, dtype=np.int32))
    nd.save(fname, {"a": a, "b": b})
    loaded = nd.load(fname)
    assert set(loaded) == {"a", "b"}
    assert_almost_equal(loaded["a"], a)
    assert (loaded["b"].asnumpy() == b.asnumpy()).all()
    # list form
    nd.save(fname, [a, b])
    lst = nd.load(fname)
    assert isinstance(lst, list) and len(lst) == 2


def test_legacy_ndarray_v2_load(tmp_path):
    """Write a reference-format blob by hand and load it
    (src/ndarray/ndarray.cc NDARRAY_V2 layout)."""
    import struct
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    blob = struct.pack("<Q", 0x112) + struct.pack("<Q", 0)
    blob += struct.pack("<Q", 1)  # count
    blob += struct.pack("<I", 0xF993FAC9)  # NDARRAY_V2 magic
    blob += struct.pack("<i", -1)  # dense stype
    blob += struct.pack("<I", 2)  # ndim
    blob += struct.pack("<qq", 2, 3)
    blob += struct.pack("<II", 1, 0)  # ctx
    blob += struct.pack("<I", 0)  # float32
    blob += arr.tobytes()
    blob += struct.pack("<Q", 1)  # one name
    blob += struct.pack("<Q", len(b"weight")) + b"weight"
    fname = str(tmp_path / "legacy.params")
    with open(fname, "wb") as f:
        f.write(blob)
    loaded = nd.load(fname)
    assert set(loaded) == {"weight"}
    assert_almost_equal(loaded["weight"], arr)


@with_seed()
def test_random_moments():
    u = nd.random.uniform(0, 1, shape=(10000,))
    assert 0.45 < float(u.mean().asscalar()) < 0.55
    n = nd.random.normal(0, 1, shape=(10000,))
    assert abs(float(n.mean().asscalar())) < 0.1
    assert 0.9 < float(((n - n.mean()) ** 2).mean().asscalar()) < 1.1
    r = nd.random.randint(0, 10, shape=(1000,))
    assert r.asnumpy().min() >= 0 and r.asnumpy().max() < 10


@with_seed()
def test_random_seed_reproducible():
    mx.random.seed(42)
    a = nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(42)
    b = nd.random.uniform(shape=(5,)).asnumpy()
    assert (a == b).all()


def test_take_pick_onehot():
    a = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    t = nd.take(a, nd.array([0, 2]))
    assert t.shape == (2, 4)
    assert_almost_equal(t, a.asnumpy()[[0, 2]])
    p = nd.pick(a, nd.array([1, 0, 3]), axis=1)
    assert_almost_equal(p, np.array([1, 4, 11], np.float32))
    oh = nd.one_hot(nd.array([0, 2]), 4)
    assert_almost_equal(oh, np.eye(4, dtype=np.float32)[[0, 2]])


def test_topk_sort_argsort():
    a = nd.array([[3.0, 1.0, 2.0], [0.5, 2.5, 1.5]])
    idx = nd.topk(a, k=2)
    assert idx.shape == (2, 2)
    assert (idx.asnumpy()[0] == [0, 2]).all()
    vals = nd.topk(a, k=1, ret_typ="value")
    assert_almost_equal(vals, np.array([[3.0], [2.5]], np.float32))
    s = nd.sort(a, axis=1)
    assert_almost_equal(s, np.sort(a.asnumpy(), 1))
    ags = nd.argsort(a, axis=1)
    assert_almost_equal(ags, np.argsort(a.asnumpy(), 1).astype(np.float32))


def test_where_clip_misc():
    cond = nd.array([1.0, 0.0, 1.0])
    x = nd.array([1.0, 2.0, 3.0])
    y = nd.array([10.0, 20.0, 30.0])
    assert_almost_equal(nd.where(cond, x, y), np.array([1, 20, 3], np.float32))
    assert_almost_equal(nd.clip(y, 15, 25), np.array([15, 20, 25], np.float32))
    assert_almost_equal(nd.abs(nd.array([-1.0, 2.0])), [1, 2])


def test_context_api():
    assert mx.cpu(0) == mx.cpu(0)
    assert mx.cpu(0) != mx.tpu(0)
    assert mx.gpu(0) != mx.tpu(0)     # an alias for the device, not the name
    assert mx.context.num_tpus() == 0   # the suite runs on the CPU backend
    with mx.Context("cpu", 0):
        a = nd.zeros((2,))
        assert a.context.device_type == "cpu"
    assert str(mx.cpu(1)) == "cpu(1)"


def test_norm_ord():
    import numpy as np
    import mxnet_tpu as mx
    x = mx.nd.array([[3.0, -4.0]])
    assert abs(float(mx.nd.norm(x, ord=1).asnumpy()) - 7.0) < 1e-6
    assert abs(float(mx.nd.norm(x, ord=2).asnumpy()) - 5.0) < 1e-6
    assert abs(float(mx.nd.norm(x).asnumpy()) - 5.0) < 1e-6


def test_global_pool_sum():
    import numpy as np
    import mxnet_tpu as mx
    x = mx.nd.ones((1, 1, 4, 4))
    out = mx.nd.Pooling(x, pool_type="sum", global_pool=True)
    assert abs(float(out.asnumpy().ravel()[0]) - 16.0) < 1e-6
    out = mx.nd.Pooling(x, pool_type="avg", global_pool=True)
    assert abs(float(out.asnumpy().ravel()[0]) - 1.0) < 1e-6


def test_registry_driven_method_surface():
    """Reference autogen parity: op registry entries exposed as NDArray
    methods, forwarding to the tape-integrated ops."""
    import numpy as np
    from mxnet_tpu import autograd
    a = nd.array(np.array([[4.0, 1.0], [9.0, 16.0]]))
    for name in ["flip", "diag", "sort", "argsort", "sign", "round",
                 "ceil", "floor", "square", "rsqrt", "log2", "sin",
                 "cos", "tan", "sinh", "pad", "batch_dot", "nansum",
                 "moments", "shape_array", "tile", "norm", "degrees",
                 "radians", "tostype", "slice"]:
        assert hasattr(a, name), name
    np.testing.assert_allclose(a.square().asnumpy(), a.asnumpy() ** 2)
    np.testing.assert_allclose(a.sort().asnumpy(), np.sort(a.asnumpy()))
    np.testing.assert_allclose(
        nd.array([np.pi]).degrees().asnumpy(), [180.0], rtol=1e-6)
    # the method form records on the tape exactly like the op form
    a.attach_grad()
    with autograd.record():
        y = a.square().sum()
    y.backward()
    np.testing.assert_allclose(a.grad.asnumpy(), 2 * a.asnumpy())
    # dense -> sparse storage conversion
    from mxnet_tpu.ndarray import sparse as sp
    r = a.tostype("row_sparse")
    assert isinstance(r, sp.RowSparseNDArray)
    np.testing.assert_allclose(r.asnumpy(), a.asnumpy())
    c = a.tostype("csr")
    assert isinstance(c, sp.CSRNDArray)


def test_boolean_mask_indexing():
    """bool-DTYPE NDArray keys mask (np-compat); float comparison
    results keep the legacy integer-gather semantics (reference mx.nd
    comparisons return float 0/1 and never meant masking)."""
    import numpy as np
    a = nd.array(np.arange(24.0).reshape(4, 6))
    mask = (a > 10).astype("bool")
    np.testing.assert_allclose(a[mask].asnumpy(),
                               np.arange(24.0)[np.arange(24.0) > 10])
    b = nd.array(np.arange(6.0))
    b[(b > 3).astype("bool")] = 0.0
    np.testing.assert_allclose(b.asnumpy(), [0, 1, 2, 3, 0, 0])
    # numpy bool keys work directly
    assert a[np.array([True, False, True, False])].shape == (2, 6)
    # a bool mask inside jit has a data-dependent shape -> clear error
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import NDArray

    def traced(d, m):
        return NDArray(d)[NDArray(m)]

    with pytest.raises(mx.MXNetError):
        jax.jit(lambda d, m: traced(d, m).data)(
            a.data, mask.data)
