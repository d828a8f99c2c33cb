"""Pretrained zoo path + NDARRAY_V2 golden checkpoint (VERDICT r3 #7).

Reference: python/mxnet/gluon/model_zoo/model_store.py (get_model_file),
src/ndarray/ndarray.cc NDArray::Save/Load (the .params container)."""
import os
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.gluon.model_zoo.model_store import get_model_file
from mxnet_tpu.ndarray.utils import load, save_legacy

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "golden_ndarray_v2.params")


def test_golden_ndarray_v2_fixture_loads_exactly():
    """The committed .params blob is byte-genuine NDARRAY_V2: verify the
    container layout by hand, then the reader's exact values."""
    blob = open(FIXTURE, "rb").read()
    assert struct.unpack_from("<Q", blob, 0)[0] == 0x112      # file magic
    assert struct.unpack_from("<Q", blob, 16)[0] == 4         # count
    assert struct.unpack_from("<I", blob, 24)[0] == 0xF993FAC9  # NDARRAY_V2
    # dense stype is 0 (kDefaultStorage) in the reference enum —
    # kUndefinedStorage (-1) never appears in genuine reference files
    assert struct.unpack_from("<i", blob, 28)[0] == 0

    d = load(FIXTURE)
    assert sorted(d) == ["arg:dense0_bias", "arg:dense0_weight",
                         "arg:embed_int", "aux:batchnorm0_running_mean"]
    rng = np.random.RandomState(42)
    np.testing.assert_array_equal(d["arg:dense0_weight"].asnumpy(),
                                  rng.randn(4, 3).astype(np.float32))
    np.testing.assert_array_equal(d["arg:dense0_bias"].asnumpy(),
                                  rng.randn(4).astype(np.float32))
    rm = d["aux:batchnorm0_running_mean"]
    np.testing.assert_array_equal(rm.asnumpy(),
                                  rng.rand(4).astype(np.float16))
    assert rm.dtype == np.float16
    ei = d["arg:embed_int"]
    np.testing.assert_array_equal(ei.asnumpy(),
                                  rng.randint(-5, 5, (2, 2)))
    assert ei.dtype == np.int32


def test_legacy_writer_reader_roundtrip(tmp_path):
    d = {"w": nd.array(np.arange(6, dtype=np.float32).reshape(2, 3)),
         "b": nd.array(np.array([1.0, 2.0], np.float16), dtype="float16"),
         "i": nd.array([1, 2, 3], dtype="int32")}
    p = str(tmp_path / "rt.params")
    save_legacy(p, d)
    back = load(p)
    for k in d:
        np.testing.assert_array_equal(back[k].asnumpy(), d[k].asnumpy())
        assert back[k].dtype == d[k].dtype
    # unnamed list form
    p2 = str(tmp_path / "rt2.params")
    save_legacy(p2, [nd.array([1.0])])
    lst = load(p2)
    assert isinstance(lst, list) and len(lst) == 1
    with pytest.raises(mx.MXNetError):
        save_legacy(str(tmp_path / "bad.params"),
                    {"x": nd.array([1.0], dtype="bfloat16")})


def test_get_model_file_resolution(tmp_path):
    root = tmp_path / "store"
    root.mkdir()
    (root / "resnet18_v1.params").write_bytes(b"x")
    assert get_model_file("resnet18_v1", str(root)).endswith(
        "resnet18_v1.params")
    # reference hashed naming also resolves
    (root / "alexnet-44335d1f.params").write_bytes(b"x")
    assert get_model_file("alexnet", str(root)).endswith(
        "alexnet-44335d1f.params")
    with pytest.raises(mx.MXNetError, match="model store"):
        get_model_file("vgg16", str(root))
    # env-var root
    os.environ["MXTPU_MODEL_STORE"] = str(root)
    try:
        assert get_model_file("alexnet").endswith(".params")
    finally:
        del os.environ["MXTPU_MODEL_STORE"]


# the end-to-end pretrained one-liner over both container formats
def test_pretrained_one_liner_offline(tmp_path):
    """get_model(name, pretrained=True, root=...) — the one-line load.
    Covers both container formats in the store: native save_parameters
    output AND a reference-era (legacy-written) NDARRAY_V2 file."""
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(1, 3, 32, 32).astype(np.float32))

    src = vision.squeezenet1_0(classes=7)
    src.initialize()
    y_src = src(x)
    root = tmp_path / "models"
    root.mkdir()
    src.save_parameters(str(root / "squeezenet1.0.params"))

    net = vision.get_model("squeezenet1.0", pretrained=True, root=str(root),
                           classes=7)
    np.testing.assert_allclose(net(x).asnumpy(), y_src.asnumpy(),
                               rtol=1e-5, atol=1e-6)

    # legacy-format store entry: same params re-written as NDARRAY_V2
    # with the structural arg:/aux: names reference checkpoints carry
    legacy_dict = {f"arg:{k}": p.data()
                   for k, p in src._collect_params_with_prefix().items()}
    save_legacy(str(root / "squeezenet1.0-deadbeef.params"), legacy_dict)
    os.remove(root / "squeezenet1.0.params")
    net2 = vision.get_model("squeezenet1.0", pretrained=True,
                            root=str(root), classes=7)
    np.testing.assert_allclose(net2(x).asnumpy(), y_src.asnumpy(),
                               rtol=1e-5, atol=1e-6)

    with pytest.raises(mx.MXNetError, match="model store"):
        vision.get_model("vgg11", pretrained=True, root=str(root))
