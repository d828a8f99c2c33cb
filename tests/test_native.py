"""Native C++ host runtime (src/): RecordIO, JPEG decode, prefetcher.

Mirrors the reference's test coverage of dmlc-core recordio and
src/io/iter_image_recordio_2.cc behavior (SURVEY.md §2.1 "Data IO").
Skips cleanly when the library is not built.
"""
import numpy as np
import pytest

from mxnet_tpu.utils import native
from mxnet_tpu import recordio

pytestmark = pytest.mark.skipif(
    not native.available(), reason="libmxtpu.so not built")


def _write_rec(tmp_path, payloads):
    path = str(tmp_path / "data.rec")
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()
    return path


def test_native_reader_matches_python(tmp_path):
    payloads = [bytes([i]) * (i * 7 + 1) for i in range(32)]
    path = _write_rec(tmp_path, payloads)
    f = native.NativeRecordFile(path)
    assert len(f) == 32
    for i, p in enumerate(payloads):
        assert f[i] == p
    # python reader agrees
    r = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert r.read() == p
    f.close()


def test_native_writer_roundtrip(tmp_path):
    path = str(tmp_path / "w.rec")
    w = native.NativeRecordWriter(path)
    payloads = [b"x" * n for n in (1, 2, 3, 4, 5, 100, 1001)]
    for p in payloads:
        w.write(p)
    w.close()
    # both readers parse it
    f = native.NativeRecordFile(path)
    assert [f[i] for i in range(len(f))] == payloads
    r = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert r.read() == p


def _make_jpeg(h=48, w=64, seed=0):
    from PIL import Image
    import io as _io
    rng = np.random.RandomState(seed)
    arr = rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
    buf = _io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=95)
    return buf.getvalue(), arr


def test_jpeg_decode_close_to_pil():
    from PIL import Image
    import io as _io
    jpg, _ = _make_jpeg()
    ours = native.jpeg_decode(jpg)
    ref = np.asarray(Image.open(_io.BytesIO(jpg)).convert("RGB"))
    assert ours.shape == ref.shape
    # both are IDCT reconstructions; allow small per-pixel drift
    assert np.mean(np.abs(ours.astype(int) - ref.astype(int))) < 3.0


def test_prefetcher_bytes_mode(tmp_path):
    payloads = [f"record-{i}".encode() * (i + 1) for i in range(25)]
    path = _write_rec(tmp_path, payloads)
    pf = native.NativePrefetcher(path, list(range(25)), batch_size=4,
                                 n_threads=3, mode="bytes")
    got = []
    for batch in pf:
        got.extend(batch)
    assert got == payloads
    pf.close()


def test_prefetcher_image_mode(tmp_path):
    path = str(tmp_path / "img.rec")
    w = recordio.MXRecordIO(path, "w")
    n = 10
    for i in range(n):
        jpg, _ = _make_jpeg(40 + i, 52, seed=i)
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0), jpg))
    w.close()
    pf = native.NativePrefetcher(path, list(range(n)), batch_size=4,
                                 n_threads=2, mode="image", edge=32)
    images, labels = [], []
    for batch, lab in pf:
        images.append(batch)
        labels.append(lab)
    images = np.concatenate(images)
    labels = np.concatenate(labels)[:, 0]
    assert images.shape == (n, 32, 32, 3)
    assert labels.tolist() == [float(i) for i in range(n)]
    pf.close()


def test_prefetcher_reset_reuses_reader(tmp_path):
    payloads = [f"r{i}".encode() for i in range(10)]
    path = _write_rec(tmp_path, payloads)
    pf = native.NativePrefetcher(path, list(range(10)), batch_size=3,
                                 n_threads=2, mode="bytes")
    first = [p for b in pf for p in b]
    assert first == payloads
    # new schedule, same open reader — no re-scan of the file
    pf.reset(list(reversed(range(10))))
    second = [p for b in pf for p in b]
    assert second == payloads[::-1]
    pf.close()


def test_image_record_iter_multi_epoch(tmp_path):
    path = str(tmp_path / "ep.rec")
    w = recordio.MXRecordIO(path, "w")
    for i in range(8):
        jpg, _ = _make_jpeg(30, 30, seed=i)
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0), jpg))
    w.close()
    from mxnet_tpu import io as mio
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 24, 24),
                             batch_size=4, shuffle=True)
    for _epoch in range(3):
        labels = [float(x) for b in it for x in b.label[0].asnumpy()]
        assert sorted(labels) == [float(i) for i in range(8)]
        it.reset()


def test_image_record_iter_native(tmp_path):
    path = str(tmp_path / "iter.rec")
    w = recordio.MXRecordIO(path, "w")
    for i in range(12):
        jpg, _ = _make_jpeg(36, 36, seed=i)
        w.write(recordio.pack(recordio.IRHeader(0, float(i % 3), i, 0), jpg))
    w.close()
    from mxnet_tpu import io as mio
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 28, 28),
                             batch_size=4)
    assert it._use_native
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 3, 28, 28)
    labels = np.concatenate([b.label[0].asnumpy() for b in batches])
    assert labels.tolist() == [float(i % 3) for i in range(12)]


def test_cpp_unit_tests():
    """Run the native C++ unit-test binary (reference tests/cpp/ role);
    builds on demand when cmake is present."""
    import os
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    binary = os.path.join(root, "src", "build", "mxtpu_cpp_tests")
    try:    # always: a binary older than its sources tests nothing new
        subprocess.run(["cmake", "--build",
                        os.path.join(root, "src", "build"),
                        "--target", "mxtpu_cpp_tests"],
                       check=True, capture_output=True, timeout=300)
    except Exception:
        if not os.path.exists(binary):
            pytest.skip("mxtpu_cpp_tests not built and cmake unavailable")
    out = subprocess.run([binary], capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL CPP TESTS PASSED" in out.stdout
