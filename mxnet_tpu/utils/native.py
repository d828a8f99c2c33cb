"""ctypes bindings to the native C++ runtime library (src/).

The native library accelerates host-side work that is NOT on the XLA compute
path (SURVEY.md design stance: XLA is the device runtime; the host runtime
around it is C++): RecordIO scanning/indexing and batch assembly with a
prefetching thread pool — the role of src/io/ + dmlc-core in the reference.

Falls back cleanly when the library has not been built
(`python setup_native.py build` produces libmxtpu.so next to this file).
"""
from __future__ import annotations

import ctypes
import os

_LIB = None
_TRIED = False


def _repo_root():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "..")


def _newest_source_mtime():
    """Newest mtime among the files the library is built from (``src/``
    without its build directory), or None when the sources are not
    there (an installed package)."""
    src = os.path.join(_repo_root(), "src")
    newest = None
    for dirpath, dirnames, filenames in os.walk(src):
        if dirpath == src and "build" in dirnames:
            dirnames.remove("build")
        for name in filenames:
            if name == ".build.lock":
                continue
            m = os.path.getmtime(os.path.join(dirpath, name))
            newest = m if newest is None else max(newest, m)
    return newest


def _find_lib():
    """A built ``libmxtpu.so`` that is no older than the sources.  A
    binary left behind by another checkout or an earlier commit (both
    build locations are git-ignored, so it survives) is not accepted:
    the caller rebuilds."""
    here = os.path.dirname(os.path.abspath(__file__))
    candidates = [
        os.path.join(here, "libmxtpu.so"),
        os.path.join(here, "..", "..", "src", "build", "libmxtpu.so"),
        os.path.join(here, "..", "..", "build", "libmxtpu.so"),
    ]
    newest = _newest_source_mtime()
    for c in candidates:
        if os.path.exists(c) and (newest is None
                                  or os.path.getmtime(c) >= newest):
            return c
    return None


def _try_build():
    """Attempt a one-shot cmake build of src/ (first use on a fresh
    checkout, or the sources changed). Logged, serialized via a file lock
    so concurrent processes (e.g. a distributed launch) don't race the
    build directory; a failure is a WARNING and leaves the pure-Python
    path in charge."""
    import fcntl
    import logging
    import shutil
    import subprocess
    src = os.path.join(_repo_root(), "src")
    if not os.path.isfile(os.path.join(src, "CMakeLists.txt")):
        return
    build = os.path.join(src, "build")
    lock_path = os.path.join(src, ".build.lock")
    try:
        with open(lock_path, "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)  # another proc may be building
            if _find_lib() is not None:
                return
            logging.getLogger("mxnet_tpu").info(
                "building native library (src/ -> libmxtpu.so); "
                "set MXTPU_NO_NATIVE_BUILD=1 to skip")
            # always configure from nothing: a build directory copied
            # from another checkout pins that checkout's absolute paths
            # in its CMakeCache.txt and cmake refuses it
            shutil.rmtree(build, ignore_errors=True)
            subprocess.run(["cmake", "-S", src, "-B", build],
                           capture_output=True, timeout=120, check=True)
            subprocess.run(["cmake", "--build", build],
                           capture_output=True, timeout=300, check=True)
    except (OSError, subprocess.SubprocessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        logging.getLogger("mxnet_tpu").warning(
            "native library build failed (%s)%s; using pure-Python IO",
            exc, ": " + detail.decode(errors="replace")[-400:]
            if detail else "")


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    path = _find_lib()
    if path is None and os.environ.get("MXTPU_NO_NATIVE_BUILD") != "1":
        _try_build()
        path = _find_lib()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.mxtpu_recordio_open.restype = ctypes.c_void_p
        lib.mxtpu_recordio_open.argtypes = [ctypes.c_char_p]
        lib.mxtpu_recordio_count.restype = ctypes.c_int64
        lib.mxtpu_recordio_count.argtypes = [ctypes.c_void_p]
        lib.mxtpu_recordio_read.restype = ctypes.c_int64
        lib.mxtpu_recordio_read.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p)]
        lib.mxtpu_recordio_close.argtypes = [ctypes.c_void_p]
        lib.mxtpu_recordio_writer_open.restype = ctypes.c_void_p
        lib.mxtpu_recordio_writer_open.argtypes = [ctypes.c_char_p]
        lib.mxtpu_recordio_writer_write.restype = ctypes.c_int64
        lib.mxtpu_recordio_writer_write.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        lib.mxtpu_recordio_writer_close.restype = ctypes.c_int
        lib.mxtpu_recordio_writer_close.argtypes = [ctypes.c_void_p]
        lib.mxtpu_jpeg_decode.restype = ctypes.c_int
        lib.mxtpu_jpeg_decode.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        lib.mxtpu_prefetch_create.restype = ctypes.c_void_p
        lib.mxtpu_prefetch_create.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32]
        lib.mxtpu_prefetch_next.restype = ctypes.c_int64
        lib.mxtpu_prefetch_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_void_p)]
        lib.mxtpu_prefetch_reset.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
        lib.mxtpu_prefetch_stats.restype = None
        lib.mxtpu_prefetch_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
        lib.mxtpu_prefetch_error.restype = ctypes.c_char_p
        lib.mxtpu_prefetch_error.argtypes = [ctypes.c_void_p]
        lib.mxtpu_prefetch_free.argtypes = [ctypes.c_void_p]
        lib.mxtpu_last_error.restype = ctypes.c_char_p
        _LIB = lib
    except OSError:
        _LIB = None
    return _LIB


def available():
    return _load() is not None


class NativeRecordFile:
    """Random-access view over a .rec file backed by the C++ reader
    (mmap + in-memory index, no per-read Python parsing)."""

    def __init__(self, path):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library not built")
        self._lib = lib
        self._handle = lib.mxtpu_recordio_open(path.encode())
        if not self._handle:
            raise IOError(f"cannot open {path}")
        self._count = lib.mxtpu_recordio_count(self._handle)

    def __len__(self):
        return self._count

    def __getitem__(self, i):
        ptr = ctypes.c_void_p()
        size = self._lib.mxtpu_recordio_read(self._handle, i,
                                             ctypes.byref(ptr))
        if size < 0:
            raise IndexError(i)
        return ctypes.string_at(ptr, size)

    def close(self):
        if self._handle:
            self._lib.mxtpu_recordio_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordWriter:
    """Sequential RecordIO writer backed by the C++ library."""

    def __init__(self, path):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library not built")
        self._lib = lib
        self._handle = lib.mxtpu_recordio_writer_open(path.encode())
        if not self._handle:
            raise IOError(f"cannot open {path} for writing")

    def write(self, buf):
        pos = self._lib.mxtpu_recordio_writer_write(
            self._handle, buf, len(buf))
        if pos < 0:
            raise IOError("native record write failed: %s"
                          % self._lib.mxtpu_last_error().decode())
        return pos

    def close(self):
        if self._handle:
            rc = self._lib.mxtpu_recordio_writer_close(self._handle)
            self._handle = None
            if rc != 0:
                raise IOError("record file close failed "
                              "(data may be truncated)")

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def jpeg_decode(buf):
    """Decode a JPEG byte string to an HxWx3 uint8 numpy array (RGB)."""
    import numpy as np
    lib = _load()
    if lib is None:
        raise RuntimeError("native library not built")
    h = ctypes.c_int32()
    w = ctypes.c_int32()
    c = ctypes.c_int32()
    if lib.mxtpu_jpeg_decode(buf, len(buf), None, 0,
                             ctypes.byref(h), ctypes.byref(w),
                             ctypes.byref(c)) != 0:
        raise ValueError("not a decodable JPEG")
    out = np.empty((h.value, w.value, 3), dtype=np.uint8)
    rc = lib.mxtpu_jpeg_decode(
        buf, len(buf), out.ctypes.data_as(ctypes.c_void_p),
        out.nbytes, ctypes.byref(h), ctypes.byref(w), ctypes.byref(c))
    if rc != 0:
        raise ValueError("JPEG decode failed")
    return out


class NativePrefetcher:
    """Prefetching batch loader over a .rec file (C++ worker threads).

    mode='bytes' yields lists of raw record payloads per batch.
    mode='image' yields (uint8 NHWC batch, float32 labels) per batch —
    records must be IRHeader+JPEG as written by pack_img/im2rec.
    """

    def __init__(self, rec_path, indices, batch_size, n_threads=4,
                 queue_depth=4, mode="bytes", edge=224, label_width=1):
        import numpy as np
        lib = _load()
        if lib is None:
            raise RuntimeError("native library not built")
        self._lib = lib
        self._np = np
        idx = np.asarray(indices, dtype=np.int64)
        self._n = len(idx)
        self.batch_size = batch_size
        self.mode = mode
        self.edge = edge
        self.label_width = label_width
        mode_i = 0 if mode == "bytes" else 1
        self._handle = lib.mxtpu_prefetch_create(
            rec_path.encode(), idx.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int64)),
            len(idx), batch_size, n_threads, queue_depth, mode_i, edge,
            label_width)
        if not self._handle:
            raise IOError(f"cannot create prefetcher for {rec_path}")

    def __iter__(self):
        return self

    def __next__(self):
        np = self._np
        data = ctypes.c_void_p()
        size = ctypes.c_int64()
        aux = ctypes.c_void_p()
        n = self._lib.mxtpu_prefetch_next(
            self._handle, ctypes.byref(data), ctypes.byref(size),
            ctypes.byref(aux))
        if n == 0:
            raise StopIteration
        if n < 0:
            raise IOError("native prefetch failed: %s"
                          % self._lib.mxtpu_prefetch_error(
                              self._handle).decode())
        if self.mode == "bytes":
            raw = ctypes.string_at(data, size.value)
            offsets = np.ctypeslib.as_array(
                ctypes.cast(aux, ctypes.POINTER(ctypes.c_int64)),
                shape=(n + 1,))
            return [raw[offsets[i]:offsets[i + 1]] for i in range(n)]
        e = self.edge
        batch = np.ctypeslib.as_array(
            ctypes.cast(data, ctypes.POINTER(ctypes.c_uint8)),
            shape=(n, e, e, 3)).copy()
        labels = np.ctypeslib.as_array(
            ctypes.cast(aux, ctypes.POINTER(ctypes.c_float)),
            shape=(n, self.label_width)).copy()
        return batch, labels

    def stats(self):
        """What the pool's threads have done since it was created (resets
        included; ``mxtpu_prefetch_stats``): ``decoded`` records built into
        batches, ``busy_ns`` reading + decoding and ``full_ns`` standing
        before a full queue (both summed over the threads), ``empty_ns``
        that ``next()`` stood before an empty one."""
        out = (ctypes.c_int64 * 4)()
        self._lib.mxtpu_prefetch_stats(self._handle, out)
        return dict(zip(("decoded", "busy_ns", "full_ns", "empty_ns"), out))

    def reset(self, indices=None):
        """Restart the epoch without re-opening/re-scanning the .rec file;
        pass a new index schedule (e.g. reshuffled) or None to replay."""
        np = self._np
        if indices is None:
            self._lib.mxtpu_prefetch_reset(
                self._handle, None, 0)
        else:
            idx = np.asarray(indices, dtype=np.int64)
            self._lib.mxtpu_prefetch_reset(
                self._handle,
                idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(idx))

    def close(self):
        if self._handle:
            self._lib.mxtpu_prefetch_free(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
