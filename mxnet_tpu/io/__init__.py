"""``mx.io`` — data iterators + the overlapped input pipeline.

Reference: python/mxnet/io/ (NDArrayIter, CSVIter, ImageRecordIter wrapper,
DataBatch, DataDesc) — SURVEY.md §2.2 "mx.io". Used by the Module API and
reference example scripts.

The pipeline layer (``io/prefetch.py``: :class:`DevicePrefetcher`,
:class:`AsyncDecodeIter`) overlaps host decode, H2D transfer, and device
compute — see docs/INPUT_PIPELINE.md.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, array, concatenate
from ..telemetry import tracing as _tracing
from .prefetch import (DevicePrefetcher, AsyncDecodeIter, PipelineStats,
                       default_prefetch_depth)

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "CSVIter",
           "LibSVMIter",
           "ResizeIter", "PrefetchingIter", "ImageRecordIter", "MNISTIter",
           "DevicePrefetcher", "AsyncDecodeIter", "PipelineStats"]


class DataDesc:
    def __init__(self, name, shape, dtype="float32", layout="NCHW"):
        self.name = name
        self.shape = tuple(shape)
        self.dtype = dtype
        self.layout = layout

    def __repr__(self):
        return f"DataDesc[{self.name},{self.shape},{self.dtype},{self.layout}]"


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None and not isinstance(data, (list, tuple)):
            raise MXNetError("Data must be list of NDArrays")
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    # -- checkpoint cursor protocol (docs/FAULT_TOLERANCE.md) -----------
    def state_dict(self):
        """JSON-able resume cursor. Base iterators report nothing; the
        estimator-level (epoch, batch) cursor still covers them via
        skip-ahead replay."""
        return {}

    def set_state(self, state):
        """Restore a :meth:`state_dict` cursor. Unknown keys ignored."""

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError


def _init_data(data, allow_empty, default_name):
    if data is None:
        return []
    if isinstance(data, (NDArray, _np.ndarray)):
        data = [data]
    if isinstance(data, (list, tuple)):
        data = {f"{default_name}{i if i else ''}"
                if len(data) > 1 else default_name: d
                for i, d in enumerate(data)}
    out = []
    for k, v in data.items():
        if not isinstance(v, NDArray):
            v = array(_np.asarray(v))
        out.append((k, v))
    return out


class NDArrayIter(DataIter):
    """Iterate over NDArray/numpy data. Reference: io.NDArrayIter
    (pad/discard/roll_over last-batch handling, shuffle)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.cursor = -batch_size
        self._cache_idx = None
        # standalone shuffle-cursor restore (PR 4 known gap): keep the
        # UNSHUFFLED arrays and the per-epoch reshuffle seeds, so
        # set_state() can rebuild this exact epoch's order in a fresh
        # process without replaying the global numpy RNG history
        self._base_data = list(self.data)
        self._base_label = list(self.label)
        self._shuffle_seeds = []
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + tuple(v.shape[1:]),
                         str(v.data.dtype)) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + tuple(v.shape[1:]),
                         str(v.data.dtype)) for k, v in self.label]

    def _apply_shuffle(self, seed):
        """Apply ONE epoch's permutation, derived from ``seed`` alone —
        composing with whatever order the arrays already carry (the
        cumulative in-``reset()`` reshuffle semantics, now replayable)."""
        idx = _np.random.RandomState(seed).permutation(self.num_data)
        self.data = [(k, NDArray(v.data[idx])) for k, v in self.data]
        self.label = [(k, NDArray(v.data[idx])) for k, v in self.label]

    def reset(self):
        if self.shuffle:
            # ONE draw from the global stream names this epoch's
            # permutation; the permutation itself comes from a private
            # RandomState(seed).  The estimator resume path still
            # round-trips (checkpointed numpy RNG -> same seed drawn),
            # and a STANDALONE set_state() can now rebuild the order
            # from the saved seed list with no RNG replay at all.
            seed = int(_np.random.randint(0, 2**31 - 1))
            self._shuffle_seeds.append(seed)
            self._apply_shuffle(seed)
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % \
                self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def _getdata(self, data_source):
        if self.cursor + self.batch_size <= self.num_data:
            return [v[self.cursor:self.cursor + self.batch_size]
                    for _, v in data_source]
        if self.last_batch_handle == "discard":
            raise StopIteration
        # pad with wrap-around
        pad = self.batch_size - (self.num_data - self.cursor)
        return [concatenate([v[self.cursor:self.num_data], v[0:pad]])
                for _, v in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0

    def state_dict(self):
        """Resume cursor: the batch cursor into this epoch's shuffled
        order PLUS the per-epoch reshuffle seeds — together they make
        the cursor restorable in a fresh process with any global RNG
        state (the PR 4 gap: the order used to reproduce only by
        replaying the checkpointed numpy stream through the estimator's
        epoch re-entry)."""
        return {"cursor": int(self.cursor),
                "shuffle_seeds": list(self._shuffle_seeds)}

    def set_state(self, state):
        seeds = state.get("shuffle_seeds")
        if seeds is not None and [int(s) for s in seeds] != \
                self._shuffle_seeds:
            # rebuild the exact saved order from scratch: base arrays,
            # then every epoch's permutation in sequence (deterministic
            # standalone — no dependence on the global numpy stream)
            self.data = list(self._base_data)
            self.label = list(self._base_label)
            self._shuffle_seeds = []
            for s in seeds:
                self._shuffle_seeds.append(int(s))
                self._apply_shuffle(int(s))
        self.cursor = int(state.get("cursor", -self.batch_size))


class CSVIter(NDArrayIter):
    """Reference: io.CSVIter (native); here: numpy loadtxt + NDArrayIter."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=None,
                 batch_size=1, **kwargs):
        data = _np.loadtxt(data_csv, delimiter=",").reshape(
            (-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = _np.loadtxt(label_csv, delimiter=",")
            if label_shape:
                label = label.reshape((-1,) + tuple(label_shape))
        super().__init__(data, label, batch_size, **kwargs)


class LibSVMIter(DataIter):
    """Reference: io.LibSVMIter (src/io/iter_libsvm.cc) — sparse
    ``label index:value ...`` rows batched as CSRNDArray data (memory
    O(nnz), the sparse-training input path)."""

    def __init__(self, data_libsvm, data_shape, batch_size=1,
                 label_libsvm=None, label_shape=None, **kwargs):
        super().__init__(batch_size)
        ncol = int(data_shape[0]) if isinstance(data_shape, (tuple, list)) \
            else int(data_shape)
        labels, indptr, indices, values = [], [0], [], []
        with open(data_libsvm) as f:
            for line in f:
                parts = line.split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                for tok in parts[1:]:
                    i, _, v = tok.partition(":")
                    idx = int(i)
                    if idx >= ncol:
                        raise MXNetError(
                            f"libsvm feature index {idx} >= data_shape "
                            f"{ncol}")
                    indices.append(idx)
                    values.append(float(v))
                indptr.append(len(indices))
        if label_libsvm is not None:
            # separate label file (reference label_libsvm): one row per
            # data row, dense floats, reshaped to label_shape
            rows = []
            with open(label_libsvm) as f:
                for line in f:
                    if line.strip():
                        rows.append([float(x) for x in line.split()])
            if len(rows) != len(labels):
                raise MXNetError(
                    f"label_libsvm has {len(rows)} rows, data file has "
                    f"{len(labels)}")
            lab = _np.asarray(rows, _np.float32)
            if label_shape:
                lab = lab.reshape((-1,) + tuple(label_shape))
            elif lab.shape[-1] == 1:
                lab = lab.reshape(-1)
            labels = lab
        self._labels = _np.asarray(labels, _np.float32)
        self._indptr = _np.asarray(indptr, _np.int64)
        self._indices = _np.asarray(indices, _np.int64)
        self._values = _np.asarray(values, _np.float32)
        self._ncol = ncol
        self._n = len(labels)
        self._cursor = 0
        self.provide_data = [DataDesc("data", (batch_size, ncol))]
        self.provide_label = [DataDesc("label", (batch_size,))]

    def reset(self):
        self._cursor = 0

    def _rows(self, lo, hi):
        """CSR slice for rows [lo, hi) plus their labels."""
        start, stop = self._indptr[lo], self._indptr[hi]
        return (self._values[start:stop], self._indptr[lo:hi + 1] - start,
                self._indices[start:stop], self._labels[lo:hi])

    def next(self):
        from ..ndarray.sparse import CSRNDArray
        from ..ndarray import array as _nd_array
        if self._cursor >= self._n:
            raise StopIteration
        lo = self._cursor
        hi = min(lo + self.batch_size, self._n)
        self._cursor = hi
        pad = self.batch_size - (hi - lo)
        vals, indptr, idx, labs = self._rows(lo, hi)
        if pad:
            # reference iterators pad the trailing batch by wrapping to
            # the file start; DataBatch.pad reports how many to discard
            wvals, windptr, widx, wlabs = self._rows(0, pad)
            vals = _np.concatenate([vals, wvals])
            idx = _np.concatenate([idx, widx])
            indptr = _np.concatenate([indptr,
                                      windptr[1:] + indptr[-1]])
            labs = _np.concatenate([labs, wlabs])
        csr = CSRNDArray(vals, indptr, idx, (self.batch_size, self._ncol))
        return DataBatch(data=[csr], label=[_nd_array(labs)], pad=pad)


class ResizeIter(DataIter):
    """Resize another iterator to size batches/epoch (reference io.ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Thread-prefetch wrapper (reference io.PrefetchingIter).

    Backed by :class:`DevicePrefetcher` in host-only mode: a worker
    thread pulls batch N+1 from the backing iter while the consumer
    holds batch N (the reference's iter_prefetcher.h double buffer).
    """

    def __init__(self, iters, rename_data=None, rename_label=None):
        if not isinstance(iters, (list, tuple)):
            iters = [iters]
        assert len(iters) == 1, "only one backing iter supported"
        self.iter = iters[0]
        super().__init__(self.iter.batch_size)
        self._pf = DevicePrefetcher(self.iter, depth=2, to_device=False)

    def reset(self):
        self._pf.reset()

    def __iter__(self):
        return self

    def __next__(self):
        return self._pf.next()

    def next(self):
        return self._pf.next()

    def close(self):
        self._pf.close()

    @property
    def provide_data(self):
        return self.iter.provide_data

    @property
    def provide_label(self):
        return self.iter.provide_label


class ImageRecordIter(DataIter):
    """Images from a .rec file with decode + augment + batch.

    Reference: native ImageRecordIter (src/io/iter_image_recordio_2.cc).
    Pure-Python path here; the C++ pipeline in src/ accelerates decode.

    Each ``next()`` leaves three spans under whatever is ambient (under a
    ``DevicePrefetcher`` its ``io.decode``): ``io.rec.fetch`` (blocked on
    the decode pool), ``io.rec.augment`` (the host's passes over the
    decoded batch) and ``io.rec.stage`` (``array(...)``); ``reset()``
    leaves ``io.epoch``."""

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, rand_crop=False, rand_mirror=False,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, std_r=1.0, std_g=1.0,
                 std_b=1.0, preprocess_threads=4, path_imgidx=None, **kwargs):
        super().__init__(batch_size)
        from .. import recordio
        from ..gluon.data.dataset import RecordFileDataset
        self._dataset = RecordFileDataset(path_imgrec)
        self._data_shape = tuple(data_shape)
        self._shuffle = shuffle
        self._rand_mirror = rand_mirror
        self._label_width = label_width
        self._mean = _np.array([mean_r, mean_g, mean_b]).reshape(3, 1, 1)
        self._std = _np.array([std_r, std_g, std_b]).reshape(3, 1, 1)
        self._order = _np.arange(len(self._dataset))
        self._pos = 0
        self._epoch = 0            # resets so far: io.epoch's number
        self._shuffle_seeds = []   # per-epoch reshuffle seeds (replayable)
        self._path_imgrec = path_imgrec
        self._n_threads = preprocess_threads
        # Native C++ decode+prefetch pipeline (src/prefetch.cc) when the
        # library is built and the target shape is square RGB.
        from ..utils import native as _native
        c, h, w = self._data_shape
        self._use_native = (_native.available() and c == 3 and h == w)
        self._native_iter = None
        self._pool_stats = {}     # NativePrefetcher.stats() at last fetch
        self._async_iter = None   # pure-Python threaded decode fan-out
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size,) + self._data_shape)]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (self.batch_size,))]

    def reset(self):
        with _tracing.span("io.epoch", epoch=self._epoch,
                           records=len(self._dataset)):
            self._epoch += 1
            self._reset()

    def _reset(self):
        self._pos = 0
        if self._shuffle:
            # same standalone-restorable scheme as NDArrayIter: ONE
            # global-stream draw names the epoch's permutation, applied
            # from a private RandomState so set_state can replay it
            seed = int(_np.random.randint(0, 2**31 - 1))
            self._shuffle_seeds.append(seed)
            _np.random.RandomState(seed).shuffle(self._order)
        if self._use_native:
            from ..utils import native as _native
            if self._native_iter is None:
                self._native_iter = _native.NativePrefetcher(
                    self._path_imgrec, self._order, self.batch_size,
                    n_threads=self._n_threads, mode="image",
                    edge=self._data_shape[1], label_width=self._label_width)
            else:  # reuse the open mmap'd reader; just reschedule
                self._native_iter.reset(self._order)
        else:
            self._reset_async()

    def _reset_async(self):
        """(Re)build the threaded decode fan-out for the pure-Python path
        so ``preprocess_threads`` is actually honored (it used to be
        accepted and ignored here).
        Determinism mode keeps decode synchronous: per-sample host RNG
        (rand_mirror) draws must happen in a fixed order."""
        from .. import debug as _debug
        if self._async_iter is not None:
            self._async_iter.close()
            self._async_iter = None
        if self._n_threads > 1 and not _debug.determinism_enabled():
            self._async_iter = AsyncDecodeIter(
                self._decode_sample, self._order, self.batch_size,
                n_workers=self._n_threads, lookahead=2)

    def iter_next(self):
        return self._pos + self.batch_size <= len(self._dataset)

    def state_dict(self):
        """Resume cursor: sample position within this epoch's order,
        plus the per-epoch reshuffle seeds that make the order itself
        restorable in a fresh process (standalone — no dependence on
        the global numpy stream history)."""
        return {"pos": int(self._pos),
                "shuffle_seeds": list(self._shuffle_seeds)}

    def set_state(self, state):
        """Reposition to a :meth:`state_dict` cursor: the next batch
        decoded is the one the interrupted run would have decoded (the
        threaded decode fan-out is rebuilt from the cursor so already-
        consumed samples are not re-decoded)."""
        seeds = state.get("shuffle_seeds")
        if seeds is not None and [int(s) for s in seeds] != \
                self._shuffle_seeds:
            self._order = _np.arange(len(self._dataset))
            self._shuffle_seeds = []
            for s in seeds:
                self._shuffle_seeds.append(int(s))
                _np.random.RandomState(int(s)).shuffle(self._order)
        pos = int(state.get("pos", 0))
        if pos % self.batch_size:
            raise MXNetError(
                f"ImageRecordIter.set_state: pos {pos} is not a batch "
                f"boundary (batch_size {self.batch_size})")
        self._pos = pos
        if self._async_iter is not None:
            self._async_iter.close()
            self._async_iter = None
        if not self._use_native:
            from .. import debug as _debug
            if self._n_threads > 1 and not _debug.determinism_enabled():
                self._async_iter = AsyncDecodeIter(
                    self._decode_sample, self._order[pos:],
                    self.batch_size, n_workers=self._n_threads,
                    lookahead=2)

    def close(self):
        """Shut down the threaded decode fan-out (no leaked workers)."""
        if self._async_iter is not None:
            self._async_iter.close()
            self._async_iter = None

    def _next_native(self):
        with _tracing.span("io.rec.fetch", native=True) as sp:
            # raises StopIteration at end
            batch, labels = next(self._native_iter)
            if _tracing.enabled():
                # what the pool's threads did since the last fetch
                now = self._native_iter.stats()
                _tracing.annotate(sp, **{
                    k: now[k] - self._pool_stats.get(k, 0)
                    for k in ("decoded", "busy_ns", "full_ns")})
                self._pool_stats = now
        if len(batch) < self.batch_size:
            raise StopIteration
        with _tracing.span("io.rec.augment") as sp:
            img = batch.astype("float32").transpose(0, 3, 1, 2)  # ->NCHW
            if self._rand_mirror:
                flip = _np.random.rand(len(img)) < 0.5
                img[flip] = img[flip][..., ::-1]
            img = (img - self._mean[None]) / self._std[None]
            lab = labels[:, 0] if self._label_width == 1 else labels
            _tracing.annotate(sp, dtype=str(img.dtype), bytes=img.nbytes)
        self._pos += self.batch_size
        return self._stage(img, lab)

    def _stage(self, img, lab):
        """The host batch as the ``DataBatch`` handed on, under
        ``io.rec.stage``: ``array()`` casts to its default dtype and puts
        the result where the current context says."""
        with _tracing.span("io.rec.stage") as sp:
            data, label = array(img), array(lab)
            _tracing.annotate(
                sp, bytes=data.data.nbytes + label.data.nbytes,
                dtype=str(data.dtype), device=data.context.device_type)
        return DataBatch(data=[data], label=[label], pad=0)

    def _decode_sample(self, ds_idx):
        """Decode + preprocess ONE record (thread-safe: recordio readers
        hand out per-thread file handles, cv2/PIL decode releases the
        GIL).  Same preprocessing as the native pipeline
        (src/prefetch.cc): short-side resize then center crop to exactly
        (h, w)."""
        from .. import recordio, image
        rec = self._dataset[int(ds_idx)]
        header, img_bytes = recordio.unpack(rec)
        img = image.imdecode(img_bytes)
        c, h, w = self._data_shape
        img = image.resize_short(img, min(h, w))
        img, _ = image.center_crop(img, (w, h))
        img = img.asnumpy().astype("float32").transpose(2, 0, 1)
        if self._rand_mirror and _np.random.rand() < 0.5:
            img = img[:, :, ::-1]
        img = (img - self._mean) / self._std
        label = header.label
        return img, float(label if _np.isscalar(label) else label[0])

    def next(self):
        if not self.iter_next():
            raise StopIteration
        if self._use_native:
            return self._next_native()
        if self._async_iter is not None:
            samples = next(self._async_iter)   # in-order; io.rec.fetch
        else:
            with _tracing.span("io.rec.fetch", native=False):
                samples = [self._decode_sample(self._order[i])
                           for i in range(self._pos,
                                          self._pos + self.batch_size)]
        # the samples come augmented out of the pool's threads; what is
        # left for this one is to stack them
        with _tracing.span("io.rec.augment") as sp:
            img = _np.stack([img for img, _ in samples])
            lab = _np.asarray([lab for _, lab in samples])
            _tracing.annotate(sp, dtype=str(img.dtype), bytes=img.nbytes)
        self._pos += self.batch_size
        return self._stage(img, lab)


class MNISTIter(NDArrayIter):
    """Reference: native MNISTIter (src/io/iter_mnist.cc)."""

    def __init__(self, image=None, label=None, batch_size=128, shuffle=True,
                 flat=False, **kwargs):
        from ..gluon.data.vision.datasets import MNIST
        train = image is None or "train" in str(image)
        ds = MNIST(train=train)
        data = ds._data.asnumpy().transpose(0, 3, 1, 2)
        if flat:
            data = data.reshape(data.shape[0], -1)
        super().__init__(data, ds._label, batch_size, shuffle=shuffle)
