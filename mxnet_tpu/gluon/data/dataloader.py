"""Gluon ``DataLoader`` + batchify + samplers.

Reference: python/mxnet/gluon/data/dataloader.py and sampler.py.

TPU-native notes: the reference forked worker *processes* and moved batches
through shared-memory NDArrays (with engine fork handlers, SURVEY.md §5.2).
Here batching produces host numpy and a single ``jax.device_put`` ships the
batch to the TPU — the XLA transfer engine overlaps it with compute, which is
the role PrefetcherIter played. Thread-based workers cover the
decode-bound case (JPEG decode releases the GIL in PIL/cv2); the native C++
recordio reader (src/) covers the IO-bound case.
"""
from __future__ import annotations

import os
import threading
import queue as _queue

import numpy as _np

from ...base import MXNetError
from ...ndarray.ndarray import NDArray, array

__all__ = ["DataLoader", "default_batchify_fn", "Sampler", "SequentialSampler",
           "RandomSampler", "BatchSampler", "FilterSampler"]


# ----------------------------------------------------------------------
# samplers (reference: gluon/data/sampler.py)
# ----------------------------------------------------------------------

class Sampler:
    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class SequentialSampler(Sampler):
    def __init__(self, length, start=0):
        self._length = length
        self._start = start

    def __iter__(self):
        return iter(range(self._start, self._start + self._length))

    def __len__(self):
        return self._length


class RandomSampler(Sampler):
    def __init__(self, length):
        self._length = length

    def __iter__(self):
        indices = _np.arange(self._length)
        _np.random.shuffle(indices)
        return iter(indices.tolist())

    def __len__(self):
        return self._length


class FilterSampler(Sampler):
    """Samples indices whose dataset element satisfies fn (reference
    gluon/data/sampler.py FilterSampler)."""

    def __init__(self, fn, dataset):
        self._indices = [i for i in range(len(dataset)) if fn(dataset[i])]

    def __iter__(self):
        return iter(self._indices)

    def __len__(self):
        return len(self._indices)


class BatchSampler(Sampler):
    def __init__(self, sampler, batch_size, last_batch="keep"):
        self._sampler = sampler
        self._batch_size = batch_size
        self._last_batch = last_batch
        self._prev = []

    def __iter__(self):
        batch, self._prev = self._prev, []
        for i in self._sampler:
            batch.append(i)
            if len(batch) == self._batch_size:
                yield batch
                batch = []
        if batch:
            if self._last_batch == "keep":
                yield batch
            elif self._last_batch == "discard":
                return
            elif self._last_batch == "rollover":
                self._prev = batch
            else:
                raise MXNetError(
                    f"last_batch must be keep/discard/rollover, got "
                    f"{self._last_batch}")

    def __len__(self):
        if self._last_batch == "keep":
            return (len(self._sampler) + self._batch_size - 1) // \
                self._batch_size
        if self._last_batch == "discard":
            return len(self._sampler) // self._batch_size
        if self._last_batch == "rollover":
            return (len(self._prev) + len(self._sampler)) // self._batch_size
        raise MXNetError(f"bad last_batch {self._last_batch}")


# ----------------------------------------------------------------------
# batchify
# ----------------------------------------------------------------------

def default_batchify_fn(data):
    """Stack samples into a batch (reference dataloader.default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        import jax.numpy as jnp
        return NDArray(jnp.stack([d.data for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = _np.asarray(data)
    return array(data, dtype=data.dtype if data.dtype != _np.float64
                 else "float32")


def _thread_worker_fn(samples, batchify_fn, dataset):
    return batchify_fn([dataset[i] for i in samples])


# ----------------------------------------------------------------------
# process workers (reference default: DataLoader forks worker processes;
# here they are SPAWNED so each worker builds its own fresh CPU-only jax
# — a forked child would inherit the parent's initialized XLA client
# whose threads do not survive fork, and must never race the parent for
# the accelerator)
# ----------------------------------------------------------------------

_MP_DATASET = None
_MP_BATCHIFY = None


def _load_cpu_pinned(payload_bytes):
    """Unpickle target of _CpuPinnedPayload: pins this process to CPU jax
    BEFORE the inner payload (which may contain NDArrays that initialize
    a backend on unpickle) is touched.  Because the pin rides inside the
    pickle itself, it holds no matter when or how the worker was spawned
    — including Pool's respawn of a dead worker, where no parent-side env
    juggling could be in effect.

    Importing this module has already imported jax (and read
    ``JAX_PLATFORMS`` as the parent left it), so the pin is applied to
    jax's config as well as the environment; no backend exists yet —
    ``import mxnet_tpu`` initializes none."""
    import os
    import pickle
    import jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    return pickle.loads(payload_bytes)


class _CpuPinnedPayload:
    """Wraps an object so that UNPICKLING it first pins the process to
    CPU jax.  Unpickles to the wrapped object itself, not the wrapper."""

    def __init__(self, obj):
        import pickle
        self._payload = pickle.dumps(obj)

    def __reduce__(self):
        return (_load_cpu_pinned, (self._payload,))


def _mp_worker_init(dataset, batchify_fn):
    # the real cpu pin already happened while unpickling the
    # _CpuPinnedPayload initargs; keep the global wiring only
    global _MP_DATASET, _MP_BATCHIFY
    _MP_DATASET = dataset
    _MP_BATCHIFY = batchify_fn


def _map_structure(fn, item):
    """Map leaves through fn preserving list/tuple/namedtuple structure."""
    if isinstance(item, (list, tuple)):
        mapped = [_map_structure(fn, i) for i in item]
        if hasattr(item, "_fields"):      # namedtuple
            return type(item)(*mapped)
        return type(item)(mapped)
    return fn(item)


def _to_host(item):
    """NDArray -> numpy for the pickle trip back to the parent."""
    return _map_structure(
        lambda x: x.asnumpy() if isinstance(x, NDArray) else x, item)


def _from_host(item):
    return _map_structure(
        lambda x: array(x) if isinstance(x, _np.ndarray) else x, item)


def _mp_worker_fn(samples):
    return _to_host(_MP_BATCHIFY([_MP_DATASET[i] for i in samples]))


class DataLoader:
    """Loads data from a Dataset and returns mini-batches.

    Reference: gluon.data.DataLoader (num_workers worker processes,
    thread_pool=False default). Deliberate TPU-first deviation: OUR
    default is ``thread_pool=True`` — device arrays are process-local
    under jax, GIL-releasing C++ decode (src/image_decode.cc) scales in
    threads, and thread workers can hold NDArray datasets/transforms
    directly. ``thread_pool=False`` opts into true worker PROCESSES
    (reference semantics) for host-only pipelines: the dataset and
    batchify_fn must pickle, workers are spawned with a fresh CPU-only
    jax (never the parent's accelerator), and batches return as numpy.
    ``num_workers=0`` means synchronous.

    ``prefetch_to_device=True`` chains an ``io.DevicePrefetcher`` after
    batching: a worker thread ships batch N+1 to the device (sharded
    over an active ``parallel`` mesh) while the training step consumes
    batch N — see docs/INPUT_PIPELINE.md.  ``prefetch_depth=`` sets how
    many batches the device stage reads ahead (default:
    ``MXTPU_PREFETCH_DEPTH`` env, else 2 — double buffering).
    """

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=True, timeout=120,
                 prefetch_to_device=False, prefetch_depth=None):
        self._dataset = dataset
        self._timeout = timeout
        self._prefetch_to_device = prefetch_to_device
        # device-stage read-ahead depth (batches staged on device beyond
        # the one being consumed); None -> MXTPU_PREFETCH_DEPTH, default 2
        self._prefetch_depth = prefetch_depth
        if batch_sampler is None:
            if batch_size is None:
                raise MXNetError(
                    "batch_size must be specified unless batch_sampler is "
                    "specified")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise MXNetError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise MXNetError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._thread_pool = thread_pool
        self._mp_pool = None

    def __iter__(self):
        if self._prefetch_to_device:
            # overlap H2D with consumer compute: batches arrive already
            # device-resident (sharded over an active parallel mesh) —
            # see io.DevicePrefetcher / docs/INPUT_PIPELINE.md
            from ...io import DevicePrefetcher
            pf = DevicePrefetcher(self._host_iter(),
                                  depth=self._prefetch_depth)
            try:
                yield from pf
            finally:
                pf.close()
        else:
            yield from self._host_iter()

    def _host_iter(self):
        from ... import debug as _debug
        if self._num_workers == 0 or _debug.determinism_enabled():
            # MXTPU_ENFORCE_DETERMINISM: random transforms draw from the
            # global numpy RNG; worker-thread interleaving would reorder the
            # draws, so the pipeline runs synchronously (throughput for
            # reproducibility, like the reference's ENFORCE_DETERMINISM
            # rejecting fast non-deterministic cuDNN algos)
            for batch in self._batch_sampler:
                yield self._batchify_fn([self._dataset[i] for i in batch])
            return
        if self._thread_pool:
            yield from self._threaded_iter()
        else:
            # reference default: worker processes (dataset + batchify must
            # pickle; results come back as numpy and re-materialize here)
            yield from self._process_iter()

    def _ensure_mp_pool(self):
        if self._mp_pool is None:
            import multiprocessing as mp
            ctx = mp.get_context("spawn")
            # Two-layer CPU pin for the spawned workers (a worker must
            # never race the parent for the chip):
            #  1. HERE, around the spawn: JAX_PLATFORMS=cpu in the
            #     parent's os.environ, which children inherit at exec.
            #  2. Inside the initargs pickle (_CpuPinnedPayload), which
            #     re-applies the pin at unpickle time — covers Pool's
            #     respawn of a dead worker, where (1) is long restored.
            saved = os.environ.get("JAX_PLATFORMS")
            os.environ["JAX_PLATFORMS"] = "cpu"
            try:
                self._mp_pool = ctx.Pool(
                    self._num_workers, initializer=_mp_worker_init,
                    initargs=(_CpuPinnedPayload(self._dataset),
                              _CpuPinnedPayload(self._batchify_fn)))
            finally:
                if saved is None:
                    os.environ.pop("JAX_PLATFORMS", None)
                else:
                    os.environ["JAX_PLATFORMS"] = saved
        return self._mp_pool

    def _process_iter(self):
        try:
            pool = self._ensure_mp_pool()
        except Exception as e:   # unpicklable dataset/transform etc.
            raise MXNetError(
                f"DataLoader process workers failed to start ({e}); pass "
                f"thread_pool=True for in-process workers (required when "
                f"the dataset or transforms are not picklable)") from e
        batches = list(self._batch_sampler)
        depth = max(self._prefetch, self._num_workers, 1)
        pending = {}
        nxt = 0
        for want in range(len(batches)):
            while nxt < len(batches) and len(pending) < depth:
                pending[nxt] = pool.apply_async(_mp_worker_fn,
                                                (batches[nxt],))
                nxt += 1
            try:
                item = pending.pop(want).get(timeout=self._timeout)
            except Exception as e:
                if "Timeout" in type(e).__name__:
                    raise MXNetError(
                        f"DataLoader worker timed out after "
                        f"{self._timeout}s waiting for batch {want}")
                raise
            yield _from_host(item)

    def __del__(self):
        pool = getattr(self, "_mp_pool", None)
        if pool is not None:
            try:
                pool.terminate()
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass

    def _threaded_iter(self):
        batches = list(self._batch_sampler)
        stop = threading.Event()
        # permits bound decoded-but-unconsumed batches (prefetch depth)
        sem = threading.Semaphore(max(self._prefetch, self._num_workers, 1))
        in_q = _queue.SimpleQueue()
        for item in enumerate(batches):
            in_q.put(item)
        results = _queue.SimpleQueue()

        def worker():
            while not stop.is_set():
                if not sem.acquire(timeout=0.1):
                    continue
                try:
                    idx, samples = in_q.get_nowait()
                except _queue.Empty:
                    sem.release()
                    return
                try:
                    results.put((idx, self._batchify_fn(
                        [self._dataset[i] for i in samples])))
                except Exception as e:  # propagate to consumer
                    results.put((idx, e))

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(self._num_workers)]
        for t in threads:
            t.start()
        buffered = {}
        try:
            for want in range(len(batches)):
                while want not in buffered:
                    try:
                        idx, item = results.get(timeout=self._timeout)
                    except _queue.Empty:
                        raise MXNetError(
                            f"DataLoader worker timed out after "
                            f"{self._timeout}s waiting for batch {want}")
                    buffered[idx] = item
                item = buffered.pop(want)
                sem.release()
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            # unblocks workers even if iteration is abandoned mid-epoch
            stop.set()

    def __len__(self):
        return len(self._batch_sampler)
