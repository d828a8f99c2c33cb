"""Overlapped input pipeline: decode -> H2D -> compute run concurrently.

The MLPerf TPU-pod lesson (PAPERS.md: Kumar et al. on MLPerf-0.6 TPU-v3
pods and "Exploring the limits of Concurrency in ML Training on Google
TPUs"): at pod scale the step time is set by whichever of {host decode,
H2D transfer, device compute} is slowest — *if* they are pipelined.  Run
serially they add up.  This module provides the two pipeline stages the
reference framework ran inside its C++ engine (iter_prefetcher.h +
threaded decode pool):

``AsyncDecodeIter``
    fans a per-sample decode function out over a thread pool and yields
    in-order batches — the host-side stage.  JPEG decode in cv2/PIL
    releases the GIL, so threads scale to the core count.

``DevicePrefetcher``
    double-buffers batches onto the device: a background thread
    ``jax.device_put``s batch N+1 (onto the active ``parallel`` mesh's
    data sharding when one is present) and *blocks on the transfer in
    the worker* while the consumer's step computes on batch N.  The
    consumer always receives device-resident arrays.

Both record per-stage wall time (decode / H2D / consumer compute /
consumer stall) in a ``PipelineStats`` (its ``summary()`` carries an
``overlap_efficiency`` figure) and as ``io.*`` spans of
``telemetry.tracing`` (docs/OBSERVABILITY.md has the tree), each stage
from one pair of clock reads.
"""
from __future__ import annotations

import threading
import time
import queue as _queue
import weakref

import numpy as _np

from ..base import MXNetError
from ..lint import racecheck as _racecheck
from ..ndarray.ndarray import NDArray
from .. import telemetry as _telem
from ..telemetry import tracing as _tracing

__all__ = ["DevicePrefetcher", "AsyncDecodeIter", "PipelineStats",
           "default_prefetch_depth"]


def default_prefetch_depth():
    """Prefetch depth when the caller does not pass one:
    ``MXTPU_PREFETCH_DEPTH`` (>= 1), default 2 (double buffering)."""
    import os
    try:
        depth = int(os.environ.get("MXTPU_PREFETCH_DEPTH", "2"))
    except ValueError:
        raise MXNetError(
            f"MXTPU_PREFETCH_DEPTH={os.environ['MXTPU_PREFETCH_DEPTH']!r}"
            f": expected an integer >= 1")
    if depth < 1:
        raise MXNetError(
            f"MXTPU_PREFETCH_DEPTH must be >= 1, got {depth}")
    return depth


class PipelineStats:
    """Wall-time accumulator for the pipeline stages.

    ``decode`` / ``h2d`` are measured in the producer thread, ``compute``
    / ``stall`` in the consumer thread; because the stages overlap, the
    stage totals may legitimately sum to more than the elapsed wall
    time — that surplus *is* the overlap.
    """

    def __init__(self):
        self._lock = _racecheck.make_lock("PipelineStats._lock")
        self.decode_s = 0.0
        self.h2d_s = 0.0
        self.compute_s = 0.0
        self.stall_s = 0.0
        self.batches = 0
        self.h2d_bytes = 0

    def add(self, stage, dt, nbytes=0):
        with self._lock:
            setattr(self, stage + "_s", getattr(self, stage + "_s") + dt)
            if stage == "h2d":
                self.h2d_bytes += nbytes
                self.batches += 1
        # mirror onto the process telemetry registry (ISSUE 9): the
        # per-instance accumulator is what the caller reads; the
        # registry is what a live scrape sees
        if _telem.enabled():
            _telem.observe(f"io.{stage}_ms", dt * 1e3)
            if stage == "h2d" and nbytes:
                _telem.inc("io.h2d_bytes", nbytes)

    def summary(self):
        """Per-stage ms/batch plus ``overlap_efficiency`` — the fraction
        of consumer wall time spent computing rather than stalled
        waiting for input (1.0 = input pipeline fully hidden)."""
        # snapshot under the lock (HB14: the producer thread's add() is
        # mid-update otherwise — a torn batches/decode_s pair skews the
        # per-batch figures); compute after release
        with self._lock:
            decode_s, h2d_s = self.decode_s, self.h2d_s
            compute_s, stall_s = self.compute_s, self.stall_s
            batches, h2d_bytes = self.batches, self.h2d_bytes
        n = max(batches, 1)
        busy = compute_s + stall_s
        out = {
            "batches": batches,
            "decode_ms_per_batch": round(decode_s / n * 1e3, 2),
            "h2d_ms_per_batch": round(h2d_s / n * 1e3, 2),
            "compute_ms_per_batch": round(compute_s / n * 1e3, 2),
            "stall_ms_per_batch": round(stall_s / n * 1e3, 2),
            "overlap_efficiency": round(compute_s / busy, 4)
            if busy > 0 else None,
        }
        if h2d_bytes and h2d_s > 0:
            out["h2d_gb_s"] = round(h2d_bytes / h2d_s / 1e9, 2)
        return out


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------

class _EndOfStream:
    pass


class _WorkerFailure:
    def __init__(self, exc):
        self.exc = exc


_END = _EndOfStream()


class _Stage:
    """One worker stage under its scoped span, timed once: the span's own
    two stamps also feed ``PipelineStats`` (and through it the registry's
    ``io.<stage>_ms`` histogram); with ``MXTPU_TRACE=0`` there is no span
    and the stage reads the clock itself.  A stage that ends in
    ``StopIteration`` found no work: no span, no sample."""

    __slots__ = ("_stats", "_stage", "_scope", "_t0", "span", "nbytes")

    def __init__(self, stats, stage, name):
        self._stats, self._stage = stats, stage
        self._scope = _tracing.span(name)
        self.nbytes = 0

    def __enter__(self):
        self.span = self._scope.__enter__()
        self._t0 = self.span.t0 if self.span.t0 is not None \
            else time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc):
        if exc_type is StopIteration:
            _tracing.discard(self.span)
        self._scope.__exit__(exc_type, *exc)
        if exc_type is None:
            t1 = self.span.t1 if self.span.t1 is not None \
                else time.perf_counter()
            self._stats.add(self._stage, t1 - self._t0, self.nbytes)
        return False


def _batch_nbytes(batch):
    """Exact bytes of one delivered batch (tuple/list of array leaves);
    0 when nothing measurable — the gauge then stays unset, never a
    fabricated zero (ISSUE 15 memory honesty)."""
    leaves = batch if isinstance(batch, (tuple, list)) else (batch,)
    total = 0
    for leaf in leaves:
        n = getattr(leaf, "nbytes", None)
        if n is None:
            n = getattr(getattr(leaf, "_data", None), "nbytes", None)
        if isinstance(n, int):
            total += n
    return total


class DevicePrefetcher:
    """Iterator wrapper that stages batches onto the device ahead of use.

    A background thread pulls batch N+1 from ``source``, ``device_put``s
    every array leaf (sharded over the mesh data axis when a mesh is
    given or a ``parallel.mesh_scope`` is active) and *blocks on the
    transfer in the worker thread*, so by the time the consumer asks for
    it the batch is already device-resident.  With ``depth=2`` this is
    classic double buffering: H2D of batch N+1 overlaps compute of N.

    ``source`` may yield ``io.DataBatch``es, (nested) tuples/lists of
    arrays, or single arrays; leaves may be numpy arrays, NDArrays, or
    jax arrays.  Structure is preserved; array leaves come back as
    device-resident :class:`NDArray`.

    Contract (tested under ``JAX_PLATFORMS=cpu``):

    * batches arrive in source order;
    * ``StopIteration`` propagates when the source is exhausted (and
      keeps raising on further calls);
    * an exception raised by the source or the transfer surfaces in the
      consumer at the position it occurred;
    * after exhaustion/close() the worker thread is joined — no leaked
      threads.
    """

    def __init__(self, source, depth=None, mesh=None, sharding=None,
                 batch_axis=0, data_axis=None, timeout=600.0,
                 to_device=True):
        if depth is None:
            depth = default_prefetch_depth()
        if depth < 1:
            raise MXNetError("DevicePrefetcher: depth must be >= 1")
        self._source = source
        self._depth = depth
        self._timeout = timeout
        self._batch_axis = batch_axis
        self._sharding = sharding
        self._to_device = to_device
        if mesh is None and sharding is None and to_device:
            from ..parallel.mesh import current_mesh
            mesh = current_mesh()
        self._mesh = mesh
        self._data_axis = data_axis
        self.stats = PipelineStats()
        self._queue = None
        self._thread = None
        self._stop = threading.Event()
        self._finished = False
        self._last_yield = None
        self._consumed = 0      # batches DELIVERED to the consumer: the
                                # honest resume cursor (the worker reads
                                # ahead of it by up to `depth` batches)
        self._skip = 0          # set_state replay-skip, applied by the
                                # worker on ITS source iterator
        self._batch_nbytes = None   # first delivered batch's exact
                                    # bytes (ISSUE 15 memory honesty)

    # -- sharding -------------------------------------------------------
    def _leaf_sharding(self, x):
        if self._sharding is not None:
            return self._sharding(x) if callable(self._sharding) \
                else self._sharding
        if self._mesh is None:
            return None
        from ..parallel.mesh import batch_sharding
        return batch_sharding(self._mesh, getattr(x, "ndim", 0),
                              batch_axis=self._batch_axis,
                              data_axis=self._data_axis)

    def _put_leaf(self, x, n):
        import jax
        raw = x.data if isinstance(x, NDArray) else x
        if not hasattr(raw, "ndim"):       # scalars, bucket keys, ...
            return x
        sharding = self._leaf_sharding(raw)
        if sharding is None:
            dev = jax.device_put(raw)
        else:
            dev = jax.device_put(raw, sharding)
        out = NDArray(dev)
        out._io_batch = n       # what train.step's span says it consumed
        return out

    def _nbytes(self, x):
        raw = x.data if isinstance(x, NDArray) else x
        return getattr(raw, "nbytes", 0)

    def _transfer(self, item, n):
        if not self._to_device:
            # host-only prefetch (legacy io.PrefetchingIter semantics):
            # the worker's time-in-source is still the decode stat
            return item, 0
        from . import DataBatch

        def rec(obj):
            if isinstance(obj, DataBatch):
                return DataBatch(
                    data=None if obj.data is None else
                    [self._put_leaf(d, n) for d in obj.data],
                    label=None if obj.label is None else
                    [self._put_leaf(l, n) for l in obj.label],
                    pad=obj.pad, index=obj.index,
                    bucket_key=obj.bucket_key,
                    provide_data=obj.provide_data,
                    provide_label=obj.provide_label)
            if isinstance(obj, (list, tuple)):
                return type(obj)(rec(o) for o in obj)
            return self._put_leaf(obj, n)

        def leaves(obj):
            if isinstance(obj, DataBatch):
                for part in (obj.data or []) + (obj.label or []):
                    yield part
            elif isinstance(obj, (list, tuple)):
                for o in obj:
                    yield from leaves(o)
            else:
                yield obj

        nbytes = sum(self._nbytes(l) for l in leaves(item))
        out = rec(item)
        # block in THIS (worker) thread: the consumer must never pay the
        # transfer latency, and the timing below stays honest
        for leaf in leaves(out):
            if isinstance(leaf, NDArray):
                try:
                    leaf.data.block_until_ready()
                except AttributeError:
                    pass
        return out, nbytes

    # -- worker ---------------------------------------------------------
    def _enqueue(self, item):
        """put() that stays responsive to stop(); False if stopping."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    def _produce(self, it, n):
        """Batch ``n`` from the source onto the device, as one trace:
        root ``io.batch`` over ``io.decode`` (the time in ``next(source)``,
        ambient there, so the source's own spans are its children) and
        ``io.h2d``.  A source that is exhausted leaves no span."""
        with _tracing.span("io.batch", batch=n) as root:
            try:
                with _Stage(self.stats, "decode", "io.decode"):
                    item = next(it)
            except StopIteration:
                _tracing.discard(root)
                raise
            with _Stage(self.stats, "h2d", "io.h2d") as stage:
                dev_item, stage.nbytes = self._transfer(item, n)
                _tracing.annotate(stage.span, bytes=stage.nbytes)
        return dev_item

    def _worker(self, n):
        """``n`` numbers the first batch: 0, or ``set_state``'s cursor."""
        try:
            it = iter(self._source)
            while self._skip > 0:   # set_state replay-skip (sources
                self._skip -= 1     # without their own cursor)
                try:
                    next(it)
                except StopIteration:
                    self._skip = 0
                    break
        except Exception as e:  # noqa: BLE001 — surface in consumer
            self._enqueue(_WorkerFailure(e))
            return
        while not self._stop.is_set():
            try:
                dev_item = self._produce(it, n)
            except StopIteration:
                self._enqueue(_END)
                return
            except Exception as e:  # noqa: BLE001 — surface in consumer
                self._enqueue(_WorkerFailure(e))
                return
            if not self._enqueue((dev_item, n)):
                return
            n += 1

    def _ensure_started(self):
        if self._thread is None and not self._finished:
            self._queue = _queue.Queue(maxsize=self._depth)
            if _telem.enabled():
                _telem.set_gauge("io.prefetch_depth", self._depth)
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._worker, args=(self._consumed,),
                name="mxtpu-device-prefetch", daemon=True)
            self._thread.start()

    # -- consumer -------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        self._ensure_started()
        queued = self._queue.qsize()    # 0: this get really waits
        now = time.perf_counter()
        if self._last_yield is not None:
            self.stats.add("compute", now - self._last_yield)
        try:
            got = self._queue.get(timeout=self._timeout)
        except _queue.Empty:
            self.close()
            raise MXNetError(
                f"DevicePrefetcher: no batch after {self._timeout}s "
                f"(worker stalled or source hung)")
        t_got = time.perf_counter()
        self.stats.add("stall", t_got - now)
        _tracing.record("io.wait", now, t_got, queued=queued,
                        batch=got[1] if isinstance(got, tuple) else None)
        if _telem.enabled():
            # read-ahead occupancy AFTER this get: depth batches queued
            # = the worker is fully ahead; 0 = the consumer is about to
            # stall on the next call
            _telem.set_gauge("io.prefetch_queue_depth",
                             self._queue.qsize())
        if got is _END:
            self._shutdown()
            raise StopIteration
        if isinstance(got, _WorkerFailure):
            self._shutdown()
            raise got.exc
        if _telem.enabled():
            # memory honesty (ISSUE 15): exact read-ahead buffer bytes
            # (queued batches + the one being handed out), so an OOM
            # post-mortem can name the prefetch pipeline.  Batch size
            # is measured once — the feed is fixed-shape by design.
            if self._batch_nbytes is None:
                self._batch_nbytes = _batch_nbytes(got[0])
            if self._batch_nbytes:
                _telem.set_gauge(
                    "io.prefetch_buffer_bytes",
                    self._batch_nbytes * (self._queue.qsize() + 1))
        self._last_yield = t_got
        self._consumed += 1
        return got[0]

    def next(self):
        return self.__next__()

    def next_k(self, k):
        """Up to ``k`` consecutive batches as a list (the multi-step
        feed: ``DataParallelTrainer.step_multi`` scans them in ONE
        dispatch, ISSUE 6).  The worker keeps prefetching ahead as
        usual, so collecting a window does not drain the pipeline.
        Returns fewer than ``k`` at end-of-stream; raises
        ``StopIteration`` only when not even one batch is left —
        callers flush the partial tail window, they never lose it."""
        if k < 1:
            raise MXNetError("DevicePrefetcher.next_k: k must be >= 1")
        out = []
        for _ in range(int(k)):
            try:
                out.append(self.__next__())
            except StopIteration:
                if out:
                    return out
                raise
        return out

    def windows(self, k):
        """Iterate the stream as lists of up to ``k`` batches (the last
        window may be short) — sugar over :meth:`next_k` for K-step
        training loops."""
        while True:
            try:
                yield self.next_k(k)
            except StopIteration:
                return

    def __len__(self):
        return len(self._source)

    # -- lifecycle ------------------------------------------------------
    def _shutdown(self):
        self._finished = True
        self._stop.set()
        # unblock a worker stuck in put(); queue may hold device arrays
        while self._queue is not None:
            try:
                self._queue.get_nowait()
            except _queue.Empty:
                break
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def close(self):
        """Stop the worker and join it. Idempotent."""
        self._shutdown()
        close = getattr(self._source, "close", None)
        if callable(close):
            try:
                close()
            except Exception:  # noqa: BLE001 — best-effort source cleanup
                pass

    def reset(self):
        """Restart from the source's beginning (source must support
        ``reset``)."""
        self._shutdown()
        reset = getattr(self._source, "reset", None)
        if callable(reset):
            reset()
        self._finished = False
        self._last_yield = None
        self._consumed = 0
        self._skip = 0

    # -- checkpoint cursor protocol -------------------------------------
    @property
    def batches_consumed(self):
        return self._consumed

    def state_dict(self):
        """Resume cursor: batches DELIVERED (not the worker's read-ahead
        position — up to ``depth`` prefetched-but-unconsumed batches must
        be replayed, not skipped).  Includes the source's own cursor when
        it has one."""
        state = {"batches_consumed": self._consumed}
        src_state = getattr(self._source, "state_dict", None)
        if callable(src_state):
            s = src_state()
            if s:
                state["source"] = s
        return state

    def set_state(self, state):
        """Reposition: reset, then either hand the source its own cursor
        (no replay decode) or have the worker skip-replay
        ``batches_consumed`` batches on ITS iterator (never through the
        device stage)."""
        self.reset()
        n = int(state.get("batches_consumed", 0))
        src_set = getattr(self._source, "set_state", None)
        if "source" in state and callable(src_set):
            src_set(state["source"])
            self._skip = 0
        else:
            self._skip = n
        self._consumed = n

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self._stop.set()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


# ---------------------------------------------------------------------------
# AsyncDecodeIter
# ---------------------------------------------------------------------------

#: weakrefs to decode-pool worker threads whose owning pool's
#: ``close()`` HAS run (work cancelled, shutdown signalled) but which
#: may still be finishing one in-flight sample decode.  The tests'
#: thread-leak guard reads this through :func:`closing_thread_idents`
#: to tell "mid-shutdown with a closer" (longer grace) from a genuine
#: leak (no closer ever ran).  Weakrefs, not idents: OS thread idents
#: are REUSED, so a bare-ident set would let a later genuinely-leaked
#: thread inherit a stale entry's grace — and grow forever.
_CLOSING_THREADS = []


def closing_thread_idents():
    """Idents of still-alive threads registered by a pool ``close()``.
    Exited (or collected) threads are pruned on every read, so the
    registry stays bounded and a reused ident never matches."""
    alive, out = [], set()
    for ref in _CLOSING_THREADS:
        t = ref()
        if t is not None and t.is_alive():
            alive.append(ref)
            if t.ident is not None:
                out.add(t.ident)
    _CLOSING_THREADS[:] = alive
    return out


class AsyncDecodeIter:
    """Fan per-sample decode out over ``n_workers`` threads, yield
    in-order batches.

    ``sample_fn(index)`` decodes one sample (any pickling-free value);
    ``order`` is the index sequence; batches of ``batch_size`` samples
    are submitted ``lookahead`` batches ahead of the consumer, so worker
    threads decode batch N+1..N+lookahead while the consumer holds batch
    N.  Sample-level parallelism *within* a batch comes for free from
    the shared pool.

    Exceptions raised by ``sample_fn`` surface at the consumer in batch
    order; ``close()`` cancels pending work and shuts the pool down.
    """

    def __init__(self, sample_fn, order, batch_size, n_workers=4,
                 lookahead=2, drop_last=True):
        from concurrent.futures import ThreadPoolExecutor
        if batch_size < 1:
            raise MXNetError("AsyncDecodeIter: batch_size must be >= 1")
        self._fn = sample_fn
        order = list(order)
        n = len(order) - (len(order) % batch_size if drop_last else 0)
        self._plan = [order[i:i + batch_size]
                      for i in range(0, n, batch_size)]
        self._n_workers = max(1, int(n_workers))
        self._lookahead = max(1, lookahead)
        self._pool = ThreadPoolExecutor(
            max_workers=self._n_workers,
            thread_name_prefix="mxtpu-decode")
        self._pending = []          # FIFO of [futures] per batch
        self._next_submit = 0
        self._closed = False
        self.stats = PipelineStats()

    def _fill(self):
        while self._next_submit < len(self._plan) and \
                len(self._pending) < self._lookahead:
            futs = [self._pool.submit(self._fn, i)
                    for i in self._plan[self._next_submit]]
            self._pending.append(futs)
            self._next_submit += 1

    def __iter__(self):
        return self

    def __len__(self):
        return len(self._plan)

    def __next__(self):
        if self._closed:
            raise StopIteration
        t0 = time.perf_counter()
        self._fill()
        if not self._pending:
            self.close()
            raise StopIteration
        futs = self._pending.pop(0)
        try:
            results = [f.result() for f in futs]
        except BaseException:
            self.close()
            raise
        self._fill()       # keep the pool primed while consumer computes
        # the whole call: handing the pool its work competes with the
        # pool's threads for the interpreter, and is part of the wait
        t1 = time.perf_counter()
        self.stats.add("decode", t1 - t0)
        _tracing.record("io.rec.fetch", t0, t1, native=False)
        return results

    def next(self):
        return self.__next__()

    def close(self, timeout_s=10.0):
        if self._closed:
            return
        self._closed = True
        for futs in self._pending:
            for f in futs:
                f.cancel()
        self._pending = []
        # JOIN the pool threads, but with a DEADLINE: the old
        # wait=True shutdown blocked close() (and test teardown) for as
        # long as one stuck sample decode — the known test_real_data
        # teardown flake on a loaded host.  Pending work was cancelled
        # above, so the join normally returns within one in-flight
        # decode; a straggler past the deadline is left to finish on
        # its own, and its ident is registered so the conftest
        # thread-leak guard knows a closer RAN and grants the longer
        # mid-shutdown grace instead of calling it a leak.
        self._pool.shutdown(wait=False, cancel_futures=True)
        threads = [t for t in getattr(self._pool, "_threads", ())
                   if t is not None]
        for t in threads:
            _CLOSING_THREADS.append(weakref.ref(t))
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
