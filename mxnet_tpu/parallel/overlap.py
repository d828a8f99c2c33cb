"""Backward-overlapped gradient communication (ISSUE 5 tentpole).

PR 3 made the data-parallel gradient sync bucketed (parallel/zero.py),
but every bucket's collective still launched only after the WHOLE
backward finished — the serialization that "Exploring the limits of
Concurrency in ML Training on Google TPUs" (arXiv:2011.03641) and the
MLPerf TPU-v3 pod paper (arXiv:1909.09756) identify as the dominant
scaling loss.  Both fix it the same way: start summing each gradient
bucket the moment its gradients are ready, so communication rides under
the remaining backprop compute.

Two halves, one per training path:

- **eager** (``gluon.Trainer``): :class:`OverlapScheduler` here.  It
  registers autograd grad-ready hooks (``_tape.register_grad_ready_hook``
  — they fire in backward order) on every parameter, groups parameters
  into backward-ordered buckets (``zero.BucketPlan(fill_order=...)``
  built from the ORDER OBSERVED on the first backward), and dispatches
  one bucketed ``kvstore.pushpull`` per bucket as soon as the bucket's
  last gradient lands — while backprop is still running.  Dispatch is
  async (jax eager dispatch does not block); ``finish()`` — called from
  ``trainer.step`` — only waits on the tail bucket.
- **in-graph** (``parallel.DataParallelTrainer``): the traced ZeRO-1
  step already makes each bucket's ``reduce_scatter_bucket`` data-
  dependent only on that bucket's own gradients; the scheduler's job
  there is done by the backward-ordered ``BucketPlan`` (buckets complete
  early-to-late during the XLA backward) plus XLA's latency-hiding
  scheduler (``runtime.lhs_flags()`` / ``MXTPU_LHS=1``), which is free
  to hoist each collective under the remaining backward compute.

``MXTPU_OVERLAP_COMM=0`` is the kill switch for both halves: bucket
plans revert to declaration order and the scheduler stands down, which
reproduces the PR 3 monolithic-sync graphs bitwise.
"""
from __future__ import annotations

import time

from ..base import MXNetError
from . import zero as _zero

__all__ = ["OverlapScheduler"]


class OverlapScheduler:
    """Dispatch per-bucket gradient communication from grad-ready hooks.

    ``params`` is the trainer's parameter list; ``keys[i]`` is the
    kvstore key of ``params[i]`` (defaults to the list position, the
    ``gluon.Trainer`` convention).  ``n_accum > 1`` supports gradient
    accumulation: hooks count backward passes per parameter and only
    the final microbatch of each cycle dispatches communication — the
    intermediate backwards accumulate locally for free.

    Lifecycle per optimization cycle::

        install()                      # once, after net.initialize()
        for micro in range(n_accum):
            loss.backward()            # hooks fire; ready buckets launch
        scheduler.finish()             # trainer.step calls this: launch
                                       # stragglers, wait on tail bucket

    The first cycle observes the hook firing order (the true backward
    order of THIS model) and builds the backward-ordered
    ``zero.BucketPlan`` from it; that first cycle therefore dispatches
    monolithically from ``finish()``.  Every later cycle launches
    bucket-by-bucket from inside backward.

    Without a multi-worker kvstore there is nothing to reduce; the
    scheduler still runs its bookkeeping and profiler spans
    (``overlap.bucket_ready`` / ``overlap.bucket_launch`` /
    ``overlap.tail_wait``) so the overlap is observable anywhere.
    """

    def __init__(self, params, kvstore=None, n_accum=1, bound_bytes=None):
        if n_accum < 1:
            raise MXNetError("OverlapScheduler: n_accum must be >= 1")
        self._all_params = list(params)
        self._all_keys = list(range(len(self._all_params)))
        self._kvstore = kvstore
        self._n_accum = int(n_accum)
        self._bound = bound_bytes
        # active set: grad-carrying, initialized params
        self._idxs = [i for i, p in enumerate(self._all_params)
                      if getattr(p, "grad_req", "write") != "null"
                      and getattr(p, "_data", None) is not None]
        self._handles = []
        self._fired = {i: 0 for i in self._idxs}
        self._observed = []            # first-cycle backward order
        self._observed_set = set()
        self._plan = None              # zero.BucketPlan over active idxs
        self._pos = {}                 # param idx -> position in plan
        self._param_bucket = {}        # param idx -> bucket id
        self._remaining = []           # per bucket: set of pending idxs
        self._launched = set()
        self._tail = None              # last launched bucket's grads

    # -- plan -----------------------------------------------------------
    @property
    def plan(self):
        return self._plan

    def _build_plan(self):
        """Backward-ordered bucket assignment from the OBSERVED firing
        order (reverse-topological fill); params that never fired this
        cycle (e.g. frozen branches) append in declaration order."""
        ready = list(self._observed)
        ready += [i for i in self._idxs if i not in self._observed_set]
        self._pos = {i: k for k, i in enumerate(ready)}
        shapes = [self._all_params[i].shape for i in ready]
        # fill_order=None: `ready` IS already the fill order of `shapes`
        self._plan = _zero.BucketPlan(
            shapes, dp=1,
            bound_bytes=self._bound if self._bound is not None
            else _zero.bucket_bound_bytes())
        self._order = ready
        self._param_bucket = {}
        for b, idxs in enumerate(self._plan.buckets):
            for k in idxs:
                self._param_bucket[ready[k]] = b
        self._reset_cycle()

    def _reset_cycle(self):
        self._remaining = [set(self._order[k] for k in idxs)
                           for idxs in self._plan.buckets]
        self._launched = set()

    # -- hooks ----------------------------------------------------------
    def install(self):
        """Register grad-ready hooks on every active parameter."""
        if self._handles:
            return self
        from .. import _tape
        for i in self._idxs:
            arr = self._all_params[i]._data
            self._handles.append(_tape.register_grad_ready_hook(
                arr, self._make_hook(i)))
        return self

    def remove(self):
        for h in self._handles:
            h.remove()
        self._handles = []

    def reset_plan(self):
        """Forget the observed backward order and the bucket plan (the
        elastic-reshard hook: after a world-size change the kvstore ring
        and the profitable bucket layout both changed).  The next cycle
        re-observes and dispatches monolithically from ``finish()``,
        exactly like the first cycle after ``install()``."""
        self._plan = None
        self._observed = []
        self._observed_set = set()
        self._param_bucket = {}
        self._remaining = []
        self._launched = set()
        self._tail = None
        self._fired = {i: 0 for i in self._idxs}

    def _make_hook(self, i):
        def hook(arr):
            self._on_ready(i)
        return hook

    def _on_ready(self, i):
        self._fired[i] = self._fired.get(i, 0) + 1
        if self._fired[i] % self._n_accum != 0:
            return                  # mid-accumulation: local add only
        if self._plan is None:
            if i not in self._observed_set:
                self._observed_set.add(i)
                self._observed.append(i)
            return                  # first cycle: order discovery
        b = self._param_bucket.get(i)
        if b is None or b in self._launched:
            return
        rem = self._remaining[b]
        rem.discard(i)
        if not rem:
            now = time.perf_counter()
            _span(f"overlap.bucket_ready.{b}", now, now)
            self._launch(b)

    # -- dispatch -------------------------------------------------------
    def _launch(self, b):
        """One bucketed communication round for bucket ``b`` — async
        dispatch; nothing here blocks on the wire."""
        from ..ndarray import sparse as _sp
        self._launched.add(b)
        keys, grads, params = [], [], []
        for k in self._plan.buckets[b]:
            i = self._order[k]
            p = self._all_params[i]
            d = p._data
            if d is None or d._grad_req == "null" or d._grad_reduced:
                continue
            g = p.grad()
            if g is None or isinstance(g, _sp.RowSparseNDArray):
                continue    # row_sparse rides the batched kvstore path
            keys.append(self._all_keys[i])
            grads.append(g)
            params.append(p)
        if not keys:
            return
        t0 = time.perf_counter()
        kv = self._kvstore
        if kv is not None and getattr(kv, "num_workers", 1) > 1:
            kv.pushpull(keys, grads, out=grads)
            for p, g in zip(params, grads):
                p._data._grad = g.data
                p._data._grad_reduced = True
        self._tail = grads
        _span(f"overlap.bucket_launch.{b}", t0, time.perf_counter())

    def finish(self):
        """Called from ``trainer.step``: complete the cycle.  Launches
        any bucket that has not gone out yet (first cycle: all of them,
        monolithically) and waits ONLY on the tail bucket — earlier
        buckets were dispatched during backward and their results are
        ordered before the tail by the runtime."""
        if self._plan is None:
            if not self._observed and not self._idxs:
                return
            self._build_plan()
        for b in self._plan.ready_order:
            if b not in self._launched:
                self._launch(b)
        if self._tail is not None:
            import jax
            t0 = time.perf_counter()
            jax.block_until_ready([g.data for g in self._tail])
            _span("overlap.tail_wait", t0, time.perf_counter())
            self._tail = None
        self._reset_cycle()


def _span(name, t0, t1):
    # the comm spans nest under whatever step/backward span is ambient
    # on the dispatching thread (ISSUE 14): bucket launches that fire
    # during backward show up INSIDE the step timeline
    from ..telemetry import tracing
    tracing.record(name, t0, t1)
