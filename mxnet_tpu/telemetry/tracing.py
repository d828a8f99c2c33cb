"""End-to-end causal tracing: trace/span trees over the telemetry spine.

PR 9's registry records *aggregate* metrics (a TTFT histogram, a
step_ms gauge) but nothing causally links one request's life (router
admission -> queue -> prefill chunk(s) -> decode boundaries -> finish)
or one training step's phases (prepare -> h2d -> dispatch -> commit).
The MLPerf TPU-pod analysis (arXiv:1909.09756) and the
concurrency-limits study (arXiv:2011.03641) attribute their wins to
exactly this per-phase timeline attribution — you cannot close an MFU
gap or a p99 tail you cannot decompose.  This module is that timeline:

- **spans** with deterministic per-process ids (monotonic counters —
  two identical runs produce identical trees, the twin-request gate in
  tests/test_tracing.py), a ``trace`` id (the root span's id), a
  ``parent`` id, ``[t0, t1]`` stamps from an injectable clock, and
  JSON-able ``args``; beside them ``t0_ns`` / ``t1_ns``, the same two
  moments on the clock the JAX profiler stamps host and device events
  with (``time.time_ns``), read at the span and not derived from one
  offset per process, so a reader can lay a span over a device trace;
- **ambient context** per thread (:func:`span` nests automatically)
  with EXPLICIT cross-thread propagation — :func:`capture` on the
  owning thread, :func:`activate` on the worker (router replica
  workers and the async checkpoint writer do this), so a span started
  on a worker thread parents under the trace that spawned the work
  (``DevicePrefetcher``'s worker does not: each batch it makes is a
  trace of its own, ``io.batch``);
- **manual spans** (:func:`start` / :func:`finish` / :func:`record`)
  for lifecycles that cross call boundaries — a serving request's root
  span lives on the ``Request`` object from admission to finish,
  surviving a drain-and-requeue hop across replicas;
- **profiler annotations**: a scoped :func:`span` also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so a profile anyone
  takes shows the program's spans beside the device lines;
- **compiles** (:func:`install_compile_listener`): every backend compile
  JAX reports becomes a pre-timed ``jit.compile`` child of whatever span
  is ambient, and bumps the counter ``jit.compiles``;
- **Chrome-trace/perfetto export** (:func:`chrome_trace`): finished
  spans as complete ``"X"`` events merged with ``mx.profiler``'s
  Task/Frame/Counter/Marker events — one timeline for both
  (``tools/telemetry_dump.py --trace out.json``).

``MXTPU_TRACE=0`` is a bitwise-inert kill switch in the PR 9 style:
every helper is one module-bool check, :func:`span` hands back one
shared no-op context manager, and nothing allocates.  The ring is
bounded by ``MXTPU_TRACE_RING`` (default 4096 finished spans).  Span
taxonomy and the export workflow: docs/OBSERVABILITY.md §Tracing.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

from ..lint import racecheck as _racecheck

__all__ = ["Span", "enabled", "configure", "configure_from_env",
           "reset", "clock", "span", "start", "finish", "discard",
           "record", "annotate", "current", "capture", "activate", "spans",
           "dropped", "chrome_trace", "install_compile_listener"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _env_enabled():
    return os.environ.get("MXTPU_TRACE", "1") != "0"


def _env_ring():
    try:
        return max(1, int(os.environ.get("MXTPU_TRACE_RING", "4096")))
    except ValueError:
        return 4096


class Span:
    """One timed, named node of a trace tree.  ``trace`` is the root
    span's id; ``parent`` is None on roots.  ``t1`` is None while the
    span is open (open spans never export)."""

    __slots__ = ("name", "trace", "span", "parent", "t0", "t1",
                 "t0_ns", "t1_ns", "thread", "args")

    def __init__(self, name, trace, span_id, parent, stamp, args):
        self.name = name
        self.trace = trace
        self.span = span_id
        self.parent = parent
        self.t0, self.t0_ns = stamp
        self.t1 = self.t1_ns = None
        self.thread = threading.current_thread().name
        self.args = args

    def to_record(self):
        return {"name": self.name, "trace": self.trace,
                "span": self.span, "parent": self.parent,
                "t0": self.t0, "t1": self.t1,
                "t0_ns": self.t0_ns, "t1_ns": self.t1_ns,
                "thread": self.thread, "args": dict(self.args)}


class _NullSpan:
    """The disabled-mode span: one shared instance, every method a
    no-op, usable as a context manager and as a ``parent=``."""

    __slots__ = ()
    name = trace = span = parent = t0 = t1 = t0_ns = t1_ns = None
    args = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Tracer:
    """The process-wide span store: deterministic id counter, bounded
    finished-span ring, per-thread ambient span stack."""

    def __init__(self, ring_size=4096, now=None):
        self.ring_size = int(ring_size)
        self._now = now if now is not None else time.perf_counter
        # an injected clock (FakeClock) also gives the ns stamps, so twin
        # runs stay identical; the real one reads both clocks at the span
        self._injected = now is not None
        self._lock = _racecheck.make_lock("telemetry.Tracer._lock")
        self._ring = deque(maxlen=self.ring_size)   # guarded-by: _lock
        self._next_id = 0                           # guarded-by: _lock
        self._dropped = 0                           # guarded-by: _lock
        self._tls = threading.local()               # per-thread ambient

    # -- ids / ambient ---------------------------------------------------
    def _new_id(self):
        with self._lock:
            self._next_id += 1
            return self._next_id

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current(self):
        st = self._stack()
        return st[-1] if st else None

    # -- the two clocks --------------------------------------------------
    def _stamp(self):
        """``(seconds on the span clock, ns on the profiler's clock)``,
        read back to back."""
        t = self._now()
        return t, (int(round(t * 1e9)) if self._injected
                   else time.time_ns())

    def _to_ns(self, t0, t1):
        """A pre-timed ``[t0, t1]`` on the profiler's clock, through an
        offset between the two clocks sampled now (they drift, so never
        one offset per process)."""
        off = 0 if self._injected \
            else time.time_ns() - int(round(self._now() * 1e9))
        return int(round(t0 * 1e9)) + off, int(round(t1 * 1e9)) + off

    # -- span lifecycle --------------------------------------------------
    def start(self, name, parent=None, **args):
        """Open a span (NOT pushed as ambient — the manual API for
        lifecycles that cross call boundaries).  ``parent`` defaults to
        the ambient span; a root span's ``trace`` is its own id."""
        if parent is None:
            parent = self.current()
        sid = self._new_id()
        if parent is None or parent is NULL_SPAN:
            return Span(name, sid, sid, None, self._stamp(), args)
        return Span(name, parent.trace, sid, parent.span, self._stamp(),
                    args)

    def finish(self, sp, **args):
        """Stamp ``t1`` and commit ``sp`` to the ring.  Idempotent on
        the null span and on already-finished spans."""
        if sp is None or sp is NULL_SPAN or sp.t1 is not None:
            return sp
        sp.t1, sp.t1_ns = self._stamp()
        if args:
            sp.args.update(args)
        self._commit(sp.to_record())
        return sp

    def _commit(self, rec):
        """Append a finished record, counting the oldest entry a full
        ring silently evicts — a truncated timeline must be VISIBLY
        truncated (``telemetry.trace.dropped_spans``, and
        :func:`chrome_trace` stamps the count into its output)."""
        with self._lock:
            evicting = len(self._ring) == self.ring_size
            self._ring.append(rec)
            if evicting:
                self._dropped += 1
        if evicting:
            from . import inc       # outside _lock; one counter bump
            inc("telemetry.trace.dropped_spans")

    def record(self, name, t0, t1, parent=None, ns=None, **args):
        """Commit an already-timed ``[t0, t1]`` span in one call (the
        pre-timed form: decode boundaries, prefetcher stage times).
        ``ns`` is the caller's own ``(t0_ns, t1_ns)`` pair where it
        stamped both clocks; without it the pair is converted."""
        if parent is None:
            parent = self.current()
        t0_ns, t1_ns = ns if ns is not None else self._to_ns(t0, t1)
        sid = self._new_id()
        if parent is None or parent is NULL_SPAN:
            sp = Span(name, sid, sid, None, (t0, t0_ns), args)
        else:
            sp = Span(name, parent.trace, sid, parent.span, (t0, t0_ns),
                      args)
        sp.t1, sp.t1_ns = t1, t1_ns
        self._commit(sp.to_record())
        return sp

    def push(self, sp):
        self._stack().append(sp)

    def pop(self, sp):
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()

    def spans(self):
        """Finished spans, oldest first (copies — the ring moves on)."""
        with self._lock:
            return [dict(r) for r in self._ring]

    def dropped(self):
        """Spans the bounded ring has evicted since the last reset."""
        with self._lock:
            return self._dropped

    def reset(self):
        with self._lock:
            self._ring.clear()
            self._next_id = 0
            self._dropped = 0
        # the calling thread's ambient stack; other threads' stacks die
        # with their work
        self._tls = threading.local()


_ENABLED = _env_enabled()
_TRACER = Tracer(ring_size=_env_ring())


def configure(enabled=None, ring_size=None, now=None):
    """Reconfigure tracing (tests; production configures via env).
    ``now`` injects the span clock — the FakeClock seam the
    twin-request determinism gate uses."""
    global _ENABLED, _TRACER
    if enabled is not None:
        _ENABLED = bool(enabled)
    if ring_size is not None or now is not None:
        if now is None and _TRACER._injected:
            now = _TRACER._now
        _TRACER = Tracer(
            ring_size=ring_size if ring_size is not None
            else _TRACER.ring_size, now=now)
    return _ENABLED


def configure_from_env():
    return configure(enabled=_env_enabled(), ring_size=_env_ring())


def enabled():
    """Whether tracing is live (``MXTPU_TRACE`` != 0).  Hot paths check
    this ONCE and skip their clock reads entirely when off — the
    zero-overhead contract."""
    return _ENABLED


def clock():
    """The tracer's span clock (perf_counter unless injected)."""
    return _TRACER._now()


class _Scope:
    """The ambient context-manager span: child of the current ambient
    span, itself ambient for the scope's duration, and for that time a
    ``TraceAnnotation`` of the same name in any profile that runs."""

    __slots__ = ("_sp", "_annotation")

    def __init__(self, name, args):
        self._annotation = TraceAnnotation(name)
        self._sp = _TRACER.start(name, **args)

    def __enter__(self):
        self._annotation.__enter__()
        _TRACER.push(self._sp)
        return self._sp

    def __exit__(self, *exc):
        _TRACER.pop(self._sp)
        _TRACER.finish(self._sp)
        self._annotation.__exit__(*exc)
        return False


def span(name, **args):
    """Scoped span: ``with tracing.span("train.step", step=i): ...`` —
    nests under the ambient span and is ambient inside the scope."""
    if not _ENABLED:
        return NULL_SPAN
    return _Scope(name, args)


def start(name, parent=None, **args):
    """Open a manual span (see :meth:`Tracer.start`); finish it with
    :func:`finish`.  Returns the shared null span when disabled."""
    if not _ENABLED:
        return NULL_SPAN
    return _TRACER.start(name, parent=parent, **args)


def finish(sp, **args):
    if not _ENABLED:
        return sp
    return _TRACER.finish(sp, **args)


def discard(sp):
    """Close an open span without committing it; the scope it belongs
    to then exits in silence.  For a scope that turned out to hold no
    work (the prefetcher's look past the end of its source)."""
    if sp is not None and sp is not NULL_SPAN and sp.t1 is None:
        sp.t1, sp.t1_ns = sp.t0, sp.t0_ns


def record(name, t0, t1, parent=None, ns=None, **args):
    """Commit a pre-timed span (no-op when disabled)."""
    if not _ENABLED:
        return NULL_SPAN
    return _TRACER.record(name, t0, t1, parent=parent, ns=ns, **args)


def annotate(sp, **args):
    """Add ``args`` to an open span; nothing on the null span, which is
    what a disabled :func:`span` hands out."""
    if sp is not None and sp is not NULL_SPAN:
        sp.args.update(args)


def current():
    """The ambient span on THIS thread (None when none or disabled)."""
    if not _ENABLED:
        return None
    return _TRACER.current()


def capture():
    """Snapshot the ambient span for hand-off to a worker thread:
    ``ctx = tracing.capture()`` on the owner, ``with
    tracing.activate(ctx):`` on the worker — spans the worker opens
    then parent under the owner's trace."""
    if not _ENABLED:
        return None
    return _TRACER.current()


class _Activation:
    __slots__ = ("_ctx", "_pushed")

    def __init__(self, ctx):
        self._ctx = ctx
        self._pushed = False

    def __enter__(self):
        if _ENABLED and self._ctx is not None \
                and self._ctx is not NULL_SPAN:
            _TRACER.push(self._ctx)
            self._pushed = True
        return self._ctx

    def __exit__(self, *exc):
        if self._pushed:
            _TRACER.pop(self._ctx)
        return False


def activate(ctx):
    """Install a :func:`capture`\\ d span as this thread's ambient
    context for the scope's duration (worker-thread half of the
    propagation hand-shake).  Safe with ``ctx=None`` (no-op)."""
    return _Activation(ctx)


def spans():
    """Finished span records, oldest first ([] when disabled)."""
    if not _ENABLED:
        return []
    return _TRACER.spans()


def dropped():
    """Finished spans the bounded ring evicted since the last reset
    (0 when disabled) — the visible-truncation counter (ISSUE 15)."""
    if not _ENABLED:
        return 0
    return _TRACER.dropped()


def reset():
    """Fresh tracer: empty ring, id counter at zero, DEFAULT clock, env
    kill switch re-read (the conftest between-tests seam) — a test that
    injected a FakeClock or disabled tracing can't leak either."""
    global _ENABLED, _TRACER
    _ENABLED = _env_enabled()
    _TRACER = Tracer(ring_size=_env_ring())


# -- compiles -----------------------------------------------------------

def _on_duration(event, secs, **_kw):
    """Which step compiled, and inside what: each backend compile JAX
    reports lands as a pre-timed ``jit.compile`` child of the span that
    is ambient on the compiling thread (a root when none is)."""
    if event != COMPILE_EVENT:
        return
    from . import inc
    inc("jit.compiles")
    if _ENABLED:
        t1, t1_ns = _TRACER._stamp()
        _TRACER.record("jit.compile", t1 - secs, t1,
                       ns=(t1_ns - int(round(secs * 1e9)), t1_ns))


_LISTENING = False


def install_compile_listener():
    """Register the one ``jax.monitoring`` listener behind
    ``jit.compile`` (``telemetry`` does, on import; JAX keeps listeners
    for the life of the process, so once)."""
    global _LISTENING
    if not _LISTENING:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True


# -- export -------------------------------------------------------------

def _span_event(r, pid, tid):
    return {
        "name": r["name"], "ph": "X", "pid": pid, "tid": tid,
        "ts": r["t0"] * 1e6,
        "dur": max(0.0, (r["t1"] - r["t0"]) * 1e6),
        "args": dict(r["args"], trace=r["trace"], span=r["span"],
                     parent=r["parent"]),
    }


def _fleet_chrome_trace(fleet):
    """Per-rank process lanes over a fleet snapshot's stitched span
    rings (ISSUE 15): ``pid`` = rank, threads keep their lanes inside
    each rank.  Span ids are per-process — cross-worker linkage rides
    the ``remote_trace``/``remote_span`` args the PS RPC context
    wrapper stamped server-side.  The estimated per-rank clock offset
    is DISCLOSED as a lane label and in ``otherData`` — timestamps are
    never shifted (the scrape round-trip bounds the estimate; shifting
    would fake a precision the estimate does not have)."""
    events, meta = [], []
    dropped = {}
    offsets = {}
    for rank_s, row in sorted((fleet.get("per_rank") or {}).items(),
                              key=lambda kv: int(kv[0])):
        pid = int(rank_s)
        off = row.get("clock_offset_est_s")
        offsets[rank_s] = off
        if row.get("dropped_spans"):
            dropped[rank_s] = row["dropped_spans"]
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": f"rank {pid}"}})
        meta.append({"name": "process_labels", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"labels":
                     ("scrape failed: " + str(row.get("error"))
                      if not row.get("ok") else
                      f"clock_offset_est_s={off} "
                      f"(disclosed estimate; NOT applied)")}})
        tids = {}
        for r in row.get("spans") or []:
            tid = tids.setdefault(r["thread"], len(tids))
            events.append(_span_event(r, pid, tid))
        meta.extend({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": thread}}
                    for thread, tid in tids.items())
    return {"traceEvents": meta + events,
            "otherData": {"fleet_schema_version":
                          fleet.get("fleet_schema_version"),
                          "clock_offset_est_s": offsets,
                          "dropped_spans": dropped}}


def chrome_trace(include_profiler=True, fleet=None):
    """The merged Chrome-trace JSON object: every finished tracing span
    as a complete ``"X"`` event (ts/dur in microseconds, ``args``
    carrying trace/span/parent ids for perfetto correlation) plus —
    when ``include_profiler`` — ``mx.profiler``'s Task / Frame /
    Counter / Marker events, so the user's own annotations and the
    causal request/step spans land on ONE timeline.  With ``fleet`` (a
    :meth:`~.fleet.FleetCollector.collect` snapshot) the export is the
    STITCHED multi-worker timeline instead: one process lane per rank,
    clock offsets disclosed, never applied.  ``otherData`` stamps the
    ring's drop count so a truncated timeline is visibly truncated.
    Valid input for chrome://tracing and https://ui.perfetto.dev."""
    if fleet is not None:
        return _fleet_chrome_trace(fleet)
    pid = os.getpid()
    events = []
    tids = {}
    for r in spans():
        tid = tids.setdefault(r["thread"], len(tids))
        events.append(_span_event(r, pid, tid))
    if include_profiler:
        from .. import profiler
        ptid = len(tids)
        for name, ph, ts, extra in profiler._STATE["events"]:
            ev = {"name": name, "ph": ph, "ts": ts * 1e6, "pid": pid,
                  "tid": ptid}
            ev.update(extra)
            events.append(ev)
    meta = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": thread}} for thread, tid in tids.items()]
    from . import events_dropped
    return {"traceEvents": meta + events,
            "otherData": {"dropped_spans": dropped(),
                          "dropped_events": events_dropped()}}
