"""Multi-replica serving router: least-loaded admission, epoch-fenced
replica membership, drain-and-requeue on replica death.

One :class:`~mxnet_tpu.serving.InferenceEngine` is one chip's decode
loop; planet-scale traffic needs a FLEET of them behind one front end
(the GluonCV/GluonNLP deployment story, arXiv:1907.04433).  The Router
owns N engine replicas, each with its own
:class:`~mxnet_tpu.serving.ContinuousBatcher`, KV pool and prefix
cache, and:

- **admits** each request to the replica with the lowest load score,
  computed from the PR 9 telemetry registry signals
  (``serving.replica<i>.queue_depth`` / ``.ttft_ms`` /
  ``.kv_block_utilization`` — the same gauges a live scrape sees;
  direct engine reads are the fallback when the registry is off);
- **numbers the replica set with an epoch** (the PR 8 membership
  discipline): every death or join bumps it, and stats/manifest carry
  it so two observations of the fleet are comparable;
- **drains and requeues** when a replica dies mid-traffic: its queued,
  prefilling and mid-decode requests are reset to their prompts and
  re-admitted to the survivors — greedy decode is deterministic, so a
  re-run request produces the same tokens it would have (the chaos
  gate: zero lost, zero duplicated, outputs bitwise the solo run);
- **shares one warmup compile cache** across replicas: executables
  close over shapes only, so the fleet pays each (kind, size) graph
  compile once (replica 2's warmup skips straight through).

Two drive modes.  ``start()`` spawns one worker THREAD per replica
(each replica's engine/batcher/prefix cache is touched only by its
worker — single-owner, no data sharing; the router's own bookkeeping
is the only locked state).  ``drive()`` steps every live replica once
on the caller's thread, round-robin — fully deterministic, zero
sleeps, what the chaos scenario and the loadgen's reproducible numbers
use.  Both modes run the same admission/death/requeue code.
"""
from __future__ import annotations

import threading
from collections import deque

from ...base import MXNetError, NotSupportedError
from ... import telemetry as _telem
from ...telemetry import tracing as _trace
from ...lint import racecheck as _racecheck
from ..kv_cache import HandoffError
from ..scheduler import ContinuousBatcher

__all__ = ["Router", "Replica", "AdmissionShed"]


class AdmissionShed(MXNetError):
    """The router is shedding new admissions (degradation-ladder rung 1
    — capacity dropped below the healthy target).  In-flight and
    requeued requests are unaffected; the caller should back off or
    route elsewhere."""


class Replica:
    """One engine + batcher + (optional) worker thread.  Everything in
    here is owned by the replica's driver; the Router only reads/writes
    it while holding the router lock in ways the drivers tolerate
    (inbox hand-off, death flag)."""

    __slots__ = ("rid", "engine", "batcher", "alive", "inbox",
                 "boundaries", "thread", "ttfts", "role", "tpots")

    def __init__(self, rid, engine, batcher, role="combined"):
        self.rid = rid
        self.engine = engine
        self.batcher = batcher
        self.alive = True
        self.inbox = []          # guarded-by: Router._lock
        self.boundaries = 0      # scheduling boundaries stepped
        self.thread = None
        self.ttfts = []          # recent TTFTs (seconds) for scoring
        self.role = role         # combined | prefill | decode
        self.tpots = []          # recent TPOTs (seconds) for scaling

    def load_signals(self, inbox_len=0):
        """The raw admission signals, read directly off the replica —
        the fallback (and the source the Router publishes to the
        telemetry registry after every boundary).  ``inbox_len`` is
        snapshotted by the caller under the router lock (the inbox is
        the one cross-thread structure here)."""
        b = self.batcher
        depth = len(b.queue) + int(inbox_len) + len(b.active) + \
            len(getattr(b, "prefilling", ()))
        recent = self.ttfts[-8:]
        # None, not 0.0, before the first measured TTFT: an unmeasured
        # replica must read as "no signal", never as "perfect" (the
        # r04/r05 null-when-unmeasured convention — ISSUE 14 fix)
        ttft_ms = (sorted(recent)[len(recent) // 2] * 1e3
                   if recent else None)
        return {"queue_depth": depth,
                "ttft_ms": ttft_ms,
                "kv_block_utilization": self.engine.cache.utilization()}


class Router:
    """Front-end over ``replicas`` engine replicas.

    Parameters
    ----------
    engine_factory : callable(compile_cache_dict) -> InferenceEngine
        (unwarmed).  Called once per replica with the SHARED compile
        cache; the router warms each engine (replica 0 pays the
        compiles, the rest reuse them).  A DISAGGREGATED router calls
        it as ``engine_factory(cc, kv_cache=shared_or_None)`` — the
        first replica creates the pool, every later one must pass the
        given ``kv_cache`` through to its ``InferenceEngine``.
    replicas : fleet size (>= 1); default ``MXTPU_SERVE_REPLICAS`` or 2.
    prefills_per_step : forwarded to each ContinuousBatcher.
    now : timestamp source for router events (FakeClock-injectable;
        never used for waiting — the router has no timeouts).
    disaggregated : split the fleet into PREFILL-role and DECODE-role
        replicas over ONE shared ``PagedKVCache`` (ISSUE 18): a prefill
        replica fills a request's blocks, then hands ownership to a
        decode replica through the CoW refcounts (adopt-then-release);
        the autoscaler scales the pools independently (TTFT grows the
        prefill pool, TPOT the decode pool).  Default reads
        ``MXTPU_SERVE_DISAGG`` (unset/0 = off).  ``drive()`` only.
    """

    def __init__(self, engine_factory, replicas=None,
                 prefills_per_step=1, now=None, disaggregated=None):
        import os
        import time
        if replicas is None:
            try:
                replicas = int(os.environ.get("MXTPU_SERVE_REPLICAS", 2))
            except ValueError:
                replicas = 2
        if replicas < 1:
            raise MXNetError(f"Router needs >= 1 replica, got {replicas}")
        if disaggregated is None:
            disaggregated = os.environ.get(
                "MXTPU_SERVE_DISAGG", "") not in ("", "0")
        self.disaggregated = bool(disaggregated)
        if self.disaggregated and replicas < 2:
            raise MXNetError(
                "disaggregated serving needs >= 2 replicas (at least "
                f"one prefill and one decode), got {replicas}")
        self._now = now if now is not None else time.time
        self._lock = _racecheck.make_lock("Router._lock")
        self._cond = threading.Condition(self._lock)
        self.epoch = 0             # guarded-by: _lock (replica-set epoch)
        self.requeues = 0          # guarded-by: _lock
        self._assigned = {}        # guarded-by: _lock — req.id -> rid
        self._submitted = {}       # guarded-by: _lock — req.id -> Request
        self._stopping = False     # guarded-by: _lock
        self._shedding = False     # guarded-by: _lock (ladder rung 1)
        self.events = []           # guarded-by: _lock — membership log
        self._factory = engine_factory
        self._prefills_per_step = prefills_per_step
        self._notices = None       # elastic.NoticeBoard (ISSUE 13)
        self._trace_ctx = None     # ambient span captured at start()
        self.compile_cache = {}
        self.replicas = []
        self.handoffs = 0          # completed prefill->decode handoffs
        self._shared_cache = None  # disagg: the fleet-wide PagedKVCache
        warm0 = None
        for rid in range(replicas):
            role = self._role_for(rid)
            eng = self._make_engine()
            before = eng.stats["compiles"]
            eng.warmup()
            if rid == 0:
                warm0 = eng.stats["compiles"] - before
            self.replicas.append(Replica(rid, eng,
                                         self._make_batcher(eng, rid,
                                                            role),
                                         role=role))
        self.warmup_compiles = warm0 or 0
        self.warmup_compiles_shared = (replicas - 1) * (warm0 or 0)

    def _role_for(self, rid):
        """Disaggregated role placement: even rids prefill, odd rids
        decode — every fleet of >= 2 has at least one of each, and the
        autoscaler overrides per-pool via ``add_replica(role=...)``."""
        if not self.disaggregated:
            return "combined"
        return "prefill" if rid % 2 == 0 else "decode"

    def _make_engine(self):
        """Build one replica engine through the stored factory.  In
        disaggregated mode the factory is called with the fleet's
        SHARED ``kv_cache`` (None for the first replica, which creates
        the pool every later replica adopts) — block handoff is only
        meaningful when both sides index the same pool."""
        if not self.disaggregated:
            return self._factory(self.compile_cache)
        eng = self._factory(self.compile_cache,
                            kv_cache=self._shared_cache)
        if self._shared_cache is None:
            self._shared_cache = eng.cache
        elif eng.cache is not self._shared_cache:
            raise HandoffError(
                "disaggregated replicas must share one PagedKVCache — "
                "the engine_factory ignored its kv_cache argument")
        # the pool CREATOR's flag flips too: its pool outlives it (the
        # fleet shares it), so its death must free its slots like any
        # other disaggregated replica's
        eng.cache_shared = True
        return eng

    def _make_batcher(self, eng, rid, role):
        return ContinuousBatcher(
            eng, self._prefills_per_step,
            slot_ns=(rid if self.disaggregated else None), role=role)

    # -- membership ------------------------------------------------------

    def live_replicas(self):
        return [r for r in self.replicas if r.alive]

    def kill_replica(self, rid):
        """Administrative kill (chaos / tests): same path a crashed
        worker takes — epoch bump, drain, requeue."""
        self._on_death(self.replicas[rid],
                       MXNetError(f"replica {rid} killed"))

    def _evacuate(self, rep, event_kind, detail):
        """Take ``rep`` out of the replica set (epoch bump) and collect
        everything it still owed: inbox, queued, mid-prefill,
        mid-decode.  Finished requests already left the building.
        Returns the requests to requeue — shared by the crash path
        (``_on_death``) and the graceful drain (``drain_replica``)."""
        with self._lock:
            rep.alive = False
            self.epoch += 1
            epoch = self.epoch
            lost = list(rep.inbox)
            rep.inbox.clear()
            self.events.append(dict(detail, kind=event_kind,
                                    rid=rep.rid, epoch=epoch,
                                    t=self._now()))
            self._cond.notify_all()   # its worker thread must exit
        b = rep.batcher
        lost += list(b.queue)
        b.queue.clear()
        # slots the dead replica still holds: with a per-replica pool
        # they die with the engine, but a SHARED pool (disaggregated
        # fleet) outlives the replica — every hold must be dropped or
        # check_leaks on the survivors reports the dead replica's
        # blocks forever
        held_slots = (list(getattr(b, "prefilling", ()))
                      + list(b.active)
                      + [slot for slot, _req in
                         getattr(b, "handoff_ready", ())])
        lost += [st.req for st in getattr(b, "prefilling", {}).values()]
        getattr(b, "prefilling", {}).clear()
        lost += list(b.active.values())
        b.active.clear()
        lost += [req for _slot, req in getattr(b, "handoff_ready", ())]
        getattr(b, "handoff_ready", deque()).clear()
        if getattr(rep.engine, "cache_shared", False):
            for slot in held_slots:
                rep.engine.cache.free(slot)
            if rep.engine.prefix_cache is not None:
                rep.engine.prefix_cache.clear()
        return lost, epoch

    def _requeue_all(self, lost, from_rid=None):
        for req in lost:
            # reset to the prompt: greedy decode reproduces the exact
            # stream on the new replica, so nothing is lost or doubled
            req.generated = []
            req.finish_reason = None
            req.first_token_t = None
            req.finish_t = None
            req._queue_t0 = None
            if _trace.enabled() and req.trace is not None:
                # the requeue hop is an instant marker in the SAME
                # trace: the re-admission chain parents under the
                # original root, so a drained request's timeline stays
                # one causally-linked tree across replicas
                t = _trace.clock()
                _trace.record("requeue", t, t, parent=req.trace,
                              from_rid=from_rid)
            with self._lock:
                self.requeues += 1
            self.submit(req, _requeue=True)

    def _on_death(self, rep, exc):
        if not rep.alive:
            return
        lost, epoch = self._evacuate(
            rep, "replica_dead",
            {"error": f"{type(exc).__name__}: {exc}"})
        if not self.live_replicas():
            raise MXNetError(
                f"router: last replica died ({exc}); "
                f"{len(lost)} request(s) unservable")
        if self.disaggregated and not any(
                r.role == rep.role for r in self.live_replicas()):
            raise MXNetError(
                f"router: last {rep.role}-role replica died ({exc}); "
                f"the disaggregated fleet cannot serve without one")
        _telem.event("serving.replica_dead", rid=rep.rid,
                     epoch=epoch, requeued=len(lost))
        _telem.inc("serving.replica_deaths")
        self._requeue_all(lost, from_rid=rep.rid)

    def drain_replica(self, rid, reason="admin"):
        """Graceful exit for a DOOMED (preemption-noticed) or
        autoscaled-away replica: epoch bump, everything it still owed
        requeued to the survivors — zero lost, zero duplicated — and
        the replica leaves the set before its machine disappears.  Same
        evacuation as the crash path, minus the surprise."""
        rep = self.replicas[rid]
        if not rep.alive:
            return 0
        if len(self.live_replicas()) <= 1:
            raise MXNetError(
                f"router: refusing to drain replica {rid} — it is the "
                f"last live replica (scale up or stop shedding first)")
        if self.disaggregated and sum(
                1 for r in self.live_replicas()
                if r.role == rep.role) <= 1:
            raise MXNetError(
                f"router: refusing to drain replica {rid} — it is the "
                f"last live {rep.role}-role replica (grow that pool "
                "first)")
        lost, epoch = self._evacuate(rep, "replica_drained",
                                     {"reason": str(reason)})
        _telem.event("serving.replica_drained", rid=rep.rid,
                     epoch=epoch, requeued=len(lost),
                     reason=str(reason))
        _telem.inc("serving.replica_drains")
        self._requeue_all(lost, from_rid=rep.rid)
        return len(lost)

    def add_replica(self, role=None):
        """Grow the fleet by one replica (the autoscaler's grow path):
        built from the stored factory against the SHARED warmup compile
        cache (pool-geometry-keyed executables — the newcomer compiles
        nothing new for known shapes), epoch bump, worker thread
        spawned when the fleet runs threaded.  ``role`` targets a
        disaggregated pool ("prefill" | "decode"); default grows the
        smaller pool.  Non-disaggregated fleets reject explicit roles."""
        if not self.disaggregated:
            if role not in (None, "combined"):
                raise MXNetError(
                    f"add_replica(role={role!r}) needs a disaggregated "
                    "router (role'd replicas share one KV pool)")
            role = "combined"
        elif role is None:
            live = self.live_replicas()
            n_pre = sum(1 for r in live if r.role == "prefill")
            n_dec = sum(1 for r in live if r.role == "decode")
            role = "prefill" if n_pre <= n_dec else "decode"
        elif role not in ("prefill", "decode"):
            raise MXNetError(
                f"add_replica role {role!r} must be prefill|decode on "
                "a disaggregated router")
        eng = self._make_engine()
        eng.warmup()
        # self.replicas stays SINGLE-WRITER (the control loop that calls
        # add_replica) and append is atomic under the GIL; every
        # concurrent reader snapshots with list(self.replicas) — so the
        # list itself needs no lock, only the epoch/event bookkeeping
        rid = len(self.replicas)
        rep = Replica(rid, eng, self._make_batcher(eng, rid, role),
                      role=role)
        threaded = any(r.thread is not None for r in self.replicas)
        self.replicas.append(rep)
        with self._lock:
            self.epoch += 1
            epoch = self.epoch
            self.events.append({"kind": "replica_added", "rid": rid,
                                "epoch": epoch, "role": role,
                                "t": self._now()})
        _telem.event("serving.replica_added", rid=rid, epoch=epoch,
                     role=role)
        _telem.inc("serving.replica_adds")
        if threaded:
            t = threading.Thread(target=self._worker, args=(rep,),
                                 name=f"router-replica{rid}",
                                 daemon=True)
            rep.thread = t
            t.start()
        return rep

    # -- degradation / notices (ISSUE 13) --------------------------------

    def set_shedding(self, on, reason=None):
        """Degradation-ladder rung 1: while shedding, NEW submissions
        raise :class:`AdmissionShed`; requeues (drain/death evacuation)
        are exempt so nothing in flight is ever lost.  Idempotent; the
        transition (only) is logged."""
        on = bool(on)
        with self._lock:
            changed = on != self._shedding
            self._shedding = on
        if changed:
            _telem.event("serving.shedding", on=on,
                         reason=str(reason) if reason else None)
            _telem.set_gauge("serving.shedding", int(on))
        return on

    @property
    def shedding(self):
        with self._lock:
            return self._shedding

    def attach_notices(self, board):
        """Wire an :class:`~mxnet_tpu.elastic.NoticeBoard` whose ranks
        name replica ids: a pending notice drains the doomed replica at
        the next scheduling boundary (requeue to survivors, zero lost);
        a revoked notice cancels the drain before it commits."""
        self._notices = board
        return self

    def _check_notices(self):
        if self._notices is None:
            return 0
        self._notices.poll()
        drained = 0
        for notice in self._notices.pending():
            rid = notice.rank
            if rid >= len(self.replicas) or not self.replicas[rid].alive:
                self._notices.mark_drained(notice)   # already gone
                continue
            self._notices.mark_drained(notice)
            self.drain_replica(rid, reason=f"notice:{notice.kind}")
            drained += 1
        return drained

    # -- admission -------------------------------------------------------

    def _signals(self, rep):
        """Per-replica load signals THROUGH the telemetry registry when
        it's live (the published gauges are the fleet's source of
        truth), falling back to direct reads.  Unmeasured signals are
        ``None`` — "no signal", NEVER a fake-perfect 0.0 (the r04/r05
        null-when-unmeasured convention): scoring drops any signal not
        measured on every candidate rather than letting an unmeasured
        replica win admission on numbers nobody observed."""
        if _telem.enabled():
            pre = f"serving.replica{rep.rid}."
            depth = _telem.value(pre + "queue_depth")
            if depth is not None:
                return {"queue_depth": depth,
                        "ttft_ms": _telem.value(pre + "ttft_ms"),
                        "kv_block_utilization":
                            _telem.value(pre + "kv_block_utilization")}
        with self._lock:
            inbox_len = len(rep.inbox)
        return rep.load_signals(inbox_len)

    def _score(self, sig, use_ttft=True, use_kv=True):
        # queue depth dominates (each queued request is a whole
        # generation of latency); KV pressure breaks ties between
        # equally-deep queues; TTFT drift demotes a replica that has
        # been serving slowly even when its queue momentarily clears.
        # A signal class unmeasured on ANY candidate is excluded for
        # ALL (the caller passes use_*) — scores stay comparable and
        # admission falls back to queue depth alone when that is the
        # only signal every replica actually has.
        s = 2.0 * sig["queue_depth"]
        if use_kv:
            s += 1.0 * sig["kv_block_utilization"]
        if use_ttft:
            s += 0.001 * sig["ttft_ms"]
        return s

    def submit(self, request, _requeue=False):
        """Admit ``request`` to the least-loaded live replica.  While
        the degradation ladder has admissions SHED, new requests are
        rejected with a typed :class:`AdmissionShed` (requeues of
        in-flight work are exempt — a drain never loses a request)."""
        if not _requeue:
            with self._lock:
                shedding = self._shedding
            if shedding:
                _telem.inc("serving.admissions_shed")
                raise AdmissionShed(
                    "router is shedding new admissions (capacity below "
                    "the healthy target — degradation-ladder rung 1); "
                    "retry after capacity recovers")
        # decode-role replicas take work through block handoff, never
        # direct admission — a fresh prompt always needs a prefill
        live = [r for r in self.live_replicas() if r.role != "decode"]
        if not live:
            raise MXNetError("router: no live replicas that can admit")
        ta0 = _trace.clock() if _trace.enabled() else None
        sigs = [self._signals(r) for r in live]
        # null-honesty: only score on signal classes EVERY candidate
        # has measured; otherwise fall back to queue depth alone
        use_ttft = all(s["ttft_ms"] is not None for s in sigs)
        use_kv = all(s["kv_block_utilization"] is not None for s in sigs)
        scored = [(self._score(s, use_ttft, use_kv), r.rid, r)
                  for s, r in zip(sigs, live)]
        scored.sort(key=lambda t: (t[0], t[1]))
        rep = scored[0][2]
        if ta0 is not None:
            if request.trace is None:
                request.trace = _trace.start("request", id=request.id)
            _trace.record("admission", ta0, _trace.clock(),
                          parent=request.trace, rid=rep.rid,
                          requeue=bool(_requeue))
        with self._lock:
            if not _requeue:
                self._submitted[request.id] = request
            self._assigned[request.id] = rep.rid
            rep.inbox.append(request)
            self._cond.notify_all()
        return request

    def _drain_inbox(self, rep):
        with self._lock:
            pending, rep.inbox = rep.inbox, []
        for req in pending:
            rep.batcher.submit(req)

    # -- driving ---------------------------------------------------------

    def _step_replica(self, rep):
        """One scheduling boundary on one replica (runs on the
        replica's owner thread — worker or deterministic driver)."""
        from ...testing import faults
        rep.boundaries += 1
        faults.fault_point(f"serving.replica{rep.rid}.step",
                           payload=rep.boundaries)
        tb0 = _trace.clock() if _trace.enabled() else None
        self._drain_inbox(rep)
        n_fin = len(rep.batcher.finished)
        moved = rep.batcher.step()
        if rep.role == "prefill":
            moved += self._drain_handoffs(rep)
        for req in rep.batcher.finished[n_fin:]:
            t = req.ttft()
            if t is not None:
                rep.ttfts.append(t)
            tp = req.tpot()
            if tp is not None:
                rep.tpots.append(tp)
        if tb0 is not None:
            # boundary span parents under the driver's ambient trace
            # (the worker thread activates the context captured at
            # start(); drive() runs on the caller's own ambient)
            _trace.record("serving.boundary", tb0, _trace.clock(),
                          rid=rep.rid)
        if _telem.enabled():
            with self._lock:
                inbox_len = len(rep.inbox)
            sig = rep.load_signals(inbox_len)
            pre = f"serving.replica{rep.rid}."
            _telem.set_gauge(pre + "queue_depth", sig["queue_depth"])
            if sig["ttft_ms"] is not None:
                # never publish a fake-perfect 0.0 before the first
                # measured TTFT: the gauge stays absent => value() is
                # None => admission scoring treats it as "no signal"
                _telem.set_gauge(pre + "ttft_ms",
                                 round(sig["ttft_ms"], 3))
            _telem.set_gauge(pre + "kv_block_utilization",
                             round(sig["kv_block_utilization"], 4))
            recent = rep.tpots[-8:]
            if recent:
                # same null-honesty as ttft_ms: absent until measured
                _telem.set_gauge(
                    pre + "tpot_ms",
                    round(sorted(recent)[len(recent) // 2] * 1e3, 3))
        return moved

    def _pick_decode(self):
        """Least-loaded live decode-role replica with a free batch
        slot (None = the decode pool is saturated; the handoff entry
        waits in the prefill outbox — pure backpressure, no loss)."""
        cands = [r for r in self.live_replicas()
                 if r.role == "decode" and r.batcher._free_slots]
        if not cands:
            return None
        cands.sort(key=lambda r: (len(r.batcher.active), r.rid))
        return cands[0]

    def _drain_handoffs(self, rep):
        """Move ``rep``'s finished prefills to decode-role replicas:
        adopt-then-release over the SHARED pool's refcounts.  The fault
        point fires BEFORE any mutation, so a replica killed mid-
        handoff leaves the head entry wholly owned by the outbox — the
        evacuation path requeues it exactly once (the chaos gate: zero
        lost, zero duplicated).  Entries the decode pool cannot take
        yet stay parked (retried next boundary)."""
        from ...testing import faults
        b = rep.batcher
        moved = 0
        while b.handoff_ready:
            slot, req = b.handoff_ready[0]
            tgt = self._pick_decode()
            if tgt is None:
                break
            faults.fault_point(f"serving.replica{rep.rid}.handoff",
                               payload=req.id)
            t0 = _telem.clock() if _telem.enabled() else None
            cache = rep.engine.cache
            n = cache.seq_len(slot)
            cache.trim(slot, n)   # drop bucket-padding past the prompt
            dst = tgt.batcher.adopt_handoff(req, cache.table(slot), n)
            if dst is None:
                break
            b.complete_handoff(slot)
            b.handoff_ready.popleft()
            with self._lock:
                self._assigned[req.id] = tgt.rid
                self.handoffs += 1
            moved += 1
            if t0 is not None:
                _telem.inc("serving.handoffs")
                _telem.observe("serving.handoff_ms",
                               (_telem.clock() - t0) * 1e3)
            if _trace.enabled():
                t = _trace.clock()
                _trace.record("handoff", t, t, parent=req.trace,
                              from_rid=rep.rid, to_rid=tgt.rid,
                              blocks=len(cache.table(dst)))
        return moved

    def _replica_idle(self, rep):
        b = rep.batcher
        return not (rep.inbox or b.queue or b.active
                    or getattr(b, "prefilling", None)
                    or getattr(b, "handoff_ready", None))

    def drive(self, max_boundaries=100000):
        """Deterministic mode: round-robin every live replica until all
        submitted requests finish.  Zero sleeps, zero threads — the
        chaos scenario's and the loadgen's reproducible path."""
        boundaries = 0
        while not self.all_done():
            self._check_notices()   # drain doomed replicas first
            progressed = False
            for rep in list(self.replicas):
                if not rep.alive or self._replica_idle(rep):
                    continue
                try:
                    self._step_replica(rep)
                except Exception as e:  # noqa: BLE001 — death path
                    self._on_death(rep, e)
                progressed = True
                boundaries += 1
                if boundaries > max_boundaries:
                    raise MXNetError("router drive exceeded "
                                     "max_boundaries — fleet stuck")
            if not progressed and not self.all_done():
                raise MXNetError(
                    "router: no replica can make progress but "
                    "requests remain (pool too small for the mix?)")
        return boundaries

    # -- threaded mode ---------------------------------------------------

    def start(self):
        """Spawn one worker thread per replica (production shape).
        Each worker owns its replica exclusively; it sleeps on the
        router condition variable when idle (no polling)."""
        if self.disaggregated:
            raise NotSupportedError(
                "threaded disaggregated serving is not supported yet: "
                "the block handoff crosses two replicas' batchers, "
                "which breaks the one-owner-thread-per-replica "
                "discipline — use drive()")
        self._trace_ctx = _trace.capture()
        for rep in self.replicas:
            if rep.thread is not None:
                continue
            t = threading.Thread(target=self._worker, args=(rep,),
                                 name=f"router-replica{rep.rid}",
                                 daemon=True)
            rep.thread = t
            t.start()
        return self

    def _worker(self, rep):
        # worker spans parent under the trace ambient at start()
        # (ISSUE 14 cross-thread propagation)
        with _trace.activate(getattr(self, "_trace_ctx", None)):
            self._worker_loop(rep)

    def _worker_loop(self, rep):
        while True:
            with self._lock:
                while (rep.alive and not self._stopping
                       and self._replica_idle(rep)):
                    self._cond.wait()  # mxlint: disable=HB16 -- Condition.wait RELEASES the router lock while sleeping
                if self._stopping or not rep.alive:
                    return
            board = self._notices
            if board is not None:
                # single-owner discipline: the DOOMED replica's own
                # worker performs its drain (it owns the batcher state)
                board.poll()
                notice = board.pending_for(rep.rid)
                if notice is not None:
                    board.mark_drained(notice)
                    self.drain_replica(rep.rid,
                                       reason=f"notice:{notice.kind}")
                    return
            try:
                self._step_replica(rep)
            except Exception as e:  # noqa: BLE001 — death path
                self._on_death(rep, e)
                return
            finally:
                with self._lock:
                    self._cond.notify_all()

    def stop(self):
        """Stop workers after they finish the current boundary."""
        with self._lock:
            self._stopping = True
            self._cond.notify_all()
        for rep in self.replicas:
            if rep.thread is not None:
                rep.thread.join(timeout=60)
                rep.thread = None
        with self._lock:
            self._stopping = False
        return self

    def wait_all_done(self, timeout=60.0):
        """Threaded mode: block until every submitted request finished.
        Event-driven, not polled — workers notify the router condition
        after every boundary."""
        import time
        deadline = time.monotonic() + timeout
        with self._lock:
            while True:
                reqs = list(self._submitted.values())
                if all(r.done for r in reqs):
                    return True
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise MXNetError(
                        "router: requests still unfinished at timeout")
                self._cond.wait(remaining)  # mxlint: disable=HB16 -- Condition.wait RELEASES the router lock while sleeping

    # -- introspection ---------------------------------------------------

    def all_done(self):
        with self._lock:
            reqs = list(self._submitted.values())
        return all(r.done for r in reqs)

    def finished(self):
        """Every finished request across live AND dead replicas (a
        request that completed before its replica died stays
        completed)."""
        out = []
        for rep in self.replicas:
            out.extend(rep.batcher.finished)
        return out

    def manifest(self):
        """The fleet's inspectable shape: epoch, per-replica liveness +
        engine config + mesh spec (the ISSUE 12 small-fix: the recorded
        MeshConfig rides along so item-2 TP serving slots in here)."""
        with self._lock:
            epoch = self.epoch
        return {
            "epoch": epoch,
            "disaggregated": self.disaggregated,
            "replicas": [{
                "rid": r.rid,
                "alive": r.alive,
                "role": r.role,
                "cache_shared": getattr(r.engine, "cache_shared",
                                        False),
                "mesh": r.engine.mesh_config.describe(),
                "max_batch": r.engine.max_batch,
                "block_size": r.engine.block_size,
                "max_context": r.engine.max_context,
                "buckets": list(r.engine.buckets),
                "quantized": r.engine.quantized,
                "prefill_chunk": r.engine.prefill_chunk,
                "prefix_cache": r.engine.prefix_cache is not None,
            } for r in self.replicas],
            "shared_compile_cache": len(self.compile_cache),
            "warmup_compiles": self.warmup_compiles,
            "warmup_compiles_shared": self.warmup_compiles_shared,
        }

    def stats(self):
        fin = self.finished()
        lat = sorted(r.latency() for r in fin
                     if r.latency() is not None)

        def pct(p):
            if not lat:
                return None
            return lat[min(len(lat) - 1, int(round(p * (len(lat) - 1))))]

        per_replica = []
        total_caw = 0
        pool_occ = {"prefill": [], "decode": []}
        for r in self.replicas:
            occ = r.batcher.occupancy()
            total_caw += r.engine.stats["compiles_after_warmup"]
            if r.role in pool_occ and occ is not None:
                pool_occ[r.role].append(occ)
            per_replica.append({
                "rid": r.rid, "alive": r.alive, "role": r.role,
                "requests": len(r.batcher.finished),
                "boundaries": r.boundaries,
                "occupancy": round(occ, 4) if occ is not None else None,
                "prefix": (r.engine.prefix_cache.stats()
                           if r.engine.prefix_cache else None),
            })
        with self._lock:
            epoch, requeues = self.epoch, self.requeues
            shedding = self._shedding
            handoffs = self.handoffs

        def _pool(vals):
            # None, not 0.0, until a pool member measured something
            return round(sum(vals) / len(vals), 4) if vals else None

        return {"replicas": len(self.replicas),
                "live": len(self.live_replicas()),
                "epoch": epoch,
                "disaggregated": self.disaggregated,
                "requests": len(fin),
                "requeues": requeues,
                "handoffs": handoffs,
                "prefill_pool_occupancy": _pool(pool_occ["prefill"]),
                "decode_pool_occupancy": _pool(pool_occ["decode"]),
                "shedding": shedding,
                "p50_latency_s": pct(0.50), "p99_latency_s": pct(0.99),
                "compiles_after_warmup": total_caw,
                "per_replica": per_replica}
