"""Elastic membership controller (ISSUE 8 tentpole): pause -> reshard
-> resume at a step boundary, without a process restart.

The pieces existed separately — PR 4 reshards ZeRO-1 optimizer state
bitwise across dp sizes and the PS layer detects dead workers by
heartbeat — this closes the loop.  On a committed membership transition
(a death fed from ``PSServer._scan_dead``, or a join announced through
the ``_OP_JOIN`` RPC and admitted at the next boundary):

1. **pause** — nothing interrupts a step mid-flight: the training loop
   (``estimator.fit`` window boundary, or a custom loop calling
   :meth:`ElasticController.check_step`) hands control over exactly
   where the PR 4 ``PreemptionHandler`` stop seam sits, so the
   in-flight step/scan-window always completes first;
2. **reshard** — peer-to-peer from the live trainer's state
   (``checkpoint.reshard_in_place``: per-parameter-space capture ->
   ``DataParallelTrainer.rebuild(mesh)`` -> restore), because the live
   state is newer than any checkpoint; retried with bounded backoff; a
   reshard that dies mid-transfer (``elastic.reshard`` fault point)
   falls back to ``checkpoint.reshard_from_checkpoint`` — the newest
   valid checkpoint, with the resume step returned so the loop rewinds;
3. **resume** — the new mesh / ``BucketPlan`` / compiled steps rebuild
   lazily on the next step; an attached kvstore's membership epoch is
   refreshed (collectives fenced by the old epoch are rejected, not
   deadlocked) and an attached ``OverlapScheduler`` re-observes its
   backward order.

Degradation policy: a join that outlives its rendezvous window — or a
joiner that dies mid-rendezvous — is dropped (``Membership.poll``) and
the job **continues at the smaller dp**; shrinking below
``MXTPU_ELASTIC_MIN_DP`` raises instead of limping.  All timeout logic
reads the injectable ``now``/``sleep`` hooks, so every path is
deterministic under ``testing.faults.FakeClock`` with zero sleeps.
"""
from __future__ import annotations

import os
import time

from ..base import MXNetError
from .. import telemetry as _telem
from ..telemetry import tracing as _trace
from .membership import Membership  # noqa: F401  (re-exported surface)
from .notices import DrainDeadline

__all__ = ["ElasticController", "elastic_enabled", "min_dp"]


def elastic_enabled():
    """Kill switch: ``MXTPU_ELASTIC=0`` makes every controller inert
    (``check_step`` returns None without touching the trainer) — the
    same opt-out semantics as ``MXTPU_FUSED_STEP``/``MXTPU_OVERLAP_COMM``.
    Default on: constructing a controller IS the opt-in."""
    return os.environ.get("MXTPU_ELASTIC", "1") != "0"


def min_dp():
    """Degradation floor (``MXTPU_ELASTIC_MIN_DP``, default 1): the
    smallest dp the controller will shrink to; a transition below it
    raises instead of continuing with a crippled job."""
    return int(os.environ.get("MXTPU_ELASTIC_MIN_DP", "1") or 1)


class ElasticController:
    """Drives elastic reshards from membership transitions.

    ``membership``: the :class:`~mxnet_tpu.elastic.Membership` machine
    (typically also attached to a ``PSServer`` so heartbeat deaths feed
    it).  ``devices``: the device pool meshes are carved from (default
    ``jax.devices()``).  ``devices_per_worker``: how many mesh devices
    each membership rank contributes (default: pool size / initial rank
    count — the v5e host granularity).  ``checkpoint_manager``: the
    fallback source when the peer transfer dies.  ``net``: the gluon
    block whose parameters ride along (required for the checkpoint
    fallback; the peer path snapshots it too when given).

    ``backoff_s``/``max_retries`` bound the peer-path retry loop;
    ``now``/``sleep`` are injectable for deterministic tests (a
    ``FakeClock`` and a no-op make every scenario sleep-free).
    """

    def __init__(self, membership, devices=None, devices_per_worker=None,
                 checkpoint_manager=None, net=None, kvstore=None,
                 scheduler=None, min_dp=None, max_retries=2,
                 backoff_s=0.5, now=None, sleep=None, notices=None,
                 ladder=None, drain_checkpoint=None):
        import jax
        self._membership = membership
        self._devices = list(devices) if devices is not None \
            else list(jax.devices())
        n_ranks = max(1, len(membership.ranks))
        self._dpw = int(devices_per_worker) if devices_per_worker \
            is not None else max(1, len(self._devices) // n_ranks)
        self._manager = checkpoint_manager
        self._net = net
        self._kvstore = kvstore
        self._scheduler = scheduler
        self._min_dp = int(min_dp) if min_dp is not None \
            else globals()["min_dp"]()
        self._max_retries = int(max_retries)
        self._backoff_s = float(backoff_s)
        self._now = now if now is not None else time.time
        self._sleep = sleep if sleep is not None else time.sleep
        self._enabled = elastic_enabled()   # read ONCE at construction
        self._applied_epoch = membership.epoch
        # ISSUE 13: preemption notices, load-based rescale requests,
        # and the graceful-degradation ladder
        self._notices = notices
        self._ladder = ladder
        self._requested_dp = None     # autoscaler target (one-shot)
        self._applied_dp = None       # dp the trainer was last built for
        self._healthy_dp = self.target_dp(include_pending=False)
        #: callable(step) run sync BEFORE a notice-driven drain commits
        #: (checkpoint-then-reshard; estimator.fit wires its own saver)
        self.drain_checkpoint = drain_checkpoint
        # ISSUE 19: process-level coordinator re-init (attach_dist_reinit)
        self._dist_reinit = None
        self.dist_reinits = 0
        self.last_reinit_ms = None
        # observability (stats() + tests)
        self.transitions = 0
        self.drains = 0
        self.degraded = False
        self.last_pause_ms = None
        self.last_reshard_ms = None
        self.last_drain_ms = None
        self.last_event = None

    # -- wiring ---------------------------------------------------------
    def attach_kvstore(self, kvstore):
        """Fence an eager kvstore's collectives by the membership epoch
        (``kvstore.attach_membership``) and keep it refreshed across
        reshards."""
        kvstore.attach_membership(self._membership)
        self._kvstore = kvstore
        return self

    def attach_notices(self, board):
        """Wire a :class:`~mxnet_tpu.elastic.NoticeBoard`: pending
        notices are drained at step boundaries AHEAD of the heartbeat
        timeout (``check_step`` commits ``worker_dead`` the moment a
        noticed rank is seen at a boundary, instead of waiting for
        ``PSServer._scan_dead``)."""
        self._notices = board
        return self

    def attach_dist_reinit(self, fn):
        """ISSUE 19: the process-level coordinator re-init seam.  When
        attached, a COMMITTED membership change (epoch bump) makes
        ``resync`` call ``fn(epoch, ranks)`` BEFORE rebuilding the mesh
        — the hook tears down and re-initializes the JAX coordination
        service at the new world size (see
        ``_dist_init.reinit_distributed``; a real death changes
        ``jax.process_count()``) and returns the new device list (or
        None to keep the current one).  Every live device buffer dies
        with the old backend, so the transition is forced onto the
        checkpoint-restore reshard path — the peer transfer has nothing
        left to read."""
        self._dist_reinit = fn
        return self

    def attach_ladder(self, ladder):
        """Wire a :class:`~mxnet_tpu.elastic.DegradationLadder`: on
        every capacity change the ladder sheds/recovers serving
        admissions, and a drop below the ``MXTPU_ELASTIC_MIN_DP`` floor
        walks rung 3 (checkpoint-and-stop via the PR 4 preemption
        contract) instead of raising."""
        self._ladder = ladder
        return self

    @property
    def membership(self):
        return self._membership

    @property
    def notices(self):
        return self._notices

    @property
    def applied_epoch(self):
        """The membership epoch the running trainer was last built for."""
        return self._applied_epoch

    @property
    def applied_dp(self):
        """The dp the trainer was last rebuilt for (None before the
        first transition — the construction-time mesh is the trainer's
        business)."""
        return self._applied_dp

    def request_dp(self, n):
        """ISSUE 13: a deliberate, load-based dp target (the
        autoscaler's seam).  Applied at the next step boundary through
        the SAME epoch-fenced ``resync`` as a membership change —
        bitwise reshard, tp/pp preserved.  The target is clamped to
        [min_dp, membership capacity]; returns the clamped value."""
        cap = self.target_dp(include_pending=True)
        self._requested_dp = max(self._min_dp, min(int(n), cap))
        return self._requested_dp

    def target_dp(self, include_pending=True):
        """The dp size the current membership implies: ranks (plus an
        in-rendezvous joiner about to be admitted) x devices-per-worker,
        capped at the device pool."""
        n = len(self._membership.ranks)
        if include_pending and self._membership.pending_join is not None:
            n += 1
        return max(1, min(n * self._dpw, len(self._devices)))

    # -- the step-boundary hook -----------------------------------------
    def pending(self):
        """True when a transition awaits the next step boundary (epoch
        moved, or a joiner sits in rendezvous).  Also expires overdue
        rendezvous — the degrade-to-smaller-dp policy needs no thread of
        its own."""
        if not self._enabled:
            return False
        if self._membership.poll() is not None:
            self.degraded = True       # rendezvous expired: continue small
        return (self._membership.epoch != self._applied_epoch
                or self._membership.pending_join is not None
                or self._requested_dp is not None)

    def _check_notices(self, step):
        """ISSUE 13: drain every pending preemption notice at this
        boundary — commit ``worker_dead`` for the doomed rank NOW,
        ahead of the heartbeat timeout, optionally checkpointing first
        (``drain_checkpoint``).  A notice whose grace window already
        lapsed raises the typed :class:`DrainDeadline` instead of
        silently degrading to the heartbeat path.  Returns the number
        of drains committed."""
        board = self._notices
        if board is None:
            return 0
        board.poll()
        pending = board.pending()
        _telem.set_gauge("elastic.pending_notices", len(pending))
        drained = 0
        for notice in pending:
            if notice.rank not in self._membership.ranks:
                # unknown or already-departed rank: nothing to drain
                board.mark_drained(notice)
                continue
            now = board.now()
            if notice.deadline is not None and now > notice.deadline:
                board.mark_expired(notice)
                raise DrainDeadline(
                    f"preemption notice for rank {notice.rank} "
                    f"({notice.kind}) expired {now - notice.deadline:.1f}s "
                    f"before this step boundary could drain it — the "
                    f"worker may already be gone and the heartbeat path "
                    f"will commit the death late; take the emergency "
                    f"exit (sync checkpoint + stop) now", notice=notice)
            t0 = time.perf_counter()
            if self.drain_checkpoint is not None and step is not None:
                # checkpoint-THEN-reshard: the drain leaves a durable
                # boundary before the membership moves
                self.drain_checkpoint(int(step))
            self._membership.worker_dead(notice.rank)
            board.mark_drained(notice)
            self.drains += 1
            self.last_drain_ms = round((time.perf_counter() - t0) * 1e3, 3)
            drained += 1
            if _telem.enabled():
                _telem.inc("elastic.drains")
                _telem.set_gauge("elastic.drain_ms", self.last_drain_ms)
                _telem.event("elastic.drain", rank=notice.rank,
                             notice=notice.kind,
                             step=None if step is None else int(step))
        return drained

    def check_step(self, step, trainer, params=None):
        """The pause seam (same contract as
        ``PreemptionHandler.check_step``): call between steps / at scan
        -window boundaries.  No transition -> None, O(1).  Otherwise the
        boundary IS the pause: reshard + resume happen here, and the
        returned dict tells the loop what happened —
        ``{"source": "peer", "step": None}`` (continue at the same
        step) or ``{"source": "checkpoint", "step": S}`` (rewind to S;
        the RNG came back with the checkpoint, so the replay is
        bitwise).  With a :class:`NoticeBoard` attached the boundary
        first drains noticed ranks (death committed AHEAD of the
        heartbeat timeout; ``elastic.pending_notices`` gauge published;
        lapsed grace raises :class:`DrainDeadline`)."""
        if not self._enabled:
            return None
        self._check_notices(step)
        if not self.pending():
            return None
        return self.resync(step, trainer, params=params)

    # -- the transition -------------------------------------------------
    def resync(self, step, trainer, params=None):
        """Apply the pending membership transition (or a load-based
        ``request_dp`` target) to ``trainer``."""
        from .. import checkpoint as _ckpt
        from ..parallel.mesh import AXIS_DP as _AXIS_DP
        t_pause = time.perf_counter()
        joiner = self._membership.pending_join
        force_ckpt = False
        if self._dist_reinit is not None \
                and self._membership.epoch != self._applied_epoch:
            # ISSUE 19: a REAL membership change — tear down + re-init
            # the JAX coordination service at the new world size before
            # any mesh math (jax.process_count() and the device pool
            # both change under us).  The old backend's buffers are
            # gone, so the peer-transfer reshard has nothing to read:
            # force the checkpoint-restore path.
            t_r = time.perf_counter()
            devices = self._dist_reinit(self._membership.epoch,
                                        sorted(self._membership.ranks))
            self.last_reinit_ms = round(
                (time.perf_counter() - t_r) * 1e3, 3)
            self.dist_reinits += 1
            if devices is not None:
                self._devices = list(devices)
            force_ckpt = True
            if _telem.enabled():
                _telem.inc("elastic.dist_reinits")
                _telem.set_gauge("elastic.coordinator_reinit_ms",
                                 self.last_reinit_ms)
                _telem.event("elastic.dist_reinit",
                             epoch=self._membership.epoch,
                             reinit_ms=self.last_reinit_ms)
        capacity = self.target_dp()
        new_dp = capacity if self._requested_dp is None \
            else max(1, min(self._requested_dp, capacity))
        same_membership = (self._membership.epoch == self._applied_epoch
                           and joiner is None)
        if same_membership and self._requested_dp is not None:
            # load-based rescale only: skip the reshard when the trainer
            # already runs at the requested dp (no-op transition)
            try:
                cur = int(dict(trainer.mesh.shape).get(_AXIS_DP, 0))
            except (AttributeError, TypeError):
                cur = 0
            if cur == new_dp:
                self._requested_dp = None
                return None
        if self._ladder is not None:
            outcome = self._ladder.assess(capacity, self._healthy_dp,
                                          self._min_dp)
            if outcome in ("stop", "stop-unhandled"):
                # rung 3: capacity below the floor.  The ladder already
                # requested the PR 4 preemption exit (sync checkpoint +
                # clean stop at the caller's boundary); do NOT reshard
                # below the floor, and do not raise when someone is
                # handling the stop.
                self.degraded = True
                self._requested_dp = None
                self._applied_epoch = self._membership.epoch
                info = {"source": "stop", "step": None, "dp": capacity,
                        "epoch": self._applied_epoch}
                self.last_event = info
                _telem.event("elastic.capacity_stop", dp=capacity,
                             floor=self._min_dp)
                if outcome == "stop-unhandled":
                    raise MXNetError(
                        f"elastic: membership epoch "
                        f"{self._membership.epoch} implies dp="
                        f"{capacity}, below the MXTPU_ELASTIC_MIN_DP="
                        f"{self._min_dp} floor, and no PreemptionHandler"
                        f"/stop hook is installed to take the "
                        f"checkpoint-and-stop exit — restore capacity "
                        f"or lower the floor")
                return info
        if new_dp < self._min_dp:
            raise MXNetError(
                f"elastic: membership epoch {self._membership.epoch} "
                f"implies dp={new_dp}, below the MXTPU_ELASTIC_MIN_DP="
                f"{self._min_dp} floor — refusing to continue crippled; "
                f"restore capacity or lower the floor")
        mesh = self._make_mesh(new_dp, trainer)
        t0 = time.perf_counter()
        info = None
        last_err = None
        for attempt in range(0 if force_ckpt else 1 + self._max_retries):
            try:
                info = _ckpt.reshard_in_place(trainer, mesh,
                                              params=params or self._net,
                                              _attempt=attempt)
                break
            except MXNetError as e:
                last_err = e
                if attempt < self._max_retries:
                    # bounded exponential backoff before re-trying the
                    # peer transfer (injectable: tests pass a no-op)
                    self._sleep(self._backoff_s * (2 ** attempt))
        if info is None:
            # peer transfer kept dying (e.g. the source worker itself
            # went down mid-reshard): recover from the newest valid
            # checkpoint instead of hanging or crashing the job
            try:
                info = _ckpt.reshard_from_checkpoint(
                    trainer, mesh, params=params or self._net,
                    manager=self._manager)
            except MXNetError as e:
                peer = ("skipped (dist reinit: buffers died with the "
                        "old backend)" if force_ckpt else last_err)
                raise MXNetError(
                    f"elastic reshard failed on both paths — peer: "
                    f"{peer}; checkpoint: {e}") from e
        if joiner is not None and \
                self._membership.pending_join == joiner:
            # state transfer done: commit the join (epoch bump)
            self._membership.confirm_join(joiner)
        self._applied_epoch = self._membership.epoch
        self._applied_dp = new_dp
        self._requested_dp = None
        if self._ladder is not None:
            # post-transition reassessment: capacity back at the healthy
            # target un-sheds serving admissions (rung 0)
            self._ladder.assess(self.target_dp(include_pending=False),
                                self._healthy_dp, self._min_dp)
        if self._kvstore is not None:
            self._kvstore.refresh_membership()
        if self._scheduler is not None:
            self._scheduler.reset_plan()
        t1 = time.perf_counter()
        self.transitions += 1
        self.last_reshard_ms = round((t1 - t0) * 1e3, 3)
        self.last_pause_ms = round((t1 - t_pause) * 1e3, 3)
        info = dict(info, dp=new_dp, epoch=self._applied_epoch,
                    reshard_ms=self.last_reshard_ms,
                    pause_ms=self.last_pause_ms)
        self.last_event = info
        if _telem.enabled():
            # live scrapes read these off the registry — same numbers
            # as stats(), one source
            _telem.set_context(step=None if step is None else int(step),
                               epoch=self._applied_epoch)
            _telem.inc("elastic.transitions")
            _telem.set_gauge("elastic.dp", new_dp)
            _telem.set_gauge("elastic.reshard_ms", self.last_reshard_ms)
            _telem.set_gauge("elastic.pause_ms", self.last_pause_ms)
            _telem.observe("elastic.reshard_ms_hist",
                           self.last_reshard_ms)
            _telem.event("elastic.transition", source=info["source"],
                         dp=new_dp, epoch=self._applied_epoch,
                         rewind_step=info.get("step"))
        if _trace.enabled():
            # the transition on the causal timeline (ISSUE 14): the
            # pause window with the reshard inside it — a training trace
            # shows exactly which step boundary paid the resync
            root = _trace.record("elastic.pause", t_pause, t1,
                                 dp=new_dp, epoch=self._applied_epoch,
                                 source=info["source"])
            _trace.record("elastic.reshard", t0, t1, parent=root)
        return info

    def _make_mesh(self, dp, trainer=None):
        """The post-transition mesh: the dp axis follows membership, the
        tp/pp axes follow the TRAINER's MeshConfig (ISSUE 11: an elastic
        transition epoch-fences all three axes — tp/pp shape is a model
        property and survives the reshard, dp is the elastic one)."""
        from ..parallel.mesh import MeshConfig
        cfg = getattr(trainer, "mesh_config", None)
        tp = cfg.tp if cfg is not None else 1
        pp = cfg.pp if cfg is not None else 1
        if tp > 1 or pp > 1:
            dp = max(1, min(dp, len(self._devices) // (tp * pp)))
        new = MeshConfig(dp=dp, tp=tp, pp=pp)
        return new.build(self._devices[:new.size])

    # -- observability ---------------------------------------------------
    def stats(self):
        """What :func:`mxnet_tpu.elastic.elastic_block` summarises."""
        return {"enabled": self._enabled,
                "dp": self.target_dp(include_pending=False),
                "membership_epoch": self._membership.epoch,
                "transitions": self.transitions,
                "degraded": self.degraded,
                "reshard_ms": self.last_reshard_ms,
                "pause_ms": self.last_pause_ms,
                "drain_ms": self.last_drain_ms,
                "drains": self.drains,
                "dist_reinits": self.dist_reinits,
                "coordinator_reinit_ms": self.last_reinit_ms,
                "pending_notices": (len(self._notices.pending())
                                    if self._notices is not None else 0)}
