"""``mxnet_tpu.elastic`` — scale data-parallel workers up/down mid-run
without a restart (ISSUE 8, ROADMAP item 4).

Three layers stitched through the existing stack:

- :class:`Membership` — the epoch-numbered membership state machine
  (``membership.py``), fed by the PS heartbeat death path
  (``PSServer.attach_membership`` + the join/announce RPC) and fully
  deterministic under ``testing.faults.FakeClock``;
- :class:`ElasticController` — pause at a step boundary, reshard
  params + ZeRO-1 optimizer state to the new dp (peer-to-peer via
  ``checkpoint.reshard_in_place``, checkpoint fallback when the
  transfer itself dies), rebuild the mesh/BucketPlan/compiled steps,
  resume — with retry/backoff and a bounded rendezvous so a flapping
  worker degrades to a smaller dp instead of hanging the job;
- the **epoch fence** — ``kvstore.attach_membership`` rejects a stale
  worker's collective with a clean error instead of letting it deadlock
  a ring against departed peers.

``estimator.fit(elastic_controller=...)`` wires the pause/resume hook
into the high-level loop; ``testing/chaos.py`` (``python -m
mxnet_tpu.testing.chaos elastic``) is the end-to-end kill-at-K /
join-at-K' smoke with bitwise continuation parity.  docs/
FAULT_TOLERANCE.md §Elastic membership has the state diagram.

ISSUE 13 adds the production half: ``notices.py`` (a pluggable
``NoticeBoard`` — GCE maintenance poller / SIGTERM-grace / scripted
fake — drains doomed workers at step boundaries AHEAD of the heartbeat
timeout; lapsed grace raises the typed ``DrainDeadline``) and
``autoscaler.py`` (an ``Autoscaler`` control loop scaling dp and
serving replicas ON LOAD through hysteresis windows + cooldown, and a
``DegradationLadder``: shed serving admissions -> run shrunken ->
checkpoint-and-stop).  Chaos gate:
``python -m mxnet_tpu.testing.chaos autoscale``.

Env knobs: ``MXTPU_ELASTIC=0`` (kill switch),
``MXTPU_ELASTIC_RENDEZVOUS_S`` (join window, default 30),
``MXTPU_ELASTIC_MIN_DP`` (degradation floor, default 1),
``MXTPU_AUTOSCALE=0`` / ``MXTPU_AUTOSCALE_COOLDOWN_S`` (autoscaler),
``MXTPU_NOTICE_SOURCE`` / ``MXTPU_NOTICE_GRACE_S`` (notices).
"""
from __future__ import annotations

from .membership import (Membership, MembershipEvent,
                         StaleMembershipEpoch, STABLE, RENDEZVOUS,
                         default_rendezvous_s)
from .controller import ElasticController, elastic_enabled, min_dp
from .notices import (Notice, NoticeBoard, NoticeSource,
                      FakeNoticeSource, SignalNoticeSource,
                      GCENoticeSource, DrainDeadline,
                      make_notice_source, default_notice_grace_s)
from .autoscaler import (ScalingRule, ScalingPolicy, Autoscaler,
                         DegradationLadder, autoscale_enabled,
                         default_cooldown_s)

__all__ = ["Membership", "MembershipEvent", "StaleMembershipEpoch",
           "ElasticController", "elastic_enabled", "min_dp",
           "default_rendezvous_s", "elastic_block", "STABLE",
           "RENDEZVOUS", "Notice", "NoticeBoard", "NoticeSource",
           "FakeNoticeSource", "SignalNoticeSource", "GCENoticeSource",
           "DrainDeadline", "make_notice_source",
           "default_notice_grace_s", "ScalingRule", "ScalingPolicy",
           "Autoscaler", "DegradationLadder", "autoscale_enabled",
           "default_cooldown_s"]


def elastic_block(enabled=False, dp=1, membership_epoch=0, transitions=0,
                  degraded=False, reshard_ms=None, pause_ms=None,
                  drain_ms=None, drains=0, pending_notices=0,
                  autoscale_decisions=None):
    """The summary of an ``ElasticController``'s ``stats()`` (the ``comm``
    / ``serving`` block discipline): static config/counters are always
    real; MEASURED fields (``reshard_ms``, ``pause_ms``, ``drain_ms``,
    ``autoscale_decisions``) default to ``None`` —
    null-when-unmeasured, so a CPU run can never pass off an absent
    measurement as "resharding is free" (the PR 6 honesty rule, gated
    by tests/test_elastic.py).  ISSUE 13 grew the block with the
    notice-drain and autoscaling evidence: ``drain_ms`` (last
    notice-driven drain commit), ``drains``/``pending_notices``
    counters, and ``autoscale_decisions`` (None until a real autoscale
    loop ran — a CPU round without one reports null, not 0-decisions-
    measured)."""
    def _r(x, n=3):
        return None if x is None else round(float(x), n)

    return {
        "enabled": bool(enabled),
        "dp": int(dp),
        "membership_epoch": int(membership_epoch),
        "transitions": int(transitions),
        "degraded": bool(degraded),
        "reshard_ms": _r(reshard_ms),
        "pause_ms": _r(pause_ms),
        "drain_ms": _r(drain_ms),
        "drains": int(drains),
        "pending_notices": int(pending_notices),
        "autoscale_decisions": (None if autoscale_decisions is None
                                else int(autoscale_decisions)),
    }
