"""Elastic membership (ISSUE 8): the epoch-numbered state machine, the
PS join/announce path, the kvstore epoch fence, controller-led reshards
with bitwise continuation parity, and the chaos elastic scenarios —
all deterministic on the simulated 8-device CPU mesh (FakeClock, zero
sleeps)."""
import json
import os
import socket

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import elastic, gluon, parallel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic import (ElasticController, Membership,
                               StaleMembershipEpoch)
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.testing import faults


# ----------------------------------------------------------------------
# membership state machine
# ----------------------------------------------------------------------

def test_membership_death_bumps_epoch_and_emits():
    clock = faults.FakeClock()
    m = Membership([0, 1, 2], now=clock)
    assert m.epoch == 0 and m.ranks == (0, 1, 2)
    ev = m.worker_dead(1)
    assert m.epoch == 1 and m.ranks == (0, 2)
    assert ev.kind == "death" and ev.rank == 1
    assert m.worker_dead(7) is None          # unknown rank: no transition
    assert m.epoch == 1


def test_membership_join_is_two_phase():
    clock = faults.FakeClock(100.0)
    m = Membership([0], now=clock, rendezvous_s=30)
    deadline = m.announce_join(1, seen_epoch=0)
    assert deadline == 130.0
    assert m.state == elastic.RENDEZVOUS and m.pending_join == 1
    assert m.epoch == 0                      # announce does NOT commit
    ev = m.confirm_join(1)
    assert ev.kind == "join" and m.epoch == 1
    assert m.ranks == (0, 1) and m.state == elastic.STABLE


def test_membership_stale_announce_rejected_cleanly():
    m = Membership([0], now=faults.FakeClock())
    m.announce_join(1, seen_epoch=0)
    m.confirm_join(1)                        # epoch -> 1
    with pytest.raises(StaleMembershipEpoch, match="stale membership"):
        m.announce_join(2, seen_epoch=0)
    with pytest.raises(MXNetError, match="already a live member"):
        m.announce_join(1, seen_epoch=m.epoch)


def test_membership_rendezvous_expiry_degrades():
    clock = faults.FakeClock(0.0)
    m = Membership([0], now=clock, rendezvous_s=10)
    m.announce_join(1, seen_epoch=0)
    assert m.poll() is None                  # still inside the window
    clock.advance(10.5)
    ev = m.poll()
    assert ev.kind == "rendezvous_expired" and ev.rank == 1
    assert m.pending_join is None and m.epoch == 0
    with pytest.raises(MXNetError, match="no matching announced join"):
        m.confirm_join(1)


def test_membership_joiner_death_cancels_rendezvous():
    clock = faults.FakeClock()
    m = Membership([0, 1], now=clock)
    m.announce_join(2, seen_epoch=0)
    ev = m.worker_dead(2)                    # the flapping worker
    assert ev.kind == "rendezvous_cancelled"
    assert m.pending_join is None and m.epoch == 0
    assert m.ranks == (0, 1)


def test_membership_check_epoch_fence():
    m = Membership([0, 1])
    m.check_epoch(0)                         # current: fine
    m.worker_dead(1)
    with pytest.raises(StaleMembershipEpoch, match="rejected instead "
                                                   "of deadlocking"):
        m.check_epoch(0)


def test_membership_view_is_jsonable():
    import json
    m = Membership([0, 1], now=faults.FakeClock())
    m.announce_join(2, seen_epoch=0)
    view = json.loads(json.dumps(m.view()))
    assert view == {"epoch": 0, "ranks": [0, 1],
                    "state": "rendezvous", "pending": 2}


# ----------------------------------------------------------------------
# PS join/announce path (satellite: the symmetric twin of the PR 4
# deterministic death-path tests)
# ----------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_ps_join_announce_and_stale_rejection():
    """Rejoin after a heartbeat-detected death: the announce RPC with
    the CURRENT epoch parks the worker in rendezvous; an announce with
    the stale pre-death epoch is rejected with a clean typed error —
    zero wall-clock sleeps anywhere."""
    from mxnet_tpu.kvstore.ps_server import PSServer, PSClient
    clock = faults.FakeClock(1000.0)
    port = _free_port()
    srv = PSServer("127.0.0.1", port, num_workers=2,
                   heartbeat_timeout=5.0)
    srv._now = clock
    membership = Membership([0, 1], now=clock, rendezvous_s=30)
    srv.attach_membership(membership)
    c0 = PSClient("127.0.0.1", port)
    c1 = PSClient("127.0.0.1", port)
    try:
        assert c0.membership() == {"epoch": 0, "ranks": [0, 1],
                                   "state": "stable", "pending": None}
        # death through the heartbeat path commits into the membership
        c0.beat_once(0)
        c1.beat_once(1)
        clock.advance(3.0)
        c0.beat_once(0)
        with faults.inject("ps.heartbeat.drop", action="drop"):
            assert not c1.beat_once(1)
        clock.advance(3.0)
        assert srv._scan_dead() == [1]
        assert membership.epoch == 1 and membership.ranks == (0,)

        # rejoin carrying the PRE-DEATH epoch: rejected cleanly
        with pytest.raises(MXNetError, match="stale membership epoch"):
            c1.join(1, 0)
        assert membership.pending_join is None

        # rejoin with the current epoch: accepted into rendezvous, and
        # the joiner counts as alive again (it just spoke to us)
        view = c1.join(1, membership.epoch)
        assert view["state"] == "rendezvous" and view["pending"] == 1
        assert view["rendezvous_deadline"] == clock() + 30
        assert srv.dead_workers() == []
        assert c0.membership()["pending"] == 1

        # a second, different joiner is refused while one is pending
        with pytest.raises(MXNetError, match="one join at a time"):
            c0.join(5, membership.epoch)

        membership.confirm_join(1)
        assert c0.membership() == {"epoch": 2, "ranks": [0, 1],
                                   "state": "stable", "pending": None}
    finally:
        c0.close()
        c1.close()
        srv._sock.close()


def test_ps_join_without_membership_errors_cleanly():
    from mxnet_tpu.kvstore.ps_server import PSServer, PSClient
    port = _free_port()
    srv = PSServer("127.0.0.1", port, num_workers=1)
    c = PSClient("127.0.0.1", port)
    try:
        assert c.membership()["epoch"] is None
        with pytest.raises(MXNetError, match="no membership attached"):
            c.join(0, 0)
    finally:
        c.close()
        srv._sock.close()


# ----------------------------------------------------------------------
# kvstore epoch fence: stale collectives are rejected, not deadlocked
# ----------------------------------------------------------------------

def test_kvstore_pushpull_fenced_by_membership_epoch():
    kv = mx.kv.create("tpu_sync")
    kv.init("w", mx.nd.zeros((4,)))
    membership = Membership([0, 1])
    kv.attach_membership(membership)
    out = mx.nd.zeros((4,))
    kv.pushpull("w", mx.nd.ones((4,)), out=out)      # current epoch: ok
    membership.worker_dead(1)                        # cluster moves on
    with pytest.raises(StaleMembershipEpoch,
                       match="membership epoch 0 .* cluster is at 1"):
        kv.pushpull("w", mx.nd.ones((4,)), out=out)
    with pytest.raises(StaleMembershipEpoch):
        kv.push("w", mx.nd.ones((4,)))
    assert kv.refresh_membership() == 1              # post-reshard re-arm
    kv.pushpull("w", mx.nd.ones((4,)), out=out)


# ----------------------------------------------------------------------
# controller-led reshard: parity, floors, kill switch
# ----------------------------------------------------------------------

def _build_dp(mesh, seed=1234):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.Dense(4)
    net.initialize()
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 0.05},
        mesh=mesh, shard_updates=True)
    return net, trainer


def _data(n=6):
    rng = np.random.RandomState(0)
    return (rng.randn(n, 16, 8).astype(np.float32),
            rng.randn(n, 16, 4).astype(np.float32))


def test_controller_shrink_reshard_is_bitwise_vs_fresh_restore():
    """dp 8 -> 4 mid-run: the in-place reshard must land EXACTLY the
    state a fresh dp=4 process restored from the same instant would
    reach — the acceptance bar's parity contract."""
    import jax
    from mxnet_tpu.checkpoint import _rng_state, _restore_rng
    devices = jax.devices()
    xs, ys = _data()
    net, trainer = _build_dp(make_mesh({"dp": 8}, devices))
    clock = faults.FakeClock()
    membership = Membership([0, 1], now=clock)
    ctrl = ElasticController(membership, devices=devices,
                             devices_per_worker=4, net=net,
                             backoff_s=0.0, now=clock,
                             sleep=lambda s: None)
    for i in range(3):
        trainer.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
    assert ctrl.check_step(3, trainer, net) is None   # no transition yet
    # boundary snapshot = what a fresh process would restore
    sd = trainer.state_dict()
    sd = {"arrays": {k: mx.nd.array(v.asnumpy())
                     for k, v in sd["arrays"].items()},
          "meta": dict(sd["meta"])}
    psnap = {n_: p.data().asnumpy().copy() for n_, p
             in net._collect_params_with_prefix().items()}
    rng_arrays, rng_meta = _rng_state()
    rng_arrays = {k: mx.nd.array(v.asnumpy())
                  for k, v in rng_arrays.items()}

    membership.worker_dead(1)
    ev = ctrl.check_step(3, trainer, net)
    assert ev["source"] == "peer" and ev["dp"] == 4
    assert trainer.mesh.shape["dp"] == 4
    assert ctrl.stats()["transitions"] == 1
    assert ctrl.stats()["reshard_ms"] is not None
    for i in range(3, 6):
        trainer.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))

    ref_net, ref_trainer = _build_dp(make_mesh({"dp": 4}, devices[:4]),
                                     seed=999)
    ref_net(mx.nd.array(xs[0]))
    target = ref_net._collect_params_with_prefix()
    for n_, v in psnap.items():
        target[n_].set_data(v)
    ref_trainer.load_state_dict(sd)
    _restore_rng(rng_arrays, rng_meta)
    for i in range(3, 6):
        ref_trainer.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))

    for n_, p in net._collect_params_with_prefix().items():
        assert np.array_equal(p.data().asnumpy(),
                              target[n_].data().asnumpy()), n_
    a = {k: v.asnumpy() for k, v in trainer.state_dict()
         ["arrays"].items()}
    b = {k: v.asnumpy() for k, v in ref_trainer.state_dict()
         ["arrays"].items()}
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(a[k], b[k]), k


def test_controller_refuses_to_shrink_below_min_dp():
    import jax
    devices = jax.devices()
    xs, ys = _data(1)
    net, trainer = _build_dp(make_mesh({"dp": 8}, devices))
    trainer.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    membership = Membership([0, 1], now=faults.FakeClock())
    ctrl = ElasticController(membership, devices=devices,
                             devices_per_worker=4, net=net, min_dp=8,
                             backoff_s=0.0, sleep=lambda s: None)
    membership.worker_dead(1)
    with pytest.raises(MXNetError, match="below the MXTPU_ELASTIC_"
                                         "MIN_DP"):
        ctrl.check_step(1, trainer, net)


def test_controller_kill_switch(monkeypatch):
    monkeypatch.setenv("MXTPU_ELASTIC", "0")
    membership = Membership([0, 1], now=faults.FakeClock())
    ctrl = ElasticController(membership, devices_per_worker=4)
    membership.worker_dead(1)
    # inert: no transition applied, no trainer touched
    assert ctrl.check_step(1, trainer=None, params=None) is None
    assert ctrl.pending() is False


def test_reshard_fault_falls_back_to_checkpoint(tmp_path):
    """Kill the peer transfer on every retry: the controller recovers
    from the newest valid checkpoint and reports the rewind step."""
    import jax
    from mxnet_tpu.checkpoint import CheckpointManager
    devices = jax.devices()
    xs, ys = _data()
    net, trainer = _build_dp(make_mesh({"dp": 8}, devices))
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3,
                            async_save=False)
    for i in range(3):
        trainer.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
    mgr.save(3, params=net, trainer=trainer, iterator={"batch": 3})
    membership = Membership([0, 1], now=faults.FakeClock())
    ctrl = ElasticController(membership, devices=devices,
                             devices_per_worker=4, net=net,
                             checkpoint_manager=mgr, max_retries=1,
                             backoff_s=0.0, sleep=lambda s: None)
    membership.worker_dead(1)
    with faults.inject("elastic.reshard"):
        ev = ctrl.check_step(3, trainer, net)
    assert ev["source"] == "checkpoint" and ev["step"] == 3
    assert trainer.mesh.shape["dp"] == 4
    trainer.step(mx.nd.array(xs[3]), mx.nd.array(ys[3]))


def test_reshard_fault_without_checkpoint_raises_both_paths():
    import jax
    devices = jax.devices()
    xs, ys = _data(1)
    net, trainer = _build_dp(make_mesh({"dp": 8}, devices))
    trainer.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    membership = Membership([0, 1], now=faults.FakeClock())
    ctrl = ElasticController(membership, devices=devices,
                             devices_per_worker=4, net=net,
                             max_retries=0, backoff_s=0.0,
                             sleep=lambda s: None)
    membership.worker_dead(1)
    with faults.inject("elastic.reshard"):
        with pytest.raises(MXNetError, match="both paths"):
            ctrl.check_step(1, trainer, net)


# ----------------------------------------------------------------------
# trainer rebuild seam
# ----------------------------------------------------------------------

def test_trainer_rebuild_crosses_dp_one():
    """shard_updates survives a rebuild through dp=1 (where ZeRO-1 is
    inert) and back up."""
    import jax
    devices = jax.devices()
    xs, ys = _data(3)
    net, trainer = _build_dp(make_mesh({"dp": 8}, devices))
    trainer.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    assert trainer._zero1_active()
    sd = trainer.state_dict()
    trainer.rebuild(make_mesh({"dp": 1}, devices[:1]))
    trainer.load_state_dict(sd)
    assert not trainer._zero1_active()
    trainer.step(mx.nd.array(xs[1]), mx.nd.array(ys[1]))
    sd = trainer.state_dict()
    trainer.rebuild(make_mesh({"dp": 8}, devices))
    trainer.load_state_dict(sd)
    assert trainer._zero1_active()
    trainer.step(mx.nd.array(xs[2]), mx.nd.array(ys[2]))


def test_overlap_scheduler_reset_plan():
    from mxnet_tpu.parallel.overlap import OverlapScheduler
    net = gluon.nn.Dense(4)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0)
                    .randn(4, 8).astype(np.float32))
    params = list(net.collect_params().values())
    sched = OverlapScheduler(params).install()
    try:
        from mxnet_tpu import autograd
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        sched.finish()                     # first cycle builds the plan
        assert sched.plan is not None
        sched.reset_plan()
        assert sched.plan is None          # next cycle re-observes
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        sched.finish()
        assert sched.plan is not None
    finally:
        sched.remove()


# ----------------------------------------------------------------------
# the chaos elastic scenarios, wired into tier-1 (fast, deterministic)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["shrink", "grow", "reshard_fault"])
def test_chaos_elastic_scenario(kind, tmp_path):
    from mxnet_tpu.testing.chaos import run_elastic_scenario
    r = run_elastic_scenario(kind, workdir=str(tmp_path))
    assert r["params_bitwise"], r
    assert r["state_bitwise"], r
    assert r["ok"], r


# ----------------------------------------------------------------------
# estimator pause/resume hook
# ----------------------------------------------------------------------

def test_estimator_elastic_pause_reshard_resume():
    import jax
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu.gluon.contrib.estimator import Estimator, BatchEnd
    devices = jax.devices()
    xs, ys = _data()
    net, trainer = _build_dp(make_mesh({"dp": 8}, devices))
    membership = Membership([0, 1], now=faults.FakeClock())
    ctrl = ElasticController(membership, devices=devices,
                             devices_per_worker=4, net=net,
                             backoff_s=0.0, sleep=lambda s: None)
    batches = [(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
               for i in range(6)]

    class KillAt(BatchEnd):
        def batch_end(self, estimator, *args, **kwargs):
            if estimator.global_step + 1 == 3 and membership.epoch == 0:
                membership.worker_dead(1)

    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[metric_mod.Loss()], trainer=trainer)
    est.fit(batches, epochs=1, event_handlers=[KillAt()],
            elastic_controller=ctrl)
    assert not est.preempted                    # peer path: no rewind
    assert est.global_step == 6
    assert trainer.mesh.shape["dp"] == 4
    assert ctrl.stats()["transitions"] == 1
    assert ctrl.stats()["membership_epoch"] == 1


def test_estimator_elastic_checkpoint_fallback_stops_cleanly(tmp_path):
    """When the peer transfer dies, the estimator adopts the PR 4
    preemption contract: restore from the checkpoint, stop with
    ``.preempted`` set, and a re-entry with resume='auto' replays."""
    import jax
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.gluon.contrib.estimator import Estimator, BatchEnd
    devices = jax.devices()
    xs, ys = _data()
    net, trainer = _build_dp(make_mesh({"dp": 8}, devices))
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=5,
                            async_save=False)
    membership = Membership([0, 1], now=faults.FakeClock())
    ctrl = ElasticController(membership, devices=devices,
                             devices_per_worker=4, net=net,
                             checkpoint_manager=mgr, max_retries=0,
                             backoff_s=0.0, sleep=lambda s: None)
    batches = [(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
               for i in range(6)]

    class KillAt(BatchEnd):
        def batch_end(self, estimator, *args, **kwargs):
            if estimator.global_step + 1 == 3 and membership.epoch == 0:
                membership.worker_dead(1)

    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[metric_mod.Loss()], trainer=trainer)
    with faults.inject("elastic.reshard"):
        est.fit(batches, epochs=1, event_handlers=[KillAt()],
                checkpoint_manager=mgr, checkpoint_every=1,
                elastic_controller=ctrl)
    assert est.preempted                        # fallback: clean stop
    # rewound to the last DURABLE boundary: the step-2 save would have
    # happened after this boundary's elastic check, so the newest valid
    # checkpoint is step 1
    assert est.global_step == 1
    assert trainer.mesh.shape["dp"] == 4
    # re-entry resumes from the restored cursor and completes
    est.fit(batches, epochs=1, resume="auto", checkpoint_manager=mgr,
            elastic_controller=ctrl)
    assert est.global_step == 6 and not est.preempted


# ----------------------------------------------------------------------
# the `elastic` block schema (ISSUE 8): config/counters always real,
# measured transition timings null-when-unmeasured — a CPU run can't
# pass off an absent measurement as "resharding is free"
# ----------------------------------------------------------------------

_ELASTIC_KEYS = {
    "enabled", "dp", "membership_epoch", "transitions", "degraded",
    "reshard_ms", "pause_ms", "drain_ms", "drains", "pending_notices",
    "autoscale_decisions",
}


def test_elastic_block_schema_is_stable():
    from mxnet_tpu.elastic import elastic_block
    blk = elastic_block()
    assert set(blk) == _ELASTIC_KEYS
    for k in ("reshard_ms", "pause_ms", "drain_ms",
              "autoscale_decisions"):
        assert blk[k] is None, k
    assert blk["enabled"] is False and blk["transitions"] == 0
    assert blk["drains"] == 0 and blk["pending_notices"] == 0
    blk2 = elastic_block(enabled=True, dp=4, membership_epoch=2,
                         transitions=1, reshard_ms=73.7777,
                         pause_ms=74.1234, drain_ms=5.5555,
                         drains=1, autoscale_decisions=3)
    assert blk2["reshard_ms"] == 73.778
    assert blk2["pause_ms"] == 74.123
    assert blk2["drain_ms"] == 5.556
    assert blk2["autoscale_decisions"] == 3
    assert json.loads(json.dumps(blk)) == blk
