"""Production elasticity (ISSUE 13): preemption notices drained AHEAD
of the heartbeat timeout, the load-based autoscaling control loop
(hysteresis / cooldown / min-max bounds), and the graceful-degradation
ladder — all FakeClock-driven, zero sleeps, each test <1 s."""
import socket

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import elastic, gluon, parallel, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic import (Autoscaler, DegradationLadder,
                               DrainDeadline, ElasticController,
                               FakeNoticeSource, GCENoticeSource,
                               Membership, NoticeBoard, ScalingPolicy,
                               ScalingRule, SignalNoticeSource)
from mxnet_tpu.parallel.mesh import AXIS_DP, make_mesh
from mxnet_tpu.testing import faults


# ----------------------------------------------------------------------
# the notice board + sources
# ----------------------------------------------------------------------

def test_notice_board_post_revoke_and_earlier_deadline_wins():
    clock = faults.FakeClock(100.0)
    b = NoticeBoard(now=clock)
    n = b.post(1, grace_s=30, kind="maintenance")
    assert n.deadline == 130.0
    # a second signal never EXTENDS the grace window
    assert b.post(1, grace_s=300).deadline == 130.0
    clock.advance(1.0)
    n2 = b.post(1, grace_s=5)                    # earlier: replaces
    assert n2.deadline == 106.0
    assert [x.rank for x in b.pending()] == [1]
    assert b.revoke(1) is n2
    assert b.pending() == [] and b.revoke(1) is None
    assert b.stats()["posted"] == 2 and b.stats()["revoked"] == 1


def test_fake_source_scripted_delivery_and_after_polls():
    clock = faults.FakeClock()
    b = NoticeBoard(now=clock)
    src = FakeNoticeSource()
    b.attach_source(src)
    src.preempt(0, grace_s=10, after_polls=1)
    assert b.poll() == []                        # deferred one poll
    assert [n.rank for n in b.poll()] == [0]
    src.revoke(0)
    assert b.poll() == []


def test_signal_source_deliver_posts_for_own_rank():
    clock = faults.FakeClock(50.0)
    b = NoticeBoard(now=clock)
    src = SignalNoticeSource(rank=3, grace_s=20)
    b.attach_source(src)
    src.deliver()                                # what the handler runs
    n = b.pending_for(3)
    assert n is not None and n.kind == "sigterm" and n.deadline == 70.0


def test_gce_source_maps_metadata_states():
    clock = faults.FakeClock()
    b = NoticeBoard(now=clock)
    state = {"v": "NONE"}
    src = GCENoticeSource(rank=0, grace_s=15, fetch=lambda: state["v"])
    b.attach_source(src)
    assert b.poll() == []                        # NONE: nothing pending
    state["v"] = "TERMINATE_ON_HOST_MAINTENANCE"
    assert [n.kind for n in b.poll()] == ["maintenance"]
    state["v"] = "NONE"                          # window cancelled
    assert b.poll() == []
    # transport failure degrades to "no event", never raises
    bad = GCENoticeSource(rank=0, fetch=lambda: 1 / 0)
    b.attach_source(bad)
    b.poll()
    assert bad.errors == 1


def test_make_notice_source_env_factory(monkeypatch):
    monkeypatch.delenv("MXTPU_NOTICE_SOURCE", raising=False)
    assert elastic.make_notice_source(rank=0) is None
    monkeypatch.setenv("MXTPU_NOTICE_SOURCE", "gce")
    src = elastic.make_notice_source(rank=2)
    assert isinstance(src, GCENoticeSource) and src.rank == 2
    monkeypatch.setenv("MXTPU_NOTICE_SOURCE", "bogus")
    with pytest.raises(MXNetError, match="MXTPU_NOTICE_SOURCE"):
        elastic.make_notice_source()


# ----------------------------------------------------------------------
# notice-driven drains at the controller boundary
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


def _data(n=4):
    rng = np.random.RandomState(0)
    return (rng.randn(n, 16, 8).astype(np.float32),
            rng.randn(n, 16, 4).astype(np.float32))


def _ctrl(membership, clock, net=None, **kw):
    import jax
    return ElasticController(membership, devices=jax.devices(),
                             devices_per_worker=4, net=net,
                             backoff_s=0.0, now=clock,
                             sleep=lambda s: None, **kw)


def test_notice_commits_death_ahead_of_heartbeat():
    """The ordering proof: with a 30 s heartbeat timeout, a 10 s-grace
    notice drains the doomed rank ~26 s BEFORE ``_scan_dead`` would
    declare it dead — and the PS scan then has nothing left to do."""
    import jax
    from mxnet_tpu.kvstore.ps_server import PSServer, PSClient
    clock = faults.FakeClock(1000.0)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    srv = PSServer("127.0.0.1", port, num_workers=2,
                   heartbeat_timeout=30.0)
    srv._now = clock
    membership = Membership([0, 1], now=clock)
    srv.attach_membership(membership)
    board = NoticeBoard(now=clock)
    xs, ys = _data(2)
    net, trainer = _build_dp(make_mesh({AXIS_DP: 8}, jax.devices()))
    ctrl = _ctrl(membership, clock, net=net, notices=board)
    c0, c1 = PSClient("127.0.0.1", port), PSClient("127.0.0.1", port)
    try:
        c0.beat_once(0)
        c1.beat_once(1)
        trainer.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
        # rank 1's platform announces the preemption; the worker goes
        # silent at the same instant
        board.post(1, grace_s=10, kind="preempt")
        clock.advance(4.0)
        assert srv._scan_dead() == []            # heartbeat: 26 s away
        ev = ctrl.check_step(1, trainer, params=net)
        assert ev is not None and ev["dp"] == 4  # drained + resharded
        assert membership.epoch == 1 and membership.ranks == (0,)
        assert ctrl.drains == 1
        assert board.stats()["drained"] == 1
        clock.advance(30.0)                      # past the hb timeout
        c0.beat_once(0)                          # the survivor is fine
        assert srv._scan_dead() == [1]           # hb finally notices...
        assert membership.epoch == 1             # ...nothing to commit
        trainer.step(mx.nd.array(xs[1]), mx.nd.array(ys[1]))
    finally:
        c0.close()
        c1.close()
        srv._sock.close()


def test_revoked_notice_cancels_pending_drain():
    clock = faults.FakeClock()
    membership = Membership([0, 1], now=clock)
    board = NoticeBoard(now=clock)
    src = FakeNoticeSource()
    board.attach_source(src)
    ctrl = _ctrl(membership, clock, notices=board)
    src.preempt(1, grace_s=60)
    board.poll()
    assert board.pending_for(1) is not None
    board.revoke(1)                              # maintenance cancelled
    assert ctrl.check_step(1, trainer=None) is None
    assert membership.epoch == 0 and membership.ranks == (0, 1)
    assert ctrl.drains == 0


def test_drain_deadline_is_typed_and_publishes_gauge():
    clock = faults.FakeClock(0.0)
    membership = Membership([0, 1], now=clock)
    board = NoticeBoard(now=clock)
    ctrl = _ctrl(membership, clock, notices=board)
    board.post(1, grace_s=2.0)
    clock.advance(3.0)                           # grace lapsed mid-step
    with pytest.raises(DrainDeadline) as ei:
        ctrl.check_step(1, trainer=None)
    assert ei.value.notice.rank == 1
    assert board.stats()["expired"] == 1
    assert membership.epoch == 0                 # heartbeat path owns it
    # the gauge was published at the boundary (satellite contract)
    if telemetry.enabled():
        assert telemetry.value("elastic.pending_notices") == 0
        assert telemetry.value("notices.expired") == 1


def test_drain_checkpoint_runs_before_the_death_commits():
    import jax
    clock = faults.FakeClock()
    membership = Membership([0, 1], now=clock)
    board = NoticeBoard(now=clock)
    xs, ys = _data(2)
    net, trainer = _build_dp(make_mesh({AXIS_DP: 8}, jax.devices()))
    order = []
    membership.subscribe(lambda ev: order.append(ev.kind))
    ctrl = _ctrl(membership, clock, net=net, notices=board,
                 drain_checkpoint=lambda s: order.append(f"ckpt@{s}"))
    trainer.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    board.post(1, grace_s=30)
    ctrl.check_step(7, trainer, params=net)
    assert order[:2] == ["ckpt@7", "death"]      # checkpoint THEN reshard
    assert ctrl.last_drain_ms is not None
    assert ctrl.stats()["drains"] == 1


# ----------------------------------------------------------------------
# the autoscaler: hysteresis, cooldown, bounds, kill switch
# ----------------------------------------------------------------------

class _StubController:
    """Just the surface Autoscaler touches — no mesh, no reshard."""

    def __init__(self, dp=4, capacity=8):
        self.applied_dp = dp
        self._capacity = capacity
        self.requests = []

    def target_dp(self, include_pending=True):
        return self._capacity

    def request_dp(self, n):
        self.requests.append(n)
        self.applied_dp = n
        return n


def test_autoscaler_hysteresis_window_and_cooldown():
    clock = faults.FakeClock(0.0)
    ctrl = _StubController(dp=4, capacity=16)
    scaler = Autoscaler(
        ScalingPolicy([ScalingRule("train.step_ms", high=100, low=10,
                                   window_s=5.0)],
                      cooldown_s=30.0, max_dp=16),
        controller=ctrl, now=clock)
    hot = {"train.step_ms": 500.0}
    assert scaler.tick(signals=hot) == []        # breach starts
    clock.advance(3.0)
    assert scaler.tick(signals=hot) == []        # 3 s < 5 s window
    clock.advance(3.0)
    (d,) = scaler.tick(signals=hot)              # window complete
    assert d["verdict"] == "grow" and d["to"] == 8
    assert ctrl.requests == [8]
    clock.advance(6.0)
    assert scaler.tick(signals=hot) == []        # cooldown holds
    assert scaler.skipped["cooldown"] >= 1
    clock.advance(30.0)
    (d2,) = scaler.tick(signals=hot)             # cooldown elapsed
    assert d2["to"] == 16
    # one in-band sample resets the hysteresis window
    clock.advance(31.0)
    assert scaler.tick(signals={"train.step_ms": 50.0}) == []
    assert scaler.tick(signals=hot) == []        # window restarts


def test_autoscaler_respects_min_max_and_capacity_bounds():
    clock = faults.FakeClock(0.0)
    ctrl = _StubController(dp=8, capacity=8)
    scaler = Autoscaler(
        ScalingPolicy([ScalingRule("train.step_ms", high=100, low=10,
                                   window_s=0.0)],
                      cooldown_s=0.0, min_dp=4, max_dp=8),
        controller=ctrl, now=clock)
    assert scaler.tick(signals={"train.step_ms": 500.0}) == []
    assert scaler.skipped["capacity"] == 1       # already at capacity
    (d,) = scaler.tick(signals={"train.step_ms": 1.0})
    assert d["verdict"] == "shrink" and ctrl.requests == [4]
    clock.advance(1.0)
    assert scaler.tick(signals={"train.step_ms": 1.0}) == []
    assert scaler.skipped["bounds"] >= 1         # min_dp floor holds


def test_autoscaler_kill_switch_is_bitwise_inert(monkeypatch):
    """MXTPU_AUTOSCALE=0: ticking the scaler every step changes NOTHING
    — the run is bitwise a run that never constructed one."""
    import jax
    monkeypatch.setenv("MXTPU_AUTOSCALE", "0")
    clock = faults.FakeClock()
    xs, ys = _data(3)

    def run(with_scaler):
        net, trainer = _build_dp(make_mesh({AXIS_DP: 8}, jax.devices()))
        scaler = None
        if with_scaler:
            membership = Membership([0, 1], now=clock)
            ctrl = _ctrl(membership, clock, net=net)
            scaler = Autoscaler(
                ScalingPolicy([ScalingRule("train.step_ms", high=0.001,
                                           window_s=0.0)],
                              cooldown_s=0.0),
                controller=ctrl, now=clock)
        for i in range(3):
            trainer.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
            if scaler is not None:
                assert scaler.tick(
                    signals={"train.step_ms": 999.0}) is None
        return {n: p.data().asnumpy()
                for n, p in net._collect_params_with_prefix().items()}

    a, b = run(True), run(False)
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(a[k], b[k]), k


def test_request_dp_load_rescale_roundtrip():
    """A deliberate load-based dp rescale (no membership change) rides
    the same epoch-fenced resync: 8 -> 4 -> 8, training continues."""
    import jax
    clock = faults.FakeClock()
    xs, ys = _data(4)
    membership = Membership([0, 1], now=clock)
    net, trainer = _build_dp(make_mesh({AXIS_DP: 8}, jax.devices()))
    ctrl = _ctrl(membership, clock, net=net)
    trainer.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    assert ctrl.request_dp(4) == 4
    ev = ctrl.check_step(1, trainer, params=net)
    assert ev["dp"] == 4 and trainer.mesh.shape[AXIS_DP] == 4
    assert membership.epoch == 0                 # no membership change
    trainer.step(mx.nd.array(xs[1]), mx.nd.array(ys[1]))
    # re-requesting the current dp is a no-op, not a reshard
    ctrl.request_dp(4)
    assert ctrl.check_step(2, trainer, params=net) is None
    assert ctrl.request_dp(64) == 8              # clamped to capacity
    ev = ctrl.check_step(2, trainer, params=net)
    assert ev["dp"] == 8 and trainer.mesh.shape[AXIS_DP] == 8
    trainer.step(mx.nd.array(xs[2]), mx.nd.array(ys[2]))
    assert ctrl.transitions == 2


# ----------------------------------------------------------------------
# the degradation ladder
# ----------------------------------------------------------------------

class _StubRouter:
    def __init__(self):
        self.shedding = None

    def set_shedding(self, on, reason=None):
        self.shedding = bool(on)
        return self.shedding


def test_degradation_ladder_rungs_and_recovery():
    clock = faults.FakeClock()
    router = _StubRouter()
    stops = []
    ladder = DegradationLadder(router=router, stop=stops.append,
                               now=clock)
    assert ladder.assess(8, 8, 2) == "ok" and router.shedding is None
    assert ladder.assess(4, 8, 2) == "shed"      # rung 1
    assert router.shedding is True and ladder.level == 1
    assert ladder.assess(1, 8, 2) == "stop"      # rung 3
    assert len(stops) == 1 and "below" in stops[0]
    assert ladder.assess(8, 8, 2) == "ok"        # recovery un-sheds
    assert router.shedding is False and ladder.level == 0
    kinds = [t["kind"] for t in ladder.transitions]
    assert kinds == ["shed", "stop", "recovered"]


def test_controller_capacity_stop_walks_ladder_rung3():
    """Below the MXTPU_ELASTIC_MIN_DP floor WITH a ladder attached the
    controller hands off to checkpoint-and-stop instead of raising."""
    clock = faults.FakeClock()
    membership = Membership([0, 1], now=clock)
    stops = []
    ladder = DegradationLadder(stop=stops.append, now=clock)
    ctrl = _ctrl(membership, clock, min_dp=8, ladder=ladder)
    membership.worker_dead(1)
    ev = ctrl.check_step(1, trainer=None)
    assert ev["source"] == "stop" and len(stops) == 1
    assert ctrl.degraded
    # and the boundary is quiescent afterwards (no retry storm)
    assert ctrl.check_step(2, trainer=None) is None


# ----------------------------------------------------------------------
# estimator wiring: drains + drain_checkpoint + the emergency exit
# ----------------------------------------------------------------------

def test_estimator_drains_notice_and_wires_drain_checkpoint(tmp_path):
    """fit(elastic_controller=, autoscaler=): a notice posted mid-epoch
    drains at the NEXT boundary (checkpoint-then-reshard through the
    loop's own manager), training continues seamlessly at the smaller
    dp, and the autoscaler ticks without effect (neutral signals)."""
    import jax
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.gluon.contrib.estimator import Estimator, BatchEnd
    clock = faults.FakeClock()
    xs, ys = _data(6)
    net, trainer = _build_dp(make_mesh({AXIS_DP: 8}, jax.devices()))
    membership = Membership([0, 1], now=clock)
    board = NoticeBoard(now=clock)
    ctrl = _ctrl(membership, clock, net=net, notices=board)
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=5,
                            async_save=False)
    scaler = Autoscaler(
        ScalingPolicy([ScalingRule("train.step_ms", high=1e12,
                                   window_s=1.0)], cooldown_s=1.0),
        controller=ctrl, now=clock)

    class NoticeAt(BatchEnd):
        def batch_end(self, estimator, *args, **kwargs):
            if estimator.global_step + 1 == 3 and \
                    board.stats()["posted"] == 0:
                board.post(1, grace_s=60, kind="preempt")

    batches = [(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
               for i in range(6)]
    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[metric_mod.Loss()], trainer=trainer)
    est.fit(batches, epochs=1, event_handlers=[NoticeAt()],
            elastic_controller=ctrl, autoscaler=scaler,
            checkpoint_manager=mgr, checkpoint_every=100)
    assert not est.preempted and est.global_step == 6
    assert trainer.mesh.shape[AXIS_DP] == 4
    assert ctrl.drains == 1 and membership.epoch == 1
    assert mgr.latest() == 2         # checkpoint-THEN-reshard, cursored


def test_estimator_drain_deadline_takes_emergency_exit(tmp_path):
    """A notice whose grace lapsed mid-step: the boundary raises the
    typed DrainDeadline and the loop takes the PR 4 exit — sync
    checkpoint, stop with .preempted."""
    import jax
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.gluon.contrib.estimator import Estimator, BatchEnd
    clock = faults.FakeClock()
    xs, ys = _data(6)
    net, trainer = _build_dp(make_mesh({AXIS_DP: 8}, jax.devices()))
    membership = Membership([0, 1], now=clock)
    board = NoticeBoard(now=clock)
    ctrl = _ctrl(membership, clock, net=net, notices=board)
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=5,
                            async_save=False)

    class LateNotice(BatchEnd):
        def batch_end(self, estimator, *args, **kwargs):
            if estimator.global_step + 1 == 3 and \
                    board.stats()["posted"] == 0:
                board.post(1, grace_s=1.0)
                clock.advance(5.0)       # the step outlived the grace

    batches = [(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
               for i in range(6)]
    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[metric_mod.Loss()], trainer=trainer)
    est.fit(batches, epochs=1, event_handlers=[LateNotice()],
            elastic_controller=ctrl, checkpoint_manager=mgr,
            checkpoint_every=100)
    assert est.preempted and est.global_step == 2
    assert mgr.latest() == 2             # the emergency sync save
    assert trainer.mesh.shape[AXIS_DP] == 8   # no reshard happened


# ----------------------------------------------------------------------
# the chaos acceptance scenario (also python -m
# mxnet_tpu.testing.chaos autoscale)
# ----------------------------------------------------------------------

@pytest.mark.slow   # the queue runner re-runs this exact scenario
def test_chaos_autoscale_scenario(tmp_path):
    from mxnet_tpu.testing.chaos import run_autoscale_scenario
    r = run_autoscale_scenario(workdir=str(tmp_path))
    assert r["params_bitwise_dp4"] and r["state_bitwise_dp4"], r
    assert r["params_bitwise"] and r["state_bitwise"], r
    assert r["serving_no_lost_or_dup"], r
    assert r["load_driven_grow"], r
    assert r["ok"], r
