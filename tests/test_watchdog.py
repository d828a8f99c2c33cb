"""Health watchdog (ISSUE 14).

Covers the rule catalog (non-finite loss/grad, loss spike vs trailing
window, FakeClock step stall, serving queue saturation, KV-block leak
trend), the typed ``watchdog.*`` event + ``reason="watchdog:<rule>"``
flight-dump contract, and the bitwise-inert ``MXTPU_WATCHDOG=0`` kill
switch.
"""
import json
import math
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.telemetry import watchdog
from mxnet_tpu.telemetry.watchdog import Watchdog
from mxnet_tpu.testing import faults
from mxnet_tpu.testing.faults import FakeClock

nd = mx.nd


def _events(kind):
    return [e for e in telemetry.events() if e["kind"] == kind]


# ----------------------------------------------------------------------
# rule catalog
# ----------------------------------------------------------------------

def test_nonfinite_loss_fires_typed_event_and_flight_dump(tmp_path,
                                                          monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    wd = Watchdog(now=FakeClock(0.0))
    watchdog.configure(enabled=True, instance=wd)
    wd.on_step(1, loss=0.5)
    wd.on_step(2, loss=float("nan"))
    evs = _events("watchdog.nonfinite_loss")
    assert len(evs) == 1
    assert evs[0]["data"]["step"] == 2
    assert telemetry.value("watchdog.trips") == 1
    path = telemetry.last_flight_dump()
    assert path and path.startswith(str(tmp_path))
    dump = json.load(open(path))
    assert dump["reason"] == "watchdog:nonfinite_loss"
    assert dump["events"][-1]["kind"] == "watchdog.nonfinite_loss"
    # edge-triggered: a NaN plateau is ONE incident...
    wd.on_step(3, loss=float("nan"))
    assert len(_events("watchdog.nonfinite_loss")) == 1
    # ...and a recovery re-arms the rule
    wd.on_step(4, loss=0.5)
    wd.on_step(5, loss=float("inf"))
    assert len(_events("watchdog.nonfinite_loss")) == 2


def test_nonfinite_grad_norm_rule():
    wd = Watchdog(now=FakeClock(0.0))
    watchdog.configure(enabled=True, instance=wd)
    wd.on_step(1, grad_norm=1.25)
    wd.on_step(2, grad_norm=float("nan"))
    assert [r for r, _ in wd.trips] == ["nonfinite_grad"]
    assert len(_events("watchdog.nonfinite_grad")) == 1


def test_loss_spike_vs_trailing_window():
    wd = Watchdog(now=FakeClock(0.0), spike_factor=10.0)
    watchdog.configure(enabled=True, instance=wd)
    for i in range(6):
        wd.on_step(i + 1, loss=1.0 + 0.01 * i)
    assert wd.trips == []
    wd.on_step(7, loss=50.0)               # ~50x the trailing mean
    evs = _events("watchdog.loss_spike")
    assert len(evs) == 1
    assert evs[0]["data"]["loss"] == 50.0
    assert 0.9 < evs[0]["data"]["trailing_mean"] < 1.1
    # steady losses (even high ones, once in the window) don't re-fire
    for i in range(8, 12):
        wd.on_step(i, loss=1.0)
    assert len(_events("watchdog.loss_spike")) == 1


def test_step_stall_via_fakeclock_gap_and_slow_step():
    clock = FakeClock(1000.0)
    wd = Watchdog(now=clock, stall_s=30.0)
    watchdog.configure(enabled=True, instance=wd)
    wd.on_step(1)
    clock.advance(5.0)
    wd.on_step(2)
    assert not wd.check(step=2)
    assert wd.trips == []
    clock.advance(31.0)                    # silence past the threshold
    assert wd.check(step=2)
    evs = _events("watchdog.step_stall")
    assert len(evs) == 1
    assert evs[0]["data"]["gap_s"] == 31.0
    assert evs[0]["data"]["stall_s"] == 30.0
    # one slow step alone (step_ms form) also counts as a stall
    wd2 = Watchdog(now=FakeClock(0.0), stall_s=30.0)
    watchdog.configure(instance=wd2)
    wd2.on_step(1, step_ms=31_000.0)
    assert [r for r, _ in wd2.trips] == ["step_stall"]


def test_queue_saturation_needs_consecutive_boundaries():
    wd = Watchdog(now=FakeClock(0.0), queue_depth=4, queue_boundaries=3)
    watchdog.configure(enabled=True, instance=wd)
    for _ in range(2):
        wd.on_serving_boundary(queue_depth=9)
    wd.on_serving_boundary(queue_depth=0)   # dip resets the streak
    for _ in range(2):
        wd.on_serving_boundary(queue_depth=9)
    assert wd.trips == []
    wd.on_serving_boundary(queue_depth=9)   # third consecutive breach
    evs = _events("watchdog.queue_saturation")
    assert len(evs) == 1
    assert evs[0]["data"]["boundaries"] == 3


def test_kv_leak_trend_rises_vs_plateau():
    wd = Watchdog(now=FakeClock(0.0), kv_window=4, kv_windows=2)
    watchdog.configure(enabled=True, instance=wd)
    # normal load: the per-window minimum returns to the same floor
    for _ in range(3):
        for v in (2, 6, 4, 2):
            wd.on_serving_boundary(kv_blocks_in_use=v)
    assert wd.trips == []
    # leak: even the emptiest boundary of each window keeps rising
    for base in (3, 4, 5):
        for v in (base, base + 4, base + 2, base):
            wd.on_serving_boundary(kv_blocks_in_use=v)
    evs = _events("watchdog.kv_leak")
    assert len(evs) == 1
    assert evs[0]["data"]["rising_windows"] == 2


def test_scheduler_boundary_ticks_watchdog(monkeypatch):
    """The ContinuousBatcher's decode boundary feeds the serving rules
    (queue depth + kv blocks) through the module seam."""
    seen = []

    class Probe:
        def on_serving_boundary(self, queue_depth=None,
                                kv_blocks_in_use=None):
            seen.append((queue_depth, kv_blocks_in_use))
    watchdog.configure(enabled=True, instance=Probe())
    from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                     LlamaForCausalLM)
    from mxnet_tpu.serving import (ContinuousBatcher, InferenceEngine,
                                   Request)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                      num_heads=2, num_kv_heads=2, intermediate_size=64,
                      max_seq_len=64, tie_embeddings=True)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    net(nd.array(np.zeros((1, 4), np.int32)))
    eng = InferenceEngine(net, max_batch=2, block_size=8,
                          max_context=32).warmup()
    b = ContinuousBatcher(eng)
    b.submit(Request([3, 5, 7], max_new_tokens=3))
    b.run()
    assert len(seen) == b.decode_steps
    assert all(isinstance(q, int) and isinstance(k, int)
               for q, k in seen)


def test_fault_point_injects_nan_loss_through_production_path(tmp_path,
                                                             monkeypatch):
    """The chaos seam: ``watchdog.loss`` (testing/faults.py) swaps the
    observed loss for a NaN inside on_step itself."""
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    wd = Watchdog(now=FakeClock(0.0))
    watchdog.configure(enabled=True, instance=wd)
    with faults.inject("watchdog.loss", at=2, times=1,
                       action=lambda p: float("nan")):
        wd.on_step(1, loss=1.0)
        wd.on_step(2, loss=1.0)            # injected: observed as NaN
    assert [r for r, _ in wd.trips] == ["nonfinite_loss"]
    dump = json.load(open(telemetry.last_flight_dump()))
    assert dump["reason"] == "watchdog:nonfinite_loss"


# ----------------------------------------------------------------------
# kill switch + estimator wiring
# ----------------------------------------------------------------------

def test_kill_switch_is_inert():
    watchdog.configure(enabled=False)
    try:
        watchdog.on_step(1, loss=float("nan"))
        watchdog.on_serving_boundary(queue_depth=10**9)
        assert watchdog.check() is False
        assert telemetry.events() == []
        assert telemetry.registry().snapshot()["counters"] == {}
    finally:
        watchdog.reset()
    assert watchdog.enabled()              # env default restored


def test_watchdog_env_defaults(monkeypatch):
    monkeypatch.setenv("MXTPU_WATCHDOG_STALL_S", "7.5")
    monkeypatch.setenv("MXTPU_WATCHDOG", "0")
    watchdog.reset()
    try:
        assert not watchdog.enabled()
        assert Watchdog().stall_s == 7.5
    finally:
        monkeypatch.delenv("MXTPU_WATCHDOG")
        monkeypatch.delenv("MXTPU_WATCHDOG_STALL_S")
        watchdog.reset()
    assert watchdog.enabled()


def test_estimator_ticks_loss_rules(tmp_path, monkeypatch):
    """estimator.fit pulls the loss for metrics anyway; the watchdog's
    loss rules ride that existing host value — a NaN batch is caught
    at the step boundary."""
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    wd = Watchdog(now=FakeClock(0.0))
    watchdog.configure(enabled=True, instance=wd)
    mx.random.seed(3)
    np.random.seed(3)
    net = gluon.nn.Dense(2)
    net.initialize()
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1})
    x = np.random.randn(4, 16, 4).astype(np.float32)
    x[2, 0, 0] = np.nan                    # one poisoned batch
    y = np.random.randn(4, 16, 2).astype(np.float32)
    data = [(nd.array(x[i]), nd.array(y[i])) for i in range(4)]
    est = Estimator(net, gluon.loss.L2Loss(), trainer=trainer)
    est.fit(data, epochs=1)
    rules = [r for r, _ in wd.trips]
    assert "nonfinite_loss" in rules
    assert _events("watchdog.nonfinite_loss")[0]["data"]["step"] == 3


def test_watchdog_chaos_scenario(tmp_path, monkeypatch):
    """The tier-1 wiring of the ``watchdog`` chaos suite: NaN-loss injection
    through the fault point + FakeClock step stall, each leaving the
    typed event and a flight dump whose reason names the rule."""
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    from mxnet_tpu.testing.chaos import run_watchdog_scenario
    r = run_watchdog_scenario(workdir=str(tmp_path))
    assert r["ok"], r
    assert r["trips"] == ["nonfinite_loss", "step_stall"]
    assert r["nan_flight"]["reason"] == "watchdog:nonfinite_loss"
    assert r["stall_flight"]["reason"] == "watchdog:step_stall"
