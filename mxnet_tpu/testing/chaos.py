"""Kill-and-resume chaos smoke: the fault-tolerance layer end to end.

``python -m mxnet_tpu.testing.chaos`` runs, on the simulated CPU mesh,
the exact scenario the acceptance bar demands — in one process, deterministically:

1. **Reference run**: N training steps, uninterrupted; final params +
   optimizer state recorded.
2. **Chaos run**: same seed/data.  The checkpoint writer is killed on
   its first attempt (the save must survive via the next one), a
   simulated preemption fires at step K, the preemption save goes
   through, and the newest checkpoint is then CORRUPTED on disk — so
   resume must fall back to the previous valid one and replay forward.
3. **Resume**: a fresh net/trainer auto-resumes from ``latest()``
   (skipping the corrupted checkpoint), trains to N total steps, and
   must match the reference run BITWISE (params and optimizer state).

Runs the scenario twice: plain ``gluon.Trainer`` and
``DataParallelTrainer(shard_updates=True)``.  Prints one JSON verdict
line; exit code 0 only if every check passed.

``python -m mxnet_tpu.testing.chaos elastic`` runs the ELASTIC MEMBERSHIP
scenarios instead (ISSUE 8) — kill/join workers mid-run and demand
bitwise continuation parity, all on the simulated 8-device CPU mesh
with a ``FakeClock`` (zero sleeps):

- ``shrink``  — PS heartbeats stop for worker 1 at step K; the server's
  ``_scan_dead`` commits the death into the membership, the controller
  pauses at the boundary, reshards dp 8 -> 4 peer-to-peer, resumes.
  Final fp32 params + optimizer state must be BITWISE a fresh dp=4
  process restored from the same boundary state.
- ``grow``    — worker 1 announces a join at step K' (epoch-checked),
  the controller admits it at the boundary: dp 4 -> 8, same parity bar
  against a fresh dp=8 process.
- ``reshard_fault`` — the death fires at K but the peer transfer
  itself is killed (``elastic.reshard`` fault point, every retry): the
  controller falls back to the newest valid checkpoint, training
  rewinds to its step and replays at dp=4 — parity against a fresh
  process restored from that same checkpoint.

``python -m mxnet_tpu.testing.chaos serving`` runs the SERVING FRONT-END
scenario instead (ISSUE 12), deterministic on CPU with a FakeClock and
zero sleeps: a 2-replica ``serving.frontend.Router`` (prefix cache +
chunked prefill on, shared warmup compile cache) serves a
shared-system-prompt mix; replica 1 is killed mid-traffic via the
``serving.replica1.step`` fault point; the router must bump the
replica-set epoch, drain and REQUEUE the dead replica's in-flight
requests, and finish every request exactly once with the exact token
stream a solo cold-path engine produces (greedy decode is
deterministic and the prefix path is bitwise the cold path).  The kill
must leave a parseable flight-recorder dump, racecheck must report
zero findings, and the surviving replica's KV pool must pass the leak
sweep (prefix-chain holds accounted).

``python -m mxnet_tpu.testing.chaos disagg`` runs the DISAGGREGATED
prefill/decode scenario (ISSUE 18): a 4-replica fleet (prefill rids
0/2, decode rids 1/3) over ONE shared ``PagedKVCache`` serves a mixed
prompt set; a prefill replica is killed mid-handoff via the
``serving.replica0.handoff`` fault point (between "prefill finished"
and "decode adopted" — the worst spot for the adopt-then-release
block-ownership protocol) and, in a second pass, a decode replica is
killed at a scheduling boundary.  Every request must finish exactly
once with the solo combined-role token stream, zero compiles after
warmup, and the shared pool must pass the leak sweep on the survivors.

``python -m mxnet_tpu.testing.chaos autoscale`` runs the PRODUCTION-ELASTICITY
scenario (ISSUE 13), deterministic on the CPU mesh with a FakeClock and
zero sleeps: a preemption NOTICE for training worker 1 drains it at a
step boundary AHEAD of the heartbeat timeout (checkpoint-then-reshard
dp 8 -> 4), the degradation ladder sheds serving admissions while
capacity is below target, the notice is then REVOKED (maintenance
cancelled) and the load-based autoscaler grows dp back 4 -> 8 through
the same epoch-fenced resync — with params + optimizer state BITWISE a
fresh restore at EACH intermediate dp.  On the serving side a notice
drains a router replica mid-traffic (zero lost/duplicated requests,
identical-prompt streams bitwise-equal) and the serving autoscaler
adds a replacement replica from the shared compile cache (zero new
compiles).  Every injected notice leaves a parseable flight dump;
racecheck is armed; the KV pools pass the leak sweep.

``python -m mxnet_tpu.testing.chaos watchdog`` runs the RUN-HEALTH scenario
(ISSUE 14): a NaN loss injected through the ``watchdog.loss`` fault
point and a FakeClock step stall must each emit a typed ``watchdog.*``
event and dump the flight recorder with ``reason="watchdog:<rule>"``.

``python -m mxnet_tpu.testing.chaos fleet`` runs the FLEET-OBSERVABILITY
scenario (ISSUE 15): N simulated workers (per-rank metric registries —
exactly what a remote ``PSClient.telemetry()`` scrape returns) stepped
under ONE FakeClock with zero sleeps, one injected straggler (its
steps run long via the ``fleet.straggle`` fault-point clock advance)
and one scrape-dead rank (its transport raises).  The
``FleetCollector`` must name BOTH ranks in typed ``fleet.straggler`` /
``fleet.scrape_dead`` events with flight dumps whose reason carries
the rule, the merged histograms must equal the element-wise per-rank
bucket sums bitwise, and racecheck must report zero findings on the
collector locks.

``python -m mxnet_tpu.testing.chaos procs`` runs the MULTI-PROCESS scenario
(ISSUE 19) — the only suite with real processes instead of threads
under FakeClock: a 4-process pod over ``jax.distributed`` (the
``mxnet_tpu.pod.PodLauncher`` runtime), one worker SIGKILLed while the
whole pod is parked at a step gate.  The launcher must commit the
membership change, the survivors must tear down + re-init the JAX
coordination service at ``jax.process_count() == 3`` and resume from
the shared checkpoint BITWISE a fresh 3-process pod restored from the
same checkpoint, the file-lease request ledger must end exactly-once
(the victim's held lease requeued), and a real fleet scrape over the
workers' PS endpoints must name the dead rank typed with ``rpc.*``
counters and a flight dump behind it.

``python -m mxnet_tpu.testing.chaos all`` runs all eight suites.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

import numpy as _np


def _racecheck_arm():
    """Run the scenario under the runtime race/lock-order detector
    (ISSUE 10): every chaos interleaving doubles as a concurrency test.
    ``MXTPU_RACECHECK=0`` is the explicit opt-out; otherwise the
    detector is enabled for the scenario regardless of ambient env, so
    the tier-1 chaos tests always exercise it."""
    from mxnet_tpu.lint import racecheck
    if os.environ.get("MXTPU_RACECHECK", "") == "0":
        return None
    racecheck.reset()               # this scenario's findings only
    racecheck.configure(enabled=True)
    return racecheck


def _racecheck_verdict(rc):
    """Post-scenario gate: zero findings, or the scenario fails."""
    if rc is None:
        return None
    found = rc.findings()
    return {"enabled": True, "findings": len(found),
            "kinds": sorted({f["kind"] for f in found}),
            "ok": not found}


def _donation_arm():
    """Run the scenario under the use-after-donate sentinel (ISSUE 16):
    every chaos interleaving doubles as a donation-correctness test —
    the trainer/engine seams poison their donated buffers and any stale
    host touch fails the scenario the way a TPU run would crash.
    ``MXTPU_DONATION_CHECK=0`` is the explicit opt-out."""
    from mxnet_tpu.lint import donation
    if os.environ.get("MXTPU_DONATION_CHECK", "") == "0":
        return None
    donation.reset()                # this scenario's findings only
    donation.configure(enabled=True)
    return donation


def _donation_verdict(dc):
    """Post-scenario gate: zero use-after-donate findings, or the
    scenario fails."""
    if dc is None:
        return None
    found = dc.findings()
    return {"enabled": True, "findings": len(found),
            "sites": sorted({f["site"] for f in found}),
            "ok": not found}


def _flight_check(expect_kind=None):
    """Assert the telemetry flight recorder left a parseable dump for
    the kill this scenario just injected (ISSUE 9): the dump must exist,
    parse, carry a metric snapshot, and its LAST event must be the
    incident (``expect_kind`` prefix, e.g. ``"preemption"`` /
    ``"fault.trip"``).  Returns None when telemetry is disabled (nothing
    to assert — the kill switch is a supported mode)."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        return None
    path = telemetry.last_flight_dump()
    out = {"ok": False, "path": path}
    if not path or not os.path.exists(path):
        return out
    try:
        with open(path) as f:
            dump = json.load(f)
    except (OSError, ValueError) as e:
        out["error"] = f"unparseable: {e}"
        return out
    events = dump.get("events") or []
    last = events[-1] if events else {}
    out["reason"] = dump.get("reason")
    out["last_kind"] = last.get("kind")
    out["last_step"] = last.get("step")
    out["ok"] = bool(dump.get("metrics")) and bool(events) and (
        expect_kind is None or str(last.get("kind", "")
                                   ).startswith(expect_kind))
    return out


def _make_data(seed, n_batches=8, batch=16, din=8, dout=4):
    rng = _np.random.RandomState(seed)
    xs = rng.randn(n_batches, batch, din).astype(_np.float32)
    ys = rng.randn(n_batches, batch, dout).astype(_np.float32)
    return xs, ys


def _build(mode, dout=4):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    mx.random.seed(1234)
    _np.random.seed(1234)
    net = gluon.nn.Dense(dout)
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    if mode == "sharded":
        trainer = parallel.DataParallelTrainer(
            net, loss_fn, "adam", {"learning_rate": 0.05},
            shard_updates=True)

        def step(x, y):
            return trainer.step(mx.nd.array(x), mx.nd.array(y))
    else:
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 0.05})

        def step(x, y):
            from mxnet_tpu import autograd
            xb, yb = mx.nd.array(x), mx.nd.array(y)
            with autograd.record():
                loss = loss_fn(net(xb), yb)
            loss.backward()
            trainer.step(xb.shape[0])
            return loss
    return net, trainer, step


def _params_of(net):
    return {name: p.data().asnumpy()
            for name, p in net._collect_params_with_prefix().items()}


def _state_of(trainer):
    sd = trainer.state_dict()
    return {k: v.asnumpy() for k, v in sd["arrays"].items()}


def _bitwise(a, b):
    return set(a) == set(b) and \
        all(_np.array_equal(a[k], b[k]) for k in a)


def run_scenario(mode, total_steps=6, preempt_at=3, workdir=None,
                 resume_steps_per_call=1):
    """``resume_steps_per_call`` > 1 (ISSUE 6): the RESUME phase drives
    ``step_multi`` windows of that size instead of per-step calls — the
    surviving checkpoint sits at a step that is NOT a multiple of K
    (written mid-scan-window relative to the resumed run's grid), so
    this asserts that a non-K-aligned resume reproduces the K=1
    reference curve bitwise (partial tail windows included).  Needs a
    trainer with ``step_multi`` (the sharded mode)."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.checkpoint import CheckpointManager, run_preemptible
    from mxnet_tpu.testing import faults

    rc = _racecheck_arm()
    dc = _donation_arm()
    k_resume = int(resume_steps_per_call)
    if k_resume > 1 and mode != "sharded":
        raise MXNetError(
            "resume_steps_per_call>1 needs the sharded "
            "(DataParallelTrainer) scenario — gluon.Trainer is eager")
    ckdir = os.path.join(workdir, f"ckpt-{mode}-k{k_resume}")
    xs, ys = _make_data(99)
    result = {"mode": mode, "preempt_at": preempt_at,
              "total_steps": total_steps,
              "resume_steps_per_call": k_resume}

    # 1. reference: uninterrupted
    net, trainer, step = _build(mode)
    for i in range(total_steps):
        step(xs[i], ys[i])
    ref_params, ref_state = _params_of(net), _state_of(trainer)

    # 2. chaos run: writer killed on attempt 1, preempted at step K
    net, trainer, step = _build(mode)
    mgr = CheckpointManager(ckdir, keep=3)
    writer_died = False

    def loop(handler):
        nonlocal writer_died
        for i in range(total_steps):
            step(xs[i], ys[i])
            done = i + 1
            if handler.check_step(done):
                # preemption: force-sync the final checkpoint and stop
                mgr.save(done, params=net, trainer=trainer,
                         iterator={"batch": done}, sync=True)
                return done
            if done == 1:
                # kill THIS save's writer thread; the error must surface
                # on the NEXT save without dropping that next snapshot
                with faults.inject("checkpoint.write", times=1):
                    t1 = mgr.save(done, params=net, trainer=trainer,
                                  iterator={"batch": done})
                    # writer must HIT the armed fault before it disarms;
                    # the error stays unconsumed for the next save
                    t1._done.wait(30)
            else:
                try:
                    ticket = mgr.save(done, params=net, trainer=trainer,
                                      iterator={"batch": done})
                except MXNetError as e:
                    writer_died = True   # previous writer's death
                    ticket = getattr(e, "pending_ticket", None)
                if ticket is not None:
                    ticket.wait()
        return total_steps

    with faults.inject("train.step", at=preempt_at,
                       action=faults.preempt_action):
        preempted, stopped_at = run_preemptible(loop, mgr)
    result["writer_kill_surfaced"] = writer_died
    result["preempted_at"] = stopped_at
    result["preempted"] = preempted
    # the injected kill must have left a flight-recorder post-mortem
    # whose last event IS the preemption (ISSUE 9)
    result["flight_dump"] = _flight_check(expect_kind="preemption")

    # 3. corrupt the newest checkpoint: latest() must skip to an older one
    newest = mgr.latest()
    faults.corrupt_file(os.path.join(
        mgr._step_dir(newest), "params.ndz"))
    fallback = mgr.latest()
    result["corrupt_skipped"] = {"newest": newest, "fallback": fallback,
                                 "ok": fallback is not None
                                 and fallback < newest}

    # 4. resume from the surviving checkpoint, replay to total_steps
    net, trainer, step = _build(mode)
    # resolve shapes before trainer state restore
    import mxnet_tpu as mx
    net(mx.nd.array(xs[0]))
    manifest = mgr.restore(params=net, trainer=trainer)
    start = manifest["iterator"]["batch"]
    result["resumed_from"] = manifest["step"]
    if k_resume > 1:
        # K-step compiled replay from a mid-window checkpoint: windows
        # re-form at the resumed step; the tail window may be short
        i = start
        while i < total_steps:
            w = min(k_resume, total_steps - i)
            trainer.step_multi(
                [(mx.nd.array(xs[j]), mx.nd.array(ys[j]))
                 for j in range(i, i + w)])
            i += w
    else:
        for i in range(start, total_steps):
            step(xs[i], ys[i])
    result["params_bitwise"] = _bitwise(ref_params, _params_of(net))
    result["state_bitwise"] = _bitwise(ref_state, _state_of(trainer))
    fd = result["flight_dump"]
    result["racecheck"] = _racecheck_verdict(rc)
    rcv = result["racecheck"]
    result["donation"] = _donation_verdict(dc)
    dcv = result["donation"]
    result["ok"] = bool(
        result["params_bitwise"] and result["state_bitwise"]
        and result["corrupt_skipped"]["ok"] and preempted
        and writer_died and (fd is None or fd["ok"])
        and (rcv is None or rcv["ok"])
        and (dcv is None or dcv["ok"]))
    return result


# ----------------------------------------------------------------------
# Elastic membership scenarios (ISSUE 8): kill-at-K / join-at-K' with
# bitwise continuation parity, deterministic on the CPU mesh (FakeClock,
# no sleeps).
# ----------------------------------------------------------------------

def _build_elastic(mesh, seed=1234, dout=4):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    mx.random.seed(seed)
    _np.random.seed(seed)
    net = gluon.nn.Dense(dout)
    net.initialize()
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 0.05},
        mesh=mesh, shard_updates=True)
    return net, trainer


def _capture_boundary(net, trainer):
    """Host snapshot of EXACTLY what a fresh process would restore from
    a checkpoint of this instant: params, per-parameter-space optimizer
    state, and both RNG streams."""
    import mxnet_tpu as mx
    from mxnet_tpu.checkpoint import _rng_state
    sd = trainer.state_dict()
    rng_arrays, rng_meta = _rng_state()
    return {
        "params": {n: p.data().asnumpy().copy() for n, p
                   in net._collect_params_with_prefix().items()},
        "sd": {"arrays": {k: mx.nd.array(v.asnumpy())
                          for k, v in sd["arrays"].items()},
               "meta": dict(sd["meta"])},
        "rng": ({k: mx.nd.array(v.asnumpy())
                 for k, v in rng_arrays.items()}, dict(rng_meta)),
    }


def _restore_boundary(net, trainer, snap):
    import mxnet_tpu as mx
    from mxnet_tpu.checkpoint import _restore_rng
    net(mx.nd.array(_np.zeros((1, 8), _np.float32)))   # resolve shapes
    target = net._collect_params_with_prefix()
    for n, v in snap["params"].items():
        target[n].set_data(v)
    trainer.load_state_dict(snap["sd"])
    _restore_rng(*snap["rng"])


def _final_state(net, trainer):
    return ({n: p.data().asnumpy() for n, p
             in net._collect_params_with_prefix().items()},
            {k: v.asnumpy() for k, v in trainer.state_dict()
             ["arrays"].items()})


def _deliver_ps_death(membership, clock, dead_rank=1, num_workers=2):
    """Close the loop THROUGH the PS heartbeat path (not a direct state
    poke): spin a PSServer on the FakeClock, beat both ranks, drop the
    victim's beats, advance past the timeout, and let ``_scan_dead``
    commit the death into the membership."""
    import socket
    from mxnet_tpu.kvstore.ps_server import PSServer, PSClient
    from mxnet_tpu.testing import faults
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    srv = PSServer("127.0.0.1", port, num_workers=num_workers,
                   heartbeat_timeout=5.0)
    srv._now = clock
    srv.attach_membership(membership)
    clients = [PSClient("127.0.0.1", port) for _ in range(num_workers)]
    try:
        for r, c in enumerate(clients):
            c.beat_once(r)
        clock.advance(3.0)
        for r, c in enumerate(clients):
            if r == dead_rank:
                with faults.inject("ps.heartbeat.drop", action="drop"):
                    assert not c.beat_once(r)
            else:
                c.beat_once(r)
        clock.advance(3.0)      # victim silent past the 5 s timeout
        return srv._scan_dead()
    finally:
        for c in clients:
            c.close()
        srv._sock.close()


def run_elastic_scenario(kind="shrink", total_steps=6, event_at=3,
                         workdir=None):
    """One elastic membership scenario; see the module docstring for
    the three kinds.  Deterministic: FakeClock, no sleeps, bitwise
    parity asserted against a fresh-process reference."""
    import mxnet_tpu as mx
    from mxnet_tpu import elastic
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.parallel.mesh import make_mesh, \
        AXIS_DP as _AXIS_DP
    from mxnet_tpu.testing import faults
    import jax

    rc = _racecheck_arm()
    dc = _donation_arm()
    devices = jax.devices()
    dpw = 4
    ranks = [0] if kind == "grow" else [0, 1]
    dp0 = dpw * len(ranks)
    dp1 = dp0 // 2 if kind != "grow" else dp0 * 2
    clock = faults.FakeClock(1000.0)
    membership = elastic.Membership(ranks, now=clock, rendezvous_s=30)
    mgr = None
    if workdir is not None:
        mgr = CheckpointManager(
            os.path.join(workdir, f"elastic-{kind}"), keep=5)
    xs, ys = _make_data(77, n_batches=total_steps, batch=16)
    net, trainer = _build_elastic(make_mesh({_AXIS_DP: dp0},
                                            devices[:dp0]))
    controller = elastic.ElasticController(
        membership, devices=devices, devices_per_worker=dpw,
        checkpoint_manager=mgr, net=net, backoff_s=0.0,
        now=clock, sleep=lambda s: None)
    result = {"kind": kind, "dp_before": dp0, "dp_after": dp1,
              "event_at": event_at, "total_steps": total_steps}

    snap = None
    ckpt_step = None
    events = []
    step = 0
    fault_ctx = None
    try:
        while step < total_steps:
            trainer.step(mx.nd.array(xs[step]), mx.nd.array(ys[step]))
            step += 1
            if kind == "reshard_fault" and mgr is not None and \
                    step % 2 == 0 and snap is None:
                # pre-event cadence: checkpoints land on EVEN steps, so
                # the fallback genuinely rewinds (event_at is odd)
                mgr.save(step, params=net, trainer=trainer,
                         iterator={"batch": step}, sync=True)
                ckpt_step = step
            if step == event_at and snap is None:
                if kind == "reshard_fault":
                    # the fallback restores the newest checkpoint; the
                    # reference must restore the SAME instant
                    snap = {"from_checkpoint": True}
                else:
                    snap = _capture_boundary(net, trainer)
                if kind == "grow":
                    membership.announce_join(1, membership.epoch)
                else:
                    dead = _deliver_ps_death(membership, clock)
                    result["ps_declared_dead"] = dead
                if kind == "reshard_fault":
                    # every peer attempt (incl. retries) dies mid-
                    # transfer -> checkpoint fallback
                    fault_ctx = faults.inject("elastic.reshard")
                    fault_ctx.__enter__()
            ev = controller.check_step(step, trainer, params=net)
            if ev is not None:
                events.append({k: ev[k] for k in
                               ("source", "step", "dp", "epoch")})
                if fault_ctx is not None:
                    fault_ctx.__exit__(None, None, None)
                    fault_ctx = None
                if ev["source"] == "checkpoint":
                    result["rewound_to"] = ev["step"]
                    step = ev["step"]
    finally:
        if fault_ctx is not None:
            fault_ctx.__exit__(None, None, None)
    params_a, state_a = _final_state(net, trainer)
    result["events"] = events
    result["membership_epoch"] = membership.epoch
    result["final_dp"] = trainer.mesh.shape[_AXIS_DP]

    # reference: a FRESH process at the new dp restored from the same
    # state the reshard moved (boundary snapshot or the fallback
    # checkpoint), replaying the remaining steps
    ref_net, ref_trainer = _build_elastic(
        make_mesh({_AXIS_DP: dp1}, devices[:dp1]), seed=4321)
    if kind == "reshard_fault":
        ref_net(mx.nd.array(xs[0]))
        manifest = mgr.restore(step=ckpt_step, params=ref_net,
                               trainer=ref_trainer)
        start = int(manifest["step"])
    else:
        _restore_boundary(ref_net, ref_trainer, snap)
        start = event_at
    for i in range(start, total_steps):
        ref_trainer.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
    params_b, state_b = _final_state(ref_net, ref_trainer)

    result["params_bitwise"] = _bitwise(params_a, params_b)
    result["state_bitwise"] = _bitwise(state_a, state_b)
    checks = [result["params_bitwise"], result["state_bitwise"],
              result["final_dp"] == dp1,
              membership.epoch >= 1, len(events) == 1]
    if kind == "reshard_fault":
        checks.append(events[0]["source"] == "checkpoint")
        checks.append(result.get("rewound_to") == ckpt_step)
        # the mid-transfer kill must have dumped the flight recorder,
        # last event = the elastic.reshard fault trip (ISSUE 9)
        result["flight_dump"] = _flight_check(expect_kind="fault.trip")
        fd = result["flight_dump"]
        checks.append(fd is None or fd["ok"])
    else:
        checks.append(events[0]["source"] == "peer")
    result["racecheck"] = _racecheck_verdict(rc)
    rcv = result["racecheck"]
    result["donation"] = _donation_verdict(dc)
    dcv = result["donation"]
    checks.append(rcv is None or rcv["ok"])
    checks.append(dcv is None or dcv["ok"])
    result["ok"] = bool(all(checks))
    return result


# ----------------------------------------------------------------------
# Serving front-end scenario (ISSUE 12): kill a router replica
# mid-traffic; zero lost/duplicated requests, outputs exactly the solo
# cold-path streams, flight dump + racecheck + KV leak sweep.
# ----------------------------------------------------------------------

def _serving_net():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                     LlamaForCausalLM)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_seq_len=64, tie_embeddings=True)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    net(mx.nd.array([[1, 2, 3]], dtype="int32"))
    net.hybridize()
    return net


def run_serving_scenario(replicas=2, n_requests=6, kill_rid=1,
                         kill_at_boundary=2, workdir=None):
    """Kill replica ``kill_rid`` at its ``kill_at_boundary``-th
    scheduling boundary while ``n_requests`` shared-system-prompt
    requests are in flight; the router requeues and every request must
    complete exactly once with the solo cold-path token stream.
    Deterministic: the router's drive() mode (no threads), FakeClock
    timestamps, zero sleeps.

    ISSUE 20: under ``MXTPU_KV_DTYPE=fp8`` (or ``bf16``) every engine
    here — solo reference AND fleet — stores its KV pool quantized
    (engines read the env at init), so ``outputs_match_solo`` stays
    the bitwise fleet-vs-solo gate *within* the quantized mode; the
    scenario then additionally teacher-forces the solo streams through
    an explicit fp32-KV engine and gates the max |logit| drift
    (``kv_drift_ok``), publishing ``serving.kv_decode_drift``."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.quant_kv import resolve_kv_dtype
    from mxnet_tpu.serving import InferenceEngine, Request, Router
    from mxnet_tpu.testing import faults

    rc = _racecheck_arm()
    dc = _donation_arm()
    clock = faults.FakeClock(5000.0)
    net = _serving_net()
    rng = _np.random.RandomState(12)
    sys_prompt = rng.randint(0, 64, (12,)).tolist()
    prompts = [sys_prompt + rng.randint(0, 64, (3 + i % 4,)).tolist()
               for i in range(n_requests)]
    speculative = os.environ.get(
        "MXTPU_SPEC_DECODE", "0") not in ("", "0")
    kv_dtype = resolve_kv_dtype()
    result = {"kind": "serving", "replicas": replicas,
              "requests": n_requests, "kill_rid": kill_rid,
              "kill_at_boundary": kill_at_boundary,
              "speculative": speculative,
              "kv_dtype": kv_dtype or "fp32"}

    # solo cold-path references: one fresh single-replica engine per
    # prompt, full-prompt prefill, greedy decode — the stream every
    # routed request must reproduce bit-for-bit.  spec_decode is
    # FORCED OFF here regardless of env: the reference is the plain
    # path, so under MXTPU_SPEC_DECODE=1 the outputs_match_solo gate
    # is exactly the speculative-bitwise acceptance criterion (and a
    # drain/requeue mid-draft must land on the same stream)
    ref_eng = InferenceEngine(net, max_batch=2, block_size=8,
                              max_context=32, spec_decode=False)
    ref_eng.warmup()
    refs = []
    ref_fed = []      # full fed token streams (for fp8 drift replay)
    ref_logits = []   # per-step decode logits under the env kv_dtype
    for p in prompts:
        tok, _ = ref_eng.prefill(0, p)
        cur = list(p) + [int(tok)]
        lgs = []
        for _ in range(3):
            pos = len(cur) - 1
            assert ref_eng.reserve(0, pos)
            nxt, lg = ref_eng.decode([(0, cur[-1], pos)])
            lgs.append(_np.asarray(lg[0], _np.float32))
            cur.append(int(nxt[0]))
        ref_eng.release(0)
        refs.append(cur[len(p):])
        ref_fed.append(cur)
        ref_logits.append(lgs)

    def factory(compile_cache):
        return InferenceEngine(net, max_batch=2, block_size=8,
                               max_context=32, num_blocks=24,
                               prefill_chunk=8, prefix_cache=True,
                               compile_cache=compile_cache)

    router = Router(factory, replicas=replicas, now=clock)
    for rep in router.replicas:
        rep.engine.pin_prefix(sys_prompt)
    reqs = [router.submit(Request(p, max_new_tokens=4))
            for p in prompts]
    with faults.inject(f"serving.replica{kill_rid}.step",
                       at=kill_at_boundary):
        router.drive()
    fin = router.finished()
    result["finished"] = len(fin)
    result["epoch"] = router.epoch
    result["requeues"] = router.requeues
    result["no_lost_or_dup"] = (
        sorted(r.id for r in fin) == sorted(r.id for r in reqs)
        and len(fin) == len(reqs))
    result["outputs_match_solo"] = all(
        r.generated == ref for r, ref in zip(reqs, refs))
    st = router.stats()
    result["compiles_after_warmup"] = st["compiles_after_warmup"]
    result["prefix_hits"] = sum(
        (pr["prefix"] or {}).get("hits", 0)
        for pr in st["per_replica"])
    if speculative:
        # speculative accounting across surviving replicas (evidence,
        # not a gate — acceptance may legitimately be 0 on this mix;
        # the gate is outputs_match_solo staying bitwise)
        drafted = sum(r.batcher.spec_drafted for r in router.replicas
                      if r.alive)
        accepted = sum(r.batcher.spec_accepted for r in router.replicas
                       if r.alive)
        result["spec_drafted"] = drafted
        result["spec_accepted"] = accepted
        result["spec_accept_rate"] = (
            round(accepted / drafted, 4) if drafted else None)
    if kv_dtype is not None:
        # ISSUE 20 drift oracle: teacher-force the SAME token streams
        # the quantized solo reference committed through an explicit
        # fp32-KV engine and bound the max |logit| gap.  The bitwise
        # fleet-vs-solo gate above already ran within the quantized
        # mode; this bounds how far the quantized store sits from full
        # precision on identical inputs.
        f32_eng = InferenceEngine(net, max_batch=2, block_size=8,
                                  max_context=32, spec_decode=False,
                                  kv_dtype="fp32")
        f32_eng.warmup()
        drift = 0.0
        for p, fed, lgs in zip(prompts, ref_fed, ref_logits):
            f32_eng.prefill(0, p)
            for j, ref_lg in enumerate(lgs):
                pos = len(p) + j
                assert f32_eng.reserve(0, pos)
                _, lg = f32_eng.decode([(0, fed[pos], pos)])
                drift = max(drift, float(_np.max(_np.abs(
                    _np.asarray(lg[0], _np.float32) - ref_lg))))
            f32_eng.release(0)
        result["kv_decode_drift"] = round(drift, 6)
        result["kv_drift_ok"] = drift <= 0.25
        if telemetry.enabled():
            telemetry.set_gauge("serving.kv_decode_drift", drift)
    # the injected kill must have left a parseable flight dump whose
    # last event is the fault trip (ISSUE 9 discipline)
    result["flight_dump"] = _flight_check(expect_kind="fault.trip")
    # KV leak sweep on the survivors: with every request released, only
    # the prefix-cache chains may still hold blocks
    leaks_ok = True
    for rep in router.replicas:
        if not rep.alive:
            continue
        try:
            rep.engine.cache.check_leaks(
                holders=rep.engine.prefix_cache.held_blocks())
        except Exception as e:  # noqa: BLE001 — verdict, not crash
            leaks_ok = False
            result["leak_error"] = f"{type(e).__name__}: {e}"
    result["kv_leaks_clean"] = leaks_ok
    fd = result["flight_dump"]
    result["racecheck"] = _racecheck_verdict(rc)
    rcv = result["racecheck"]
    result["donation"] = _donation_verdict(dc)
    dcv = result["donation"]
    result["ok"] = bool(
        result["no_lost_or_dup"] and result["outputs_match_solo"]
        and result["epoch"] >= 1 and result["requeues"] >= 1
        and result["compiles_after_warmup"] == 0 and leaks_ok
        and result.get("kv_drift_ok", True)
        and (fd is None or fd["ok"]) and (rcv is None or rcv["ok"])
        and (dcv is None or dcv["ok"]))
    return result


# ----------------------------------------------------------------------
# Disaggregated prefill/decode scenario (ISSUE 18): paged-KV block
# handoff over ONE shared pool survives a replica killed mid-handoff.
# ----------------------------------------------------------------------

def run_disagg_scenario(n_requests=6, kill_rid=0, kill_point="handoff",
                        kill_at=2, workdir=None):
    """Kill one replica of a 4-replica DISAGGREGATED fleet (prefill
    rids 0/2, decode rids 1/3, ONE shared ``PagedKVCache``) while
    ``n_requests`` requests are in flight.  ``kill_point="handoff"``
    trips the ``serving.replica{rid}.handoff`` fault point — the kill
    lands BETWEEN "prefill finished" and "decode adopted", the worst
    spot for the adopt-then-release block-ownership protocol — and
    ``"step"`` kills at a plain scheduling boundary (pass an odd
    ``kill_rid`` to kill a decode-role replica).  Every request must
    finish exactly once with the solo combined-role token stream, and
    the SHARED pool must pass the leak sweep on the survivors (the dead
    replica's slot holds evacuated, zero blocks stranded).
    Deterministic: drive() mode, FakeClock, zero sleeps."""
    from mxnet_tpu.serving import (ContinuousBatcher, InferenceEngine,
                                   Request, Router)
    from mxnet_tpu.testing import faults

    rc = _racecheck_arm()
    dc = _donation_arm()
    clock = faults.FakeClock(5000.0)
    net = _serving_net()
    rng = _np.random.RandomState(18)
    prompts = [rng.randint(0, 64, (3 + i % 5,)).tolist()
               for i in range(n_requests)]
    result = {"kind": "disagg", "requests": n_requests,
              "kill_rid": kill_rid, "kill_point": kill_point,
              "kill_at": kill_at}

    # solo combined-role reference: one engine, one batcher, no fleet —
    # the stream the disaggregated path must reproduce bit-for-bit
    solo = ContinuousBatcher(InferenceEngine(
        net, max_batch=2, block_size=8, num_blocks=32,
        max_context=32).warmup())
    solo_reqs = [solo.submit(Request(p, max_new_tokens=4))
                 for p in prompts]
    solo.run()
    refs = [list(r.generated) for r in solo_reqs]

    def factory(compile_cache, kv_cache=None):
        return InferenceEngine(net, max_batch=2, block_size=8,
                               num_blocks=32, max_context=32,
                               compile_cache=compile_cache,
                               kv_cache=kv_cache)

    router = Router(factory, replicas=4, disaggregated=True, now=clock)
    reqs = [Request(p, max_new_tokens=4) for p in prompts]
    for r in reqs:
        router.submit(r)
    with faults.inject(f"serving.replica{kill_rid}.{kill_point}",
                       at=kill_at):
        router.drive()
    fin = router.finished()
    result["finished"] = len(fin)
    result["epoch"] = router.epoch
    result["requeues"] = router.requeues
    result["handoffs"] = router.handoffs
    result["no_lost_or_dup"] = (
        sorted(r.id for r in fin) == sorted(r.id for r in reqs)
        and len(fin) == len(reqs))
    result["outputs_match_solo"] = all(
        list(r.generated) == ref for r, ref in zip(reqs, refs))
    st = router.stats()
    result["compiles_after_warmup"] = st["compiles_after_warmup"]
    result["prefill_pool_occupancy"] = st["prefill_pool_occupancy"]
    result["decode_pool_occupancy"] = st["decode_pool_occupancy"]
    result["flight_dump"] = _flight_check(expect_kind="fault.trip")
    # leak sweep on the ONE shared pool: every request finished and the
    # dead replica's holds evacuated, so zero blocks may remain (the
    # scenario runs without prefix chains — no legitimate holders)
    leaks_ok = True
    try:
        router._shared_cache.check_leaks(holders=0)
    except Exception as e:  # noqa: BLE001 — verdict, not crash
        leaks_ok = False
        result["leak_error"] = f"{type(e).__name__}: {e}"
    result["kv_leaks_clean"] = leaks_ok
    fd = result["flight_dump"]
    result["racecheck"] = _racecheck_verdict(rc)
    rcv = result["racecheck"]
    result["donation"] = _donation_verdict(dc)
    dcv = result["donation"]
    result["ok"] = bool(
        result["no_lost_or_dup"] and result["outputs_match_solo"]
        and result["epoch"] >= 1 and result["requeues"] >= 1
        and result["handoffs"] >= 1
        and result["compiles_after_warmup"] == 0 and leaks_ok
        and (fd is None or fd["ok"]) and (rcv is None or rcv["ok"])
        and (dcv is None or dcv["ok"]))
    return result


# ----------------------------------------------------------------------
# Production-elasticity scenario (ISSUE 13): preemption notice -> drain
# -> shrink under load -> notice revoked -> load-driven grow back, with
# bitwise parity at each dp; serving replica drained by notice with
# zero lost requests and an autoscaled replacement replica.
# ----------------------------------------------------------------------

def run_autoscale_scenario(total_steps=6, notice_at=2, revoke_at=4,
                           workdir=None):
    """The ISSUE 13 acceptance scenario; see the module docstring.
    Deterministic: FakeClock, zero sleeps, drive()-mode router."""
    import mxnet_tpu as mx
    from mxnet_tpu import elastic
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.parallel.mesh import make_mesh, AXIS_DP as _AXIS_DP
    from mxnet_tpu.serving import (AdmissionShed, InferenceEngine,
                                   Request, Router)
    from mxnet_tpu.testing import faults
    import jax

    rc = _racecheck_arm()
    dc = _donation_arm()
    clock = faults.FakeClock(2000.0)
    devices = jax.devices()
    dpw, ranks = 4, [0, 1]
    dp0 = dpw * len(ranks)               # 8
    dp_small = dp0 // 2                  # 4 after the drain
    result = {"kind": "autoscale", "dp_before": dp0,
              "dp_small": dp_small, "notice_at": notice_at,
              "revoke_at": revoke_at, "total_steps": total_steps}

    # -- serving fleet: 2 replicas, shared-system-prompt mix ------------
    net_s = _serving_net()
    rng = _np.random.RandomState(21)
    sys_prompt = rng.randint(0, 64, (12,)).tolist()
    # 3 unique prompts, each submitted twice: greedy decode is
    # deterministic, so the twin of a drained-and-requeued request is
    # the bitwise oracle for its stream — no second warmup needed
    uniq = [sys_prompt + rng.randint(0, 64, (3 + i,)).tolist()
            for i in range(3)]
    prompts = [p for p in uniq for _ in range(2)]

    def factory(compile_cache):
        return InferenceEngine(net_s, max_batch=2, block_size=8,
                               max_context=32, num_blocks=24,
                               prefill_chunk=8, prefix_cache=True,
                               compile_cache=compile_cache)

    router = Router(factory, replicas=2, now=clock)
    for rep in router.replicas:
        rep.engine.pin_prefix(sys_prompt)
    sboard = elastic.NoticeBoard(now=clock)
    ssrc = elastic.FakeNoticeSource()
    sboard.attach_source(ssrc)
    router.attach_notices(sboard)
    serve_scaler = elastic.Autoscaler(
        elastic.ScalingPolicy(
            [elastic.ScalingRule("serving.queue_depth", high=10,
                                 domain="serving", window_s=0.0)],
            cooldown_s=0.0, max_replicas=3),
        router=router, now=clock)

    reqs = [router.submit(Request(p, max_new_tokens=4)) for p in prompts]
    # the doomed replica steps twice, THEN the notice lands mid-traffic
    ssrc.preempt(1, grace_s=60, after_polls=2)
    router.drive()
    result["serving_flight_dump"] = _flight_check(expect_kind="notice")
    fin = router.finished()
    result["serving_no_lost_or_dup"] = (
        sorted(r.id for r in fin) == sorted(r.id for r in reqs)
        and len(fin) == len(reqs))
    by_prompt = {}
    for r in reqs:
        by_prompt.setdefault(tuple(r.tokens), []).append(r.generated)
    result["serving_twin_streams_bitwise"] = all(
        all(len(g) > 0 for g in gs) and all(g == gs[0] for g in gs)
        for gs in by_prompt.values())
    result["serving_drained"] = any(
        e["kind"] == "replica_drained" for e in router.events)
    # load-driven replacement: the serving autoscaler adds replica 2
    # from the SHARED warmup compile cache — zero new compiles
    serve_scaler.tick(signals={"serving.queue_depth": 99.0})
    result["serving_replicas_live"] = len(router.live_replicas())
    router.replicas[-1].engine.pin_prefix(sys_prompt)

    # -- training: notice -> drain -> shrink -> revoke -> grow back -----
    xs, ys = _make_data(77, n_batches=total_steps, batch=16)
    net, trainer = _build_elastic(make_mesh({_AXIS_DP: dp0},
                                            devices[:dp0]))
    membership = elastic.Membership(ranks, now=clock, rendezvous_s=60)
    board = elastic.NoticeBoard(now=clock)
    src = elastic.FakeNoticeSource()
    board.attach_source(src)
    mgr = None
    if workdir is not None:
        mgr = CheckpointManager(
            os.path.join(workdir, "autoscale"), keep=5, async_save=False)
    ladder = elastic.DegradationLadder(router=router, now=clock)
    controller = elastic.ElasticController(
        membership, devices=devices, devices_per_worker=dpw,
        checkpoint_manager=mgr, net=net, backoff_s=0.0,
        now=clock, sleep=lambda s: None, notices=board, ladder=ladder)
    if mgr is not None:
        # checkpoint-THEN-reshard on every notice-driven drain
        controller.drain_checkpoint = lambda s: mgr.save(
            s, params=net, trainer=trainer, iterator={"batch": s},
            sync=True)
    scaler = elastic.Autoscaler(
        elastic.ScalingPolicy(
            [elastic.ScalingRule("train.step_ms", high=100.0,
                                 domain="train", window_s=5.0)],
            cooldown_s=5.0, max_dp=dp0),
        controller=controller, now=clock)

    snap_a = snap_b = None
    shed_blocked = False
    events = []
    for step in range(1, total_steps + 1):
        clock.advance(2.0)
        trainer.step(mx.nd.array(xs[step - 1]), mx.nd.array(ys[step - 1]))
        if step == notice_at:
            # GCE-style advance warning for worker 1, 30 s grace: the
            # boundary below drains it AHEAD of any heartbeat timeout
            src.preempt(1, grace_s=30)
            snap_a = _capture_boundary(net, trainer)
        if step == revoke_at:
            # maintenance cancelled: notice revoked, the worker lives
            # and re-announces; the grow itself is LOAD-driven (below)
            src.revoke(1)
            board.poll()
            membership.announce_join(1, membership.epoch)
        # the load-based control loop ticks at every boundary (the
        # synthetic step_ms signal stays hot, so the autoscaler wants
        # capacity the moment membership can back it)
        scaler.tick(signals={"train.step_ms": 500.0}, step=step)
        if step == revoke_at:
            snap_b = _capture_boundary(net, trainer)
        ev = controller.check_step(step, trainer, params=net)
        if ev is not None:
            events.append({k: ev.get(k) for k in
                           ("source", "step", "dp", "epoch")})
        if step == notice_at:
            result["training_flight_dump"] = _flight_check(
                expect_kind="notice")
            result["shed_after_drain"] = router.shedding
            try:
                router.submit(Request(prompts[0], max_new_tokens=2))
            except AdmissionShed:
                shed_blocked = True
    result["events"] = events
    result["shed_blocked"] = shed_blocked
    result["unshed_after_grow"] = not router.shedding
    result["drain_checkpoint_at"] = None if mgr is None else mgr.latest()
    result["membership_epoch"] = membership.epoch
    result["final_dp"] = trainer.mesh.shape[_AXIS_DP]
    result["drains"] = controller.drains
    result["autoscale"] = scaler.stats()
    grow = [d for d in scaler.decisions
            if d["domain"] == "train" and d["verdict"] == "grow"]
    result["load_driven_grow"] = bool(grow) and grow[0]["to"] == dp0
    params_final, state_final = _final_state(net, trainer)

    # parity 1: the dp=4 segment must be BITWISE a fresh dp=4 process
    # restored from the drain-boundary state
    ref_net, ref_trainer = _build_elastic(
        make_mesh({_AXIS_DP: dp_small}, devices[:dp_small]), seed=4321)
    _restore_boundary(ref_net, ref_trainer, snap_a)
    for i in range(notice_at, revoke_at):
        ref_trainer.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
    pa, sa = _final_state(ref_net, ref_trainer)
    result["params_bitwise_dp4"] = _bitwise(
        {n: v for n, v in snap_b["params"].items()}, pa)
    result["state_bitwise_dp4"] = _bitwise(
        {k: v.asnumpy() for k, v in snap_b["sd"]["arrays"].items()}, sa)

    # parity 2: the grown dp=8 tail must be BITWISE a fresh dp=8
    # process restored from the grow-boundary state
    ref_net8, ref_trainer8 = _build_elastic(
        make_mesh({_AXIS_DP: dp0}, devices[:dp0]), seed=9876)
    _restore_boundary(ref_net8, ref_trainer8, snap_b)
    for i in range(revoke_at, total_steps):
        ref_trainer8.step(mx.nd.array(xs[i]), mx.nd.array(ys[i]))
    pb, sb = _final_state(ref_net8, ref_trainer8)
    result["params_bitwise"] = _bitwise(params_final, pb)
    result["state_bitwise"] = _bitwise(state_final, sb)

    # serving epilogue: admissions recovered — two more requests ride
    # the grown fleet (incl. the autoscaled replica) to completion
    extra = [router.submit(Request(p, max_new_tokens=4))
             for p in uniq[:2]]
    router.drive()
    result["serving_post_recovery_ok"] = all(r.done for r in extra)
    st = router.stats()
    result["compiles_after_warmup"] = st["compiles_after_warmup"]
    leaks_ok = True
    for rep in router.replicas:
        if not rep.alive:
            continue
        try:
            rep.engine.cache.check_leaks(
                holders=rep.engine.prefix_cache.held_blocks())
        except Exception as e:  # noqa: BLE001 — verdict, not crash
            leaks_ok = False
            result["leak_error"] = f"{type(e).__name__}: {e}"
    result["kv_leaks_clean"] = leaks_ok

    result["racecheck"] = _racecheck_verdict(rc)
    rcv = result["racecheck"]
    result["donation"] = _donation_verdict(dc)
    dcv = result["donation"]
    fds = [result.get("serving_flight_dump"),
           result.get("training_flight_dump")]
    checks = [
        result["serving_no_lost_or_dup"],
        result["serving_twin_streams_bitwise"],
        result["serving_drained"],
        result["serving_replicas_live"] == 2,
        result["serving_post_recovery_ok"],
        result["compiles_after_warmup"] == 0,
        leaks_ok,
        result["shed_after_drain"], shed_blocked,
        result["unshed_after_grow"],
        mgr is None or result["drain_checkpoint_at"] == notice_at,
        result["drains"] == 1,
        result["membership_epoch"] == 2,       # death + join
        result["final_dp"] == dp0,
        result["load_driven_grow"],
        len(events) == 2,
        result["params_bitwise_dp4"], result["state_bitwise_dp4"],
        result["params_bitwise"], result["state_bitwise"],
        all(fd is None or fd["ok"] for fd in fds),
        rcv is None or rcv["ok"],
        dcv is None or dcv["ok"],
    ]
    result["ok"] = bool(all(checks))
    return result


# ----------------------------------------------------------------------
# Watchdog scenario (ISSUE 14): injected NaN loss + FakeClock step
# stall, each leaving a typed watchdog.* event and a flight dump whose
# reason names the rule.
# ----------------------------------------------------------------------

def run_watchdog_scenario(total_steps=6, nan_at=3, workdir=None):
    """Run-health watchdog end to end: train a tiny sharded model,
    inject a NaN loss through the ``watchdog.loss`` fault point
    (testing/faults.py — the detection path is exactly production's),
    then starve the step clock (FakeClock, zero sleeps) past
    ``stall_s``.  Each incident must emit its typed ``watchdog.*``
    event and dump the flight recorder with ``reason="watchdog:<rule>"``
    — the same gates ``python -m mxnet_tpu.testing.chaos watchdog``
    applies in a child process."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import watchdog as wd_mod
    from mxnet_tpu.testing import faults

    rc = _racecheck_arm()
    dc = _donation_arm()
    result = {"mode": "watchdog", "nan_at": nan_at,
              "total_steps": total_steps}
    clock = faults.FakeClock(1000.0)
    wd = wd_mod.Watchdog(now=clock, stall_s=30.0)
    wd_mod.configure(enabled=True, instance=wd)
    try:
        xs, ys = _make_data(7)
        net, trainer, step = _build("sharded")
        with faults.inject("watchdog.loss", at=nan_at, times=1,
                           action=lambda p: float("nan")):
            for i in range(total_steps):
                loss = step(xs[i], ys[i])
                # the estimator's seam: tick with the host loss the
                # metric path already pulled (the fault point swaps in
                # the NaN at step nan_at)
                wd_mod.on_step(i + 1,
                               loss=float(loss.asnumpy().mean()))
                clock.advance(1.0)
        kinds = [e["kind"] for e in telemetry.events()]
        result["nan_event"] = "watchdog.nonfinite_loss" in kinds
        result["nan_flight"] = _flight_check(expect_kind="watchdog")
        nan_reason = (result["nan_flight"] or {}).get("reason")
        result["nan_reason_ok"] = nan_reason == "watchdog:nonfinite_loss"

        # training went quiet: no step for > stall_s (FakeClock)
        clock.advance(31.0)
        stalled = wd_mod.check(step=total_steps)
        kinds = [e["kind"] for e in telemetry.events()]
        result["stall_detected"] = bool(stalled)
        result["stall_event"] = "watchdog.step_stall" in kinds
        result["stall_flight"] = _flight_check(expect_kind="watchdog")
        stall_reason = (result["stall_flight"] or {}).get("reason")
        result["stall_reason_ok"] = stall_reason == "watchdog:step_stall"
        result["trips"] = [r for r, _ in wd.trips]
    finally:
        wd_mod.reset()           # never leak the FakeClock instance
    result["racecheck"] = _racecheck_verdict(rc)
    rcv = result["racecheck"]
    result["donation"] = _donation_verdict(dc)
    dcv = result["donation"]
    nf, sf = result["nan_flight"], result["stall_flight"]
    result["ok"] = bool(
        result["nan_event"] and result["stall_event"]
        and result["stall_detected"]
        and (nf is None or (nf["ok"] and result["nan_reason_ok"]))
        and (sf is None or (sf["ok"] and result["stall_reason_ok"]))
        and (rcv is None or rcv["ok"])
        and (dcv is None or dcv["ok"]))
    return result


# ----------------------------------------------------------------------
# Fleet observability scenario (ISSUE 15): N simulated workers, one
# straggler + one scrape-dead rank — the fleet collector must name both
# by rank, merge histograms exactly, and stay racecheck-clean.
# ----------------------------------------------------------------------

def run_fleet_scenario(n_workers=4, straggler_rank=2, dead_rank=3,
                       steps=4, workdir=None):
    """The ISSUE 15 acceptance scenario; see the module docstring.
    Deterministic: per-rank registries on ONE FakeClock, zero sleeps,
    the straggler's extra step time injected through the
    ``fleet.straggle`` fault point (the detection path is exactly what
    a real pod scrape sees)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import fleet as fleet_mod
    from mxnet_tpu.telemetry.registry import MetricsRegistry
    from mxnet_tpu.testing import faults

    rc = _racecheck_arm()
    dc = _donation_arm()
    clock = faults.FakeClock(3000.0)
    result = {"kind": "fleet", "workers": n_workers,
              "straggler_rank": straggler_rank, "dead_rank": dead_rank,
              "steps": steps}

    # N simulated workers: each rank is its own registry — exactly the
    # snapshot a remote PSClient.telemetry() scrape returns — stepped
    # under the same FakeClock.  Every rank also carries the same
    # membership epoch (no desync in this scenario) and its own step
    # counter.
    regs = {r: MetricsRegistry(now=clock) for r in range(n_workers)}
    with faults.inject("fleet.straggle",
                       action=lambda rank: clock.advance(0.45)):
        for _ in range(steps):
            for r in range(n_workers):
                t0 = clock()
                clock.advance(0.05)          # the nominal 50 ms step
                if r == straggler_rank:
                    # the injected straggler: the armed fault point
                    # advances the clock mid-"step", so THIS rank's
                    # step_ms histogram runs ~10x long
                    faults.fault_point("fleet.straggle", payload=r)
                regs[r].histogram("train.step_ms").observe(
                    (clock() - t0) * 1e3)
                regs[r].counter("train.steps").inc()
                regs[r].gauge("elastic.epoch").set(3)

    def transport(rank):
        def scrape():
            if rank == dead_rank:
                raise ConnectionError("simulated dead scrape endpoint")
            return {"snapshot": regs[rank].snapshot()}
        return scrape

    coll = fleet_mod.FleetCollector(
        {r: transport(r) for r in range(n_workers)},
        now=clock, skew=3.0, scrape_s=0.0)
    snap = coll.collect()

    kinds = {}
    for ev in telemetry.events():
        kinds.setdefault(ev["kind"], []).append(ev["data"])
    stragglers = kinds.get("fleet.straggler", [])
    deads = kinds.get("fleet.scrape_dead", [])
    result["straggler_named"] = any(
        d.get("rank") == straggler_rank for d in stragglers)
    result["scrape_dead_named"] = any(
        d.get("rank") == dead_rank for d in deads)
    result["slowest_rank"] = snap["skew"]["slowest_rank"]
    result["skew_ratio"] = snap["skew"]["skew_ratio"]
    result["dead_error_typed"] = bool(
        snap["per_rank"][str(dead_rank)].get("error"))

    # the rule firings must have left a flight dump whose reason names
    # a fleet rule and whose last event is the incident (ISSUE 9/14
    # contract, reused verbatim)
    result["flight_dump"] = _flight_check(expect_kind="fleet")
    fd = result["flight_dump"]
    reason_ok = fd is None or str(fd.get("reason", "")
                                  ).startswith("fleet:")

    # merge exactness: every merged histogram equals the element-wise
    # sum of the per-rank buckets, computed here in the same ascending
    # rank order the collector uses — bitwise, not approximately
    alive = [r for r in range(n_workers) if r != dead_rank]
    merged = snap["histograms"]["train.step_ms"]
    expect_counts = [0] * (len(merged["edges"]) + 1)
    expect_sum, expect_count = 0.0, 0
    for r in alive:
        st = regs[r].snapshot()["histograms"]["train.step_ms"]
        for i, c in enumerate(st["counts"]):
            expect_counts[i] += c
        expect_sum += st["sum"]
        expect_count += st["count"]
    result["hist_merge_bitwise"] = (
        merged["counts"] == expect_counts
        and merged["sum"] == expect_sum
        and merged["count"] == expect_count)
    result["counters_summed"] = (
        snap["counters"]["train.steps"] == steps * len(alive))

    result["racecheck"] = _racecheck_verdict(rc)
    rcv = result["racecheck"]
    result["donation"] = _donation_verdict(dc)
    dcv = result["donation"]
    result["ok"] = bool(
        result["straggler_named"] and result["scrape_dead_named"]
        and result["slowest_rank"] == straggler_rank
        and result["dead_error_typed"]
        and result["hist_merge_bitwise"] and result["counters_summed"]
        and (fd is None or (fd["ok"] and reason_ok))
        and (rcv is None or rcv["ok"])
        and (dcv is None or dcv["ok"]))
    return result


def run_multiprocess_scenario(n_procs=4, victim=2, steps=8,
                              ckpt_every=3, kill_step=5, park_step=7,
                              workdir=None):
    """ISSUE 19 acceptance: SIGKILL a REAL worker process mid-run and
    assert the notice→drain→reshard path end-to-end at process level.

    Unlike every other suite (threads under FakeClock), this one spawns
    ``n_procs`` real processes over ``jax.distributed`` through
    :class:`mxnet_tpu.pod.PodLauncher` and kills one with SIGKILL — no
    simulation anywhere:

    - the launcher detects the death, requeues the victim's serving
      leases, and COMMITS a membership change (fresh coordinator port);
    - survivors drain at the step gate, tear down + re-init the
      coordination service (``reinit_distributed``) and re-rendezvous
      at ``jax.process_count() == n_procs - 1``;
    - training resumes from the shared checkpoint BITWISE a fresh
      ``n_procs - 1``-process pod restored from the same checkpoint;
    - the file-lease request ledger ends exactly-once (zero lost, zero
      duplicated) including the victim's requeued lease;
    - a fleet scrape over the workers' live PS telemetry endpoints
      (taken while survivors are parked at ``park_step``) names the
      dead rank typed, and the scrape failure leaves rpc.* counters
      plus a flight dump.

    The kill lands while every worker is parked at the held step gate
    — between collectives, which is exactly the elastic controller's
    drain-at-step-boundary contract (a kill mid-collective would wedge
    the survivors inside gloo, which is the launcher-level reason the
    gate exists at all)."""
    import shutil as _shutil
    import threading
    import time as _time

    from mxnet_tpu import telemetry
    from mxnet_tpu.kvstore import rpc as _rpc
    from mxnet_tpu.pod import (PodLauncher, queue_ledger,
                               submit_request)
    from mxnet_tpu.telemetry import fleet as fleet_mod

    workdir = workdir or tempfile.mkdtemp(prefix="mxtpu-chaos-procs-")
    pod_dir = os.path.join(workdir, "pod")
    result = {"kind": "procs", "procs": n_procs, "victim": victim,
              "steps": steps, "kill_step": kill_step}
    n_requests = 2 * n_procs
    for i in range(n_requests):
        submit_request(pod_dir, f"r{i}", {"x": i})
    launcher = PodLauncher(
        n_procs, pod_dir, steps=steps, ckpt_every=ckpt_every,
        env={"MXTPU_POD_HOLD_RANK": str(victim),
             "MXTPU_POD_SERVE_PER_STEP": "1"})
    launcher.hold_step = kill_step
    launcher.start()
    sup = {}

    def _run():
        try:
            sup["summary"] = launcher.supervise(timeout_s=180.0)
        except Exception as e:  # noqa: BLE001 — surfaced in verdict
            sup["error"] = f"{type(e).__name__}: {e}"
    thread = threading.Thread(target=_run)
    thread.start()

    def _wait(cond, what, timeout=90.0):
        deadline = _time.monotonic() + timeout
        while not cond():
            if _time.monotonic() > deadline:
                raise TimeoutError(f"chaos procs: timed out waiting "
                                   f"for {what}")
            _time.sleep(0.02)

    frozen = os.path.join(workdir, "ckpt.frozen.npz")
    fleet_snap = None
    try:
        # 1. everyone parked at the held gate (checkpoint exists)
        _wait(lambda: launcher.ready_ranks(kill_step)
              == set(range(n_procs)), f"gate {kill_step}")
        _shutil.copy(os.path.join(pod_dir, "ckpt.npz"), frozen)
        # 2. the real SIGKILL; survivors park again post-reshard so the
        #    fleet scrape sees live survivor endpoints + one dead port
        launcher.kill(victim)
        launcher.hold_step = park_step
        survivors = set(range(n_procs)) - {victim}
        _wait(lambda: launcher.ready_ranks(park_step) >= survivors,
              f"survivors at gate {park_step}")
        policy = _rpc.RetryPolicy(retries=0, timeout_s=5.0)
        coll = fleet_mod.FleetCollector(
            {r: fleet_mod.ps_transport("127.0.0.1",
                                       launcher.ps_ports[r],
                                       retries=1, policy=policy)
             for r in range(n_procs)}, scrape_s=0.0)
        fleet_snap = coll.collect()
        launcher.hold_step = None
        thread.join(timeout=120.0)
    finally:
        launcher.shutdown()
        thread.join(timeout=10.0)
    summary = sup.get("summary") or {}
    result["supervise_error"] = sup.get("error")
    result["summary"] = {k: summary.get(k)
                         for k in ("epoch", "dead", "done", "requeued")}

    # survivors re-rendezvoused at the smaller world (real
    # jax.process_count(), reported by each survivor post-reinit)
    statuses = launcher.statuses()
    worlds = {r: s.get("world") for r, s in statuses.items()
              if r != victim}
    reinits = [s.get("reinit_ms") for r, s in statuses.items()
               if r != victim]
    result["survivor_worlds"] = worlds
    result["world_ok"] = (len(worlds) == n_procs - 1 and
                          all(w == n_procs - 1 for w in worlds.values()))
    result["coordinator_reinit_ms"] = max(
        [r for r in reinits if r is not None], default=None)
    result["reinit_ok"] = all(r is not None for r in reinits)

    # exactly-once serving ledger, including the victim's requeued lease
    ledger = queue_ledger(pod_dir)
    result["requeued"] = summary.get("requeued")
    result["ledger"] = {k: len(v) for k, v in ledger.items()}
    result["ledger_exactly_once"] = (
        ledger["pending"] == [] and ledger["inflight"] == []
        and ledger["done"] == sorted(f"r{i}" for i in range(n_requests)))
    result["requeue_exercised"] = bool(summary.get("requeued"))

    # bitwise: survivor post-reshard digests == a fresh (n-1)-proc pod
    # restored from the SAME checkpoint
    surv_rank = min(set(range(n_procs)) - {victim})
    surv = [(r["step"], r["digest"])
            for r in launcher.digests(surv_rank)
            if r["world"] == n_procs - 1]
    fresh_dir = os.path.join(workdir, "pod_fresh")
    fresh_launcher = PodLauncher(
        n_procs - 1, fresh_dir, steps=steps, ckpt_every=ckpt_every,
        env={"MXTPU_POD_RESTORE": frozen})
    fresh_launcher.start()
    try:
        fresh_launcher.supervise(timeout_s=120.0)
    finally:
        fresh_launcher.shutdown()
    fresh = [(r["step"], r["digest"])
             for r in fresh_launcher.digests(0)]
    result["resumed_steps"] = [s for s, _ in surv]
    result["bitwise_resume"] = bool(surv) and surv == fresh

    # fleet snapshot names the dead rank, typed, from a REAL scrape
    dead_row = (fleet_snap or {}).get("per_rank", {}).get(str(victim),
                                                          {})
    result["dead_error"] = dead_row.get("error")
    result["dead_error_typed"] = "PeerUnreachable" in str(
        dead_row.get("error", "")) or "RPCTimeout" in str(
        dead_row.get("error", ""))
    kinds = {}
    for ev in telemetry.events():
        kinds.setdefault(ev["kind"], []).append(ev["data"])
    result["scrape_dead_named"] = any(
        d.get("rank") == victim
        for d in kinds.get("fleet.scrape_dead", []))
    snap = telemetry.snapshot()
    result["rpc_failures_counted"] = (
        snap.get("counters", {}).get("rpc.failures", 0) > 0)
    result["flight_dump"] = _flight_check()
    fd = result["flight_dump"]
    reason_ok = fd is None or str(fd.get("reason", "")).startswith(
        ("fleet:", "rpc_failure:"))

    result["ok"] = bool(
        not result["supervise_error"]
        and summary.get("dead") == [victim]
        and result["world_ok"] and result["reinit_ok"]
        and result["ledger_exactly_once"]
        and result["requeue_exercised"]
        and result["bitwise_resume"]
        and result["dead_error_typed"]
        and result["scrape_dead_named"]
        and result["rpc_failures_counted"]
        and (fd is None or (fd.get("path") and reason_ok)))
    return result


def main(argv=None):
    # the smoke must run anywhere — force the simulated CPU mesh exactly
    # like tests/conftest.py does
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    argv = list(sys.argv[1:] if argv is None else argv)
    suite = argv[0] if argv else "preempt"
    workdir = tempfile.mkdtemp(prefix="mxtpu-chaos-")
    # flight-recorder dumps land in the scenario workdir (cleaned up
    # with it) unless the caller pinned a directory
    os.environ.setdefault("MXTPU_FLIGHT_DIR", workdir)
    results = []
    try:
        if suite in ("preempt", "all"):
            results += [run_scenario(mode, workdir=workdir)
                        for mode in ("plain", "sharded")]
            # ISSUE 6: resume from the (non-K-aligned) surviving
            # checkpoint with K=4 multi-step windows — still bitwise K=1
            results.append(run_scenario("sharded", workdir=workdir,
                                        resume_steps_per_call=4))
        if suite in ("elastic", "all"):
            results += [run_elastic_scenario(kind, workdir=workdir)
                        for kind in ("shrink", "grow", "reshard_fault")]
        if suite in ("serving", "all"):
            results.append(run_serving_scenario(workdir=workdir))
        if suite in ("disagg", "all"):
            # prefill replica killed mid-handoff, then a decode replica
            # killed at a plain boundary — both over the shared pool
            results.append(run_disagg_scenario(workdir=workdir))
            results.append(run_disagg_scenario(
                kill_rid=1, kill_point="step", kill_at=3,
                workdir=workdir))
        if suite in ("autoscale", "all"):
            results.append(run_autoscale_scenario(workdir=workdir))
        if suite in ("watchdog", "all"):
            results.append(run_watchdog_scenario(workdir=workdir))
        if suite in ("fleet", "all"):
            results.append(run_fleet_scenario(workdir=workdir))
        if suite in ("procs", "all"):
            # the only suite with REAL processes + SIGKILL (ISSUE 19);
            # everything above runs threads under FakeClock
            results.append(run_multiprocess_scenario(workdir=workdir))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    ok = bool(results) and all(r["ok"] for r in results)
    print(json.dumps({"chaos": results, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
