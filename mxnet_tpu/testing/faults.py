"""Deterministic fault injection for the fault-tolerance layer.

The recovery code paths (torn-checkpoint skip, writer-failure surfacing,
heartbeat death, preemption save) are exactly the paths a normal run
never exercises.  This module lets tests — and the chaos smoke
(``python -m mxnet_tpu.testing.chaos``) — provoke each failure on purpose
and deterministically (no wall-clock races, no real SIGKILL needed).

Instrumented code calls :func:`fault_point` at named sites::

    faults.fault_point("checkpoint.write", payload=path)

which is a single module-bool check when nothing is armed (safe on warm
paths).  Tests arm a site with the :func:`inject` context manager::

    with faults.inject("checkpoint.write", exc=OSError("disk full")):
        mgr.save(...)          # the writer thread dies with OSError

or with a callable action (e.g. :func:`truncate_file` /
:func:`corrupt_file` against the payload), firing on hit ``at`` (1-based)
for ``times`` consecutive hits.

Subprocesses (chaos mode) arm sites through the env hook::

    MXTPU_FAULT_INJECT="checkpoint.write:at=1,train.step:at=3:mode=preempt"

Fault points currently instrumented:

==========================  ===============================================
site                        payload / effect
==========================  ===============================================
``checkpoint.write``        path being written; raise -> writer thread dies
``checkpoint.manifest``     manifest path, fired BEFORE the atomic
                            ``os.replace`` -> torn checkpoint on raise
``checkpoint.d2h``          array name during the device->host snapshot
``ndarray.d2h``             raise on any ``asnumpy()`` D2H copy
``ps.heartbeat.drop``       heartbeat send suppressed (silent worker)
``train.step``              global step index; ``mode=preempt`` delivers a
                            simulated preemption signal at step K
``elastic.reshard``         attempt index during an elastic reshard's
                            peer-to-peer state transfer; raise -> the
                            transfer dies mid-flight and the controller
                            falls back to the newest valid checkpoint
``serving.replica<i>.step`` boundary counter of serving-router replica
                            ``i``; raise -> the replica dies mid-traffic
                            and the router drains + requeues its
                            requests (``--chaos serving``)
==========================  ===============================================
"""
from __future__ import annotations

import os
import threading
from contextlib import contextmanager

from ..base import MXNetError

__all__ = ["FaultInjected", "inject", "fault_point", "active", "reset",
           "truncate_file", "corrupt_file", "FakeClock"]


class FaultInjected(MXNetError):
    """Default exception raised by an armed fault point."""


_lock = threading.Lock()
_active = {}           # name -> _Fault
_armed = False         # fast-path guard: False => fault_point is a no-op
_env_parsed = False


class _Fault:
    __slots__ = ("name", "exc", "action", "at", "times", "hits", "fired")

    def __init__(self, name, exc=None, action=None, at=1, times=None):
        self.name = name
        self.exc = exc
        self.action = action
        self.at = int(at)
        self.times = None if times is None else int(times)
        self.hits = 0
        self.fired = 0

    def should_fire(self):
        self.hits += 1
        if self.hits < self.at:
            return False
        if self.times is not None and self.fired >= self.times:
            return False
        self.fired += 1
        return True


def _rearm():
    global _armed
    _armed = bool(_active)


def _parse_env():
    """``MXTPU_FAULT_INJECT="site:at=K:times=N:mode=raise|preempt|drop"``
    (comma-separated specs).  Parsed once; subprocess-friendly — the
    chaos runner arms its children this way."""
    global _env_parsed
    _env_parsed = True
    spec = os.environ.get("MXTPU_FAULT_INJECT", "").strip()
    if not spec:
        return
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        name = fields[0]
        kw = {}
        for f in fields[1:]:
            k, _, v = f.partition("=")
            kw[k.strip()] = v.strip()
        mode = kw.get("mode", "raise")
        action = None
        exc = None
        if mode == "preempt":
            action = _preempt_action
        elif mode == "drop":
            action = _drop_action
        else:
            exc = FaultInjected(f"injected fault at {name!r} "
                                f"(MXTPU_FAULT_INJECT)")
        with _lock:
            _active[name] = _Fault(name, exc=exc, action=action,
                                   at=int(kw.get("at", 1)),
                                   times=(int(kw["times"])
                                          if "times" in kw else None))
    _rearm()


def _preempt_action(payload):
    """Deliver a simulated preemption: flips the installed
    :class:`~mxnet_tpu.checkpoint.PreemptionHandler` (graceful, exactly
    what a SIGTERM handler would do) — or raises if none is installed,
    so an unguarded loop cannot silently ignore the fault."""
    from .. import checkpoint as _ckpt
    handler = _ckpt.PreemptionHandler.installed()
    if handler is None:
        raise FaultInjected(
            "simulated preemption fired but no PreemptionHandler is "
            "installed (wrap the loop in run_preemptible / install())")
    handler.request(reason=f"injected preemption (payload={payload!r})")


#: public alias — arm with ``inject("train.step", at=K,
#: action=preempt_action)`` to deliver a simulated preemption at step K
def preempt_action(payload):
    return _preempt_action(payload)


def _drop_action(payload):
    """Swallow the instrumented side effect (used by heartbeat sends):
    the fault point returns True and the caller skips the send."""
    return "drop"


def fault_point(name, payload=None):
    """Instrumentation hook.  No-op (one bool check) unless a fault is
    armed for ``name``.  Returns ``"drop"`` when the armed fault says to
    suppress the caller's side effect; raises the armed exception for
    ``exc`` faults; runs (and returns the result of) callable actions.

    ``payload`` gives the action something to chew on (a path to
    corrupt, a step index); for ``at=K`` matching against an integer
    payload (step counters), K is compared against the payload rather
    than the hit count — "preempt at step 3" means step 3, however many
    times the point is hit before that.
    """
    if not _armed:
        if not _env_parsed:
            _parse_env()
            if not _armed:
                return None
        else:
            return None
    with _lock:
        f = _active.get(name)
        if f is None:
            return None
        if isinstance(payload, int) and f.at > 1:
            # step-indexed matching: fire exactly when payload reaches at
            if payload < f.at or \
                    (f.times is not None and f.fired >= f.times):
                f.hits += 1
                return None
            f.fired += 1
        elif not f.should_fire():
            return None
        exc, action = f.exc, f.action
    # the fault IS firing: record the trip + dump the flight recorder
    # BEFORE the exception/action changes control flow (ISSUE 9) — the
    # dump's last event is this trip, payload = the failing step/path.
    # Outside the lock: telemetry has its own locks and never calls
    # back into this module.
    from .. import telemetry as _telem
    _telem.on_fault(name, payload)
    if action is not None:
        return action(payload)
    raise exc if exc is not None else FaultInjected(
        f"injected fault at {name!r}")


@contextmanager
def inject(name, exc=None, action=None, at=1, times=None):
    """Arm fault point ``name`` for the scope's duration.

    ``exc``: exception instance to raise at the point (default
    :class:`FaultInjected` if no action given).  ``action``: callable
    run with the point's payload instead of raising (return ``"drop"``
    to suppress the caller's side effect), or the string ``"drop"`` as
    shorthand for the suppress action.  ``at``: 1-based hit index (or
    step index for integer payloads) to start firing.  ``times``: fire
    at most N times (default: every hit from ``at`` on).
    """
    if action == "drop":
        action = _drop_action
    if exc is None and action is None:
        exc = FaultInjected(f"injected fault at {name!r}")
    f = _Fault(name, exc=exc, action=action, at=at, times=times)
    with _lock:
        prev = _active.get(name)
        _active[name] = f
    _rearm()
    try:
        yield f
    finally:
        with _lock:
            if prev is None:
                _active.pop(name, None)
            else:
                _active[name] = prev
        _rearm()


def active():
    """Names of currently armed fault points (test introspection)."""
    with _lock:
        return sorted(_active)


def reset():
    """Disarm everything (incl. env-armed faults; env re-parses only on
    the next interpreter, not the next call)."""
    with _lock:
        _active.clear()
    _rearm()


# -- ready-made destructive actions (checkpoint corruption) -------------

def truncate_file(path, keep_bytes=16):
    """Truncate ``path`` to ``keep_bytes`` — a torn write."""
    with open(path, "r+b") as f:
        f.truncate(keep_bytes)


def corrupt_file(path, offset=-64, nbytes=32):
    """Flip a span of bytes in ``path`` (default: 32 bytes near the
    end, inside the tensor payload) — CRC must catch it."""
    size = os.path.getsize(path)
    off = offset if offset >= 0 else max(0, size + offset)
    with open(path, "r+b") as f:
        f.seek(off)
        span = f.read(nbytes)
        f.seek(off)
        f.write(bytes(b ^ 0xFF for b in span))


class FakeClock:
    """Controllable clock for deterministic timeout tests (the PS
    heartbeat death path).  Callable like ``time.time``."""

    def __init__(self, start=1_000_000.0):
        self._t = float(start)
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            return self._t

    def advance(self, dt):
        with self._lock:
            self._t += float(dt)
            return self._t
