"""``mx.telemetry`` — the unified observability spine (ISSUE 9).

Every subsystem built in PRs 2-8 kept its own ad-hoc numbers
(``InferenceEngine.stats``, the overlap probe's ``exposed_comm_ms``,
``CheckpointManager`` timings, elastic ``reshard_ms``...) and none of it
was observable from a *running* job.  This package is the one spine they
now all publish to:

- a process-wide **metrics registry** (:mod:`registry`): counters,
  gauges, and histograms with FIXED bucket edges so aggregation across
  workers is deterministic; injectable clock (the PR 4 FakeClock
  discipline);
- a schema-versioned **structured event log** (:mod:`events`): JSONL
  records with a monotonic ``seq``, the current training ``step`` and
  membership ``epoch``, kept in a bounded in-memory ring and optionally
  appended to ``MXTPU_EVENT_LOG``;
- a **flight recorder** (:mod:`flight`): the ring + a metric snapshot
  dumped to disk on SIGTERM (via PR 4's ``PreemptionHandler``), on any
  fault-point trip (``testing/faults.py``), and on unhandled train-step
  exceptions — the post-mortem a preempted pod job otherwise never
  leaves behind.

Exposure, three ways: :func:`snapshot` (the API), a Prometheus-style
text dump (:func:`prom_text`, ``tools/telemetry_dump.py``, and the PS
server's ``_OP_TELEMETRY`` RPC for live pod scraping), and perfetto
correlation — ``mx.profiler``'s scoped events carry the current
step/epoch from :func:`context`.

Zero overhead when ``MXTPU_TELEMETRY=0``: every helper below is a single
module-bool check (the ``testing.faults.fault_point`` discipline) and
the registry hands back one shared no-op metric — no allocation, no
locks, no dict growth.  See docs/OBSERVABILITY.md for the metric
catalog and the event/flight-recorder schema.
"""
from __future__ import annotations

import os
import time

from .registry import (MetricsRegistry, Counter, Gauge, Histogram,
                       NULL_METRIC, DEFAULT_MS_EDGES)
from .events import EventLog, SCHEMA_VERSION
from .flight import FlightRecorder, memory_block
from .prom import prom_text as _render_prom
from . import tracing
from . import watchdog
from . import fleet

__all__ = ["SCHEMA_VERSION", "enabled", "registry", "counter", "gauge",
           "histogram", "inc", "set_gauge", "observe", "value", "event",
           "events", "events_dropped", "set_context", "context",
           "snapshot", "prom_text", "flight", "dump_flight",
           "last_flight_dump", "on_fault", "on_preemption",
           "on_step_error", "reset", "configure", "clock",
           "MetricsRegistry", "EventLog", "FlightRecorder",
           "memory_block", "Counter", "Gauge", "Histogram",
           "DEFAULT_MS_EDGES", "tracing", "watchdog", "fleet"]


def _env_enabled():
    return os.environ.get("MXTPU_TELEMETRY", "1") != "0"


def _env_ring():
    try:
        return max(1, int(os.environ.get("MXTPU_TELEMETRY_RING", "256")))
    except ValueError:
        return 256


_ENABLED = _env_enabled()
_REGISTRY = MetricsRegistry(now=time.time)
_EVENTS = EventLog(ring_size=_env_ring(),
                   path=os.environ.get("MXTPU_EVENT_LOG") or None,
                   now=time.time)
_FLIGHT = FlightRecorder(_REGISTRY, _EVENTS)
tracing.install_compile_listener()     # jit.compile spans, jit.compiles


def configure(enabled=None, ring_size=None, event_log=None, now=None):
    """Reconfigure the process-wide telemetry state (tests; production
    configures through the env vars at import).  ``now`` replaces the
    timestamp clock on the registry AND the event log — the FakeClock
    seam."""
    global _ENABLED, _REGISTRY, _EVENTS, _FLIGHT
    if enabled is not None:
        _ENABLED = bool(enabled)
    if ring_size is not None or event_log is not None or now is not None:
        clk = now if now is not None else _EVENTS._now
        _REGISTRY = MetricsRegistry(now=clk)
        _EVENTS = EventLog(
            ring_size=ring_size if ring_size is not None
            else _EVENTS.ring_size,
            path=event_log if event_log is not None else _EVENTS.path,
            now=clk)
        _FLIGHT = FlightRecorder(_REGISTRY, _EVENTS)
    return _ENABLED


def configure_from_env():
    """Re-read ``MXTPU_TELEMETRY`` / ``MXTPU_TELEMETRY_RING`` /
    ``MXTPU_EVENT_LOG`` (subprocess harnesses that mutate env after
    import)."""
    return configure(enabled=_env_enabled(), ring_size=_env_ring(),
                     event_log=os.environ.get("MXTPU_EVENT_LOG") or "")


def enabled():
    """Whether telemetry is live (``MXTPU_TELEMETRY`` != 0).  Callers on
    hot paths check this ONCE and skip their timing reads entirely when
    off — the zero-overhead contract."""
    return _ENABLED


def registry():
    return _REGISTRY


def clock():
    """Monotonic duration clock for instrumentation sites (NOT the
    injectable wall clock — durations must never go backwards under a
    FakeClock that only stamps events)."""
    return time.perf_counter()


# -- metric helpers (each a single bool check when disabled) ------------

def counter(name):
    if not _ENABLED:
        return NULL_METRIC
    return _REGISTRY.counter(name)


def gauge(name):
    if not _ENABLED:
        return NULL_METRIC
    return _REGISTRY.gauge(name)


def histogram(name, edges=None):
    if not _ENABLED:
        return NULL_METRIC
    return _REGISTRY.histogram(name, edges=edges)


def inc(name, n=1):
    if not _ENABLED:
        return
    _REGISTRY.counter(name).inc(n)


def set_gauge(name, v):
    if not _ENABLED:
        return
    _REGISTRY.gauge(name).set(v)


def observe(name, v, edges=None):
    if not _ENABLED:
        return
    _REGISTRY.histogram(name, edges=edges).observe(v)


def value(name):
    """Current value of a counter/gauge (None when absent or disabled)
    — the thin-reader seam ``tools/serve_loadgen.py`` consumes."""
    if not _ENABLED:
        return None
    return _REGISTRY.value(name)


# -- events / context ---------------------------------------------------

def set_context(step=None, epoch=None):
    """Update the ambient (step, membership-epoch) every event record —
    and every ``mx.profiler`` Task/Frame event — is stamped with.  The
    trainer sets ``step``; the elastic layer sets ``epoch``."""
    if not _ENABLED:
        return
    _EVENTS.set_context(step=step, epoch=epoch)


def context():
    """The ambient {step, epoch} (empty dict when unset or disabled)."""
    if not _ENABLED:
        return {}
    return _EVENTS.context()


def event(kind, **data):
    if not _ENABLED:
        return None
    return _EVENTS.emit(kind, **data)


def events():
    """The in-memory ring's current contents (oldest first)."""
    if not _ENABLED:
        return []
    return _EVENTS.events()


def events_dropped():
    """Event records the bounded ring evicted since the last reset
    (0 when disabled) — visible truncation (ISSUE 15)."""
    if not _ENABLED:
        return 0
    return _EVENTS.dropped


# -- snapshot / rendering -----------------------------------------------

def snapshot():
    """One JSON-able view of the whole registry + context: the
    ``mx.telemetry.snapshot()`` API of ISSUE 9.  ``{"enabled": False}``
    when telemetry is off — never fake zeros (the PR 6 honesty rule)."""
    if not _ENABLED:
        return {"schema_version": SCHEMA_VERSION, "enabled": False}
    snap = _REGISTRY.snapshot()
    snap["enabled"] = True
    snap["context"] = _EVENTS.context()
    snap["events_seen"] = _EVENTS.seq
    return snap


def prom_text(snap=None):
    """Prometheus text-format rendering of ``snap`` (default: a fresh
    :func:`snapshot`)."""
    return _render_prom(snapshot() if snap is None else snap)


# -- flight recorder ----------------------------------------------------

def flight():
    return _FLIGHT


def dump_flight(reason, path=None):
    """Write the flight-recorder dump (ring + snapshot) now.  Returns
    the path, or None when disabled."""
    if not _ENABLED:
        return None
    return _FLIGHT.dump(reason, path=path)


def last_flight_dump():
    """Path of the most recent dump this process wrote (None if none)."""
    return _FLIGHT.last_dump_path


def on_fault(site, payload=None):
    """Fault-point trip hook (called by ``testing.faults.fault_point``
    the moment an armed fault fires): record the trip as an event and
    dump the flight recorder — the post-mortem of an injected or real
    failure."""
    if not _ENABLED:
        return
    _EVENTS.emit("fault.trip", site=site,
                 payload=payload if isinstance(payload, (int, float, str,
                                                         bool, type(None)))
                 else repr(payload))
    _REGISTRY.counter("faults.trips").inc()
    _FLIGHT.dump(f"fault:{site}")


def on_preemption(reason):
    """Preemption hook (called by ``checkpoint.PreemptionHandler
    .request`` — the SIGTERM path): record + dump."""
    if not _ENABLED:
        return
    _EVENTS.emit("preemption", reason=str(reason))
    _REGISTRY.counter("preemptions").inc()
    _FLIGHT.dump(f"preemption:{reason}")


def on_step_error(step, exc):
    """Unhandled train-step exception hook (the trainer's dispatch
    wrapper): record + dump, then the caller re-raises."""
    if not _ENABLED:
        return
    _EVENTS.emit("train.step_error", step=int(step),
                 error=f"{type(exc).__name__}: {exc}")
    _REGISTRY.counter("train.step_errors").inc()
    _FLIGHT.dump(f"step_error:{step}")


def reset():
    """Clear metrics, events, context and the last-dump marker IN PLACE
    (module references held by instrumented sites stay valid).  The
    conftest autouse hook calls this between tests so metric assertions
    can't pair-flake — the profiler.reset() discipline.  The tracing
    ring and the watchdog rule state are process-global in the same
    way and reset alongside (both re-read their env kill switches)."""
    _REGISTRY.reset()
    _EVENTS.reset()
    _FLIGHT.last_dump_path = None
    tracing.reset()
    watchdog.reset()
