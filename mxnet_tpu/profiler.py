"""``mx.profiler`` — profiling API over jax.profiler.

Reference: python/mxnet/profiler.py + src/profiler/ (SURVEY.md §5.1). The
reference wrote Chrome-trace JSON from a C++ ring buffer; here
``jax.profiler`` produces TensorBoard/perfetto traces of the actual XLA
execution, exposed behind the same set_config/start/stop/dumps API, plus the
custom Task/Frame/Counter/Marker objects for user annotation.
"""
from __future__ import annotations

import json
import os
import time
import warnings

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "reset", "Task", "Frame", "Counter", "Marker",
           "Domain", "scope"]

_CONFIG = {"filename": "profile.json", "profile_all": False,
           "aggregate_stats": False}
_STATE = {"running": False, "trace_dir": None, "events": [],
          "t0": None}


def set_config(**kwargs):
    """Accepts the reference kwargs (profile_all, profile_symbolic,
    profile_imperative, profile_memory, profile_api, aggregate_stats,
    filename, ...)."""
    _CONFIG.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def start(profile_process="worker"):
    import jax
    trace_dir = os.path.splitext(_CONFIG.get("filename",
                                             "profile.json"))[0] + "_trace"
    try:
        jax.profiler.start_trace(trace_dir)
        _STATE["trace_dir"] = trace_dir
    except Exception as e:  # already running etc.
        warnings.warn(f"jax trace not started: {e}")
    _STATE["running"] = True
    _STATE["t0"] = time.time()


def stop(profile_process="worker"):
    import jax
    if _STATE.get("trace_dir"):
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        _STATE["trace_dir"] = None
    _STATE["running"] = False


def pause(profile_process="worker"):
    stop()


def resume(profile_process="worker"):
    start()


def dump(finished=True, profile_process="worker"):
    """Write collected custom events as Chrome trace JSON (the reference
    format), alongside the XLA trace directory."""
    events = [{"name": name, "ph": ph, "ts": ts * 1e6, "pid": 0, "tid": 0,
               **extra}
              for name, ph, ts, extra in _STATE["events"]]
    with open(_CONFIG["filename"], "w") as f:
        json.dump({"traceEvents": events}, f)


def dumps(reset=False):
    """Aggregate table of scoped events (reference: aggregate_stats.cc /
    mx.profiler.dumps): per-name count and total duration."""
    opens = {}
    stats = {}
    for name, ph, ts, _ in _STATE["events"]:
        if ph == "B":
            opens.setdefault(name, []).append(ts)
        elif ph == "E" and opens.get(name):
            t0 = opens[name].pop()
            cnt, tot = stats.get(name, (0, 0.0))
            stats[name] = (cnt + 1, tot + (ts - t0))
    lines = ["Profile Statistics:",
             f"{'Name':<32}{'Count':>8}{'Total(ms)':>12}"]
    for name, (cnt, tot) in sorted(stats.items()):
        lines.append(f"{name:<32}{cnt:>8}{tot * 1e3:>12.3f}")
    lines.append(f"(XLA trace under "
                 f"{os.path.splitext(_CONFIG['filename'])[0]}_trace)")
    if reset:
        _STATE["events"] = []
    return "\n".join(lines)


def reset():
    """Drop every collected custom event (the ``dumps()`` aggregation
    source).  The span store is process-global, so without this seam two
    tests' B/E events could pair ACROSS tests and span assertions would
    flake depending on test order — the exact failure mode the gluon
    name-counter fixture fixed for auto-naming (PR 5).  A conftest
    autouse hook calls this around every test."""
    _STATE["events"] = []


def _span_context():
    """The ambient {step, epoch} tags (mx.telemetry context) attached to
    every span while a profile runs, so perfetto/Chrome-trace rows
    correlate with the telemetry event log (ISSUE 9)."""
    from . import telemetry as _telem
    ctx = _telem.context()
    return {"args": ctx} if ctx else {}


def _emit(name, ph, **extra):
    if not extra:
        extra = _span_context()
    _STATE["events"].append((name, ph, time.time(), extra))


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Scoped:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def start(self):
        _emit(self.name, "B")

    def stop(self):
        _emit(self.name, "E")

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Task(_Scoped):
    pass


class Frame(_Scoped):
    pass


class Counter:
    def __init__(self, domain, name, value=None):
        self.name = name
        self.value = value or 0

    def set_value(self, value):
        self.value = value
        _emit(self.name, "C", args={"value": value})

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)


class Marker:
    def __init__(self, domain, name):
        self.name = name

    def mark(self, scope="process"):
        _emit(self.name, "i", s=scope[0])


class scope:
    """Annotate a region; inside jit this becomes a jax.named_scope so the
    region is visible in the XLA trace."""

    def __init__(self, name):
        self.name = name
        self._ctx = None

    def __enter__(self):
        import jax
        self._ctx = jax.named_scope(self.name)
        self._ctx.__enter__()
        _emit(self.name, "B")
        return self

    def __exit__(self, *exc):
        _emit(self.name, "E")
        return self._ctx.__exit__(*exc)
