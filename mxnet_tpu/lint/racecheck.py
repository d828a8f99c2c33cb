"""Runtime race / lock-order detector — the dynamic half of HB14-HB16.

The static concurrency pass (``concurrency.py``) reasons about lock
discipline it can SEE in the source; this module watches the locks a
live process actually takes.  With ``MXTPU_RACECHECK=1`` the threaded
subsystems (``io.DevicePrefetcher``, ``AsyncCheckpointer``, the PS
server/heartbeat threads, elastic ``Membership``, the telemetry
registry/event log, ``recordio`` readers) create their locks through
:func:`make_lock` / :func:`make_rlock` / :func:`make_condition`, which
hand back instrumented wrappers that

- record, per thread, the stack of locks currently held plus the
  acquisition call stack;
- maintain the process-wide **lock-order graph** (edge A -> B when a
  thread acquires B while holding A, keyed by lock *name* so two
  instances of the same role share a node — the lockdep "lock class"
  idea) and flag a cycle the moment an edge closes one: the static
  HB15 inversion, caught at runtime even when the two orders live in
  different modules;
- check **registered guarded structures** (:func:`guard`): a dict
  registered against a lock that is mutated (or read) by a thread NOT
  holding that lock is an HB14 race observed live.

Findings are recorded in-process (:func:`findings`), emitted as
``racecheck.*`` telemetry events, and dumped through the PR 9 flight
recorder (``reason="racecheck:<kind>"``) so a chaos run that races
leaves the same post-mortem a kill does.  The chaos suites
(``testing/chaos.py``, ``python -m mxnet_tpu.testing.chaos``) run under
the detector and assert an empty findings list after every scenario.

Zero overhead when off (the default): :func:`make_lock` returns a plain
``threading.Lock`` — no wrapper allocation, no graph, no thread-local —
and :func:`guard` returns the structure unchanged.  Enabling mid-process
(``configure(enabled=True)``) instruments locks created AFTER the call;
locks built while disabled stay plain.

Stdlib-only at import (the ``mx.lint`` contract): telemetry is imported
lazily and only when a finding fires.
"""
from __future__ import annotations

import os
import threading
import traceback

__all__ = ["enabled", "configure", "configure_from_env", "make_lock",
           "make_rlock", "make_condition", "guard", "findings",
           "assert_clean", "reset", "TrackedLock", "GuardedDict",
           "RaceCheckError"]


class RaceCheckError(AssertionError):
    """:func:`assert_clean` failed — the run produced findings."""


def _env_enabled():
    return os.environ.get("MXTPU_RACECHECK", "0") not in ("", "0")


_ENABLED = _env_enabled()

# internal bookkeeping lock: a PLAIN lock, never tracked — the detector
# must not observe (or deadlock on) its own state
_STATE_LOCK = threading.Lock()
_EDGES = {}        # name -> {name}: the live lock-order graph
_EDGE_SITES = {}   # (a, b) -> (thread_name, short_stack)
_CYCLES_SEEN = set()
_FINDINGS = []
_HELD = threading.local()   # per-thread list of lock names (stack order)


def enabled():
    """Whether the detector is live (``MXTPU_RACECHECK=1``)."""
    return _ENABLED


def configure(enabled=None):
    """Flip the detector (tests / chaos harness).  Only locks created
    AFTER enabling are tracked — the zero-overhead contract means
    disabled-mode locks carry no wrapper to retrofit."""
    global _ENABLED
    if enabled is not None:
        _ENABLED = bool(enabled)
    return _ENABLED


def configure_from_env():
    """Re-read ``MXTPU_RACECHECK`` (subprocess harnesses that mutate the
    env after import)."""
    return configure(enabled=_env_enabled())


def reset():
    """Clear the graph, findings, and edge sites, and re-read the env
    (the conftest per-test hook, alongside telemetry/profiler reset)."""
    global _ENABLED
    with _STATE_LOCK:
        _EDGES.clear()
        _EDGE_SITES.clear()
        _CYCLES_SEEN.clear()
        del _FINDINGS[:]
    _ENABLED = _env_enabled()


def findings():
    """All findings so far, oldest first (list of dicts:
    ``{"kind", "detail", "locks", "thread", "stack"}``)."""
    with _STATE_LOCK:
        return [dict(f) for f in _FINDINGS]


def assert_clean(context=""):
    """Raise :class:`RaceCheckError` when any finding was recorded —
    the chaos suites' post-scenario gate."""
    found = findings()
    if found:
        lines = [f"  [{f['kind']}] {f['detail']}" for f in found]
        raise RaceCheckError(
            f"racecheck: {len(found)} finding(s)"
            + (f" after {context}" if context else "") + ":\n"
            + "\n".join(lines))


def _short_stack(skip=3, limit=6):
    """Compact acquisition stack: the frames above the wrapper."""
    frames = traceback.extract_stack()[:-skip]
    return [f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
            for f in frames[-limit:]]


def _record(kind, detail, locks=(), stack=None):
    rec = {"kind": kind, "detail": detail, "locks": list(locks),
           "thread": threading.current_thread().name,
           "stack": list(stack or _short_stack())}
    with _STATE_LOCK:
        _FINDINGS.append(rec)
    _dump(kind, rec)
    return rec


def _dump(kind, rec):
    """Emit the finding as a telemetry event and dump the flight
    recorder (the PR 9 post-mortem path).  Lazy absolute import: this
    module must stay stdlib-importable (tools/mxlint.py loads lint/
    standalone), and a finding in a process without mxnet_tpu loaded
    just stays in-process."""
    try:
        import sys
        mx = sys.modules.get("mxnet_tpu")
        if mx is None:
            return
        telemetry = mx.telemetry
    except (ImportError, AttributeError):
        return
    try:
        telemetry.event(f"racecheck.{kind}", detail=rec["detail"],
                        locks=",".join(rec["locks"]),
                        thread=rec["thread"])
        telemetry.inc("racecheck.findings")
        telemetry.dump_flight(f"racecheck:{kind}")
    except Exception:  # noqa: BLE001 — reporting must never take the run down
        pass


# -- lock-order graph ---------------------------------------------------

def _held_list():
    lst = getattr(_HELD, "names", None)
    if lst is None:
        lst = _HELD.names = []
    return lst


def _reachable(graph, src, dst):
    stack, seen = [src], set()
    while stack:
        n = stack.pop()
        if n == dst:
            return True
        if n in seen:
            continue
        seen.add(n)
        stack.extend(graph.get(n, ()))
    return False


def _on_acquire(name):
    held = _held_list()
    new_edges = []
    with _STATE_LOCK:
        for h in held:
            if h == name:
                continue              # re-entrant RLock: no self-edge
            if name not in _EDGES.get(h, ()):
                new_edges.append(h)
    cycle_hits = []
    if new_edges:
        stack = _short_stack(skip=4)
        tname = threading.current_thread().name
        with _STATE_LOCK:
            for h in new_edges:
                # cycle check BEFORE inserting: does name already reach h?
                if _reachable(_EDGES, name, h):
                    key = frozenset((h, name))
                    if key not in _CYCLES_SEEN:
                        _CYCLES_SEEN.add(key)
                        other = _EDGE_SITES.get((name, h))
                        cycle_hits.append((h, name, stack, other))
                _EDGES.setdefault(h, set()).add(name)
                _EDGE_SITES.setdefault((h, name), (tname, stack))
    held.append(name)
    for h, n, stack, other in cycle_hits:
        where = (f"; reverse order taken by thread {other[0]!r} at "
                 f"{' < '.join(other[1])}" if other else "")
        _record(
            "lock-order",
            f"lock-order inversion: acquired {n!r} while holding {h!r}, "
            f"but {n!r} is (transitively) acquired before {h!r} "
            f"elsewhere — two threads interleaving these orders "
            f"deadlock{where}",
            locks=(h, n), stack=stack)


def _on_release(name):
    held = _held_list()
    # remove by identity of name, newest first (cv.wait releases out of
    # strict LIFO order when the waiter holds other locks)
    for i in range(len(held) - 1, -1, -1):
        if held[i] == name:
            del held[i]
            return


class TrackedLock:
    """Instrumented Lock/RLock: same blocking semantics (delegates to a
    real primitive), plus held-stack and lock-order bookkeeping."""

    __slots__ = ("name", "_lock")

    def __init__(self, name, rlock=False):
        self.name = str(name)
        self._lock = threading.RLock() if rlock else threading.Lock()

    def acquire(self, blocking=True, timeout=-1):
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            _on_acquire(self.name)
        return ok

    def release(self):
        _on_release(self.name)
        self._lock.release()

    def locked(self):
        return self._lock.locked()

    def held_by_current_thread(self):
        return self.name in _held_list()

    # threading.Condition uses _is_owned when the wrapped lock offers it
    def _is_owned(self):
        return self.held_by_current_thread()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return f"TrackedLock({self.name!r})"


def make_lock(name):
    """A mutex for ``name`` (a stable role string like
    ``"PSServer._lock"`` — instances of the same role share one graph
    node).  Disabled: a plain ``threading.Lock`` — NO wrapper."""
    if not _ENABLED:
        return threading.Lock()
    return TrackedLock(name)


def make_rlock(name):
    if not _ENABLED:
        return threading.RLock()
    return TrackedLock(name, rlock=True)


def make_condition(name):
    """A condition variable whose underlying mutex is tracked (the
    ``PSServer._barrier_cv`` shape)."""
    if not _ENABLED:
        return threading.Condition()
    return threading.Condition(lock=TrackedLock(name))


# -- guarded structures -------------------------------------------------

def _holds(lock):
    if isinstance(lock, TrackedLock):
        return lock.held_by_current_thread()
    inner = getattr(lock, "_lock", None)       # Condition wrapping one
    if isinstance(inner, TrackedLock):
        return inner.held_by_current_thread()
    # plain lock: best effort — held by SOMEONE counts (cannot attribute
    # to this thread without the wrapper)
    try:
        return lock.locked()
    except AttributeError:
        return False


class GuardedDict(dict):
    """A dict whose every access must happen with the registered lock
    held by the CURRENT thread; violations are recorded, never raised —
    the detector observes, the chaos gate fails the run."""

    def __init__(self, data, lock, name):
        super().__init__(data)
        self._rc_lock = lock
        self._rc_name = str(name)

    def _rc_check(self, op):
        if not _holds(self._rc_lock):
            _record(
                "unguarded-access",
                f"guarded structure {self._rc_name!r} {op} without its "
                f"lock held by thread "
                f"{threading.current_thread().name!r}",
                locks=(getattr(self._rc_lock, "name", "<lock>"),))

    def __getitem__(self, k):
        self._rc_check(f"read [{k!r}]")
        return super().__getitem__(k)

    def __setitem__(self, k, v):
        self._rc_check(f"write [{k!r}]")
        super().__setitem__(k, v)

    def __delitem__(self, k):
        self._rc_check(f"del [{k!r}]")
        super().__delitem__(k)

    def __contains__(self, k):
        self._rc_check(f"contains [{k!r}]")
        return super().__contains__(k)

    def get(self, k, default=None):
        self._rc_check(f"get [{k!r}]")
        return super().get(k, default)

    def pop(self, k, *default):
        self._rc_check(f"pop [{k!r}]")
        return super().pop(k, *default)

    def update(self, *a, **kw):
        self._rc_check("update")
        super().update(*a, **kw)

    def clear(self):
        self._rc_check("clear")
        super().clear()

    def setdefault(self, k, default=None):
        self._rc_check(f"setdefault [{k!r}]")
        return super().setdefault(k, default)


def guard(mapping, lock, name):
    """Register ``mapping`` (a dict) as guarded by ``lock``: every
    access from a thread not holding the lock is a finding.  Disabled:
    returns ``mapping`` unchanged (zero overhead)."""
    if not _ENABLED:
        return mapping
    return GuardedDict(mapping, lock, name)
