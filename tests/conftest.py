"""Test configuration.

Mesh/collective tests run on a virtual 8-device CPU mesh
(SURVEY.md §4 technique 3: the reference faked clusters with N local
processes; we fake a pod with N host devices).

Must run before any jax import in the test process.
"""
import os
import sys

repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if repo_root not in sys.path:
    sys.path.insert(0, repo_root)

os.environ.setdefault("MXTPU_SYNTHETIC_DATA", "1")

# The suite runs on the CPU backend, on a virtual 8-device host mesh
# unless the caller already chose a device count.
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import threading

import pytest


@pytest.fixture(scope="session", autouse=True)
def chip_smoke_rehearsal(request, tmp_path_factory):
    """``chip_smoke.py --rehearse-on-cpu`` takes ~30 s of compiles, over
    the per-test budget below (ROADMAP D8).  So it is started here, when
    the session starts, in a process of its own on one CPU device (no
    all-chips phase: that one is rehearsed by hand, see the verify
    skill), and runs beside the suite; ``tests/test_chip_smoke.py``
    joins it and reads its output.  Started only when that test was
    collected.  Yields ``(process, path of its combined output)``."""
    import subprocess
    wanted = any(
        item.nodeid.endswith("test_chip_smoke.py::test_rehearsal_passes")
        for item in request.session.items)
    if not wanted:
        yield None
        return
    out = tmp_path_factory.mktemp("chip_smoke") / "rehearsal.log"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(out.parent / "jax_cache"))
    with open(out, "w") as f:
        # at the lowest priority: it takes the cores the suite leaves idle
        proc = subprocess.Popen(
            ["nice", "-n", "19", sys.executable,
             os.path.join(repo_root, "chip_smoke.py"), "--rehearse-on-cpu"],
            stdout=f, stderr=subprocess.STDOUT, env=env, cwd=repo_root)
    try:
        yield proc, out
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()


@pytest.fixture(autouse=True)
def deterministic_gluon_naming():
    """Reset gluon's GLOBAL auto-naming counters before every test.

    Root cause of the historical test_lint.py -> test_sharded_sync.py
    ``step_accum`` pairing flake: tests that build throwaway blocks
    advance ``gluon.block._GLOBAL_COUNTERS`` (process-global), so a
    later test's auto names depend on which tests ran before it.  Param
    names sort LEXICOGRAPHICALLY — ``"dense10" < "dense9"`` — so when a
    build happened to land on a digit-length boundary, sorted-name
    iteration (used for deterministic weight init and kvstore key
    assignment) visited the layers in a DIFFERENT order than the
    comparison build two counts later, and parity asserts failed in
    some test orders only.  Pinning the counters to zero per test makes
    every test's names a function of the test alone."""
    from mxnet_tpu.gluon import block as _blk
    from mxnet_tpu import name as _name
    _blk._GLOBAL_COUNTERS.clear()
    # symbol-level auto-naming: drop any leaked managers and fresh-count
    if hasattr(_name.NameManager._state, "stack"):
        _name.NameManager._state.stack = []
    yield


@pytest.fixture(autouse=True)
def reset_profiler_and_telemetry():
    """Reset the PROCESS-GLOBAL profiler span store, telemetry
    registry/event-ring, and racecheck state before every test (same
    pattern as the gluon name-counter fixture above).

    ``profiler._STATE['events']`` had no reset seam: a test that opened
    a span without closing it (or vice versa) leaked B/E events that
    PAIRED with a later test's spans in ``dumps()``, so span-count
    assertions depended on test order.  Telemetry metrics have the same
    process-global shape — a counter assertion must count only its own
    test's increments.  Racecheck (ISSUE 10) likewise: its lock-order
    graph and findings are process-global, and a chaos test that
    enabled it must not leave the detector armed (reset() re-reads
    MXTPU_RACECHECK).  The donation sentinel (ISSUE 16) has the same
    shape: its poison registry and findings are process-global and
    reset() re-reads MXTPU_DONATION_CHECK.  Lazy ``sys.modules``
    lookup: tests that never import mxnet_tpu must not pay the
    import."""
    for mod in ("mxnet_tpu.profiler", "mxnet_tpu.telemetry",
                "mxnet_tpu.lint.racecheck",
                "mxnet_tpu.lint.donation"):
        m = sys.modules.get(mod)
        if m is not None:
            m.reset()
    yield


@pytest.fixture(autouse=True)
def no_leaked_nondaemon_threads():
    """Fail any test that leaves a live NON-daemon thread behind
    (leaked checkpoint writers, heartbeat loops, decode pools —
    ThreadPoolExecutor workers are non-daemon, so an unclosed pool
    would otherwise hang the run at interpreter exit and only show up
    as a CI timeout).  Daemon threads are excluded: the framework's
    long-lived service threads (PS accept loops, prefetchers) are
    deliberately daemonic."""
    before = {t.ident for t in threading.enumerate()}
    yield
    leaked = [t for t in threading.enumerate()
              if t.is_alive() and not t.daemon and t.ident not in before
              and t is not threading.current_thread()]
    if not leaked:
        return
    # grace: threads mid-shutdown (e.g. a pool drained by close()) get
    # a moment to exit before we call it a leak — one SHARED 2 s budget,
    # not 2 s per thread.  Threads whose pool REGISTERED a closer
    # (AsyncDecodeIter.close() ran: work cancelled, shutdown signalled,
    # possibly one in-flight sample decode left) get a longer budget —
    # the known test_real_data teardown flake on a loaded host was this
    # guard sampling mid-wind-down, not an actual leak.
    import time as _time
    try:
        from mxnet_tpu.io.prefetch import closing_thread_idents
        closing = closing_thread_idents()
    except Exception:  # noqa: BLE001 — guard must never error a pass
        closing = set()
    grace = 10.0 if any(t.ident in closing for t in leaked) else 2.0
    end = _time.monotonic() + grace
    for t in leaked:
        t.join(timeout=max(0.0, end - _time.monotonic()))
    leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        "test leaked live non-daemon thread(s): "
        + ", ".join(repr(t.name) for t in leaked)
        + " — close() your iterators/pools or mark the thread daemon")


# ----------------------------------------------------------------------
# tier-1 duration guard (ISSUE 16): anything creeping past the budget
# without a `slow` marker fails the run via test_zz_duration_guard.py
# ----------------------------------------------------------------------

#: per-test wall budget (call phase) for NON-slow tests.  The driver runs
#: tier-1 over six xdist workers, split by file (`-n 6 --dist loadfile`,
#: `timeout 1470`, about 300 s): one unmarked 40 s test holds its file's
#: worker while the others go idle.  Tests legitimately past this go
#: behind `@pytest.mark.slow`, which the driver's `-m 'not slow'` leaves
#: out.  Under xdist each worker keeps its own list, and the guard sees
#: the list of the worker it runs on.
DURATION_BUDGET_S = 20.0

#: (nodeid, seconds) for every non-slow test whose call phase crossed
#: the budget this session; read by tests/test_zz_duration_guard.py,
#: which sorts last alphabetically so the sweep has already run.
DURATION_OFFENDERS = []


def pytest_runtest_logreport(report):
    if report.when != "call" or report.duration <= DURATION_BUDGET_S:
        return
    if "slow" in getattr(report, "keywords", {}):
        return
    DURATION_OFFENDERS.append((report.nodeid, round(report.duration, 2)))
