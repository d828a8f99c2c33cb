"""Multi-process rendezvous, run before any JAX computation.

Reference contract: ps-lite rendezvous happens when the first KVStore is
created from DMLC_* env (SURVEY.md §3.5). JAX's coordination service must
instead be up BEFORE the backend initializes, so this runs at package
import when tools/launch.py (or an operator) set the MXTPU_* env.

ISSUE 19 (real multi-process pods) made the init path fault-TOLERANT:
``jax.distributed.initialize``'s default client installs a
missed-heartbeat / error-poll callback that ``LOG(FATAL)``-terminates
the process the moment ANY peer dies, and its ``shutdown()`` runs a
coordination-service barrier that can never be satisfied once a peer
was SIGKILLed — i.e. the stock path turns one death into pod suicide.
``_raw_init`` builds the same service/client pair through the jaxlib
extension directly, but with a benign missed-heartbeat callback (a
peer death is the POD LAUNCHER's membership signal, not a reason to
terminate survivors) and ``shutdown_on_destruction=False`` so teardown
can ORPHAN a coordination service whose shutdown barrier is
unsatisfiable.  ``reinit_distributed`` is the committed-membership-
change seam: tear down, clear every cached world-size view, re-init at
the new coordinates.
"""
from __future__ import annotations

import os

_DONE = False

#: orphaned (client, service) pairs from pre-reshard epochs — kept
#: referenced so their destructors (which would block on RPCs to dead
#: peers) never run; the port leak lasts only for the process lifetime
_ORPHANED = []


def _heartbeat_knobs():
    """(interval_s, max_missing) for the coordination service/client.
    The defaults keep detection with the launcher (which watches real
    pids) rather than the coordination service: a huge miss budget so
    the service never error-propagates a death into the survivors —
    they'll have re-initialized at a new epoch long before."""
    try:
        interval = int(os.environ.get(
            "MXTPU_COORD_HEARTBEAT_INTERVAL_S", "10") or 10)
    except ValueError:
        interval = 10
    try:
        max_missing = int(os.environ.get(
            "MXTPU_COORD_MAX_MISSING_HEARTBEATS", "1000") or 1000)
    except ValueError:
        max_missing = 1000
    return max(1, interval), max(1, max_missing)


def _raw_init(coordinator, num_processes, process_id):
    """Bring up the coordination service (process 0) + client without
    the stock fatal-on-peer-death callbacks.  Fills
    ``jax._src.distributed.global_state`` exactly like
    ``jax.distributed.initialize`` so the backend and
    ``multihost_utils`` see a normal distributed world."""
    import jax
    from jax._src import distributed as _dist
    from jax._src.lib import _jax

    gs = _dist.global_state
    if gs.client is not None:       # operator initialized it already
        return
    interval, max_missing = _heartbeat_knobs()
    # jaxlib takes one timeout where it used to take an interval and a
    # miss budget; their product is the same silence before a peer
    # counts as missing
    heartbeat_timeout = interval * max_missing
    port = str(coordinator).rsplit(":", 1)[1]
    if int(process_id) == 0 and gs.service is None:
        gs.service = _jax.get_distributed_runtime_service(
            "[::]:" + port, int(num_processes),
            heartbeat_timeout=heartbeat_timeout)

    def _on_missed(status):
        # a silent peer is the launcher's membership problem; log +
        # count, never terminate (the stock callback LOG(FATAL)s here)
        try:
            from . import telemetry as _telemetry
            _telemetry.inc("pod.coordination_errors")
            _telemetry.event("pod.coordination_error",
                             status=str(status))
        except Exception:  # noqa: BLE001 — never raise into the cb
            pass

    gs.client = _jax.get_distributed_runtime_client(
        str(coordinator), int(process_id),
        init_timeout=int(os.environ.get("MXTPU_COORD_INIT_TIMEOUT_S",
                                        "120") or 120),
        heartbeat_timeout=heartbeat_timeout,
        missed_heartbeat_callback=_on_missed,
        shutdown_on_destruction=False,
        use_compression=True)
    gs.client.connect()
    gs.process_id = int(process_id)
    gs.num_processes = int(num_processes)
    gs.coordinator_address = str(coordinator)
    assert jax  # keep the import: config side-effects must have run


def maybe_init_distributed():
    global _DONE
    if _DONE:
        return
    coord = os.environ.get("MXTPU_COORDINATOR")
    nproc = int(os.environ.get("MXTPU_NUM_PROCESSES", "1"))
    if coord and nproc > 1:
        # only latch once an actual init was attempted, so a store created
        # before the env is set still triggers rendezvous later
        _DONE = True
        import jax
        try:
            if os.environ.get("JAX_PLATFORMS", "") == "cpu":
                # CPU processes need an XLA collective transport for the
                # in-graph allreduce wire path (kvstore
                # _bucketed_allreduce); gloo ships with jaxlib
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
        except Exception:  # noqa: BLE001 — allgather fallback still works
            pass
        _raw_init(coord, nproc,
                  int(os.environ.get("MXTPU_PROCESS_ID", "0")))


def teardown_distributed(graceful=False):
    """Leave the current coordination service WITHOUT the shutdown
    barrier (unsatisfiable once a peer was SIGKILLed): orphan the
    client/service pair so no destructor blocks on dead peers, then
    clear every cached world-size view so the next init starts clean.
    ``graceful=True`` additionally attempts the barriered shutdown
    first (clean full-pod exits, where every peer participates)."""
    import jax
    from jax._src import distributed as _dist
    from jax._src import xla_bridge

    gs = _dist.global_state
    if graceful and gs.client is not None:
        try:
            gs.client.shutdown()
            gs.client = None
        except Exception:  # noqa: BLE001 — fall through to orphaning
            pass
    if gs.client is not None or gs.service is not None:
        _ORPHANED.append((gs.client, gs.service))
    gs.client = None
    gs.service = None
    gs.preemption_sync_manager = None
    gs.process_id = 0
    gs.num_processes = 1
    gs.coordinator_address = None
    # jax.distributed.initialize refuses to run once backends exist,
    # and the old backend pins the old world size.  Every LIVE device
    # buffer dies here: callers must capture state to host (numpy /
    # checkpoint) FIRST — which is why the elastic controller drives
    # resharding through the checkpoint restore path on this route.
    xla_bridge._clear_backends()
    # both are @lru_cache'd on the bridge and would keep reporting the
    # old world (process_index is not cached)
    for cached in (xla_bridge.process_count, xla_bridge.local_devices):
        try:
            cached.cache_clear()
        except AttributeError:
            pass
    # compiled computations hold old Device objects; executing them
    # against the new backend fails with a buffer-on-wrong-client
    # error even though the device NAMES match
    jax.clear_caches()


def reinit_distributed(coordinator, num_processes, process_id):
    """Tear down and re-create the JAX coordination service at a new
    world size (ISSUE 19) — what a COMMITTED membership change means at
    process level: a real death changes ``jax.process_count()``, and
    that number is baked into the coordination service, the backend
    client, and several ``lru_cache``\\ d accessors.

    Also re-exports the MXTPU_* env so children forked after the change
    inherit the new world.  Returns the elapsed seconds (the
    ``coordinator_reinit_ms`` of ``ElasticController.stats()``).
    """
    import time as _time

    t0 = _time.monotonic()
    teardown_distributed()
    os.environ["MXTPU_COORDINATOR"] = str(coordinator)
    os.environ["MXTPU_NUM_PROCESSES"] = str(num_processes)
    os.environ["MXTPU_PROCESS_ID"] = str(process_id)
    if int(num_processes) > 1:
        _raw_init(coordinator, num_processes, process_id)
    return _time.monotonic() - t0
