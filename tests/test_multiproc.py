"""Process-level pod runtime (ISSUE 19): mxnet_tpu.pod + chaos procs.

The full SIGKILL scenario (4 real processes, coordinator re-init,
bitwise resume) is ``slow`` — it belongs to the ``procs`` chaos suite.  Tier-1
keeps one tiny real-process smoke (2 CPU workers, 2 steps, clean exit)
plus the pure-file control-plane unit tests, so the launcher protocol
is exercised on every run without paying the full scenario.
"""
import json
import os

import pytest

from mxnet_tpu.pod import (PodLauncher, queue_ledger, read_membership,
                           submit_request, write_membership)


# ----------------------------------------------------------------------
# control plane: pure file ops, no processes
# ----------------------------------------------------------------------

def test_membership_roundtrip_and_shape(tmp_path):
    d = str(tmp_path)
    write_membership(d, 2, "127.0.0.1:5555", {0: 0, 1: 1, 3: 2},
                     dead=[2])
    m = read_membership(d)
    assert m["epoch"] == 2 and m["world"] == 3
    assert m["coordinator"] == "127.0.0.1:5555"
    assert m["ranks"] == {"0": 0, "1": 1, "3": 2}   # orig -> contiguous
    assert m["dead"] == [2]


def test_queue_ledger_states_and_lease_naming(tmp_path):
    d = str(tmp_path)
    submit_request(d, "a", {"x": 1})
    submit_request(d, "b", {"x": 2})
    led = queue_ledger(d)
    assert led == {"pending": ["a", "b"], "inflight": [], "done": []}
    # a claim is an atomic rename into inflight with the owner suffixed
    os.replace(os.path.join(d, "queue", "pending", "a.json"),
               os.path.join(d, "queue", "inflight", "a.json.lease.3"))
    led = queue_ledger(d)
    assert led["inflight"] == ["a"] and led["pending"] == ["b"]


def test_requeue_returns_unfinished_only(tmp_path):
    """Exactly-once: a dead rank's lease whose result already landed in
    ``done`` is completed work — released, never requeued."""
    d = str(tmp_path)
    for rid in ("a", "b", "c"):
        submit_request(d, rid, {})
    q = os.path.join(d, "queue")
    # rank 3 held a (unfinished) and b (finished, unreleased)
    os.replace(os.path.join(q, "pending", "a.json"),
               os.path.join(q, "inflight", "a.json.lease.3"))
    os.replace(os.path.join(q, "pending", "b.json"),
               os.path.join(q, "inflight", "b.json.lease.3"))
    with open(os.path.join(q, "done", "b.json"), "w") as f:
        json.dump({"id": "b"}, f)
    launcher = PodLauncher.__new__(PodLauncher)
    launcher.pod_dir = d
    requeued = launcher._requeue_leases({3})
    assert requeued == ["a"]
    led = queue_ledger(d)
    assert led["pending"] == ["a", "c"]       # a back in line, b is done
    assert led["inflight"] == [] and led["done"] == ["b"]


def test_requeue_skips_junk_lease_names(tmp_path):
    """_requeue_leases runs inside supervise()'s death handling: a
    corrupt/foreign inflight name with a non-numeric owner suffix must
    be skipped, not crash the whole pod run with ValueError."""
    d = str(tmp_path)
    submit_request(d, "a", {})
    q = os.path.join(d, "queue")
    os.replace(os.path.join(q, "pending", "a.json"),
               os.path.join(q, "inflight", "a.json.lease.1"))
    for junk in ("b.json.lease.", "b.json.lease.abc", "noise.tmp"):
        open(os.path.join(q, "inflight", junk), "w").close()
    launcher = PodLauncher.__new__(PodLauncher)
    launcher.pod_dir = d
    assert launcher._requeue_leases({1}) == ["a"]
    assert queue_ledger(d)["pending"] == ["a"]


def test_gate_hold_withholds_approval(tmp_path):
    launcher = PodLauncher(2, str(tmp_path))
    launcher.epoch = 1
    launcher.procs = {0: None, 1: None}       # _live() sees both
    for r in (0, 1):
        open(os.path.join(str(tmp_path), f"ready.1.4.{r}"), "w").close()
    launcher.hold_step = 4
    launcher._gate_scan()
    assert not os.path.exists(os.path.join(str(tmp_path), "go.1.4"))
    launcher.hold_step = None
    launcher._gate_scan()
    assert os.path.exists(os.path.join(str(tmp_path), "go.1.4"))
    assert launcher.ready_ranks(4) == {0, 1}


# ----------------------------------------------------------------------
# the tier-1 REAL-PROCESS smoke: 2 CPU workers, 2 steps, clean exit
# ----------------------------------------------------------------------

def test_two_process_pod_smoke(tmp_path):
    launcher = PodLauncher(2, str(tmp_path), steps=2, ckpt_every=2)
    launcher.start()
    try:
        summary = launcher.supervise(timeout_s=90.0)
    finally:
        launcher.shutdown()
    assert summary["dead"] == [] and summary["done"] == [0, 1]
    assert summary["epoch"] == 1              # no membership change
    # both ranks saw the distributed world and agree bitwise per step
    # (the summed-allgather update is identical on every rank)
    d0, d1 = launcher.digests(0), launcher.digests(1)
    assert [r["step"] for r in d0] == [1, 2]
    assert [(r["step"], r["digest"]) for r in d0] \
        == [(r["step"], r["digest"]) for r in d1]
    assert all(r["world"] == 2 for r in d0 + d1)
    worlds = {r: s["world"] for r, s in launcher.statuses().items()}
    assert worlds == {0: 2, 1: 2}             # real jax.process_count()


# ----------------------------------------------------------------------
# the full SIGKILL scenario: real processes, out of the tier-1 budget
# ----------------------------------------------------------------------

@pytest.mark.slow   # ~30 s: spawns 4+3 real jax.distributed processes
def test_sigkill_reshard_scenario(tmp_path):
    from mxnet_tpu.testing.chaos import run_multiprocess_scenario
    verdict = run_multiprocess_scenario(workdir=str(tmp_path))
    assert verdict["ok"], json.dumps(verdict, indent=2)
    assert verdict["world_ok"] and verdict["bitwise_resume"]
    assert verdict["ledger_exactly_once"] and verdict["requeue_exercised"]
    assert verdict["scrape_dead_named"] and verdict["dead_error_typed"]
