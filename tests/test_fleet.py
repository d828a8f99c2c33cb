"""Fleet observability (ISSUE 15): pod-wide aggregation, straggler
detection, cross-worker trace stitching.

Everything here is deterministic — simulated workers are per-rank
``MetricsRegistry`` instances (exactly what a remote
``PSClient.telemetry()`` scrape returns), clocks are FakeClocks, zero
sleeps.  The PR 9 fixed histogram bucket edges make the merge EXACT:
the gates below compare bitwise, not approximately.
"""
import json
import socket

import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.telemetry import fleet as fleet_mod
from mxnet_tpu.telemetry import tracing
from mxnet_tpu.telemetry.fleet import (FleetCollector, fleet_block,
                                       merge_histograms,
                                       fleet_prom_snapshot,
                                       FLEET_SCHEMA_VERSION)
from mxnet_tpu.telemetry.registry import MetricsRegistry
from mxnet_tpu.testing.faults import FakeClock


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_registry(clock, steps=3, step_ms=50.0, epoch=1):
    reg = MetricsRegistry(now=clock)
    for _ in range(steps):
        reg.histogram("train.step_ms").observe(step_ms)
        reg.counter("train.steps").inc()
    reg.gauge("elastic.epoch").set(epoch)
    return reg


def _transports(regs, dead=()):
    def make(rank):
        def scrape():
            if rank in dead:
                raise ConnectionError("endpoint down")
            return {"snapshot": regs[rank].snapshot()}
        return scrape
    return {r: make(r) for r in regs}


# ----------------------------------------------------------------------
# exact merge
# ----------------------------------------------------------------------

def test_histogram_merge_is_exact_sum_of_buckets():
    clock = FakeClock(10.0)
    regs = {r: _worker_registry(clock, steps=2 + r,
                                step_ms=10.0 * (r + 1))
            for r in range(3)}
    coll = FleetCollector(_transports(regs), now=clock)
    snap = coll.collect()
    merged = snap["histograms"]["train.step_ms"]
    states = [regs[r].snapshot()["histograms"]["train.step_ms"]
              for r in sorted(regs)]
    expect = [0] * len(merged["counts"])
    for st in states:
        for i, c in enumerate(st["counts"]):
            expect[i] += c
    assert merged["counts"] == expect
    # sum/count accumulate in rank order — bitwise, not approximately
    s = 0.0
    for st in states:
        s += st["sum"]
    assert merged["sum"] == s
    assert merged["count"] == sum(st["count"] for st in states)
    assert merged["min"] == 10.0 and merged["max"] == 30.0
    # counters sum; gauges stay per-rank
    assert snap["counters"]["train.steps"] == 2 + 3 + 4
    assert snap["gauges"]["elastic.epoch"] == {"0": 1, "1": 1, "2": 1}
    assert snap["fleet_schema_version"] == FLEET_SCHEMA_VERSION
    # the whole fleet snapshot is JSON-able (the dump/scrape contract)
    json.dumps(snap)


def test_histogram_merge_refuses_mismatched_edges():
    with pytest.raises(MXNetError, match="edges differ"):
        merge_histograms([
            {"edges": [1.0, 2.0], "counts": [1, 0, 0], "sum": 1.0,
             "count": 1, "min": 1.0, "max": 1.0},
            {"edges": [1.0, 4.0], "counts": [1, 0, 0], "sum": 1.0,
             "count": 1, "min": 1.0, "max": 1.0}])


def test_schema_drift_rank_is_excluded_and_typed():
    clock = FakeClock(10.0)
    regs = {0: _worker_registry(clock), 1: _worker_registry(clock)}
    good = _transports(regs)

    def drifted():
        snap = regs[1].snapshot()
        snap["schema_version"] = 999
        return {"snapshot": snap}

    coll = FleetCollector({0: good[0], 1: drifted}, now=clock)
    snap = coll.collect()
    assert snap["alive"] == [0] and snap["dead"] == [1]
    assert "schema drift" in snap["per_rank"]["1"]["error"]
    # the merge used rank 0 alone — no silent mixing across schemas
    assert snap["counters"]["train.steps"] == 3


# ----------------------------------------------------------------------
# skew analysis + fleet watchdog rules
# ----------------------------------------------------------------------

def _gauge_worker(clock, step_ms, epoch=1):
    reg = MetricsRegistry(now=clock)
    reg.gauge("train.step_ms").set(step_ms)
    reg.gauge("elastic.epoch").set(epoch)
    return reg


def test_straggler_named_by_rank_with_flight_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    clock = FakeClock(50.0)
    regs = {0: _gauge_worker(clock, 50.0), 1: _gauge_worker(clock, 50.0),
            2: _gauge_worker(clock, 500.0)}
    coll = FleetCollector(_transports(regs), now=clock, skew=2.0)
    snap = coll.collect()
    assert snap["skew"]["slowest_rank"] == 2
    assert snap["skew"]["skew_ratio"] == 10.0
    assert snap["skew"]["straggler_scores"]["2"] == 10.0
    evs = [e for e in telemetry.events()
           if e["kind"] == "fleet.straggler"]
    assert len(evs) == 1 and evs[0]["data"]["rank"] == 2
    assert evs[0]["data"]["score"] == 10.0
    dump = telemetry.last_flight_dump()
    assert dump is not None
    with open(dump) as f:
        payload = json.load(f)
    assert payload["reason"] == "fleet:straggler"
    assert payload["events"][-1]["kind"] == "fleet.straggler"
    # edge-triggered: the same incident does not re-fire...
    coll.collect()
    assert len([e for e in telemetry.events()
                if e["kind"] == "fleet.straggler"]) == 1
    # ...until the condition clears and recurs
    regs[2].gauge("train.step_ms").set(50.0)
    coll.collect()
    regs[2].gauge("train.step_ms").set(500.0)
    coll.collect()
    assert len([e for e in telemetry.events()
                if e["kind"] == "fleet.straggler"]) == 2
    # the fleet analysis landed on the local registry (thin readers)
    assert telemetry.value("fleet.slowest_rank") == 2
    assert telemetry.value("fleet.step_ms_skew") == 10.0


def test_epoch_desync_names_the_laggard(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    clock = FakeClock(50.0)
    regs = {0: _gauge_worker(clock, 50.0, epoch=4),
            1: _gauge_worker(clock, 50.0, epoch=4),
            2: _gauge_worker(clock, 50.0, epoch=3)}
    coll = FleetCollector(_transports(regs), now=clock)
    snap = coll.collect()
    assert snap["epoch_desync"]["laggards"] == [2]
    evs = [e for e in telemetry.events()
           if e["kind"] == "fleet.epoch_desync"]
    assert len(evs) == 1 and evs[0]["data"]["rank"] == 2
    # resync re-arms the edge
    regs[2].gauge("elastic.epoch").set(4)
    snap = coll.collect()
    assert snap["epoch_desync"] is None


def test_scrape_dead_is_typed_not_fatal(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    clock = FakeClock(50.0)
    regs = {0: _gauge_worker(clock, 50.0), 1: _gauge_worker(clock, 50.0)}
    coll = FleetCollector(_transports(regs, dead=(1,)), now=clock)
    snap = coll.collect()
    assert snap["alive"] == [0] and snap["dead"] == [1]
    assert "ConnectionError" in snap["per_rank"]["1"]["error"]
    evs = [e for e in telemetry.events()
           if e["kind"] == "fleet.scrape_dead"]
    assert len(evs) == 1 and evs[0]["data"]["rank"] == 1
    with open(telemetry.last_flight_dump()) as f:
        assert json.load(f)["reason"] == "fleet:scrape_dead"
    # recovery re-arms
    coll2 = FleetCollector(_transports(regs), now=clock)
    coll2.collect()
    assert len([e for e in telemetry.events()
                if e["kind"] == "fleet.scrape_dead"]) == 1


def test_single_rank_fleet_never_flags_a_straggler():
    clock = FakeClock(50.0)
    regs = {0: _gauge_worker(clock, 500.0)}
    coll = FleetCollector(_transports(regs), now=clock, skew=2.0)
    snap = coll.collect()
    # a fleet of one has no median to lag: score exists, rule silent
    assert snap["skew"]["slowest_rank"] == 0
    assert not [e for e in telemetry.events()
                if e["kind"] == "fleet.straggler"]


# ----------------------------------------------------------------------
# kill switch + pacing
# ----------------------------------------------------------------------

def test_fleet_kill_switch_is_inert(monkeypatch):
    monkeypatch.setenv("MXTPU_FLEET", "0")
    calls = []
    coll = FleetCollector({0: lambda: calls.append(1)})
    before = telemetry.snapshot()
    snap = coll.collect()
    assert snap == {"fleet_schema_version": FLEET_SCHEMA_VERSION,
                    "enabled": False}
    assert coll.poll() is None
    assert not calls                      # no transport ever ran
    assert telemetry.events() == []       # nothing emitted
    after = telemetry.snapshot()
    assert before["counters"] == after["counters"]
    assert before["gauges"] == after["gauges"]


def test_poll_paces_on_the_injected_clock():
    clock = FakeClock(100.0)
    regs = {0: _gauge_worker(clock, 50.0)}
    coll = FleetCollector(_transports(regs), now=clock, scrape_s=30.0)
    assert coll.poll() is not None        # first scrape immediate
    assert coll.poll() is None
    clock.advance(29.0)
    assert coll.poll() is None
    clock.advance(2.0)
    assert coll.poll() is not None
    assert telemetry.value("fleet.scrapes") == 2


# ----------------------------------------------------------------------
# cross-worker trace stitching
# ----------------------------------------------------------------------

def test_ps_rpc_carries_span_context():
    """A PS RPC issued inside an ambient span gets a server-side
    ``ps.rpc.<op>`` span whose args DISCLOSE the remote parent ids —
    the stitch the fleet timeline correlates on."""
    from mxnet_tpu.kvstore.ps_server import PSClient, PSServer
    port = _free_port()
    srv = PSServer("127.0.0.1", port, num_workers=1)
    client = PSClient("127.0.0.1", port)
    try:
        client.init("w", np.zeros(4, np.float32))   # no ambient span
        with tracing.span("coord.pushpull") as root:
            client.push("w", np.ones(4, np.float32))
            root_ids = (root.trace, root.span)
        # the serve loop is sequential per connection: by the time this
        # second (span-free) RPC returns, the push's server-side span
        # has committed — no sleep, no race
        payload = client.telemetry(fmt="fleet")
        rpc = [s for s in tracing.spans()
               if s["name"] == "ps.rpc.push"]
        assert len(rpc) == 1
        assert rpc[0]["args"]["remote_trace"] == root_ids[0]
        assert rpc[0]["args"]["remote_span"] == root_ids[1]
        # the span-free init was NOT wrapped (no fake linkage)
        assert not [s for s in tracing.spans()
                    if s["name"] == "ps.rpc.init"]
        # fleet scrape fmt: snapshot + this rank's span ring
        assert "snapshot" in payload and "spans" in payload
        assert payload["snapshot"]["schema_version"] == \
            telemetry.SCHEMA_VERSION
        assert any(s["name"] == "ps.rpc.push"
                   for s in payload["spans"])
    finally:
        client.close()
        srv._sock.close()


def test_fleet_chrome_trace_lanes_and_offset_disclosure():
    """chrome_trace(fleet=...) puts each rank on its own process lane,
    DISCLOSES the estimated clock offset, and never shifts
    timestamps."""
    clock = FakeClock(1000.0)          # collector's wall clock
    remote_clock = FakeClock(1250.0)   # rank 1 runs 250 s ahead
    span = {"name": "train.step", "trace": 1, "span": 1, "parent": None,
            "t0": 3.0, "t1": 3.5, "thread": "MainThread", "args": {}}

    def rank0():
        return {"snapshot": MetricsRegistry(now=clock).snapshot(),
                "spans": [dict(span)]}

    def rank1():
        return {"snapshot": MetricsRegistry(now=remote_clock).snapshot(),
                "spans": [dict(span)], "dropped_spans": 7}

    coll = FleetCollector({0: rank0, 1: rank1}, now=clock)
    snap = coll.collect()
    assert snap["per_rank"]["0"]["clock_offset_est_s"] == 0.0
    assert snap["per_rank"]["1"]["clock_offset_est_s"] == 250.0
    ct = tracing.chrome_trace(fleet=snap)
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["pid"] for e in xs) == [0, 1]
    # timestamps are the RAW per-rank clocks — the offset is disclosed,
    # never applied
    assert all(e["ts"] == 3.0 * 1e6 for e in xs)
    labels = [e for e in ct["traceEvents"]
              if e.get("name") == "process_labels"]
    assert any("clock_offset_est_s=250.0" in e["args"]["labels"]
               and "NOT applied" in e["args"]["labels"]
               for e in labels)
    assert ct["otherData"]["clock_offset_est_s"]["1"] == 250.0
    assert ct["otherData"]["dropped_spans"] == {"1": 7}


# ----------------------------------------------------------------------
# visible truncation (ISSUE 15 satellite): ring drops are counted
# ----------------------------------------------------------------------

def test_trace_ring_drops_are_counted_and_stamped():
    tracing.configure(ring_size=3)
    for i in range(5):
        tracing.finish(tracing.start(f"s{i}"))
    assert tracing.dropped() == 2
    assert telemetry.value("telemetry.trace.dropped_spans") == 2
    ct = tracing.chrome_trace(include_profiler=False)
    assert ct["otherData"]["dropped_spans"] == 2


def test_event_ring_drops_are_counted():
    telemetry.configure(ring_size=3)
    for i in range(5):
        telemetry.event(f"e{i}")
    assert telemetry.events_dropped() == 2
    assert telemetry.value("telemetry.events.dropped") == 2
    assert len(telemetry.events()) == 3


# ----------------------------------------------------------------------
# memory honesty (ISSUE 15 satellite): flight dumps name the consumer
# ----------------------------------------------------------------------

def test_flight_dump_carries_memory_block(tmp_path):
    path = str(tmp_path / "dump.json")
    telemetry.dump_flight("test", path=path)
    with open(path) as f:
        dump = json.load(f)
    mem = dump["memory"]
    # gauges: present-or-null, never fabricated zeros
    for name in ("train.param_bytes", "serving.kv_bytes_in_use",
                 "io.prefetch_buffer_bytes"):
        assert name in mem["gauges"]
        assert mem["gauges"][name] is None
    # device stats: the CPU backend exposes none -> None, never 0
    if mem["devices"] is not None:
        for row in mem["devices"]:
            assert row["bytes_in_use"] is None or row["bytes_in_use"] > 0


def test_trainer_publishes_exact_byte_gauges(tmp_path):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    mx.random.seed(7)
    np.random.seed(7)
    net = gluon.nn.Dense(4)
    net.initialize()
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 0.05},
        shard_updates=True)
    x = mx.nd.array(np.random.randn(16, 8).astype(np.float32))
    y = mx.nd.array(np.random.randn(16, 4).astype(np.float32))
    trainer.step(x, y)
    pbytes = telemetry.value("train.param_bytes")
    # dense 8x4 + bias 4 in fp32 = (32 + 4) * 4 bytes exactly
    assert pbytes == 36 * 4
    sbytes = telemetry.value("train.zero1_shard_bytes")
    rbytes = telemetry.value("train.opt_state_bytes")
    assert (sbytes is not None) or (rbytes is not None)
    # and the flight dump names them
    path = str(tmp_path / "dump.json")
    telemetry.dump_flight("test", path=path)
    with open(path) as f:
        gauges = json.load(f)["memory"]["gauges"]
    assert gauges["train.param_bytes"] == pbytes


def test_kv_cache_block_nbytes_is_exact():
    from mxnet_tpu.serving.kv_cache import PagedKVCache
    cache = PagedKVCache(num_layers=2, num_kv_heads=2, head_dim=4,
                         num_blocks=8, block_size=4)
    # 2 pools x 2 layers x 4 tokens x 2 heads x 4 dims x 4 bytes
    assert cache.block_nbytes == 2 * 2 * 4 * 2 * 4 * 4


# ----------------------------------------------------------------------
# chaos + tooling wiring
# ----------------------------------------------------------------------

def test_chaos_fleet_scenario(tmp_path, monkeypatch):
    """The tier-1 wiring of ``python -m mxnet_tpu.testing.chaos fleet``:
    straggler + scrape-dead ranks named, histograms merged bitwise,
    racecheck clean."""
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    from mxnet_tpu.testing.chaos import run_fleet_scenario
    r = run_fleet_scenario(workdir=str(tmp_path))
    assert r["ok"], r


def test_telemetry_dump_fleet_multi_host(tmp_path, capsys):
    """tools/telemetry_dump.py --fleet: multi-host scrape merged into
    one snapshot; a dead host is a typed SCRAPE_FAILED line, not an
    abort."""
    from mxnet_tpu.kvstore.ps_server import PSServer
    import tools.telemetry_dump as td
    telemetry.inc("train.steps", 4)
    ports = [_free_port(), _free_port()]
    servers = [PSServer("127.0.0.1", p, num_workers=1) for p in ports]
    dead_port = _free_port()
    try:
        spec = (f"127.0.0.1:{ports[0]},127.0.0.1:{ports[1]},"
                f"127.0.0.1:{dead_port}")
        rc = td.main(["--fleet", "--host", spec, "--format", "json"])
        out = capsys.readouterr().out
        assert rc == 0
        failed = [l for l in out.splitlines()
                  if l.startswith("SCRAPE_FAILED ")]
        assert len(failed) == 1
        assert json.loads(failed[0][len("SCRAPE_FAILED "):])["rank"] == 2
        body = out[out.index("\n{") + 1:] if "\n{" in out \
            else out[out.index("{"):]
        snap = json.loads(body)
        # both live ranks scraped THIS process: counters sum to 2x
        assert snap["counters"]["train.steps"] == 8
        assert snap["alive"] == [0, 1] and snap["dead"] == [2]
        # prom rendering of the merged view
        rc = td.main(["--fleet", "--host", spec])
        out = capsys.readouterr().out
        assert "mxtpu_train_steps 8" in out
        # fleet trace export writes per-rank lanes
        trace_out = str(tmp_path / "fleet.json")
        rc = td.main(["--fleet", "--host", spec, "--trace", trace_out])
        capsys.readouterr()
        assert rc == 0
        with open(trace_out) as f:
            ct = json.load(f)
        assert "otherData" in ct
    finally:
        for srv in servers:
            srv._sock.close()


def test_multi_host_dump_reports_per_host_failures(capsys):
    """--host h1,h2 (no --fleet): per-host sections, typed failure
    lines instead of aborting on the first dead host."""
    from mxnet_tpu.kvstore.ps_server import PSServer
    import tools.telemetry_dump as td
    telemetry.inc("train.steps", 2)
    port = _free_port()
    srv = PSServer("127.0.0.1", port, num_workers=1)
    dead_port = _free_port()
    try:
        rc = td.main(["--host",
                      f"127.0.0.1:{dead_port},127.0.0.1:{port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "SCRAPE_FAILED " in out.splitlines()[0]
        assert "mxtpu_train_steps 2" in out
    finally:
        srv._sock.close()


def test_fleet_prom_snapshot_flattens_per_rank_gauges():
    clock = FakeClock(10.0)
    regs = {0: _gauge_worker(clock, 50.0), 1: _gauge_worker(clock, 60.0)}
    coll = FleetCollector(_transports(regs), now=clock)
    snap = coll.collect()
    from mxnet_tpu.telemetry.prom import prom_text
    text = prom_text(fleet_prom_snapshot(snap))
    assert "mxtpu_train_step_ms_rank0 50.0" in text
    assert "mxtpu_train_step_ms_rank1 60.0" in text
    assert "mxtpu_fleet_ranks 2" in text


# ----------------------------------------------------------------------
# the `fleet` block schema (ISSUE 15): config always real, measured
# skew/scrape fields null-when-unmeasured — a single-process run can't
# pass off "no fleet to scrape" as "zero skew measured"
# ----------------------------------------------------------------------

_FLEET_KEYS = {
    "fleet_schema_version", "enabled", "ranks", "slowest_rank",
    "step_ms_skew", "scrape_ms", "stragglers", "epoch_desync",
    "scrape_dead",
}


def test_fleet_block_schema_is_stable():
    from mxnet_tpu.telemetry.fleet import (fleet_block,
                                           FLEET_SCHEMA_VERSION)
    blk = fleet_block()
    assert set(blk) == _FLEET_KEYS
    assert blk["fleet_schema_version"] == FLEET_SCHEMA_VERSION
    for k in ("slowest_rank", "step_ms_skew", "scrape_ms",
              "stragglers", "epoch_desync", "scrape_dead"):
        assert blk[k] is None, k
    assert blk["enabled"] is False and blk["ranks"] == 0
    blk2 = fleet_block(enabled=True, ranks=4, slowest_rank=2,
                       step_ms_skew=3.14159, scrape_ms=12.5555,
                       stragglers=1, epoch_desync=False, scrape_dead=1)
    assert blk2["step_ms_skew"] == 3.1416
    assert blk2["scrape_ms"] == 12.556
    assert blk2["slowest_rank"] == 2 and blk2["scrape_dead"] == 1
    assert json.loads(json.dumps(blk)) == blk
