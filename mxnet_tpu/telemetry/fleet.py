"""Fleet observability: pod-wide telemetry aggregation (ISSUE 15).

Every observability surface built so far — the PR 9 registry, PR 14
tracing/watchdog/live-MFU — is process-local, yet on a v5e-256 pod the
signal that matters is *cross-worker*: one straggling host sets the
step time for all 32 (the MLPerf TPU-pod analysis, arXiv:1909.09756,
attributes most lost scale efficiency to exactly this; the
concurrency-limits study, arXiv:2011.03641, shows the tail worker is
the ceiling).  PR 9 shipped the raw ingredients — a PS ``_OP_TELEMETRY``
scrape RPC, FIXED histogram bucket edges chosen for deterministic
cross-worker aggregation, schema-versioned events — and this module is
the aggregation plane that finally consumes them fleet-wide:

- :class:`FleetCollector` scrapes every worker's registry snapshot
  (``PSClient.telemetry()`` for remote ranks, the local registry for
  rank 0, or any injectable transport — N simulated workers test under
  FakeClock with zero sleeps) and merges them into ONE fleet snapshot:
  counters summed, gauges kept per-rank, histograms merged EXACTLY
  (possible because PR 9 fixed the bucket edges — element-wise bucket
  addition, never a re-binning estimate; mismatched edges REFUSE to
  merge).
- Per-rank **skew analysis**: each rank's ``train.step_ms`` vs. the
  fleet median gives a ``straggler_score``; the snapshot names the
  slowest rank, the skew ratio, and any desynced membership epoch.
- **Fleet watchdog rules** on the PR 14 edge-trigger machinery
  (:class:`~.watchdog.EdgeRuleEngine`): ``fleet.straggler``,
  ``fleet.epoch_desync``, ``fleet.scrape_dead`` — each firing is a
  typed ``fleet.<rule>`` event + a flight dump
  (``reason="fleet:<rule>"``) NAMING the offending rank, re-armed only
  after the condition clears.
- **Cross-worker trace stitching**: a ``fleet`` scrape also pulls each
  rank's finished-span ring (PS ``_OP_TELEMETRY`` fmt=2), and
  ``tracing.chrome_trace(fleet=...)`` merges them into one perfetto
  timeline with per-rank process lanes.  The per-rank clock offset is
  ESTIMATED from the scrape round-trip and DISCLOSED as a lane label —
  never silently applied to timestamps.

``MXTPU_FLEET=0`` is a bitwise-inert kill switch in the PR 9 style
(:meth:`FleetCollector.collect` scrapes nothing, emits nothing);
``MXTPU_FLEET_SCRAPE_S`` paces :meth:`FleetCollector.poll` (default
30 s, injectable clock — zero sleeps in tests); ``MXTPU_FLEET_SKEW``
is the straggler-score threshold (default 2.0).  Exposure:
``tools/telemetry_dump.py --fleet`` (multi-host scrape -> merged prom
text / JSON / ``--trace`` fleet timeline) and the ``fleet`` summary
(:func:`fleet_block`, null-when-unmeasured on a single process).
Topology diagram and merge-semantics table: docs/OBSERVABILITY.md
§Fleet.
"""
from __future__ import annotations

import os
import threading
import time

from ..base import MXNetError
from .events import SCHEMA_VERSION
from .watchdog import EdgeRuleEngine

__all__ = ["FLEET_SCHEMA_VERSION", "FleetCollector", "enabled",
           "default_scrape_s", "default_skew", "merge_histograms",
           "local_transport", "ps_transport", "transports_from_addrs",
           "fleet_prom_snapshot", "fleet_block"]

#: bump on any BREAKING fleet-snapshot field change (additive fields
#: keep the version), like the telemetry schema
FLEET_SCHEMA_VERSION = 1


def enabled():
    """Whether the fleet plane is live (``MXTPU_FLEET`` != 0).  Read at
    call time so chaos/tests can flip it without a reimport."""
    return os.environ.get("MXTPU_FLEET", "1") != "0"


def default_scrape_s():
    try:
        return float(os.environ.get("MXTPU_FLEET_SCRAPE_S", "") or 30.0)
    except ValueError:
        return 30.0


def default_skew():
    try:
        return float(os.environ.get("MXTPU_FLEET_SKEW", "") or 2.0)
    except ValueError:
        return 2.0


# -- transports ---------------------------------------------------------

def local_transport():
    """Scrape THIS process (rank 0's view in the default topology where
    the collector runs on the coordinator)."""
    def scrape():
        from . import snapshot
        from . import tracing
        return {"snapshot": snapshot(), "spans": tracing.spans(),
                "dropped_spans": tracing.dropped()}
    return scrape


def ps_transport(host, port, retries=3, policy=None):
    """Scrape a remote rank over its PS server's ``_OP_TELEMETRY`` RPC
    (fmt=2: snapshot + finished-span ring — the fleet payload).  A
    fresh connection per scrape: a hung worker must fail THIS scrape,
    not block the collector's socket forever.  ``policy`` (a
    ``kvstore.rpc.RetryPolicy``) bounds the connect/read deadlines and
    retries (ISSUE 19); the default reads the ``MXTPU_RPC_*`` env, so a
    dead rank fails TYPED within the deadline instead of hanging the
    scrape."""
    def scrape():
        from ..kvstore.ps_server import PSClient
        client = PSClient(host, int(port), retries=retries,
                          policy=policy)
        try:
            return client.telemetry(fmt="fleet")
        finally:
            client.close()
    return scrape


def transports_from_addrs(addrs, retries=3):
    """``"h0:p0,h1:p1,..."`` -> an ordered {rank: transport} map, rank =
    position in the list."""
    out = {}
    for rank, part in enumerate(p for p in str(addrs).split(",")
                                if p.strip()):
        host, _, port = part.strip().rpartition(":")
        if not host:
            raise MXNetError(f"fleet transport spec {part!r}: expected "
                             f"host:port")
        out[rank] = ps_transport(host, int(port), retries=retries)
    return out


# -- exact merge --------------------------------------------------------

def merge_histograms(states):
    """Element-wise merge of fixed-edge histogram states — EXACT, the
    PR 9 contract: all ranks must carry identical edges (they do, the
    edges are fixed at creation) or the merge REFUSES rather than
    re-bin.  Summation runs in the caller's rank order, so two merges
    of the same snapshots are bitwise identical."""
    states = list(states)
    if not states:
        return None
    edges = list(states[0]["edges"])
    for st in states[1:]:
        if list(st["edges"]) != edges:
            raise MXNetError(
                f"fleet merge: histogram edges differ across ranks "
                f"({edges} vs {list(st['edges'])}); fixed-edge "
                f"histograms merge exactly or not at all")
    counts = [0] * (len(edges) + 1)
    total_sum, total_count = 0.0, 0
    vmin = vmax = None
    for st in states:
        for i, c in enumerate(st["counts"]):
            counts[i] += c
        total_sum += st["sum"]
        total_count += st["count"]
        if st["min"] is not None and (vmin is None or st["min"] < vmin):
            vmin = st["min"]
        if st["max"] is not None and (vmax is None or st["max"] > vmax):
            vmax = st["max"]
    return {"edges": edges, "counts": counts, "sum": total_sum,
            "count": total_count, "min": vmin, "max": vmax}


def _normalize_payload(payload):
    """A transport may return the fleet payload ``{"snapshot": ...,
    "spans": [...]}`` or a bare registry snapshot (the PR 9 json fmt) —
    normalize to (snapshot, spans, dropped_spans)."""
    if isinstance(payload, dict) and "snapshot" in payload \
            and "counters" not in payload:
        return (payload["snapshot"], payload.get("spans") or [],
                payload.get("dropped_spans"))
    return payload, [], None


def _rank_step_ms(snap):
    """A rank's ``train.step_ms`` view: the fixed-edge histogram's mean
    (sum/count — exact, and what the merge preserves); None before the
    first committed step."""
    h = (snap.get("histograms") or {}).get("train.step_ms")
    if h and h.get("count"):
        return h["sum"] / h["count"]
    return (snap.get("gauges") or {}).get("train.step_ms")


def _rank_epoch(snap):
    v = (snap.get("gauges") or {}).get("elastic.epoch")
    if v is None:
        v = (snap.get("context") or {}).get("epoch")
    return v


class FleetCollector(EdgeRuleEngine):
    """The aggregation plane: scrape every rank, merge exactly, analyze
    skew, fire the fleet watchdog rules.

    ``transports`` is {rank: callable() -> scrape payload}; the
    callable raises on a dead endpoint (that IS the ``scrape_dead``
    signal).  ``now`` is the scrape/pacing clock (``time.time`` unless
    injected — FakeClock in tests and chaos, zero sleeps)."""

    _PREFIX = "fleet"

    def __init__(self, transports, now=None, skew=None, scrape_s=None):
        super().__init__()
        self._transports = dict(transports)
        self._now = now if now is not None else time.time
        self.skew = float(skew) if skew is not None else default_skew()
        self.scrape_s = float(scrape_s) if scrape_s is not None \
            else default_scrape_s()
        self._last_scrape_t = None   # poll() cadence (collector thread)
        self._stop = None            # threading.Event while started
        self.last = None             # newest fleet snapshot

    # -- scrape ----------------------------------------------------------
    def _scrape(self):
        """One pass over every transport, in rank order.  Per-rank
        result: the payload + round-trip, or a TYPED failure — a dead
        rank must never abort the fleet view."""
        out = {}
        for rank in sorted(self._transports):
            t0 = self._now()
            try:
                payload = self._transports[rank]()
            except Exception as e:  # noqa: BLE001 — typed, not fatal
                out[rank] = {
                    "ok": False,
                    "error": f"{type(e).__name__}: {e}",
                    "scrape_ms": round((self._now() - t0) * 1e3, 3)}
                continue
            t1 = self._now()
            snap, spans, dropped = _normalize_payload(payload)
            remote_t = snap.get("time") if isinstance(snap, dict) else None
            # clock-offset ESTIMATE: remote wall time vs the scrape
            # round-trip midpoint.  Disclosed on the trace lane, never
            # applied to timestamps (docs/OBSERVABILITY.md §Fleet).
            offset = (round(remote_t - (t0 + t1) / 2.0, 6)
                      if isinstance(remote_t, (int, float)) else None)
            sv = snap.get("schema_version") if isinstance(snap, dict) \
                else None
            if not isinstance(snap, dict) or "counters" not in snap:
                out[rank] = {"ok": False, "scrape_ms":
                             round((t1 - t0) * 1e3, 3),
                             "error": "malformed snapshot (no counters)"}
            elif sv != SCHEMA_VERSION:
                # a rank on a different telemetry schema cannot merge
                # deterministically — excluded, disclosed, typed
                out[rank] = {"ok": False, "scrape_ms":
                             round((t1 - t0) * 1e3, 3),
                             "error": f"telemetry schema drift "
                                      f"(rank v{sv} != local "
                                      f"v{SCHEMA_VERSION})"}
            else:
                out[rank] = {"ok": True, "snapshot": snap,
                             "spans": spans, "dropped_spans": dropped,
                             "scrape_ms": round((t1 - t0) * 1e3, 3),
                             "clock_offset_est_s": offset}
        return out

    # -- merge + analysis ------------------------------------------------
    def collect(self):
        """Scrape + merge + analyze + fire rules; returns the fleet
        snapshot.  With ``MXTPU_FLEET=0`` this is inert: no transport
        is called, nothing is emitted (the kill-switch gate)."""
        if not enabled():
            return {"fleet_schema_version": FLEET_SCHEMA_VERSION,
                    "enabled": False}
        scraped = self._scrape()
        alive = [r for r in sorted(scraped) if scraped[r]["ok"]]
        dead = [r for r in sorted(scraped) if not scraped[r]["ok"]]

        counters, gauges, hist_states = {}, {}, {}
        per_rank = {}
        for rank in sorted(scraped):
            info = scraped[rank]
            row = {"ok": info["ok"], "scrape_ms": info["scrape_ms"],
                   "error": info.get("error")}
            if info["ok"]:
                snap = info["snapshot"]
                row["clock_offset_est_s"] = info.get("clock_offset_est_s")
                row["step_ms"] = _rank_step_ms(snap)
                row["epoch"] = _rank_epoch(snap)
                row["events_seen"] = snap.get("events_seen")
                row["spans"] = info.get("spans") or []
                row["dropped_spans"] = info.get("dropped_spans")
                for name, v in (snap.get("counters") or {}).items():
                    counters[name] = counters.get(name, 0) + v
                for name, v in (snap.get("gauges") or {}).items():
                    gauges.setdefault(name, {})[str(rank)] = v
                for name, st in (snap.get("histograms") or {}).items():
                    hist_states.setdefault(name, []).append(st)
            per_rank[str(rank)] = row
        histograms = {name: merge_histograms(sts)
                      for name, sts in hist_states.items()}

        fleet = {"fleet_schema_version": FLEET_SCHEMA_VERSION,
                 "schema_version": SCHEMA_VERSION,
                 "enabled": True,
                 "time": self._now(),
                 "ranks": sorted(scraped),
                 "alive": alive, "dead": dead,
                 "per_rank": per_rank,
                 "counters": counters, "gauges": gauges,
                 "histograms": histograms}
        fleet["scrape_ms"] = round(max(
            (scraped[r]["scrape_ms"] for r in scraped), default=0.0), 3)
        self._analyze(fleet)
        self._publish(fleet)
        self._drain()
        self.last = fleet
        return fleet

    def _analyze(self, fleet):
        """Skew analysis + edge-triggered rule evaluation over the
        freshly merged view.  Rules queue under ``_lock`` and fire in
        :meth:`_drain` (the EdgeRuleEngine discipline)."""
        per_rank = fleet["per_rank"]
        steps = {r: per_rank[str(r)]["step_ms"] for r in fleet["alive"]
                 if per_rank[str(r)].get("step_ms") is not None}
        skew = {"median_step_ms": None, "slowest_rank": None,
                "skew_ratio": None, "straggler_scores": {}}
        if steps:
            vals = sorted(steps.values())
            n = len(vals)
            median = (vals[n // 2] if n % 2 else
                      (vals[n // 2 - 1] + vals[n // 2]) / 2.0)
            skew["median_step_ms"] = round(median, 3)
            slowest = max(sorted(steps), key=lambda r: steps[r])
            skew["slowest_rank"] = slowest
            if median > 0:
                skew["skew_ratio"] = round(steps[slowest] / median, 4)
                skew["straggler_scores"] = {
                    str(r): round(steps[r] / median, 4)
                    for r in sorted(steps)}
        fleet["skew"] = skew

        epochs = {r: per_rank[str(r)]["epoch"] for r in fleet["alive"]
                  if per_rank[str(r)].get("epoch") is not None}
        desynced = []
        if len(epochs) >= 2 and len(set(epochs.values())) > 1:
            newest = max(epochs.values())
            desynced = sorted(r for r, e in epochs.items() if e < newest)
        fleet["epoch_desync"] = ({"epochs": {str(r): epochs[r]
                                             for r in sorted(epochs)},
                                  "laggards": desynced}
                                 if desynced else None)

        with self._lock:
            # stragglers: per-rank edges so TWO slow hosts both get
            # named; needs >= 2 measured ranks (a fleet of one has no
            # median to lag)
            scores = skew["straggler_scores"]
            for r in sorted(steps):
                score = scores.get(str(r))
                firing = (score is not None and len(steps) >= 2
                          and score >= self.skew)
                self._edge(f"straggler:{r}", firing, rule="straggler",
                           rank=r, step_ms=round(steps[r], 3),
                           median_step_ms=skew["median_step_ms"],
                           score=score, threshold=self.skew)
            for r in fleet["ranks"]:
                row = per_rank[str(r)]
                self._edge(f"epoch_desync:{r}",
                           r in desynced, rule="epoch_desync",
                           rank=r, epoch=row.get("epoch"),
                           epochs={str(k): epochs[k]
                                   for k in sorted(epochs)})
                self._edge(f"scrape_dead:{r}", not row["ok"],
                           rule="scrape_dead", rank=r,
                           error=row.get("error"))

    def _publish(self, fleet):
        """Thin-reader seam: the fleet-level analysis lands on the LOCAL
        registry so :func:`fleet_block`'s caller and a live scrape of the
        coordinator read one source (the ISSUE 9 discipline)."""
        from . import enabled as telem_enabled, inc, set_gauge
        if not telem_enabled():
            return
        inc("fleet.scrapes")
        set_gauge("fleet.ranks", len(fleet["ranks"]))
        set_gauge("fleet.ranks_alive", len(fleet["alive"]))
        set_gauge("fleet.scrape_ms", fleet["scrape_ms"])
        skew = fleet["skew"]
        if skew["slowest_rank"] is not None:
            set_gauge("fleet.slowest_rank", skew["slowest_rank"])
        if skew["skew_ratio"] is not None:
            set_gauge("fleet.step_ms_skew", skew["skew_ratio"])

    # -- pacing ----------------------------------------------------------
    def poll(self):
        """Collect when a scrape is due per ``scrape_s``; None when not
        due (or disabled).  The injectable-clock twin of the background
        thread — chaos drives this with a FakeClock, zero sleeps."""
        if not enabled():
            return None
        t = self._now()
        if self._last_scrape_t is not None and \
                t - self._last_scrape_t < self.scrape_s:
            return None
        self._last_scrape_t = t
        return self.collect()

    def start(self):
        """Background scrape loop at ``scrape_s`` (production pacing;
        daemon thread).  No-op when already started or disabled."""
        if self._stop is not None or not enabled():
            return self
        stop = threading.Event()
        self._stop = stop

        def _loop():
            while not stop.is_set():
                try:
                    self.collect()
                except Exception:  # noqa: BLE001 — the scrape loop
                    pass           # must survive any one bad pass
                stop.wait(max(0.05, self.scrape_s))

        threading.Thread(target=_loop, name="mxtpu-fleet-scrape",
                         daemon=True).start()
        return self

    def stop(self):
        if self._stop is not None:
            self._stop.set()
            self._stop = None

    def state(self):
        with self._lock:
            return {"trips": [r for r, _ in self.trips],
                    "tripped": sorted(self._tripped)}


# -- rendering ----------------------------------------------------------

def fleet_prom_snapshot(fleet):
    """A registry-snapshot-shaped view of a fleet snapshot so the PR 9
    :func:`~.prom.prom_text` renderer serves the fleet path unchanged:
    merged counters/histograms pass through; per-rank gauges flatten to
    ``<name>.rank<r>``; the skew analysis lands as gauges."""
    gauges = {}
    for name, per in (fleet.get("gauges") or {}).items():
        for r, v in sorted(per.items()):
            gauges[f"{name}.rank{r}"] = v
    skew = fleet.get("skew") or {}
    for k in ("median_step_ms", "slowest_rank", "skew_ratio"):
        if skew.get(k) is not None:
            gauges[f"fleet.{k}"] = skew[k]
    gauges["fleet.ranks"] = len(fleet.get("ranks") or [])
    gauges["fleet.ranks_alive"] = len(fleet.get("alive") or [])
    return {"enabled": True,
            "schema_version": fleet.get("schema_version"),
            "counters": fleet.get("counters") or {},
            "gauges": gauges,
            "histograms": fleet.get("histograms") or {},
            "context": {}}


def fleet_block(enabled=False, ranks=0, slowest_rank=None,
                step_ms_skew=None, scrape_ms=None, stragglers=None,
                epoch_desync=None, scrape_dead=None):
    """The summary of a :class:`FleetCollector` scrape (the ``comm`` /
    ``serving`` / ``elastic`` block discipline): config is always real;
    MEASURED fields default to ``None`` — null-when-unmeasured, so a
    single-process CPU run can never pass off "no fleet to scrape" as
    "zero skew measured" (the PR 6 honesty rule, gated by
    tests/test_fleet.py)."""
    def _r(x, n=3):
        return None if x is None else round(float(x), n)

    return {
        "fleet_schema_version": FLEET_SCHEMA_VERSION,
        "enabled": bool(enabled),
        "ranks": int(ranks),
        "slowest_rank": None if slowest_rank is None else int(slowest_rank),
        "step_ms_skew": _r(step_ms_skew, 4),
        "scrape_ms": _r(scrape_ms),
        "stragglers": None if stragglers is None else int(stragglers),
        "epoch_desync": None if epoch_desync is None else bool(epoch_desync),
        "scrape_dead": None if scrape_dead is None else int(scrape_dead),
    }
