"""Compare bench.py JSON payloads across rounds — the perf GATE.

The repo accumulates per-round bench evidence (``BENCH_r0*.json``
trajectory files written by the driver, ``.bench_full.json`` written by
every ``bench.py`` run) but until ISSUE 11 nothing READ them: a
regression only surfaced when a human eyeballed two JSON blobs.  This
tool diffs two payloads metric-by-metric, direction-aware, and can gate
a run:

    python tools/bench_diff.py BENCH_r04.json BENCH_r05.json
    python tools/bench_diff.py old.json new.json --fail-on-regression 10

Rules of the diff (the PR 6 honesty discipline applies):

- null-when-unmeasured fields are SKIPPED, never treated as 0 — a CPU
  fallback round cannot fake a regression (or an improvement) on a
  TPU-only metric;
- ``telemetry_schema_version`` is checked first: payloads from
  different schemas do not compare (exit 2) unless
  ``--allow-schema-drift``; the bench ``fleet`` block's
  ``fleet_schema_version`` (ISSUE 15) and the ``lint`` block's
  ``lint_schema_version`` (ISSUE 16) are checked the same way;
- direction comes from the metric name (``*_ms``/latency: lower is
  better; throughput/efficiency/MFU: higher is better); metrics with
  unknown direction are reported informationally and never gate;
- both platforms must match (a cpu-vs-tpu pair compares apples to
  oranges; informational only, exit 0, unless --force).

``--fail-on-regression <pct>`` exits 1 when any direction-aware metric
got worse by more than ``pct`` percent.
The last stdout line is always ``BENCHDIFF {...json...}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# metric-name suffix -> direction ("up" = higher is better)
_UP_SUFFIXES = ("value", "mfu", "tflops_delivered", "samples_s",
                "img_s", "img_s_overlapped", "tokens_per_sec", "tok_s",
                "tokens_s_chip", "gb_s", "efficiency", "overlap_frac",
                "overlap_efficiency", "speedup", "per_key_speedup",
                "occupancy", "vs_baseline", "weak_scaling_efficiency",
                "projected_efficiency", "proj_eff_8", "proj_eff_256",
                "tokens_per_step_ratio", "tokens_per_dispatch",
                "spec_accept_rate", "kv_capacity_ratio",
                "quant_train_mfu")
_DOWN_SUFFIXES = ("_ms", "p99", "p50", "ttft", "bubble_frac",
                  "pp_bubble_frac", "exposed_ms", "kv_decode_drift")
# config/provenance keys: never compared (a changed knob is not a perf
# regression; the human reads those out of the payload directly)
_SKIP_KEYS = {"telemetry_schema_version", "fleet_schema_version",
              "lint_schema_version", "multiproc_schema_version",
              "batch", "dtype", "data",
              "steps_per_call", "s2d_stem", "n", "rc", "cmd", "tail",
              "time", "cached_at", "dp", "buckets", "epoch",
              "membership_epoch", "transitions", "ranks",
              "slowest_rank", "tp_shards",
              "procs", "world_size", "rpc_retries", "rpc_timeout_s",
              "quant_schema_version", "compute_dtype", "kv_dtype"}


def direction(key):
    leaf = key.rsplit(".", 1)[-1]
    for s in _DOWN_SUFFIXES:
        if leaf.endswith(s):
            return "down"
    for s in _UP_SUFFIXES:
        if leaf == s or leaf.endswith("_" + s) or leaf.endswith(s):
            return "up"
    return None


def load_payload(path):
    """A bench payload: either a raw bench.py JSON, or a driver
    ``BENCH_r*.json`` wrapper (``{"n", "cmd", "rc", "parsed": {...}}``)
    whose ``parsed`` field carries the payload (None when that round's
    line did not parse — nothing to compare)."""
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    if isinstance(d, dict) and "parsed" in d and "cmd" in d:
        return d.get("parsed")
    return d


def flatten(d, prefix="", out=None):
    """Scalar numeric leaves as {dotted.path: float}; nulls, bools,
    strings and config keys dropped."""
    if out is None:
        out = {}
    if not isinstance(d, dict):
        return out
    for k, v in d.items():
        if k in _SKIP_KEYS:
            continue
        path = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, dict):
            flatten(v, path, out)
        elif isinstance(v, list):
            for i, item in enumerate(v):
                if isinstance(item, dict):
                    flatten(item, f"{path}[{i}]", out)
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[path] = float(v)
        # None / str / bool: skipped (null-when-unmeasured honesty)
    return out


def diff_payloads(old, new, threshold_pct):
    """Compare shared measured metrics; returns (rows, regressions)."""
    fo, fn = flatten(old), flatten(new)
    rows = []
    regressions = []
    for key in sorted(set(fo) & set(fn)):
        a, b = fo[key], fn[key]
        d = direction(key)
        if a == 0:
            pct = None if b == 0 else float("inf")
        else:
            pct = (b - a) / abs(a) * 100.0
        worse = None
        if d == "up" and pct is not None:
            worse = -pct
        elif d == "down" and pct is not None:
            worse = pct
        row = {"metric": key, "old": a, "new": b,
               "change_pct": None if pct in (None, float("inf"))
               else round(pct, 2),
               "direction": d}
        if worse is not None and worse > threshold_pct:
            row["regression"] = True
            regressions.append(row)
        rows.append(row)
    return rows, regressions


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="diff two bench.py JSON payloads, direction-aware")
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--fail-on-regression", type=float, default=None,
                    metavar="PCT",
                    help="exit 1 when any metric is worse by > PCT%%")
    ap.add_argument("--allow-schema-drift", action="store_true",
                    help="compare across telemetry_schema_version drift")
    ap.add_argument("--force", action="store_true",
                    help="gate even when the platforms differ")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    old, new = load_payload(args.old), load_payload(args.new)
    verdict = {"old": os.path.basename(args.old),
               "new": os.path.basename(args.new)}
    if not isinstance(old, dict) or not isinstance(new, dict):
        verdict.update(status="unparsed",
                       note="one side carries no parsed payload — "
                            "nothing to compare")
        print("BENCHDIFF " + json.dumps(verdict))
        return 0

    vo = old.get("telemetry_schema_version")
    vn = new.get("telemetry_schema_version")
    if vo is not None and vn is not None and vo != vn \
            and not args.allow_schema_drift:
        verdict.update(status="schema_drift", old_schema=vo,
                       new_schema=vn)
        print("BENCHDIFF " + json.dumps(verdict))
        return 2

    # the fleet snapshot schema is versioned the same way (ISSUE 15):
    # payloads whose `fleet` blocks come from different schemas do not
    # compare
    fvo = ((old.get("extra") or {}).get("fleet")
           or {}).get("fleet_schema_version")
    fvn = ((new.get("extra") or {}).get("fleet")
           or {}).get("fleet_schema_version")
    if fvo is not None and fvn is not None and fvo != fvn \
            and not args.allow_schema_drift:
        verdict.update(status="fleet_schema_drift", old_schema=fvo,
                       new_schema=fvn)
        print("BENCHDIFF " + json.dumps(verdict))
        return 2

    # the lint block (ISSUE 16) is versioned the same way: its counts
    # (rules_enabled, findings, suppressions) only compare within one
    # schema
    lvo = ((old.get("extra") or {}).get("lint")
           or {}).get("lint_schema_version")
    lvn = ((new.get("extra") or {}).get("lint")
           or {}).get("lint_schema_version")
    if lvo is not None and lvn is not None and lvo != lvn \
            and not args.allow_schema_drift:
        verdict.update(status="lint_schema_drift", old_schema=lvo,
                       new_schema=lvn)
        print("BENCHDIFF " + json.dumps(verdict))
        return 2

    # the multiproc block (ISSUE 19) is versioned the same way: its
    # recovery costs (coordinator_reinit_ms, sigkill_recover_ms) only
    # compare within one schema
    mvo = ((old.get("extra") or {}).get("multiproc")
           or {}).get("multiproc_schema_version")
    mvn = ((new.get("extra") or {}).get("multiproc")
           or {}).get("multiproc_schema_version")
    if mvo is not None and mvn is not None and mvo != mvn \
            and not args.allow_schema_drift:
        verdict.update(status="multiproc_schema_drift", old_schema=mvo,
                       new_schema=mvn)
        print("BENCHDIFF " + json.dumps(verdict))
        return 2

    # the quant block (ISSUE 20) is versioned the same way: its
    # capacity/drift fields only compare within one schema
    qvo = ((old.get("extra") or {}).get("quant")
           or {}).get("quant_schema_version")
    qvn = ((new.get("extra") or {}).get("quant")
           or {}).get("quant_schema_version")
    if qvo is not None and qvn is not None and qvo != qvn \
            and not args.allow_schema_drift:
        verdict.update(status="quant_schema_drift", old_schema=qvo,
                       new_schema=qvn)
        print("BENCHDIFF " + json.dumps(verdict))
        return 2

    po, pn = old.get("platform"), new.get("platform")
    gate = args.fail_on_regression is not None
    if po != pn and not args.force:
        # cpu vs tpu rounds: informational only — gating a CPU round
        # against TPU numbers would "detect" a 90% regression that is
        # really a different machine
        gate = False
        verdict["platform_mismatch"] = [po, pn]

    threshold = args.fail_on_regression if args.fail_on_regression \
        is not None else 10.0
    rows, regressions = diff_payloads(old, new, threshold)
    if not args.quiet:
        for r in rows:
            mark = " REGRESSION" if r.get("regression") else ""
            d = {"up": "^", "down": "v", None: "?"}[r["direction"]]
            print(f"{r['metric']:58s} {d} {r['old']:>12.4g} -> "
                  f"{r['new']:>12.4g}  {r['change_pct']}%{mark}")
    verdict.update(status="ok" if not regressions else "regression",
                   compared=len(rows),
                   regressions=[{k: r[k] for k in
                                 ("metric", "old", "new", "change_pct")}
                                for r in regressions],
                   threshold_pct=threshold, gated=bool(gate))
    print("BENCHDIFF " + json.dumps(verdict))
    return 1 if (gate and regressions) else 0


if __name__ == "__main__":
    sys.exit(main())
