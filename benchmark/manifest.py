"""What `BENCHMARK.json` and the data files under `benchmark/` say, checked.

Everything that belongs to one configuration, one traffic mix or one layer
metric sits in a file of its own, found by the name that `BENCHMARK.json`
(or the traffic file) gives it.  A later PR adds files and appends entries; it
edits nothing that is here.  No code reads meaning out of a cell's name.
"""
from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# the driver's limits on a name and a unit (builder's contract)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(ValueError):
    """A data file says something the harness or the driver would refuse."""


def _json(path):
    with open(path) as f:
        return json.load(f)


def check_name(what, name):
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(
            f"{what} {name!r}: a name is 1-64 of letters, digits, '_', '.', "
            f"'-' and does not start with '.' or '-'")
    return name


def check_unit(what, unit):
    if not isinstance(unit, str) or not UNIT.match(unit):
        raise ManifestError(
            f"{what}: unit {unit!r} is not 1-16 of letters, digits, '_', "
            f"'/', '%', '.', '-'")
    return unit


def peaks(device_kind, path=None):
    """The published peaks of exactly this ``device_kind``.  A kind that is
    not in the table is an error, never a default."""
    table = _json(path or os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise ManifestError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)}); add its published numbers with "
            f"their source")
    return table[device_kind]


def layer_metric(name, directory=None):
    """One per-layer metric's file: name, unit, layer, moves, better, reader."""
    check_name("layer metric", name)
    path = os.path.join(directory or os.path.join(HERE, "layer_metrics"),
                        name + ".json")
    if not os.path.exists(path):
        raise ManifestError(f"layer metric {name!r}: no file {path}")
    m = _json(path)
    if m.get("name") != name:
        raise ManifestError(f"{path}: 'name' is {m.get('name')!r}")
    check_unit(f"layer metric {name}", m.get("unit"))
    if m.get("better") not in ("lower", "higher"):
        raise ManifestError(f"layer metric {name}: 'better' is "
                            f"{m.get('better')!r}")
    if ":" not in m.get("reader", ""):
        raise ManifestError(f"layer metric {name}: 'reader' must be "
                            f"module:function under benchmark/readers/")
    return m


def reader(metric):
    """The function that reads this metric from spans, counters or the trace."""
    module, func = metric["reader"].split(":")
    return getattr(importlib.import_module("readers." + module), func)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    the layer metrics its traffic file says it reports."""

    def __init__(self, entry, config, traffic, layer_metrics):
        self.name = entry["name"]
        self.chips = entry["chips"]
        self.config = config
        self.traffic = traffic
        self.layer_metrics = layer_metrics


class Manifest:
    def __init__(self, root=ROOT):
        self.root = root
        self.doc = _json(os.path.join(root, "BENCHMARK.json"))
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}

    def traffic(self, name):
        check_name("traffic", name)
        return _json(os.path.join(HERE, "traffic", name + ".json"))

    def config(self, name):
        return _json(os.path.join(self.root, self.configs[name]["file"]))

    def cell(self, name):
        if name not in self.workloads:
            raise ManifestError(f"no workload {name!r} in BENCHMARK.json "
                                f"(has: {sorted(self.workloads)})")
        entry = self.workloads[name]
        traffic = self.traffic(entry["traffic"])
        metrics = []
        for mname in traffic["layer_metrics"]:
            m = layer_metric(mname)
            listed = self.per_layer.get(mname)
            if listed is None:
                raise ManifestError(
                    f"traffic {entry['traffic']}: layer metric {mname!r} is "
                    f"not in BENCHMARK.json's per_layer")
            for key in ("unit", "better", "layer", "moves", "source"):
                if listed[key] != m[key]:
                    raise ManifestError(
                        f"layer metric {mname}: {key} is {m[key]!r} in its "
                        f"file and {listed[key]!r} in BENCHMARK.json")
            if "workloads" in listed and name not in listed["workloads"]:
                raise ManifestError(
                    f"layer metric {mname}: BENCHMARK.json lists it for "
                    f"{listed['workloads']}, and {name} reports it too")
            metrics.append(m)
        for mname, listed in self.per_layer.items():
            cells = listed.get("workloads")
            if (cells is None or name in cells) and \
                    mname not in traffic["layer_metrics"]:
                raise ManifestError(
                    f"BENCHMARK.json says {name} reports {mname}, its "
                    f"traffic file {entry['traffic']} does not")
        return Cell(entry, self.config(entry["config"]), traffic, metrics)

    def validate(self):
        """Every limit the driver refuses a file over, as far as the files
        here can break it."""
        doc = self.doc
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [check_name(group, e["name"]) for e in doc[group]]
            if len(set(names)) != len(names):
                raise ManifestError(f"{group}: a name appears twice")
        for m in doc["end_to_end"] + doc["per_layer"]:
            check_unit(m["name"], m["unit"])
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"{m['name']}: better={m['better']!r}")
        if "setup_s" not in self.end_to_end:
            raise ManifestError("end_to_end has no setup_s")
        for m in doc["per_layer"]:
            if m["moves"] not in self.end_to_end:
                raise ManifestError(f"{m['name']}: moves {m['moves']!r}, "
                                    f"which is no end-to-end metric")
        pairs = set()
        for w in doc["workloads"]:
            check_name("config", w["config"])
            check_name("traffic", w["traffic"])
            if w["chips"] not in (1, 4):
                raise ManifestError(f"{w['name']}: chips={w['chips']}")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"{w['name']}: its pair of configuration "
                                    f"and traffic appears twice")
            pairs.add((w["config"], w["traffic"]))
            self.cell(w["name"])
        return self
