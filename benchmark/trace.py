"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to
numbers.  The only reader of a device trace in the benchmark; the layer
metrics under ``readers/`` call into it.

A :class:`Trace` holds, for each traced device, the events of each of its
lines (``XLA Modules``, ``XLA Ops``, ``Async XLA Ops``, ...) as
``(name, start_ns, duration_ns)``, the benchmark's own host annotations
(``bench.*``) on the same clock, and for each operation a short label made
from its HLO line (which is what the profiler names a device event by).  ``XLA Ops`` is what ran on the core; ``Async XLA Ops``
holds the copies and collectives that run beside it.

All arithmetic is on lists of ``(start, end)`` pairs and is checked in
``tests/test_trace.py`` on hand-made intervals and on a recorded trace.
"""
from __future__ import annotations

import gzip
import json
import re

OPS, ASYNC_OPS, MODULES = "XLA Ops", "Async XLA Ops", "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)")


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals):
    return sum(end - start for start, end in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of ``a`` that no interval of ``b`` covers (both any order)."""
    out, b = [], union(b)
    for start, end in union(a):
        at = start
        for bs, be in b:
            if be <= at:
                continue
            if bs >= end:
                break
            if bs > at:
                out.append((at, bs))
            at = max(at, be)
            if at >= end:
                break
        if at < end:
            out.append((at, end))
    return out


def spans(events):
    return [(start, start + dur) for _, start, dur in events]


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

class Trace:
    def __init__(self, devices, host, labels):
        self.devices = devices      # {index: {line: [(name, start, dur)]}}
        self.host = host            # [(name, start, dur)], bench.* only
        self.labels = labels        # {op name: "name [kind dtype[shape]]"}

    # -- where it comes from -------------------------------------------------
    @classmethod
    def from_xplane(cls, path):
        from jax.profiler import ProfileData
        devices, host, labels, seen = {}, [], {}, {}
        for plane in ProfileData.from_file(path).planes:
            m = _DEVICE.match(plane.name)
            if m:
                lines = devices.setdefault(int(m.group(1)), {})
                for line in plane.lines:
                    events = lines.setdefault(line.name, [])
                    for ev in line.events:
                        name = seen.get(ev.name)
                        if name is None:
                            name, hlo = split_hlo(ev.name)
                            seen[ev.name] = name
                            if hlo:
                                labels[name] = short_form(name, hlo)
                        events.append((name, int(ev.start_ns),
                                       int(ev.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            host.append((ev.name, int(ev.start_ns),
                                         int(ev.duration_ns)))
        for lines in devices.values():
            for events in lines.values():
                events.sort(key=lambda e: e[1])
        host.sort(key=lambda e: e[1])
        return cls(devices, host, labels)

    def to_json(self):
        return {"devices": {str(d): lines for d, lines in self.devices.items()},
                "host": self.host, "labels": self.labels}

    @classmethod
    def from_json(cls, doc):
        devices = {int(d): {line: [tuple(e) for e in events]
                            for line, events in lines.items()}
                   for d, lines in doc["devices"].items()}
        return cls(devices, [tuple(e) for e in doc["host"]], doc["labels"])

    def dump(self, path):
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path):
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    # -- what it says ---------------------------------------------------------
    def line(self, device, name):
        return self.devices.get(device, {}).get(name, [])

    def window(self, device):
        """``(start, end, steps)``: the executions of the step program on
        this device that the trace holds - the program that took most of the
        time; a step also runs a few tiny ones (key split, type conversion)
        - leaving out the first and the last, which the trace may have
        cut."""
        modules = self.line(device, MODULES)
        spent = {}
        for name, _, dur in modules:
            spent[name] = spent.get(name, 0) + dur
        modules = [m for m in modules
                   if m[0] == max(spent, key=spent.get)] if spent else []
        if len(modules) >= 4:
            modules = modules[1:-1]
        if not modules:
            return None
        return (modules[0][1], modules[-1][1] + modules[-1][2], len(modules))

    def busy(self, device):
        """``(busy_ns, window_ns, steps)``: the union of the intervals in
        which an operation ran on this device's core, inside its window."""
        w = self.window(device)
        if w is None:
            return None
        lo, hi, steps = w
        ran = union(clip(spans(self.line(device, OPS)), lo, hi))
        return total(ran), hi - lo, steps

    def op_time(self, device, pattern):
        """``(ns, calls)`` of the core operations in the window whose name
        carries ``pattern``."""
        lo, hi, _ = self.window(device)
        ns = calls = 0
        for name, start, dur in self.line(device, OPS):
            if start >= lo and start + dur <= hi and pattern in name:
                ns += dur
                calls += 1
        return ns, calls

    def exposed_collective(self, device):
        """``(exposed_ns, collective_ns)`` in the window: the union of the
        collectives' intervals (on the core's line or beside it), and the part
        of it during which no other operation ran on the core."""
        lo, hi, _ = self.window(device)
        ops = self.line(device, OPS)
        coll = [e for e in ops + self.line(device, ASYNC_OPS)
                if COLLECTIVE.match(e[0])]
        other = [e for e in ops if not COLLECTIVE.match(e[0])]
        coll = union(clip(spans(coll), lo, hi))
        return total(subtract(coll, clip(spans(other), lo, hi))), total(coll)

    def top_ops(self, device, n=10):
        """The ``n`` core operations that took most time in the window, as
        ``[short form, seconds]``, summed over the window's steps."""
        lo, hi, _ = self.window(device)
        by_name = {}
        for name, start, dur in self.line(device, OPS):
            if start >= lo and start + dur <= hi:
                by_name[name] = by_name.get(name, 0) + dur
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[self.labels.get(name, name), ns / 1e9]
                for name, ns in ranked]

    def idle_gaps(self, device, n=10):
        """The core's idle time in the window by what the benchmark's host
        thread was doing in it (its ``bench.*`` annotations, on the same
        clock); what no annotation covers is ``unattributed``."""
        lo, hi, _ = self.window(device)
        gaps = subtract([(lo, hi)], spans(self.line(device, OPS)))
        by_name, covered = {}, []
        for name, start, dur in self.host:
            part = total(clip(gaps, start, start + dur))
            if part:
                by_name[name] = by_name.get(name, 0) + part
                covered.append((start, start + dur))
        rest = total(subtract(gaps, covered))
        if rest:
            by_name["unattributed"] = rest
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]


_HLO = re.compile(r"^%?(\S+) = (.*)$", re.S)


def split_hlo(event_name):
    """``(name, rest)`` of a device event's name, which is its HLO line:
    ``%fusion.3 = bf16[...]{...} fusion(...), kind=kLoop``."""
    m = _HLO.match(event_name)
    return (m.group(1), m.group(2)) if m else (event_name.lstrip("%"), "")


_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_KIND = re.compile(r"(?:^|[\s)])([a-z][a-z\-]*)\(")
_FUSION_KIND = re.compile(r"kind=k(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_form(name, hlo):
    """``name [kind dtype[shape]]`` from the rest of an HLO line such as
    ``(bf16[256]{0}, bf16[256,256,56,56]{...}) fusion(...), kind=kOutput``:
    what kind of operation, and the largest of its results without its
    layout."""
    if not hlo:
        return name
    plain = re.sub(r"\{[^}]*\}", "", hlo)
    kind = _KIND.search(plain)
    results = _SHAPE.findall(plain[:kind.start(1)] if kind else plain)
    label = kind.group(1) if kind else "op"
    fusion = _FUSION_KIND.search(hlo)
    if fusion:
        label += ":" + fusion.group(1)
    target = _TARGET.search(hlo)
    if target:
        label += ":" + target.group(1)

    def elements(shape):
        n = 1
        for dim in filter(None, shape[1].split(",")):
            n *= int(dim)
        return n
    if not results:
        return f"{name} [{label} ?]"
    dtype, dims = max(results, key=elements)
    return f"{name} [{label} {dtype}[{dims}]]"
