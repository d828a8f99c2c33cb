#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it loads the cell's configuration and traffic files, builds and
warms up (set-up), measures for ``--seconds``, checks the results, and prints
one JSON object as the last line of its standard output.  With ``--trace 0``
the metrics are the end-to-end ones, with ``--trace 1`` the cell's per-layer
ones, read from the program's spans and from a profiler trace of about 16 of
the window's steps.  A platform other than ``tpu``, or fewer devices than the
cell's ``chips``, ends the run with a non-zero code before any work.

``--rehearse`` (never in BENCHMARK.json's command) runs the configuration's
tiny ``rehearsal`` sizes on the CPU backend with the Pallas kernels in the
interpreter: a rehearsal of control flow for the sandbox.  Its line says
``"platform": "cpu"`` and ``"rehearsal": true``; nothing in it is a number.
"""
from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()    # set-up counts from here

import argparse          # noqa: E402
import contextlib        # noqa: E402
import gc                # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import manifest as manifest_mod      # noqa: E402

TRACE_AFTER_STEPS = 6    # steps of the window before the profiler starts
TRACE_STEPS = 16         # steps it records: 16-24 MB of .xplane.pb
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def say(key, value):
    print(f"# {key}: {json.dumps(value)}", flush=True)


class Job:
    """What a runner is given: the cell's sizes and traffic, the seed, the
    devices, and a clock for the phases of its set-up."""

    def __init__(self, cell, seed, devices, rehearse):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.rehearse = rehearse
        self.sizes = dict(cell.config)
        self.traffic = dict(cell.traffic)
        if rehearse:
            self.sizes.update(cell.config["rehearsal"])
            self.traffic.update(cell.traffic["rehearsal"])
        self.phases_s = {}

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases_s[name] = self.phases_s.get(name, 0.0) + \
                time.perf_counter() - t0


class Compiles:
    """Counts the programs JAX builds or loads from its cache."""

    def __init__(self):
        from jax import monitoring
        self.built_or_loaded = self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if event == COMPILE_EVENT:
            self.built_or_loaded += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


class ReadContext:
    """What a layer metric's reader is given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def note(self, key, value):
        say(key, value)


def percentile(values, q):
    """Linear interpolation between closest ranks (numpy's default)."""
    values = sorted(values)
    at = (len(values) - 1) * q / 100.0
    lo = math.floor(at)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (at - lo)


def memory_peak_bytes(devices, rehearse):
    """The fullest chip's ``peak_bytes_in_use + peak_bytes_reserved``: the
    first is the peak of the arrays the process held (parameters, optimizer
    state, batches), the second the peak of what running programs reserved
    for their temporaries (a training step's activations).  Neither alone is
    what the chip had to have free."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        if rehearse:
            return 0, None
        raise RuntimeError("a device gives no memory_stats()")
    return max(((s["peak_bytes_in_use"] + s["peak_bytes_reserved"], s)
                for s in stats), key=lambda pair: pair[0])


def measure(runner, state, seconds, trace_dir):
    """The window.  One step stays in flight: dispatch step i+1, then wait
    for the loss of step i and stamp the clock - what a training loop with
    lagged logging does, and it keeps the device's queue fed.  The window
    closes when the last dispatched step's loss is on the host."""
    import jax
    if trace_dir:
        annotate = jax.profiler.TraceAnnotation
    else:
        def annotate(_name):
            return contextlib.nullcontext()
    clock = time.perf_counter
    stamps, losses, raised = [], [], []
    host_ms, collections = [], []
    gc_began = []

    def on_gc(phase, info):
        if phase == "start":
            gc_began.append(clock())
        else:
            collections.append([info["generation"], len(stamps),
                                (clock() - gc_began.pop()) * 1e3])
    gc.callbacks.append(on_gc)
    tracing = False
    t_start = clock()
    pending = runner.step(state, 0)
    dispatched = 1
    try:
        while True:
            if trace_dir and dispatched == TRACE_AFTER_STEPS:
                jax.profiler.start_trace(trace_dir)
                tracing = True
            t0, cpu0 = clock(), time.thread_time()
            with annotate("bench.dispatch"):
                following = runner.step(state, dispatched)
            dispatched += 1
            t1 = clock()
            with annotate("bench.wait"):
                losses.append(pending())
            stamps.append(clock())
            host_ms.append([(t1 - t0) * 1e3, (stamps[-1] - t1) * 1e3,
                            (time.thread_time() - cpu0) * 1e3])
            pending = following
            if tracing and dispatched >= TRACE_AFTER_STEPS + TRACE_STEPS + 1:
                jax.profiler.stop_trace()
                tracing = False
            if stamps[-1] - t_start >= seconds:
                break
        t1, cpu0 = clock(), time.thread_time()
        losses.append(pending())
        stamps.append(clock())
        host_ms.append([0.0, (stamps[-1] - t1) * 1e3,
                        (time.thread_time() - cpu0) * 1e3])
    except Exception as e:  # noqa: BLE001 - a step that raises is a failed
        raised.append(repr(e))      # step, reported; the run goes on to print
    finally:
        gc.callbacks.remove(on_gc)
        if tracing:
            jax.profiler.stop_trace()
    return dict(t_start=t_start, t_end=stamps[-1] if stamps else clock(),
                dispatched=dispatched, stamps=stamps, losses=losses,
                raised=raised, host_ms=host_ms, collections=collections)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend: a rehearsal of "
                         "control flow, not a measurement")
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="also write the reduced trace (intervals) here")
    args = ap.parse_args(argv)

    man = manifest_mod.Manifest(ROOT).validate()
    cell = man.cell(args.workload)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            print("run.py: --rehearse is for the CPU backend "
                  "(JAX_PLATFORMS=cpu)", file=sys.stderr)
            return 2
    elif platform != "tpu":
        print(f"run.py: JAX found no TPU (first device is {platform!r}); "
              f"a benchmark run needs the chip", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    t_backend = time.perf_counter()

    # every program, however small or quick to compile, goes to the cache:
    # initialize() compiles ~200 one-op programs that JAX's default minimum
    # compile time (1 s) would keep out of it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from mxnet_tpu import runtime
    from mxnet_tpu.ops.kernel_mode import interpret_kernels
    from mxnet_tpu.telemetry import tracing as program_spans
    cache_dir = runtime.enable_compile_cache()
    compiles = Compiles()

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peaks = None if args.rehearse else manifest_mod.peaks(device["kind"])
    say("cell", {"name": cell.name, "chips": cell.chips, "seed": args.seed,
                 "seconds": args.seconds, "trace": args.trace,
                 "rehearsal": args.rehearse})
    say("device", device)
    say("compile_cache", cache_dir)

    job = Job(cell, args.seed, devices, args.rehearse)
    runner = importlib.import_module("runners." + job.traffic["runner"])
    trace_dir = tempfile.mkdtemp(prefix="mxtpu_bench_trace_") \
        if args.trace else None
    try:
        with interpret_kernels() if args.rehearse \
                else contextlib.nullcontext():
            state = runner.prepare(job)
            with job.phase("warmup_steps"):
                for i in range(job.traffic["warmup_steps"]):
                    runner.step(state, i)()
            program_spans.reset()
            # what set-up left on the heap (traced programs, HLO, the
            # model's blocks) is collected once and then set aside, so that
            # the one full collection Python would otherwise start somewhere
            # in the window does not walk it all: on the chip that was one
            # stall of 0.2-1.7 s in one run out of four
            with job.phase("gc_collect_and_freeze"):
                gc.collect()
                gc.freeze()
            before = compiles.built_or_loaded
            setup_s = time.perf_counter() - _T_PROCESS
            win = measure(runner, state, args.seconds, trace_dir)
            in_window = compiles.built_or_loaded - before
            spans = program_spans.spans()
            peak_bytes, peak_stats = memory_peak_bytes(devices,
                                                       args.rehearse)
            checks = runner.finish(state)
        trace = None
        if trace_dir:
            found = [os.path.join(d, f) for d, _, files in os.walk(trace_dir)
                     for f in files if f.endswith(".xplane.pb")]
            if found:
                from trace import Trace
                say("trace_file_bytes", os.path.getsize(found[0]))
                trace = Trace.from_xplane(found[0])
                if args.keep_trace:
                    trace.dump(args.keep_trace)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    job.phases_s["start_to_backend"] = t_backend - _T_PROCESS
    say("setup", {"setup_s": setup_s, "phases_s": job.phases_s,
                  "programs_built_or_loaded": before,
                  "of_which_cache_hits": compiles.cache_hits})

    stamps, losses = win["stamps"], win["losses"]
    window_s = win["t_end"] - win["t_start"]
    completed = len(stamps)
    intervals = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    step_p95 = percentile(intervals, 95) if intervals else None
    failed = len(win["raised"]) + sum(not math.isfinite(x) for x in losses)
    say("window", {
        "seconds": window_s, "steps": completed,
        "global_batch": state.global_batch,
        "step_ms_median": percentile(intervals, 50) if intervals else None,
        "step_ms_p95": step_p95,
        "step_ms_max": max(intervals) if intervals else None,
        "intervals": len(intervals),
        # [step, interval, of which in dispatch, in wait, thread CPU], ms
        "slowest": [[i + 1, intervals[i]] + win["host_ms"][i + 1]
                    for i in sorted(range(len(intervals)),
                                    key=lambda i: -intervals[i])[:3]
                    if i + 1 < len(win["host_ms"])],
        # [generation, step, ms] of full or slow garbage collections
        "gc": [c for c in win["collections"] if c[0] == 2 or c[2] > 5],
        "programs_built_or_loaded_in_window": in_window,
        "raised": win["raised"],
        "spans_dropped": program_spans.dropped()})
    say("losses", {"first": losses[:4], "last": losses[-4:]})

    checks["every_loss_finite"] = (
        bool(losses) and all(math.isfinite(x) for x in losses),
        f"{len(losses)} losses")
    checks["loss_moved"] = (len(set(losses)) > 1,
                            f"{len(set(losses))} distinct values")
    checks["no_compile_in_window"] = (
        in_window == 0, f"{in_window} programs built or loaded in the window")
    checks["no_step_raised"] = (not win["raised"], "; ".join(win["raised"]))
    for name, (ok, detail) in checks.items():
        say("check." + name, {"ok": ok, "detail": detail})
    correct = all(ok for ok, _ in checks.values())
    say("memory", {"memory_peak_bytes": peak_bytes,
                   "fullest_chip_stats": peak_stats})

    end_to_end = {
        "samples_per_s": completed * state.global_batch / window_s,
        "step_p95_ms": step_p95,
        "hbm_peak_gb": peak_bytes / 1e9,
        "setup_s": setup_s,
    }
    metrics = {}
    if not args.trace:
        for name, value in end_to_end.items():
            if value is not None:
                metrics[name] = {"value": value,
                                 "unit": man.end_to_end[name]["unit"]}
    else:
        say("end_to_end_of_traced_run", end_to_end)
        ctx = ReadContext(
            trace=trace, spans=spans, t_start=win["t_start"],
            t_end=win["t_end"], sizes=job.sizes, traffic=job.traffic,
            peaks=peaks, device_ids=[d.id for d in devices],
            global_batch=state.global_batch,
            flops_per_sample=state.flops_per_sample)
        for m in cell.layer_metrics:
            value = manifest_mod.reader(m)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace is not None:
            d0 = devices[0].id
            got = [trace.busy(d.id) for d in devices]
            got = [g for g in got if g is not None]
            if got:
                device["busy_s"] = sum(g[0] for g in got) / len(got) / 1e9
                device["window_s"] = got[0][1] / 1e9
                say("trace", {"steps_in_window": got[0][2],
                              "lines": {str(d): sorted(lines) for d, lines
                                        in trace.devices.items()},
                              "host_annotations": len(trace.host)})
    device["memory_peak_bytes"] = peak_bytes
    result = {"correct": correct, "attempted": win["dispatched"],
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and trace is not None and trace.window(d0) is not None:
        result["breakdown"] = {"device_ops": trace.top_ops(d0),
                               "idle_gaps": trace.idle_gaps(d0)}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
