"""``readers/io.py`` on hand-made span lists and hand-made idle gaps: a wait
that covers the device's gap, one that half covers it, none at all; the
worker's traces beside them, which must not be laid over the device; and a
program without the spans, which must give nothing.  Then the cell itself:
found by name, and rehearsed end to end on the CPU."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import manifest                         # noqa: E402
import trace as tr                      # noqa: E402
from readers import device, host, io    # noqa: E402
from readers.spans import WALL_STAMP    # noqa: E402

CELL = "resnet50-fused-b256-rec"
METRICS = ["train.host_ms", "device.idle_pct", "device.mfu_pct",
           "io.wait_ms", "io.fetch_ms", "io.augment_ms", "io.h2d_ms",
           "io.idle_in_wait_pct"]
SESSION = 1_790_000_000_000_000_000     # the profiler's start, wall clock ns
US = 1_000
WORKER = "mxtpu-device-prefetch"


class _Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


def _span(name, t0_us, t1_us, span, parent=None, trace=None,
          thread="MainThread", **args):
    return {"name": name, "trace": trace or parent or span, "span": span,
            "parent": parent, "t0": t0_us / 1e6, "t1": t1_us / 1e6,
            "t0_ns": SESSION + t0_us * US, "t1_ns": SESSION + t1_us * US,
            "thread": thread, "args": args}


def _step(k, wait_from=10):
    """Step ``k`` of 1000 us.  The consumer waits for its batch from
    ``wait_from`` to 610 (``None``: the batch was queued, no wait), is inside
    ``train.step`` 615-675, and the device runs that step 680-980: it idles
    from 980 of the step before to 680.  The worker makes the batch 0-900:
    fetch 50, augment 600, stage 200, h2d 50."""
    o, n = k * 1000, 20 * k
    wait = (o + 610, o + 610, 1) if wait_from is None \
        else (o + wait_from, o + 610, 0)
    s = [_span("io.wait", wait[0], wait[1], n + 1, batch=k, queued=wait[2]),
         _span("train.step", o + 615, o + 675, n + 2, step=k, batch=k),
         _span("train.phase.dispatch", o + 620, o + 670, n + 3,
               parent=n + 2),
         _span("io.batch", o, o + 900, n + 4, thread=WORKER, batch=k + 1),
         _span("io.decode", o, o + 850, n + 5, parent=n + 4, trace=n + 4,
               thread=WORKER),
         _span("io.rec.fetch", o, o + 50, n + 6, parent=n + 5, trace=n + 4,
               thread=WORKER, native=True, decoded=256, busy_ns=120_000,
               full_ns=30_000),
         _span("io.rec.augment", o + 50, o + 650, n + 7, parent=n + 5,
               trace=n + 4, thread=WORKER, dtype="float64", bytes=800),
         _span("io.rec.stage", o + 650, o + 850, n + 8, parent=n + 5,
               trace=n + 4, thread=WORKER, dtype="float32", bytes=404,
               device="tpu"),
         _span("io.h2d", o + 850, o + 900, n + 9, parent=n + 4, trace=n + 4,
               thread=WORKER, bytes=404)]
    annotations = [
        ("bench.dispatch", (o + 5) * US, 675 * US),
        (WALL_STAMP + str(SESSION + (o + 6) * US), (o + 6) * US, 1 * US),
        ("bench.wait", (o + 681) * US, 300 * US)]
    modules = [("jit_train_step", (o + 680) * US, 300 * US)]
    ops = [("fusion.1", (o + 680) * US, 300 * US)]
    return s, annotations, modules, ops


def _run(steps=6, waits=None):
    spans, annotations, modules, ops = [], [], [], []
    for k in range(steps):
        s, a, m, o = _step(k, **({"wait_from": waits[k]}
                                 if waits and k in waits else {}))
        spans += s
        annotations += a
        modules += m
        ops += o
    trace = tr.Trace({0: {tr.MODULES: modules, tr.OPS: ops}}, annotations,
                     {})
    return _Ctx(trace=trace, spans=spans, device_ids=[0], t_start=0.0,
                t_end=steps * 1000 / 1e6)


def test_host_metrics_are_means_of_the_spans_in_the_window():
    ctx = _run()
    assert io.wait_ms(ctx) == pytest.approx(0.600)
    assert io.fetch_ms(ctx) == pytest.approx(0.050)
    assert io.augment_ms(ctx) == pytest.approx(0.600)
    assert io.h2d_ms(ctx) == pytest.approx(0.200 + 0.050)
    assert host.train_step_ms(ctx) == pytest.approx(0.060)
    # the three stages beside the batch's mean, the worker's period and the
    # median step: a reader of the run sees whether they close
    period = ctx.notes["io.period"]
    assert period["fetch_ms"] + period["augment_ms"] + period["stage_ms"] \
        + period["h2d_ms"] == pytest.approx(period["batch_ms"])
    assert period["decode_ms"] == pytest.approx(0.850)
    assert period["worker_period_ms_median"] == pytest.approx(1.0)
    assert period["step_ms_median"] == pytest.approx(1.0)
    assert (period["batches"], period["steps"], period["waits"]) == (6, 6, 6)
    assert period["waits_that_found_the_queue_empty"] == 6
    assert period["pool_busy_ms_per_fetch"] == pytest.approx(0.120)
    assert period["pool_before_full_queue_ms_per_fetch"] == \
        pytest.approx(0.030)
    assert period["native"] is True
    assert period["train_phase_ms"]["dispatch"] == pytest.approx(0.050)
    assert period["train_phase_ms"]["h2d"] is None
    assert ctx.notes["io.bytes"]["stage"]["bytes"] == 404
    assert ctx.notes["io.bytes"]["augment_out"]["dtype"] == "float64"
    # a window that cuts the last step: its wait lies inside, its train.step
    # does not, so six waits are shared among five steps
    ctx.t_end = (5 * 1000 + 650) / 1e6
    assert io.wait_ms(ctx) == pytest.approx(0.600 * 6 / 5)
    assert io.augment_ms(ctx) == pytest.approx(0.600)


def test_a_wait_that_covers_the_gap_one_that_half_covers_it_and_none():
    # the traced window: steps 1-4's modules, 1680 to 4980 us, holding the
    # idle stretches 1980-2680, 2980-3680, 3980-4680 (700 us each)
    ctx = _run()
    assert ctx.trace.window(0) == (1680 * US, 4980 * US, 4)
    window = 3300
    # covered: of each 700 the wait holds 600 (10-610), train.step 60
    # (615-675), and 40 lie before, between and after them
    assert io.idle_in_wait_pct(ctx) == pytest.approx(100 * 3 * 600 / window)
    table = ctx.notes["idle_by_span"]["idle_pct_of_window"]
    assert set(table) == {"io.wait", "train.step", "uncovered"}
    assert table["train.step"] == pytest.approx(100 * 3 * 60 / window)
    assert table["uncovered"] == pytest.approx(100 * 3 * 40 / window)
    assert sum(table.values()) == pytest.approx(device.idle_pct(ctx))
    assert ctx.notes["clock"]["aligned"]
    assert ctx.notes["clock"]["largest_overhang_us"] == 0
    # step 3 waits from 310 only (half the gap), step 4 not at all
    ctx = _run(waits={3: 310, 4: None})
    assert io.idle_in_wait_pct(ctx) == \
        pytest.approx(100 * (600 + 300 + 0) / window)
    table = ctx.notes["idle_by_span"]["idle_pct_of_window"]
    assert table["uncovered"] == \
        pytest.approx(100 * (3 * 40 + 300 + 600) / window)
    assert sum(table.values()) == pytest.approx(device.idle_pct(ctx))
    # no wait in the whole window: the metric reads zero, not nothing
    ctx = _run(waits={k: None for k in range(6)})
    assert io.idle_in_wait_pct(ctx) == 0.0
    assert io.wait_ms(ctx) == 0.0


def test_the_workers_traces_are_not_laid_over_the_device():
    """``io.batch`` is a root too, on another thread, beside the consumer's
    spans: counting it would count the idle time twice."""
    ctx = _run()
    table = io._idle_by_span(ctx)
    assert "io.batch" not in table
    assert sum(ns for name, ns in table.items() if name != "window") == \
        3 * 700 * US


def test_an_io_clock_that_does_not_fit_gives_no_number():
    ctx = _run()
    for s in ctx.spans:     # the ns stamps 250 us late against the trace
        s["t0_ns"] += 250 * US
        s["t1_ns"] += 250 * US
    assert io.idle_in_wait_pct(ctx) is None
    assert not ctx.notes["clock"]["aligned"]
    assert "idle_by_span" not in ctx.notes
    assert io.wait_ms(ctx) == pytest.approx(0.600)  # needs no such clock


def test_a_program_without_the_spans_gives_nothing_and_does_not_raise():
    readers = (io.wait_ms, io.fetch_ms, io.augment_ms, io.h2d_ms,
               io.idle_in_wait_pct)
    # the fused cells' program: train.step alone
    ctx = _run()
    ctx.spans = [s for s in ctx.spans if s["name"].startswith("train.")]
    assert [r(ctx) for r in readers] == [None] * 5 and ctx.notes == {}
    # the parent's program under this runner: io.wait, io.decode and io.h2d
    # without numbers, no io.batch, no io.rec.*
    ctx = _run()
    ctx.spans = [dict(s, args={}) for s in ctx.spans
                 if s["name"] in ("train.step", "io.wait", "io.decode",
                                  "io.h2d")]
    assert io.wait_ms(ctx) == pytest.approx(0.600)
    assert [r(ctx) for r in readers[1:4]] == [None] * 3
    assert io.idle_in_wait_pct(ctx) == pytest.approx(100 * 1800 / 3300)
    assert "io.period" not in ctx.notes and "io.bytes" not in ctx.notes
    # an untraced run, a run without a wall-clock stamp, records without ns
    ctx = _run()
    ctx.trace = None
    assert io.idle_in_wait_pct(ctx) is None
    ctx = _run()
    ctx.trace.host = [h for h in ctx.trace.host
                      if not h[0].startswith(WALL_STAMP)]
    assert io.idle_in_wait_pct(ctx) is None and "clock" not in ctx.notes
    ctx = _run()
    for s in ctx.spans:
        s["t0_ns"] = s["t1_ns"] = None
    assert io.idle_in_wait_pct(ctx) is None
    ctx = _run()
    ctx.spans = []
    assert [r(ctx) for r in readers] == [None] * 5 and ctx.notes == {}


def _with_prepared(tmp_path, name):
    """A root whose BENCHMARK.json is the repo's with the entries of
    ``prepared/<name>.json`` appended, as its ``what`` says to."""
    doc = manifest.Manifest().doc
    with open(os.path.join(manifest.HERE, "prepared", name + ".json")) as f:
        prepared = json.load(f)
    for group in ("workloads", "per_layer"):
        doc[group] = doc[group] + prepared[group]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    # everything else by link: the package, src/ and what src/ builds from
    # (its CMakeLists.txt reaches into tests/cpp)
    for entry in os.listdir(ROOT):
        if entry != "BENCHMARK.json":
            os.symlink(os.path.join(ROOT, entry), tmp_path / entry)
    return str(tmp_path)


def _root_with_the_cell(tmp_path):
    """The repo itself once the cell is in BENCHMARK.json; while it is
    prepared, a root of links with its entries appended."""
    if CELL in manifest.Manifest().workloads:
        return ROOT
    return _with_prepared(tmp_path, CELL)


def test_manifest_finds_the_cell_by_name(tmp_path):
    man = manifest.Manifest(_root_with_the_cell(tmp_path)).validate()
    entry = man.workloads[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("resnet50_v1", "fused-b256-rec", 1)
    cell = man.cell(CELL)
    assert cell.traffic["runner"] == "train_fused_rec"
    assert cell.traffic["pool"] == 1 and cell.traffic["per_chip_batch"] == 256
    assert cell.traffic["rec"]["records"] % cell.traffic["per_chip_batch"] \
        == 0
    assert [m["name"] for m in cell.layer_metrics] == METRICS
    for name in METRICS[3:]:
        listed = man.per_layer[name]
        assert listed["workloads"] == [CELL]
        assert listed["layer"] == "Input pipeline, io/ + src/*.cc"
        assert listed["moves"] == "samples_per_s"
        assert callable(manifest.reader(manifest.layer_metric(name)))
    # it shares cell 1's configuration file, untouched
    assert man.configs["resnet50_v1"]["file"] == \
        man.configs[man.workloads["resnet50-fused-b256"]["config"]]["file"]


def _rehearse(root, *args, control=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_BENCH_CONTROL", None)
    # a root of links would keep a compile cache of its own, cold every time
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    if control:
        env["MXTPU_BENCH_CONTROL"] = control
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seconds", "1", "--rehearse", *args],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1]), out.stdout


def test_rehearsal_ends_correct_with_every_layer_metric_or_none(tmp_path):
    """``run.py --rehearse`` on the CPU: the file written, the pipeline
    checked against the plain decode, epochs counted; with ``--trace 1``
    every host metric has a number and the two that need a device trace are
    left out (the CPU's profile has no TPU line)."""
    root = _root_with_the_cell(tmp_path)
    line, text = _rehearse(root, "--seed", "4100000063", "--trace", "1")
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0
    got = line["metrics"]
    for name in ("train.host_ms", "io.wait_ms", "io.fetch_ms",
                 "io.augment_ms", "io.h2d_ms"):
        assert got[name]["value"] >= 0, name
    assert set(got) <= set(METRICS)
    assert "# check.first_batch_vs_plain_decode: {\"ok\": true" in text
    assert "# check.every_epoch_delivered_the_file_once: {\"ok\": true" \
        in text
    assert "# io.period:" in text and "# rec:" in text


@pytest.mark.parametrize("control, check", [
    ("next_record", "first_batch_vs_plain_decode"),
    ("double_batch", "every_epoch_delivered_the_file_once")])
def test_controls_are_refused(tmp_path, control, check):
    root = _root_with_the_cell(tmp_path)
    line, text = _rehearse(root, "--seed", "7", "--trace", "0",
                           control=control)
    assert line["correct"] is False
    assert f"# check.{check}: {{\"ok\": false" in text
