"""``readers/spans.py`` on hand-made spans and intervals: idle time inside a
span, outside every span, a span the window cuts, and a clock that does not
fit, which must give no number."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import manifest                         # noqa: E402
import trace as tr                      # noqa: E402
from readers import device, spans       # noqa: E402

SESSION = 1_790_000_000_000_000_000     # the profiler's start, wall clock ns
US = 1_000


class _Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


def _span(name, t0_us, t1_us, span, parent=None, shift_ns=0, **args):
    """A finished span record as the program writes it: ``t0``/``t1`` in
    seconds on the host clock (here: the trace's clock in seconds), the ns
    pair on the wall clock."""
    return {"name": name, "trace": parent or span, "span": span,
            "parent": parent, "t0": t0_us / 1e6, "t1": t1_us / 1e6,
            "t0_ns": SESSION + t0_us * US + shift_ns,
            "t1_ns": SESSION + t1_us * US + shift_ns,
            "thread": "MainThread", "args": args}


def _step(k, shift_ns=0):
    """Step ``k`` of 1000 us: the host dispatches for 300 us (forward 20-90,
    loss 95-110, backward 120-200, update 210-280), the device runs 100-400
    and 450-1000, and idles 0-100 (inside forward and the loss) and 400-450
    (after every span)."""
    o, n = k * 1000, 10 * k
    s = [_span("gluon.forward", o + 20, o + 90, n + 1, shift_ns=shift_ns,
               block="net", hybridized=True, retrace=False),
         _span("gluon.forward", o + 95, o + 110, n + 2, shift_ns=shift_ns,
               block="loss", hybridized=False, retrace=False),
         _span("autograd.backward", o + 120, o + 200, n + 3,
               shift_ns=shift_ns, heads=1, nodes=2),
         _span("gluon.update", o + 210, o + 280, n + 4, shift_ns=shift_ns,
               path="fused_jit", programs=1, params=161)]
    host = [("bench.dispatch", (o + 5) * US, 295 * US),
            (spans.WALL_STAMP + str(SESSION + (o + 6) * US), (o + 6) * US,
             1 * US),
            ("bench.wait", (o + 300) * US, 700 * US)]
    modules = [("jit_bwd", (o + 450) * US, 550 * US)]
    ops = [("fusion.1", (o + 100) * US, 300 * US),
           ("fusion.2", (o + 450) * US, 550 * US)]
    return s, host, modules, ops


def _run(steps=6, shift_ns=0, extra_spans=()):
    all_spans, host, modules, ops = list(extra_spans), [], [], []
    for k in range(steps):
        s, h, m, o = _step(k, shift_ns)
        all_spans += s
        host += h
        modules += m
        ops += o
    trace = tr.Trace({0: {tr.MODULES: modules, tr.OPS: ops}}, host, {})
    return _Ctx(trace=trace, spans=all_spans, device_ids=[0],
                t_start=0.0, t_end=steps * 1000 / 1e6)


def test_host_metrics_from_the_root_spans_in_the_window():
    ctx = _run()
    assert spans.fwd_bwd_host_ms(ctx) == pytest.approx(0.165)
    assert spans.update_host_ms(ctx) == pytest.approx(0.070)
    assert spans.update_programs(ctx) == 1.0
    # a window that cuts the last step's update: that step's forward and
    # backward count towards five steps
    ctx.t_end = (5 * 1000 + 250) / 1e6
    assert spans.update_host_ms(ctx) == pytest.approx(0.070)
    assert spans.fwd_bwd_host_ms(ctx) == pytest.approx(0.165 * 6 / 5)


def test_idle_inside_a_span_outside_every_span_and_a_span_cut_by_the_window():
    ctx = _run()
    # the traced window: steps 1-4's backward modules, 1450 to 5000 us
    lo, hi, steps = ctx.trace.window(0)
    assert (lo, hi, steps) == (1450 * US, 5000 * US, 4)
    # idle in it: 2000-2100, 3000-3100, 4000-4100 and 2400-2450, 3400-3450,
    # 4400-4450; the first lot while the host was in forward (20-90), the
    # loss (95-110), between them (5 + 15 us of 0-20, 90-95) - the second
    # lot after the step's last span
    fwd_bwd = spans.idle_in_fwd_bwd_pct(ctx)
    assert fwd_bwd == pytest.approx(100 * 3 * (70 + 5) / 3550)
    assert spans.idle_in_update_pct(ctx) == 0.0
    table = ctx.notes["idle_by_span"]["idle_pct_of_window"]
    assert table["uncovered"] == pytest.approx(100 * 3 * (25 + 50) / 3550)
    assert fwd_bwd + table["uncovered"] == pytest.approx(device.idle_pct(ctx))
    assert set(table) == {"gluon.forward", "uncovered"}
    clock = ctx.notes["clock"]
    assert clock["aligned"] and clock["largest_overhang_us"] == 0
    assert clock["session_start_wall_ns"] == SESSION
    assert clock["wall_stamps"] == 6
    # a span that the window cuts counts for its part inside: move the
    # window's start into step 1's forward by ending step 0's module there
    ctx2 = _run()
    ctx2.trace.devices[0][tr.MODULES][1] = ("jit_bwd", 1050 * US, 950 * US)
    assert ctx2.trace.window(0)[0] == 1050 * US
    table2 = spans._idle_by_span(ctx2)
    # of step 1's idle 1000-1100 only 1050-1100 is in the window: forward
    # 1050-1090, the 5 us to the loss, and 5 us of the loss (1095-1100)
    assert table2["gluon.forward"] == (3 * 75 + 45) * US


def test_update_and_compile_rows():
    # a device that idles during every update (210-280) as well
    ctx = _run(extra_spans=[
        _span("jit.compile", 2030, 2080, 999, parent=21)])
    for k in range(6):
        ops = ctx.trace.devices[0][tr.OPS]
        ops[2 * k] = ("fusion.1", (k * 1000 + 100) * US, 100 * US)
        ops.append(("fusion.3", (k * 1000 + 300) * US, 100 * US))
    assert spans.idle_in_update_pct(ctx) == pytest.approx(100 * 3 * 70 / 3550)
    table = ctx.notes["idle_by_span"]
    # the compile sits inside step 2's forward: its row repeats idle time
    # its parent's row already holds, and the note says so
    assert table["idle_pct_of_window"]["jit.compile"] == \
        pytest.approx(100 * 50 / 3550)
    assert table["inside_other_spans"] == ["jit.compile"]
    total = spans.idle_in_fwd_bwd_pct(ctx) + spans.idle_in_update_pct(ctx) \
        + table["idle_pct_of_window"]["uncovered"]
    assert total == pytest.approx(device.idle_pct(ctx))


def test_a_clock_that_does_not_fit_gives_no_number():
    # the spans' ns stamps 250 us late against the trace: the update sticks
    # out of bench.dispatch by 230 us
    ctx = _run(shift_ns=250 * US)
    assert spans.idle_in_fwd_bwd_pct(ctx) is None
    assert spans.idle_in_update_pct(ctx) is None
    clock = ctx.notes["clock"]
    assert not clock["aligned"]
    assert clock["largest_overhang_us"] == pytest.approx(230.0)
    assert "idle_by_span" not in ctx.notes
    # 60 us late is inside the limit of 100
    ctx = _run(shift_ns=60 * US)
    assert spans.idle_in_update_pct(ctx) is not None
    assert ctx.notes["clock"]["largest_overhang_us"] == pytest.approx(40.0)
    # hours off (another clock altogether): no span among the annotations
    ctx = _run(shift_ns=3600 * 10**9)
    assert spans.idle_in_fwd_bwd_pct(ctx) is None
    assert ctx.notes["clock"]["largest_overhang_us"] is None
    # the host metrics do not need the clock
    assert spans.update_host_ms(ctx) == pytest.approx(0.070)


def test_nothing_to_read_returns_nothing_and_does_not_raise():
    # the parent's program: no gluon.* span, no ns stamp on any record
    old = [{"name": "train.step", "trace": 1, "span": 1, "parent": None,
            "t0": 0.001, "t1": 0.002, "thread": "MainThread", "args": {}}]
    ctx = _run()
    ctx.spans = old
    for read in (spans.fwd_bwd_host_ms, spans.update_host_ms,
                 spans.update_programs, spans.idle_in_fwd_bwd_pct,
                 spans.idle_in_update_pct):
        assert read(ctx) is None
    assert ctx.notes == {}
    # no trace (an untraced run), or a runner that left no wall-clock stamp
    ctx = _run()
    ctx.trace = None
    assert spans.idle_in_update_pct(ctx) is None
    ctx = _run()
    ctx.trace.host = [h for h in ctx.trace.host
                      if not h[0].startswith(spans.WALL_STAMP)]
    assert spans.idle_in_update_pct(ctx) is None and ctx.notes == {}


def _with_prepared(tmp_path, name):
    """A root whose BENCHMARK.json is the repo's with the entries of
    ``prepared/<name>.json`` appended, as its ``what`` says to."""
    doc = manifest.Manifest().doc
    with open(os.path.join(manifest.HERE, "prepared", name + ".json")) as f:
        prepared = json.load(f)
    for group in ("configs", "workloads", "per_layer"):
        doc[group] = doc[group] + prepared[group]
    for m in doc["per_layer"]:
        if m["name"] in prepared["per_layer_workloads"]:
            m["workloads"] = prepared["per_layer_workloads"][m["name"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    os.symlink(manifest.HERE, tmp_path / "benchmark")
    return manifest.Manifest(str(tmp_path)).validate()


def test_manifest_accepts_the_three_cells_and_the_prepared_fourth(tmp_path):
    man = manifest.Manifest().validate()
    assert list(man.workloads) == [
        "resnet50-fused-b256", "bert-base-fused-b128-s128",
        "bert-base-fused-b128-s128-dp4"]
    assert [w["chips"] for w in man.doc["workloads"]] == [1, 1, 4]
    dp4 = man.cell("bert-base-fused-b128-s128-dp4")
    assert [m["name"] for m in dp4.layer_metrics] == [
        "train.host_ms", "device.idle_pct", "device.mfu_pct",
        "kernel.flash_fwd_ms", "kernel.flash_fwd_roofline",
        "collective.exposed_ms"]
    # the Gluon cell is prepared, not in: its entries make a fourth cell
    man = _with_prepared(tmp_path, "resnet50-gluon-b32")
    assert list(man.workloads)[3:] == ["resnet50-gluon-b32"]
    for name in man.workloads:
        man.cell(name)
    gluon = man.cell("resnet50-gluon-b32")
    assert gluon.chips == 1 and gluon.traffic["per_chip_batch"] == 32
    assert gluon.config["dtype"] == "float32" and "amp" not in gluon.config
    assert gluon.traffic["runner"] == "train_gluon"
    assert [m["name"] for m in gluon.layer_metrics] == [
        "device.idle_pct", "device.mfu_pct", "gluon.fwd_bwd_host_ms",
        "gluon.update_host_ms", "gluon.update_programs",
        "gluon.idle_in_fwd_bwd_pct", "gluon.idle_in_update_pct"]
    # the Gluon cell has no train.step span, the fused cells no gluon.* one
    assert "resnet50-gluon-b32" not in \
        man.per_layer["train.host_ms"]["workloads"]
    for name, m in man.per_layer.items():
        if name.startswith("gluon."):
            assert m["workloads"] == ["resnet50-gluon-b32"]
            assert m["layer"] == "Eager NDArray + tape, Gluon blocks"
