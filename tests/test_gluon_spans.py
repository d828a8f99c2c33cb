"""Spans where ported scripts spend their time (ISSUE 27): the Gluon
loop's root spans (``gluon.forward`` / ``autograd.backward`` /
``gluon.update``), the ``jit.compile`` child a compile leaves under the
span that caused it, the two clocks on every span record, and what
replaced the live-MFU gauges on the fused trainer's path.
"""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, parallel, telemetry
from mxnet_tpu.telemetry import tracing
from mxnet_tpu.testing.faults import FakeClock

nd = mx.nd


def _net(hybridize=True):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    if hybridize:
        net.hybridize()
    return net


def _batch(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return (nd.array(rng.randn(n, 5).astype(np.float32)),
            nd.array(rng.randint(0, 3, n).astype(np.float32)))


def _loop(net, trainer, steps, batch=None):
    """The README loop."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = batch or _batch()
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
    return loss


def _sgd(net):
    return gluon.Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})


def _fused_steps(n):
    net = gluon.nn.Dense(4)
    net.initialize()
    tr = parallel.DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                                      {"learning_rate": 0.1})
    x = nd.array(np.zeros((8, 4), np.float32))
    for _ in range(n):
        tr.step(x, x)
    return tr


def _named(name, **match):
    return [s for s in tracing.spans() if s["name"] == name and
            all(s["args"].get(k) == v for k, v in match.items())]


# ----------------------------------------------------------------------
# the Gluon loop's root spans
# ----------------------------------------------------------------------

def test_root_spans_once_per_step_with_parents_and_args():
    net = _net()
    _loop(net, _sgd(net), steps=3)
    forwards = _named("gluon.forward", block=net.name)
    backwards = _named("autograd.backward")
    updates = _named("gluon.update")
    assert len(forwards) == len(backwards) == len(updates) == 3
    # the loss is a block of its own, called outermost: one more a step
    assert len(_named("gluon.forward")) == 6
    for s in forwards + backwards + updates:
        assert s["parent"] is None and s["trace"] == s["span"]
    assert all(s["args"]["hybridized"] is True for s in forwards)
    # net's CachedOp and the loss's operations: two tape nodes, one head
    assert [s["args"] for s in backwards] == [{"heads": 1, "nodes": 2}] * 3
    assert [s["args"] for s in updates] == \
        [{"path": "fused_jit", "programs": 1, "params": 4}] * 3
    # a step's spans follow each other on the calling thread
    for f, b, u in zip(forwards, backwards, updates):
        assert f["t1"] <= b["t0"] and b["t1"] <= u["t0"]
        assert f["thread"] == b["thread"] == u["thread"]


def test_retrace_true_on_a_new_signature_only():
    net = _net()
    trainer = _sgd(net)
    _loop(net, trainer, steps=2)
    _loop(net, trainer, steps=1, batch=_batch(n=6))     # a new shape
    _loop(net, trainer, steps=1, batch=_batch(n=6))
    got = [s["args"]["retrace"]
           for s in _named("gluon.forward", block=net.name)]
    assert got == [True, False, True, False]


def test_nested_blocks_add_no_forward_span():
    net = _net(hybridize=False)         # children run as blocks, eagerly
    x, _ = _batch()
    net(x)
    spans = _named("gluon.forward")
    assert len(spans) == 1
    assert spans[0]["args"] == {"block": net.name, "hybridized": False,
                                "retrace": False}
    # and a block that an enclosing jit trace inlines adds none either
    tracing.reset()
    net.hybridize()
    net(x)                              # traces net.forward: children
    assert len(_named("gluon.forward")) == 1        # are calls inside it


@pytest.mark.parametrize("path, optimizer, fused_step, programs", [
    ("fused_jit", "sgd", "1", 1),
    ("fused_group", "sgd", "0", 1),
    ("eager", "adam", "0", 4),
])
def test_update_span_names_the_path_that_ran(monkeypatch, path, optimizer,
                                             fused_step, programs):
    monkeypatch.setenv("MXTPU_FUSED_STEP", fused_step)
    net = _net()
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            {"learning_rate": 0.01})
    _loop(net, trainer, steps=2)
    assert [s["args"] for s in _named("gluon.update")] == \
        [{"path": path, "programs": programs, "params": 4}] * 2
    # no kvstore reduces on one worker: no allreduce child
    assert _named("gluon.update.allreduce") == []


def test_kill_switch_leaves_the_loop_bitwise_equal_and_records_nothing():
    results = {}
    for mode in (True, False):
        tracing.configure(enabled=mode)
        try:
            mx.random.seed(5)
            np.random.seed(5)
            net = _net()
            loss = _loop(net, _sgd(net), steps=3)
            results[mode] = [loss.asnumpy()] + [
                p.data().asnumpy()
                for _, p in sorted(net.collect_params().items())]
            if not mode:
                assert tracing.spans() == []
        finally:
            tracing.configure(enabled=True)
    for on, off in zip(results[True], results[False]):
        assert np.array_equal(on, off)


# ----------------------------------------------------------------------
# jit.compile
# ----------------------------------------------------------------------

def test_compile_lands_under_the_span_that_caused_it():
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda a: a * 3 + 1)
    x = jnp.arange(7.0)
    jax.block_until_ready(x)
    before = telemetry.value("jit.compiles") or 0
    tracing.reset()
    with tracing.span("outer") as outer:
        fn(x)                   # compiles here
        with tracing.span("inner"):
            fn(x)               # cached: nothing
    compiles = _named("jit.compile")
    assert len(compiles) == 1
    assert compiles[0]["parent"] == outer.span
    assert compiles[0]["t0"] >= outer.t0
    assert compiles[0]["t1"] - compiles[0]["t0"] > 0
    assert telemetry.value("jit.compiles") == before + 1
    # with no ambient span it is a root; the Gluon loop's first step
    # compiles inside its spans and its later steps do not compile
    tracing.reset()
    net = _net()
    _loop(net, _sgd(net), steps=4)
    spans = tracing.spans()
    by_id = {s["span"]: s for s in spans}
    parents = [by_id[s["parent"]]["name"] for s in spans
               if s["name"] == "jit.compile" and s["parent"] is not None]
    assert {"gluon.forward", "autograd.backward", "gluon.update"} <= \
        set(parents)
    last = max(s["t1"] for s in _named("gluon.update"))
    third = sorted(s["t0"] for s in _named("gluon.update"))[2]
    assert not [s for s in _named("jit.compile") if third < s["t0"] < last]


def test_fused_step_root_is_scoped_and_owns_its_compile():
    _fused_steps(2)
    roots = _named("train.step")
    # the first root also carries the trainer's byte gauges (ISSUE 39)
    assert set(roots[0]["args"]) == {"step", "param_bytes", "state_bytes",
                                     "grad_buffer_bytes"}
    assert roots[0]["args"]["step"] == 1
    assert roots[0]["args"]["grad_buffer_bytes"] == 0
    assert roots[1]["args"] == {"step": 2}
    owners = {s["parent"] for s in _named("jit.compile")}
    assert roots[0]["span"] in owners and roots[1]["span"] not in owners
    # the pre-timed phases tile the root from its own start
    kids = [s for s in tracing.spans() if s["parent"] == roots[1]["span"]]
    assert kids[0]["t0"] == roots[1]["t0"]
    assert kids[-1]["t1"] <= roots[1]["t1"]


# ----------------------------------------------------------------------
# every span a stamp on the profiler's clock
# ----------------------------------------------------------------------

def test_ns_stamps_are_ordered_nest_and_read_the_wall_clock():
    wall0 = time.time_ns()
    with tracing.span("root"):
        with tracing.span("child"):
            time.sleep(0.002)
        t0 = tracing.clock()
        time.sleep(0.001)
        tracing.record("pretimed", t0, tracing.clock())
        tracing.record("stamped", 1.0, 2.0, ns=(10, 20))
    wall1 = time.time_ns()
    sp = {s["name"]: s for s in tracing.spans()}
    root, child, pre = sp["root"], sp["child"], sp["pretimed"]
    for s in (root, child, pre):
        assert wall0 <= s["t0_ns"] <= s["t1_ns"] <= wall1
        # both clocks give the same duration, to the two reads' jitter
        assert abs((s["t1_ns"] - s["t0_ns"]) / 1e9 -
                   (s["t1"] - s["t0"])) < 1e-3
    assert root["t0_ns"] <= child["t0_ns"] and \
        child["t1_ns"] <= root["t1_ns"]
    assert child["t1_ns"] <= pre["t0_ns"] + 50_000      # converted: 50 us
    assert pre["t1_ns"] <= root["t1_ns"] + 50_000
    # a caller that stamped both clocks itself keeps its pair
    assert (sp["stamped"]["t0_ns"], sp["stamped"]["t1_ns"]) == (10, 20)


def test_fakeclock_twin_runs_identical_with_ns():
    def run():
        clock = FakeClock(100.0)
        tracing.reset()
        tracing.configure(now=clock)
        with tracing.span("serve"):
            clock.advance(0.5)
            with tracing.span("inner", k=1):
                clock.advance(0.25)
            tracing.record("pre", 100.1, 100.2)
        return tracing.spans()
    a, b = run(), run()
    assert a == b
    inner = next(s for s in a if s["name"] == "inner")
    assert (inner["t0_ns"], inner["t1_ns"]) == \
        (100_500_000_000, 100_750_000_000)
    pre = next(s for s in a if s["name"] == "pre")
    assert (pre["t0_ns"], pre["t1_ns"]) == \
        (100_100_000_000, 100_200_000_000)


def test_scoped_span_enters_a_trace_annotation_of_its_name(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    with tracing.span("a"):
        with tracing.span("b"):
            pass
    tracing.record("pretimed", 0.0, 1.0)        # no scope, no annotation
    assert seen == [("enter", "a"), ("enter", "b"), ("exit", "b"),
                    ("exit", "a")]
    tracing.configure(enabled=False)
    try:
        with tracing.span("off"):
            pass
    finally:
        tracing.configure(enabled=True)
    assert len(seen) == 4


# ----------------------------------------------------------------------
# what replaced the live-MFU gauges
# ----------------------------------------------------------------------

def test_step_interval_observed_from_the_second_step_on():
    _fused_steps(1)
    assert "train.step_interval_ms" not in \
        telemetry.snapshot()["histograms"]
    telemetry.reset()
    _fused_steps(4)
    hists = telemetry.snapshot()["histograms"]
    assert hists["train.step_interval_ms"]["count"] == 3
    assert hists["train.step_ms"]["count"] == 4
    # an interval holds the step's own host time and the loop's between
    assert hists["train.step_interval_ms"]["min"] >= \
        hists["train.step_ms"]["min"]


def test_live_mfu_gauges_and_their_cost_analysis_are_gone():
    tr = _fused_steps(2)
    snap = telemetry.snapshot()
    for name in ("train.mfu", "train.tflops_delivered", "train.step_flops"):
        assert name not in snap["gauges"]
        assert telemetry.value(name) is None
    assert not hasattr(tr, "_live_cost")
