"""Causal tracing (ISSUE 14 tentpole): span trees, cross-thread
propagation, per-step phase attribution, per-request serving chains,
Chrome-trace export, and the bitwise-inert kill switch.

The acceptance gates covered here:

- every finished serving request carries a COMPLETE, correctly-parented
  span chain (admission -> queue -> prefill[chunk(s)] -> N decode
  boundaries -> finish), including a request drained and requeued
  across replicas;
- a training step's phase spans tile the step: their sum is within 10%
  of the measured step wall time on the CPU smoke;
- ``MXTPU_TRACE=0`` is bitwise-inert (fp32 params identical on/off);
- twin runs produce IDENTICAL span trees under FakeClock (deterministic
  ids + injectable clock — zero sleeps).
"""
import json
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.telemetry import tracing
from mxnet_tpu.testing.faults import FakeClock

nd = mx.nd

_CC = {}     # module-wide serving compile cache (graphs compile once)


# ----------------------------------------------------------------------
# core span semantics
# ----------------------------------------------------------------------

def test_span_nesting_ids_and_tree_shape():
    with tracing.span("root", job="r") as root:
        with tracing.span("child.a"):
            with tracing.span("leaf"):
                pass
        with tracing.span("child.b"):
            pass
    sp = {s["name"]: s for s in tracing.spans()}
    assert set(sp) == {"root", "child.a", "leaf", "child.b"}
    r = sp["root"]
    assert r["parent"] is None and r["trace"] == r["span"]
    assert sp["child.a"]["parent"] == r["span"]
    assert sp["child.b"]["parent"] == r["span"]
    assert sp["leaf"]["parent"] == sp["child.a"]["span"]
    # one trace id threads the whole tree; ids are deterministic ints
    assert {s["trace"] for s in sp.values()} == {r["span"]}
    assert r["span"] == 1                      # reset by conftest
    assert r["args"] == {"job": "r"}
    assert all(s["t1"] >= s["t0"] for s in sp.values())


def test_manual_spans_and_pretimed_records():
    root = tracing.start("request", id=42)
    mid = tracing.record("queue", 1.0, 2.0, parent=root)
    tracing.finish(root, reason="done")
    sp = {s["name"]: s for s in tracing.spans()}
    assert sp["queue"]["parent"] == root.span
    assert sp["queue"]["t0"] == 1.0 and sp["queue"]["t1"] == 2.0
    assert sp["request"]["args"] == {"id": 42, "reason": "done"}
    assert mid.trace == root.span
    # finish is idempotent; finishing None/null spans never raises
    tracing.finish(root)
    tracing.finish(None)
    assert len(tracing.spans()) == 2


def test_twin_runs_identical_trees_under_fakeclock():
    """Deterministic ids + injectable clock: two identical runs emit
    byte-identical span trees (the twin-request acceptance gate)."""
    def run():
        clock = FakeClock(100.0)
        tracing.reset()                 # fresh ids, default clock...
        tracing.configure(now=clock)    # ...then inject the FakeClock
        with tracing.span("serve"):
            clock.advance(1.0)
            req = tracing.start("request", id=7)
            clock.advance(0.5)
            tracing.record("queue", 100.0, 101.5, parent=req)
            tracing.finish(req, reason="eos")
        out = tracing.spans()
        tracing.reset()                 # restore the default clock
        return out

    a, b = run(), run()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a[0]["t0"] == 100.0                  # FakeClock stamps


def test_cross_thread_capture_activate():
    """The explicit propagation hand-shake: a span opened on a worker
    thread parents under the captured ambient trace."""
    out = {}
    with tracing.span("owner") as owner:
        ctx = tracing.capture()

        def work():
            with tracing.activate(ctx):
                with tracing.span("worker.task") as sp:
                    out["parent"] = sp.parent
                    out["trace"] = sp.trace
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert out["parent"] == owner.span
    assert out["trace"] == owner.trace
    # without activation the same work would have been a fresh root
    sp = {s["name"]: s for s in tracing.spans()}
    assert sp["worker.task"]["thread"] != sp["owner"]["thread"]


def test_kill_switch_no_spans_and_null_ops():
    tracing.configure(enabled=False)
    try:
        with tracing.span("never") as sp:
            assert sp is tracing.NULL_SPAN
        assert tracing.start("x") is tracing.NULL_SPAN
        tracing.record("y", 0.0, 1.0)
        tracing.finish(tracing.start("z"))
        assert tracing.spans() == []
        assert tracing.capture() is None
        with tracing.activate(None):
            pass
    finally:
        tracing.configure(enabled=True)
    assert tracing.spans() == []


# ----------------------------------------------------------------------
# trainer: per-step phase spans + bitwise-inert switch
# ----------------------------------------------------------------------

def _tiny_trainer(seed=1234):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.Dense(4)
    net.initialize()
    return net, parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 0.05})


def test_train_step_phase_spans_tile_the_step():
    """Acceptance: the phase spans' sum is within 10% of the measured
    step wall time (they tile the root span by construction)."""
    net, tr = _tiny_trainer()
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(16, 8).astype(np.float32))
    y = nd.array(rng.randn(16, 4).astype(np.float32))
    for _ in range(3):
        tr.step(x, y)
    spans = tracing.spans()
    roots = [s for s in spans if s["name"] == "train.step"]
    assert len(roots) == 3
    phases = ("train.phase.prepare", "train.phase.h2d",
              "train.phase.dispatch", "train.phase.commit")
    for root in roots:
        kids = [s for s in spans if s["parent"] == root["span"]
                and s["name"] != "jit.compile"]    # the first step's
        assert [k["name"] for k in kids] == list(phases)
        wall = root["t1"] - root["t0"]
        covered = sum(k["t1"] - k["t0"] for k in kids)
        assert wall > 0
        assert abs(covered - wall) <= 0.10 * wall
        # phases are contiguous and ordered
        for a, b in zip(kids, kids[1:]):
            assert b["t0"] >= a["t1"] - 1e-9
    # step_multi gets the same phase tree (one root covering K steps)
    tr2 = _tiny_trainer()[1]
    tracing.reset()
    tr2.step_multi([(x, y), (x, y)])
    spans = tracing.spans()
    roots = [s for s in spans if s["name"] == "train.step"]
    assert len(roots) == 1
    kids = [s for s in spans if s["parent"] == roots[0]["span"]
            and s["name"] != "jit.compile"]
    assert [k["name"] for k in kids] == list(phases)


def test_trace_kill_switch_is_bitwise_inert():
    rng = np.random.RandomState(3)
    xs = rng.randn(2, 16, 8).astype(np.float32)
    ys = rng.randn(2, 16, 4).astype(np.float32)
    results = {}
    for mode in (True, False):
        tracing.configure(enabled=mode)
        try:
            net, tr = _tiny_trainer()
            for i in range(2):
                tr.step(nd.array(xs[i]), nd.array(ys[i]))
            results[mode] = {
                n: p.data().asnumpy()
                for n, p in net._collect_params_with_prefix().items()}
            if not mode:
                assert tracing.spans() == []
        finally:
            tracing.configure(enabled=True)
    assert set(results[True]) == set(results[False])
    for k in results[True]:
        assert np.array_equal(results[True][k], results[False][k]), k


def test_prefetcher_worker_spans_are_traces_of_their_own():
    """DevicePrefetcher's worker opens one ``io.batch`` root a batch with
    its stage spans under it: nothing it records hangs under the span
    that was ambient when the consumer started iterating.  ``io.wait`` is
    the consumer's."""
    from mxnet_tpu.io import DevicePrefetcher
    batches = [np.ones((4, 2), np.float32) * i for i in range(3)]
    with tracing.span("epoch") as root:
        pf = DevicePrefetcher(iter(batches), depth=2, mesh=None)
        got = list(pf)
        pf.close()
    assert len(got) == 3
    sp = tracing.spans()
    roots = [s for s in sp if s["name"] == "io.batch"]
    decodes = [s for s in sp if s["name"] == "io.decode"]
    h2ds = [s for s in sp if s["name"] == "io.h2d"]
    waits = [s for s in sp if s["name"] == "io.wait"]
    assert len(roots) == len(decodes) == len(h2ds) == 3 and len(waits) >= 3
    assert [r["args"]["batch"] for r in roots] == [0, 1, 2]
    for r, d, h in zip(roots, decodes, h2ds):
        assert r["parent"] is None and r["trace"] != root.trace
        assert d["parent"] == h["parent"] == r["span"]
        assert d["trace"] == h["trace"] == r["span"]
        assert r["thread"] != root.thread      # worker-side emission
        assert h["args"]["bytes"] == 4 * 2 * 4
    for s in waits:                            # consumer-side emission
        assert s["parent"] == root.span


def test_async_checkpoint_writer_span_parents_under_trace(tmp_path):
    from mxnet_tpu.checkpoint import AsyncCheckpointer
    net, _tr = _tiny_trainer()
    net(nd.array(np.zeros((2, 8), np.float32)))   # resolve deferred init
    arrays = {k: p.data() for k, p in
              net._collect_params_with_prefix().items()}
    with tracing.span("train") as root:
        ck = AsyncCheckpointer()
        ck.save(str(tmp_path / "m.params"), arrays)
        ck.wait_until_finished()
    writes = [s for s in tracing.spans()
              if s["name"] == "checkpoint.async_write"]
    assert len(writes) == 1
    assert writes[0]["parent"] == root.span
    assert writes[0]["thread"] != root.thread


# ----------------------------------------------------------------------
# serving: complete per-request chains (the acceptance criterion)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama():
    from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                     LlamaForCausalLM)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_layers=1,
                      num_heads=2, num_kv_heads=2, intermediate_size=64,
                      max_seq_len=64, tie_embeddings=True)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    net(nd.array(np.zeros((1, 4), np.int32)))
    net.hybridize()
    return net


def _request_chain(spans, req):
    """The request's child spans in ring (= causal) order."""
    assert req.trace is not None
    return [s for s in spans if s["trace"] == req.trace.span]


def test_request_span_chain_complete(llama):
    from mxnet_tpu.serving import (ContinuousBatcher, InferenceEngine,
                                   Request)
    eng = InferenceEngine(llama, max_batch=2, block_size=8,
                          max_context=32, compile_cache=_CC).warmup()
    b = ContinuousBatcher(eng)
    reqs = [b.submit(Request([3, 5, 7], max_new_tokens=3)),
            b.submit(Request([11, 2], max_new_tokens=2))]
    b.run()
    spans = tracing.spans()
    for req in reqs:
        chain = _request_chain(spans, req)
        names = [s["name"] for s in chain]
        # queue -> prefill -> N decode boundaries -> the root itself
        assert names[0] == "queue"
        assert names[1] == "prefill"
        n_decode = len(req.generated) - 1      # first token from prefill
        assert names[2:2 + n_decode] == ["decode"] * n_decode
        assert names[-1] == "request"
        root = chain[-1]
        assert root["args"]["reason"] == req.finish_reason
        assert root["args"]["tokens"] == len(req.generated)
        # every hop parents on the root; the chain is time-ordered
        for s in chain[:-1]:
            assert s["parent"] == root["span"]
        for a, c in zip(chain, chain[1:-1]):
            assert c["t0"] >= a["t0"] - 1e-9


def test_chunked_prefill_chain_has_chunk_spans(llama):
    from mxnet_tpu.serving import (ContinuousBatcher, InferenceEngine,
                                   Request)
    eng = InferenceEngine(llama, max_batch=2, block_size=8,
                          max_context=32, prefill_chunk=8,
                          compile_cache=_CC).warmup()
    b = ContinuousBatcher(eng)
    # 13 prompt tokens over chunk=8 => two prefill_chunk dispatch rows
    req = b.submit(Request(list(range(1, 14)), max_new_tokens=2))
    b.run()
    chain = _request_chain(tracing.spans(), req)
    names = [s["name"] for s in chain]
    assert names.count("prefill_chunk") == 2
    assert names[0] == "queue" and names[-1] == "request"
    starts = [s["args"]["start"] for s in chain
              if s["name"] == "prefill_chunk"]
    assert starts == [0, 8]


def test_drained_request_chain_spans_replicas(llama):
    """Acceptance: a request drained off a dying replica and requeued
    keeps ONE causally-linked trace — admission x2 with a requeue hop
    between, then a complete prefill/decode chain to finish."""
    from mxnet_tpu.serving import InferenceEngine, Request, Router
    from mxnet_tpu.testing import faults

    def factory(_cc):
        return InferenceEngine(llama, max_batch=2, block_size=8,
                               max_context=32, compile_cache=_CC)

    router = Router(factory, replicas=2)
    rng = np.random.RandomState(5)
    reqs = [router.submit(Request(rng.randint(0, 64, (3,)).tolist(),
                                  max_new_tokens=3)) for _ in range(4)]
    with faults.inject("serving.replica1.step", at=2):
        router.drive()
    assert router.requeues >= 1
    spans = tracing.spans()
    moved = [r for r in reqs
             if any(s["name"] == "requeue"
                    for s in _request_chain(spans, r))]
    assert moved, "the kill must have displaced at least one request"
    for req in moved:
        chain = _request_chain(spans, req)
        names = [s["name"] for s in chain]
        admissions = [s for s in chain if s["name"] == "admission"]
        assert len(admissions) == 2
        assert admissions[0]["args"]["requeue"] is False
        assert admissions[1]["args"]["requeue"] is True
        hop = next(s for s in chain if s["name"] == "requeue")
        assert hop["args"]["from_rid"] == 1
        # the post-requeue chain still completes fully
        i_re = names.index("requeue")
        tail = names[i_re + 1:]
        assert "prefill" in tail and "decode" in tail
        assert names[-1] == "request"
        n_decode = len(req.generated) - 1
        assert tail.count("decode") == n_decode
        root = chain[-1]
        assert all(s["parent"] == root["span"] for s in chain[:-1])


# ----------------------------------------------------------------------
# export: merged Chrome-trace JSON
# ----------------------------------------------------------------------

def test_chrome_trace_merges_tracing_and_profiler_streams():
    from mxnet_tpu import profiler
    with tracing.span("step", step=1):
        pass
    # mx.profiler's MXNet-compatible scoped events are the other
    # stream chrome_trace() merges (the program itself writes spans only)
    with profiler.Task(profiler.Domain("user"), "user:decode"):
        pass
    payload = tracing.chrome_trace()
    evs = payload["traceEvents"]
    assert isinstance(evs, list)
    xs = [e for e in evs if e.get("ph") == "X"]
    bes = [e for e in evs if e.get("ph") in ("B", "E")]
    assert len(xs) == 1 and xs[0]["name"] == "step"
    assert xs[0]["args"]["trace"] == xs[0]["args"]["span"]
    assert xs[0]["dur"] >= 0
    assert {e["name"] for e in bes} == {"user:decode"}
    for e in xs + bes:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
    # valid JSON end to end (the chrome://tracing contract)
    assert json.loads(json.dumps(payload)) == payload


def test_telemetry_dump_trace_export(tmp_path, capsys):
    """tools/telemetry_dump.py --trace writes valid Chrome-trace JSON
    (the tier-1 schema smoke the satellite asks for)."""
    import tools.telemetry_dump as td
    out = tmp_path / "trace.json"
    rc = td.main(["--self-test", "--format=json", "--trace", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert "traceEvents" in payload
    xs = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in xs}
    assert {"selftest.root", "selftest.child"} <= names
    child = next(e for e in xs if e["name"] == "selftest.child")
    root = next(e for e in xs if e["name"] == "selftest.root")
    assert child["args"]["parent"] == root["args"]["span"]


def test_tracing_overhead_smoke():
    """20k no-op calls when disabled and 2k recorded spans when enabled
    both stay far under a second — the <5% step-overhead budget has
    huge headroom at the per-span cost this asserts."""
    import time
    tracing.configure(enabled=False)
    try:
        t0 = time.perf_counter()
        for _ in range(20000):
            tracing.record("x", 0.0, 1.0)
        assert time.perf_counter() - t0 < 1.0
    finally:
        tracing.configure(enabled=True)
    t0 = time.perf_counter()
    for _ in range(2000):
        tracing.record("x", 0.0, 1.0)
    assert time.perf_counter() - t0 < 1.0
