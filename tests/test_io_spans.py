"""The input pipeline's spans (PR 37): one trace a batch under
``DevicePrefetcher``, ``ImageRecordIter``'s three stages under its
``io.decode``, the C++ pool's counts on ``io.rec.fetch``, the batch's number
on ``io.wait`` and on the ``train.step`` that consumed it."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, recordio
from mxnet_tpu.io import DevicePrefetcher, ImageRecordIter
from mxnet_tpu.telemetry import tracing
from mxnet_tpu.utils import native

RECORDS, BATCH, EDGE = 48, 16, 112
STAGES = ["io.rec.fetch", "io.rec.augment", "io.rec.stage"]


@pytest.fixture(scope="module")
def rec_path(tmp_path_factory):
    """48 JPEGs of 128 x 128 as ``im2rec`` packs them, label = number % 10."""
    d = tmp_path_factory.mktemp("rec")
    path = str(d / "a.rec")
    w = recordio.MXIndexedRecordIO(str(d / "a.idx"), path, "w")
    rng = np.random.RandomState(0)
    for i in range(RECORDS):
        img = rng.randint(0, 255, (128, 128, 3)).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img, quality=95))
    w.close()
    return path


def _iterator(path, native_path, threads=2):
    it = ImageRecordIter(path, (3, EDGE, EDGE), BATCH, shuffle=True,
                         rand_mirror=True, preprocess_threads=threads)
    if not native_path and it._use_native:
        it._use_native = False
        it.reset()
    return it


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize("path_kind", ["native", "python_pool",
                                       "python_inline"])
def test_span_tree_of_a_prefetcher_over_an_image_record_iter(rec_path,
                                                             path_kind):
    if path_kind == "native" and not native.available():
        pytest.skip("libmxtpu.so is not built")
    it = _iterator(rec_path, path_kind == "native",
                   threads=1 if path_kind == "python_inline" else 2)
    with tracing.span("consumer") as consumer:
        pf = DevicePrefetcher(it, mesh=None)
        got = list(pf)
        pf.close()
    it.close()
    spans = tracing.spans()
    n = RECORDS // BATCH
    assert len(got) == n
    # one root a batch, numbered in order, with the two stages under it
    roots = _by_name(spans, "io.batch")
    assert [r["args"]["batch"] for r in roots] == list(range(n))
    for r in roots:
        assert r["parent"] is None
        kids = [s for s in spans if s["parent"] == r["span"]]
        assert [k["name"] for k in kids] == ["io.decode", "io.h2d"]
        decode, h2d = kids
        assert h2d["args"]["bytes"] == BATCH * 3 * EDGE * EDGE * 4 + BATCH * 4
        # the iterator's three stages tile its decode: self time under 5 %
        stages = [s for s in spans if s["parent"] == decode["span"]
                  and s["name"] != "jit.compile"]
        assert [s["name"] for s in stages] == STAGES
        covered = sum(s["t1"] - s["t0"] for s in stages)
        assert covered >= 0.95 * (decode["t1"] - decode["t0"])
        fetch, augment, stage = stages
        assert fetch["args"]["native"] is (path_kind == "native")
        assert augment["args"]["dtype"] == "float64"       # mean / std are
        assert augment["args"]["bytes"] == BATCH * 3 * EDGE * EDGE * 8
        assert stage["args"]["dtype"] == "float32"
        assert stage["args"]["bytes"] == h2d["args"]["bytes"]
        assert stage["args"]["device"] == "cpu"
    # nothing the worker recorded hangs under the consumer's span
    worker = roots[0]["thread"]
    assert worker != consumer.thread
    for s in spans:
        if s["thread"] == worker:
            assert s["trace"] != consumer.trace
    # io.wait: the consumer's, with the number of the batch it returned
    waits = _by_name(spans, "io.wait")
    assert all(w["parent"] == consumer.span for w in waits)
    assert [w["args"]["batch"] for w in waits] == list(range(n)) + [None]
    assert all(isinstance(w["args"]["queued"], int) for w in waits)
    for i, batch in enumerate(got):
        assert batch.data[0]._io_batch == batch.label[0]._io_batch == i
    if path_kind == "native":
        # the pool's own counts ride on the fetches: every record once
        fetches = _by_name(spans, "io.rec.fetch")
        assert sum(f["args"]["decoded"] for f in fetches) == RECORDS
        assert all(f["args"]["busy_ns"] >= 0 and f["args"]["full_ns"] >= 0
                   for f in fetches)


def test_reset_marks_the_epoch_and_set_state_numbers_from_its_cursor(
        rec_path):
    it = _iterator(rec_path, native.available())
    pf = DevicePrefetcher(it, mesh=None)
    assert len(list(pf)) == 3
    pf.reset()
    next(pf)
    pf.set_state({"batches_consumed": 2})
    assert next(pf).data[0]._io_batch == 2
    pf.close()
    it.close()
    epochs = _by_name(tracing.spans(), "io.epoch")
    # construction, (the Python path resets once more,) and two resets
    assert [e["args"]["epoch"] for e in epochs] == list(range(len(epochs)))
    assert len(epochs) >= 3
    assert all(e["args"]["records"] == RECORDS for e in epochs)
    batches = [r["args"]["batch"] for r in _by_name(tracing.spans(),
                                                    "io.batch")]
    assert batches[:4] == [0, 1, 2, 0] and batches[-1] >= 2
    assert 2 in batches[4:]


def test_trace_off_leaves_no_span_and_keeps_the_stats(rec_path):
    tracing.configure(enabled=False)
    try:
        it = _iterator(rec_path, native.available())
        pf = DevicePrefetcher(it, mesh=None)
        got = list(pf)
        pf.close()
        it.close()
        assert tracing.spans() == []
    finally:
        tracing.configure(enabled=True)
    assert tracing.spans() == []
    assert len(got) == 3 and got[2].data[0]._io_batch == 2
    summary = pf.stats.summary()
    assert summary["batches"] == 3
    assert summary["decode_ms_per_batch"] > 0
    assert summary["h2d_ms_per_batch"] > 0


def test_stats_and_spans_share_their_stamps(rec_path):
    """One pair of clock reads a stage: ``PipelineStats``' seconds are the
    spans' own durations, to the last bit."""
    pf = DevicePrefetcher(
        (np.ones((4, 2), np.float32) for _ in range(3)), mesh=None)
    list(pf)
    pf.close()
    spans = tracing.spans()
    for stage, name in (("decode", "io.decode"), ("h2d", "io.h2d"),
                        ("stall", "io.wait")):
        total = 0.0
        for s in _by_name(spans, name):
            total += s["t1"] - s["t0"]
        assert getattr(pf.stats, stage + "_s") == total, stage


def test_train_step_carries_the_batch_it_consumed():
    mx.random.seed(3)
    net = gluon.nn.Dense(4)
    net.initialize()
    trainer = parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.05})
    rng = np.random.RandomState(0)
    source = [(rng.randn(8, 8).astype(np.float32),
               rng.randn(8, 4).astype(np.float32)) for _ in range(3)]
    pf = DevicePrefetcher(iter(source), mesh=None)
    for x, y in pf:
        trainer.step(x, y)
    pf.close()
    trainer.step(mx.nd.array(source[0][0]), mx.nd.array(source[0][1]))
    steps = _by_name(tracing.spans(), "train.step")
    assert [s["args"].get("batch") for s in steps] == [0, 1, 2, None]


def test_native_pool_stats_are_monotone_and_count_every_record(rec_path):
    if not native.available():
        pytest.skip("libmxtpu.so is not built")
    pool = native.NativePrefetcher(rec_path, np.arange(RECORDS), BATCH,
                                   n_threads=2, mode="image", edge=EDGE)
    last = pool.stats()
    assert set(last) == {"decoded", "busy_ns", "full_ns", "empty_ns"}
    delivered = 0
    for epoch in (1, 2):
        for batch, _labels in pool:
            delivered += len(batch)
            now = pool.stats()
            assert all(now[k] >= last[k] for k in now)
            last = now
        assert delivered == RECORDS * epoch
        # the pool runs ahead of next(); at an epoch's end it has decoded
        # what it delivered and nothing more
        assert pool.stats()["decoded"] == delivered
        pool.reset()
    assert last["busy_ns"] > 0
    pool.close()


def test_discard_closes_a_scope_without_a_record():
    with tracing.span("kept"):
        with tracing.span("dropped") as sp:
            tracing.discard(sp)
    assert [s["name"] for s in tracing.spans()] == ["kept"]
    tracing.discard(None)
    tracing.discard(tracing.NULL_SPAN)
