"""Overlapped input pipeline (ISSUE 2): DevicePrefetcher ordering /
StopIteration / worker-exception surfacing / clean shutdown,
AsyncDecodeIter fan-out, ImageRecordIter preprocess_threads plumbing,
thread-safe recordio random reads, the donated fused Trainer.step path,
and the DataLoader prefetch_to_device hook — all under JAX_PLATFORMS=cpu
(conftest pins the backend; speedup claims are TPU-gated, correctness is
not).
"""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, recordio
from mxnet_tpu.gluon import nn
from mxnet_tpu.io import (AsyncDecodeIter, DataBatch, DevicePrefetcher,
                          NDArrayIter, PrefetchingIter)


def _no_prefetch_threads():
    return not any(t.name.startswith("mxtpu-device-prefetch")
                   for t in threading.enumerate())


def _wait_threads_gone(timeout=5.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if _no_prefetch_threads():
            return True
        time.sleep(0.05)
    return False


# ----------------------------------------------------------------------
# DevicePrefetcher
# ----------------------------------------------------------------------

def test_device_prefetcher_ordering_and_stop_iteration():
    src = ((np.full((4, 3), i, np.float32), np.full((4,), i, np.float32))
           for i in range(12))
    pf = DevicePrefetcher(src, depth=2)
    seen = []
    for data, label in pf:
        assert isinstance(data, mx.nd.NDArray)
        seen.append((float(data.asnumpy()[0, 0]),
                     float(label.asnumpy()[0])))
    assert seen == [(float(i), float(i)) for i in range(12)]
    # StopIteration keeps propagating and the worker is joined
    with pytest.raises(StopIteration):
        next(pf)
    assert pf._thread is None
    assert _wait_threads_gone()
    s = pf.stats.summary()
    assert s["batches"] == 12
    assert s["overlap_efficiency"] is not None
    assert 0.0 <= s["overlap_efficiency"] <= 1.0


def test_device_prefetcher_worker_exception_surfaces():
    def bad_source():
        yield np.ones((2, 2), np.float32)
        yield np.ones((2, 2), np.float32)
        raise ValueError("decode exploded")

    pf = DevicePrefetcher(bad_source(), depth=2)
    next(pf)
    next(pf)
    with pytest.raises(ValueError, match="decode exploded"):
        next(pf)
    assert pf._thread is None
    assert _wait_threads_gone()


def test_device_prefetcher_close_mid_stream_no_leaked_threads():
    def endless():
        while True:
            yield np.ones((8, 8), np.float32)

    pf = DevicePrefetcher(endless(), depth=2)
    next(pf)
    pf.close()
    assert pf._thread is None
    assert _wait_threads_gone()
    # closed prefetcher behaves as exhausted
    with pytest.raises(StopIteration):
        next(pf)


def test_device_prefetcher_mesh_sharding():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh, mesh_scope

    mesh = make_mesh({"dp": -1})
    dp = mesh.shape["dp"]
    batch = 2 * dp
    with mesh_scope(mesh):   # picked up implicitly, like the trainers
        pf = DevicePrefetcher(iter(
            [(np.ones((batch, 3), np.float32),
              np.zeros((batch,), np.float32))]))
        data, label = next(pf)
    assert data.data.sharding.is_equivalent_to(
        NamedSharding(mesh, P("dp", None)), 2)
    # rank-1 labels shard on axis 0 (same _eff_bax convention as the
    # fused trainers)
    assert label.data.sharding.is_equivalent_to(
        NamedSharding(mesh, P("dp")), 1)
    pf.close()


def test_device_prefetcher_databatch_structure_preserved():
    batches = [DataBatch(data=[np.ones((4, 2), np.float32)],
                         label=[np.zeros((4,), np.float32)], pad=i)
               for i in range(3)]
    pf = DevicePrefetcher(iter(batches))
    out = list(pf)
    assert [b.pad for b in out] == [0, 1, 2]
    assert all(isinstance(b, DataBatch) for b in out)
    assert all(isinstance(b.data[0], mx.nd.NDArray) for b in out)


def test_device_prefetcher_reset_replays_resettable_source():
    base = NDArrayIter(np.arange(32, dtype=np.float32).reshape(8, 4),
                       np.arange(8, dtype=np.float32), batch_size=4)
    pf = DevicePrefetcher(base, depth=2)
    assert len(list(pf)) == 2
    pf.reset()
    assert len(list(pf)) == 2
    pf.close()
    assert _wait_threads_gone()


def test_legacy_prefetching_iter_actually_prefetches():
    base = NDArrayIter(np.arange(48, dtype=np.float32).reshape(12, 4),
                       np.arange(12, dtype=np.float32), batch_size=4)
    it = PrefetchingIter(base)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 4)
    it.reset()
    assert len(list(it)) == 3
    it.close()
    assert _wait_threads_gone()


# ----------------------------------------------------------------------
# AsyncDecodeIter
# ----------------------------------------------------------------------

def test_async_decode_iter_in_order_batches():
    def decode(i):
        time.sleep(0.001 * (i % 3))   # jitter the completion order
        return i * 10

    it = AsyncDecodeIter(decode, range(20), batch_size=4, n_workers=4)
    assert list(it) == [[i * 10 for i in range(j, j + 4)]
                        for j in range(0, 20, 4)]
    with pytest.raises(StopIteration):
        next(it)


def test_async_decode_iter_drops_partial_batch():
    it = AsyncDecodeIter(lambda i: i, range(10), batch_size=4,
                         n_workers=2)
    assert len(list(it)) == 2    # 10 // 4, trailing 2 samples dropped


def test_async_decode_iter_exception_in_batch_order():
    def decode(i):
        if i == 6:
            raise RuntimeError("bad sample 6")
        return i

    it = AsyncDecodeIter(decode, range(12), batch_size=4, n_workers=4)
    assert next(it) == [0, 1, 2, 3]
    with pytest.raises(RuntimeError, match="bad sample 6"):
        next(it)       # the batch containing sample 6
    it.close()


def test_async_decode_iter_close_cancels_pending():
    started = []

    def decode(i):
        started.append(i)
        time.sleep(0.01)
        return i

    it = AsyncDecodeIter(decode, range(64), batch_size=4, n_workers=2,
                         lookahead=2)
    next(it)
    it.close()
    n_started = len(started)
    time.sleep(0.1)
    # nothing new scheduled after close (running samples may finish)
    assert len(started) <= n_started + 2


def test_async_decode_iter_close_joins_pool_threads():
    """ISSUE 13 satellite: close() must JOIN the decode workers, not
    just signal them — with wait=False the non-daemon pool threads were
    still winding down when the conftest 2 s thread-leak grace sampled
    them on a loaded host (the known test_real_data teardown flake)."""
    import threading

    it = AsyncDecodeIter(lambda i: i, range(32), batch_size=4,
                         n_workers=4, lookahead=2)
    next(it)
    pool_threads = list(it._pool._threads)
    assert any(t.is_alive() for t in pool_threads)
    it.close()
    # joined INSIDE close — zero grace needed, nothing for the conftest
    # leak guard to race against
    assert all(not t.is_alive() for t in pool_threads)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("mxtpu-decode")]
    it.close()                                    # idempotent


def test_closing_thread_registry_prunes_dead_threads():
    """OS thread idents are reused: an ident left registered after its
    thread exited could hand the conftest leak guard's long grace to a
    LATER genuinely-leaked thread (and the registry would grow without
    bound).  closing_thread_idents() must prune exited threads."""
    from mxnet_tpu.io.prefetch import closing_thread_idents

    it = AsyncDecodeIter(lambda i: i, range(8), batch_size=4,
                         n_workers=2, lookahead=1)
    next(it)
    pool_threads = list(it._pool._threads)
    it.close()                     # registers, then joins the workers
    assert all(not t.is_alive() for t in pool_threads)
    dead_idents = {t.ident for t in pool_threads}
    assert not closing_thread_idents() & dead_idents


# ----------------------------------------------------------------------
# ImageRecordIter preprocess_threads plumbing (pure-Python decode path)
# ----------------------------------------------------------------------

def _write_rec(tmp_path, n=16, edge=32):
    import cv2
    path = str(tmp_path / "pipe.rec")
    w = recordio.MXRecordIO(path, "w")
    for i in range(n):
        img = np.full((edge, edge, 3), (i * 9) % 255, np.uint8)
        _, buf = cv2.imencode(".png", img)    # lossless: exact compare
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                              buf.tobytes()))
    w.close()
    return path


def test_image_record_iter_honors_preprocess_threads(tmp_path,
                                                     monkeypatch):
    from mxnet_tpu.utils import native
    monkeypatch.setattr(native, "available", lambda: False)
    path = _write_rec(tmp_path)
    it1 = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 24, 24),
                                batch_size=4, preprocess_threads=1)
    it4 = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 24, 24),
                                batch_size=4, preprocess_threads=4)
    assert it1._async_iter is None        # synchronous decode
    assert it4._async_iter is not None    # threaded fan-out is LIVE
    assert it4._async_iter._n_workers == 4
    for b1, b4 in zip(it1, it4):
        np.testing.assert_array_equal(b1.data[0].asnumpy(),
                                      b4.data[0].asnumpy())
        np.testing.assert_array_equal(b1.label[0].asnumpy(),
                                      b4.label[0].asnumpy())
    # epoch restart rebuilds the fan-out and yields the same count
    it4.reset()
    assert len(list(it4)) == 4
    it1.close()
    it4.close()


def test_image_record_iter_determinism_mode_stays_synchronous(
        tmp_path, monkeypatch):
    from mxnet_tpu import debug
    from mxnet_tpu.utils import native
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(debug, "determinism_enabled", lambda: True)
    path = _write_rec(tmp_path, n=8)
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 24, 24),
                               batch_size=4, preprocess_threads=4)
    assert it._async_iter is None
    assert len(list(it)) == 2


def test_recordio_read_idx_thread_safe(tmp_path):
    path = str(tmp_path / "mt")
    w = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    for i in range(32):
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0),
            bytes([i]) * (50 + i)))
    w.close()
    r = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "r")
    errors = []

    def hammer(tid):
        try:
            rs = np.random.RandomState(tid)
            for _ in range(100):
                k = int(rs.randint(32))
                header, payload = recordio.unpack(r.read_idx(k))
                assert float(header.label) == float(k)
                assert payload == bytes([k]) * (50 + k)
        except Exception as e:  # noqa: BLE001 — reported to main thread
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    r.close()
    assert all(f.closed for f in r._tl_handles)


# ----------------------------------------------------------------------
# fused, donated Trainer.step
# ----------------------------------------------------------------------

def _tiny_net():
    mx.random.seed(7)
    net = nn.Sequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    return net


def _train(net, optimizer, opt_kw, fused, steps=3):
    os.environ["MXTPU_FUSED_STEP"] = "1" if fused else "0"
    try:
        trainer = gluon.Trainer(net.collect_params(), optimizer, opt_kw)
        loss_fn = gluon.loss.L2Loss()
        rs = np.random.RandomState(0)
        for _ in range(steps):
            x = mx.nd.array(rs.randn(16, 10).astype("float32"))
            y = mx.nd.array(rs.randn(16, 4).astype("float32"))
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(16)
    finally:
        os.environ.pop("MXTPU_FUSED_STEP", None)
    vals = [p.data().asnumpy()
            for _, p in sorted(net.collect_params().items())]
    return vals, trainer


@pytest.mark.parametrize("optimizer,opt_kw", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.1}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2, "wd": 1e-4}),
    ("adamw", {"learning_rate": 1e-2, "wd": 1e-2}),
])
def test_fused_trainer_step_matches_eager(optimizer, opt_kw):
    fused_vals, fused_tr = _train(_tiny_net(), optimizer, dict(opt_kw),
                                  fused=True)
    eager_vals, eager_tr = _train(_tiny_net(), optimizer, dict(opt_kw),
                                  fused=False)
    assert len(fused_tr._fused_jit_cache) == 1    # the jit path RAN
    assert len(eager_tr._fused_jit_cache) == 0    # ... and was off here
    for f, e in zip(fused_vals, eager_vals):
        np.testing.assert_allclose(f, e, rtol=2e-5, atol=2e-6)


def test_fused_trainer_one_program_and_counters():
    net = _tiny_net()
    vals, trainer = _train(net, "adam", {"learning_rate": 1e-3},
                           fused=True, steps=4)
    # one compiled program for the whole group, not one per param
    assert len(trainer._fused_jit_cache) == 1
    assert trainer._optimizer.num_update == 4
    # eager-format states survive for save_states/load_states
    assert all(isinstance(s, tuple) and len(s) == 2
               for s in trainer._states.values())


def test_fused_trainer_save_load_states_roundtrip(tmp_path):
    net = _tiny_net()
    _, trainer = _train(net, "adam", {"learning_rate": 1e-3}, fused=True)
    f = str(tmp_path / "states")
    trainer.save_states(f)
    net2 = _tiny_net()
    _, trainer2 = _train(net2, "adam", {"learning_rate": 1e-3},
                         fused=True)
    trainer2.load_states(f)
    assert trainer2._optimizer.num_update == trainer._optimizer.num_update
    for i, s in trainer._states.items():
        np.testing.assert_allclose(s[0].asnumpy(),
                                   trainer2._states[i][0].asnumpy())


def test_fused_trainer_falls_back_for_unsupported_optimizer():
    net = _tiny_net()
    _, trainer = _train(net, "adagrad", {"learning_rate": 0.05},
                        fused=True)
    assert len(trainer._fused_jit_cache) == 0    # eager path ran


def test_fused_trainer_stale_grad_raises():
    net = _tiny_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.array(np.ones((2, 10), np.float32))
    net(x)    # forward only — no grads
    with pytest.raises(mx.MXNetError, match="Call backward"):
        trainer.step(2)


# ----------------------------------------------------------------------
# end-to-end: decode -> DevicePrefetcher -> donated fused step
# ----------------------------------------------------------------------

def test_pipeline_end_to_end_trains(tmp_path, monkeypatch):
    from mxnet_tpu.utils import native
    monkeypatch.setattr(native, "available", lambda: False)
    path = _write_rec(tmp_path, n=16, edge=28)
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 24, 24),
                               batch_size=8, preprocess_threads=2,
                               std_r=255.0, std_g=255.0, std_b=255.0)
    net = nn.Sequential()
    net.add(nn.Flatten(), nn.Dense(16, activation="relu"))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.L2Loss()
    pf = DevicePrefetcher(it, depth=2)
    n = 0
    for batch in pf:
        data, label = batch.data[0], batch.label[0]
        with autograd.record():
            out = net(data)
            loss = loss_fn(out, mx.nd.zeros(out.shape))
        loss.backward()
        trainer.step(data.shape[0])
        n += 1
    assert n == 2
    s = pf.stats.summary()
    assert s["batches"] == 2 and s["h2d_ms_per_batch"] >= 0
    pf.close()
    it.close()
    assert _wait_threads_gone()


def test_pipeline_spans_go_to_the_one_span_stream(tmp_path):
    """The stages write to telemetry.tracing only: a running profile's
    own B/E list (``profiler.dumps()``) no longer holds a second copy
    of the same intervals under another name."""
    from mxnet_tpu import profiler
    from mxnet_tpu.telemetry import tracing
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.start()
    try:
        pf = DevicePrefetcher(
            (np.ones((4, 2), np.float32) for _ in range(3)))
        list(pf)
    finally:
        profiler.stop()
    names = [s["name"] for s in tracing.spans()]
    assert names.count("io.decode") == 3 and names.count("io.h2d") == 3
    assert "io.wait" in names
    table = profiler.dumps(reset=True)
    assert "pipeline:" not in table and "io.decode" not in table


def test_ndarray_iter_shuffle_cursor_restores_standalone():
    """PR 4 known gap closed (ISSUE 8 satellite): a shuffling
    NDArrayIter's mid-epoch cursor now round-trips in a FRESH process
    with an arbitrary global numpy RNG state — the saved per-epoch
    reshuffle seeds rebuild the exact order, no estimator-path RNG
    replay required."""
    data = np.arange(40, dtype=np.float32).reshape(20, 2)
    label = np.arange(20, dtype=np.float32)
    np.random.seed(0)
    it = NDArrayIter(data, label, batch_size=4, shuffle=True)
    for _ in range(5):
        it.next()                       # epoch 1 consumed
    it.reset()                          # epoch 2 reshuffles in reset()
    it.next()                           # one batch into epoch 2
    saved = it.state_dict()
    assert "shuffle_seeds" in saved and len(saved["shuffle_seeds"]) == 2
    expect = [(it.next().data[0].asnumpy(),
               it.next().label[0].asnumpy()) for _ in range(2)]

    # "fresh process": unrelated RNG history, then restore the cursor
    np.random.seed(98765)
    np.random.rand(17)
    it2 = NDArrayIter(data, label, batch_size=4, shuffle=True)
    it2.set_state(saved)
    got = [(it2.next().data[0].asnumpy(),
            it2.next().label[0].asnumpy()) for _ in range(2)]
    for (ed, el), (gd, gl) in zip(expect, got):
        np.testing.assert_array_equal(ed, gd)
        np.testing.assert_array_equal(el, gl)


def test_ndarray_iter_shuffle_same_stream_replay_still_works():
    """The estimator resume path (restore numpy RNG, re-enter the epoch
    the same way) must keep producing the identical order."""
    data = np.arange(24, dtype=np.float32).reshape(12, 2)
    np.random.seed(3)
    it = NDArrayIter(data, batch_size=4, shuffle=True)
    a = [it.next().data[0].asnumpy() for _ in range(3)]
    np.random.seed(3)
    it2 = NDArrayIter(data, batch_size=4, shuffle=True)
    b = [it2.next().data[0].asnumpy() for _ in range(3)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_image_record_iter_shuffle_cursor_restores_standalone(
        tmp_path, monkeypatch):
    """Same standalone-restore contract for the rec-file iterator: the
    saved shuffle seeds rebuild the epoch order in a fresh process."""
    from mxnet_tpu.utils import native
    monkeypatch.setattr(native, "available", lambda: False)
    path = _write_rec(tmp_path, n=16)
    np.random.seed(11)
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 24, 24),
                               batch_size=4, shuffle=True,
                               preprocess_threads=1)
    list(it)                       # epoch 1
    it.reset()                     # epoch 2 reshuffles
    it.next()
    saved = it.state_dict()
    expect = it.next()
    np.random.seed(777)            # unrelated "fresh process" RNG state
    it2 = mx.io.ImageRecordIter(path_imgrec=path,
                                data_shape=(3, 24, 24), batch_size=4,
                                shuffle=True, preprocess_threads=1)
    it2.set_state(saved)
    got = it2.next()
    np.testing.assert_array_equal(expect.label[0].asnumpy(),
                                  got.label[0].asnumpy())
    np.testing.assert_array_equal(expect.data[0].asnumpy(),
                                  got.data[0].asnumpy())
    it.close()
    it2.close()
