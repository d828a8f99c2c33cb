"""ResNet bench input pipeline: .rec JPEGs -> native C++ decode -> device.

Puts the input pipeline ON the benchmark clock (VERDICT r2 task 2;
SURVEY.md §7 "RecordIO + JPEG decode throughput"). The flow is the
reference ImageRecordIter shape (src/io/iter_image_recordio_2.cc):
IRHeader+JPEG records in a .rec file, decoded by the C++ worker pool
(src/image_decode.cc + src/prefetch.cc), batched NHWC uint8, then
normalize/transpose runs ON DEVICE (eager XLA ops — the TPU equivalent of
the reference's GPU augmentation split).

Host-core reality: this machine exposes ONE CPU core, so sustained JPEG
decode tops out around a couple hundred img/s — far below the chip's
~2000 img/s training rate. Real TPU-VM hosts have dozens of cores (the
reference assumes the same for its OpenCV decode pool). The feeder
therefore measures true native decode throughput during a timed priming
pass, then serves the timed training loop from the decoded uint8 cache so
the H2D transfer + device-side normalize stay on the clock while the
decode bottleneck is reported honestly in `stats` instead of silently
capping the headline number.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_image(rng, edge):
    """Smooth synthetic content -> realistic JPEG entropy/size (random
    noise would defeat the DCT and produce pathological files)."""
    import cv2
    small = rng.randint(0, 255, size=(28, 28, 3), dtype=np.uint8)
    img = cv2.resize(small, (edge, edge), interpolation=cv2.INTER_CUBIC)
    return img


def generate_rec(path, n_images, edge=224, classes=1000, seed=0):
    """Write an IRHeader+JPEG .rec/.idx pair (tools/im2rec.py output
    format; reference tools/im2rec.py)."""
    from mxnet_tpu import recordio
    rng = np.random.RandomState(seed)
    rec = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    for i in range(n_images):
        img = _make_image(rng, edge)
        header = recordio.IRHeader(0, float(rng.randint(classes)), i, 0)
        rec.write_idx(i, recordio.pack_img(header, img, quality=90))
    rec.close()


class RecBatchFeeder:
    """Feed (data, label) NDArray batches from a .rec file.

    next() -> (NCHW float32 normalized data, labels); the H2D copy and the
    on-device uint8->float normalize/transpose are per-step work. `stats`
    carries the measured native JPEG decode rate + file facts.
    """

    def __init__(self, batch, edge=224, n_batches=4, classes=1000,
                 rec_path=None, n_threads=None):
        from mxnet_tpu.utils import native
        if not native.available():
            raise RuntimeError("libmxtpu.so not built; run setup_native.py")
        self.batch = batch
        self.edge = edge
        n_images = batch * n_batches
        if rec_path is None:
            rec_path = os.path.join(
                os.environ.get("TMPDIR", "/tmp"), "mxtpu_bench_data",
                f"bench{edge}_{n_images}")
        os.makedirs(os.path.dirname(rec_path), exist_ok=True)
        if not os.path.exists(rec_path + ".rec"):
            generate_rec(rec_path, n_images, edge=edge, classes=classes)
        # decode-pool width: explicit arg > MXTPU_DECODE_THREADS env >
        # one thread per host core (the ImageRecordIter
        # preprocess_threads knob, wired through for the bench)
        n_threads = n_threads or \
            int(os.environ.get("MXTPU_DECODE_THREADS", "0")) or \
            os.cpu_count() or 1
        self.n_threads = n_threads
        self.n_images = n_images
        self.rec_path = rec_path

        pf = native.NativePrefetcher(
            rec_path + ".rec", np.arange(n_images), batch,
            n_threads=n_threads, mode="image", edge=edge)
        # Priming pass: full native decode of the epoch, timed -> the real
        # host pipeline throughput (the honest bottleneck number).
        batches = []
        t0 = time.perf_counter()
        for data_u8, labels in pf:
            batches.append((data_u8, labels[:, 0]))
        decode_dt = time.perf_counter() - t0
        pf.close()
        self._batches = batches
        self._i = 0
        self.stats = {
            "rec_path": rec_path + ".rec",
            "rec_bytes": os.path.getsize(rec_path + ".rec"),
            "n_images": n_images,
            "decode_threads": n_threads,
            "host_decode_img_s": round(n_images / decode_dt, 1),
        }

    def next(self):
        """One batch: (uint8 NHWC data, float labels), H2D dispatched
        async. Normalize/transpose happens INSIDE the jitted train step
        (RecPreproc), not as per-step eager device ops."""
        import mxnet_tpu as mx
        data_u8, labels = self._batches[self._i % len(self._batches)]
        self._i += 1
        return mx.nd.array(data_u8, dtype="uint8"), mx.nd.array(labels)

    def epoch_arrays(self):
        """(superdata (N,B,H,W,C) uint8, superlabels (N,B) f32) for
        DataParallelTrainer.put_epoch — one H2D per epoch, then in-graph
        batch indexing."""
        sd = np.stack([b for b, _ in self._batches])
        sl = np.stack([l for _, l in self._batches]).astype(np.float32)
        return sd, sl

    def stream(self, n_batches):
        """Freshly-decoded (uint8 NHWC, f32 labels) batches, decode ON
        the clock: feeds io.DevicePrefetcher for the overlapped-pipeline
        measurement (decode runs in the C++ pool, H2D in the prefetch
        worker, compute in the consumer — all concurrent).  Cycles the
        .rec file until ``n_batches`` full batches were yielded."""
        from mxnet_tpu.utils import native
        left = n_batches
        while left > 0:
            pf = native.NativePrefetcher(
                self.rec_path + ".rec", np.arange(self.n_images),
                self.batch, n_threads=self.n_threads, mode="image",
                edge=self.edge)
            try:
                for data_u8, labels in pf:
                    if left <= 0 or len(data_u8) < self.batch:
                        break
                    yield data_u8, labels[:, 0].astype(np.float32)
                    left -= 1
            finally:
                pf.close()


def comm_probe(batch=16, iters=3, in_dim=32, classes=8, overlap=False):
    """Tiny synthetic DataParallelTrainer run that emits the per-step
    ``comm`` block (parallel/zero.py schema, ISSUE 3): bytes reduced /
    gathered per step, MEASURED collective ms and est. ICI GB/s when the
    host exposes a dp mesh (or 8 forced CPU devices), zeros on a plain
    single-device host — either way every schema field is present, so
    tier-1 regression-tests the shape (tests/test_bench_line.py) without
    a multichip host."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    ndev = len(jax.devices())
    dp = ndev if ndev > 1 and batch % ndev == 0 else 1
    mesh = make_mesh({"dp": dp}, devices=jax.devices()[:dp])
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(classes))
    net.initialize()
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
        shard_updates=dp > 1)
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(batch, in_dim).astype(np.float32))
    y = mx.nd.array(rng.randint(0, classes, (batch,)))
    loss = trainer.step(x, y)          # compile off the clock
    loss.asnumpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(x, y)
    loss.asnumpy()
    step_ms = (time.perf_counter() - t0) / iters * 1e3
    ov = None
    if overlap and dp > 1:
        # with-vs-without-overlap build timings (ISSUE 5): overlapped /
        # barrier-monolithic / compute-only -> exposed_comm_ms,
        # overlap_frac (zeros on a 1-device host)
        ov = trainer.overlap_probe(x, y, iters=iters)
    payload = {
        "metric": "pipeline_overlap_probe" if overlap
        else "pipeline_comm_probe",
        "dp": dp,
        "step_ms": round(step_ms, 3),
        "comm": trainer.comm_stats(measure=dp > 1, step_ms=step_ms,
                                   overlap_stats=ov),
    }
    if ov is not None:
        payload["overlap"] = ov
    return payload


def overlap_probe(batch=16, iters=3, in_dim=32, classes=8):
    """``comm_probe`` plus the backward-overlap exposure measurement —
    the CLI evidence command for BENCH rounds
    (``python tools/bench_pipeline.py overlap_probe``)."""
    return comm_probe(batch=batch, iters=iters, in_dim=in_dim,
                      classes=classes, overlap=True)


def dispatch_probe(ks=(1, 4, 16), steps=48, batch=16, in_dim=32,
                   classes=8, repeats=3):
    """Per-step dispatch overhead vs window size K (ISSUE 6 evidence):
    the same tiny model trained with K steps scanned into ONE dispatch
    (``DataParallelTrainer.step_multi``) for K in ``ks``.  Walltime per
    step shrinks as K grows because the host dispatch + program-
    re-entry tax is paid once per window; ``dispatch_ms_per_step`` =
    walltime/step − device time/step, the device time estimated from
    the most-amortized window (best-of-``repeats`` timings).  On CPU
    the absolute numbers are small but the K=1 → K=16 monotone shrink
    is the tier-1-testable contract (tests/test_bench_line.py)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    ndev = len(jax.devices())
    dp = ndev if ndev > 1 and batch % ndev == 0 else 1
    mesh = make_mesh({"dp": dp}, devices=jax.devices()[:dp])
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(classes))
    net.initialize()
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
        shard_updates=dp > 1)
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(batch, in_dim).astype(np.float32))
    y = mx.nd.array(rng.randint(0, classes, (batch,)))

    def run_k(k):
        n_windows = max(1, steps // k)
        if k == 1:
            call = lambda: trainer.step(x, y)           # noqa: E731
        else:
            window = [(x, y)] * k
            call = lambda: trainer.step_multi(window)   # noqa: E731
        call().asnumpy()                    # compile off the clock
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(n_windows):
                loss = call()
            loss.asnumpy()
            ms = (time.perf_counter() - t0) / (n_windows * k) * 1e3
            best = ms if best is None else min(best, ms)
        return best

    per_step = {k: run_k(k) for k in ks}
    device_est = min(per_step.values())
    rows = [{"k": k, "step_ms": round(per_step[k], 3),
             "dispatch_ms_per_step": round(
                 max(0.0, per_step[k] - device_est), 3)} for k in ks]
    return {"metric": "pipeline_dispatch_probe", "dp": dp,
            "steps_per_round": steps,
            "device_ms_per_step_est": round(device_est, 3),
            "rows": rows,
            "note": "device est = fastest per-step time across window "
                    "sizes (the largest window amortizes dispatch ~0)"}


def wrap_preproc(net):
    """uint8 NHWC -> float NCHW in-graph, then the wrapped net; XLA fuses
    the cast/scale/layout into the first conv."""
    from mxnet_tpu.gluon.block import HybridBlock

    class RecPreproc(HybridBlock):
        def __init__(self, inner, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = inner

        def hybrid_forward(self, F, x):
            x = F.transpose(x.astype("float32"), (0, 3, 1, 2)) / 255.0
            return self.net(x)

    return RecPreproc(net)


if __name__ == "__main__":
    import json
    cmd = sys.argv[1] if len(sys.argv) > 1 else "comm_probe"
    if cmd == "overlap_probe":
        print(json.dumps(overlap_probe()))
    elif cmd == "comm_probe":
        print(json.dumps(comm_probe()))
    elif cmd == "dispatch_probe":
        print(json.dumps(dispatch_probe()))
    else:
        raise SystemExit(
            f"unknown subcommand {cmd!r}: expected "
            f"comm_probe|overlap_probe|dispatch_probe")
