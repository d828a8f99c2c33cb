"""The fused training step fed by the input pipeline: a ``.rec`` of JPEGs
read by ``mx.io.ImageRecordIter`` under ``mx.io.DevicePrefetcher``, each
batch handed to ``DataParallelTrainer.step``::

    it = mx.io.ImageRecordIter(path_imgrec, data_shape=(3, 224, 224), ...)
    for batch in mx.io.DevicePrefetcher(it, mesh=mesh):
        loss = trainer.step(batch.data[0], batch.label[0])

``prepare`` is ``train_fused``'s (the same program, checked against the same
reference on one synthetic batch) and then writes the file from the seed,
builds the pipeline and checks its first batch against a plain decode of the
same records; ``step`` takes the next batch and dispatches one step, starting
the next epoch where one ends; ``finish`` counts what every epoch delivered.
All sizes from the traffic file.

``MXTPU_BENCH_CONTROL=next_record`` hands the plain decode each record's
successor, ``=double_batch`` delivers one batch of every epoch twice: both
must end ``correct: false``.
"""
from __future__ import annotations

import atexit
import collections
import json
import os
import shutil
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from readers.spans import WALL_STAMP
from runners import train_fused

# Mean absolute difference, in levels of 256, between a delivered image and
# the plain decode of a record (the image or its mirror, whichever is
# nearer), the largest over the first batch's samples.  The right record
# reads what two bilinear resizers from 256 to 224 differ by on noise of
# +-12 levels: 0.129 to 0.130 (the iterator's C++ resizer against cv2's),
# 0.620 to 0.629 on the Python path (its resize rounds through uint8 once
# more).  The next record, another picture, reads 66.5 to 67.9 at the
# smallest of a batch (this sandbox's CPU, 8 seeds at the cell's sizes, both
# paths, PR 37; each run prints both readings on its check line).  The limit
# is the geometric middle of 0.63 and 66.5: a factor of ten from each.
IMAGE_MAD_LIMIT = 6.5
CONTROLS = ("next_record", "double_batch")


def _say(key, value):
    print(f"# {key}: {json.dumps(value)}", flush=True)


def _threads():
    return min(8, os.cpu_count() or 1)


def _picture(seed, k, edge):
    """Record ``k``'s picture, a function of the seed and ``k`` alone: a
    coarse grid of colours resized by a cubic, plus noise."""
    import cv2
    rng = np.random.RandomState([seed % 2**32, k])
    coarse = rng.randint(0, 256, (max(edge // 9, 2),) * 2 + (3,))
    img = cv2.resize(coarse.astype(np.uint8), (edge, edge),
                     interpolation=cv2.INTER_CUBIC)
    noise = rng.randint(-12, 13, img.shape)
    return np.clip(img.astype(np.int16) + noise, 0, 255).astype(np.uint8)


def _jpeg(seed, k, rec):
    """The JPEG bytes of record ``k`` (RGB in, as ``pack_img`` takes it)."""
    import cv2
    ok, buf = cv2.imencode(
        ".jpg", cv2.cvtColor(_picture(seed, k, rec["edge"]),
                             cv2.COLOR_RGB2BGR),
        [cv2.IMWRITE_JPEG_QUALITY, rec["jpeg_quality"]])
    if not ok:
        raise RuntimeError(f"cv2 cannot encode record {k}")
    return buf.tobytes()


def write_rec(path, seed, rec, classes):
    """The ``.rec`` + ``.idx`` pair as ``tools/im2rec.py`` leaves it:
    ``IRHeader`` (label = number % classes, id = number) + JPEG."""
    from mxnet_tpu import recordio
    out = recordio.MXIndexedRecordIO(path[:-4] + ".idx", path, "w")
    with ThreadPoolExecutor(_threads()) as pool:
        for k, jpeg in enumerate(pool.map(
                lambda k: _jpeg(seed, k, rec), range(rec["records"]))):
            out.write_idx(k, recordio.pack(
                recordio.IRHeader(0, float(k % classes), k, 0), jpeg))
    out.close()


def plain_batch(seed, records, rec, size, mean, std):
    """What the iterator should deliver for these records, without its
    mirror: decode, short side to ``size``, centre crop, normalise, NCHW
    float32.  cv2 alone; nothing of the program."""
    import cv2

    def plain(k):
        img = cv2.imdecode(np.frombuffer(_jpeg(seed, int(k), rec), np.uint8),
                           cv2.IMREAD_COLOR)
        img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)
        h, w = img.shape[:2]
        if h < w:
            h, w = size, w * size // h
        else:
            h, w = h * size // w, size
        img = cv2.resize(img, (w, h), interpolation=cv2.INTER_LINEAR)
        y0, x0 = (h - size) // 2, (w - size) // 2
        img = img[y0:y0 + size, x0:x0 + size].astype(np.float32)
        return ((img - mean) / std).transpose(2, 0, 1)

    with ThreadPoolExecutor(_threads()) as pool:
        return np.stack(list(pool.map(plain, records)))


def image_mad_levels(got, plain, std):
    """Per sample: mean |delivered - plain| in levels of 256, against the
    plain image or its mirror, whichever is nearer (the mirror's draw is the
    iterator's own)."""
    scale = std.reshape(1, 3, 1, 1)
    straight = np.abs((got - plain) * scale).mean(axis=(1, 2, 3))
    mirrored = np.abs((got - plain[..., ::-1]) * scale).mean(axis=(1, 2, 3))
    return np.minimum(straight, mirrored)


def epoch_order(it, records):
    """The records of the iterator's current epoch in the order it delivers
    them, replayed from its public cursor: one seed an epoch, each shuffling
    the order the last one left."""
    order = np.arange(records)
    for s in it.state_dict()["shuffle_seeds"]:
        np.random.RandomState(int(s)).shuffle(order)
    return order


def _batches(st):
    """Every batch of every epoch, for ever: the loop a training script
    writes, with the iterator reset at each epoch's end.  Keeps each
    delivered batch's labels (the device array, 4 B a sample) and the order
    the iterator's cursor gives the epoch."""
    while True:
        st.epochs.append([])
        st.orders.append(epoch_order(st.iterator, st.records))
        for batch in st.prefetcher:
            delivered = st.epochs[-1]
            delivered.append(batch.label[0].data)
            yield batch
            if st.control == "double_batch" and len(delivered) == 2:
                delivered.append(batch.label[0].data)
                yield batch
        st.prefetcher.reset()


def prepare(job):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.utils import native

    sizes, traffic = job.sizes, job.traffic
    control = os.environ.get("MXTPU_BENCH_CONTROL")
    if control not in (None, "") + CONTROLS:
        raise ValueError(f"MXTPU_BENCH_CONTROL={control!r}: this cell knows "
                         f"{CONTROLS}")
    st = train_fused.prepare(job)
    st.control = control or None
    st.annotate = jax.profiler.TraceAnnotation
    rec, size = traffic["rec"], sizes["image_size"]
    st.labels = collections.Counter(
        float(k % sizes["classes"]) for k in range(rec["records"]))

    with job.phase("native_library"):
        has_native = native.available()
    st.tmp = tempfile.mkdtemp(prefix="mxtpu_bench_rec_")
    atexit.register(shutil.rmtree, st.tmp, ignore_errors=True)
    path = os.path.join(st.tmp, "train.rec")
    with job.phase("write_rec"):
        write_rec(path, job.seed, rec, sizes["classes"])
    with job.phase("pipeline"):
        np.random.seed(job.seed % 2**32)
        st.iterator = mx.io.ImageRecordIter(
            path_imgrec=path, data_shape=(3, size, size),
            batch_size=st.global_batch, **traffic["iterator"])
        st.prefetcher = mx.io.DevicePrefetcher(
            st.iterator, depth=traffic["prefetch_depth"], mesh=st.mesh)
        st.epochs, st.orders = [], []
        st.records, st.classes = rec["records"], sizes["classes"]
        st.batches = _batches(st)
    _say("rec", {"file_bytes": os.path.getsize(path),
                 "records": rec["records"], "native": has_native,
                 "decode_threads": traffic["iterator"]["preprocess_threads"],
                 "batches_per_epoch": rec["records"] // st.global_batch,
                 "control": st.control})

    with job.phase("data_check"):
        it = traffic["iterator"]
        mean = np.array([it["mean_r"], it["mean_g"], it["mean_b"]],
                        np.float32)
        std = np.array([it["std_r"], it["std_g"], it["std_b"]], np.float32)
        st.first = next(st.batches)
        order = st.orders[0][:st.global_batch]
        got = np.asarray(st.first.data[0].data, np.float32)
        labels = np.asarray(st.first.label[0].data)
        shift = 1 if st.control == "next_record" else 0
        mine = (order + shift) % rec["records"]
        other = (order + 1 - shift) % rec["records"]
        mad = image_mad_levels(
            got, plain_batch(job.seed, mine, rec, size, mean, std), std)
        mad_other = image_mad_levels(
            got, plain_batch(job.seed, other, rec, size, mean, std), std)
        labels_equal = bool(np.array_equal(
            labels, (mine % sizes["classes"]).astype(labels.dtype)))
        st.checks["first_batch_vs_plain_decode"] = (
            bool(labels_equal and got.shape == (st.global_batch, 3, size,
                                                size)
                 and mad.max() <= IMAGE_MAD_LIMIT),
            f"{len(mine)} records: labels "
            f"{'equal' if labels_equal else 'DIFFER'}; mean |image - plain| "
            f"largest {mad.max():.3f} of 256 levels (limit "
            f"{IMAGE_MAD_LIMIT}); against each record's "
            f"{'own' if shift else 'next'} picture smallest "
            f"{mad_other.min():.3f}; delivered {got.dtype} "
            f"{list(got.shape)}")
    return st


def step(st, i):
    """Dispatch step ``i`` on the pipeline's next batch; the returned call
    waits for that step and gives its loss."""
    # where the profiler's session began, for readers/io.py
    with st.annotate(WALL_STAMP + str(time.time_ns())):
        pass
    batch, st.first = st.first or next(st.batches), None
    handle = st.trainer.step(batch.data[0], batch.label[0])
    return lambda: float(handle.asnumpy())


def finish(st):
    """After the window, from the labels kept: every finished epoch
    delivered the file's labels, each as often as the file holds it (a count
    that knows nothing of the iterator), and every epoch, the one under way
    too, delivered them in the order its cursor gave (so a batch dropped or
    delivered twice shows wherever the window ends: at the 160 samples/s of
    PR 37 no epoch of 8192 finishes in a window)."""
    st.prefetcher.close()
    st.iterator.close()
    shutil.rmtree(st.tmp, ignore_errors=True)
    delivered = [np.concatenate([np.asarray(a) for a in epoch])
                 for epoch in st.epochs]
    miscounted = [i for i, got in enumerate(delivered[:-1])
                  if collections.Counter(got.tolist()) != st.labels]
    out_of_order = [i for i, (got, order) in enumerate(zip(delivered,
                                                           st.orders))
                    if len(got) > len(order) or not np.array_equal(
                        got, (order[:len(got)] % st.classes).astype(got.dtype))]
    st.checks["every_epoch_delivered_the_file_once"] = (
        not miscounted and not out_of_order,
        f"{len(delivered) - 1} finished epochs of {st.records} records, "
        f"{len(miscounted)} with other label counts than the file's "
        f"{miscounted[:4]}; {sum(map(len, delivered))} samples delivered in "
        f"{len(delivered)} epochs, {len(out_of_order)} of them not in their "
        f"cursor's order {out_of_order[:4]}")
    return train_fused.finish(st)
