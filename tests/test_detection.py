"""Detection/segmentation ops + models.

Mirrors the reference tests: tests/python/unittest/test_contrib_operator.py
(box_nms, box_iou, bipartite_matching), test_operator.py (ROIPooling),
gluoncv model unit tests (SSD/YOLO/seg forward shapes).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _tape

nd = mx.nd


def test_box_iou():
    a = nd.array([[[0, 0, 2, 2], [1, 1, 3, 3]]])
    b = nd.array([[[0, 0, 2, 2], [10, 10, 11, 11]]])
    iou = nd.contrib.box_iou(a, b).asnumpy()
    assert np.allclose(iou[0, 0, 0], 1.0)
    assert np.allclose(iou[0, 1, 0], 1.0 / 7.0, atol=1e-5)
    assert np.allclose(iou[0, :, 1], 0.0)


def test_box_iou_center_format():
    # both in center format: (cx, cy, w, h) = (1,1,2,2) -> corners (0,0,2,2)
    a = nd.array([[[1.0, 1.0, 2.0, 2.0]]])
    b = nd.array([[[1.0, 1.0, 2.0, 2.0], [1.0, 1.0, 4.0, 4.0]]])
    iou = nd.contrib.box_iou(a, b, format="center").asnumpy()
    assert np.allclose(iou[0, 0, 0], 1.0)
    assert np.allclose(iou[0, 0, 1], 0.25)


def test_box_nms_suppression_and_sort():
    dets = nd.array([[[0, 0.8, 0.1, 0.1, 2, 2],
                      [0, 0.9, 0, 0, 2, 2],
                      [1, 0.7, 5, 5, 6, 6],
                      [0, 0.05, 0, 0, 1, 1]]])
    out = nd.contrib.box_nms(dets, overlap_thresh=0.5, valid_thresh=0.1,
                             coord_start=2, score_index=1,
                             id_index=0).asnumpy()[0]
    # sorted by score desc; overlapping same-class 0.8 box suppressed
    assert out[0, 1] == pytest.approx(0.9)
    assert out[1, 1] == -1.0
    assert out[2, 1] == pytest.approx(0.7)
    assert out[3, 1] == -1.0


def test_box_nms_force_suppress():
    # different class, same box: survives without force, dies with force
    dets = nd.array([[[0, 0.9, 0, 0, 2, 2], [1, 0.8, 0, 0, 2, 2]]])
    keep = nd.contrib.box_nms(dets, id_index=0, coord_start=2,
                              score_index=1).asnumpy()[0]
    assert (keep[:, 1] > 0).sum() == 2
    sup = nd.contrib.box_nms(dets, id_index=0, coord_start=2, score_index=1,
                             force_suppress=True).asnumpy()[0]
    assert (sup[:, 1] > 0).sum() == 1


def test_box_nms_topk():
    n = 10
    rows = [[0, 1.0 - 0.05 * i] + [i * 3.0, i * 3.0, i * 3.0 + 2, i * 3.0 + 2]
            for i in range(n)]
    dets = nd.array([rows])
    out = nd.contrib.box_nms(dets, topk=4, coord_start=2, score_index=1,
                             id_index=0).asnumpy()[0]
    assert (out[:, 1] > 0).sum() == 4


def test_box_encode_decode_roundtrip():
    anchors = nd.array([[[0.0, 0.0, 1.0, 1.0], [0.5, 0.5, 1.5, 1.5]]])
    gt = nd.array([[[0.1, 0.1, 0.9, 1.1]]])
    samples = nd.array([[1.0, 1.0]])
    matches = nd.array([[0.0, 0.0]])
    targets, masks = nd.contrib.box_encode(samples, matches, anchors, gt)
    dec = nd.contrib.box_decode(targets, anchors, format="corner").asnumpy()
    assert np.allclose(dec[0, 0], [0.1, 0.1, 0.9, 1.1], atol=1e-5)
    assert np.allclose(dec[0, 1], [0.1, 0.1, 0.9, 1.1], atol=1e-5)


def test_bipartite_matching():
    m = nd.array([[[0.9, 0.1], [0.8, 0.7]]])
    r, c = nd.contrib.bipartite_matching(m)
    assert r.asnumpy().tolist() == [[0.0, 1.0]]
    assert c.asnumpy().tolist() == [[0.0, 1.0]]


def test_roi_align_shape_and_values():
    feat = nd.array(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    rois = nd.array([[0, 0, 0, 3, 3]])
    out = nd.contrib.ROIAlign(feat, rois, pooled_size=(2, 2),
                              spatial_scale=1.0).asnumpy()
    assert out.shape == (1, 1, 2, 2)
    # values increase left->right and top->bottom
    assert out[0, 0, 0, 0] < out[0, 0, 0, 1] < out[0, 0, 1, 1]


def test_roi_pooling():
    feat = nd.array(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    rois = nd.array([[0, 0, 0, 3, 3]])
    out = nd.contrib.ROIPooling(feat, rois, pooled_size=(2, 2),
                                spatial_scale=1.0).asnumpy()
    assert out.shape == (1, 1, 2, 2)
    assert out[0, 0, 1, 1] == 15.0     # max of bottom-right bin


def test_multibox_prior():
    x = nd.zeros((1, 3, 4, 4))
    anchors = nd.contrib.MultiBoxPrior(x, sizes=[0.5, 0.25],
                                       ratios=[1, 2]).asnumpy()
    assert anchors.shape == (1, 4 * 4 * 3, 4)
    # first anchor centered at (0.125, 0.125) with size 0.5
    assert np.allclose(anchors[0, 0], [0.125 - 0.25, 0.125 - 0.25,
                                       0.125 + 0.25, 0.125 + 0.25])


def test_multibox_target_assigns_positive():
    anchors = nd.array([[[0.0, 0.0, 0.5, 0.5], [0.5, 0.5, 1.0, 1.0]]])
    label = nd.array([[[1.0, 0.45, 0.45, 1.0, 1.0]]])   # matches anchor 2
    cls_pred = nd.zeros((1, 3, 2))
    bt, bm, ct = nd.contrib.MultiBoxTarget(anchors, label, cls_pred)
    ct = ct.asnumpy()
    assert ct.shape == (1, 2)
    assert ct[0, 1] == 2.0       # class 1 -> target 2 (0 is background)
    assert bm.asnumpy()[0].reshape(2, 4)[1].all()


def test_bilinear_resize():
    x = nd.array(np.arange(4, dtype=np.float32).reshape(1, 1, 2, 2))
    y = nd.contrib.BilinearResize2D(x, height=4, width=4)
    assert y.shape == (1, 1, 4, 4)
    assert np.allclose(y.asnumpy()[0, 0, 0, 0], 0.0, atol=1e-5)


def test_adaptive_avg_pool():
    x = nd.array(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    y = nd.contrib.AdaptiveAvgPooling2D(x, output_size=2).asnumpy()
    assert y.shape == (1, 1, 2, 2)
    assert y[0, 0, 0, 0] == pytest.approx(np.mean([0, 1, 4, 5]))


@pytest.mark.slow
def test_ssd_forward():
    from mxnet_tpu.gluon.model_zoo.vision.ssd import ssd_300_resnet34_v1
    net = ssd_300_resnet34_v1()
    net.initialize()
    x = nd.random.uniform(shape=(1, 3, 128, 128))
    prev = _tape.set_training(True)
    try:
        cls_p, box_p, anch = net(x)
    finally:
        _tape.set_training(prev)
    n = anch.shape[1]
    assert cls_p.shape == (1, n, 21)
    assert box_p.shape == (1, n, 4)
    prev = _tape.set_training(False)
    try:
        ids, scores, bboxes = net(x)
    finally:
        _tape.set_training(prev)
    assert ids.shape == (1, n, 1)
    assert bboxes.shape == (1, n, 4)


@pytest.mark.slow
def test_yolo3_forward():
    from mxnet_tpu.gluon.model_zoo.vision.yolo import yolo3_darknet53
    net = yolo3_darknet53(classes=20)
    net.initialize()
    x = nd.random.uniform(shape=(1, 3, 64, 64))
    prev = _tape.set_training(True)
    try:
        preds, boxes, scores = net(x)
    finally:
        _tape.set_training(prev)
    assert len(preds) == 3
    assert preds[0].shape[1] == 3 * (5 + 20)
    prev = _tape.set_training(False)
    try:
        ids, sc, bb = net(x)
    finally:
        _tape.set_training(prev)
    assert bb.shape[-1] == 4


@pytest.mark.slow
def test_segmentation_models():
    from mxnet_tpu.gluon.model_zoo.vision.segmentation import get_fcn
    net = get_fcn(nclass=5)
    net.initialize()
    x = nd.random.uniform(shape=(1, 3, 32, 32))
    prev = _tape.set_training(True)
    try:
        out, aux = net(x)
    finally:
        _tape.set_training(prev)
    assert out.shape == (1, 5, 32, 32)
    assert aux.shape == (1, 5, 32, 32)
    pred = net.evaluate(x)
    assert pred.shape == (1, 5, 32, 32)


# zoo registration enumeration (darknet53 full forward)
def test_get_model_detection_names():
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    net = get_model("darknet53")
    net.initialize()
    out = net(nd.random.uniform(shape=(1, 3, 64, 64)))
    assert out.shape == (1, 1000)


@pytest.mark.slow
def test_faster_rcnn_forward():
    from mxnet_tpu.gluon.model_zoo.vision.rcnn import \
        faster_rcnn_resnet50_v1b
    net = faster_rcnn_resnet50_v1b()
    net.initialize()
    x = nd.random.uniform(shape=(1, 3, 128, 128))
    prev = _tape.set_training(True)
    try:
        cls_p, box_p, rois, rpn_s, rpn_l, anchors = net(x)
    finally:
        _tape.set_training(prev)
    assert cls_p.shape == (300, 21)
    assert box_p.shape == (300, 80)
    assert rois.shape == (1, 300, 4)
    prev = _tape.set_training(False)
    try:
        ids, scores, bboxes = net(x)
    finally:
        _tape.set_training(prev)
    assert bboxes.shape == (1, 300, 4)
    # rois must lie inside the image
    r = rois.asnumpy()
    assert (r >= 0).all() and (r[..., 0::2] <= 128).all() \
        and (r[..., 1::2] <= 128).all()


@pytest.mark.slow   # model-zoo forward smoke, no unique op coverage
def test_simple_pose():
    """SimplePose (gluoncv simple_pose_resnet.py): trunk -> 3 deconvs ->
    per-joint heatmaps at input/4; on-device argmax decode."""
    from mxnet_tpu.gluon.model_zoo.vision.pose import (heatmap_to_coord,
                                                       simple_pose_resnet18_v1b)
    net = simple_pose_resnet18_v1b(num_joints=17)
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).randn(1, 3, 64, 64)
                 .astype(np.float32))
    hm = net(x)
    assert hm.shape == (1, 17, 16, 16)
    coords, scores = heatmap_to_coord(hm)
    assert coords.shape == (1, 17, 2) and scores.shape == (1, 17)
    # decoded coords index the max heatmap cell
    h = hm.asnumpy()
    cx, cy = int(coords.asnumpy()[0, 0, 0]), int(coords.asnumpy()[0, 0, 1])
    assert h[0, 0, cy, cx] == h[0, 0].max()
