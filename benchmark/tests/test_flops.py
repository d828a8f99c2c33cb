"""``flops_per_sample`` of both models against sums made by hand, and the
flash kernel's operations and bytes."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from models import bert, resnet_v1      # noqa: E402
from readers import kernels             # noqa: E402


def _config(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


def test_resnet50_flops_against_a_hand_sum():
    # multiply-accumulates per image, MXNet's v1 (stride on the first 1x1):
    # stem 7x7x3x64 at 112^2
    macs = 7 * 7 * 3 * 64 * 112 * 112
    # stage 1 (56^2, 64 -> 256, 3 blocks): 1x1 + 3x3 + 1x1 (+ projection)
    macs += 56 * 56 * ((64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
                       + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    # stage 2 (28^2, 256 -> 512, 4 blocks)
    macs += 28 * 28 * ((256 * 128 + 9 * 128 * 128 + 128 * 512 + 256 * 512)
                       + 3 * (512 * 128 + 9 * 128 * 128 + 128 * 512))
    # stage 3 (14^2, 512 -> 1024, 6 blocks)
    macs += 14 * 14 * ((512 * 256 + 9 * 256 * 256 + 256 * 1024 + 512 * 1024)
                       + 5 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024))
    # stage 4 (7^2, 1024 -> 2048, 3 blocks)
    macs += 7 * 7 * ((1024 * 512 + 9 * 512 * 512 + 512 * 2048 + 1024 * 2048)
                     + 2 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048))
    macs += 2048 * 1000
    got = resnet_v1.flops_per_sample(_config("resnet50_v1"), {})
    assert got == 3 * 2 * macs
    assert got == pytest.approx(23.2e9, rel=0.01)
    # not the 3 * 4.1e9 of telemetry/costmodel.py, which is half of it
    assert got > 1.8 * 3 * 4.1e9


def test_bert_base_s128_flops_against_a_hand_sum():
    per_token_layer = 4 * 768 * 768 + 2 * 768 * 3072 + 2 * 128 * 768
    want = 3 * 2 * 12 * per_token_layer * 128
    got = bert.flops_per_sample(_config("bert_base"), {"seq_len": 128})
    assert got == want
    assert got == pytest.approx(67e9, rel=0.01)


def test_flash_forward_cost_from_shapes():
    sizes = _config("bert_base")
    flops, nbytes = kernels.flash_fwd_cost(
        sizes, {"per_chip_batch": 128, "seq_len": 128})
    rows = 128 * 12
    assert flops == 4 * rows * 128 * 128 * 64
    assert nbytes == 4 * rows * 128 * 64 * 2 + rows * 128 * 4
    # on a v5e it is the bytes that bound it
    assert nbytes / 819e9 > flops / 197e12
