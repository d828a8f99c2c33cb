"""``flops_per_sample`` of the kanana configuration against a sum made by
hand, the two new kernels' operations and bytes, and what the new readers do
with a trace that has none of their operations."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

from models import deepseek_v3            # noqa: E402
from readers import mla, moe              # noqa: E402

TRAFFIC = {"per_chip_batch": 2, "seq_len": 4096}


def _config():
    return json.load(open(os.path.join(
        BENCH, "configs", "kanana2_30b_a3b_ep8.json")))


def test_flops_per_sample_against_a_hand_sum():
    # forward multiply-accumulates of one token (ISSUE 29's count)
    attention = (2048 * 32 * 192        # W_q
                 + 2048 * (512 + 64)    # W_kva: latent + the rotary key
                 + 512 * 32 * 256       # W_kvb: k_nope and v of 32 heads
                 + 32 * 128 * 2048)     # W_o
    assert attention == 26_345_472
    scores = 32 * (4096 + 1) / 2 * (192 + 128)     # causal: (L + 1) / 2 keys
    dense = 3 * 2048 * 6144
    shared = 2 * 3 * 2048 * 768
    router = 2048 * 128
    routed = 3 * 2048 * 768 * 6 * 16 / 128         # uniform share of top-6
    head = 2048 * 16032
    per_token = 5 * (attention + scores) + dense \
        + 4 * (shared + router + routed) + head
    assert per_token == pytest.approx(360.1e6, rel=1e-3)
    got = deepseek_v3.flops_per_sample(_config(), TRAFFIC)
    assert got == pytest.approx(6 * 4096 * per_token, rel=1e-12)
    assert got == pytest.approx(8.85e12, rel=1e-3)
    parts = deepseek_v3.macs_per_token(_config(), TRAFFIC)
    total = sum(parts.values())
    assert parts["mla_scores"] / total == pytest.approx(0.29, abs=0.01)
    assert parts["shared_experts"] / total == pytest.approx(0.10, abs=0.01)
    assert parts["routed_experts"] / total == pytest.approx(0.04, abs=0.01)
    assert (parts["mla_scores"] + parts["mla_projections"]) / total == \
        pytest.approx(0.66, abs=0.01)


def test_parameters_of_the_cut_against_a_hand_sum():
    """The 576.0 M parameters (9.22 GB at 16 B) the cell is sized by."""
    attention = 26_345_472 + 512                   # + the latent's norm
    expert = 3 * 2048 * 768
    layer = attention + 2 * 2048                   # + the two RMSNorms
    dense_layer = layer + 3 * 2048 * 6144
    expert_layer = layer + 2 * expert + 128 * 2048 + 128 + 16 * expert
    total = dense_layer + 4 * expert_layer + 2 * 16032 * 2048 + 2048
    assert total == pytest.approx(576.0e6, rel=1e-3)
    assert total * 16 == pytest.approx(9.22e9, rel=2e-3)


def test_mla_flash_forward_cost_from_shapes():
    flops, nbytes = mla.mla_flash_fwd_cost(_config(), TRAFFIC)
    rows = 2 * 32
    assert flops == 2 * rows * (4096 * 4097 // 2) * (192 + 128)
    assert flops == pytest.approx(343.7e9, rel=1e-3)
    assert nbytes == rows * 4096 * (2 * 192 + 2 * 128) * 2 + rows * 4096 * 4
    # FLOP-bound on a v5e: 1.74 ms least a call
    assert flops / 197e12 == pytest.approx(1.74e-3, rel=1e-2)
    assert flops / 197e12 > nbytes / 819e9


def test_grouped_product_cost_at_uniform_routing():
    flops, nbytes = moe.moe_gmm_cost(_config(), TRAFFIC)
    rows = 8192 * 6 * 16 // 128
    assert rows == 6144
    assert flops == 2 * rows * 2048 * 768
    assert nbytes == (rows * (2048 + 768) + 16 * 2048 * 768) * 2
    # the experts' weights are most of the bytes, and on a v5e the bytes
    # bound it, barely (104 us against 98 us of FLOPs: at the ridge)
    assert flops / 197e12 == pytest.approx(98.1e-6, rel=1e-2)
    assert nbytes / 819e9 == pytest.approx(103.8e-6, rel=1e-2)


def test_grouped_product_cost_at_the_rows_the_runner_counted():
    """``runners/train_fused_grads.py`` leaves the rows each layer routed,
    before the first step and after the window (a dense layer counts 0):
    their mean over the expert layers and the two counts is the yardstick."""
    sizes = dict(_config(), routed_rows={
        "first": [0, 6000, 6100, 6200, 6300], "last": [0, 6400, 6500, 6600,
                                                       6700]})
    assert moe.routed_rows(sizes, TRAFFIC) == (6350.0, 6144.0)
    flops, nbytes = moe.moe_gmm_cost(sizes, TRAFFIC)
    assert flops == 2 * 6350 * 2048 * 768
    assert nbytes == (6350 * (2048 + 768) + 16 * 2048 * 768) * 2


class _Ctx:
    """A read context over a trace without the kernels: what the parent
    commit's program gives the new readers."""

    def __init__(self, trace):
        self.trace, self.device_ids = trace, [0]
        self.sizes, self.traffic = _config(), TRAFFIC
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


@pytest.mark.parametrize("reader", [
    mla.mla_flash_fwd_ms, mla.mla_flash_fwd_roofline,
    moe.moe_gmm_ms, moe.moe_gmm_roofline])
def test_readers_return_nothing_where_the_trace_has_nothing(reader):
    from trace import Trace
    assert reader(_Ctx(None)) is None
    step = [("jit_train_step", i * 1000, 900) for i in range(6)]
    ops = [("fusion.1", i * 1000 + 10, 500) for i in range(6)]
    empty = Trace({0: {"XLA Modules": step, "XLA Ops": ops}}, [], {})
    ctx = _Ctx(empty)
    assert reader(ctx) is None and not ctx.notes


def test_readers_on_a_small_made_up_trace():
    from trace import Trace
    step = [("jit_train_step", i * 10_000_000, 9_000_000) for i in range(6)]
    ops = []
    for i in range(6):
        t = i * 10_000_000
        ops += [("mxtpu_flash_fwd.%d" % j, t + j * 1000, 4_000_000 // 10)
                for j in range(10)]
        ops += [(name, t + 5_000_000 + j, 250_000 // 3)
                for j, name in enumerate(("mxtpu_gmm.1", "mxtpu_gmm_dlhs.2",
                                          "mxtpu_gmm_drhs.3"))]
    ctx = _Ctx(Trace({0: {"XLA Modules": step, "XLA Ops": sorted(
        ops, key=lambda e: e[1])}}, [], {}))
    assert mla.mla_flash_fwd_ms(ctx) == pytest.approx(4.0)
    # 1.745 ms least a call over 0.4 ms measured would be over 100 %: the
    # reader reports what the arithmetic gives, it does not clip
    assert mla.mla_flash_fwd_roofline(ctx) == pytest.approx(
        100 * 343.7e9 / 197e12 / 0.4e-3, rel=1e-3)
    assert moe.moe_gmm_ms(ctx) == pytest.approx(0.25, rel=1e-3)
    assert ctx.notes["bound.kernel.mla_flash_fwd_roofline"]["bound"] == \
        "flops"
    moe.moe_gmm_roofline(ctx)
    assert ctx.notes["bound.kernel.moe_gmm_roofline"]["calls_per_step"] == 3
    assert ctx.notes["rows.kernel.moe_gmm_roofline"] == {
        "costed_at": 6144.0, "uniform": 6144.0, "counted": None}
