"""``flops_per_sample`` and the parameters of the SDAR configuration against
sums made by hand, block-diffusion attention's operations and bytes against
a count of the visible pairs made position by position, what the readers do
with a trace that has none of their operations, and what the manifest says
of the two cells added beside it (SDAR's and the ZeRO-1 BERT cell)."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import manifest                           # noqa: E402
from models import sdar_moe               # noqa: E402
from readers import block_diffusion, moe  # noqa: E402

TRAFFIC = {"per_chip_batch": 1, "seq_len": 8192}
CELL = "sdar-30b-a3b-ep8-fused-b1-s8192-bd4"
ZERO1 = "bert-base-fused-b128-s128-dp4-zero1"
CONFIG = "sdar_30b_a3b_ep8"
# a half's queries see 4 (p // 4 + 1) keys each: 16 x (1 + .. + 2048)
PAIRS = 16 * 2048 * 2049 // 2


def _config():
    return json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))


@pytest.mark.parametrize("seq,block", [(8, 4), (64, 4), (96, 8), (40, 1)])
def test_visible_pairs_against_the_mask_counted_position_by_position(seq,
                                                                     block):
    """Each half's count against the published mask written out: clean
    queries over clean keys block-causally, noisy queries over the clean
    blocks before their own and their own noisy block."""
    blk = np.arange(seq) // block
    clean = (blk[None, :] <= blk[:, None]).sum()
    noisy = (blk[None, :] < blk[:, None]).sum() + \
        (blk[None, :] == blk[:, None]).sum()
    assert block_diffusion.visible_pairs(seq, block) == clean == noisy


def test_sdar_flops_per_sample_against_a_hand_sum():
    assert PAIRS == 33_570_816
    assert 2 * PAIRS == pytest.approx(67.1e6, rel=1e-3)
    tokens = 16384
    matrices = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    layer = (tokens * (matrices + 2048 * 128)        # projections, router
             + 2 * PAIRS * 32 * (128 + 128)          # Q.K and P.V, 2 halves
             + tokens * 8 * 16 / 128 * 3 * 2048 * 768)  # 1024 rows an expert
    head = 8192 * 2048 * 18992                       # the noisy half only
    got = sdar_moe.flops_per_sample(_config(), TRAFFIC)
    assert got == pytest.approx(6 * (4 * layer + head), rel=1e-12)
    parts = sdar_moe.macs_per_sample(_config(), TRAFFIC)
    # one layer's forward: attention 1100 GFLOP, projections 618, experts
    # 155, attention 58 % of a layer
    assert 2 * parts["attention"] / 4 == pytest.approx(1100e9, rel=1e-3)
    assert 2 * parts["projections"] / 4 == pytest.approx(618e9, rel=1e-3)
    assert 2 * parts["experts"] / 4 == pytest.approx(154.6e9, rel=1e-3)
    assert 2 * parts["head"] == pytest.approx(637e9, rel=1e-3)
    by_layer = parts["attention"] / (parts["attention"] + parts["projections"]
                                     + parts["experts"] + parts["router"])
    assert by_layer == pytest.approx(0.58, abs=0.01)


def test_sdar_parameters_of_the_cut_against_a_hand_sum():
    """The 456.4 M parameters (7.30 GB at 16 B) the cell is sized by."""
    expert = 3 * 2048 * 768
    layer = (18_874_368 + 2 * 128            # + the q and k head norms
             + 128 * 2048                    # the router
             + 2 * 2048 + 16 * expert)       # the two RMSNorms, 16 experts
    total = 4 * layer + 2 * 18992 * 2048 + 2048
    assert layer == pytest.approx(94.6e6, rel=1e-3)
    assert total == pytest.approx(456.4e6, rel=1e-3)
    assert total * 16 == pytest.approx(7.30e9, rel=2e-3)


def test_block_diffusion_costs_from_shapes():
    cfg = _config()
    flops, nbytes = block_diffusion.bd_attn_fwd_cost(cfg, TRAFFIC)
    assert flops == 2 * 2 * (2 * PAIRS) * 32 * 128
    assert nbytes == 16384 * (128 * 2 * (2 * 32 + 2 * 4) + 4 * 32)
    # FLOP-bound on a v5e: 5.58 ms least a layer's forward
    assert flops / 197e12 == pytest.approx(5.584e-3, rel=1e-3)
    assert flops / 197e12 > nbytes / 819e9
    bwd, bwd_bytes = block_diffusion.bd_attn_bwd_cost(cfg, TRAFFIC)
    assert bwd == 2 * flops
    assert bwd_bytes == 16384 * (128 * 2 * (4 * 32 + 4 * 4) + 4 * 32)


def test_sdar_grouped_product_is_costed_by_the_accepted_reader():
    """``readers/moe.py`` reads ``n_routed_experts``: the file repeats
    ``num_experts`` under that name, held and published; the runner's count
    of the rows (both halves go through the experts) is what it costs."""
    cfg = _config()
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 16
    assert cfg["published"]["n_routed_experts"] == \
        cfg["published"]["num_experts"] == 128
    counted = dict(cfg, routed_rows={"first": [16000, 17000, 18000, 19000],
                                     "last": [20000, 21000, 22000, 23000]})
    assert moe.routed_rows(counted, TRAFFIC)[0] == 19500.0


class _Ctx:
    """A read context over a made-up trace."""

    def __init__(self, trace):
        self.trace, self.device_ids = trace, [0]
        self.sizes, self.traffic = _config(), TRAFFIC
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


READERS = [block_diffusion.bd_attn_fwd_ms,
           block_diffusion.bd_attn_fwd_roofline,
           block_diffusion.bd_attn_bwd_ms,
           block_diffusion.bd_attn_bwd_roofline]


@pytest.mark.parametrize("reader", READERS, ids=lambda f: f.__name__)
def test_bd_readers_return_nothing_where_the_trace_has_nothing(reader):
    """What the parent commit's program gives the new readers: no such
    operation (its causal kernels are ``mxtpu_flash_*``), so no metric and
    no note, and nothing raised."""
    from trace import Trace
    assert reader(_Ctx(None)) is None
    step = [("jit_train_step", i * 1000, 900) for i in range(6)]
    ops = [("mxtpu_flash_fwd.1", i * 1000 + 10, 500) for i in range(6)]
    ctx = _Ctx(Trace({0: {"XLA Modules": step, "XLA Ops": ops}}, [], {}))
    assert reader(ctx) is None and not ctx.notes


@pytest.mark.parametrize("split", [1, 2])
@pytest.mark.parametrize("remat", [False, True])
def test_bd_readers_count_passes_from_the_configuration(split, remat):
    """Two kernels a pass (the clean call and the offset one) or one: the
    share reads the same; 4 layers' forward passes (8 under remat, which
    runs each twice), 4 backward."""
    from trace import Trace
    forwards = 8 if remat else 4
    step = [("jit_train_step", i * 1_000_000_000, 900_000_000)
            for i in range(6)]
    ops = []
    for i in range(6):
        t = i * 1_000_000_000
        for j in range(forwards * split):
            ops.append((f"mxtpu_bd_attn_fwd.{j}", t + j * 20_000_000,
                        10_000_000 // split))
        for j in range(4 * split):
            ops.append((f"mxtpu_bd_attn_bwd.{j}", t + 400_000_000
                        + j * 40_000_000, 30_000_000 // split))
    ctx = _Ctx(Trace({0: {"XLA Modules": step, "XLA Ops": sorted(
        ops, key=lambda e: e[1])}}, [], {}))
    ctx.sizes = dict(ctx.sizes, remat=remat)
    assert block_diffusion.bd_attn_fwd_ms(ctx) == pytest.approx(
        10.0 * forwards)
    assert block_diffusion.bd_attn_bwd_ms(ctx) == pytest.approx(120.0)
    assert block_diffusion.bd_attn_fwd_roofline(ctx) == pytest.approx(
        100 * 5.584e-3 / 10e-3, rel=1e-3)
    assert block_diffusion.bd_attn_bwd_roofline(ctx) == pytest.approx(
        100 * 4 * 11.168e-3 / 120e-3, rel=1e-3)
    note = ctx.notes["bound.kernel.bd_attn_fwd_roofline"]
    assert note["bound"] == "flops" and note["passes_per_step"] == forwards
    assert note["calls_per_step"] == forwards * split


def test_manifest_accepts_both_new_cells():
    man = manifest.Manifest().validate()
    entry = man.configs[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = man.cell(CELL)
    assert cell.chips == 1 and cell.traffic["runner"] == "train_fused_grads"
    assert (cell.traffic["per_chip_batch"], cell.traffic["seq_len"],
            cell.traffic["pool"]) == (1, 8192, 4)
    names = [m["name"] for m in cell.layer_metrics]
    assert names == [
        "train.host_ms", "device.idle_pct", "device.mfu_pct",
        "kernel.moe_gmm_ms", "kernel.moe_gmm_roofline",
        "kernel.bd_attn_fwd_ms", "kernel.bd_attn_fwd_roofline",
        "kernel.bd_attn_bwd_ms", "kernel.bd_attn_bwd_roofline"]
    for name in names[5:]:
        assert man.per_layer[name]["workloads"] == [CELL]
        assert man.per_layer[name]["layer"] == \
            "Pallas kernels, ops/flash_attention.py"
    zero1 = man.cell(ZERO1)
    assert zero1.chips == 4 and zero1.traffic["shard_updates"] is True
    assert zero1.traffic["runner"] == "train_fused_zero1"
    assert zero1.config["model"] == "bert"
    dp4 = man.traffic("fused-b128-s128-dp4")
    assert dp4["runner"] == "train_fused"
    same = ("name", "who", "why", "shard_updates", "runner")
    assert {k: v for k, v in zero1.traffic.items() if k not in same} == \
        {k: v for k, v in dp4.items() if k not in same}
    assert "shard_updates" not in dp4
    # the accepted lists gained the new cells at their ends, nothing else
    cells = list(man.workloads)
    assert cells[-2:] == [ZERO1, CELL]
    for name, cell_name in (("kernel.moe_gmm_ms", CELL),
                            ("kernel.moe_gmm_roofline", CELL),
                            ("kernel.flash_fwd_ms", ZERO1),
                            ("kernel.flash_fwd_roofline", ZERO1),
                            ("collective.exposed_ms", ZERO1)):
        assert man.per_layer[name]["workloads"][-1] == cell_name
    four = [w for w in man.doc["workloads"] if w["chips"] == 4]
    assert len(four) == 2 == len(cells) // 4
    for w in (man.workloads[CELL], man.workloads[ZERO1], entry):
        assert len(w["why"]) <= 200


def test_every_published_sdar_width_is_in_the_file_unchanged():
    """The catalog's ``config`` for SDAR-30B-A3B-Chat, key for key: only
    the three keys under ``reduced`` differ, and ``published`` has those."""
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    cfg = _config()
    differs = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["published"][key] == catalog[key]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["vocab_size"] * 8 == catalog["vocab_size"]
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    assert "8 chips share each layer" in cfg["deployment"]
    assert "pipeline stages" in cfg["deployment"]
    assert cfg["expert_offset"] == 0 and cfg["dtype"] == "bfloat16"
    for name in ("block_length", "noise", "layout", "mask", "loss",
                 "qk_norm", "mask_token", "initializer_range",
                 "embedding_initializer_range"):
        assert name in cfg["assumed"]


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn"}), ("mlp_only_layers", [0]),
    ("tie_word_embeddings", True), ("mask_token_id", 0)])
def test_sdar_adapter_refuses_what_the_model_zoo_does_not_build(key, value):
    with pytest.raises(ValueError):
        sdar_moe.build(dict(_config(), **{key: value}))


def test_pool_rows_carry_their_noise():
    """A row of the pool: ``x_t`` is ``x_0`` with tokens masked at their
    block's ``t``; the label's weight is ``1 / t`` exactly where a token was
    masked, one ``t`` a block; the same seed gives the same rows."""
    cfg = dict(_config(), vocab_size=64, mask_token_id=63)
    traffic = {"seq_len": 256}
    (tokens, label), = sdar_moe.make_pool(cfg, traffic, 2, 1, 5_000_000_011)
    again, = sdar_moe.make_pool(cfg, traffic, 2, 1, 5_000_000_011)
    tokens, label = np.asarray(tokens), np.asarray(label)
    np.testing.assert_array_equal(tokens, np.asarray(again[0]))
    xt, x0 = tokens[:, :256], tokens[:, 256:]
    assert tokens.shape == (2, 512) and label.shape == (2, 2, 256)
    np.testing.assert_array_equal(label[:, 0], x0)
    assert x0.max() < 63 and x0.min() >= 0
    masked = xt == 63
    np.testing.assert_array_equal(xt[~masked], x0[~masked])
    weight = label[:, 1]
    assert (weight[~masked] == 0).all() and (weight[masked] >= 1).all()
    blocks = weight.reshape(2, 64, 4)
    top = blocks.max(-1, keepdims=True)
    assert ((blocks == top) | (blocks == 0)).all()
    assert 0.2 < masked.mean() < 0.8
