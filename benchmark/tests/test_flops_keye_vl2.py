"""``flops_per_sample`` of the keye configuration against a sum made by hand,
the sparse-attention kernels' operations and bytes, what their readers do
with a trace that has none of their operations, and what the manifest says
of the new configuration and its cell."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import manifest                           # noqa: E402
from models import keye_vl2               # noqa: E402
from readers import dsa, moe              # noqa: E402

TRAFFIC = {"per_chip_batch": 1, "seq_len": 16384}
CELL = "keye-vl2-30b-a3b-ep8-fused-b1-s16384"
# sum_t min(t + 1, 2048) over 16384 queries
KEPT = 2048 * 2049 // 2 + (16384 - 2048) * 2048


def _config():
    return json.load(open(os.path.join(
        BENCH, "configs", "keye_vl2_30b_a3b_ep8.json")))


def test_keye_flops_per_sample_against_a_hand_sum():
    assert KEPT == 31_458_304
    assert keye_vl2.selected_pairs(16384, 2048) == KEPT
    assert KEPT / (16384 * 16385 / 2) == pytest.approx(0.2344, abs=1e-4)
    # forward multiply-accumulates of one layer on one sequence (ISSUE 33)
    attention_matrices = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    indexer_matrices = 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert attention_matrices == 18_874_368
    assert indexer_matrices == 2_260_992
    layer = (16384 * (attention_matrices + indexer_matrices + 2048 * 128)
             + (16384 * 16385 // 2) * 16 * 64       # index scores, causal
             + KEPT * 32 * (128 + 128)              # Q.K and P.V, kept pairs
             + KEPT * 32                            # the mean over the heads
             + 16384 * 8 * 16 / 128 * 3 * 2048 * 768)   # 1024 rows an expert
    head = 16384 * 2048 * 18992
    got = keye_vl2.flops_per_sample(_config(), TRAFFIC)
    assert got == pytest.approx(6 * (4 * layer + head), rel=1e-12)
    assert got == pytest.approx(23.6e12, rel=5e-3)
    parts = keye_vl2.macs_per_sequence(_config(), TRAFFIC)
    total = sum(parts.values())
    assert parts["attention"] / total == pytest.approx(0.262, abs=0.005)
    assert parts["index_scores"] / total == pytest.approx(0.140, abs=0.005)
    assert parts["experts"] / total == pytest.approx(0.079, abs=0.005)
    # a masked-dense kernel executes the causal half where this counts the
    # kept pairs: 4.27 times the attention counted here
    assert (16384 * 16385 / 2) / KEPT == pytest.approx(4.27, abs=0.01)


def test_keye_parameters_of_the_cut_against_a_hand_sum():
    """The 465.4 M parameters (7.45 GB at 16 B) the cell is sized by."""
    expert = 3 * 2048 * 768
    layer = (18_874_368 + 2 * 128            # + the q and k head norms
             + 2_260_992 + 128 * 2048        # indexer, router
             + 2 * 2048 + 16 * expert)       # the two RMSNorms, 16 experts
    total = 4 * layer + 2 * 18992 * 2048 + 2048
    assert layer == pytest.approx(96.9e6, rel=1e-3)
    assert total == pytest.approx(465.4e6, rel=1e-3)
    assert total * 16 == pytest.approx(7.45e9, rel=2e-3)


def test_sparse_kernel_costs_from_shapes():
    cfg = _config()
    flops, nbytes = dsa.dsa_attn_fwd_cost(cfg, TRAFFIC)
    assert flops == 2 * 32 * (128 + 128) * KEPT
    assert flops == pytest.approx(515.4e9, rel=1e-3)
    assert nbytes == (16384 * 128 * 2 * (2 * 32 + 2 * 4) + 16384 * 32 * 4
                      + (16384 * 16385 // 2) // 8)
    # FLOP-bound on a v5e: 2.62 ms least a call; a kernel that executes the
    # causal half at the MXU's peak takes 11.2 ms: 23.4 % at most
    assert flops / 197e12 == pytest.approx(2.616e-3, rel=1e-3)
    assert flops / 197e12 > nbytes / 819e9
    bwd, _ = dsa.dsa_attn_bwd_cost(cfg, TRAFFIC)
    assert bwd == 2 * 32 * 5 * 128 * KEPT
    index, index_bytes = dsa.dsa_index_cost(cfg, TRAFFIC)
    assert index == 2 * 16 * 64 * (16384 * 16385 // 2)
    assert index / 197e12 == pytest.approx(1.395e-3, rel=1e-3)
    assert index / 197e12 > index_bytes / 819e9
    loss, _ = dsa.dsa_index_loss_cost(cfg, TRAFFIC)
    assert loss == 2 * KEPT * (32 * 128 + 3 * 16 * 64)


def test_grouped_product_is_costed_by_the_accepted_reader():
    """``readers/moe.py`` reads ``n_routed_experts``: the file repeats
    ``num_experts`` under that name, held and published."""
    cfg = _config()
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 16
    assert cfg["published"]["n_routed_experts"] == \
        cfg["published"]["num_experts"] == 128
    assert moe.routed_rows(cfg, TRAFFIC) == (16384.0, 16384.0)
    flops, _ = moe.moe_gmm_cost(cfg, TRAFFIC)
    assert flops == 2 * 16384 * 2048 * 768      # 1024 rows an expert x 16


class _Ctx:
    """A read context over a made-up trace."""

    def __init__(self, trace):
        self.trace, self.device_ids = trace, [0]
        self.sizes, self.traffic = _config(), TRAFFIC
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


READERS = [dsa.dsa_attn_fwd_ms, dsa.dsa_attn_fwd_roofline,
           dsa.dsa_attn_bwd_ms, dsa.dsa_attn_bwd_roofline,
           dsa.dsa_index_ms, dsa.dsa_index_roofline,
           dsa.dsa_index_loss_ms, dsa.dsa_index_loss_roofline]


@pytest.mark.parametrize("reader", READERS, ids=lambda f: f.__name__)
def test_dsa_readers_return_nothing_where_the_trace_has_nothing(reader):
    """What the parent commit's program gives the new readers: no such
    operation, so no metric and no note, and nothing raised."""
    from trace import Trace
    assert reader(_Ctx(None)) is None
    step = [("jit_train_step", i * 1000, 900) for i in range(6)]
    ops = [("mxtpu_flash_fwd.1", i * 1000 + 10, 500) for i in range(6)]
    ctx = _Ctx(Trace({0: {"XLA Modules": step, "XLA Ops": ops}}, [], {}))
    assert reader(ctx) is None and not ctx.notes


def test_dsa_readers_on_a_small_made_up_trace():
    from trace import Trace
    step = [("jit_train_step", i * 1_000_000_000, 900_000_000)
            for i in range(6)]
    ops = []
    for i in range(6):
        t = i * 1_000_000_000
        for j in range(8):          # remat: the forward twice a layer
            ops.append((f"mxtpu_dsa_attn_fwd.{j}", t + j * 30_000_000,
                        20_000_000))
        for j in range(4):
            ops.append((f"mxtpu_dsa_attn_bwd.{j}", t + 300_000_000
                        + j * 60_000_000, 50_000_000))
            ops.append((f"mxtpu_dsa_index_select.{j}", t + 600_000_000
                        + j * 10_000_000, 5_000_000))
            ops.append((f"mxtpu_dsa_align_loss.{j}", t + 700_000_000
                        + j * 30_000_000, 25_000_000))
    ctx = _Ctx(Trace({0: {"XLA Modules": step, "XLA Ops": sorted(
        ops, key=lambda e: e[1])}}, [], {}))
    assert dsa.dsa_attn_fwd_ms(ctx) == pytest.approx(160.0)
    assert dsa.dsa_attn_bwd_ms(ctx) == pytest.approx(200.0)
    assert dsa.dsa_index_ms(ctx) == pytest.approx(20.0)
    assert dsa.dsa_index_loss_ms(ctx) == pytest.approx(100.0)
    # 2.616 ms least a call over 20 ms measured
    assert dsa.dsa_attn_fwd_roofline(ctx) == pytest.approx(13.08, rel=1e-3)
    assert ctx.notes["bound.kernel.dsa_attn_fwd_roofline"]["bound"] == \
        "flops"
    assert ctx.notes["bound.kernel.dsa_attn_fwd_roofline"][
        "calls_per_step"] == 8
    assert dsa.dsa_attn_bwd_roofline(ctx) == pytest.approx(
        100 * 6.54e-3 / 50e-3, rel=2e-3)
    assert 0 < dsa.dsa_index_roofline(ctx) < 100
    assert 0 < dsa.dsa_index_loss_roofline(ctx) < 100


def test_manifest_accepts_the_new_configuration_and_cell():
    man = manifest.Manifest().validate()
    entry = man.configs["keye_vl2_30b_a3b_ep8"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert man.doc["configs"][-1] is entry
    assert man.doc["workloads"][-1]["name"] == CELL
    cell = man.cell(CELL)
    assert cell.chips == 1 and cell.traffic["runner"] == "train_fused_grads"
    names = [m["name"] for m in cell.layer_metrics]
    assert names[:5] == ["train.host_ms", "device.idle_pct", "device.mfu_pct",
                         "kernel.moe_gmm_ms", "kernel.moe_gmm_roofline"]
    assert set(names[5:]) == {m for m in man.per_layer
                              if m.startswith("kernel.dsa_")}
    for name in names[3:]:
        assert CELL in man.per_layer[name]["workloads"]
    # the accepted metrics' lists gained the cell at their end, nothing else
    assert man.per_layer["kernel.moe_gmm_ms"]["workloads"] == [
        "kanana2-30b-a3b-ep8-fused-b2-s4096", CELL]
    assert len(man.doc["workloads"][-1]["why"]) <= 200
    assert len(entry["why"]) <= 200


def test_every_published_width_is_in_the_file_unchanged():
    """The catalog's ``config`` for Keye-VL-2.0-30B-A3B, key for key: only
    the three keys under ``reduced`` differ, and ``published`` has those."""
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    cfg = _config()
    differs = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["published"][key] == catalog[key]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["vocab_size"] * 8 == catalog["vocab_size"]
    assert "vision tower is not part" in cfg["deployment"]
    assert cfg["expert_offset"] == 0 and cfg["dtype"] == "bfloat16"
