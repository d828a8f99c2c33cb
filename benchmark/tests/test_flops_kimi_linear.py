"""``flops_per_sample`` and the parameter count of the kimi configuration
against sums made by hand from ISSUE 35's shapes, the scan's cost functions,
what their readers do with a trace that has none of their operations, and what
the manifest says of the new configuration and its cell."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import manifest                           # noqa: E402
from models import kimi_linear            # noqa: E402
from readers import kda, mla, moe         # noqa: E402

TRAFFIC = {"per_chip_batch": 1, "seq_len": 8192}
CELL = "kimi-linear-48b-a3b-ep32-fused-b1-s8192"
CONFIG = "kimi_linear_48b_a3b_ep32"


def _config():
    return json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))


def test_kimi_parameters_of_the_cut_against_a_hand_sum():
    """The 602.4 M parameters (9.64 GB at 16 B) the cell is sized by."""
    d = 2304
    kda_layer = (4 * d * 4096                   # q, k, v, o
                 + 2 * (d * 128 + 128 * 4096)   # the two low-rank pairs
                 + d * 32                       # beta
                 + 3 * 4096 * 4                 # three convolutions
                 + 32 + 4096 + 128)             # A_log, dt_bias, the norm
    mla_layer = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 4096 * d
    expert = 3 * d * 1024
    moe_layer = 9 * expert + 256 * d + 256      # 8 held + 1 shared, router
    assert kda_layer == pytest.approx(39.51e6, rel=1e-3)
    assert mla_layer == pytest.approx(29.12e6, rel=1e-3)
    assert expert == pytest.approx(7.08e6, rel=1e-3)
    total = (4 * kda_layer + mla_layer + 3 * d * 9216 + 4 * moe_layer
             + 5 * 2 * d + d + 2 * 20480 * d)
    assert kimi_linear.parameter_count(_config()) == total
    assert total == pytest.approx(602.4e6, rel=1e-4)
    assert total * 16 == pytest.approx(9.64e9, rel=1e-3)
    assert kimi_linear.layer_counts(_config()) == (4, 1, 1)


def test_kimi_flops_per_sample_against_a_hand_sum():
    d, t = 2304, 8192
    kda_layer = (4 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32
                 + 3 * 4096 * 4
                 + 32 * 7 * 128 * 128)          # the scan, the recurrence's
    mla_layer = (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
                 + 32 * (t + 1) / 2 * (192 + 128))
    expert = 3 * d * 1024
    moe_layer = expert + d * 256 + expert * 8 * 8 / 256
    token = (4 * kda_layer + mla_layer + 3 * d * 9216 + 4 * moe_layer
             + d * 20480)
    got = kimi_linear.flops_per_sample(_config(), TRAFFIC)
    assert got == pytest.approx(6 * t * token, rel=1e-12)
    assert got == pytest.approx(19.5e12, rel=2e-2)
    parts = kimi_linear.macs_per_token(_config(), TRAFFIC)
    total = sum(parts.values())
    # the scan is a twentieth of the counted work, the one MLA layer's scores
    # a tenth: what the cell weighs is the time, not the FLOPs
    assert parts["kda_scan"] == 4 * 32 * 7 * 128 * 128
    assert parts["kda_scan"] / total == pytest.approx(0.037, abs=0.005)
    assert parts["mla_scores"] / total == pytest.approx(0.106, abs=0.005)
    assert parts["routed_experts"] / total == pytest.approx(0.018, abs=0.003)


def test_scan_costs_are_the_recurrence_s_own():
    cfg = _config()
    flops, nbytes = kda.kda_fwd_cost(cfg, TRAFFIC)
    assert flops == 2 * 7 * 128 * 128 * 32 * 8192
    assert flops == pytest.approx(60.1e9, rel=1e-3)
    # q, k, v, o in bfloat16, the log-decay a channel and beta float32
    assert nbytes == 8192 * 32 * (4 * 128 * 2 + 128 * 4 + 4)
    # bytes-bound on a v5e: 0.49 ms least a forward pass
    assert nbytes / 819e9 > flops / 197e12
    assert nbytes / 819e9 == pytest.approx(0.493e-3, rel=5e-3)
    bwd_flops, bwd_bytes = kda.kda_bwd_cost(cfg, TRAFFIC)
    assert bwd_flops == 2 * flops
    assert bwd_bytes == 8192 * 32 * (7 * 128 * 2 + 2 * 128 * 4 + 8)
    assert bwd_bytes / 819e9 == pytest.approx(0.90e-3, rel=1e-2)
    assert bwd_bytes / 819e9 > bwd_flops / 197e12


def test_accepted_readers_cost_the_new_cell_from_its_file():
    """``readers/mla.py`` and ``readers/moe.py`` read the keys the file
    repeats under their names: one MLA layer at L = 8192, 2048 rows a
    layer at uniform routing into 8 experts of 2304 x 1024."""
    cfg = _config()
    flops, _ = mla.mla_flash_fwd_cost(cfg, TRAFFIC)
    assert flops == 2 * 32 * (8192 * 8193 // 2) * (192 + 128)
    assert flops / 197e12 == pytest.approx(3.49e-3, rel=1e-2)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 8
    assert cfg["published"]["n_routed_experts"] == \
        cfg["published"]["num_experts"] == 256
    assert cfg["num_experts_per_tok"] == cfg["num_experts_per_token"] == 8
    assert moe.routed_rows(cfg, TRAFFIC) == (2048.0, 2048.0)
    gmm, _ = moe.moe_gmm_cost(cfg, TRAFFIC)
    assert gmm == 2 * 2048 * 2304 * 1024


class _Ctx:
    """A read context over a made-up trace."""

    def __init__(self, trace):
        self.trace, self.device_ids = trace, [0]
        self.sizes, self.traffic = _config(), TRAFFIC
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.notes = {}

    def note(self, key, value):
        self.notes[key] = value


KDA_READERS = [kda.kda_fwd_ms, kda.kda_fwd_roofline, kda.kda_bwd_ms,
               kda.kda_bwd_roofline]


@pytest.mark.parametrize("reader", KDA_READERS, ids=lambda f: f.__name__)
def test_kda_readers_return_nothing_where_the_trace_has_nothing(reader):
    """What the parent commit's program gives the new readers: no such
    operation, so no metric and no note, and nothing raised."""
    from trace import Trace
    assert reader(_Ctx(None)) is None
    step = [("jit_train_step", i * 1000, 900) for i in range(6)]
    ops = [("mxtpu_flash_fwd.1", i * 1000 + 10, 500) for i in range(6)]
    ctx = _Ctx(Trace({0: {"XLA Modules": step, "XLA Ops": ops}}, [], {}))
    assert reader(ctx) is None and not ctx.notes


@pytest.mark.parametrize("split", [1, 2], ids=["one_kernel", "two_kernels"])
def test_kda_readers_on_a_small_made_up_trace(split):
    """Eight forward calls of 5 ms (``remat``) and four backward of 12 ms a
    step; a pass split over two kernels that carry the pattern reads the
    same share: the passes come from the configuration, not the calls."""
    from trace import Trace
    step = [("jit_train_step", i * 1_000_000_000, 900_000_000)
            for i in range(6)]
    ops = []
    for i in range(6):
        t = i * 1_000_000_000
        for j in range(8 * split):
            ops.append((f"mxtpu_kda_fwd{'_part' if j % split else ''}.{j}",
                        t + j * 6_000_000, 5_000_000 // split))
        for j in range(4 * split):
            ops.append((f"mxtpu_kda_bwd.{j}", t + 300_000_000
                        + j * 13_000_000, 12_000_000 // split))
    ctx = _Ctx(Trace({0: {"XLA Modules": step, "XLA Ops": sorted(
        ops, key=lambda e: e[1])}}, [], {}))
    assert kda.kda_fwd_ms(ctx) == pytest.approx(40.0)
    assert kda.kda_bwd_ms(ctx) == pytest.approx(48.0)
    # 0.493 ms least a forward pass, 8 a step, over 40 ms
    assert kda.kda_fwd_roofline(ctx) == pytest.approx(
        100 * 8 * 0.4933e-3 / 40e-3, rel=2e-3)
    assert kda.kda_bwd_roofline(ctx) == pytest.approx(
        100 * 4 * 0.9027e-3 / 48e-3, rel=2e-3)
    note = ctx.notes["bound.kernel.kda_fwd_roofline"]
    assert note["bound"] == "bytes" and note["passes_per_step"] == 8
    assert note["calls_per_step"] == 8 * split


def test_manifest_accepts_the_kimi_configuration_and_cell():
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
        "kernel.mla_flash_fwd_ms", "kernel.mla_flash_fwd_roofline",
        "kernel.moe_gmm_ms", "kernel.moe_gmm_roofline",
        "kernel.kda_fwd_ms", "kernel.kda_fwd_roofline",
        "kernel.kda_bwd_ms", "kernel.kda_bwd_roofline"]
    for name in names[3:]:
        assert CELL in man.per_layer[name]["workloads"]
    for name in names[7:]:
        assert man.per_layer[name]["workloads"] == [CELL]
        assert man.per_layer[name]["layer"] == \
            "Pallas kernels, ops/linear_attention.py"
    # the accepted metrics' lists gained the cell behind what they had
    gmm = man.per_layer["kernel.moe_gmm_ms"]["workloads"]
    assert gmm[:2] == ["kanana2-30b-a3b-ep8-fused-b2-s4096",
                       "keye-vl2-30b-a3b-ep8-fused-b1-s16384"]
    assert gmm.index(CELL) == 2
    assert man.per_layer["kernel.mla_flash_fwd_roofline"]["workloads"][:1] \
        == ["kanana2-30b-a3b-ep8-fused-b2-s4096"]
    assert len(man.workloads[CELL]["why"]) <= 200
    assert len(entry["why"]) <= 200


# What the keye file's manifest test holds of the two language cells that
# were there, by name and not by place in a list: that test asserts keye's
# entries are the last, which the kimi entries behind them ended, and tier-1
# carries it as a strict xfail until a `benchmark` PR rewrites it (ROADMAP.md,
# PERF.md §7).  Nothing it covered is left uncovered meanwhile.
@pytest.mark.parametrize("config,reduced,cell,own,shared", [
    ("kanana2_30b_a3b_ep8",
     ["num_hidden_layers", "n_routed_experts", "vocab_size"],
     "kanana2-30b-a3b-ep8-fused-b2-s4096",
     ["kernel.mla_flash_fwd_ms", "kernel.mla_flash_fwd_roofline",
      "kernel.moe_gmm_ms", "kernel.moe_gmm_roofline"], "kernel.mla_flash_"),
    ("keye_vl2_30b_a3b_ep8",
     ["num_hidden_layers", "num_experts", "vocab_size"],
     "keye-vl2-30b-a3b-ep8-fused-b1-s16384",
     ["kernel.moe_gmm_ms", "kernel.moe_gmm_roofline"], "kernel.dsa_"),
])
def test_the_language_cells_that_were_there_are_as_they_were(
        config, reduced, cell, own, shared):
    man = manifest.Manifest().validate()
    entry = man.configs[config]
    assert entry["reduced"] == reduced
    assert entry["file"] == f"benchmark/configs/{config}.json"
    assert man.workloads[cell]["config"] == config
    got = man.cell(cell)
    assert got.chips == 1 and got.traffic["runner"] == "train_fused_grads"
    names = [m["name"] for m in got.layer_metrics]
    assert names[:3] == ["train.host_ms", "device.idle_pct", "device.mfu_pct"]
    if config.startswith("keye"):
        assert names[3:5] == own
        assert set(names[5:]) == {m for m in man.per_layer
                                  if m.startswith(shared)}
        assert all(man.per_layer[m]["workloads"] == [cell]
                   for m in names[5:])
    else:
        assert names[3:] == own
    for name in names[3:]:
        assert cell in man.per_layer[name]["workloads"]
    # the lists this PR's cell joined keep what they had, in its order
    cells = list(man.workloads)
    for name in ("kernel.mla_flash_fwd_ms", "kernel.mla_flash_fwd_roofline",
                 "kernel.moe_gmm_ms", "kernel.moe_gmm_roofline"):
        listed = man.per_layer[name]["workloads"]
        assert listed == sorted(listed, key=cells.index) and listed[-1] == CELL
    assert man.per_layer["kernel.moe_gmm_ms"]["workloads"][:2] == [
        "kanana2-30b-a3b-ep8-fused-b2-s4096",
        "keye-vl2-30b-a3b-ep8-fused-b1-s16384"]
    assert len(man.workloads[cell]["why"]) <= 200
    assert len(entry["why"]) <= 200


def test_every_published_kimi_width_is_in_the_file_unchanged():
    """The catalog's ``config`` for Kimi-Linear-48B-A3B-Instruct, key for
    key: only the three keys under ``reduced`` differ, ``published`` has
    those, and ``linear_attn_config`` is the source's, whole."""
    catalog = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    cfg = _config()
    differs = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["published"][key] == catalog[key]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["vocab_size"] * 8 == catalog["vocab_size"]
    assert cfg["num_experts"] * 32 == catalog["num_experts"]
    assert cfg["layer_schedule"]["kda_layers"] == [1, 2, 3, 5]
    assert cfg["layer_schedule"]["full_attn_layers"] == [4]
    assert "32 chips share each layer" in cfg["deployment"]
    assert cfg["expert_offset"] == 0 and cfg["dtype"] == "bfloat16"
    for name in ("kda_low_rank", "kda_activations", "kda_decay", "kda_beta",
                 "kda_gate", "kda_conv", "initializer_range", "input", "loss",
                 "remat", "e_score_correction_bias"):
        assert name in cfg["assumed"]


@pytest.mark.parametrize("key,value", [
    ("num_nextn_predict_layers", 1), ("rope_scaling", {"type": "yarn"}),
    ("q_lora_rank", 1536), ("num_expert_group", 8), ("topk_group", 4),
    ("tie_word_embeddings", True)])
def test_adapter_refuses_what_the_model_zoo_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        kimi_linear.build(dict(_config(), **{key: value}))
