"""The kimi cell through ``run.py --rehearse`` (tiny sizes on the CPU, one
process a run as on the chip) and its four controls at the rehearsal size: the
plain run is ``correct``; ``no_delta`` in the program's place is refused; and
each of the reference's stand-ins (``MXTPU_BENCH_CONTROL``) fails at least one
of the rehearsal's limits against the reference's own gradients —
``no_decay`` and ``no_delta`` (what tells KDA from its simpler cousins) among
them."""
import functools
import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RUN = os.path.join(BENCH, "run.py")
CELL = "kimi-linear-48b-a3b-ep32-fused-b1-s8192"
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def _rehearse(control):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    env.pop("MXTPU_BENCH_CONTROL", None)
    if control:
        env["MXTPU_BENCH_CONTROL"] = control
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000011",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    notes = {line[2:].split(": ", 1)[0]: json.loads(line.split(": ", 1)[1])
             for line in lines if line.startswith("# ")}
    return json.loads(lines[-1]), notes


@pytest.mark.parametrize("control", [None, "no_delta"])
def test_kimi_gradient_check_passes_the_program_and_refuses_no_delta(control):
    result, notes = _rehearse(control)
    grads = notes["check.first_gradient_vs_reference"]
    assert result["correct"] is (control is None), grads
    assert grads["ok"] is (control is None)
    if control:
        assert "control no_delta in the program's place" in grads["detail"]
    else:
        assert notes["check.first_loss_vs_reference"]["ok"]
    rows = notes["routed_rows"]     # a dense layer, then two expert layers
    assert [len(rows[k]) for k in ("first", "last")] == [3, 3]
    assert rows["first"][0] == 0 and min(rows["first"][1:]) > 0


@functools.lru_cache(maxsize=None)
def _rehearsal():
    """(Cached, not a fixture: tier-1 collects this file's tests by name.)
    The rehearsal's sizes, seeded float32 parameters by the program's names,
    a batch, and the reference's own loss and gradients."""
    import jax.numpy as jnp
    import numpy as np
    import manifest
    cell = manifest.Manifest().cell(CELL)
    sizes = dict(cell.config)
    sizes.update(cell.config["rehearsal"])
    reference = importlib.import_module("reference.kimi_linear")
    rng = np.random.RandomState(11)
    d, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    lin = sizes["linear_attn_config"]
    hk, dk = lin["num_heads"], lin["head_dim"]
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    kda = {f"attn_{n}_proj_weight": (hk * dk, d) for n in "qkv"}
    kda.update({f"attn_{n}_conv_weight": (hk * dk, 4) for n in "qkv"})
    kda.update({"attn_f_a_proj_weight": (dk, d),
                "attn_f_b_proj_weight": (hk * dk, dk),
                "attn_g_a_proj_weight": (dk, d),
                "attn_g_b_proj_weight": (hk * dk, dk),
                "attn_b_proj_weight": (hk, d),
                "attn_o_proj_weight": (d, hk * dk)})
    mla = {"attn_q_proj_weight": (h * (nope + rope), d),
           "attn_kv_a_proj_weight": (rank + rope, d),
           "attn_kv_b_proj_weight": (h * (nope + dv), rank),
           "attn_o_proj_weight": (d, h * dv)}
    dense = {f"mlp_dense{i}_weight": s for i, s in enumerate(
        [(sizes["intermediate_size"], d)] * 2
        + [(d, sizes["intermediate_size"])])}
    moe = {"moe_router_weight": (sizes["published"]["num_experts"], d),
           "moe_experts_gate_weight": (sizes["num_experts"], d, w),
           "moe_experts_up_weight": (sizes["num_experts"], d, w),
           "moe_experts_down_weight": (sizes["num_experts"], w, d),
           "moe_shared_dense0_weight": (w, d),
           "moe_shared_dense1_weight": (w, d),
           "moe_shared_dense2_weight": (d, w)}
    shapes = {"model_embed_weight": (sizes["vocab_size"], d),
              "lm_head_weight": (sizes["vocab_size"], d)}
    ones = {"model_norm_weight": d}
    for i in range(sizes["num_hidden_layers"]):
        mixer = kda if i + 1 in lin["kda_layers"] else mla
        ffn = dense if i < sizes["first_k_dense_replace"] else moe
        shapes.update({f"model_layer{i}_{k}": s
                       for k, s in {**mixer, **ffn}.items()})
        ones.update({f"model_layer{i}_input_norm_weight": d,
                     f"model_layer{i}_post_norm_weight": d})
        ones[f"model_layer{i}_attn_o_norm_weight" if mixer is kda
             else f"model_layer{i}_attn_kv_a_norm_weight"] = \
            dk if mixer is kda else rank
    # a wider start than the configuration's 0.02, so that at these toy
    # widths the decay, beta and the routing matter
    params = {k: jnp.asarray(0.2 * rng.randn(*s), "float32")
              for k, s in shapes.items()}
    params.update({k: jnp.ones((n,)) for k, n in ones.items()})
    for i in range(sizes["num_hidden_layers"]):
        if i + 1 in lin["kda_layers"]:
            params[f"model_layer{i}_attn_A_log"] = jnp.asarray(
                np.log(rng.uniform(1, 16, hk)), "float32")
            params[f"model_layer{i}_attn_dt_bias"] = jnp.asarray(
                rng.uniform(-4, -1, hk * dk), "float32")
        elif i >= sizes["first_k_dense_replace"]:
            pass
        if i >= sizes["first_k_dense_replace"]:
            params[f"model_layer{i}_moe_e_score_correction_bias"] = \
                jnp.zeros((sizes["published"]["num_experts"],))
    ids = rng.randint(0, sizes["vocab_size"], (1, 129))
    batch = (jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:]))
    limits = sizes["checks"]
    loss, _, grads = reference.gradient_program(
        sizes, limits["gradients"])(params, batch)
    return reference, sizes, params, batch, float(loss), grads


def test_kimi_reference_gives_every_checked_gradient_at_the_rehearsal_size():
    """What the controls below are read against: a loss within 2 of
    ``log(vocab_size)`` (seeded weights of 0.2, not 0.02) and a gradient that is not zero for every leaf the
    configuration's ``checks`` name, so each relative reading is defined."""
    import numpy as np
    _, sizes, _, _, loss, grads = _rehearsal()
    assert abs(loss - np.log(sizes["vocab_size"])) < 2.0
    for name in sizes["checks"]["gradients"]:
        norm = float(np.linalg.norm(np.asarray(grads[name], np.float64)))
        assert np.isfinite(norm) and norm > 0, name


@pytest.mark.parametrize("control,refused_by", [
    ("float8", None),
    ("no_decay", ["model_layer0_attn_A_log"]),
    ("no_delta", None),
    ("no_experts", ["model_layer1_moe_router_weight",
                    "model_layer2_moe_experts_down_weight"]),
])
def test_kimi_control_is_refused_at_the_rehearsal_size(control, refused_by):
    """The comparison ``runners/train_fused_grads.py`` makes with a control
    in the program's place, by limits a tenth of the rehearsal's own (which
    are wide: 0.5, for a bfloat16 program at toy widths): the reference
    against itself reads 0, each stand-in over 0.05 somewhere."""
    import numpy as np
    reference, sizes, params, batch, loss, grads = _rehearsal()
    limits = sizes["checks"]["gradients"]
    got_loss, _, got = reference.gradient_program(
        sizes, limits, stand_in=control)(params, batch)

    def reading(name):
        want = np.asarray(grads[name], np.float64)
        return float(np.linalg.norm(np.asarray(got[name], np.float64) - want)
                     / np.linalg.norm(want))
    over = sorted(name for name in limits if reading(name) > limits[name] / 10)
    assert over, {name: reading(name) for name in limits}
    if refused_by:         # the leaves it removes read a gradient of zero
        assert set(refused_by) <= set(over)
        assert all(reading(name) == pytest.approx(1.0)
                   for name in refused_by)
