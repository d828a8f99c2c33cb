"""The keye cell through ``run.py --rehearse`` (tiny sizes on the CPU, one
process a run as on the chip) and its four controls at the rehearsal size:
the plain run is ``correct``; each of the reference's stand-ins
(``MXTPU_BENCH_CONTROL``) is refused by the rehearsal's limits — ``float8``
and ``dense_attention`` by at least one gradient limit, ``no_experts`` and
``no_index_loss`` by the leaves they remove."""
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
CELL = "keye-vl2-30b-a3b-ep8-fused-b1-s16384"
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


@pytest.mark.parametrize("control", [None, "dense_attention"])
def test_keye_gradient_check_passes_the_program_and_refuses_dense_attention(
        control):
    result, notes = _rehearse(control)
    grads = notes["check.first_gradient_vs_reference"]
    assert result["correct"] is (control is None), grads
    assert grads["ok"] is (control is None)
    if control:
        assert "control dense_attention in the program's place" in \
            grads["detail"]
    else:
        assert notes["check.first_loss_vs_reference"]["ok"]
    rows = notes["routed_rows"]         # one expert layer, and it routed
    assert [len(rows[k]) for k in ("first", "last")] == [1, 1]
    assert min(rows["first"]) > 0


@functools.lru_cache(maxsize=None)
def _rehearsal():
    """(Cached, not a fixture: tier-1 collects this file's tests by name.)
    The rehearsal's sizes, seeded float32 parameters by the program's
    names, a batch, and the reference's own loss and gradients."""
    import jax
    import numpy as np
    import manifest
    cell = manifest.Manifest().cell(CELL)
    sizes = dict(cell.config)
    sizes.update(cell.config["rehearsal"])
    reference = importlib.import_module("reference.keye_vl2")
    rng = np.random.RandomState(11)
    d, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    h, hkv, hd = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    sa = sizes["sa_config"]
    shapes = {"attn_q_proj_weight": (h * hd, d),
              "attn_k_proj_weight": (hkv * hd, d),
              "attn_v_proj_weight": (hkv * hd, d),
              "attn_o_proj_weight": (d, h * hd),
              "indexer_wq_proj_weight": (sa["indexer_num_heads"]
                                         * sa["indexer_head_dim"], d),
              "indexer_wk_proj_weight": (sa["indexer_head_dim"], d),
              "indexer_weights_proj_weight": (sa["indexer_num_heads"], d),
              "moe_router_weight": (sizes["published"]["num_experts"], d),
              "moe_experts_gate_weight": (sizes["num_experts"], d, w),
              "moe_experts_up_weight": (sizes["num_experts"], d, w),
              "moe_experts_down_weight": (sizes["num_experts"], w, d)}
    params = {"model_embed_weight": (sizes["vocab_size"], d),
              "lm_head_weight": (sizes["vocab_size"], d)}
    for i in range(sizes["num_hidden_layers"]):
        params.update({f"model_layer{i}_{k}": s for k, s in shapes.items()})
    # a wider start than the configuration's 0.02, so that at these toy
    # widths the scores are spread and the selection and the routing matter
    params = {k: jax.numpy.asarray(0.2 * rng.randn(*s), "float32")
              for k, s in params.items()}
    for i in range(sizes["num_hidden_layers"]):
        for k, n in (("input_norm", d), ("post_norm", d),
                     ("attn_q_norm", hd), ("attn_k_norm", hd)):
            params[f"model_layer{i}_{k}_weight"] = jax.numpy.ones((n,))
    params["model_norm_weight"] = jax.numpy.ones((d,))
    ids = rng.randint(0, sizes["vocab_size"], (1, 129))
    batch = (jax.numpy.asarray(ids[:, :-1]), jax.numpy.asarray(ids[:, 1:]))
    limits = sizes["checks"]
    loss, _, grads = reference.gradient_program(
        sizes, limits["gradients"])(params, batch)
    return reference, sizes, params, batch, float(loss), grads


@pytest.mark.parametrize("control,refused_by", [
    ("float8", None), ("dense_attention", None),
    ("no_experts", ["model_layer0_moe_router_weight",
                    "model_layer0_moe_experts_gate_weight",
                    "model_layer0_moe_experts_down_weight"]),
    ("no_index_loss", ["model_layer0_indexer_wq_proj_weight",
                       "model_layer0_indexer_wk_proj_weight"]),
])
def test_control_is_refused_at_the_rehearsal_size(control, refused_by):
    """The comparison ``runners/train_fused_grads.py`` makes with a control
    in the program's place, by the rehearsal's own limits."""
    import numpy as np
    reference, sizes, params, batch, loss, grads = _rehearsal()
    limits = sizes["checks"]["gradients"]
    got_loss, _, got = reference.gradient_program(
        sizes, limits, stand_in=control)(params, batch)

    def reading(name):
        want = np.asarray(grads[name], np.float64)
        return float(np.linalg.norm(np.asarray(got[name], np.float64) - want)
                     / np.linalg.norm(want))
    over = sorted(name for name in limits if reading(name) > limits[name])
    assert over, {name: reading(name) for name in limits}
    if refused_by:         # the leaves it removes read a gradient of zero
        assert set(refused_by) <= set(over)
        assert all(reading(name) == pytest.approx(1.0)
                   for name in refused_by)
    if control == "no_index_loss":      # the first loss sees this one too
        assert abs(float(got_loss) - loss) / loss > \
            sizes["checks"]["loss_rtol"]
