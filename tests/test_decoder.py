"""``gluon.model_zoo.nlp.decoder``, the decoder and expert layer that
``deepseek_v3``, ``keye_vl2``, ``kimi_linear`` and ``sdar_moe`` share,
through each model's tiny preset on the CPU (8 experts, hidden 64, expert
width 32)."""
import functools

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo.nlp import deepseek_v3, keye_vl2, \
    kimi_linear, sdar_moe
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer


def _next_token_batch(seed, b=2, t=32, vocab=128):
    ids = np.random.RandomState(seed).randint(0, vocab, (b, t + 1))
    return mx.nd.array(ids[:, :-1], dtype="int32"), \
        mx.nd.array(ids[:, 1:], dtype="int32")


def _block_diffusion_batch(seed, b=2, t=32, vocab=128):
    """Rows ``x_t ⊕ x_0`` and their labels (clean ids, 1 / t where masked):
    a ``t`` a block of 4, the last row the mask token."""
    rng = np.random.RandomState(seed)
    x0 = rng.randint(0, vocab - 1, (b, t))
    noise = rng.uniform(1e-3, 1, (b, t // 4)).repeat(4, axis=1)
    masked = rng.uniform(size=(b, t)) < noise
    xt = np.where(masked, vocab - 1, x0)
    return mx.nd.array(np.concatenate([xt, x0], 1), dtype="int32"), \
        mx.nd.array(np.stack([x0, np.where(masked, 1 / noise, 0)], 1))


def _cross_entropy():
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    return lambda out, label: ce(out, label)


# preset: (network, loss, batch, first expert layer, sigmoid router with a
# shared expert); kimi at two layers, one of each mixer, for the compile time
PRESETS = {
    "deepseek_v3": (deepseek_v3.deepseek_v3_tiny, _cross_entropy,
                    _next_token_batch, 1, True),
    "keye_vl2": (keye_vl2.keye_vl2_tiny, keye_vl2.causal_lm_loss,
                 _next_token_batch, 0, False),
    "kimi_linear": (functools.partial(kimi_linear.kimi_linear_tiny,
                                      num_hidden_layers=2,
                                      full_attn_layers=(2,)),
                    _cross_entropy, _next_token_batch, 1, True),
    "sdar_moe": (sdar_moe.sdar_moe_tiny, sdar_moe.block_diffusion_loss,
                 _block_diffusion_batch, 0, False),
}


@pytest.fixture(scope="module", autouse=True)
def _first_use():
    """A preset's first network pays for its ~100 one-op programs (deferred
    shapes are resolved by an eager pass); pay them here, as set-up, so that
    no test's own time depends on coming first."""
    for make, _, batch, _, _ in PRESETS.values():
        net = make()
        net.initialize()
        net(batch(0)[0])


@pytest.mark.parametrize("preset", list(PRESETS))
def test_remat_changes_no_number(preset):
    """Two SGD steps through the fused step with and without per-layer
    remat give the same losses."""
    make, loss, batch, _, _ = PRESETS[preset]
    data, label = batch(12)
    losses = []
    for remat in (False, True):
        mx.random.seed(13)
        net = make()
        net.initialize()
        net.hybridize()
        if remat:
            net.model.remat()
        trainer = DataParallelTrainer(
            net, loss(), "sgd", {"learning_rate": 0.5},
            mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
        losses.append([float(trainer.step(data, label).asnumpy())
                       for _ in range(2)])
    # the same float32 operations, scheduled again in the backward pass
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)


@pytest.mark.parametrize("preset", list(PRESETS))
def test_block_holds_a_share_of_the_experts(preset):
    """``experts_held`` / ``expert_offset`` reach every expert layer: a
    share's network has the share's expert weights and the full router;
    the selection bias and the shared expert are there only where the
    model has them."""
    make, _, _, first, sigmoid_shared = PRESETS[preset]
    net = make(experts_held=2, expert_offset=4)
    shapes = {n[len(net.prefix):]: p.shape
              for n, p in net.collect_params().items()}
    layer = f"model_layer{first}_moe_"
    assert shapes[layer + "experts_gate_weight"] == (2, 64, 32)
    assert shapes[layer + "experts_down_weight"] == (2, 32, 64)
    assert shapes[layer + "router_weight"] == (8, 64)
    assert all(shape[0] == 2 for name, shape in shapes.items()
               if "_moe_experts_" in name)
    assert (shapes.get(layer + "e_score_correction_bias") == (8,)) \
        == sigmoid_shared
    assert any(name.startswith(layer + "shared_") for name in shapes) \
        == sigmoid_shared
