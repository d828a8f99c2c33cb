"""``gluon.model_zoo.nlp.sdar_moe`` (grouped-query attention under the
block-diffusion mask over a noisy copy beside the clean one, softmax top-k
experts without a shared one, the masked-denoising loss) against the plain
float32 reference in ``benchmark/reference/sdar_moe.py``, at a tiny preset
on the CPU: hidden 64, 4 query / 2 key-value heads of 16, blocks of 4, 2
layers, 8 experts top-2, vocabulary 128 (the last row the mask token)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, telemetry
from mxnet_tpu.gluon.model_zoo.nlp import sdar_moe as zoo
from mxnet_tpu.ops.kernel_mode import interpret_kernels
from mxnet_tpu.parallel import make_mesh, moe
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

from references import sdar_moe as ref

SIZES = dict(vocab_size=128, hidden_size=64, moe_intermediate_size=32,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_experts=8,
             num_experts_per_tok=2, norm_topk_prob=True, rope_theta=1e6,
             rms_norm_eps=1e-6, block_length=4, expert_offset=0)
WATCHED = ["model_layer0_attn_v_proj_weight", "model_embed_weight",
           "model_layer0_moe_router_weight",
           "model_layer0_moe_experts_gate_weight",
           "model_layer1_moe_experts_down_weight"]


def _net(seed=0, **overrides):
    mx.random.seed(seed)
    net = zoo.sdar_moe_tiny(rope_theta=1e6, **overrides)
    net.initialize()
    net.hybridize()         # one compiled forward, not a program an op
    return net


def _params(net):
    return {name[len(net.prefix):]: p.data().data
            for name, p in net.collect_params().items()}


def _batch(seed=0, b=2, t=32, vocab=128):
    """Rows ``x_t ⊕ x_0`` and their labels as the benchmark draws them: a
    ``t`` a block of 4, each token masked (the last row) with it."""
    rng = np.random.RandomState(seed)
    x0 = rng.randint(0, vocab - 1, (b, t))
    noise = rng.uniform(1e-3, 1, (b, t // 4)).repeat(4, axis=1)
    masked = rng.uniform(size=(b, t)) < noise
    xt = np.where(masked, vocab - 1, x0)
    return jnp.asarray(np.concatenate([xt, x0], 1), jnp.int32), \
        jnp.asarray(np.stack([x0, np.where(masked, 1 / noise, 0)], 1),
                    jnp.float32)


@pytest.fixture(scope="module")
def net():
    net = _net()
    # the first call settles the deferred shapes an op at a time and builds
    # the forward: set-up of every test below, not the first one's own time
    net(mx.nd.array(np.asarray(_batch()[0]), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def program_gradients(net):
    tokens, label = _batch()
    loss = zoo.block_diffusion_loss()
    with autograd.record():
        out = net(mx.nd.array(np.asarray(tokens), dtype="int32"))
        value = loss(out, mx.nd.array(np.asarray(label))).mean()
    value.backward()
    return out.shape, float(value.asnumpy()), {
        name: net.collect_params()[net.prefix + name].grad().asnumpy()
        for name in WATCHED}


@pytest.fixture(scope="module")
def reference_gradients(net):
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, _batch(), SIZES)))(_params(net))


def test_loss_matches_the_reference_and_the_head_sees_the_noisy_half(
        program_gradients, reference_gradients):
    shape, value, _ = program_gradients
    assert shape == (2, 32, 128)
    assert value == pytest.approx(float(reference_gradients[0]), rel=1e-6)


@pytest.mark.parametrize("name", WATCHED)
def test_watched_gradient_matches_the_reference(program_gradients,
                                                reference_gradients, name):
    got, want = program_gradients[2][name], np.asarray(
        reference_gradients[1][name])
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
    assert np.linalg.norm(want) > 0


def test_gradient_program_is_jax_grad_of_the_loss(net, reference_gradients):
    """The layer-at-a-time program the chip runs gives what ``jax.grad`` of
    the whole model's loss gives, the embedding's rows included."""
    value, rows, grads = ref.gradient_program(SIZES, WATCHED)(
        _params(net), _batch())
    assert float(value) == pytest.approx(float(reference_gradients[0]),
                                         rel=1e-6)
    # every expert is held: all 2 rows x 64 tokens x 2 choices land here
    assert np.asarray(rows).tolist() == [256, 256]
    for name in WATCHED:
        want = reference_gradients[1][name]
        assert float(jnp.linalg.norm(grads[name] - want)) <= \
            1e-5 * float(jnp.linalg.norm(want))


def test_the_reference_mask_is_the_published_rule():
    """Rows of the (2T, 2T) mask: a noisy query its own noisy block and the
    clean blocks before it; a clean query the clean blocks up to its own."""
    seen = np.asarray(ref.visible(0, 16, 8, 4))
    noisy_q5 = np.zeros(16, bool)
    noisy_q5[4:8] = True                # its own noisy block
    noisy_q5[8:12] = True               # the clean block before its own
    np.testing.assert_array_equal(seen[5], noisy_q5)
    assert not seen[2, 8:].any() and seen[2, :4].all()  # block 0: own only
    clean_q13 = np.zeros(16, bool)
    clean_q13[8:16] = True
    np.testing.assert_array_equal(seen[13], clean_q13)
    with ref.control("leak"):
        assert np.asarray(ref.visible(0, 16, 8, 4))[5, 12:16].all()
    with ref.control("causal"):
        np.testing.assert_array_equal(np.asarray(ref.visible(0, 16, 8, 4)),
                                      np.tril(np.ones((16, 16), bool)))


@pytest.mark.parametrize("control,moved,unmoved", [
    ("causal", "model_layer0_attn_v_proj_weight", None),
    ("leak", "model_layer0_attn_v_proj_weight", None),
    ("no_experts", "model_layer0_moe_experts_gate_weight", None),
    ("float8", "model_layer1_moe_experts_down_weight", None),
])
def test_controls_move_the_leaves_they_should(net, reference_gradients,
                                              control, moved, unmoved):
    _, _, grads = ref.gradient_program(SIZES, WATCHED, stand_in=control)(
        _params(net), _batch())

    def reading(name):
        want = reference_gradients[1][name]
        return float(jnp.linalg.norm(grads[name] - want)
                     / jnp.linalg.norm(want))
    assert reading(moved) > 0.02


@pytest.mark.parametrize("held", [1, 2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(net, held):
    """The share test: the routed parts of all ``8 / held`` shares of the
    expert layer add up to what the uncut reference gives for the whole
    layer (there is no shared expert, so nothing is counted once)."""
    own = ref.layer_parameters(_params(net), 0)
    rng = np.random.RandomState(5)
    y = jnp.asarray(rng.randn(2, 64, 64), jnp.float32)
    whole = ref.experts(own, y.reshape(-1, 64),
                        dict(SIZES, num_experts=8)).reshape(y.shape)
    total = 0.0
    for offset in range(0, 8, held):
        routed, weights = moe.route_softmax_top_k(
            y.reshape(-1, 64), own["moe_router_weight"], 2)
        total = total + moe.dropless_moe_apply(
            y.reshape(-1, 64), routed, weights,
            *(own[f"moe_experts_{k}_weight"][offset:offset + held]
              for k in ("gate", "up", "down")),
            expert_offset=offset).reshape(y.shape)
    np.testing.assert_allclose(total, whole, atol=2e-6)


@pytest.fixture
def bf16():
    amp.init(target_dtype="bfloat16")
    yield
    amp._deinit_for_tests()


def test_trains_through_the_fused_step_under_amp_with_the_kernels(bf16):
    """``DataParallelTrainer.step`` under ``amp`` with ``remat``, the Pallas
    kernels in the interpreter (head dims of 64, T = 128 a half): the loss
    falls, and the compiled step counts its kernels by their own names."""
    mx.random.seed(1)
    net = zoo.sdar_moe_tiny(head_dim=64, num_attention_heads=2,
                            num_key_value_heads=1, num_hidden_layers=1)
    net.initialize()
    tokens, label = _batch(seed=2, b=1, t=128)
    net(mx.nd.array(np.asarray(tokens), dtype="int32"))
    net.model.remat()
    with interpret_kernels():
        trainer = DataParallelTrainer(
            net, zoo.block_diffusion_loss(), "adam",
            {"learning_rate": 1e-3},
            mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
        telemetry.reset()
        batch = [mx.nd.array(np.asarray(tokens), dtype="int32"),
                 mx.nd.array(np.asarray(label))]
        losses = [float(trainer.step(*batch).asnumpy()) for _ in range(3)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    # one layer: two kernel calls a forward (traced again where remat
    # recomputes it), two a backward
    assert telemetry.value("bd.attn.bwd.pallas") == 2
    assert telemetry.value("bd.attn.fwd.pallas") in (2, 4)
    assert telemetry.value("bd.layers") >= 1
    for name in ("bd.attn.fwd.xla", "bd.attn.bwd.xla", "flash.fwd.pallas",
                 "flash.bwd.pallas"):
        assert not telemetry.value(name)
    assert telemetry.value("bd.block_length") == 4
    assert telemetry.value("bd.offset_rows_empty") == 4
    assert telemetry.value("flash.fwd.blocks_live") >= 1


def test_config_refuses_what_the_block_cannot_build():
    with pytest.raises(mx.MXNetError, match="power of two"):
        zoo.SDARMoeConfig(block_length=3)
    with pytest.raises(mx.MXNetError, match="num_key_value_heads"):
        zoo.SDARMoeConfig(num_attention_heads=6, num_key_value_heads=4)
    with pytest.raises(mx.MXNetError, match="not among"):
        zoo.SDARMoeConfig(num_experts=8, experts_held=4, expert_offset=6)
