"""``gluon.model_zoo.nlp.kimi_linear`` (Kimi Delta Attention in three layers
of four, latent attention without positions in the fourth, sigmoid top-k
experts with a shared one) against the plain float32 reference in
``benchmark/reference/kimi_linear.py``, whose KDA is the per-token
recurrence, at a tiny preset on the CPU: hidden 64, 4 heads of 16, four
layers (the first dense, the third MLA), 8 experts top-2, vocabulary 128."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, telemetry
from mxnet_tpu.gluon.model_zoo.nlp import kimi_linear as zoo
from mxnet_tpu.ops.kernel_mode import interpret_kernels
from mxnet_tpu.parallel import make_mesh, moe
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

from references import kimi_linear as ref

SIZES = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=4,
             num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
             linear_attn_config={"num_heads": 4, "head_dim": 16,
                                 "short_conv_kernel_size": 4,
                                 "kda_layers": [1, 2, 4],
                                 "full_attn_layers": [3]},
             n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
             norm_topk_prob=True, routed_scaling_factor=2.446,
             rms_norm_eps=1e-5, expert_offset=0,
             published={"n_routed_experts": 8})


def _net(seed=0, **overrides):
    mx.random.seed(seed)
    net = zoo.kimi_linear_tiny(**overrides)
    net.initialize()
    net.hybridize()         # one compiled forward, not a program an op
    return net


def _params(net):
    return {name[len(net.prefix):]: p.data().data
            for name, p in net.collect_params().items()}


def _batch(seed=0, b=2, t=48, vocab=128):
    ids = np.random.RandomState(seed).randint(0, vocab, (b, t + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), \
        jnp.asarray(ids[:, 1:], jnp.int32)


def _nd(a):
    return mx.nd.array(np.asarray(a), dtype="int32")


@pytest.fixture(scope="module")
def net():
    net = _net()
    net(_nd(_batch()[0]))   # deferred shapes and the forward: set-up
    return net


@pytest.fixture(scope="module")
def program_gradients(net):
    tokens, targets = _batch()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        value = ce(net(_nd(tokens)), _nd(targets)).mean()
    value.backward()
    return float(value.asnumpy()), {
        name[len(net.prefix):]: p.grad().asnumpy()
        for name, p in net.collect_params().items() if p.grad_req != "null"}


@pytest.fixture(scope="module")
def reference_gradients(net):
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, _batch(), SIZES)))(_params(net))


def test_logits_match_the_reference(net):
    tokens, _ = _batch()
    # float32 on both sides: what is left is the order of the sums (a chunk's
    # products against a token's)
    np.testing.assert_allclose(net(_nd(tokens)).asnumpy(),
                               ref.logits(_params(net), tokens, SIZES),
                               atol=3e-6)


def test_layer_schedule_puts_latent_attention_where_the_lists_say(net):
    kinds = [type(layer.attention).__name__ for layer in net.model.layers]
    assert kinds == ["KimiDeltaAttention", "KimiDeltaAttention",
                     "MLAAttention", "KimiDeltaAttention"]
    dense = [type(layer.mlp).__name__ for layer in net.model.layers]
    assert dense == ["LlamaMLP", "MoEBlock", "MoEBlock", "MoEBlock"]
    # the published schedule: 3 : 1, the last layer latent attention too
    cfg = zoo.KimiLinearConfig()
    assert cfg.full_attn_layers == (4, 8, 12, 16, 20, 24, 27)
    assert len(cfg.kda_layers) == 20 and cfg.kda_layers[:4] == (1, 2, 3, 5)
    cut = zoo.KimiLinearConfig(num_hidden_layers=5)
    assert cut.kda_layers == (1, 2, 3, 5) and cut.full_attn_layers == (4,)


def test_loss_matches_the_reference(program_gradients, reference_gradients):
    assert program_gradients[0] == pytest.approx(
        float(reference_gradients[0]), rel=1e-6)


def test_every_parameter_gradient_matches_the_reference(
        program_gradients, reference_gradients):
    """Every trained parameter: the chunked scan's hand-written backward
    (all five of its gradients reach a leaf: q / k / v through the
    convolutions, g through ``A_log``, ``dt_bias`` and the low-rank pair,
    beta through ``b_proj``) against ``jax.grad`` through the per-token
    recurrence.  1e-4 of a leaf's norm: float32 both sides, sums in another
    order."""
    got, want = program_gradients[1], reference_gradients[1]
    assert len(got) >= 60
    for name in sorted(got):
        w = np.asarray(want[name])
        assert np.linalg.norm(w) > 0, name
        assert np.linalg.norm(got[name] - w) <= 1e-4 * np.linalg.norm(w), name


def test_gradient_program_is_jax_grad_of_the_loss(net, reference_gradients):
    watched = ["model_layer0_attn_v_proj_weight", "model_layer0_attn_A_log",
               "model_layer0_attn_b_proj_weight",
               "model_layer2_attn_kv_b_proj_weight",
               "model_layer1_moe_router_weight",
               "model_layer3_moe_experts_down_weight"]
    value, rows, grads = ref.gradient_program(SIZES, watched)(
        _params(net), _batch())
    assert float(value) == pytest.approx(float(reference_gradients[0]),
                                         rel=1e-6)
    # every expert is held: all 2 x 48 x 2 choices land here; layer 0 dense
    assert np.asarray(rows).tolist() == [0, 192, 192, 192]
    for name in watched:
        want = reference_gradients[1][name]
        assert float(jnp.linalg.norm(grads[name] - want)) <= \
            1e-5 * float(jnp.linalg.norm(want))


@pytest.mark.parametrize("control,moved", [
    ("no_decay", "model_layer0_attn_v_proj_weight"),
    ("no_delta", "model_layer0_attn_b_proj_weight"),
    ("no_experts", "model_layer1_moe_router_weight"),
    ("float8", "model_layer3_moe_experts_down_weight"),
])
def test_controls_move_the_leaves_they_should(net, reference_gradients,
                                              control, moved):
    watched = ["model_layer0_attn_v_proj_weight", "model_layer0_attn_A_log",
               "model_layer0_attn_b_proj_weight",
               "model_layer1_moe_router_weight",
               "model_layer3_moe_experts_down_weight"]
    _, _, grads = ref.gradient_program(SIZES, watched, stand_in=control)(
        _params(net), _batch())

    def reading(name):
        want = reference_gradients[1][name]
        return float(jnp.linalg.norm(grads[name] - want)
                     / jnp.linalg.norm(want))
    assert reading(moved) > 0.02
    if control == "no_decay":       # alpha = 1: the decay gets no gradient
        assert reading("model_layer0_attn_A_log") == pytest.approx(1.0)


def test_nope_latent_attention_is_the_reference_without_rotation(net):
    """``nd.mla_attention(use_nope=True)`` inside the block equals the reference
    without rotation and differs from the rotated form."""
    own = ref.layer_parameters(_params(net), 2)
    x = jnp.asarray(np.random.RandomState(4).randn(2, 48, 64), jnp.float32)
    got = net.model.layers[2].attention(mx.nd.array(np.asarray(x))).asnumpy()
    with jax.default_matmul_precision("highest"):
        plain = ref.mla(own, x, SIZES)
        rotated = ref.mla(own, x, SIZES, rotate=True)
    np.testing.assert_allclose(got, plain, atol=2e-6)
    assert float(jnp.abs(plain - rotated).max()) > 1e-4      # 50 x atol


@pytest.mark.parametrize("held", [2, 4])
def test_shares_add_up_to_the_uncut_layer(net, held):
    """The share test: the routed parts that all ``8 / held`` shares of one
    expert layer give, with the shared expert (which every chip computes
    alike) counted once, add up to the uncut reference's layer."""
    own = ref.layer_parameters(_params(net), 1)
    y = jnp.asarray(np.random.RandomState(5).randn(96, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.ds.moe(own, "moe_", y, SIZES)
        shared = ref.ds.swiglu(own, "moe_shared_", y)
    total = shared
    routed, weights = moe.route_sigmoid_top_k(
        y, own["moe_router_weight"], own["moe_e_score_correction_bias"], 2,
        scale=2.446)
    for offset in range(0, 8, held):
        total = total + moe.dropless_moe_apply(
            y, routed, weights,
            *(own[f"moe_experts_{k}_weight"][offset:offset + held]
              for k in ("gate", "up", "down")), expert_offset=offset)
    np.testing.assert_allclose(total, whole, atol=3e-6)
    # and the block, told its share, holds the share's weights alone
    shapes = {n: p.shape for n, p in _net(
        experts_held=held, expert_offset=4).collect_params().items()}
    assert {s for n, s in shapes.items()
            if n.endswith("layer1_moe_experts_gate_weight")} == \
        {(held, 64, 32)}
    assert {s for n, s in shapes.items()
            if n.endswith("layer1_moe_router_weight")} == {(8, 64)}


def test_decay_parameters_are_drawn_as_the_configuration_says():
    net = _net(seed=3, kda_num_heads=64, kda_head_dim=16, hidden_size=64)
    p = {name[len(net.prefix):]: q.data().asnumpy()
         for name, q in net.collect_params().items()
         if name.endswith(("layer0_attn_A_log", "layer0_attn_dt_bias"))}
    rate = np.exp(np.asarray(p["model_layer0_attn_A_log"]))
    assert 1.0 <= rate.min() and rate.max() <= 16.0 and rate.std() > 2
    dt = np.log1p(np.exp(np.asarray(p["model_layer0_attn_dt_bias"])))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert np.std(np.log(dt)) > 1.0     # log-uniform over two decades


@pytest.fixture
def bf16():
    amp.init(target_dtype="bfloat16")
    yield
    amp._deinit_for_tests()


@pytest.fixture
def amp_net(bf16):
    """Two layers under ``amp`` (KDA over a dense SwiGLU, then latent
    attention at head dims the flash kernels take over an expert layer), the
    deferred shapes settled at a short length: set-up, an op at a time."""
    mx.random.seed(1)
    net = zoo.kimi_linear_tiny(num_hidden_layers=2, full_attn_layers=(2,),
                               qk_nope_head_dim=64, qk_rope_head_dim=64,
                               v_head_dim=64, num_attention_heads=2,
                               kda_num_heads=2, kda_head_dim=32)
    net.initialize()
    net(_nd(_batch(seed=2, b=1, t=16)[0]))
    net.model.remat()
    return net


def test_trains_through_the_fused_step_under_amp_with_the_kernels(amp_net):
    """``DataParallelTrainer.step`` under ``amp`` with per-layer ``remat``,
    the scan's kernels in the interpreter: the loss falls, and the compiled
    step counts the kernels and no XLA form."""
    net = amp_net
    tokens, targets = _batch(seed=2, b=1, t=128)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    with interpret_kernels():
        trainer = DataParallelTrainer(
            net, lambda logits, y: ce(logits.astype("float32"), y), "adam",
            {"learning_rate": 1e-3},
            mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
        telemetry.reset()
        batch = [_nd(tokens), _nd(targets)]
        losses = [float(trainer.step(*batch).asnumpy()) for _ in range(2)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    # one KDA layer, traced again where remat recomputes it
    assert telemetry.value("kda.layers") in (1, 2)
    assert telemetry.value("kda.fwd.pallas") in (1, 2)
    assert telemetry.value("kda.bwd.pallas") == 1
    assert not telemetry.value("kda.fwd.xla")
    assert not telemetry.value("kda.bwd.xla")
    assert telemetry.value("mla.nope") >= 1
    assert telemetry.value("kda.heads") == 2
    assert telemetry.value("kda.heads_per_program") == 2
    assert telemetry.value("kda.chunk") == 64
    assert telemetry.value("kda.chunks_per_seq") == 2
    assert telemetry.value("moe.layers") >= 1


def test_config_refuses_what_the_block_cannot_build():
    with pytest.raises(mx.MXNetError, match="one mixer"):
        zoo.KimiLinearConfig(num_hidden_layers=4, kda_layers=(1, 2),
                             full_attn_layers=(3,))
    with pytest.raises(mx.MXNetError, match="not among"):
        zoo.KimiLinearConfig(num_experts=8, experts_held=4, expert_offset=6)
    with pytest.raises(mx.MXNetError, match="exceeds"):
        zoo.KimiLinearConfig(num_experts=4, num_experts_per_token=8)
