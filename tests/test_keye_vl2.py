"""``gluon.model_zoo.nlp.keye_vl2`` (grouped-query attention under a learned
sparse-attention indexer, softmax top-k experts without a shared one) against
the plain float32 reference in ``benchmark/reference/keye_vl2.py``, at a tiny
preset on the CPU: hidden 64, 4 query / 2 key-value heads of 16, 2 index
heads of 8, a selection of 8 keys among 32, 2 layers, 8 experts top-2,
vocabulary 128."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, telemetry
from mxnet_tpu.gluon.model_zoo.nlp import keye_vl2 as zoo
from mxnet_tpu.ops.kernel_mode import interpret_kernels
from mxnet_tpu.parallel import make_mesh, moe
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

from references import keye_vl2 as ref

SIZES = dict(vocab_size=128, hidden_size=64, moe_intermediate_size=32,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_experts=8,
             num_experts_per_tok=2, norm_topk_prob=True, rope_theta=1e7,
             rope_scaling={"mrope_section": [2, 3, 3]}, rms_norm_eps=1e-6,
             sa_config={"indexer_num_heads": 2, "indexer_head_dim": 8,
                        "topk": 8}, expert_offset=0)
WATCHED = ["model_layer0_attn_v_proj_weight", "model_layer0_moe_router_weight",
           "model_layer0_moe_experts_gate_weight",
           "model_layer1_moe_experts_down_weight",
           "model_layer0_indexer_wq_proj_weight",
           "model_layer1_indexer_wk_proj_weight"]


def _net(seed=0, **overrides):
    mx.random.seed(seed)
    net = zoo.keye_vl2_tiny(**overrides)
    net.initialize()
    net.hybridize()         # one compiled forward, not a program an op
    return net


def _params(net):
    return {name[len(net.prefix):]: p.data().data
            for name, p in net.collect_params().items()}


def _batch(seed=0, b=2, t=32, vocab=128):
    ids = np.random.RandomState(seed).randint(0, vocab, (b, t + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), \
        jnp.asarray(ids[:, 1:], jnp.int32)


def _streams(b=2, t=32):
    """Unequal position streams: an image's rows and columns in the middle
    of the text."""
    rng = np.random.RandomState(7)
    return np.stack([np.broadcast_to(np.arange(t), (b, t)),
                     rng.randint(0, 9, (b, t)),
                     rng.randint(0, 9, (b, t))]).astype(np.int32)


@pytest.fixture(scope="module")
def net():
    net = _net()
    # the first call settles the deferred shapes an op at a time and builds
    # the forward: set-up of every test below, not the first one's own time
    net(mx.nd.array(np.asarray(_batch()[0]), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def program_gradients(net):
    tokens, targets = _batch()
    loss = zoo.causal_lm_loss()
    with autograd.record():
        out = net(mx.nd.array(np.asarray(tokens), dtype="int32"))
        value = loss(out, mx.nd.array(np.asarray(targets),
                                      dtype="int32")).mean()
    value.backward()
    return float(value.asnumpy()), {
        name: net.collect_params()[net.prefix + name].grad().asnumpy()
        for name in WATCHED}


@pytest.fixture(scope="module")
def reference_gradients(net):
    return jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, _batch(), SIZES)))(_params(net))


@pytest.mark.parametrize("streams", ["text", "unequal"])
def test_logits_and_index_loss_match_the_reference(net, streams):
    tokens, _ = _batch()
    positions = None if streams == "text" else _streams()
    args = [mx.nd.array(np.asarray(tokens), dtype="int32")]
    if positions is not None:
        args.append(mx.nd.array(positions, dtype="int32"))
    logits, index_loss = net(*args)
    want_logits, want_loss = ref.forward(
        _params(net), tokens, SIZES,
        None if positions is None else jnp.asarray(positions))
    np.testing.assert_allclose(logits.asnumpy(), want_logits, atol=2e-6)
    np.testing.assert_allclose(index_loss.asnumpy(), want_loss, rtol=1e-5)
    assert index_loss.shape == (2,) and float(index_loss.asnumpy().min()) > 0


def test_unequal_position_streams_change_the_result(net):
    tokens = mx.nd.array(np.asarray(_batch()[0]), dtype="int32")
    text = net(tokens)[0].asnumpy()
    image = net(tokens, mx.nd.array(_streams(), dtype="int32"))[0].asnumpy()
    assert np.abs(text - image).max() > 1e-3


def test_both_losses_match_the_reference(program_gradients,
                                         reference_gradients):
    assert program_gradients[0] == pytest.approx(
        float(reference_gradients[0]), rel=1e-6)


@pytest.mark.parametrize("name", WATCHED)
def test_watched_gradient_matches_the_reference(program_gradients,
                                                reference_gradients, name):
    got, want = program_gradients[1][name], np.asarray(
        reference_gradients[1][name])
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
    assert np.linalg.norm(want) > 0


def test_gradient_program_is_jax_grad_of_the_loss(net, reference_gradients):
    """The layer-at-a-time program the chip runs gives what ``jax.grad`` of
    the whole model's loss gives, the loss with both its terms."""
    value, rows, grads = ref.gradient_program(SIZES, WATCHED)(
        _params(net), _batch())
    assert float(value) == pytest.approx(float(reference_gradients[0]),
                                         rel=1e-6)
    # every expert is held: all 2 x 32 x 2 choices land here
    assert np.asarray(rows).tolist() == [128, 128]
    for name in WATCHED:
        want = reference_gradients[1][name]
        assert float(jnp.linalg.norm(grads[name] - want)) <= \
            1e-5 * float(jnp.linalg.norm(want))


@pytest.mark.parametrize("control,moved,unmoved", [
    ("dense_attention", "model_layer0_attn_v_proj_weight", None),
    ("no_experts", "model_layer0_moe_experts_gate_weight",
     "model_layer0_indexer_wq_proj_weight"),
    ("no_index_loss", "model_layer0_indexer_wq_proj_weight",
     "model_layer0_moe_router_weight"),
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
    if unmoved:
        assert reading(unmoved) < 1e-5


def test_softmax_router_matches_the_reference_router():
    rng = np.random.RandomState(3)
    y = jnp.asarray(rng.randn(64, 16), jnp.float32)
    w = jnp.asarray(rng.randn(8, 16), jnp.float32)
    experts, weights = moe.route_softmax_top_k(y, w, 3)
    table = np.zeros((64, 8), np.float32)
    np.put_along_axis(table, np.asarray(experts), np.asarray(weights), axis=1)
    want = ref.router({"moe_router_weight": w}, y,
                      dict(num_experts_per_tok=3, norm_topk_prob=True))
    np.testing.assert_allclose(table, want, atol=1e-6)
    np.testing.assert_allclose(table.sum(1), 1.0, atol=1e-6)
    # without the renormalisation the chosen gates are the softmax's own
    _, raw = moe.route_softmax_top_k(y, w, 3, norm_topk_prob=False)
    assert float(raw.sum(1).max()) < 1.0


@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(net, held):
    """The share test: the routed parts of all ``8 / held`` shares of the
    expert layer add up to what the uncut reference gives for the whole
    layer.  Nothing else is computed by every chip alike (there is no shared
    expert), so nothing is counted once."""
    own = ref.layer_parameters(_params(net), 0)
    rng = np.random.RandomState(5)
    y = jnp.asarray(rng.randn(2, 32, 64), jnp.float32)
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
        # and the reference, given the same share, gives the same part
    np.testing.assert_allclose(total, whole, atol=2e-6)
    part = ref.experts({**own, **{
        f"moe_experts_{k}_weight": own[f"moe_experts_{k}_weight"][4:4 + held]
        for k in ("gate", "up", "down")}}, y.reshape(-1, 64),
        dict(SIZES, num_experts=held, expert_offset=4))
    assert 0 < float(jnp.abs(part).max()) < float(jnp.abs(whole).max()) * 2


@pytest.fixture
def bf16():
    amp.init(target_dtype="bfloat16")
    yield
    amp._deinit_for_tests()


def test_trains_through_the_fused_step_under_amp_with_the_kernels(bf16):
    """``DataParallelTrainer.step`` under ``amp`` with ``remat``, the Pallas
    kernels in the interpreter (head dims of 64, L = 128): the loss falls,
    and the compiled step counts its four kinds of kernel."""
    mx.random.seed(1)
    net = zoo.keye_vl2_tiny(head_dim=64, mrope_section=(8, 12, 12),
                            indexer_head_dim=64, topk=32,
                            num_attention_heads=2, num_key_value_heads=1,
                            num_hidden_layers=1)
    net.initialize()
    tokens, targets = _batch(seed=2, b=1, t=128)
    net(mx.nd.array(np.asarray(tokens), dtype="int32"))
    net.model.remat()
    with interpret_kernels():
        trainer = DataParallelTrainer(
            net, zoo.causal_lm_loss(), "adam", {"learning_rate": 1e-3},
            mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
        telemetry.reset()
        batch = [mx.nd.array(np.asarray(a), dtype="int32")
                 for a in (tokens, targets)]
        losses = [float(trainer.step(*batch).asnumpy()) for _ in range(3)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    # one layer, the forward traced again where remat recomputes it
    assert telemetry.value("dsa.attn.bwd.pallas") == 1
    assert telemetry.value("dsa.attn.fwd.pallas") in (1, 2)
    assert telemetry.value("dsa.index.pallas") >= 1
    assert telemetry.value("dsa.index_loss.pallas") >= 1
    # the loss's two kernels: the value kernel in every trace of the
    # forward, the gradient kernel in the backward rule alone, once a layer
    assert telemetry.value("dsa.index_loss.value.pallas") >= 1
    assert telemetry.value("dsa.index_loss.grad.pallas") == 1
    for name in ("dsa.attn.fwd.scan", "dsa.attn.bwd.scan", "dsa.index.xla",
                 "dsa.index_loss.xla"):
        assert not telemetry.value(name)
    assert telemetry.value("dsa.topk") == 32
    assert telemetry.value("gqa.kv_repeat") == 2
    # the forward's programs take both query heads of the one kv head
    assert telemetry.value("flash.fwd.heads_per_kv_block") == 2
    assert telemetry.value("moe.layers") >= 1


def test_config_refuses_what_the_block_cannot_build():
    with pytest.raises(mx.MXNetError, match="mrope_section"):
        zoo.KeyeVL2Config(head_dim=16, mrope_section=(2, 2, 2))
    with pytest.raises(mx.MXNetError, match="num_key_value_heads"):
        zoo.KeyeVL2Config(num_attention_heads=6, num_key_value_heads=4)
    with pytest.raises(mx.MXNetError, match="not among"):
        zoo.KeyeVL2Config(num_experts=8, experts_held=4, expert_offset=6)


@pytest.mark.parametrize("given, want", [(None, 0.02), (1.0, 1.0)])
def test_embedding_has_a_standard_deviation_of_its_own(given, want):
    """``embedding_initializer_range`` sets the embedding's rows alone
    (``initializer_range`` where it is None); the matrices keep theirs."""
    net = _net(embedding_initializer_range=given)
    net(mx.nd.array(np.zeros((1, 32)), dtype="int32"))  # deferred shapes
    params = _params(net)
    assert abs(float(jnp.std(params["model_embed_weight"])) / want - 1) < 0.05
    assert abs(float(jnp.std(params["lm_head_weight"])) / 0.02 - 1) < 0.05
