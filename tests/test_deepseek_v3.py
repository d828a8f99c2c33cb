"""``gluon.model_zoo.nlp.deepseek_v3`` (latent attention, the dropless
held-share expert layer, shared experts) against the plain float32
reference in ``tests/references/deepseek_v3.py``, at a tiny preset on the
CPU: hidden 64, 4 heads of 16 + 8 / 16, latent 32, 1 dense + 2 expert
layers, 8 experts top-2, 1 shared, vocabulary 128."""
import contextlib
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.gluon.model_zoo.nlp import deepseek_v3 as zoo
from mxnet_tpu.gluon.model_zoo.nlp.decoder import MoEBlock
from mxnet_tpu.ops.kernel_mode import interpret_kernels
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

from references import deepseek_v3 as ref

SIZES = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
             routed_scaling_factor=2.448, norm_topk_prob=True,
             rope_theta=1000000.0, rms_norm_eps=1e-6)


def _net(seed=0, **overrides):
    """The tiny preset with seeded weights; ``overrides`` in the config's
    own names (``n_routed_experts`` is the router's width here)."""
    mx.random.seed(seed)
    net = zoo.deepseek_v3_tiny(**overrides)
    net.initialize()
    net.hybridize()         # one compiled forward, not a program an op
    net(mx.nd.array(np.zeros((2, 8)), dtype="int32"))
    return net


@pytest.fixture(scope="module", autouse=True)
def _first_use():
    """The process's first network pays for ~100 one-op programs (deferred
    shapes are resolved by an eager pass); pay it here, as set-up, so that
    no test's own time depends on being the first of the file."""
    _net()


def _params(net):
    return {name[len(net.prefix):]: p.data().data
            for name, p in net.collect_params().items()}


def _batch(seed, b=2, t=32, vocab=128):
    ids = np.random.RandomState(seed).randint(0, vocab, (b, t + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), \
        jnp.asarray(ids[:, 1:], jnp.int32)


def _sizes(**overrides):
    """The reference's view: ``n_routed_experts`` the experts held, the
    router's width under ``published``."""
    s = dict(SIZES, **overrides)
    s.setdefault("published", {"n_routed_experts": s["n_routed_experts"]})
    return s


def _ref_loss(params, batch, sizes):
    """The reference's loss as one compiled program (op by op the CPU takes
    ten times as long)."""
    return float(jax.jit(lambda p, b: ref.loss(p, b, sizes))(params, batch))


# ---------------------------------------------------------------------------
# logits and loss
# ---------------------------------------------------------------------------

# float32 on both sides; the program sums the same products in another
# order (flash blocks, sorted rows, fused projections) through 3 layers
# whose logits are O(1): 2e-5 is ~100 float32 roundings of such a value and
# far under what a wrong mask, scale or weight (>= 1e-2) would give.
@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_logits_and_loss_match_the_reference(kernels):
    # T = 128 so that the interpreted kernels take these shapes
    # (flash blocks of 128; 128 * 2 buffer rows); d = 24 declines the flash
    # kernel (not a multiple of 64), the grouped product runs interpreted
    net = _net()
    tokens, targets = _batch(1, t=128)
    with interpret_kernels() if kernels == "interpret" else contextlib.nullcontext():
        got = net(mx.nd.array(tokens, dtype="int32")).asnumpy()
    want = jax.jit(lambda p, t: ref.logits(p, t, _sizes()))(
        _params(net), tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    got_loss = float(ce(mx.nd.array(got), mx.nd.array(targets)).mean()
                     .asnumpy())
    want_loss = _ref_loss(_params(net), (tokens, targets), _sizes())
    assert got_loss == pytest.approx(want_loss, rel=1e-5)
    assert abs(want_loss - np.log(128)) < 0.5       # a from-scratch loss


def test_every_gradient_through_the_fused_step_matches_jax_grad():
    """One SGD step at rate 1 through ``DataParallelTrainer``: w0 - w1 is
    the program's gradient.  Against ``jax.grad`` of the reference, every
    parameter.  float32; a gradient is a sum over 64 tokens of products that
    went through 3 layers twice, and w0 - w1 itself is rounded at 6e-8 of
    |w| <= 1: 2e-5 absolute + 1e-3 relative (largest gradients are ~1e-1)."""
    net = _net(seed=3)
    tokens, targets = _batch(4)
    before = {k: np.asarray(v) for k, v in _params(net).items()}
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, (tokens, targets), _sizes())))(
        {k: jnp.asarray(v) for k, v in before.items()})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = DataParallelTrainer(
        net, lambda out, label: ce(out, label), "sgd",
        {"learning_rate": 1.0},
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    loss0 = float(trainer.step(mx.nd.array(tokens, dtype="int32"),
                               mx.nd.array(targets, dtype="int32")).asnumpy())
    assert loss0 == pytest.approx(float(want_loss), rel=1e-5)
    after = _params(net)
    assert len(before) == 3 + 10 + 2 * 15       # every parameter is here
    for name, w0 in before.items():
        got = w0 - np.asarray(after[name])
        np.testing.assert_allclose(got, want[name], rtol=1e-3, atol=2e-5,
                                   err_msg=name)
        if "e_score_correction_bias" in name:
            assert not got.any()                # a buffer: no gradient
        else:
            assert np.abs(got).max() > 1e-6, name   # and none is dead


# ---------------------------------------------------------------------------
# the expert layer and its share
# ---------------------------------------------------------------------------

def _moe_block(weights, offset, held, shared):
    """A MoEBlock holding experts offset .. offset + held of ``weights``
    (the uncut layer's parameters by the reference's names)."""
    block = MoEBlock(
        hidden_size=SIZES["hidden_size"],
        expert_width=SIZES["moe_intermediate_size"],
        num_experts=SIZES["n_routed_experts"], experts_held=held,
        expert_offset=offset, top_k=SIZES["num_experts_per_tok"],
        shared_experts=1 if shared else 0,
        routed_scaling_factor=SIZES["routed_scaling_factor"],
        norm_topk_prob=SIZES["norm_topk_prob"], scoring_func="sigmoid",
        initializer_range=0.02, prefix="moe_")
    block.initialize()
    block(mx.nd.zeros((1, 4, SIZES["hidden_size"])))
    for name, p in block.collect_params().items():
        value = weights[name]
        if "_experts_" in name:
            value = value[offset:offset + held]
        p.set_data(mx.nd.array(value))
    return block


def _share_of(weights, offset, held):
    """The parameters one share holds, as the reference takes them."""
    return {k: jnp.asarray(v[offset:offset + held] if "_experts_" in k
                           else v) for k, v in weights.items()}


def _uncut_layer(seed, bias=None):
    rng = np.random.RandomState(seed)
    d, w, e = SIZES["hidden_size"], SIZES["moe_intermediate_size"], 8
    weights = {
        "moe_router_weight": rng.randn(e, d) * 0.3,
        "moe_e_score_correction_bias":
            np.zeros(e) if bias is None else np.asarray(bias, np.float64),
        "moe_experts_gate_weight": rng.randn(e, d, w) * 0.2,
        "moe_experts_up_weight": rng.randn(e, d, w) * 0.2,
        "moe_experts_down_weight": rng.randn(e, w, d) * 0.2,
        "moe_shared_dense0_weight": rng.randn(w, d) * 0.2,
        "moe_shared_dense1_weight": rng.randn(w, d) * 0.2,
        "moe_shared_dense2_weight": rng.randn(d, w) * 0.2,
    }
    weights = {k: v.astype(np.float32) for k, v in weights.items()}
    y = rng.randn(2, 64, d).astype(np.float32)
    return weights, y


# float32, outputs O(1), two or three reorderings of 32- and 64-term sums
TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_the_shares_add_up_to_the_uncut_layer(kernels):
    """8 experts as 4 shares of 2: the routed parts of all shares, plus the
    shared expert counted once, are what the uncut reference layer gives."""
    weights, y = _uncut_layer(5)
    total = np.zeros_like(y)
    with interpret_kernels() if kernels == "interpret" else contextlib.nullcontext():
        for share in range(4):
            block = _moe_block(weights, 2 * share, 2, shared=share == 0)
            part = block(mx.nd.array(y)).asnumpy()
            # each share alone is the reference given the same share
            want_part = ref.routed_experts(
                _share_of(weights, 2 * share, 2), "moe_", jnp.asarray(y),
                _sizes(), expert_offset=2 * share, held=2)
            if share == 0:
                want_part = want_part + ref.swiglu(weights, "moe_shared_", y)
            np.testing.assert_allclose(part, want_part, **TOL)
            total += part
    uncut = ref.moe({k: jnp.asarray(v) for k, v in weights.items()}, "moe_",
                    jnp.asarray(y), _sizes())
    np.testing.assert_allclose(total, uncut, **TOL)
    # and a share is not the whole: the parts differ from one another
    assert np.abs(total - part).max() > 1e-2


@pytest.mark.parametrize("kernels", ["xla", "interpret"])
def test_dropless_when_every_token_goes_to_the_held_experts(kernels):
    """A selection bias of +10 on experts 4 and 5 sends both choices of
    every token to the two experts held: the buffer's worst case, all
    tokens * 2 rows routed.  Nothing is dropped: the result is the
    reference's, which has no buffer at all."""
    bias = [0, 0, 0, 0, 10, 10, 0, 0]
    weights, y = _uncut_layer(6, bias=bias)
    block = _moe_block(weights, 4, 2, shared=True)
    before = telemetry.value("moe.layers") or 0
    with interpret_kernels() if kernels == "interpret" else contextlib.nullcontext():
        got = block(mx.nd.array(y)).asnumpy()
    table = np.asarray(ref.router(weights, "moe_", jnp.asarray(y), _sizes()))
    assert ((table > 0).sum(-1) == 2).all()
    assert (table[..., 4:6] > 0).all()          # every choice is held here
    want = ref.routed_experts(_share_of(weights, 4, 2), "moe_",
                              jnp.asarray(y), _sizes(), expert_offset=4,
                              held=2) \
        + ref.swiglu(weights, "moe_shared_", y)
    np.testing.assert_allclose(got, want, **TOL)
    assert telemetry.value("moe.layers") - before == 1
    assert telemetry.value("moe.rows_buffer") == 2 * 64 * 2
    assert telemetry.value("moe.experts_held") == 2
    assert telemetry.value("moe.top_k") == 2


def test_no_choice_held_gives_the_shared_expert_alone():
    bias = [10, 10, 0, 0, 0, 0, 0, 0]
    weights, y = _uncut_layer(7, bias=bias)
    block = _moe_block(weights, 6, 2, shared=True)
    got = block(mx.nd.array(y)).asnumpy()
    np.testing.assert_allclose(got, ref.swiglu(weights, "moe_shared_", y),
                               **TOL)


def test_config_refuses_a_share_outside_the_routed_experts():
    with pytest.raises(mx.MXNetError):
        zoo.DeepseekV3Config(n_routed_experts=8, experts_held=4,
                             expert_offset=6)
    with pytest.raises(mx.MXNetError):
        zoo.DeepseekV3Config(n_routed_experts=4, num_experts_per_tok=6)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def test_rope_in_place_and_permute_then_rotate_give_the_same_scores():
    """The noted departure: Hugging Face de-interleaves q_pe and k_pe and
    rotates halves, the program rotates interleaved pairs in place.  The
    two results are the same vector up to one fixed permutation of its
    dims, so every q . k is the same (float32: 1e-5 of O(10) dot products)."""
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(2, 4, 32, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 1, 32, 8), jnp.float32)
    angles = ref.rope_angles(jnp.arange(32), 8, 1e6)
    ours = jnp.einsum("bhqd,bhkd->bhqk", ref.rope_interleaved(q, angles),
                      jnp.broadcast_to(ref.rope_interleaved(k, angles),
                                       q.shape))
    hf = jnp.einsum(
        "bhqd,bhkd->bhqk", ref.rope_permute_then_rotate_halves(q, angles),
        jnp.broadcast_to(ref.rope_permute_then_rotate_halves(k, angles),
                         q.shape))
    np.testing.assert_allclose(ours, hf, rtol=1e-5, atol=1e-5)
    # the program's own rotation is the reference's
    from mxnet_tpu.ops.norm_rope import rope_interleaved as _rot_interleaved
    np.testing.assert_allclose(
        _rot_interleaved(q, jnp.cos(angles), jnp.sin(angles)),
        ref.rope_interleaved(q, angles), rtol=1e-6, atol=1e-6)
    # and the permutation is what relates the two forms
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    np.testing.assert_allclose(
        np.asarray(ref.rope_interleaved(q, angles))[..., perm],
        ref.rope_permute_then_rotate_halves(q, angles), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kernels", ["scan", "interpret"])
def test_mla_attention_op_at_the_published_head_dims(kernels):
    """``nd.mla_attention`` at 128 + 64 / 128 (the flash kernel's shapes,
    interpreted, and the scan) against the reference's plain attention."""
    rng = np.random.RandomState(9)
    h, nope, rope, dv, t = 2, 128, 64, 128, 128
    q = rng.randn(1, t, h * (nope + rope)).astype(np.float32)
    kv = rng.randn(1, t, h * (nope + dv)).astype(np.float32)
    k_pe = rng.randn(1, t, rope).astype(np.float32)
    before = telemetry.value("flash.fwd.pallas") or 0
    with interpret_kernels() if kernels == "interpret" else contextlib.nullcontext():
        got = mx.nd.mla_attention(
            mx.nd.array(q), mx.nd.array(kv), mx.nd.array(k_pe), num_heads=h,
            qk_nope_head_dim=nope, qk_rope_head_dim=rope, v_head_dim=dv,
            rope_theta=1e6).asnumpy()
    assert (telemetry.value("flash.fwd.pallas") or 0) - before == \
        (1 if kernels == "interpret" else 0)
    angles = ref.rope_angles(jnp.arange(t), rope, 1e6)
    q4 = jnp.asarray(q).reshape(1, t, h, -1).transpose(0, 2, 1, 3)
    kv4 = jnp.asarray(kv).reshape(1, t, h, -1).transpose(0, 2, 1, 3)
    query = jnp.concatenate(
        [q4[..., :nope], ref.rope_interleaved(q4[..., nope:], angles)], -1)
    key = jnp.concatenate(
        [kv4[..., :nope], jnp.broadcast_to(
            ref.rope_interleaved(jnp.asarray(k_pe)[:, None], angles),
            (1, h, t, rope))], -1)
    want = ref.causal_attention(query, key, kv4[..., nope:])
    want = want.transpose(0, 2, 1, 3).reshape(1, t, h * dv)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("nope_switch", [False, True])
def test_mla_attention_nope_switch_off_gives_what_it_gave(nope_switch):
    """``nd.mla_attention(use_nope=False)`` is the call it was — the rotated
    result, and the very program (its jaxpr equals the default call's, so
    kanana's step traces what it traced) — and ``use_nope=True`` is attention
    over the same operands with no rotation at all (``kimi_linear``)."""
    from mxnet_tpu.ndarray import ops as nd_ops
    rng = np.random.RandomState(4)
    h, nope, rope, dv, t = 2, 16, 8, 16, 24
    q = jnp.asarray(rng.randn(1, t, h * (nope + rope)), jnp.float32)
    kv = jnp.asarray(rng.randn(1, t, h * (nope + dv)), jnp.float32)
    k_pe = jnp.asarray(rng.randn(1, t, rope), jnp.float32)
    kw = dict(num_heads=h, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
              v_head_dim=dv, rope_theta=1e6)

    def call(**more):
        return lambda *a: nd_ops.mla_attention(
            *(mx.nd.NDArray(x) for x in a), **kw, **more).data
    got = call(use_nope=nope_switch)(q, kv, k_pe)
    q4 = q.reshape(1, t, h, -1).transpose(0, 2, 1, 3)
    kv4 = kv.reshape(1, t, h, -1).transpose(0, 2, 1, 3)
    q_pe, key_pe = q4[..., nope:], k_pe[:, None]
    if not nope_switch:
        angles = ref.rope_angles(jnp.arange(t), rope, 1e6)
        q_pe = ref.rope_interleaved(q_pe, angles)
        key_pe = ref.rope_interleaved(key_pe, angles)
        assert str(jax.make_jaxpr(call())(q, kv, k_pe)) == \
            str(jax.make_jaxpr(call(use_nope=False))(q, kv, k_pe))
    want = ref.causal_attention(
        jnp.concatenate([q4[..., :nope], q_pe], -1),
        jnp.concatenate([kv4[..., :nope],
                         jnp.broadcast_to(key_pe, (1, h, t, rope))], -1),
        kv4[..., nope:]).transpose(0, 2, 1, 3).reshape(1, t, h * dv)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# counters, the constructor, remat, the benchmark's copy of the reference
# ---------------------------------------------------------------------------

def test_counters_of_one_traced_step():
    from mxnet_tpu.parallel import moe
    moe._forward.clear_cache()      # the expert layer's traces are shared
    moe._backward.clear_cache()     # by shape: count this step's own
    net = _net(seed=10)
    tokens, targets = _batch(11)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = DataParallelTrainer(
        net, lambda out, label: ce(out, label), "adam",
        {"learning_rate": 1e-3},
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    names = ("moe.layers", "mla.layers", "moe.gmm.xla", "flash.fwd.scan")
    before = {n: telemetry.value(n) or 0 for n in names}
    losses = [float(trainer.step(mx.nd.array(tokens, dtype="int32"),
                                 mx.nd.array(targets, dtype="int32"))
                    .asnumpy()) for _ in range(3)]
    delta = {n: telemetry.value(n) - before[n] for n in names}
    # counted while tracing: once a layer for the one compiled step; the
    # grouped products once for both expert layers, which share a jitted
    # trace a direction (3 in the forward, 3 in the backward's own forward)
    assert delta == {"moe.layers": 2, "mla.layers": 3, "moe.gmm.xla": 6,
                     "flash.fwd.scan": 3}
    assert losses[2] < losses[0]


def test_published_constructor_has_the_published_shapes():
    """No parameter is allocated: the shapes the constructor declares at the
    published sizes, one chip's share (deferred input dims stay 0)."""
    net = zoo.kanana_2_30b_a3b(num_hidden_layers=2, experts_held=16,
                               vocab_size=16032)
    shapes = {name[len(net.prefix):]: p.shape
              for name, p in net.collect_params().items()}
    assert shapes["model_layer1_moe_router_weight"] == (128, 2048)
    assert shapes["model_layer1_moe_experts_gate_weight"] == (16, 2048, 768)
    assert shapes["model_layer1_moe_experts_down_weight"] == (16, 768, 2048)
    assert shapes["model_layer1_moe_shared_dense0_weight"][0] == 2 * 768
    assert shapes["model_layer0_mlp_dense0_weight"][0] == 6144
    assert shapes["model_layer0_attn_q_proj_weight"][0] == 32 * 192
    assert shapes["model_layer0_attn_kv_a_proj_weight"][0] == 512 + 64
    assert shapes["model_layer0_attn_kv_b_proj_weight"][0] == 32 * 256
    assert shapes["lm_head_weight"][0] == 16032
    cfg = zoo.DeepseekV3Config()
    assert (cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.rope_theta, cfg.rms_norm_eps) == (6, 2.448, 1e6, 1e-6)


def test_a_block_of_heads_at_a_time_gives_the_same_loss_and_gradients():
    """The benchmark takes the reference with ``blocked_attention`` (one
    sequence and one block of heads at a time, each block a
    ``jax.checkpoint``), the tests here with plain ``causal_attention``: the
    same file, and at the tiny size the same loss and gradients to float32
    rounding.  The rows it counts for the expert layers are the choices that
    landed on a held expert."""
    net = _net(seed=14, experts_held=4, expert_offset=0)
    tokens, targets = _batch(15)
    sizes = _sizes(n_routed_experts=4, published={"n_routed_experts": 8})
    params = _params(net)
    plain, g_plain = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, (tokens, targets), sizes)))(params)
    (blocked, rows), g_blocked = jax.jit(jax.value_and_grad(
        lambda p: ref.benchmark_copy.loss_and_rows(p, (tokens, targets),
                                                   sizes), has_aux=True))(
        params)
    assert float(plain) == pytest.approx(float(blocked), rel=1e-6)
    for name in g_plain:
        np.testing.assert_allclose(g_plain[name], g_blocked[name], rtol=1e-4,
                                   atol=1e-7, err_msg=name)
    # a dense layer, then 2 expert layers: 64 tokens choosing 2 of 8
    # experts, 4 of them held
    assert rows.shape == (3,) and int(rows[0]) == 0
    assert all(0 < int(r) < 64 * 2 for r in rows[1:])
    got = float(gluon.loss.SoftmaxCrossEntropyLoss()(
        net(mx.nd.array(tokens, dtype="int32")),
        mx.nd.array(targets)).mean().asnumpy())
    assert got == pytest.approx(float(plain), rel=1e-5)


# ---------------------------------------------------------------------------
# what the benchmark cell's gradient check stands on
# (benchmark/runners/train_fused_grads.py)
# ---------------------------------------------------------------------------

WATCHED = ["model_layer0_attn_kv_b_proj_weight",
           "model_layer1_moe_router_weight",
           "model_layer1_moe_experts_gate_weight",
           "model_layer2_moe_experts_down_weight"]


def _relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_adams_first_moment_after_one_step_is_the_gradient():
    """The cell trains with Adam; its runner reads the program's gradient
    out of the first moment that ``state_dict()`` hands over after the first
    step from zero state: ``m = (1 - beta1) g``.  float32 here, so the
    gradient agrees with ``jax.grad`` of the reference as the SGD test's
    does (1e-3 of the leaf's norm is ten times what that test sees)."""
    net = _net(seed=21)
    tokens, targets = _batch(22)
    want = jax.jit(jax.grad(
        lambda p: ref.loss(p, (tokens, targets), _sizes())))(_params(net))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = DataParallelTrainer(
        net, lambda out, label: ce(out, label), "adam",
        {"learning_rate": 1e-5},
        mesh=make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    trainer.step(mx.nd.array(tokens, dtype="int32"),
                 mx.nd.array(targets, dtype="int32"))
    moments = trainer.state_dict()["arrays"]
    order = sorted(net.collect_params())
    for name in WATCHED:
        m = moments[f"opt/{order.index(net.prefix + name)}/m"].asnumpy()
        assert _relative(m / (1 - 0.9), want[name]) < 1e-3, name


def _loss_and_gradients(params, batch, control=None):
    loss, _, grads = ref.benchmark_copy.gradient_program(
        _sizes(), WATCHED, stand_in=control)(params, batch)
    return float(loss), grads


@functools.lru_cache(maxsize=None)
def _stand_in_case():
    """Parameters, a batch, and the reference's own loss and gradients: the
    same for both controls, so computed once."""
    params, batch = _params(_net(seed=23)), _batch(24)
    return (params, batch) + _loss_and_gradients(params, batch)


def test_gradients_a_layer_at_a_time_are_jax_grad_of_the_loss():
    """``gradient_program`` chains the layers' vjps a sequence and a layer
    at a time (small programs for the chip); the same numbers as ``jax.grad``
    of the whole loss, to float32 rounding, and the same loss and rows."""
    params, batch, loss, grads = _stand_in_case()
    (want_loss, want_rows), want = jax.jit(jax.value_and_grad(
        lambda p: ref.benchmark_copy.loss_and_rows(p, batch, _sizes()),
        has_aux=True))(params)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert sorted(grads) == sorted(WATCHED)
    for name in WATCHED:
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-4,
                                   atol=1e-7, err_msg=name)
    _, rows, none = ref.benchmark_copy.gradient_program(_sizes(), WATCHED)(
        params, batch, gradients=False)
    assert not none and np.array_equal(rows, want_rows)
    with pytest.raises(ValueError):
        ref.benchmark_copy.gradient_program(_sizes(), ["lm_head_weight"])


@pytest.mark.parametrize("control", ["float8", "no_experts", "bfloat16"])
def test_the_references_stand_ins_move_the_gradients(control):
    """``reference.control``: the stand-ins a run of the cell has to refuse.
    With the routed experts left out, their weights and the router get no
    gradient, which reads 1 (a state left unchanged), and the leaf under
    them moves; with every matmul's operands rounded to float8 every
    watched gradient moves by percents, the loss by far less; rounded to
    bfloat16, by less than a quarter of that where the path from the loss is
    continuous (the first and the last leaf).  The router and the expert
    under it can read as much in bfloat16 as in float8: one top-k choice of
    the 128 here that flips moves them more than any rounding."""
    params, batch, want_loss, want = _stand_in_case()
    got_loss, got = _loss_and_gradients(params, batch, control)
    readings = {name: _relative(got[name], want[name]) for name in WATCHED}
    if control == "no_experts":
        for name in WATCHED[1:]:
            assert readings[name] == 1.0, name
        assert readings[WATCHED[0]] > 0.05
    elif control == "float8":
        assert all(0.02 < r < 0.9 for r in readings.values()), readings
        assert abs(got_loss - want_loss) < 2e-2 * want_loss
        _stand_in_case.float8 = readings
    else:
        worse = getattr(_stand_in_case, "float8", None) or {
            name: _relative(g, want[name]) for name, g in
            _loss_and_gradients(params, batch, "float8")[1].items()}
        assert all(0 < readings[name] < worse[name] / 4
                   for name in (WATCHED[0], WATCHED[-1]))
        assert all(readings[name] > 0 for name in WATCHED)
    with pytest.raises(ValueError):
        with ref.benchmark_copy.control("float4"):
            pass
