"""AMP mixed precision (reference: tests/python/unittest/test_amp.py).

Checks: op-list casting (MXU ops run bf16, blacklist ops run fp32),
end-to-end bf16 training step, fp16 dynamic loss scaling skip-on-overflow.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon

nd = mx.nd


@pytest.fixture
def amp_bf16():
    amp.init(target_dtype="bfloat16")
    yield
    amp._deinit_for_tests()


@pytest.fixture
def amp_fp16():
    amp.init(target_dtype="float16")
    yield
    amp._deinit_for_tests()


def test_target_ops_cast_down(amp_bf16):
    a = nd.random.uniform(shape=(4, 8))
    b = nd.random.uniform(shape=(8, 4))
    out = nd.dot(a, b)
    assert str(out.data.dtype) == "bfloat16"


def test_fp32_ops_cast_up(amp_bf16):
    x = nd.random.uniform(shape=(4, 8)).astype("bfloat16")
    out = nd.softmax(x)
    assert str(out.data.dtype) == "float32"


def test_latent_attention_and_expert_ops_are_placed(amp_bf16):
    """The ops of model_zoo.nlp.deepseek_v3: the router's scores and RMSNorm
    run in float32 whatever arrives, latent attention and the routed
    experts' grouped products in bfloat16 (float32 parameters cast at the
    op, as FullyConnected's are)."""
    from mxnet_tpu.amp import lists
    assert {"mla_attention", "moe_experts"} <= set(lists.TARGET_DTYPE_OPS)
    assert {"rms_norm", "moe_router"} <= set(lists.FP32_OPS)
    x16 = nd.random.uniform(shape=(2, 8, 16)).astype("bfloat16")
    normed = nd.rms_norm(x16, nd.ones((16,)), eps=1e-6)
    assert str(normed.data.dtype) == "float32"
    chosen, gates = nd.moe_router(x16, nd.random.uniform(shape=(4, 16)),
                                  nd.zeros((4,)), top_k=2)
    assert str(chosen.data.dtype) == str(gates.data.dtype) == "float32"
    assert chosen.shape == gates.shape == (2, 8, 2)
    assert (gates.asnumpy() > 0).all()
    # the ids and the combine weights reach the expert op as the router made
    # them (float32) ...
    w = [nd.random.uniform(shape=(4, 16, 8)),
         nd.random.uniform(shape=(4, 16, 8)),
         nd.random.uniform(shape=(4, 8, 16))]
    fine = gates * 0 + (1 + 2.0 ** -10)
    experts = nd.moe_experts(normed, chosen, fine, *w)
    assert str(experts.data.dtype) == "bfloat16"
    assert experts.shape == (2, 8, 16)
    # ... because amp leaves the two arguments lists.KEEP_DTYPE_ARGS names as
    # they arrive, by position and by keyword, and casts the rest
    assert lists.KEEP_DTYPE_ARGS["moe_experts"] == ("experts", "weights")
    seen = {}
    spy = amp._wrap(
        lambda data, experts, weights: seen.update(
            data=data, experts=experts, weights=weights),
        "low", "bfloat16", lists.KEEP_DTYPE_ARGS["moe_experts"])
    spy(normed, chosen, weights=fine)
    assert [str(seen[k].data.dtype) for k in ("data", "experts", "weights")] \
        == ["bfloat16", "float32", "float32"]
    attn = nd.mla_attention(
        nd.random.uniform(shape=(1, 8, 2 * 12)),
        nd.random.uniform(shape=(1, 8, 2 * 16)),
        nd.random.uniform(shape=(1, 8, 4)), num_heads=2, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8)
    assert str(attn.data.dtype) == "bfloat16"
    assert attn.shape == (1, 8, 16)
    # and the model zoo's RMSNorm block goes through the placed op
    from mxnet_tpu.gluon.model_zoo.nlp.llama import RMSNorm
    norm = RMSNorm(16)
    norm.initialize()
    assert str(norm(x16).data.dtype) == "float32"


def test_bf16_training_step(amp_bf16):
    net = gluon.nn.Dense(4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    amp.init_trainer(trainer)
    x = nd.random.uniform(shape=(8, 16))
    y = nd.zeros((8,))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        out = net(x)
        loss = loss_fn(out, y)
        with amp.scale_loss(loss, trainer) as scaled:
            scaled.backward()
    w_before = net.weight.data().asnumpy().copy()
    trainer.step(8)
    assert not np.allclose(net.weight.data().asnumpy(), w_before)


def test_fp16_loss_scaler_overflow_skips_step(amp_fp16):
    net = gluon.nn.Dense(2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    amp.init_trainer(trainer)
    scaler = trainer._amp_loss_scaler
    assert scaler.loss_scale > 1.0
    x = nd.random.uniform(shape=(4, 4))
    with autograd.record():
        out = net(x)
        loss = (out * float("inf")).sum()
        loss.backward()
    w_before = net.weight.data().asnumpy().copy()
    s_before = scaler.loss_scale
    trainer.step(4)
    # overflow: weights unchanged, scale halved
    assert np.allclose(net.weight.data().asnumpy(), w_before)
    assert scaler.loss_scale == s_before / 2


def test_loss_scaler_growth():
    s = amp.LossScaler(init_scale=4.0, scale_window=2)
    s.update_scale(False)
    s.update_scale(False)
    assert s.loss_scale == 8.0
    s.update_scale(True)
    assert s.loss_scale == 4.0


def test_convert_hybrid_block(amp_bf16):
    net = gluon.nn.Dense(3)
    net.initialize()
    net(nd.zeros((2, 5)))
    amp.convert_hybrid_block(net)
    assert str(net.weight.data().data.dtype) == "bfloat16"


def test_amp_lists_and_convert_model():
    """amp.list_lp16_ops/list_fp32_ops + convert_model (reference
    contrib/amp Module-API surface)."""
    from mxnet_tpu import amp
    lp16, fp32 = amp.list_lp16_ops(), amp.list_fp32_ops()
    assert "dot" in lp16 or "FullyConnected" in lp16
    assert len(fp32) > 0 and not set(lp16) & set(fp32)
    data = mx.sym.Variable("data")
    out = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    args = {"fc_weight": nd.ones((3, 5)), "fc_bias": nd.zeros((3,))}
    try:
        s2, a2, x2 = amp.convert_model(out, args, {},
                                       cast_optional_params=True)
        assert s2 is out
        assert str(a2["fc_weight"].dtype) == "bfloat16"
    finally:
        amp._deinit_for_tests()


def test_convert_model_guards():
    """Review findings: integer aux params keep their dtype; a second
    convert_model with a DIFFERENT target dtype raises instead of
    silently keeping the old policy; reference kwargs are accepted."""
    from mxnet_tpu import amp
    import numpy as np
    data = mx.sym.Variable("data")
    out = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    args = {"fc_weight": nd.ones((2, 3))}
    aux = {"step": nd.array([4], dtype="int32")}
    try:
        aux["bn_running_mean"] = nd.array([0.1, 0.2])
        _, a2, x2 = amp.convert_model(out, args, aux,
                                      excluded_sym_names=["fc"],
                                      cast_optional_params=True)
        assert str(a2["fc_weight"].dtype) == "bfloat16"
        assert x2["step"].dtype == np.int32          # int aux untouched
        assert x2["bn_running_mean"].dtype == np.float32  # norm stays fp32
        with pytest.raises(mx.MXNetError, match="already initialized"):
            amp.convert_model(out, args, aux, target_dtype="float16")
        with pytest.raises(mx.MXNetError, match="FIRST"):
            amp.convert_model(out, args, aux, fp32_ops=["exp"])
        # aux_params=None normalizes to {} on every path
        _, _, x3 = amp.convert_model(out, args, None)
        assert x3 == {}
    finally:
        amp._deinit_for_tests()
