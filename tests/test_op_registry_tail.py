"""Round-4 op-registry tail (VERDICT r3 #5): bitwise/int ops, numpy-parity
math, the random_pdf_* family, the optimizer update-op tail, multi-tensor
utility ops, and legacy structured ops. Reference: src/operator/tensor/
elemwise_binary_op_logic.cc, random/pdf_op.cc, optimizer_op.cc,
contrib/multi_*.cc, spatial_transformer.cc."""
import numpy as np
import pytest
from scipy import stats

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import check_numeric_gradient


def test_bitwise_and_shift_ops():
    a = nd.array([5, 12, 7], dtype="int32")
    b = nd.array([3, 10, 2], dtype="int32")
    np.testing.assert_array_equal(nd.bitwise_and(a, b).asnumpy(), [1, 8, 2])
    np.testing.assert_array_equal(nd.bitwise_or(a, b).asnumpy(), [7, 14, 7])
    np.testing.assert_array_equal(nd.bitwise_xor(a, b).asnumpy(), [6, 6, 5])
    np.testing.assert_array_equal(nd.bitwise_not(a).asnumpy(), [-6, -13, -8])
    np.testing.assert_array_equal(nd.invert(a).asnumpy(), [-6, -13, -8])
    np.testing.assert_array_equal(nd.left_shift(a, b).asnumpy(),
                                  [40, 12288, 28])
    np.testing.assert_array_equal(
        nd.right_shift(nd.array([40, 12288], dtype="int32"),
                       nd.array([3, 10], dtype="int32")).asnumpy(), [5, 12])
    np.testing.assert_array_equal(nd.lcm(a, b).asnumpy(), [15, 60, 14])
    np.testing.assert_array_equal(nd.gcd(a, b).asnumpy(), [1, 2, 1])


def test_numpy_parity_math_ops():
    x = nd.array([np.inf, -np.inf, np.nan, 1.0])
    np.testing.assert_array_equal(nd.isposinf(x).asnumpy(), [1, 0, 0, 0])
    np.testing.assert_array_equal(nd.isneginf(x).asnumpy(), [0, 1, 0, 0])
    np.testing.assert_allclose(
        nd.nan_to_num(x, nan=9.0, posinf=5.0, neginf=-5.0).asnumpy(),
        [5.0, -5.0, 9.0, 1.0])
    e = nd.ediff1d(nd.array([1.0, 3.0, 6.0]), to_begin=0.0, to_end=[9.0])
    np.testing.assert_allclose(e.asnumpy(), [0.0, 2.0, 3.0, 9.0])
    y = nd.interp(nd.array([0.5, 1.5]), nd.array([0.0, 1.0, 2.0]),
                  nd.array([0.0, 10.0, 20.0]))
    np.testing.assert_allclose(y.asnumpy(), [5.0, 15.0])
    p = nd.polyval(nd.array([1.0, 0.0, -2.0]), nd.array([3.0]))
    np.testing.assert_allclose(p.asnumpy(), [7.0])    # x^2 - 2 at 3
    q, r = nd.divmod(nd.array([7.0, -7.0]), nd.array([3.0, 3.0]))
    np.testing.assert_allclose(q.asnumpy(), [2.0, -3.0])
    np.testing.assert_allclose(r.asnumpy(), [1.0, 2.0])
    bins = nd.array([0.0, 1.0, 2.0])
    np.testing.assert_array_equal(
        nd.digitize(nd.array([-0.5, 0.5, 1.5, 2.5]), bins).asnumpy(),
        [0, 1, 2, 3])
    np.testing.assert_array_equal(
        nd.searchsorted(bins, nd.array([1.5])).asnumpy(), [2])
    with pytest.raises(mx.MXNetError):
        nd.searchsorted(bins, nd.array([1.5]), sorter=[0, 1, 2])


def test_random_pdf_family_vs_scipy():
    s = nd.array([[0.5, 1.5], [2.0, 3.0]])
    got = nd.random_pdf_normal(s, nd.array([0.0, 1.0]),
                               nd.array([1.0, 2.0])).asnumpy()
    want = np.stack([stats.norm.pdf([0.5, 1.5], 0, 1),
                     stats.norm.pdf([2, 3], 1, 2)])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # gamma in (shape, rate) parametrization per the reference pdf op
    got = nd.random_pdf_gamma(s, nd.array([2.0, 3.0]),
                              nd.array([1.0, 0.5])).asnumpy()
    want = np.stack([stats.gamma.pdf([0.5, 1.5], 2, scale=1.0),
                     stats.gamma.pdf([2, 3], 3, scale=2.0)])
    np.testing.assert_allclose(got, want, rtol=1e-4)
    got = nd.random_pdf_exponential(s, nd.array([1.0, 2.0])).asnumpy()
    want = np.stack([stats.expon.pdf([0.5, 1.5], scale=1.0),
                     stats.expon.pdf([2, 3], scale=0.5)])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    got = nd.random_pdf_uniform(s, nd.array([0.0, 0.0]),
                                nd.array([1.0, 4.0])).asnumpy()
    np.testing.assert_allclose(got, [[1.0, 0.0], [0.25, 0.25]], atol=1e-6)
    ks = nd.array([[0.0, 1, 2, 3]])
    got = nd.random_pdf_poisson(ks, nd.array([2.0]), is_log=True).asnumpy()
    np.testing.assert_allclose(got[0], stats.poisson.logpmf([0, 1, 2, 3], 2),
                               rtol=1e-4)
    got = nd.random_pdf_negative_binomial(
        nd.array([[0.0, 1, 2]]), nd.array([3.0]), nd.array([0.4])).asnumpy()
    np.testing.assert_allclose(got[0], stats.nbinom.pmf([0, 1, 2], 3, 0.4),
                               rtol=1e-4)
    # generalized nb reduces to nbinom with r=1/alpha, p=r/(r+mu)
    mu, alpha = 2.0, 0.5
    r = 1 / alpha
    got = nd.random_pdf_generalized_negative_binomial(
        nd.array([[0.0, 1, 2]]), nd.array([mu]), nd.array([alpha])).asnumpy()
    np.testing.assert_allclose(
        got[0], stats.nbinom.pmf([0, 1, 2], r, r / (r + mu)), rtol=1e-4)
    ds = nd.array([[[0.2, 0.3, 0.5], [0.1, 0.1, 0.8]]])
    got = nd.random_pdf_dirichlet(ds, nd.array([[1.0, 2.0, 3.0]])).asnumpy()
    want = [[stats.dirichlet.pdf([0.2, 0.3, 0.5], [1, 2, 3]),
             stats.dirichlet.pdf([0.1, 0.1, 0.8], [1, 2, 3])]]
    np.testing.assert_allclose(got, want, rtol=1e-4)
    # pdf ops are differentiable through the tape
    mu_nd = nd.array([0.0, 1.0])
    check_numeric_gradient(
        lambda m: nd.random_pdf_normal(s, m, nd.array([1.0, 2.0])).sum(),
        [mu_nd])


def _sgdish_states(*shapes):
    return [nd.zeros(s) for s in shapes]


def test_optimizer_update_op_tail():
    # signsgd / signum
    w = nd.array([1.0, -2.0])
    nd.signsgd_update(w, nd.array([0.3, -0.4]), lr=0.1)
    np.testing.assert_allclose(w.asnumpy(), [0.9, -1.9], rtol=1e-6)
    w, m = nd.array([1.0, -2.0]), nd.zeros((2,))
    nd.signum_update(w, nd.array([0.3, -0.4]), m, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(w.asnumpy(), [0.9, -1.9], rtol=1e-6)
    np.testing.assert_allclose(m.asnumpy(), [-0.03, 0.04], rtol=1e-5)

    # rmsprop: hand-check one step
    w, n = nd.array([1.0]), nd.zeros((1,))
    nd.rmsprop_update(w, nd.array([2.0]), n, lr=0.1, gamma1=0.9,
                      epsilon=1e-8)
    n_want = 0.1 * 4.0
    np.testing.assert_allclose(n.asnumpy(), [n_want], rtol=1e-6)
    np.testing.assert_allclose(
        w.asnumpy(), [1.0 - 0.1 * 2.0 / (np.sqrt(n_want) + 1e-8)],
        rtol=1e-6)

    # rmspropalex: states all mutate, weight moves by delta
    w, n, g, d = (nd.array([1.0]), nd.zeros((1,)), nd.zeros((1,)),
                  nd.zeros((1,)))
    nd.rmspropalex_update(w, nd.array([2.0]), n, g, d, lr=0.1)
    assert abs(float(w.asnumpy()) - 1.0) > 1e-4
    assert float(n.asnumpy()) > 0 and abs(float(g.asnumpy())) > 0

    # ftrl matches the Ftrl optimizer class one step
    w_op, z, n = nd.array([0.5]), nd.zeros((1,)), nd.zeros((1,))
    nd.ftrl_update(w_op, nd.array([0.2]), z, n, lr=0.1, lamda1=0.01,
                   beta=1.0)
    opt = mx.optimizer.Ftrl(lamda1=0.01, learning_rate=0.1, beta=1.0, wd=0.0)
    w_cls = nd.array([0.5])
    state = opt.create_state(0, w_cls)
    opt.update(0, w_cls, nd.array([0.2]), state)
    np.testing.assert_allclose(w_op.asnumpy(), w_cls.asnumpy(), rtol=1e-6)

    # adagrad / nag
    w, h = nd.array([1.0]), nd.zeros((1,))
    nd.adagrad_update(w, nd.array([3.0]), h, lr=0.1, epsilon=1e-7)
    np.testing.assert_allclose(h.asnumpy(), [9.0], rtol=1e-6)
    w, m = nd.array([1.0]), nd.zeros((1,))
    nd.nag_mom_update(w, nd.array([1.0]), m, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(m.asnumpy(), [1.0], rtol=1e-6)
    np.testing.assert_allclose(w.asnumpy(), [1.0 - 0.1 * 1.9], rtol=1e-6)

    # ftml / adamax / nadam smoke + state mutation
    w, d, v, z = nd.array([1.0]), *_sgdish_states((1,), (1,), (1,))
    nd.ftml_update(w, nd.array([0.5]), d, v, z, lr=0.1, t=1)
    assert float(d.asnumpy()) != 0 and float(v.asnumpy()) != 0
    w, m, u = nd.array([1.0]), *_sgdish_states((1,), (1,))
    nd.adamax_update(w, nd.array([0.5]), m, u, lr=0.1)
    np.testing.assert_allclose(u.asnumpy(), [0.5], rtol=1e-6)
    w, m, v = nd.array([1.0]), *_sgdish_states((1,), (1,))
    nd.nadam_update(w, nd.array([0.5]), m, v, lr=0.002, t=1)
    assert float(w.asnumpy()) < 1.0


def test_mp_update_ops_keep_fp32_master():
    w16 = nd.array(np.array([1.0, 2.0]), dtype="float16")
    w32 = nd.array([1.0, 2.0])
    nd.mp_sgd_update(w16, nd.array(np.array([1.0, 1.0]), dtype="float16"),
                     w32, lr=0.25)
    assert w16.dtype == np.float16 and w32.dtype == np.float32
    np.testing.assert_allclose(w32.asnumpy(), [0.75, 1.75], rtol=1e-6)
    np.testing.assert_allclose(w16.asnumpy(), [0.75, 1.75], rtol=1e-3)
    w16, m, w32 = (nd.array(np.array([1.0]), dtype="float16"),
                   nd.zeros((1,)), nd.array([1.0]))
    nd.mp_sgd_mom_update(w16, nd.array(np.array([1.0]), dtype="float16"),
                         m, w32, lr=0.5, momentum=0.9)
    np.testing.assert_allclose(w32.asnumpy(), [0.5], rtol=1e-6)
    w16, m, w32 = (nd.array(np.array([1.0]), dtype="float16"),
                   nd.zeros((1,)), nd.array([1.0]))
    nd.mp_nag_mom_update(w16, nd.array(np.array([1.0]), dtype="float16"),
                         m, w32, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(w32.asnumpy(), [1.0 - 0.1 * 1.9], rtol=1e-5)


def test_lamb_phase_ops_match_lamb_optimizer():
    rng = np.random.RandomState(3)
    w0 = rng.randn(6).astype(np.float32)
    g0 = rng.randn(6).astype(np.float32)

    w_op = nd.array(w0)
    mean, var = nd.zeros((6,)), nd.zeros((6,))
    gp = nd.lamb_update_phase1(w_op, nd.array(g0), mean, var, t=1,
                               beta1=0.9, beta2=0.999, epsilon=1e-6,
                               wd=0.01)
    r1, r2 = nd.norm(w_op), nd.norm(gp)
    nd.lamb_update_phase2(w_op, gp, r1, r2, lr=0.01)

    opt = mx.optimizer.LAMB(learning_rate=0.01, beta1=0.9, beta2=0.999,
                            epsilon=1e-6, wd=0.01)
    w_cls = nd.array(w0)
    state = opt.create_state(0, w_cls)
    opt.update(0, w_cls, nd.array(g0), state)
    np.testing.assert_allclose(w_op.asnumpy(), w_cls.asnumpy(), rtol=1e-4,
                               atol=1e-6)

    # mp variant tracks the fp32 master
    w16 = nd.array(w0, dtype="float16")
    w32 = nd.array(w0)
    mean, var = nd.zeros((6,)), nd.zeros((6,))
    gp = nd.mp_lamb_update_phase1(w16, nd.array(g0, dtype="float16"),
                                  mean, var, w32, t=1, wd=0.01)
    r1, r2 = nd.norm(w32), nd.norm(gp)
    nd.mp_lamb_update_phase2(w16, gp, r1, r2, w32, lr=0.01)
    np.testing.assert_allclose(w32.asnumpy(), w_cls.asnumpy(), rtol=1e-3,
                               atol=1e-4)


def test_preloaded_multi_sgd_family():
    w0, g0 = nd.array([1.0]), nd.array([1.0])
    w1, g1 = nd.array([2.0]), nd.array([1.0])
    lrs, wds = nd.array([0.1, 0.5]), nd.array([0.0, 0.0])
    outs = nd.preloaded_multi_sgd_update(w0, g0, w1, g1, lrs, wds,
                                         num_weights=2)
    np.testing.assert_allclose(w0.asnumpy(), [0.9], rtol=1e-6)
    np.testing.assert_allclose(w1.asnumpy(), [1.5], rtol=1e-6)
    assert outs[0] is w0 and outs[1] is w1

    w0, g0, m0 = nd.array([1.0]), nd.array([1.0]), nd.zeros((1,))
    w1, g1, m1 = nd.array([2.0]), nd.array([1.0]), nd.zeros((1,))
    nd.preloaded_multi_sgd_mom_update(w0, g0, m0, w1, g1, m1, lrs, wds,
                                      momentum=0.9, num_weights=2)
    np.testing.assert_allclose(m0.asnumpy(), [-0.1], rtol=1e-6)

    w16 = nd.array(np.array([1.0]), dtype="float16")
    w32 = nd.array([1.0])
    nd.preloaded_multi_mp_sgd_update(
        w16, nd.array(np.array([1.0]), dtype="float16"), w32,
        nd.array([0.25]), nd.array([0.0]), num_weights=1)
    np.testing.assert_allclose(w32.asnumpy(), [0.75], rtol=1e-6)

    with pytest.raises(mx.MXNetError):
        nd.preloaded_multi_sgd_update(w0, g0, lrs, wds, num_weights=2)


def test_multi_tensor_utility_ops():
    assert nd.all_finite(nd.array([1.0, 2.0])).asnumpy()[0] == 1.0
    assert nd.all_finite(nd.array([1.0, np.inf])).asnumpy()[0] == 0.0
    ok = nd.multi_all_finite(nd.array([1.0]), nd.array([2.0]),
                             num_arrays=2)
    assert ok.asnumpy()[0] == 1.0
    bad = nd.multi_all_finite(nd.array([1.0]), nd.array([np.nan]),
                              num_arrays=2)
    assert bad.asnumpy()[0] == 0.0
    s = nd.multi_sum_sq(nd.array([1.0, 2.0]), nd.array([3.0]),
                        num_arrays=2)
    np.testing.assert_allclose(s.asnumpy(), [5.0, 9.0], rtol=1e-6)
    lrs = nd.multi_lars(nd.array([0.1, 0.1]), nd.array([4.0, 0.0]),
                        nd.array([1.0, 1.0]), nd.array([0.0, 0.0]),
                        eta=1.0, eps=0.0)
    np.testing.assert_allclose(lrs.asnumpy(), [0.2, 0.1], rtol=1e-6)

    a = nd.amp_cast(nd.array([1.5]), dtype="float16")
    assert a.dtype == np.float16
    o1, o2 = nd.amp_multicast(nd.array(np.array([1.0]), dtype="float16"),
                              nd.array([2.0]), num_outputs=2)
    assert o1.dtype == np.float32 and o2.dtype == np.float32
    n1, n2 = nd.amp_multicast(nd.array(np.array([1.0]), dtype="float16"),
                              nd.array([2.0]), num_outputs=2,
                              cast_narrow=True)
    assert n1.dtype == np.float16 and n2.dtype == np.float16

    x = nd.array([[1.0, 2.0], [3.0, 4.0]])
    mu, var = nd.moments(x, axes=0)
    np.testing.assert_allclose(mu.asnumpy(), [2.0, 3.0])
    np.testing.assert_allclose(var.asnumpy(), [1.0, 1.0])
    check_numeric_gradient(lambda d: nd.moments(d, axes=0)[1].sum(),
                           [nd.array([[1.0, 2.0], [3.0, 5.0]])])


def test_legacy_structured_ops():
    x = nd.array([[1.0, 2.0], [3.0, 4.0]])
    np.testing.assert_allclose(
        nd.choose_element_0index(x, nd.array([1, 0])).asnumpy(), [2.0, 3.0])
    filled = nd.fill_element_0index(x, nd.array([9.0, 8.0]),
                                    nd.array([0, 1]))
    np.testing.assert_allclose(filled.asnumpy(), [[9.0, 2.0], [3.0, 8.0]])

    # identity affine transform reproduces the input
    img = nd.array(np.random.RandomState(0)
                   .rand(1, 1, 5, 5).astype(np.float32))
    loc = nd.array([[1.0, 0.0, 0.0, 0.0, 1.0, 0.0]])
    out = nd.SpatialTransformer(img, loc, target_shape=(5, 5))
    np.testing.assert_allclose(out.asnumpy(), img.asnumpy(), atol=1e-5)
    with pytest.raises(mx.MXNetError):
        nd.SpatialTransformer(img, loc, target_shape=(5, 5),
                              transform_type="warp")

    # KL sparse reg: identity forward, penalty-shifted backward
    d = nd.array([[0.2, 0.8], [0.4, 0.6]])
    d.attach_grad()
    with autograd.record():
        y = nd.IdentityAttachKLSparseReg(d, sparseness_target=0.1,
                                         penalty=0.001).sum()
    y.backward()
    rho = np.clip(np.mean([[0.2, 0.8], [0.4, 0.6]], axis=0), 1e-6, 1 - 1e-6)
    kl = 0.001 * (-0.1 / rho + 0.9 / (1 - rho)) / 2
    np.testing.assert_allclose(d.grad.asnumpy(), 1.0 + np.tile(kl, (2, 1)),
                               rtol=1e-5)


def test_int_ops_accept_python_scalar_rhs():
    """Review finding: scalar rhs must not be coerced to float32."""
    a = nd.array([5, 12, 7], dtype="int32")
    np.testing.assert_array_equal(nd.left_shift(a, 2).asnumpy(),
                                  [20, 48, 28])
    np.testing.assert_array_equal(nd.right_shift(a, 1).asnumpy(), [2, 6, 3])
    np.testing.assert_array_equal(nd.bitwise_and(a, 3).asnumpy(), [1, 0, 3])
    np.testing.assert_array_equal(nd.bitwise_or(a, 8).asnumpy(),
                                  [13, 12, 15])
    np.testing.assert_array_equal(nd.gcd(a, 4).asnumpy(), [1, 4, 1])


def test_nadam_update_cumulative_schedule():
    """Review finding: bias correction must use the cumulative
    m_schedule product, not just the current step's mu."""
    b1, b2, lr, eps, sd = 0.9, 0.999, 0.002, 1e-8, 0.004
    w = nd.array([1.0])
    m, v = nd.zeros((1,)), nd.zeros((1,))
    w_ref, m_ref, v_ref, msched = 1.0, 0.0, 0.0, 1.0
    rng = np.random.RandomState(0)
    for t in range(1, 8):
        g = float(rng.randn())
        mu_t = b1 * (1 - 0.5 * 0.96 ** (t * sd))
        mu_tp1 = b1 * (1 - 0.5 * 0.96 ** ((t + 1) * sd))
        msched = msched * mu_t
        m_ref = b1 * m_ref + (1 - b1) * g
        v_ref = b2 * v_ref + (1 - b2) * g * g
        g_bar = ((1 - mu_t) * g / (1 - msched)
                 + mu_tp1 * m_ref / (1 - msched * mu_tp1))
        w_ref -= lr * g_bar / (np.sqrt(v_ref / (1 - b2 ** t)) + eps)
        nd.nadam_update(w, nd.array([g]), m, v, lr=lr, t=t)
        np.testing.assert_allclose(w.asnumpy(), [w_ref], rtol=1e-6)


@pytest.mark.slow   # ~28 s, over the 20 s per-test budget;
# RNN-vs-torch parity still gates via
# test_torch_rnn_consistency.py
def test_fused_rnn_op_matches_gluon_layer():
    """nd.RNN (reference src/operator/rnn.cc packed-parameter fused op)
    must reproduce the gluon fused layer bit-for-bit when fed the same
    weights flattened into the reference layout."""
    from mxnet_tpu import gluon

    rng = np.random.RandomState(5)
    T, B, I, H, L = 6, 3, 4, 5, 2
    for mode, cls, bidir in (("lstm", gluon.rnn.LSTM, False),
                             ("gru", gluon.rnn.GRU, True),
                             ("rnn_relu", gluon.rnn.RNN, False)):
        dirs = 2 if bidir else 1
        layer = cls(H, num_layers=L, layout="TNC", bidirectional=bidir) \
            if mode != "rnn_relu" else cls(H, num_layers=L, layout="TNC")
        layer.initialize()
        x = nd.array(rng.randn(T, B, I).astype(np.float32))
        states = layer.begin_state(batch_size=B)
        out_ref = layer(x, states)
        out_ref, states_ref = out_ref if isinstance(out_ref, tuple) \
            else (out_ref, None)

        # flatten weights into the reference packed layout: all weights
        # (layer-major, dir-major: i2h, h2h), then all biases
        flat = []
        dirl = ["l", "r"] if dirs == 2 else ["l"]
        for part in ("weight", "bias"):
            for li in range(L):
                for d in dirl:
                    for kind in ("i2h", "h2h"):
                        arr = getattr(layer,
                                      f"{d}{li}_{kind}_{part}").data()
                        flat.append(arr.asnumpy().ravel())
        params = nd.array(np.concatenate(flat))

        kw = {}
        if mode == "lstm":
            kw["state_cell"] = states[1]
        res = nd.RNN(x, params, states[0], num_layers=L, mode=mode,
                     bidirectional=bidir, state_outputs=True,
                     state_size=H, **kw)
        out = res[0]
        np.testing.assert_allclose(out.asnumpy(), out_ref.asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=mode)
        # final hidden states also agree
        np.testing.assert_allclose(res[1].asnumpy(),
                                   states_ref[0].asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=mode)
    # grads flow through the packed vector (inputs fixed OUTSIDE the
    # closure: the numeric check re-evaluates it many times)
    xg = nd.array(rng.randn(3, 2, 4).astype(np.float32))
    h0, c0 = nd.zeros((1, 2, 3)), nd.zeros((1, 2, 3))
    check_numeric_gradient(
        lambda pp: nd.RNN(xg, pp, h0, state_cell=c0, state_size=3,
                          mode="lstm").sum(),
        [nd.array(rng.randn(4 * 3 * 4 + 4 * 3 * 3 + 2 * 4 * 3)
                  .astype(np.float32) * 0.1)])
