"""A variable that takes gradients holds a gradient ARRAY only once a
backward has produced one or something has asked for one (ISSUE 39).

``attach_grad`` / ``Parameter.initialize`` used to allocate zeros of the
variable's size that no program read or wrote: a fifth of what a trained
parameter cost under the fused step (float32 weight + Adam's two moments
+ that buffer), and a second copy of a served network.  Two halves:

- residency: after ``initialize()`` and a fused step there is no
  gradient array, and what lives on the device for the network and its
  optimizer is 4 B x parameters x (1 + slots);
- what MXNet shows is what it always showed: ``.grad`` before a backward
  reads zeros (made at that read, and kept: the live view a clip writes
  through), ``zero_grad``, ``grad_req='add'``, ``row_sparse`` gradients,
  ``autograd.grad``, ``gluon.Trainer``, ``amp`` and ``Module``.
"""
import gc

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import _tape, amp, autograd, gluon, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray.sparse import RowSparseNDArray
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
from mxnet_tpu.telemetry import tracing

nd = mx.nd

# shapes no other test's leftovers share, so jax.live_arrays() can be
# counted by shape in a process that ran other files before this one
_DIN, _DHID, _DOUT = 23, 37, 5
_SHAPES = {(_DHID, _DIN), (_DHID,), (_DOUT, _DHID), (_DOUT,)}
_N_PARAMS = _DHID * _DIN + _DHID + _DOUT * _DHID + _DOUT

_OPTIMIZERS = {
    "sgd": ({"learning_rate": 0.1, "momentum": 0.9}, 1),
    "adam": ({"learning_rate": 1e-3}, 2),
}


def _net(seed=3):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(_DHID, activation="relu", in_units=_DIN),
            nn.Dense(_DOUT, in_units=_DHID))
    net.initialize()
    return net


def _batch(seed=0, n=16):
    rs = np.random.RandomState(seed)
    return (nd.array(rs.randn(n, _DIN).astype(np.float32)),
            nd.array(rs.randint(0, _DOUT, (n,)).astype(np.float32)))


def _live_bytes():
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays()
               if tuple(a.shape) in _SHAPES)


def _holds_no_grad_array(net):
    return all(p._data._grad is None
               for p in net.collect_params().values())


# ----------------------------------------------------------------------
# (a) residency under the fused step
# ----------------------------------------------------------------------

@pytest.mark.parametrize("entry", ["step", "step_accum", "step_multi"])
@pytest.mark.parametrize("precision", ["float32", "amp_bfloat16"])
@pytest.mark.parametrize("optimizer", sorted(_OPTIMIZERS))
def test_fused_step_keeps_no_gradient_array(optimizer, precision, entry):
    opt_args, slots = _OPTIMIZERS[optimizer]
    base = _live_bytes()
    if precision == "amp_bfloat16":
        amp.init(target_dtype="bfloat16")
    try:
        net = _net()
        assert _holds_no_grad_array(net)         # initialize() made none
        assert _live_bytes() - base == 4 * _N_PARAMS
        tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 optimizer, dict(opt_args))
        x, y = _batch()
        if entry == "step":
            loss = tr.step(x, y)
        elif entry == "step_accum":
            loss = tr.step_accum(x, y, n_micro=2)
        else:
            loss = tr.step_multi([_batch(0), _batch(1)])
        assert np.isfinite(np.asarray(loss.asnumpy())).all()
        del x, y, loss
        assert _holds_no_grad_array(net)
        # float32 weight + the optimizer's slots, and nothing else of a
        # parameter's shape (the step's own gradients died with the step)
        assert _live_bytes() - base == 4 * _N_PARAMS * (1 + slots)
    finally:
        if precision == "amp_bfloat16":
            amp._deinit_for_tests()


def test_memory_gauges_ride_the_first_step_span():
    """train.param_bytes / train.state_bytes / autograd.grad_buffer_bytes:
    set once at the first step, arguments of its ``train.step`` root."""
    was = tracing.enabled()
    tracing.configure(enabled=True)
    tracing.reset()
    try:
        net = _net()
        tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "adam", {"learning_rate": 1e-3})
        x, y = _batch()
        tr.step(x, y)
        tr.step(x, y)
        roots = [r for r in tracing.spans() if r["name"] == "train.step"]
        assert len(roots) == 2
        first, second = roots[0]["args"], roots[1]["args"]
        assert first["param_bytes"] == 4 * _N_PARAMS
        # two moments a parameter and Adam's scalar counters
        assert 8 * _N_PARAMS <= first["state_bytes"] < 8 * _N_PARAMS + 64
        assert first["grad_buffer_bytes"] == 0
        assert "param_bytes" not in second
        assert telemetry.value("train.param_bytes") == 4 * _N_PARAMS
        assert telemetry.value("train.state_bytes") == first["state_bytes"]
        assert telemetry.value("autograd.grad_buffer_bytes") == 0
    finally:
        tracing.reset()
        tracing.configure(enabled=was)


def test_grad_buffer_gauge_counts_what_an_eager_backward_left():
    net = _net()
    x, y = _batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    tr = DataParallelTrainer(net, loss_fn, "sgd", {"learning_rate": 0.1})
    tr.step(x, y)
    assert telemetry.value("autograd.grad_buffer_bytes") == 4 * _N_PARAMS
    net.collect_params().zero_grad()
    assert _holds_no_grad_array(net)


# ----------------------------------------------------------------------
# (b) what the eager surface shows
# ----------------------------------------------------------------------

def _variable(stype, grad_req):
    """A (10, 4) variable and a recorded loss whose gradient is 1 a use
    of a row: dense through ``take``-free arithmetic, row_sparse through
    Embedding's sparse pullback."""
    w = nd.array(np.arange(40, dtype=np.float32).reshape(10, 4))
    w.attach_grad(grad_req, stype="row_sparse" if stype == "row_sparse"
                  else None)
    ids = nd.array(np.array([1, 3, 3], np.float32))

    def loss_of():
        with autograd.record():
            out = nd.Embedding(ids, w, input_dim=10, output_dim=4,
                               sparse_grad=(stype == "row_sparse"))
            return out.sum()
    expect = np.zeros((10, 4), np.float32)
    expect[1], expect[3] = 1, 2
    return w, loss_of, expect


def _dense(g):
    return g.tostype("default").asnumpy() \
        if isinstance(g, RowSparseNDArray) else g.asnumpy()


_STYPES = ["dense", "row_sparse"]
_REQS = ["write", "add"]


@pytest.mark.parametrize("grad_req", _REQS)
@pytest.mark.parametrize("stype", _STYPES)
def test_grad_before_a_backward(stype, grad_req):
    w, _, _ = _variable(stype, grad_req)
    assert w._grad is None                     # attach_grad made no array
    g = w.grad
    if stype == "row_sparse":
        # O(nnz) contract: never a dense zero buffer, as before
        assert g is None and w._grad is None
        return
    assert g.shape == w.shape and g.dtype == w.dtype
    assert g.context == w.context
    assert (g.asnumpy() == 0).all()
    # made at the first read and kept: a second read sees the same array
    assert w.grad.data is g.data
    # the wrapper is the live view an in-place clip writes through
    g[:] = 3.0
    assert (w.grad.asnumpy() == 3.0).all()
    g *= 0.5
    assert (w.grad.asnumpy() == 1.5).all()


@pytest.mark.parametrize("grad_req", _REQS)
@pytest.mark.parametrize("stype", _STYPES)
def test_two_backwards_write_or_accumulate(stype, grad_req):
    w, loss_of, expect = _variable(stype, grad_req)
    loss_of().backward()
    g = w.grad
    assert isinstance(g, RowSparseNDArray) == (stype == "row_sparse")
    np.testing.assert_array_equal(_dense(g), expect)
    loss_of().backward()
    if stype == "row_sparse":
        assert w.grad._dense_cache is None     # still never densified
    times = 2 if grad_req == "add" else 1
    np.testing.assert_array_equal(_dense(w.grad), times * expect)


@pytest.mark.parametrize("grad_req", _REQS)
@pytest.mark.parametrize("stype", _STYPES)
def test_zero_grad_drops_to_the_empty_state(stype, grad_req):
    p = gluon.Parameter("w", grad_req=grad_req, shape=(10, 4),
                        grad_stype="row_sparse" if stype == "row_sparse"
                        else "default")
    p.initialize(mx.init.One())
    assert p._data._grad is None
    ids = nd.array(np.array([1, 3, 3], np.float32))

    def backward():
        with autograd.record():
            out = nd.Embedding(ids, p.data(), input_dim=10, output_dim=4,
                               sparse_grad=(stype == "row_sparse"))
            loss = out.sum()
        loss.backward()
    backward()
    assert _tape.grad_bytes(p._data) > 0
    p.zero_grad()
    assert p._data._grad is None and _tape.grad_bytes(p._data) == 0
    if stype == "dense":
        assert (p.grad().asnumpy() == 0).all()
        assert (p.list_grad()[0].asnumpy() == 0).all()
    else:
        assert p.grad() is None
    # and accumulation starts over from it
    backward()
    expect = np.zeros((10, 4), np.float32)
    expect[1], expect[3] = 1, 2
    np.testing.assert_array_equal(_dense(p.grad()), expect)


def test_clip_global_norm_writes_through_grads_read_after_backward():
    net = _net()
    x, y = _batch()
    with autograd.record():
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(net(x), y)
    loss.backward()
    params = list(net.collect_params().values())
    before = [p.grad().asnumpy() for p in params]
    norm = float(np.sqrt(sum((g.astype(np.float64) ** 2).sum()
                             for g in before)))
    gluon.utils.clip_global_norm([p.grad() for p in params], norm / 4)
    for p, b in zip(params, before):
        np.testing.assert_allclose(p.grad().asnumpy(), b / 4, rtol=1e-5)


@pytest.mark.parametrize("grad_req", _REQS)
def test_autograd_grad_leaves_the_variable_as_it_found_it(grad_req):
    x = nd.array(np.array([1.0, 2.0, 3.0], np.float32))
    x.attach_grad(grad_req)
    with autograd.record():
        y = (x * x).sum()
    g = autograd.grad(y, x, retain_graph=True)
    np.testing.assert_array_equal(g.asnumpy(), [2.0, 4.0, 6.0])
    assert x._grad is None and x._grad_req == grad_req
    assert (x.grad.asnumpy() == 0).all()
    # a variable outside the graph is still refused
    z = nd.array(np.ones(3, np.float32))
    z.attach_grad()
    with pytest.raises(MXNetError, match="does not participate"):
        autograd.grad(y, z)


def test_null_grad_req_still_raises_and_holds_nothing():
    p = gluon.Parameter("frozen", grad_req="null", shape=(3, 3))
    p.initialize()
    with pytest.raises(MXNetError, match="grad_req='null'"):
        p.grad()
    assert p._data.grad is None and p._data._grad is None
    p.grad_req = "write"
    assert p._data._grad is None and (p.grad().asnumpy() == 0).all()
    p.grad_req = "null"
    assert p._data._grad is None


def test_cast_and_reset_ctx_keep_taking_gradients_without_an_array():
    p = gluon.Parameter("w", shape=(4, 4))
    p.initialize()
    p.grad()                                    # materialise, then move
    p.cast("bfloat16")
    assert p._data._grad_req == "write" and p._data._grad is None
    assert str(p.grad().data.dtype) == "bfloat16"
    p.reset_ctx(mx.cpu())
    assert p._data._grad_req == "write" and p._data._grad is None


def _train_eager(grad_req, optimizer, opt_args, read_grads_first, steps=3):
    from mxnet_tpu.gluon import block as _blk
    _blk._GLOBAL_COUNTERS.clear()
    net = _net(seed=7)
    net.collect_params().setattr("grad_req", grad_req)
    if read_grads_first:
        # the parent's state after initialize(): zeros in every _grad
        for p in net.collect_params().values():
            assert (p.grad().asnumpy() == 0).all()
    trainer = gluon.Trainer(net.collect_params(), optimizer, dict(opt_args))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for i in range(steps):
        x, y = _batch(seed=i)
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(16)
        if grad_req == "add":
            net.collect_params().zero_grad()
    return {k: p.data().asnumpy() for k, p in net.collect_params().items()}


@pytest.mark.parametrize("optimizer", sorted(_OPTIMIZERS))
@pytest.mark.parametrize("grad_req", _REQS)
def test_trainer_step_bitwise_what_preallocated_buffers_gave(grad_req,
                                                             optimizer):
    """A seeded two-layer net trained from the empty state against the
    same net whose every gradient was first read into being (zeros in
    every ``_grad``: what ``initialize()`` used to leave)."""
    opt_args, _ = _OPTIMIZERS[optimizer]
    lazy = _train_eager(grad_req, optimizer, opt_args, False)
    eager = _train_eager(grad_req, optimizer, opt_args, True)
    assert set(lazy) == set(eager)
    for k in lazy:
        assert np.array_equal(lazy[k], eager[k]), k


class _OneBranch(gluon.Block):
    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.used = nn.Dense(4, in_units=6)
            self.unused = nn.Dense(4, in_units=6)

    def forward(self, x):
        return self.used(x)


@pytest.mark.parametrize("fused", ["1", "0"])
def test_parameter_no_backward_reached_is_stale(fused, monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_STEP", fused)
    net = _OneBranch()
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(np.ones((2, 6), np.float32))
    w_unused = net.unused.weight.data().asnumpy()
    w_used = net.used.weight.data().asnumpy()
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    with pytest.raises(MXNetError, match="dense1.*has not been computed"):
        trainer.step(2)
    trainer.step(2, ignore_stale_grad=True)
    assert not np.array_equal(net.used.weight.data().asnumpy(), w_used)
    np.testing.assert_array_equal(net.unused.weight.data().asnumpy(),
                                  w_unused)
    # the check itself made no array for the parameter nothing reached
    assert net.unused.weight._data._grad is None
    assert net.unused.bias._data._grad is None


class _KeysKV:
    """pushpull spy: identity reduce, records the keys of each round."""

    def __init__(self):
        self.keys_seen = []

    def pushpull(self, keys, grads, out=None, priority=0):
        self.keys_seen.append(list(keys))


def test_all_reduce_sends_zeros_for_a_parameter_no_backward_reached():
    """Every worker sends the same keys whatever its backward reached:
    a dense parameter without a gradient goes out as zeros (made then),
    as it did from its preallocated buffer."""
    from mxnet_tpu.parallel import all_reduce_gradients
    net = _OneBranch()
    net.initialize()
    params = list(net.collect_params().values())
    with autograd.record():
        loss = net(nd.array(np.ones((2, 6), np.float32))).sum()
    loss.backward()
    kv = _KeysKV()
    all_reduce_gradients(params, kvstore=kv)
    assert kv.keys_seen == [list(range(len(params)))]
    assert (net.unused.weight.grad().asnumpy() == 0).all()
    assert all(p._data._grad_reduced for p in params)


def test_step_after_zero_grad_applies_zeros_as_it_always_did():
    """backward, zero_grad, step: the gradient was computed, then
    cleared — momentum and weight decay still move the weight."""
    net = _net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "wd": 0.1})
    x, y = _batch()
    with autograd.record():
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(net(x), y)
    loss.backward()
    before = {k: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    net.collect_params().zero_grad()
    trainer.step(16)
    for k, p in net.collect_params().items():
        np.testing.assert_allclose(p.data().asnumpy(),
                                   before[k] * (1 - 0.1 * 0.1), rtol=1e-6)


def test_amp_unscale_and_overflow_check_skip_what_has_no_array():
    amp.init(target_dtype="float16")
    try:
        net = _OneBranch()
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        amp.init_trainer(trainer)
        scaler = trainer._amp_loss_scaler
        scaler.loss_scale = 128.0      # 2**16 overflows float16 at once
        x = nd.array(np.ones((2, 6), np.float32))
        with autograd.record():
            loss = net(x).sum()
            with amp.scale_loss(loss, trainer) as scaled:
                scaled.backward()
        assert not scaler.has_overflow(trainer._params)
        g = net.used.bias.grad().asnumpy()
        amp.unscale(trainer)
        np.testing.assert_allclose(net.used.bias.grad().asnumpy(),
                                   g / scaler.loss_scale)
        assert net.unused.weight._data._grad is None
        # an overflow skips the step and marks every gradient stale
        net.used.bias.grad()[:] = np.inf
        assert scaler.has_overflow(trainer._params)
        w = net.used.weight.data().asnumpy()
        trainer.step(2, ignore_stale_grad=True)
        np.testing.assert_array_equal(net.used.weight.data().asnumpy(), w)
        assert not net.used.weight._data._grad_fresh
    finally:
        amp._deinit_for_tests()


def test_module_bind_backward_update():
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(fc2, name="softmax"),
                        data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    rs = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[nd.array(rs.randn(4, 6).astype(np.float32))],
        label=[nd.array(np.array([0, 1, 2, 1], np.float32))])
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    mod.forward(batch, is_train=True)
    # binding for training marked the arguments and made no array
    assert all(mod._exec.arg_dict[k]._grad is None for k in before)
    mod.backward()
    assert set(mod._exec.grad_dict) >= set(before)
    assert np.abs(mod._exec.grad_dict["fc2_bias"].asnumpy()).sum() > 0
    mod.update()
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert set(before) == {"fc1_weight", "fc1_bias",
                           "fc2_weight", "fc2_bias"}
    for k in before:
        assert not np.array_equal(before[k], after[k]), k
