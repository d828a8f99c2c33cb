"""Composable 3D parallelism (ISSUE 11): one named-axis MeshConfig
(dp x tp x pp) drives DataParallelTrainer end to end on the virtual
8-device CPU mesh.

Acceptance gates:
- ``MXTPU_MESH`` unset is BITWISE the flat dp-only trainer (params +
  optimizer state; plain/accum/multi-step);
- ``2x2x2`` and ``4x1x2`` meshes match the pure-dp reference to float
  eps across plain/accum/multi-step;
- a checkpoint written at ``2x2x2`` reshards onto ``dp8`` bitwise (and
  back);
- the pp executor runs the canonical 1F1B schedule (order-regression
  test) and fires the PR 5 grad-ready hooks inside the bubble;
- a tp-sharded Dense trains to the replicated reference.
"""
import os

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import block as gblock
from mxnet_tpu.parallel import (MeshConfig, DataParallelTrainer,
                                make_mesh, one_f_one_b_schedule,
                                bubble_fraction, split_into_stages)

nd = mx.nd

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 virtual devices")


def _build_mlp(layers=(16, 16, 16, 8), in_dim=12, seed=1):
    """Fresh identically-initialized MLP.  Counters cleared per build so
    sorted param names (and therefore the seeded init order) are stable
    across builds inside ONE test (the PR 5 digit-boundary lesson)."""
    gblock._GLOBAL_COUNTERS.clear()
    net = gluon.nn.HybridSequential()
    for i, u in enumerate(layers):
        net.add(gluon.nn.Dense(u, activation="relu"
                               if i < len(layers) - 1 else None))
    net.initialize()
    net(nd.zeros((2, in_dim)))
    rs = np.random.RandomState(seed)
    for _, p in sorted(net.collect_params().items()):
        p.set_data(nd.array(rs.randn(*p.shape).astype(np.float32) * 0.3))
    return net


def _batch(n=16, in_dim=12, classes=8, seed=2):
    rs = np.random.RandomState(seed)
    return (nd.array(rs.randn(n, in_dim).astype(np.float32)),
            nd.array(rs.randint(0, classes, (n,))))


def _params(net):
    return {n: p.data().asnumpy().copy()
            for n, p in net.collect_params().items()}


def _run_mixed_steps(trainer, x, y):
    """The plain/accum/multi sequence every parity test replays."""
    mx.random.seed(7)
    losses = [float(trainer.step(x, y).asnumpy()) for _ in range(2)]
    losses.append(float(trainer.step_accum(x, y, n_micro=2).asnumpy()))
    lm = trainer.step_multi([(x, y), (x, y)])
    losses.extend(float(v) for v in np.asarray(lm.asnumpy()).ravel())
    return losses


# ---------------------------------------------------------------------------
# MeshConfig semantics
# ---------------------------------------------------------------------------

def test_mesh_config_spec_roundtrip():
    c = MeshConfig.from_spec("2x2x2")
    assert (c.dp, c.tp, c.pp) == (2, 2, 2)
    assert c.describe() == "dp2tp2pp2"
    assert MeshConfig.from_spec(c.describe()) == c
    assert MeshConfig.from_spec("dp8").as_dict() == \
        {"dp": 8, "tp": 1, "pp": 1}
    assert MeshConfig.from_spec("4x1x2").describe() == "dp4pp2"
    assert MeshConfig.from_spec("dp-1tp2").resolve(8).dp == 4
    with pytest.raises(mx.MXNetError):
        MeshConfig.from_spec("qq4")
    with pytest.raises(mx.MXNetError):
        MeshConfig.from_spec("dp2dp4")
    with pytest.raises(mx.MXNetError):
        MeshConfig(dp=2, tp=-1)


@needs8
def test_mesh_config_build_and_stage_meshes():
    # unset default == the flat trainer's mesh, axis for axis
    flat = MeshConfig(dp=8).build()
    legacy = make_mesh({"dp": -1})
    assert flat == legacy and flat.axis_names == legacy.axis_names
    # size-1 axes are DISABLED: they never appear in the built mesh
    assert MeshConfig.from_spec("4x1x2").build().axis_names == \
        ("pp", "dp")
    m3 = MeshConfig.from_spec("2x2x2")
    full = m3.build()
    assert full.axis_names == ("pp", "dp", "tp")
    s0, s1 = m3.stage_mesh(0), m3.stage_mesh(1)
    assert s0.axis_names == ("dp", "tp") and dict(s0.shape) == \
        {"dp": 2, "tp": 2}
    d0 = {d.id for d in np.asarray(s0.devices).ravel()}
    d1 = {d.id for d in np.asarray(s1.devices).ravel()}
    assert not (d0 & d1), "pipeline stages must own disjoint devices"


@needs8
def test_env_spec_resolves(monkeypatch):
    monkeypatch.setenv("MXTPU_MESH", "dp4tp2")
    net = _build_mlp()
    tr = DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                             {"learning_rate": 0.1})
    assert tr.mesh_config.describe() == "dp4tp2"
    assert tr.mesh.axis_names == ("dp", "tp")


# ---------------------------------------------------------------------------
# parity: MXTPU_MESH unset is bitwise the flat dp trainer
# ---------------------------------------------------------------------------

@needs8
def test_unset_env_is_bitwise_flat_dp(monkeypatch):
    monkeypatch.delenv("MXTPU_MESH", raising=False)
    x, y = _batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    legacy_net = _build_mlp()
    legacy = DataParallelTrainer(legacy_net, loss_fn, "adam",
                                 {"learning_rate": 1e-2},
                                 mesh=make_mesh({"dp": -1}))
    l_legacy = _run_mixed_steps(legacy, x, y)

    new_net = _build_mlp()
    fresh = DataParallelTrainer(new_net, loss_fn, "adam",
                                {"learning_rate": 1e-2})
    l_new = _run_mixed_steps(fresh, x, y)

    assert l_new == l_legacy          # losses bitwise
    for (n, a), (_, b) in zip(sorted(legacy_net.collect_params().items()),
                              sorted(new_net.collect_params().items())):
        assert (a.data().asnumpy() == b.data().asnumpy()).all(), n
    sa, sb = legacy.state_dict(), fresh.state_dict()
    assert set(sa["arrays"]) == set(sb["arrays"])
    for k in sa["arrays"]:
        assert (sa["arrays"][k].asnumpy() ==
                sb["arrays"][k].asnumpy()).all(), k


# ---------------------------------------------------------------------------
# parity: 3D meshes vs the pure-dp reference (float eps)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("spec", ["2x2x2", "4x1x2"])
# 2x2x2 exercises every axis; 4x1x2 is the degenerate-axis twin
def test_3d_mesh_matches_pure_dp_reference(spec):
    # batch 32: divides dp=4 x (pp_microbatches=4 x n_micro=2)
    x, y = _batch(n=32)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    ref_net = _build_mlp()
    ref = DataParallelTrainer(ref_net, loss_fn, "adam",
                              {"learning_rate": 1e-2},
                              mesh_config=MeshConfig.from_spec("dp8"))
    l_ref = _run_mixed_steps(ref, x, y)

    net = _build_mlp()
    tr = DataParallelTrainer(net, loss_fn, "adam",
                             {"learning_rate": 1e-2},
                             mesh_config=MeshConfig.from_spec(spec),
                             pp_microbatches=4)
    l_3d = _run_mixed_steps(tr, x, y)

    np.testing.assert_allclose(l_3d, l_ref, rtol=1e-5)
    for (n, a), (_, b) in zip(sorted(ref_net.collect_params().items()),
                              sorted(net.collect_params().items())):
        np.testing.assert_allclose(a.data().asnumpy(),
                                   b.data().asnumpy(), rtol=2e-4,
                                   atol=2e-5, err_msg=n)
    # pp-staged params: each stage's arrays live ONLY on its slice
    if tr.mesh_config.pp > 1:
        ex = tr._pp_exec
        placements = [
            {d.id for v in vals for d in v.sharding.device_set}
            for vals in ex._param_vals]
        assert not (placements[0] & placements[1])


# ---------------------------------------------------------------------------
# checkpoint reshard: 2x2x2 -> dp8 bitwise round trip
# ---------------------------------------------------------------------------

@needs8
def test_checkpoint_reshards_2x2x2_to_dp8_bitwise(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager
    x, y = _batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _build_mlp()
    t3 = DataParallelTrainer(net, loss_fn, "adam",
                             {"learning_rate": 1e-2},
                             mesh_config=MeshConfig.from_spec("2x2x2"),
                             pp_microbatches=4)
    mx.random.seed(5)
    for _ in range(3):
        t3.step(x, y)
    src_params = _params(net)
    src_state = {k: v.asnumpy().copy()
                 for k, v in t3.state_dict()["arrays"].items()}

    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(3, params=net, trainer=t3)

    net8 = _build_mlp(seed=99)            # junk init: restore overwrites
    t8 = DataParallelTrainer(net8, loss_fn, "adam",
                             {"learning_rate": 1e-2},
                             mesh_config=MeshConfig.from_spec("dp8"))
    mgr.restore(params=net8, trainer=t8)
    for n, p in net8.collect_params().items():
        assert (p.data().asnumpy() == src_params[n]).all(), n
    sd8 = t8.state_dict()
    assert set(sd8["arrays"]) == set(src_state)
    for k, v in sd8["arrays"].items():
        assert (v.asnumpy() == src_state[k]).all(), k
    assert sd8["meta"]["num_update"] == 3

    # and back into a fresh 3D trainer (dp8 -> 2x2x2)
    net3 = _build_mlp(seed=98)
    t3b = DataParallelTrainer(net3, loss_fn, "adam",
                              {"learning_rate": 1e-2},
                              mesh_config=MeshConfig.from_spec("2x2x2"),
                              pp_microbatches=4)
    mgr.restore(params=net3, trainer=t3b)
    for k, v in t3b.state_dict()["arrays"].items():
        assert (v.asnumpy() == src_state[k]).all(), k


@needs8
def test_elastic_reshard_in_place_covers_all_axes():
    """``reshard_in_place`` moves a live 2x2x2 trainer onto dp8 (and
    the trainer keeps stepping) — the elastic transition re-fences the
    tp and pp axes, not just dp."""
    from mxnet_tpu.checkpoint import reshard_in_place
    x, y = _batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _build_mlp()
    tr = DataParallelTrainer(net, loss_fn, "adam",
                             {"learning_rate": 1e-2},
                             mesh_config=MeshConfig.from_spec("2x2x2"),
                             pp_microbatches=4)
    mx.random.seed(11)
    for _ in range(2):
        tr.step(x, y)
    state_before = {k: v.asnumpy().copy()
                    for k, v in tr.state_dict()["arrays"].items()}
    info = reshard_in_place(tr, MeshConfig.from_spec("dp8").build(),
                            params=net)
    assert info["source"] == "peer"
    assert tr.mesh_config.describe() == "dp8"
    assert tr._pp_exec is None            # executor dropped with the axis
    for k, v in tr.state_dict()["arrays"].items():
        assert (v.asnumpy() == state_before[k]).all(), k
    tr.step(x, y)                          # and it still trains
    assert tr._num_update == 3


# ---------------------------------------------------------------------------
# 1F1B schedule-order regression + bubble-filling hooks
# ---------------------------------------------------------------------------

def test_1f1b_schedule_is_canonical():
    s = one_f_one_b_schedule(2, 4)
    assert s.ops_by_stage[0] == [("F", 0), ("F", 1), ("B", 0), ("F", 2),
                                 ("B", 1), ("F", 3), ("B", 2), ("B", 3)]
    assert s.ops_by_stage[1] == [("F", 0), ("B", 0), ("F", 1), ("B", 1),
                                 ("F", 2), ("B", 2), ("F", 3), ("B", 3)]
    # dependencies hold tick-by-tick for a deeper schedule
    s4 = one_f_one_b_schedule(4, 8)
    done = {}
    for t, ops in enumerate(s4.ticks):
        for st, (ph, mb) in ops.items():
            if ph == "F" and st > 0:
                assert done[("F", st - 1, mb)] < t
            if ph == "B":
                assert done[("F", st, mb)] < t
                if st < 3:
                    assert done[("B", st + 1, mb)] < t
            done[(ph, st, mb)] = t
    # last stage never idles; earlier stages idle (S-1-s) warmup +
    # cooldown ticks — the bubbles the executor fills
    assert s4.bubble_ticks(3) == 0 and s4.bubble_ticks(0) == 6
    assert bubble_fraction(2, 4) == pytest.approx(0.2)
    with pytest.raises(mx.MXNetError):
        one_f_one_b_schedule(0, 4)


@needs8
def test_pp_executor_order_and_bubble_hooks():
    """The executor's event log IS the 1F1B schedule, stage grads fire
    the PR 5 grad-ready hooks the moment they are final (inside the
    bubble, BEFORE earlier stages finish backward), and the stage
    update dispatches right there."""
    from mxnet_tpu import _tape
    x, y = _batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _build_mlp()
    tr = DataParallelTrainer(net, loss_fn, "sgd",
                             {"learning_rate": 0.1},
                             mesh_config=MeshConfig.from_spec("4x1x2"),
                             pp_microbatches=4)
    fired = []
    handles = []
    tr._collect(nd.zeros((2, 12)))
    for _, p in sorted(net.collect_params().items()):
        handles.append(_tape.register_grad_ready_hook(
            p._data, lambda arr: fired.append(id(arr))))
    try:
        tr.step(x, y)
    finally:
        for h in handles:
            h.remove()
    ev = tr._pp_exec.events
    sched = one_f_one_b_schedule(2, 4)
    for s in range(2):
        ops = [(e[0], e[2]) for e in ev if e[0] in ("F", "B")
               and e[1] == s]
        assert ops == sched.ops_by_stage[s], (s, ops)
    # bubble filling: stage 1's grads are final (hooks fired + update
    # dispatched) BEFORE stage 0 finishes its last backward
    i_ready1 = ev.index(("ready", 1))
    i_upd1 = ev.index(("update", 1))
    i_last_b0 = ev.index(("B", 0, 3))
    assert i_ready1 < i_last_b0 and i_upd1 < i_last_b0
    # the tape grad-ready hooks really fired — once per parameter
    assert len(fired) == len(net.collect_params())


@needs8
def test_pp_requires_sequential_and_even_microbatches():
    x, y = _batch()
    net = gluon.nn.Dense(8)
    net.initialize()
    net(nd.zeros((2, 12)))
    tr = DataParallelTrainer(net, gluon.loss.L2Loss(), "sgd",
                             {"learning_rate": 0.1},
                             mesh_config=MeshConfig.from_spec("4x1x2"))
    with pytest.raises(mx.MXNetError, match="Sequential"):
        tr.step(x, y)
    net2 = _build_mlp()
    tr2 = DataParallelTrainer(net2, gluon.loss.L2Loss(), "sgd",
                              {"learning_rate": 0.1},
                              mesh_config=MeshConfig.from_spec("4x1x2"),
                              pp_microbatches=5)
    with pytest.raises(mx.MXNetError, match="divisible"):
        tr2.step(x, y)


def test_split_into_stages_balances_param_counts():
    net = _build_mlp(layers=(32, 16, 16, 8), in_dim=12)
    stages = split_into_stages(net, 2)
    assert len(stages) == 2 and all(stages)
    n_children = sum(len(s) for s in stages)
    assert n_children == 4
    with pytest.raises(mx.MXNetError):
        split_into_stages(net, 5)         # more stages than layers


# ---------------------------------------------------------------------------
# tp-sharded Dense parity (the satellite's named test)
# ---------------------------------------------------------------------------

@needs8
def test_tp_sharded_dense_training_matches_replicated():
    from mxnet_tpu.parallel import ParallelDense
    from mxnet_tpu.parallel.mesh import AXIS_TP
    x, y = _batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def build(tp):
        gblock._GLOBAL_COUNTERS.clear()
        net = gluon.nn.HybridSequential()
        if tp:
            net.add(ParallelDense(16, parallel_mode="column",
                                  activation="relu"),
                    ParallelDense(8, parallel_mode="row"))
        else:
            net.add(gluon.nn.Dense(16, activation="relu"),
                    gluon.nn.Dense(8))
        net.initialize()
        net(nd.zeros((2, 12)))
        rs = np.random.RandomState(1)
        for _, p in sorted(net.collect_params().items()):
            p.set_data(nd.array(rs.randn(*p.shape).astype(np.float32)
                                * 0.3))
        return net

    ref_net = build(False)
    ref = DataParallelTrainer(ref_net, loss_fn, "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9},
                              mesh_config=MeshConfig.from_spec("dp8"))
    l_ref = [float(ref.step(x, y).asnumpy()) for _ in range(3)]

    net = build(True)
    tr = DataParallelTrainer(net, loss_fn, "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9},
                             mesh_config=MeshConfig.from_spec("dp4tp2"))
    l_tp = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
    np.testing.assert_allclose(l_tp, l_ref, rtol=1e-5)
    # the weights are PHYSICALLY tp-sharded on the 3D mesh
    w = [p for _, p in sorted(net.collect_params().items())][0]
    assert AXIS_TP in (w._data._data.sharding.spec or ())
    for (_, a), (_, b) in zip(sorted(ref_net.collect_params().items()),
                              sorted(net.collect_params().items())):
        np.testing.assert_allclose(a.data().asnumpy(),
                                   b.data().asnumpy(), rtol=2e-4,
                                   atol=2e-5)


@needs8
def test_zoo_tp_rules_annotate_llama_and_bert():
    from mxnet_tpu.parallel import shard_model_tp
    from mxnet_tpu.gluon.model_zoo.nlp.llama import (LlamaConfig,
                                                     LlamaModel)
    gblock._GLOBAL_COUNTERS.clear()
    cfg = LlamaConfig(vocab_size=32, hidden_size=8, intermediate_size=16,
                      num_layers=1, num_heads=2, num_kv_heads=1,
                      max_seq_len=16)
    net = LlamaModel(cfg)
    net.initialize()
    net(nd.zeros((1, 4), dtype="int32"))
    shard_model_tp(net, "llama")
    annotated = [n for n, p in net.collect_params().items()
                 if p.shard_spec is not None]
    assert len(annotated) == 7            # q/k/v/o + gate/up/down
    from mxnet_tpu.gluon.model_zoo.nlp.bert import BERTEncoder
    gblock._GLOBAL_COUNTERS.clear()
    enc = BERTEncoder(num_layers=1, units=8, hidden_size=16,
                      num_heads=2, use_flash=False)
    enc.initialize()
    enc(nd.zeros((1, 4, 8)))
    shard_model_tp(enc, "bert")
    bs = [n for n, p in enc.collect_params().items()
          if p.shard_spec is not None]
    assert len(bs) == 12                  # 6 layers x (weight + bias)
    with pytest.raises(mx.MXNetError):
        shard_model_tp(enc, "resnet")
