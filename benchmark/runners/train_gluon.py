"""The README loop: a hybridized Gluon block under the eager tape, driven the
way a ported MXNet script drives it::

    net.hybridize()
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(batch)

One step is several programs (the block's jitted forward, the loss's eager
operations, the tape's pullbacks, ``gluon.Trainer``'s update), dispatched from
Python between each other.  ``prepare`` makes the same two checks against the
plain reference as ``train_fused`` does, under the same tolerances; ``step``
dispatches one step and hands back the call that waits for its loss.  Which
model, which sizes: from the job's configuration and traffic files.
"""
from __future__ import annotations

import importlib
import time
import types

import numpy as np

from readers.spans import WALL_STAMP
from runners.train_fused import LOSS_RTOL, UPDATE_RTOL, _parameters


def prepare(job):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.ndarray.ndarray import NDArray

    sizes, traffic = job.sizes, job.traffic
    model = importlib.import_module("models." + sizes["model"])
    reference = importlib.import_module("reference." + sizes["model"])
    st = types.SimpleNamespace(checks={}, record=autograd.record,
                               annotate=jax.profiler.TraceAnnotation)

    if sizes["dtype"] != "float32":
        raise ValueError(f"the README loop runs without amp: dtype "
                         f"{sizes['dtype']!r}")
    if len(job.devices) != 1 or traffic["mesh"] != {"dp": 1}:
        raise ValueError("the README loop is a one-chip loop")
    st.global_batch = traffic["per_chip_batch"]
    st.flops_per_sample = model.flops_per_sample(sizes, traffic)

    with job.phase("make_pool"):
        pool = model.make_pool(sizes, traffic, st.global_batch,
                               traffic["pool"], job.seed)
    with job.phase("build_and_initialize"):
        mx.random.seed(job.seed)
        net = model.build(sizes)
        net.initialize(ctx=mx.cpu(0) if job.rehearse else mx.tpu(0))
        # one eager forward of two samples resolves the deferred shapes
        net(*[NDArray(a) for a in model.shape_probe(pool[0])])
        net.hybridize()

    with job.phase("reference"):
        params0 = _parameters(net)
        ref_loss = float(jax.jit(
            lambda p, b: reference.loss(p, b, sizes))(params0, pool[0]))
        name = sizes["update_check"]["parameter"]
        w0 = np.asarray(params0[name], np.float64)
        g_ref = np.asarray(jax.jit(jax.grad(
            lambda w, p, b: reference.loss({**p, name: w}, b, sizes)))(
                params0[name], params0, pool[0]), np.float64)
        del params0

    opt = dict(sizes["optimizer"])
    if opt.pop("name") != "sgd":
        raise ValueError("update_check knows the first SGD step only")
    st.net, st.loss_fn = net, model.make_loss()
    st.trainer = gluon.Trainer(net.collect_params(), "sgd", opt)
    st.pool = [[NDArray(a) for a in batch] for batch in pool]
    del pool

    with job.phase("first_step"):
        loss0 = step(st, 0)()
    # the loss is taken in the forward, before the update of its own step
    err = abs(loss0 - ref_loss) / max(abs(ref_loss), 1e-30)
    st.checks["first_loss_vs_reference"] = (
        bool(np.isfinite(loss0) and err <= LOSS_RTOL),
        f"program {loss0:.6f} reference {ref_loss:.6f} rel_err {err:.3e} "
        f"tolerance {LOSS_RTOL}")
    w1 = np.asarray(_parameters(net)[name], np.float64)
    lr_g = sizes["optimizer"]["learning_rate"] * g_ref
    err = float(np.max(np.abs(w1 - (w0 - lr_g))) /
                max(float(np.max(np.abs(lr_g))), 1e-30))
    st.checks["first_update_vs_reference"] = (
        bool(err <= UPDATE_RTOL),
        f"{name}: max |w1 - (w0 - lr g_ref)| / max |lr g_ref| = "
        f"{err:.3e} tolerance {UPDATE_RTOL}")
    return st


def step(st, i):
    """Dispatch step ``i`` on the pool's next batch; the returned call waits
    for that step's loss (the mean over the batch, as a script logs it).  The
    loss is ready when the step's forward is: its backward and update may
    still be running when the call returns."""
    # where the profiler's session began, for readers/spans.py
    with st.annotate(WALL_STAMP + str(time.time_ns())):
        pass
    x, y = st.pool[i % len(st.pool)]
    with st.record():
        loss = st.loss_fn(st.net(x), y)
    loss.backward()
    st.trainer.step(st.global_batch)
    handle = loss.mean()
    return lambda: float(handle.asnumpy())


def finish(st):
    return st.checks
