"""``train_fused`` for a cell whose first loss says little about its layers,
held to its own limits: the same fused step, window and ``finish``, and in
``prepare`` a comparison of the program's *gradients* with ``jax.grad`` of the
plain float32 reference, beside the first loss at a limit the configuration
states.

Why: a language model at initialisation has a loss of ln(vocabulary) plus what
its head's random logits add, whatever its layers compute, so
``train_fused``'s 2e-2 on the first loss cannot fail for it.  The gradient of
a parameter deep in the stack can: it passes through every layer above it,
forward and backward.

Where the program's gradient comes from: the step under test itself.  The
cell trains with Adam, whose first moment after one step from zero state is
``(1 - beta1) * g``; the trainer's ``state_dict()`` hands that array over, so
no second program is built and nothing is held beside the step.  The
reference's gradients go to the host before the trainer exists, and it runs
a layer at a time (``reference.gradient_program``), so that its programs
stay under the step's own temporaries (``hbm_peak_gb`` is the process's
peak).

The configuration's ``checks`` give ``loss_rtol`` and the ``gradients`` to
compare, each with its own limit, by the program's parameter names.  A
gradient's reading is ``|g - g_ref| / |g_ref|`` (2-norms, float64 on the host): 0 for
the reference itself, 1 for a gradient of zero, a state left unchanged.

The reference also counts the rows each expert layer routed to the experts
held here, before the first step and after the window (``# routed_rows``),
and leaves the counts in ``job.sizes["routed_rows"]``, where the grouped
product's roofline reader finds them: the trace cannot say how many rows a
call had.

``MXTPU_BENCH_CONTROL=float8|no_experts|bfloat16`` (never set by the driver)
makes a control run: the reference's own stand-in of that name
(``reference.control``) takes the program's place in both comparisons.  With
the first two the run has to print ``correct: false``; the third shows what
the configuration's own precision reads.  That is how the limits were set
(PERF.md section 2).
"""
from __future__ import annotations

import importlib
import json
import os
import types

import numpy as np

from runners import train_fused as base

CONTROL_ENV = "MXTPU_BENCH_CONTROL"


def _relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) /
                 max(float(np.linalg.norm(want)), 1e-300))


def prepare(job):
    import jax
    import mxnet_tpu as mx
    from jax.sharding import NamedSharding, PartitionSpec
    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    sizes, traffic = job.sizes, job.traffic
    limits = sizes["checks"]
    model = importlib.import_module("models." + sizes["model"])
    reference = importlib.import_module("reference." + sizes["model"])
    st = types.SimpleNamespace(checks={}, job=job)
    control = os.environ.get(CONTROL_ENV)

    if sizes["dtype"] != "bfloat16" or sizes["optimizer"]["name"] != "adam":
        raise ValueError("train_fused_grads reads the gradient out of Adam's "
                         "first moment in a bfloat16 cell")
    amp.init(target_dtype="bfloat16")
    mesh = make_mesh(dict(traffic["mesh"]), devices=job.devices)
    st.global_batch = traffic["per_chip_batch"] * len(job.devices)
    st.flops_per_sample = model.flops_per_sample(sizes, traffic)

    with job.phase("make_pool"):
        pool = model.make_pool(sizes, traffic, st.global_batch,
                               traffic["pool"], job.seed)
    with job.phase("build_and_initialize"):
        mx.random.seed(job.seed)
        net = model.build(sizes)
        net.initialize(ctx=mx.cpu(0) if job.rehearse else mx.tpu(0))
        net(*[NDArray(a) for a in model.shape_probe(pool[0])])

    with job.phase("reference"):
        params0 = base._parameters(net)
        st.reference = reference.gradient_program(sizes, limits["gradients"])
        st.batch0 = pool[0]
        ref_loss, rows, g_ref = st.reference(params0, st.batch0)
        ref_loss, g_ref = float(ref_loss), jax.device_get(g_ref)
        st.rows = {"first": np.asarray(rows).tolist()}
        if control:
            got_loss, _, got = reference.gradient_program(
                sizes, limits["gradients"], stand_in=control)(
                    params0, st.batch0)
            got_loss, got = float(got_loss), jax.device_get(got)
        del params0

    opt = dict(sizes["optimizer"])
    opt_name = opt.pop("name")
    st.trainer = DataParallelTrainer(net, model.make_loss(), opt_name, opt,
                                     mesh=mesh)
    by_batch = NamedSharding(mesh, PartitionSpec("dp"))
    st.pool = [[NDArray(jax.device_put(a, by_batch)) for a in batch]
               for batch in pool]
    del pool

    with job.phase("first_step"):
        loss0 = float(st.trainer.step(*st.pool[0]).asnumpy())
    who = "program"
    if control:
        who = f"control {control} in the program's place"
    else:
        # the step returns the loss of the parameters it was given; Adam's
        # first moment after that one step from zero is (1 - beta1) g
        got_loss = loss0
        moments = st.trainer.state_dict()["arrays"]
        order = sorted(net.collect_params())
        beta1 = sizes["optimizer"].get("beta1", 0.9)
        got = {name: moments[f"opt/{order.index(net.prefix + name)}/m"]
               .asnumpy() / (1.0 - beta1) for name in g_ref}
        del moments
    err = abs(got_loss - ref_loss) / max(abs(ref_loss), 1e-30)
    st.checks["first_loss_vs_reference"] = (
        bool(np.isfinite(got_loss) and err <= limits["loss_rtol"]),
        f"{who} {got_loss:.6f} reference {ref_loss:.6f} rel_err {err:.3e} "
        f"tolerance {limits['loss_rtol']}")
    readings = {name: _relative(got[name], g_ref[name]) for name in g_ref}
    st.checks["first_gradient_vs_reference"] = (
        all(np.isfinite(r) and r <= limits["gradients"][name]
            for name, r in readings.items()),
        f"{who}: |g - g_ref| / |g_ref| " + ", ".join(
            f"{name} {r:.3e} (tolerance {limits['gradients'][name]})"
            for name, r in readings.items()))
    st.net, st.mesh = net, mesh
    return st


step = base.step


def finish(st):
    """``train_fused``'s checks, and the routed rows of the first batch at
    the parameters the window ended with."""
    _, rows, _ = st.reference(base._parameters(st.net), st.batch0,
                              gradients=False)
    st.rows["last"] = np.asarray(rows).tolist()
    st.job.sizes["routed_rows"] = st.rows
    print(f"# routed_rows: {json.dumps(st.rows)}", flush=True)
    return base.finish(st)
