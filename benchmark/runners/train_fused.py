"""The fused training step: ``DataParallelTrainer.step`` over a mesh, one
jitted program for forward, backward, gradient reduction and update.

``prepare`` builds everything and makes the checks that need the initial
parameters (all of it set-up, outside the window); ``step`` dispatches one
step and hands back the call that waits for its loss; ``finish`` makes the
checks that need the state after the window.  Which model, which sizes, which
mesh: all from the job's configuration and traffic files.
"""
from __future__ import annotations

import importlib
import types

import numpy as np

# bf16 program against float32 reference.  The loss is a mean over the batch
# of a log-softmax whose logits went through 50 (ResNet) or 12x6 (BERT)
# bf16 matmuls: each rounds to 2^-8 = 4e-3 relative, the errors are
# independent and mostly average out, and earlier runs on the chip saw 2e-5
# to 4e-3.  2e-2 leaves a factor of five over that and still fails a dropped
# bias, a wrong normalisation axis, a missing residual or a wrong label
# column, each of which moves a from-scratch loss of ~7 (ln 1000) or ~0.7
# (ln 2) by far more than 2 %.
LOSS_RTOL = 2e-2
# first SGD step of one small parameter against w0 - lr * g_ref, as a share
# of the largest |lr * g_ref|: the gradient of a bias is mean(softmax - 1hot),
# and at a random start the softmax is peaked, so the bf16 error of the
# logits reaches it: 0.6e-2 to 2.5e-2 over 13 runs on the chip (PR 24).  1e-1
# leaves a factor of four and still fails a wrong sign (2.0), a missing update
# (1.0), a sum where a mean belongs (x batch) or a rate off by a tenth.
UPDATE_RTOL = 1e-1


def _parameters(net):
    """name -> jax array, the network's own prefix (``resnetv10_``,
    ``bertmodel0_``) removed, so that the reference finds each parameter by a
    name that does not count how many networks the process has built."""
    prefix = net.prefix
    return {name[len(prefix):] if name.startswith(prefix) else name:
            p.data().data for name, p in net.collect_params().items()}


def prepare(job):
    import jax
    import mxnet_tpu as mx
    from jax.sharding import NamedSharding, PartitionSpec
    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    sizes, traffic = job.sizes, job.traffic
    model = importlib.import_module("models." + sizes["model"])
    reference = importlib.import_module("reference." + sizes["model"])
    st = types.SimpleNamespace(checks={})

    if sizes["dtype"] == "bfloat16":
        amp.init(target_dtype="bfloat16")
    elif sizes["dtype"] != "float32":
        raise ValueError(f"dtype {sizes['dtype']!r}")
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
        # one eager forward of two samples resolves the deferred shapes; the
        # trainer would otherwise do it at the full batch
        net(*[NDArray(a) for a in model.shape_probe(pool[0])])

    with job.phase("reference"):
        params0 = _parameters(net)
        ref_loss = float(jax.jit(
            lambda p, b: reference.loss(p, b, sizes))(params0, pool[0]))
        watched = sizes.get("update_check")
        if watched:
            name = watched["parameter"]
            w0 = np.asarray(params0[name], np.float64)
            g_ref = np.asarray(jax.jit(jax.grad(
                lambda w, p, b: reference.loss({**p, name: w}, b, sizes)))(
                    params0[name], params0, pool[0]), np.float64)
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
    # the step returns the loss of the parameters it was given, so the first
    # one is the program's loss before any update
    err = abs(loss0 - ref_loss) / max(abs(ref_loss), 1e-30)
    st.checks["first_loss_vs_reference"] = (
        bool(np.isfinite(loss0) and err <= LOSS_RTOL),
        f"program {loss0:.6f} reference {ref_loss:.6f} rel_err {err:.3e} "
        f"tolerance {LOSS_RTOL}")
    if watched:
        if opt_name != "sgd":
            raise ValueError("update_check knows the first SGD step only")
        w1 = np.asarray(_parameters(net)[name], np.float64)
        step = sizes["optimizer"]["learning_rate"] * g_ref
        err = float(np.max(np.abs(w1 - (w0 - step))) /
                    max(float(np.max(np.abs(step))), 1e-30))
        st.checks["first_update_vs_reference"] = (
            bool(err <= UPDATE_RTOL),
            f"{name}: max |w1 - (w0 - lr g_ref)| / max |lr g_ref| = "
            f"{err:.3e} tolerance {UPDATE_RTOL}")
    st.net, st.mesh = net, mesh
    return st


def step(st, i):
    """Dispatch step ``i`` on the pool's next batch; the returned call waits
    for that step and gives its loss."""
    handle = st.trainer.step(*st.pool[i % len(st.pool)])
    return lambda: float(handle.asnumpy())


def finish(st):
    """After the window: over more than one chip, every parameter has to be
    the same on all of them, bit for bit."""
    if st.mesh.size > 1:
        st.checks["replicas_identical"] = _replicas_identical(st)
    return st.checks


def _replicas_identical(st):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def fingerprints(*local):
        # each chip sums the bits of its own copy, plainly and weighted by
        # position: two copies that differ anywhere differ here
        out = []
        for x in local:
            bits = lax.bitcast_convert_type(
                x.astype(jnp.float32).reshape(-1), jnp.uint32)
            weight = jnp.arange(bits.size, dtype=jnp.uint32) * \
                jnp.uint32(2654435761) + jnp.uint32(1)
            out.append(jnp.stack([jnp.sum(bits), jnp.sum(bits * weight)]))
        return jnp.stack(out)[None]

    values = [p.data().data for p in st.net.collect_params().values()]
    table = np.asarray(jax.jit(jax.shard_map(
        fingerprints, mesh=st.mesh, in_specs=P(), out_specs=P("dp"),
        check_vma=False))(*values))
    same = bool((table == table[:1]).all())
    return same, (f"{len(values)} parameters on {st.mesh.size} chips: "
                  f"{'identical' if same else 'DIFFERENT'} bit sums")
