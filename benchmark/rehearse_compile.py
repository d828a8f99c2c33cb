#!/usr/bin/env python3
"""Compile each cell's training step for a described ``v5e:2x2`` topology and
print what the compiler says it needs: no chip, no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py [--workload NAME ...]
        [--per-chip-batch N]

The TPU compiler is installed in the sandbox and compiles for chips that are
described, not attached.  For each cell this prints ``memory_analysis()`` of
the step (argument, output, temporary and aliased bytes per chip; what has to
fit in 16 GB is argument + output - alias + temporary, beside the batch pool)
and, over several chips, the collectives in the compiled program.  Nothing
runs, so nothing here is a time.  A PR that adds a cell sizes it with this
before it spends chip time; ``--per-chip-batch`` tries another batch without
editing the traffic file.

It reaches into ``DataParallelTrainer`` (``_collect``, ``_build``,
``_jitted``, ``_rule_init``) because the trainer places its parameters with
``device_put``, which a described device cannot take; it hands the jitted
step shapes instead.
"""
from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import re
import sys
from unittest import mock

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import manifest as manifest_mod      # noqa: E402

# "<result type> all-reduce(" - the type ends in '}', ')' or ']'; an operand
# that names a collective ("(%all-reduce.4)") does not match
COLLECTIVE = re.compile(
    r"[})\]] (all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)(-start)?\(")


def compile_cell(cell, topo_devices, per_chip_batch=None):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu import amp
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    sizes, traffic = dict(cell.config), dict(cell.traffic)
    if per_chip_batch:
        traffic["per_chip_batch"] = per_chip_batch
    model = importlib.import_module("models." + sizes["model"])
    if sizes["dtype"] == "bfloat16":
        amp.init(target_dtype="bfloat16")
    devices = topo_devices[:cell.chips]
    axes = traffic["mesh"]
    mesh = Mesh(np.asarray(devices).reshape(list(axes.values())),
                tuple(axes))
    global_batch = traffic["per_chip_batch"] * cell.chips

    # shapes only: two real samples on the CPU resolve the deferred parameter
    # shapes, the full batch exists as ShapeDtypeStructs
    probe = model.make_pool(sizes, traffic, 2, 1, 0)[0]
    net = model.build(sizes)
    net.initialize(ctx=mx.cpu(0))
    opt = dict(sizes["optimizer"])
    trainer = DataParallelTrainer(net, model.make_loss(), opt.pop("name"),
                                  opt, mesh=mesh)
    params = trainer._collect(*[NDArray(a) for a in model.shape_probe(probe)])
    trainer._build()

    replicated = NamedSharding(mesh, P())
    by_batch = NamedSharding(mesh, P("dp"))

    def shaped(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    values = [p.data().data for p in params]
    param_shapes = [shaped(v, replicated) for v in values]
    state_shapes = [jax.tree.map(lambda s: shaped(s, replicated),
                                 jax.eval_shape(trainer._rule_init, v))
                    for v in values]
    batch_shapes = [jax.ShapeDtypeStruct((global_batch,) + a.shape[1:],
                                         a.dtype, sharding=by_batch)
                    for a in probe]
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)
    key = jax.tree.map(lambda s: shaped(s, replicated),
                       jax.eval_shape(lambda: jax.random.key(0)))
    # the kernels ask jax.default_backend() whether Mosaic compiles them
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = trainer._jitted.lower(param_shapes, state_shapes, lr, key,
                                        *batch_shapes)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    pool_bytes = traffic["pool"] * sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in batch_shapes) \
        // cell.chips
    report = {
        "cell": cell.name, "chips": cell.chips,
        "per_chip_batch": traffic["per_chip_batch"],
        "per_chip_bytes": {
            "argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "generated_code": ma.generated_code_size_in_bytes,
            "batch_pool": pool_bytes,
        },
        "mosaic_calls": text.count("tpu_custom_call"),
        "collectives": dict(collections.Counter(
            m.group(1) for m in COLLECTIVE.finditer(text))),
    }
    b = report["per_chip_bytes"]
    report["step_needs_gb"] = (b["argument"] + b["output"] - b["alias"]
                               + b["temp"]) / 1e9
    report["with_pool_gb"] = report["step_needs_gb"] + pool_bytes / 1e9
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--per-chip-batch", type=int)
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = manifest_mod.Manifest()
    for name in args.workload or list(man.workloads):
        print(json.dumps(compile_cell(man.cell(name), list(topo.devices),
                                      args.per_chip_batch)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
