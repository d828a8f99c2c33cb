"""``train_fused`` with the optimizer's update sharded over the data axis
(ZeRO-1: ``DataParallelTrainer(shard_updates=...)``, as the traffic file's
``shard_updates`` says): the same pool, step, window and checks, and one
check more — that the trainer took the sharded path, which it leaves for
the replicated update where it cannot shard (one chip, an update rule that
needs whole tensors, a parameter sharded otherwise); the cell would then
measure the other path.

``train_fused`` builds its trainer without the argument and a change that
adds a cell leaves the files it finds as they are, so this runner hands
``train_fused.prepare`` a trainer class with the argument bound."""
from __future__ import annotations

import functools
from unittest import mock

from runners import train_fused as base


def prepare(job):
    from mxnet_tpu.parallel import data_parallel
    shard = job.traffic["shard_updates"]
    with mock.patch.object(
            data_parallel, "DataParallelTrainer", functools.partial(
                data_parallel.DataParallelTrainer, shard_updates=shard)):
        st = base.prepare(job)
    sharded = bool(st.trainer._zero1_active())
    st.checks["updates_sharded"] = (
        sharded == shard, f"ZeRO-1 {'on' if sharded else 'off'} over "
                          f"{st.mesh.size} chips, shard_updates {shard}")
    return st


step = base.step
finish = base.finish
