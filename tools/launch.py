#!/usr/bin/env python
"""Distributed job launcher (reference: tools/launch.py + dmlc-core tracker).

The reference spawns scheduler/server/worker processes over ssh/mpi/local
and wires them with ``DMLC_*`` env. The TPU-native rebuild has no servers:
each host runs ONE worker process and the processes rendezvous through
``jax.distributed`` (coordinator = worker 0). This launcher keeps the
reference's CLI:

    python tools/launch.py -n 4 --launcher local python train.py --kv-store dist_sync

``--launcher local`` forks N worker processes on this machine (the
reference's fake-cluster mode used by tests/nightly/dist_sync_kvstore.py);
each gets JAX_PLATFORMS=cpu and a private coordinator port so the whole
flow (rendezvous, psum over processes, barrier) runs on one box.
``--launcher ssh`` emits the per-host command lines (zero-egress images
cannot ssh; print instead of exec so the operator's scheduler runs them).

``--supervise`` (ISSUE 19) upgrades the local mode into the real pod
launcher built on :class:`mxnet_tpu.pod.PodLauncher`: children are
watched, a worker death is COMMITTED as a membership change (atomic
``membership.json`` with a fresh coordinator port), and the survivors
tear down + re-init the JAX coordination service at the smaller world
size (``_dist_init.reinit_distributed``) and resume from the shared
checkpoint — a real death changes ``jax.process_count()``.  With no
command given it runs the deterministic ``mxnet_tpu.testing.pod_worker``
workload; the final stdout line is one JSON summary (epoch, dead ranks,
requeued requests).

    python tools/launch.py -n 4 --supervise --pod-dir /tmp/pod --steps 8
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def supervise(args):
    """The pod mode: spawn + supervise through mxnet_tpu.pod, print one
    JSON summary line."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from mxnet_tpu.pod import PodLauncher
    pod_dir = args.pod_dir or tempfile.mkdtemp(prefix="mxtpu_pod_")
    env = {}
    for kv in args.env:
        k, _, v = kv.partition("=")
        env[k] = v
    launcher = PodLauncher(args.num_workers, pod_dir,
                           argv=args.command or None, env=env,
                           steps=args.steps,
                           ckpt_every=args.ckpt_every)
    launcher.start()
    try:
        summary = launcher.supervise(timeout_s=args.timeout)
    finally:
        launcher.shutdown()
    summary["pod_dir"] = pod_dir
    print("PODLAUNCH " + json.dumps(summary))
    return 0 if set(summary["done"]) else 1


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="parameter-server processes for dist_async "
                         "(reference DMLC_NUM_SERVER); keys shard across "
                         "them by crc32. 0 = no server role (dist_sync "
                         "needs none; dist_async then runs one server "
                         "inside worker 0)")
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None,
                    help="one host per line (ssh launcher)")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE env for workers")
    ap.add_argument("--supervise", action="store_true",
                    help="pod mode (ISSUE 19): watch children, commit "
                         "membership changes on death, survivors "
                         "re-init jax.distributed at the new world")
    ap.add_argument("--pod-dir", default=None,
                    help="control-plane directory for --supervise "
                         "(default: a fresh temp dir)")
    ap.add_argument("--steps", type=int, default=8,
                    help="pod_worker training steps (--supervise)")
    ap.add_argument("--ckpt-every", type=int, default=3,
                    help="pod_worker checkpoint cadence (--supervise)")
    ap.add_argument("--timeout", type=float, default=300.0,
                    help="supervision deadline in seconds")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if args.supervise:
        return supervise(args)
    if not args.command:
        ap.error("no command given")
    cmd = args.command

    port = _free_port()
    coordinator = f"127.0.0.1:{port}"

    if args.launcher == "ssh":
        hosts = []
        if args.hostfile:
            with open(args.hostfile) as f:
                hosts = [h.strip() for h in f if h.strip()]
        if not hosts:
            hosts = [f"host{i}" for i in range(args.num_workers)]
        coord = f"{hosts[0]}:{port}"
        ps_env = ""
        print("# zero-egress image: run these on each host")
        if args.num_servers > 0:
            addrs = ",".join(
                f"{hosts[s % len(hosts)]}:{port + 1000 + s}"
                for s in range(args.num_servers))
            ps_env = f"MXTPU_PS_ADDRS={addrs} "
            for sid in range(args.num_servers):
                env = (f"DMLC_ROLE=server "
                       f"DMLC_NUM_WORKER={args.num_workers} "
                       f"DMLC_NUM_SERVER={args.num_servers} "
                       f"{ps_env}MXTPU_SERVER_ID={sid} "
                       f"MXTPU_NUM_PROCESSES={args.num_workers}")
                print(f"ssh {hosts[sid % len(hosts)]} '{env} "
                      f"{sys.executable} -m mxnet_tpu.kvstore.ps_server'")
        for rank in range(args.num_workers):
            env = (f"DMLC_ROLE=worker DMLC_NUM_WORKER={args.num_workers} "
                   f"DMLC_NUM_SERVER={args.num_servers} "
                   f"{ps_env}"
                   f"DMLC_WORKER_ID={rank} "
                   f"MXTPU_COORDINATOR={coord} "
                   f"MXTPU_NUM_PROCESSES={args.num_workers} "
                   f"MXTPU_PROCESS_ID={rank}")
            print(f"ssh {hosts[rank % len(hosts)]} '{env} "
                  f"{' '.join(cmd)}'")
        return 0

    ps_addrs = ""
    if args.num_servers > 0:
        ps_addrs = ",".join(f"127.0.0.1:{_free_port()}"
                            for _ in range(args.num_servers))

    procs = []
    try:
        for sid in range(args.num_servers):
            env = dict(os.environ)
            env.update({
                "DMLC_ROLE": "server",
                "DMLC_NUM_WORKER": str(args.num_workers),
                "DMLC_NUM_SERVER": str(args.num_servers),
                "MXTPU_PS_ADDRS": ps_addrs,
                "MXTPU_SERVER_ID": str(sid),
                "MXTPU_NUM_PROCESSES": str(args.num_workers),
                "JAX_PLATFORMS": "cpu",
            })
            for kv in args.env:
                k, _, v = kv.partition("=")
                env[k] = v
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "mxnet_tpu.kvstore.ps_server"],
                env=env))
        workers = []
        for rank in range(args.num_workers):
            env = dict(os.environ)
            env.update({
                "DMLC_ROLE": "worker",
                "DMLC_NUM_WORKER": str(args.num_workers),
                "DMLC_NUM_SERVER": str(args.num_servers),
                "DMLC_WORKER_ID": str(rank),
                "MXTPU_COORDINATOR": coordinator,
                "MXTPU_NUM_PROCESSES": str(args.num_workers),
                "MXTPU_PROCESS_ID": str(rank),
                # local fake cluster runs on CPU (SURVEY.md §4 technique 3)
                "JAX_PLATFORMS": "cpu",
            })
            if ps_addrs:
                env["MXTPU_PS_ADDRS"] = ps_addrs
            for kv in args.env:
                k, _, v = kv.partition("=")
                env[k] = v
            p = subprocess.Popen(cmd, env=env)
            procs.append(p)
            workers.append(p)
        rc = 0
        for p in workers:     # servers serve until torn down below
            rc = p.wait() or rc
        return rc
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)


if __name__ == "__main__":
    sys.exit(main())
