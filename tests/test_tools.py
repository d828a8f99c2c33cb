"""tools/: launcher, im2rec, bandwidth (reference: tools/ +
tests/nightly/dist_sync_kvstore.py run through launch.py --launcher local)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env():
    """Subprocess env: CPU jax, the repo on the path."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [REPO])
    return env


def test_im2rec_roundtrip(tmp_path):
    # fake "images": raw bytes are packed as-is (--pass-through semantics)
    root = tmp_path / "data"
    for cls in ("cat", "dog"):
        d = root / cls
        d.mkdir(parents=True)
        for i in range(3):
            (d / f"{i}.jpg").write_bytes(bytes([i]) * 100)
    prefix = str(tmp_path / "set")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix, str(root), "--list"], capture_output=True, text=True,
        env=_cpu_env())
    assert r.returncode == 0, r.stderr
    assert os.path.exists(prefix + ".lst")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix + ".lst", str(root)], capture_output=True, text=True,
        env=_cpu_env())
    assert r.returncode == 0, r.stderr
    rec = mx.recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    assert len(rec.keys) == 6
    header, blob = mx.recordio.unpack(rec.read_idx(rec.keys[0]))
    assert len(blob) == 100
    rec.close()


def test_bandwidth_measure_runs():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bandwidth",
                                      "measure.py"),
         "--data-mb", "1", "--iters", "2", "--warmup", "1",
         "--num-keys", "2"],
        capture_output=True, text=True, env=_cpu_env())
    assert r.returncode == 0, r.stderr
    assert "GB/s" in r.stdout


@pytest.mark.slow
def test_launch_local_dist_kvstore(tmp_path):
    """The reference nightly dist test: N local processes, dist_sync
    pushpull sums across workers."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "kv = mx.kv.create('dist_sync')\n"
        "rank, size = kv.rank, kv.num_workers\n"
        "assert size == 2, size\n"
        "v = mx.nd.ones((4,)) * (rank + 1)\n"
        "kv.init('w', mx.nd.zeros((4,)))\n"
        "kv.pushpull('w', v, out=v)\n"
        "np.testing.assert_allclose(v.asnumpy(), 3.0 * np.ones(4))\n"
        "assert kv._wire_mode == 'allreduce', kv._wire_mode  # in-graph path\n"
        "kv.barrier()\n"
        "print('WORKER_OK', rank)\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=_cpu_env())
    assert r.returncode == 0, r.stderr + r.stdout
    assert r.stdout.count("WORKER_OK") == 2, r.stdout + r.stderr


@pytest.mark.slow
def test_launch_local_dist_async(tmp_path):
    """True dist_async (r2 missing #3): server-side optimizer applied per
    push with NO step barrier; workers push at DIFFERENT rates and the
    final weight reflects every (stale) gradient."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "kv = mx.kv.create('dist_async')\n"
        "assert kv.type == 'dist_async'\n"
        "rank = kv.rank\n"
        "if rank == 0:\n"
        "    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))\n"
        "kv.init('w', mx.nd.ones((4,)))   # barriers after worker-0 init\n"
        "for _ in range(10 if rank == 0 else 5):\n"
        "    kv.push('w', mx.nd.ones((4,)))   # async apply, no waiting\n"
        "kv.barrier()\n"
        "w = mx.nd.zeros((4,))\n"
        "kv.pull('w', out=w)\n"
        "np.testing.assert_allclose(w.asnumpy(), -0.5 * np.ones(4),\n"
        "                           rtol=1e-5)   # 1 - 0.1*15\n"
        "assert kv.push_stats()['w'] == 15\n"
        "print('ASYNC_OK', rank)\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=_cpu_env())
    assert r.returncode == 0, r.stderr + r.stdout
    assert r.stdout.count("ASYNC_OK") == 2, r.stdout + r.stderr


@pytest.mark.slow   # 2-process launch; the int8 wire math is gated
# fast in test_kvstore.py
def test_launch_local_dist_int8_compression(tmp_path):
    """2-process dist_sync with EQuARX-style int8 wire compression: the
    cross-worker sum matches within the per-block quantization bound."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "kv = mx.kv.create('dist_sync')\n"
        "rank, size = kv.rank, kv.num_workers\n"
        "assert size == 2, size\n"
        "kv.set_gradient_compression({'type': 'int8'})\n"
        "g = np.linspace(-1, 1, 600).astype(np.float32) * (rank + 1)\n"
        "kv.init('w', mx.nd.zeros((600,)))\n"
        "v = mx.nd.array(g)\n"
        "kv.pushpull('w', v, out=v)\n"
        "expect = np.linspace(-1, 1, 600) * 3.0\n"
        "np.testing.assert_allclose(v.asnumpy(), expect, atol=3 / 127.0)\n"
        "kv.barrier()\n"
        "print('WORKER_OK', rank)\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=_cpu_env())
    assert r.returncode == 0, r.stderr + r.stdout
    assert r.stdout.count("WORKER_OK") == 2, r.stdout + r.stderr


def test_dist_async_sharded_servers(tmp_path):
    """VERDICT r3 #8: launch.py -s 2 runs two dedicated server processes;
    keys hash across both (crc32), the binary typed protocol carries
    everything (no pickle on the wire), and the server-side optimizer
    applies on whichever server owns the key."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "kv = mx.kv.create('dist_async')\n"
        "assert len(kv._clients) == 2, len(kv._clients)\n"
        "kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))\n"
        "keys = [f'w{i}' for i in range(8)]\n"
        "for k in keys:\n"
        "    kv.init(k, mx.nd.ones((3,)))\n"
        "for k in keys:\n"
        "    kv.push(k, mx.nd.ones((3,)))\n"
        "for k in keys:\n"
        "    out = mx.nd.zeros((3,))\n"
        "    kv.pull(k, out=out)\n"
        "    np.testing.assert_allclose(out.asnumpy(), 0.9 * np.ones(3),\n"
        "                               rtol=1e-5)\n"
        "per = kv.per_server_stats()\n"
        "assert len(per) == 2\n"
        "assert all(len(s) > 0 for s in per), per   # both servers own keys\n"
        "assert sum(sum(s.values()) for s in per) == 8\n"
        "from mxnet_tpu.kvstore.ps_server import key_to_server\n"
        "for k in keys:\n"
        "    sid = key_to_server(k, 2)\n"
        "    assert k in per[sid] and k not in per[1 - sid]\n"
        "print('SHARDED_OK')\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "1", "-s", "2", "--launcher", "local",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=_cpu_env())
    assert r.returncode == 0, r.stderr + r.stdout
    assert "SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_ps_wire_protocol_is_binary_typed():
    """No pickle anywhere in the PS wire path (VERDICT r3 weak #7: pickled
    frames are arbitrary-code-execution if the port is reachable)."""
    src = open(os.path.join(REPO, "mxnet_tpu", "kvstore",
                            "ps_server.py")).read()
    for needle in ("import pickle", "pickle.loads", "pickle.dumps",
                   "cPickle", "marshal", "eval(", "exec("):
        assert needle not in src, needle
    # optimizer travels as typed JSON config, reconstructed via the
    # registry — round-trip preserves hyper-parameters
    from mxnet_tpu.kvstore.ps_server import (
        _serialize_optimizer_conf, _deserialize_optimizer_conf)
    opt = mx.optimizer.SGD(learning_rate=0.25, momentum=0.9, wd=1e-4)
    back = _deserialize_optimizer_conf(_serialize_optimizer_conf(opt))
    assert type(back).__name__ == "SGD"
    assert back.lr == 0.25 and back.momentum == 0.9 and back.wd == 1e-4
    # a non-data optimizer config is refused, not silently pickled
    bad = mx.optimizer.SGD(learning_rate=0.1)
    bad.weird = object()
    with pytest.raises(mx.MXNetError, match="JSON"):
        _serialize_optimizer_conf(bad)


def test_ps_wire_bfloat16_roundtrip():
    """bf16 (the headline TPU dtype) must survive the binary wire."""
    import numpy as _onp
    import ml_dtypes
    from mxnet_tpu.kvstore.ps_server import _pack_tensor, _unpack_tensor
    a = _onp.arange(6, dtype=_onp.float32).reshape(2, 3) \
        .astype(ml_dtypes.bfloat16)
    back, _ = _unpack_tensor(_pack_tensor(a), 0)
    assert back.dtype == ml_dtypes.bfloat16
    _onp.testing.assert_array_equal(back.astype(_onp.float32),
                                    a.astype(_onp.float32))


def test_launch_ssh_emits_server_role_lines(tmp_path):
    hosts = tmp_path / "hosts"
    hosts.write_text("hostA\nhostB\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "-s", "2", "--launcher", "ssh", "-H", str(hosts),
         "python", "train.py"],
        capture_output=True, text=True, timeout=60, env=_cpu_env())
    assert r.returncode == 0, r.stderr
    assert r.stdout.count("DMLC_ROLE=server") == 2, r.stdout
    assert r.stdout.count("mxnet_tpu.kvstore.ps_server") == 2
    assert r.stdout.count("MXTPU_PS_ADDRS=") == 4   # servers + workers


def test_dist_async_send_command_retunes_server_lr(tmp_path):
    """send_command_to_servers(0, 'lr:x') reaches the server optimizer
    (reference ps-lite kController use)."""
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "kv = mx.kv.create('dist_async')\n"
        "kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))\n"
        "kv.init('w', mx.nd.ones((2,)))\n"
        "kv.push('w', mx.nd.ones((2,)))    # lr 0.1 -> w = 0.9\n"
        "kv.send_command_to_servers(0, 'lr:0.5')\n"
        "kv.push('w', mx.nd.ones((2,)))    # lr 0.5 -> w = 0.4\n"
        "out = mx.nd.zeros((2,))\n"
        "kv.pull('w', out=out)\n"
        "np.testing.assert_allclose(out.asnumpy(), [0.4, 0.4], rtol=1e-5)\n"
        "log = kv._clients[0].command_log()\n"
        "assert log == [[0, 'lr:0.5']], log\n"
        "print('CMD_OK')\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "1", "--launcher", "local", sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=_cpu_env())
    assert r.returncode == 0, r.stderr + r.stdout
    assert "CMD_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_ps_heartbeat_detects_sigkilled_worker(tmp_path):
    """Failure detection (reference ps-lite PS_HEARTBEAT_TIMEOUT,
    SURVEY §5.3): 3 workers beat the server; one is SIGKILLed. The
    server must declare the silent rank dead and log it, dist_async
    push/pull must keep serving the survivors (async degrade), and a
    barrier must abort with a clean MXNetError naming the dead rank
    instead of hanging."""
    import signal
    import socket
    import time
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.kvstore.ps_server import PSServer, PSClient

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    srv = PSServer("127.0.0.1", port, num_workers=3,
                   heartbeat_timeout=1.5)
    c0 = PSClient("127.0.0.1", port)
    c0.start_heartbeat(0, interval=0.3)
    c1 = PSClient("127.0.0.1", port)
    c1.start_heartbeat(1, interval=0.3)
    c0.init("w", np.ones(4, np.float32))

    # rank 2 is a real process we SIGKILL mid-beat
    script = tmp_path / "rank2.py"
    script.write_text(
        "import sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from mxnet_tpu.kvstore.ps_server import PSClient\n"
        f"c = PSClient('127.0.0.1', {port})\n"
        "c.start_heartbeat(2, interval=0.3)\n"
        "print('BEATING', flush=True)\n"
        "time.sleep(120)\n")
    p = subprocess.Popen([sys.executable, str(script)],
                         stdout=subprocess.PIPE, text=True, env=_cpu_env())
    try:
        assert p.stdout.readline().strip() == "BEATING"
        deadline = time.time() + 15
        while time.time() < deadline:
            if "2" in c0.health()["alive"]:
                break
            time.sleep(0.2)
        else:
            raise AssertionError(f"rank 2 never beat: {c0.health()}")

        p.send_signal(signal.SIGKILL)
        p.wait(timeout=10)
        deadline = time.time() + 20
        while time.time() < deadline:
            if c0.health()["dead"] == [2]:
                break
            time.sleep(0.3)
        else:
            raise AssertionError(
                f"rank 2 never declared dead: {c0.health()}")

        # async degrade: survivors keep pushing/pulling
        c1.push("w", np.ones(4, np.float32))
        np.testing.assert_allclose(c0.pull("w"),
                                   2.0 * np.ones(4, np.float32))
        # barrier aborts cleanly, naming the dead rank
        with pytest.raises(MXNetError, match=r"rank\(s\) \[2\]"):
            c0.barrier()
        assert "2" not in c0.health()["alive"]
    finally:
        if p.poll() is None:
            p.kill()
        for c in (c0, c1):
            c.close()
        srv._sock.close()


# ----------------------------------------------------------------------
# tools/telemetry_dump.py (ISSUE 9): flight-dump/snapshot rendering +
# the live PS-server scrape path — tier-1 smoke
# ----------------------------------------------------------------------

def test_telemetry_dump_renders_flight_file(tmp_path):
    """End-to-end: take a real flight-recorder dump in-process, then
    render it with the offline tool in both formats."""
    import json as _json
    from mxnet_tpu import telemetry
    telemetry.inc("train.steps", 7)
    telemetry.set_gauge("elastic.epoch", 2)
    telemetry.observe("train.step_ms", 12.5)
    telemetry.event("unit.test", detail="smoke")
    path = telemetry.dump_flight("unit-test",
                                 path=str(tmp_path / "flight.json"))
    assert path is not None and os.path.exists(path)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_dump.py"),
         "--file", path, "--format=prom", "--events"],
        capture_output=True, text=True, timeout=120, env=_cpu_env())
    assert r.returncode == 0, r.stderr
    assert "mxtpu_train_steps 7" in r.stdout
    assert "# TYPE mxtpu_train_step_ms histogram" in r.stdout
    assert 'reason=' in r.stdout          # flight header line
    # --events appends the ring as JSONL; the last line is our event
    ev = _json.loads(r.stdout.strip().splitlines()[-1])
    assert ev["kind"] == "unit.test" and ev["v"] == 1
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_dump.py"),
         "--file", path, "--format=json"],
        capture_output=True, text=True, timeout=120, env=_cpu_env())
    assert r.returncode == 0, r.stderr
    payload = _json.loads(r.stdout)
    assert payload["reason"] == "unit-test"
    assert payload["metrics"]["counters"]["train.steps"] == 7


def test_telemetry_dump_self_test_prom():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_dump.py"),
         "--self-test", "--format=prom"],
        capture_output=True, text=True, timeout=300, env=_cpu_env())
    assert r.returncode == 0, r.stderr
    assert "mxtpu_selftest_counter 3" in r.stdout
    assert 'mxtpu_selftest_ms_bucket{le="+Inf"} 1' in r.stdout
