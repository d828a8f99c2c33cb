"""The ZeRO-1 BERT cell through ``run.py --rehearse`` on four virtual CPU
devices (one process, as on the chip): ``correct``, with the trainer on the
sharded path and every parameter the same on all four after the window."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")
CELL = "bert-base-fused-b128-s128-dp4-zero1"


def test_zero1_cell_rehearses_correct_on_the_sharded_path():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("MXTPU_SHARDED_SYNC", None)
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000011",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    notes = {line[2:].split(": ", 1)[0]: json.loads(line.split(": ", 1)[1])
             for line in lines if line.startswith("# ")}
    assert json.loads(lines[-1])["correct"] is True
    assert notes["check.updates_sharded"]["ok"]
    assert "ZeRO-1 on over 4 chips" in notes["check.updates_sharded"]["detail"]
    assert notes["check.replicas_identical"]["ok"]
