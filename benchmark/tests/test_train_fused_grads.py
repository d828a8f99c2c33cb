"""``runners/train_fused_grads.py`` through ``run.py --rehearse``: tiny sizes
on the CPU, one process a run as on the chip.  The plain run is ``correct``;
with the reference's ``no_experts`` stand-in in the program's place
(``MXTPU_BENCH_CONTROL``) the gradient check refuses it, by the readings of
exactly the leaves the routed experts feed."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")
CELL = "kanana2-30b-a3b-ep8-fused-b2-s4096"


def _rehearse(control):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    env.pop("MXTPU_BENCH_CONTROL", None)
    if control:
        env["MXTPU_BENCH_CONTROL"] = control
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    notes = {line[2:].split(": ", 1)[0]: json.loads(line.split(": ", 1)[1])
             for line in lines if line.startswith("# ")}
    return json.loads(lines[-1]), notes


@pytest.mark.parametrize("control", [None, "no_experts"])
def test_gradient_check_passes_the_program_and_refuses_the_stand_in(control):
    result, notes = _rehearse(control)
    loss = notes["check.first_loss_vs_reference"]
    grads = notes["check.first_gradient_vs_reference"]
    assert result["correct"] is (control is None)
    assert grads["ok"] is (control is None)
    assert loss["ok"]       # the first loss cannot tell: why the check exists
    if control:
        assert "control no_experts in the program's place" in grads["detail"]
        assert grads["detail"].count(" 1.000e+00") == 3
    # a dense layer, then two expert layers that routed something
    rows = notes["routed_rows"]
    assert [len(rows[k]) for k in ("first", "last")] == [3, 3]
    assert rows["first"][0] == 0 and min(rows["first"][1:]) > 0
