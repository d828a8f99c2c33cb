"""Tier-1's entry to ``benchmark/tests/``: the CPU tests of the harness that
decides every PR.  Each test of every file there is collected here once,
under its own name, so a test a ``benchmark`` PR adds is held to from the
run after it; nothing under ``benchmark/`` knows of this module.  The two
marks below are tier-1's, not the benchmark's."""
import glob
import importlib.util
import os

import pytest

import conftest

for _path in sorted(glob.glob(os.path.join(
        conftest.repo_root, "benchmark", "tests", "test_*.py"))):
    _spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + os.path.basename(_path)[:-3], _path)
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    for _name, _test in vars(_module).items():
        if _name.startswith("test_"):
            assert _name not in globals(), f"two benchmark tests named {_name}"
            globals()[_name] = _test

pytest.mark.xfail(
    strict=True, reason="`PERF.md` §7 (5): a `benchmark` PR's to fix")(
    test_manifest_accepts_the_three_cells_and_the_prepared_fourth)  # noqa: F821

_gradient_check = \
    test_gradient_check_passes_the_program_and_refuses_the_stand_in  # noqa: F821


# the plain case takes 24.5 s alone on the CPU sandbox, over conftest's 20 s
# guard; its stand-in twin (12.7 s) runs
@pytest.mark.parametrize("control", [
    pytest.param(None, marks=pytest.mark.slow), "no_experts"])
def test_gradient_check_passes_the_program_and_refuses_the_stand_in(control):
    _gradient_check(control)


# it asserts that keye's configuration and cell are the last of their lists,
# which held until the next configuration was appended (PR 35); the file is
# the benchmark's, so the repair is a `benchmark` PR's: `PERF.md` §7
pytest.mark.xfail(
    strict=True, reason="`PERF.md` §7: a `benchmark` PR's to fix")(
    test_manifest_accepts_the_new_configuration_and_cell)  # noqa: F821

_kimi_gradient_check = \
    test_kimi_gradient_check_passes_the_program_and_refuses_no_delta  # noqa: F821


# a whole rehearsal of the kimi cell in a process of its own takes 35 s on the
# CPU sandbox (an op at a time through five kinds of layer, then the step),
# over conftest's 20 s guard either way; the same comparison runs in-process
# in test_kimi_control_is_refused_at_the_rehearsal_size
@pytest.mark.slow
@pytest.mark.parametrize("control", [None, "no_delta"])
def test_kimi_gradient_check_passes_the_program_and_refuses_no_delta(control):
    _kimi_gradient_check(control)

_language_cells = \
    test_the_language_cells_that_were_there_are_as_they_were  # noqa: F821


# the kimi file's test held that the four lists the kimi cell joined end
# with it, which held until the next language cell was appended to two of
# them; the file is the benchmark's, so tier-1 holds the rest of it here,
# unchanged, and of the lists that they keep the workloads' order and hold
# the kimi cell, until a `benchmark` PR compares by name (`PERF.md` §7)
def test_the_language_cells_that_were_there_are_as_they_were(
        config, reduced, cell, own, shared):
    manifest = _language_cells.__globals__["manifest"]
    kimi = _language_cells.__globals__["CELL"]
    man = manifest.Manifest().validate()
    entry = man.configs[config]
    assert entry["reduced"] == reduced
    assert entry["file"] == f"benchmark/configs/{config}.json"
    assert man.workloads[cell]["config"] == config
    got = man.cell(cell)
    assert got.chips == 1 and got.traffic["runner"] == "train_fused_grads"
    names = [m["name"] for m in got.layer_metrics]
    assert names[:3] == ["train.host_ms", "device.idle_pct", "device.mfu_pct"]
    if config.startswith("keye"):
        assert names[3:5] == own
        assert set(names[5:]) == {m for m in man.per_layer
                                  if m.startswith(shared)}
        assert all(man.per_layer[m]["workloads"] == [cell]
                   for m in names[5:])
    else:
        assert names[3:] == own
    for name in names[3:]:
        assert cell in man.per_layer[name]["workloads"]
    cells = list(man.workloads)
    for name in ("kernel.mla_flash_fwd_ms", "kernel.mla_flash_fwd_roofline",
                 "kernel.moe_gmm_ms", "kernel.moe_gmm_roofline"):
        listed = man.per_layer[name]["workloads"]
        assert listed == sorted(listed, key=cells.index) and kimi in listed
    assert man.per_layer["kernel.moe_gmm_ms"]["workloads"][:3] == [
        "kanana2-30b-a3b-ep8-fused-b2-s4096",
        "keye-vl2-30b-a3b-ep8-fused-b1-s16384", kimi]
    assert len(man.workloads[cell]["why"]) <= 200
    assert len(entry["why"]) <= 200


test_the_language_cells_that_were_there_are_as_they_were.pytestmark = \
    _language_cells.pytestmark
