"""HybridBlock.export / SymbolBlock.imports roundtrip + examples smoke
(reference: tests/python/unittest/test_gluon.py export tests; the examples
are the reference's acceptance surface, SURVEY.md §2.4)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
nd = mx.nd


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_SYNTHETIC_DATA"] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [REPO])
    return env


def test_export_import_roundtrip(tmp_path):
    # export saves FULL param names (arg:dense0_weight ...), so the
    # reloading net must use the same name prefixes — reference semantics
    # (load_parameters of an export'd file needs matching prefixes;
    # structural matching is save_parameters' job)
    def build(prefix):
        net = gluon.nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(gluon.nn.Dense(8, activation="relu"))
            net.add(gluon.nn.Dense(3))
        return net

    net = build("m_")
    net.initialize()
    net.hybridize()
    x = nd.random.uniform(shape=(2, 5))
    ref = net(x).asnumpy()
    prefix = str(tmp_path / "model")
    net.export(prefix)
    files = os.listdir(tmp_path)
    assert any(f.endswith(".params") for f in files), files
    assert any(f.endswith("-symbol.json") for f in files), files
    net2 = build("m_")
    param_file = [f for f in files if f.endswith(".params")][0]
    net2.load_parameters(str(tmp_path / param_file))
    np.testing.assert_allclose(net2(x).asnumpy(), ref, rtol=1e-5)


def _run_example(name, *args, timeout=420):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name), *args],
        capture_output=True, text=True, timeout=timeout, env=_cpu_env())
    assert r.returncode == 0, f"{name} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_example_image_classification():
    out = _run_example("image_classification.py", "--num-epochs", "2")
    assert "final validation" in out


@pytest.mark.slow
def test_example_dcgan():
    out = _run_example("dcgan.py", "--num-iters", "5")
    assert "ok" in out


@pytest.mark.slow
def test_example_sparse_fm():
    out = _run_example("sparse_factorization_machine.py")
    assert "ok" in out
