"""chip_smoke.py and the rules it stands on: a run that finds no chip
fails, ``mx.tpu()`` never answers with a CPU device, importing the package
takes no chip, and the compile cache is placed from outside.

The rehearsal (toy sizes, CPU backend, Pallas kernels interpreted) is chosen
by an explicit argument; it checks the script's control flow, nothing else.
"""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_no_chip_no_result():
    """On the CPU, without the rehearsal argument: non-zero exit before
    any phase, and no result line."""
    r = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, timeout=120, env=_env(), cwd=REPO)
    assert r.returncode != 0, r.stdout + r.stderr
    assert "platform: cpu" in r.stdout
    assert "no TPU" in r.stderr
    assert "[" not in r.stdout and '"ok"' not in r.stdout, r.stdout


def test_tpu_context_raises_without_a_tpu():
    """``tpu``/``gpu`` mean the TPU backend: on a CPU-only backend they
    raise, naming what JAX did find, instead of handing out cpu:0."""
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(MXNetError, match=r"found \['cpu'\]"):
            ctx.jax_device
    with pytest.raises(MXNetError):
        mx.nd.ones((2,), ctx=mx.tpu(0))
    assert mx.context.num_tpus() == 0
    assert mx.context.default_context() == mx.cpu(0)
    assert mx.cpu(0).jax_device.platform == "cpu"


def test_import_initializes_no_backend():
    """A parent that imports the package must still be able to start a
    child that needs the chip."""
    code = ("import mxnet_tpu as mx\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "mx.nd.random.uniform(shape=(2,)).asnumpy()\n"
            "assert xla_bridge._backends\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=_env(), cwd=REPO)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("outside", [None, "/some/dir"])
def test_compile_cache_is_placed_from_outside(outside, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper touches no setting
    (JAX read the variable itself, at import); unset, the cache goes to
    <checkout>/.jax_cache.  ``jax.config.update`` is recorded, not
    applied: the suite's own process keeps its configuration."""
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    if outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = mx.runtime.enable_compile_cache()
    if outside:
        assert where == outside and calls == []
    else:
        assert where == os.path.join(REPO, ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", where)]


def test_rehearsal_passes(chip_smoke_rehearsal):
    """Every one-chip phase runs to the end at the rehearsal size and says
    what it is.  The process was started with the session (conftest.py)."""
    proc, out = chip_smoke_rehearsal
    rc = proc.wait(timeout=600)
    text = out.read_text()
    assert rc == 0, text[-4000:]
    lines = text.strip().splitlines()
    assert "REHEARSAL" in text
    for phase in ("1 context", "2a resnet", "2b resnet", "3 bert",
                  "4 kernels flash_attention", "4 kernels grouped_matmul",
                  "4 kernels paged_decode_attention", "5 serve"):
        assert f"[{phase}" in text, phase
    # latent attention's shape (Q.K over 192, V at 128) went through too
    assert "(B*H,L,D,Dv)=(2,128,192,128) bf16 causal=True" in text
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
