"""Debug/determinism switches (SURVEY §5.2, §5.6): MXTPU_DEBUG_NANS names
the failing op; MXTPU_ENFORCE_DETERMINISM makes two seeded runs
bit-identical end-to-end (sampler order + augmenters + init + updates).

Both flags are read at import, so each scenario runs in a subprocess."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("MXTPU_", "JAX_DEBUG"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [REPO])
    env.update(extra)
    return env


def _run(code, **extra):
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=_env(**extra))


def test_debug_nans_names_forward_op():
    r = _run(
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd\n"
        "nd.log(nd.array([-1.0])).asnumpy()\n",
        MXTPU_DEBUG_NANS="1")
    assert r.returncode != 0
    assert "MXNetError" in r.stderr
    assert "log" in r.stderr and "MXTPU_DEBUG_NANS" in r.stderr


def test_debug_nans_names_backward_op():
    # forward is finite, backward of sqrt at 0 is inf -> must name the op.
    # inf-checking is a separate opt-in (models carry intentional -inf in
    # attention masks), hence MXTPU_DEBUG_INFS here.
    r = _run(
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd, autograd\n"
        "x = nd.array([0.0]); x.attach_grad()\n"
        "with autograd.record():\n"
        "    y = nd.sqrt(x)\n"
        "y.backward()\n",
        MXTPU_DEBUG_INFS="1")
    assert r.returncode != 0
    assert "MXNetError" in r.stderr
    assert "sqrt" in r.stderr and "MXTPU_DEBUG_NANS" in r.stderr


def test_debug_nans_off_by_default():
    r = _run(
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd\n"
        "import numpy as np\n"
        "v = nd.log(nd.array([-1.0])).asnumpy()\n"
        "assert np.isnan(v).all()\n")
    assert r.returncode == 0, r.stderr


_DET_SCRIPT = """
import hashlib
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data.vision import transforms

mx.random.seed(7)

class Tiny(gluon.data.Dataset):
    def __init__(self):
        rng = np.random.RandomState(0)
        self._x = rng.rand(48, 8, 8, 1).astype(np.float32)
        self._y = rng.randint(0, 4, size=(48,))
    def __len__(self):
        return len(self._x)
    def __getitem__(self, i):
        return self._t(nd.array(self._x[i])), self._y[i]

t = transforms.Compose([transforms.RandomFlipLeftRight(),
                        transforms.ToTensor()])
ds = Tiny(); ds._t = t
loader = gluon.data.DataLoader(ds, batch_size=8, shuffle=True,
                               num_workers=2)
net = nn.Sequential()
net.add(nn.Flatten(), nn.Dense(16, activation="relu"), nn.Dense(4))
net.initialize(init=mx.init.Xavier())
trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
for epoch in range(2):
    for data, label in loader:
        with autograd.record():
            loss = loss_fn(net(data), nd.array(label))
        loss.backward()
        trainer.step(8)
h = hashlib.sha256()
for k in sorted(net.collect_params()):
    h.update(net.collect_params()[k].data().asnumpy().tobytes())
print("PARAMS", h.hexdigest())
"""


def test_enforce_determinism_two_runs_bit_identical():
    outs = []
    for _ in range(2):
        r = _run(_DET_SCRIPT, MXTPU_ENFORCE_DETERMINISM="1")
        assert r.returncode == 0, r.stderr
        line = [l for l in r.stdout.splitlines() if l.startswith("PARAMS")]
        assert line, r.stdout
        outs.append(line[0])
    assert outs[0] == outs[1]


def test_mxtpu_seed_env_seeds_global_rng():
    code = ("import mxnet_tpu as mx\n"
            "from mxnet_tpu import nd\n"
            "print('V', nd.random.uniform(shape=(3,)).asnumpy().tolist())\n")
    r1 = _run(code, MXTPU_SEED="123")
    r2 = _run(code, MXTPU_SEED="123")
    r3 = _run(code, MXTPU_SEED="124")
    assert r1.returncode == r2.returncode == r3.returncode == 0, \
        r1.stderr + r2.stderr + r3.stderr
    assert r1.stdout == r2.stdout
    assert r1.stdout != r3.stdout


def test_debug_nans_tolerates_intentional_neg_inf():
    # attention masking uses -inf; NaN-mode alone must not flag it
    r = _run(
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd\n"
        "import jax.numpy as jnp\n"
        "s = nd.array([[1.0, 2.0], [3.0, 4.0]])\n"
        "m = nd.array([[1.0, 0.0], [1.0, 1.0]])\n"
        "masked = nd.where(m, s, nd.full((2, 2), -jnp.inf))\n"
        "out = nd.softmax(masked).asnumpy()\n"
        "assert out[0, 1] == 0.0\n",
        MXTPU_DEBUG_NANS="1")
    assert r.returncode == 0, r.stderr
