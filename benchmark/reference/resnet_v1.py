"""ResNet v1 bottleneck network, forward and loss, written out plainly in
float32 ``jax.numpy``: no model zoo, no amp, no kernels.  It follows He et al.
2015 (Table 1) as MXNet's model zoo builds it; the departures from the paper
are MXNet's: the stage's stride on the first 1x1 convolution, and a bias on
the body's 1x1 convolutions.  Parameters come in by the program's names."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _batchnorm(x, gamma, beta):
    """Training mode: the batch's own mean and (biased) variance."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=(0, 2, 3), keepdims=True)
    return (x - mean) * lax.rsqrt(var + EPS) * gamma.reshape(1, -1, 1, 1) \
        + beta.reshape(1, -1, 1, 1)


def logits(params, images, sizes):
    def conv_bn(x, scope, i_conv, i_bn, stride, pad):
        y = _conv(x, params[f"{scope}conv2d{i_conv}_weight"], stride, pad)
        bias = params.get(f"{scope}conv2d{i_conv}_bias")
        if bias is not None:
            y = y + bias.reshape(1, -1, 1, 1)
        return _batchnorm(y, params[f"{scope}batchnorm{i_bn}_gamma"],
                          params[f"{scope}batchnorm{i_bn}_beta"])

    x = jax.nn.relu(conv_bn(images, "", 0, 0, 2, 3))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, n in enumerate(sizes["block_counts"], start=1):
        scope, i = f"stage{stage}_", 0
        for block in range(n):
            stride = 2 if (stage > 1 and block == 0) else 1
            y = jax.nn.relu(conv_bn(x, scope, i, i, stride, 0))
            y = jax.nn.relu(conv_bn(y, scope, i + 1, i + 1, 1, 1))
            y = conv_bn(y, scope, i + 2, i + 2, 1, 0)
            i += 3
            if block == 0:
                x = conv_bn(x, scope, i, i, stride, 0)
                i += 1
            x = jax.nn.relu(y + x)
    x = jnp.mean(x, axis=(2, 3))
    return x @ params["dense0_weight"].T + params["dense0_bias"]


def loss(params, batch, sizes):
    """Mean softmax cross-entropy of the batch, in float32 with every matmul
    and convolution at full float32 precision."""
    images, labels = batch
    with jax.default_matmul_precision("highest"):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        z = logits(params, images.astype(jnp.float32), sizes)
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(
            logp, labels.astype(jnp.int32)[:, None], axis=-1)
        return -jnp.mean(picked)
