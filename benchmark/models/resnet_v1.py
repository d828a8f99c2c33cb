"""ResNet v1 (He et al. 2015) for the benchmark: the model zoo's own network,
seeded synthetic batches, the loss, and the FLOPs of one sample from the layer
shapes.  ``sizes`` is the configuration file, or in a rehearsal the file with
its ``rehearsal`` sizes laid over it."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def build(sizes):
    """What ``gluon.model_zoo.vision.resnet50_v1()`` builds, from the block
    counts and channels the configuration file states (``get_resnet`` looks
    the same two lists up in its own table)."""
    from mxnet_tpu.gluon.model_zoo import vision
    blocks = {"bottleneck": vision.BottleneckV1}
    return vision.ResNetV1(blocks[sizes["block"]], sizes["block_counts"],
                           sizes["channels"], classes=sizes["classes"])


def shape_probe(batch):
    """Two samples of a batch: enough for the one eager forward that resolves
    the deferred parameter shapes."""
    return (batch[0][:2],)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, batch, image, classes):
    kx, ky = jax.random.split(key)
    x = jax.random.uniform(kx, (batch, 3, image, image), jnp.float32,
                           -1.0, 1.0)
    y = jax.random.randint(ky, (batch,), 0, classes)
    return x, y.astype(jnp.float32)


def make_pool(sizes, traffic, batch, pool, seed):
    """``pool`` batches of ``batch`` samples, each made on the device in one
    jitted call from the seed: ``[(images, labels), ...]``."""
    key = jax.random.key(seed)
    return [_draw(jax.random.fold_in(key, i), batch, sizes["image_size"],
                  sizes["classes"]) for i in range(pool)]


def make_loss():
    from mxnet_tpu import gluon
    return gluon.loss.SoftmaxCrossEntropyLoss()


def conv_shapes(sizes):
    """Every convolution and the classifier as ``(c_in, c_out, k, out_hw)``,
    walked the way the network is built: 7x7/2 stem, 3x3/2 max pool, then for
    each stage its bottleneck blocks (1x1 carrying the stage's stride, 3x3,
    1x1, and a 1x1 projection on the first block)."""
    channels, counts = sizes["channels"], sizes["block_counts"]
    hw = (sizes["image_size"] + 2 * 3 - 7) // 2 + 1
    shapes = [(3, channels[0], 7, hw)]
    hw = (hw + 2 * 1 - 3) // 2 + 1
    c_in = channels[0]
    for stage, (n, c_out) in enumerate(zip(counts, channels[1:])):
        mid = c_out // 4
        for block in range(n):
            stride = 2 if (stage > 0 and block == 0) else 1
            hw = (hw - 1) // stride + 1
            shapes += [(c_in, mid, 1, hw), (mid, mid, 3, hw),
                       (mid, c_out, 1, hw)]
            if block == 0:
                shapes.append((c_in, c_out, 1, hw))
            c_in = c_out
    shapes.append((c_in, sizes["classes"], 1, 1))
    return shapes


def flops_per_sample(sizes, traffic):
    """Forward + backward FLOPs of one image: 2 x multiply-accumulates of
    every convolution and the classifier, x 3 (the backward pass costs twice
    the forward).  Recomputation is never counted; BatchNorm, ReLU, pooling
    and the loss are left out (under 1 % of the total)."""
    macs = sum(ci * co * k * k * hw * hw for ci, co, k, hw in
               conv_shapes(sizes))
    return 3 * 2 * macs
