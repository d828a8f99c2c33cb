#!/usr/bin/env python3
"""Three ``DataParallelTrainer`` steps of Kimi Linear's tiny preset (Kimi
Delta Attention as a chunked scan in three layers of four, latent attention
without positions in the other, sigmoid top-k experts with a shared one) on
the CPU: ``JAX_PLATFORMS=cpu python examples/kimi_linear_step.py``."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo.nlp import kimi_linear
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

mx.random.seed(0)
net = kimi_linear.kimi_linear_tiny()
net.initialize()
ids = np.random.RandomState(0).randint(0, 128, (8, 65))
tokens, targets = (mx.nd.array(a, dtype="int32")
                   for a in (ids[:, :-1], ids[:, 1:]))
net(tokens[:1, :16])            # the deferred shapes
net.model.remat()
ce = gluon.loss.SoftmaxCrossEntropyLoss()
trainer = DataParallelTrainer(
    net, lambda logits, y: ce(logits.astype("float32"), y), "adam",
    {"learning_rate": 1e-3})
losses = [float(trainer.step(tokens, targets).asnumpy()) for _ in range(3)]
print("cross-entropy:", losses)
assert losses[-1] < losses[0]
