#!/usr/bin/env python3
"""One ``DataParallelTrainer`` step of the Keye-VL-2.0 language model's tiny
preset (sparse grouped-query attention + softmax top-k experts) on the CPU:
``JAX_PLATFORMS=cpu python examples/keye_vl2_step.py``."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.nlp import keye_vl2
from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

mx.random.seed(0)
net = keye_vl2.keye_vl2_tiny()
net.initialize()
ids = np.random.RandomState(0).randint(0, 128, (8, 33))
tokens, targets = (mx.nd.array(a, dtype="int32")
                   for a in (ids[:, :-1], ids[:, 1:]))
trainer = DataParallelTrainer(net, keye_vl2.causal_lm_loss(), "adam",
                              {"learning_rate": 1e-3})
losses = [float(trainer.step(tokens, targets).asnumpy()) for _ in range(3)]
print("cross-entropy + index loss:", losses)
assert losses[-1] < losses[0]
