"""BERT encoder with the sentence-pair classifier, for the benchmark: the
model zoo's own network, seeded synthetic batches, the loss, and the FLOPs of
one sample from the layer shapes.  ``sizes`` is the configuration file, or in
a rehearsal the file with its ``rehearsal`` sizes laid over it."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def build(sizes):
    from mxnet_tpu.gluon.model_zoo.nlp.bert import get_bert_model
    if sizes["hidden_dropout_prob"] != sizes["attention_probs_dropout_prob"]:
        raise ValueError("the model zoo's BERT takes one dropout rate")
    return get_bert_model(
        sizes["num_hidden_layers"], sizes["hidden_size"],
        sizes["intermediate_size"], sizes["num_attention_heads"],
        vocab_size=sizes["vocab_size"],
        max_length=sizes["max_position_embeddings"],
        dropout=sizes["hidden_dropout_prob"], use_flash=sizes["use_flash"],
        use_decoder=False)


def shape_probe(batch):
    tokens, types, _ = batch
    return tokens[:2], types[:2]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, batch, seq, vocab, labels):
    kx, ky = jax.random.split(key)
    tokens = jax.random.randint(kx, (batch, seq), 0, vocab)
    label = jax.random.randint(ky, (batch,), 0, labels)
    return tokens, jnp.zeros_like(tokens), label


def make_pool(sizes, traffic, batch, pool, seed):
    """``pool`` batches of ``batch`` full-length sequences, each made on the
    device in one jitted call from the seed:
    ``[(tokens, token_types, labels), ...]``."""
    key = jax.random.key(seed)
    return [_draw(jax.random.fold_in(key, i), batch, traffic["seq_len"],
                  sizes["vocab_size"], sizes["num_labels"])
            for i in range(pool)]


def make_loss():
    from mxnet_tpu import gluon
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    # the network returns (sequence, pooled, classifier scores)
    return lambda out, label: ce(out[-1], label)


def flops_per_sample(sizes, traffic):
    """Forward + backward FLOPs of one sequence: the encoder's matrix
    multiplications (the arithmetic of ``costmodel.bert_train_flops_per_
    sample``, which is sound).  Multiply-accumulates per token and layer:
    4 d^2 for the Q, K, V and output projections, 2 d ff for the FFN,
    2 L d for QK^T and PV; x 2 FLOPs x 3 for forward + backward.
    Embeddings, LayerNorm, softmax, pooler and classifier are left out
    (under 1 %); recomputation is never counted."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    seq = traffic["seq_len"]
    per_token = sizes["num_hidden_layers"] * (
        4 * d * d + 2 * d * ff + 2 * seq * d)
    return 3 * 2 * per_token * seq
