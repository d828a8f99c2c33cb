"""BERT encoder with the sentence-pair classifier, forward and loss, written
out plainly in float32 ``jax.numpy``: no model zoo, no amp, no flash kernel.
It follows Devlin et al. 2018 (post-norm encoder, erf GELU, learned
positions); the departures are the configuration's: no dropout, no MLM head.
Parameters come in by the program's names."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dense(params, name, x):
    return x @ params[name + "_weight"].T + params[name + "_bias"]


def _layernorm(params, name, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * params[name + "_gamma"] \
        + params[name + "_beta"]


def logits(params, tokens, types, sizes):
    heads, eps = sizes["num_attention_heads"], sizes["layer_norm_eps"]
    b, seq = tokens.shape
    x = params["word_embed_weight"][tokens] \
        + params["token_type_embed_weight"][types]
    x = x + params["encoder_position_weight"][:seq][None]
    x = _layernorm(params, "encoder_layernorm0", x, eps)
    for i in range(sizes["num_hidden_layers"]):
        cell = f"encoder_cells_layer{i}_"
        att = cell + "multiheadattention0_"

        def split(t):
            return t.reshape(b, seq, heads, -1).transpose(0, 2, 1, 3)

        q = split(_dense(params, att + "query", x))
        k = split(_dense(params, att + "key", x))
        v = split(_dense(params, att + "value", x))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, seq, -1)
        x = _layernorm(params, cell + "layernorm0",
                       x + _dense(params, att + "out", ctx), eps)
        ffn = cell + "_positionwiseffn0_"
        h = jax.nn.gelu(_dense(params, ffn + "ffn1", x), approximate=False)
        x = _layernorm(params, ffn + "layernorm0",
                       x + _dense(params, ffn + "ffn2", h), eps)
    pooled = jnp.tanh(_dense(params, "pooler", x[:, 0]))
    return _dense(params, "classifier", pooled)


def loss(params, batch, sizes):
    """Mean softmax cross-entropy of the batch, in float32 with every matmul
    at full float32 precision."""
    tokens, types, labels = batch
    with jax.default_matmul_precision("highest"):
        params = {k: v.astype(jnp.float32) for k, v in params.items()}
        z = logits(params, tokens, types, sizes)
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(
            logp, labels.astype(jnp.int32)[:, None], axis=-1)
        return -jnp.mean(picked)
