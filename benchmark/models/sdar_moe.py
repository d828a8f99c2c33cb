"""SDAR's mixture-of-experts decoder (``gluon.model_zoo.nlp.sdar_moe``) as one
chip of an expert-parallel deployment, trained as a block-diffusion language
model, for the benchmark: the model zoo's own network, seeded synthetic
training rows ``x_t ⊕ x_0`` with their noise, the masked-denoising loss, and
the FLOPs of one row from the layer shapes.  ``sizes`` is the configuration
file, or in a rehearsal the file with its ``rehearsal`` sizes laid over it.

In the file ``num_experts`` and ``vocab_size`` are what this chip holds;
``published`` has the router's width (and the whole vocabulary, for the
record).  The slice's last row is the mask token (``mask_token_id``); the
data's ids are the other rows."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from readers.block_diffusion import visible_pairs


def build(sizes):
    from mxnet_tpu.gluon.model_zoo.nlp.sdar_moe import sdar_moe_30b_a3b
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False), ("hidden_act", "silu"),
                      ("attention_bias", False), ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        if sizes[key] != want:
            raise ValueError(f"{key}={sizes[key]!r}: the model zoo's "
                             f"sdar_moe has {want!r} only")
    if sizes["mask_token_id"] != sizes["vocab_size"] - 1:
        raise ValueError("the mask token is the slice's last row")
    # what the file and the constructor call by the same name, then what the
    # cut renames: the file's num_experts is the experts held here, the
    # router keeps the published width
    same = ("vocab_size", "hidden_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "block_length", "expert_offset",
            "initializer_range", "embedding_initializer_range",
            "moe_fixed_rows")
    net = sdar_moe_30b_a3b(
        **{key: sizes[key] for key in same},
        num_experts=sizes["published"]["num_experts"],
        experts_held=sizes["num_experts"],
        rope_theta=float(sizes["rope_theta"]))
    if sizes["remat"]:
        net.model.remat()
    return net


def shape_probe(batch):
    tokens, _ = batch
    return (tokens[:2, :256],)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _draw(key, batch, seq, mask_id, block, t_min, t_max):
    ids, noise, coins = jax.random.split(key, 3)
    x0 = jax.random.randint(ids, (batch, seq), 0, mask_id)
    t = jnp.repeat(jax.random.uniform(noise, (batch, seq // block),
                                      minval=t_min, maxval=t_max),
                   block, axis=1)
    masked = jax.random.uniform(coins, (batch, seq)) < t
    tokens = jnp.concatenate([jnp.where(masked, mask_id, x0), x0], axis=1)
    label = jnp.stack([x0.astype(jnp.float32),
                       jnp.where(masked, 1.0 / t, 0.0)], axis=1)
    return tokens.astype(jnp.int32), label


def make_pool(sizes, traffic, batch, pool, seed):
    """``pool`` batches of ``batch`` training rows, each made on the device
    in one jitted call from the seed: ``[(tokens, label), ...]``.  ``x_0``
    uniform over the slice's rows but the mask token's; each block of
    ``block_length`` draws its ``t`` uniform on the noise range, each token
    of it is masked with probability ``t``; tokens ``x_t ⊕ x_0`` (B, 2L)
    int32, label (B, 2, L) float32: the clean ids, and ``1 / t`` where a
    token was masked, 0 where not."""
    key = jax.random.key(seed)
    noise = sizes["noise"]
    return [_draw(jax.random.fold_in(key, i), batch, traffic["seq_len"],
                  sizes["mask_token_id"], sizes["block_length"],
                  noise["t_min"], noise["t_max"]) for i in range(pool)]


def make_loss():
    from mxnet_tpu.gluon.model_zoo.nlp.sdar_moe import block_diffusion_loss
    return block_diffusion_loss()


def macs_per_sample(sizes, traffic):
    """Forward multiply-accumulates of one training row (``x_t ⊕ x_0``: 2L
    tokens through every layer) by part, from the layer shapes — the
    mathematics, not what implements it: attention at the visible pairs of
    both halves (``visible_pairs``; Q.K and P.V over ``head_dim``), the
    experts at the uniform share of the routed rows
    (``num_experts_per_tok`` choices, of which held / routed land here),
    the head over the noisy half's L positions."""
    seq = traffic["seq_len"]
    tokens = 2 * seq
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    hkv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    layers = sizes["num_hidden_layers"]
    routed = sizes["published"]["num_experts"]
    return {
        "projections": layers * tokens * (2 * d * h * hd + 2 * d * hkv * hd),
        "attention": layers * 2 * visible_pairs(seq, sizes["block_length"])
        * h * 2 * hd,
        "router": layers * tokens * d * routed,
        "experts": layers * tokens * 3 * d * sizes["moe_intermediate_size"]
        * sizes["num_experts_per_tok"] * sizes["num_experts"] / routed,
        "head": seq * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes, traffic):
    """Forward + backward FLOPs of one training row: 2 FLOPs a multiply-
    accumulate, the backward twice the forward; embeddings, norms, softmax,
    rotary and the loss's log-softmax are left out; recomputation is never
    counted, and neither is a masked pair, whatever the kernels execute."""
    return 3 * 2 * sum(macs_per_sample(sizes, traffic).values())
