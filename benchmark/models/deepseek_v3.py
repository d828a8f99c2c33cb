"""DeepSeek-V3-style causal LM (``gluon.model_zoo.nlp.deepseek_v3``) as one
chip of an expert-parallel deployment, for the benchmark: the model zoo's own
network, seeded synthetic batches, the next-token loss, and the FLOPs of one
sequence from the layer shapes.  ``sizes`` is the configuration file, or in a
rehearsal the file with its ``rehearsal`` sizes laid over it.

In the file ``n_routed_experts`` and ``vocab_size`` are what this chip holds;
``published`` has the router's width (and the whole vocabulary, for the
record)."""
from __future__ import annotations

import functools

import jax


def build(sizes):
    from mxnet_tpu.gluon.model_zoo.nlp.deepseek_v3 import kanana_2_30b_a3b
    for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                      ("moe_layer_freq", 1), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if sizes[key] != want:
            raise ValueError(f"{key}={sizes[key]!r}: the model zoo's "
                             f"deepseek_v3 has {want!r} only")
    # what the file and the constructor call by the same name, then the two
    # that the cut renames: the file's n_routed_experts is the experts held
    # here, the router keeps the published width
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "expert_offset", "initializer_range")
    net = kanana_2_30b_a3b(
        **{key: sizes[key] for key in same},
        n_routed_experts=sizes["published"]["n_routed_experts"],
        experts_held=sizes["n_routed_experts"],
        rope_theta=float(sizes["rope_theta"]))
    if sizes["remat"]:
        net.model.remat()
    return net


def shape_probe(batch):
    tokens, _ = batch
    return (tokens[:2, :128],)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, batch, seq, vocab):
    ids = jax.random.randint(key, (batch, seq + 1), 0, vocab)
    return ids[:, :-1], ids[:, 1:]


def make_pool(sizes, traffic, batch, pool, seed):
    """``pool`` batches of ``batch`` full-length sequences, each made on the
    device in one jitted call from the seed: ``[(tokens, targets), ...]``,
    ids uniform over the vocabulary slice held, targets the ids shifted by
    one."""
    key = jax.random.key(seed)
    return [_draw(jax.random.fold_in(key, i), batch, traffic["seq_len"],
                  sizes["vocab_size"]) for i in range(pool)]


def make_loss():
    from mxnet_tpu import gluon
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    # logits (B, T, V) against targets (B, T): mean over every position.
    # The head's logits are bfloat16 under amp; the log-softmax over the
    # vocabulary and the mean are taken in float32, as anyone training a
    # language model does (a bfloat16 loss near 10 moves in steps of 0.06)
    return lambda logits, targets: ce(logits.astype("float32"), targets)


def macs_per_token(sizes, traffic):
    """Forward multiply-accumulates of one token by part, from the layer
    shapes: the projections of latent attention, its scores (causal: a query
    sees (L + 1) / 2 keys on average, Q.K over nope + rope and P.V over v),
    the dense layers' SwiGLU, the shared experts, the router, the routed
    experts at the uniform share (``num_experts_per_tok`` choices, of which
    held / routed land here), and the head over the vocabulary held."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    layers, dense = sizes["num_hidden_layers"], sizes["first_k_dense_replace"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes["published"]["n_routed_experts"]
    keys = (traffic["seq_len"] + 1) / 2
    return {
        "mla_projections": layers * (
            d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d),
        "mla_scores": layers * h * keys * (nope + rope + dv),
        "dense_mlp": dense * 3 * d * sizes["intermediate_size"],
        "shared_experts": (layers - dense) * sizes["n_shared_experts"]
        * expert,
        "router": (layers - dense) * d * routed,
        "routed_experts": (layers - dense) * expert
        * sizes["num_experts_per_tok"] * sizes["n_routed_experts"] / routed,
        "head": d * sizes["vocab_size"],
    }


def flops_per_sample(sizes, traffic):
    """Forward + backward FLOPs of one sequence: 2 FLOPs a multiply-
    accumulate, the backward twice the forward; embeddings, norms, softmax
    and RoPE are left out (under 1 %); recomputation is never counted."""
    return 3 * 2 * traffic["seq_len"] * \
        sum(macs_per_token(sizes, traffic).values())
