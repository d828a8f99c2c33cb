"""Kimi Linear (``gluon.model_zoo.nlp.kimi_linear``) as one chip of an
expert-parallel deployment, for the benchmark: the model zoo's own network,
seeded synthetic batches, the next-token loss, and the FLOPs of one sequence
from the layer shapes.  ``sizes`` is the configuration file, or in a rehearsal
the file with its ``rehearsal`` sizes laid over it.

In the file ``num_experts`` and ``vocab_size`` are what this chip holds;
``published`` has the router's width (and the whole vocabulary, for the
record).  ``linear_attn_config`` is the source's, whole: of its two layer
lists the layers up to ``num_hidden_layers`` are built."""
from __future__ import annotations

from models.deepseek_v3 import make_loss, make_pool, shape_probe  # noqa: F401


def build(sizes):
    from mxnet_tpu.gluon.model_zoo.nlp.kimi_linear import kimi_linear_48b_a3b
    for key, want in (("num_nextn_predict_layers", 0), ("rope_scaling", None),
                      ("q_lora_rank", None), ("num_expert_group", 1),
                      ("topk_group", 1), ("tie_word_embeddings", False),
                      ("moe_layer_freq", 1), ("hidden_act", "silu"),
                      ("moe_router_activation_func", "sigmoid")):
        if sizes[key] != want:
            raise ValueError(f"{key}={sizes[key]!r}: the model zoo's "
                             f"kimi_linear has {want!r} only")
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError("latent attention has a key-value head a head")
    lin = sizes["linear_attn_config"]
    # what the file and the constructor call by the same name, then what the
    # cut renames: the file's num_experts is the experts held here, the
    # router keeps the published width
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "mla_use_nope", "num_experts_per_token", "num_shared_experts",
            "routed_scaling_factor", "moe_renormalize", "rms_norm_eps",
            "expert_offset", "initializer_range",
            "embedding_initializer_range")
    net = kimi_linear_48b_a3b(
        **{key: sizes[key] for key in same},
        num_experts=sizes["published"]["num_experts"],
        experts_held=sizes["num_experts"],
        rope_theta=float(sizes["rope_theta"]),
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]))
    if sizes["remat"]:
        net.model.remat()
    return net


def layer_counts(sizes):
    """``(kda, mla, dense)`` layers among the ``num_hidden_layers`` built."""
    lin, depth = sizes["linear_attn_config"], sizes["num_hidden_layers"]
    return (sum(i <= depth for i in lin["kda_layers"]),
            sum(i <= depth for i in lin["full_attn_layers"]),
            min(sizes["first_k_dense_replace"], depth))


def macs_per_token(sizes, traffic):
    """Forward multiply-accumulates of one token by part, from the layer
    shapes — the mathematics, not what implements it.  A KDA layer: three
    projections, the two low-rank pairs, beta's projection and the output's,
    three convolutions of ``short_conv_kernel_size`` taps, and **the scan at
    the recurrence's own count**, a head and token: the decay of the state
    (dk dv), ``k^T S`` (dk dv), the rank-one write (dk dv + dv for the step
    size's product, counted as dk dv), ``q^T S`` (dk dv) and the three
    vector updates between them — 7 dk dv.  A latent-attention layer as
    ``models/deepseek_v3.py`` counts it (causal: (L + 1) / 2 keys a query).
    The dense SwiGLU, the shared expert, the router over the published
    width, the routed experts at the uniform share (``num_experts_per_tok``
    choices, of which held / routed land here), the head over the
    vocabulary held."""
    d = sizes["hidden_size"]
    lin = sizes["linear_attn_config"]
    hk, dk = lin["num_heads"], lin["head_dim"]
    h = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    kda, mla, dense = layer_counts(sizes)
    moe = sizes["num_hidden_layers"] - dense
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes["published"]["num_experts"]
    keys = (traffic["seq_len"] + 1) / 2
    return {
        "kda_projections": kda * (4 * d * hk * dk + 2 * (d * dk + dk * hk * dk)
                                  + d * hk),
        "kda_conv": kda * 3 * hk * dk * lin["short_conv_kernel_size"],
        "kda_scan": kda * hk * 7 * dk * dk,
        "mla_projections": mla * (
            d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d),
        "mla_scores": mla * h * keys * (nope + rope + dv),
        "dense_mlp": dense * 3 * d * sizes["intermediate_size"],
        "shared_experts": moe * sizes["num_shared_experts"] * expert,
        "router": moe * d * routed,
        "routed_experts": moe * expert * sizes["num_experts_per_token"]
        * sizes["num_experts"] / routed,
        "head": d * sizes["vocab_size"],
    }


def flops_per_sample(sizes, traffic):
    """Forward + backward FLOPs of one sequence: 2 FLOPs a multiply-
    accumulate, the backward twice the forward; embeddings, norms, softmax,
    SiLU and the gates' elementwise passes are left out; recomputation is
    never counted, and the scan is counted at the recurrence's operations
    whatever the chunked form executes."""
    return 3 * 2 * traffic["seq_len"] * \
        sum(macs_per_token(sizes, traffic).values())


def parameter_count(sizes):
    """Parameters this chip holds, from the layer shapes (the file's
    ``deployment`` arithmetic: 602.4 M)."""
    d = sizes["hidden_size"]
    lin = sizes["linear_attn_config"]
    hk, dk = lin["num_heads"], lin["head_dim"]
    h = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    kda, mla, dense = layer_counts(sizes)
    moe = sizes["num_hidden_layers"] - dense
    expert = 3 * d * sizes["moe_intermediate_size"]
    kda_layer = 4 * d * hk * dk + 2 * (d * dk + dk * hk * dk) + d * hk \
        + 3 * hk * dk * lin["short_conv_kernel_size"] + hk + hk * dk + dk
    mla_layer = d * h * (nope + rope) + d * (rank + rope) + rank \
        + rank * h * (nope + dv) + h * dv * d
    # held and shared experts, the router over the published width and its
    # selection bias
    moe_layer = (sizes["num_experts"] + sizes["num_shared_experts"]) * expert \
        + sizes["published"]["num_experts"] * (d + 1)
    return kda * kda_layer + mla * mla_layer \
        + dense * 3 * d * sizes["intermediate_size"] + moe * moe_layer \
        + sizes["num_hidden_layers"] * 2 * d + d \
        + 2 * sizes["vocab_size"] * d
