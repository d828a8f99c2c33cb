"""The language model of Keye-VL-2.0 (``gluon.model_zoo.nlp.keye_vl2``) as one
chip of an expert-parallel deployment, for the benchmark: the model zoo's own
network, seeded synthetic batches of text, the next-token loss with the
indexer's alignment loss, and the FLOPs of one sequence from the layer
shapes.  ``sizes`` is the configuration file, or in a rehearsal the file with
its ``rehearsal`` sizes laid over it.

In the file ``num_experts`` and ``vocab_size`` are what this chip holds;
``published`` has the router's width (and the whole vocabulary, for the
record)."""
from __future__ import annotations

import numpy as np

from models.deepseek_v3 import make_pool, shape_probe     # noqa: F401


def build(sizes):
    from mxnet_tpu.gluon.model_zoo.nlp.keye_vl2 import keye_vl2_30b_a3b
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False), ("hidden_act", "silu"),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if sizes[key] != want:
            raise ValueError(f"{key}={sizes[key]!r}: the model zoo's "
                             f"keye_vl2 has {want!r} only")
    sa = sizes["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key head")
    # what the file and the constructor call by the same name, then what the
    # cut renames: the file's num_experts is the experts held here, the
    # router keeps the published width
    same = ("vocab_size", "hidden_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "expert_offset",
            "initializer_range", "embedding_initializer_range")
    net = keye_vl2_30b_a3b(
        **{key: sizes[key] for key in same},
        num_experts=sizes["published"]["num_experts"],
        experts_held=sizes["num_experts"],
        rope_theta=float(sizes["rope_theta"]),
        mrope_section=tuple(sizes["rope_scaling"]["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"])
    if sizes["remat"]:
        net.model.remat()
    return net


def make_loss():
    from mxnet_tpu.gluon.model_zoo.nlp.keye_vl2 import causal_lm_loss
    # (logits, index_loss) against targets (B, T): the mean cross-entropy
    # over every position, the log-softmax in float32, plus L_I
    return causal_lm_loss()


def selected_pairs(seq, topk):
    """``sum_t min(t + 1, topk)``: the (query, key) pairs of one sequence
    that the selection keeps."""
    return int(np.minimum(np.arange(1, seq + 1), topk).sum())


def macs_per_sequence(sizes, traffic):
    """Forward multiply-accumulates of one sequence by part, from the layer
    shapes — the mathematics, not what implements it: attention at the pairs
    the selection keeps (Q.K and P.V over ``head_dim``), the indexer's
    scores at the causal half, ``L_I``'s target one accumulate a head a
    kept pair (the mean of the probabilities over the heads), the experts
    at the uniform share (``num_experts_per_tok`` choices, of which held /
    routed land here), and the head over the vocabulary held."""
    seq = traffic["seq_len"]
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    hkv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    sa = sizes["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layers = sizes["num_hidden_layers"]
    routed = sizes["published"]["num_experts"]
    kept = selected_pairs(seq, sa["topk"])
    return {
        "projections": layers * seq * (2 * d * h * hd + 2 * d * hkv * hd),
        "indexer_projections": layers * seq * d * (hi * di + di + hi),
        "index_scores": layers * (seq * (seq + 1) // 2) * hi * di,
        "attention": layers * kept * h * 2 * hd,
        "index_loss_target": layers * kept * h,
        "router": layers * seq * d * routed,
        "experts": layers * seq * 3 * d * sizes["moe_intermediate_size"]
        * sizes["num_experts_per_tok"] * sizes["num_experts"] / routed,
        "head": seq * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes, traffic):
    """Forward + backward FLOPs of one sequence: 2 FLOPs a multiply-
    accumulate, the backward twice the forward; embeddings, norms, softmax,
    RoPE and the selection's compare-and-count passes are left out;
    recomputation is never counted, and neither is a causal pair that the
    selection drops, whatever the kernels execute."""
    return 3 * 2 * sum(macs_per_sequence(sizes, traffic).values())
