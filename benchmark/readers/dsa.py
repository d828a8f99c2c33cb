"""The sparse-attention kernels (``mxnet_tpu/ops/sparse_attention.py`` and the
masked forms of the flash kernels): device time from the trace, operations
and bytes from the shapes — the work the mathematics needs, the same whatever
implements it.  A kernel that streams every causal block and masks by the
selection executes (L + 1) / 2 keys a query where these functions count
``min(t + 1, topk)``: at L = 16384 and topk 2048 it can therefore read at
most 23.4 % of its roofline, and that gap is the cell's headroom.  Where the
trace has no such operation (a program without the kernel, as the parent of
the PR that brought these) the readers return ``None`` and the metric is
left out."""

from readers import roofline

ATTN_FWD = "mxtpu_dsa_attn_fwd"
ATTN_BWD = "mxtpu_dsa_attn_bwd"
INDEX = "mxtpu_dsa_index_select"
INDEX_LOSS = "mxtpu_dsa_align_loss"
_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _shapes(sizes, traffic):
    sa = sizes["sa_config"]
    seq, batch = traffic["seq_len"], traffic["per_chip_batch"]
    kept = batch * sum(min(t + 1, sa["topk"]) for t in range(seq))
    return dict(
        seq=seq, batch=batch, kept=kept, causal=batch * seq * (seq + 1) // 2,
        h=sizes["num_attention_heads"], hkv=sizes["num_key_value_heads"],
        d=sizes["head_dim"], hi=sa["indexer_num_heads"],
        di=sa["indexer_head_dim"], item=_ITEMSIZE[sizes["dtype"]])


def _qkvo_bytes(s, passes_q, passes_kv):
    """Arrays of the queries' shape and of the key-value heads' shape, each
    moved once, the float32 log-sum-exp, and the selection as one bit a
    causal pair."""
    tokens = s["batch"] * s["seq"]
    return tokens * s["d"] * s["item"] * (passes_q * s["h"]
                                          + passes_kv * s["hkv"]) \
        + tokens * s["h"] * 4 + s["causal"] // 8


def dsa_attn_fwd_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's forward: Q.K and P.V over
    ``head_dim`` for every head at the pairs the selection keeps; q and o
    once at the query heads, k and v once at the key-value heads, the
    log-sum-exp, the selection."""
    s = _shapes(sizes, traffic)
    return 2 * s["h"] * 2 * s["d"] * s["kept"], _qkvo_bytes(s, 2, 2)


def dsa_attn_bwd_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's backward: five products over the
    same pairs (S, dP, dV, dK, dQ); q, o, dO in and dQ out at the query
    heads, k, v in and dK, dV out at the key-value heads."""
    s = _shapes(sizes, traffic)
    return 2 * s["h"] * 5 * s["d"] * s["kept"], _qkvo_bytes(s, 4, 4)


def dsa_index_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's index scores over the causal half;
    the index queries, the one key head and the float32 weights in, the
    selection, the threshold and the scores' log-sum-exp out."""
    s = _shapes(sizes, traffic)
    tokens = s["batch"] * s["seq"]
    return 2 * s["hi"] * s["di"] * s["causal"], \
        tokens * (s["hi"] + 1) * s["di"] * s["item"] \
        + tokens * (s["hi"] + 2) * 4 + s["causal"] // 8


def dsa_index_loss_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's alignment loss with the indexer's
    gradients, at the pairs the selection keeps: the heads' Q.K for the
    target, the index scores, and the two products of their backward (for
    the index queries and for the index key); q, k, the log-sum-exp and the
    indexer's operands in, their gradients out in float32."""
    s = _shapes(sizes, traffic)
    tokens = s["batch"] * s["seq"]
    flops = 2 * s["kept"] * (s["h"] * s["d"] + 3 * s["hi"] * s["di"])
    nbytes = tokens * s["d"] * s["item"] * (s["h"] + s["hkv"]) \
        + tokens * s["h"] * 4 + s["causal"] // 8 \
        + tokens * ((s["hi"] + 1) * s["di"] * (s["item"] + 4)
                    + s["hi"] * 8 + 8)
    return flops, nbytes


def dsa_attn_fwd_ms(ctx):
    return roofline.ms_per_step(ctx, ATTN_FWD)


def dsa_attn_fwd_roofline(ctx):
    return roofline.roofline_pct(ctx, "kernel.dsa_attn_fwd_roofline",
                                 ATTN_FWD, dsa_attn_fwd_cost)


def dsa_attn_bwd_ms(ctx):
    return roofline.ms_per_step(ctx, ATTN_BWD)


def dsa_attn_bwd_roofline(ctx):
    return roofline.roofline_pct(ctx, "kernel.dsa_attn_bwd_roofline",
                                 ATTN_BWD, dsa_attn_bwd_cost)


def dsa_index_ms(ctx):
    return roofline.ms_per_step(ctx, INDEX)


def dsa_index_roofline(ctx):
    return roofline.roofline_pct(ctx, "kernel.dsa_index_roofline", INDEX,
                                 dsa_index_cost)


def dsa_index_loss_ms(ctx):
    return roofline.ms_per_step(ctx, INDEX_LOSS)


def dsa_index_loss_roofline(ctx):
    return roofline.roofline_pct(ctx, "kernel.dsa_index_loss_roofline",
                                 INDEX_LOSS, dsa_index_loss_cost)
