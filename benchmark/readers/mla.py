"""Latent attention's flash-forward kernel: device time from the trace,
operations and bytes from the MLA shapes (Q and K at ``qk_nope_head_dim +
qk_rope_head_dim``, V and O at ``v_head_dim``, causal).  Where the trace has
no ``mxtpu_flash_fwd`` operation the readers return ``None`` and the metric
is left out."""

from readers import roofline

FLASH_FWD = "mxtpu_flash_fwd"
_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def mla_flash_fwd_cost(sizes, traffic):
    """``(flops, bytes)`` of one chip's causal forward call: a query sees
    (L + 1) / 2 keys on average, each a multiply-accumulate over ``d`` for
    QK^T and over ``dv`` for PV; Q and K (at ``d``) and V (at ``dv``) are
    read once and O (at ``dv``) written once in the compute dtype, and the
    log-sum-exp row (float32) is written for the backward."""
    rows = traffic["per_chip_batch"] * sizes["num_attention_heads"]
    seq = traffic["seq_len"]
    d = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    flops = 2 * rows * (seq * (seq + 1) // 2) * (d + dv)
    nbytes = rows * seq * (2 * d + 2 * dv) * _ITEMSIZE[sizes["dtype"]] \
        + rows * seq * 4
    return flops, nbytes


def mla_flash_fwd_ms(ctx):
    """Device time per step of the operations that carry the kernel's name
    (with per-layer recomputation the forward runs twice a layer: both
    count, as the chip ran both)."""
    return roofline.ms_per_step(ctx, FLASH_FWD)


def mla_flash_fwd_roofline(ctx):
    """The least time the chip could take for the kernel's calls over the
    time they took."""
    return roofline.roofline_pct(ctx, "kernel.mla_flash_fwd_roofline",
                                 FLASH_FWD, mla_flash_fwd_cost)
