"""Pallas kernel layer metrics: device time from the trace, operations and
bytes from the shapes."""

FLASH_FWD = "mxtpu_flash_fwd"
_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def flash_fwd_cost(sizes, traffic):
    """``(flops, bytes)`` one chip's forward flash-attention call needs, from
    the shapes alone: QK^T and PV are 2 x L x L x d multiply-accumulates per
    head each; Q, K and V are read once and O written once in the compute
    dtype, and the log-sum-exp row (float32) is written for the backward."""
    rows = traffic["per_chip_batch"] * sizes["num_attention_heads"]
    seq = traffic["seq_len"]
    d = sizes["hidden_size"] // sizes["num_attention_heads"]
    flops = 2 * 2 * rows * seq * seq * d
    nbytes = 4 * rows * seq * d * _ITEMSIZE[sizes["dtype"]] + rows * seq * 4
    return flops, nbytes


def _kernel_time(ctx):
    if ctx.trace is None:
        return None
    d = ctx.device_ids[0]
    window = ctx.trace.window(d)
    if window is None:
        return None
    ns, calls = ctx.trace.op_time(d, FLASH_FWD)
    if not calls:
        return None
    return ns, calls, window[2]


def flash_fwd_ms(ctx):
    """Device time per step of the operations that carry the kernel's name."""
    got = _kernel_time(ctx)
    if got is None:
        return None
    ns, _, steps = got
    return ns / 1e6 / steps


def flash_fwd_roofline(ctx):
    """The least time the chip could take for the kernel's calls (the larger
    of FLOPs over peak FLOP/s and bytes over peak bytes/s) over the time they
    took."""
    got = _kernel_time(ctx)
    if got is None:
        return None
    ns, calls, _ = got
    flops, nbytes = flash_fwd_cost(ctx.sizes, ctx.traffic)
    by_flops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.note("bound.kernel.flash_fwd_roofline",
             {"bound": "bytes" if by_bytes >= by_flops else "flops",
              "least_us_per_call": max(by_flops, by_bytes) * 1e6,
              "measured_us_per_call": ns / 1e3 / calls, "calls": calls})
    return 100.0 * max(by_flops, by_bytes) * calls / (ns / 1e9)
