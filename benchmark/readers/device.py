"""Device-level layer metrics from the profiler trace."""


def idle_pct(ctx):
    """1 - (union of the core's operation intervals) / window, on the chip
    that idles most.  A collective that runs on the core counts as busy."""
    if ctx.trace is None:
        return None
    shares = []
    for d in ctx.device_ids:
        got = ctx.trace.busy(d)
        if got is not None:
            busy, window, _ = got
            shares.append(100.0 * (1.0 - busy / window))
    return max(shares) if shares else None


def mfu_pct(ctx):
    """Model FLOP utilization of the traced window: the model's FLOPs per
    sample (from the layer shapes, recomputation not counted) x samples per
    second of the window, over chips x the chip's published bf16 peak."""
    if ctx.trace is None:
        return None
    got = ctx.trace.busy(ctx.device_ids[0])
    if got is None:
        return None
    _, window_ns, steps = got
    samples_per_s = steps * ctx.global_batch / (window_ns / 1e9)
    peak = len(ctx.device_ids) * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * ctx.flops_per_sample * samples_per_s / peak
