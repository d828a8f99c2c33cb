"""What a kernel's two layer metrics share: its device time from the trace,
and the share of its roofline from a cost function of the shapes."""


def kernel_time(ctx, pattern):
    """``(ns, calls, steps)`` of the first chip's core operations whose name
    carries ``pattern`` in the traced window, or ``None`` where the trace
    has none (a program without the kernel, or no trace)."""
    if ctx.trace is None:
        return None
    d = ctx.device_ids[0]
    window = ctx.trace.window(d)
    if window is None:
        return None
    ns, calls = ctx.trace.op_time(d, pattern)
    if not calls:
        return None
    return ns, calls, window[2]


def ms_per_step(ctx, pattern):
    got = kernel_time(ctx, pattern)
    if got is None:
        return None
    ns, _, steps = got
    return ns / 1e6 / steps


def roofline_pct(ctx, metric, pattern, cost):
    """The least time the chip could take for the calls the trace holds (the
    larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, ``cost``
    giving one call's ``(flops, bytes)``) over the time they took; which
    bound holds goes on a ``# bound.<metric>`` line."""
    got = kernel_time(ctx, pattern)
    if got is None:
        return None
    ns, calls, steps = got
    flops, nbytes = cost(ctx.sizes, ctx.traffic)
    by_flops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.note("bound." + metric,
             {"bound": "bytes" if by_bytes >= by_flops else "flops",
              "least_us_per_call": max(by_flops, by_bytes) * 1e6,
              "measured_us_per_call": ns / 1e3 / calls, "calls": calls,
              "calls_per_step": calls / steps})
    return 100.0 * max(by_flops, by_bytes) * calls / (ns / 1e9)
