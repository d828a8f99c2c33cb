"""Collective layer metrics from the profiler trace (several chips)."""


def exposed_ms(ctx):
    """Per step, on the first chip: the time in which a collective
    (all-reduce, reduce-scatter, all-gather; on the core's line or on the
    line beside it) ran and no other operation did."""
    if ctx.trace is None:
        return None
    d = ctx.device_ids[0]
    w = ctx.trace.window(d)
    if w is None:
        return None
    exposed, whole = ctx.trace.exposed_collective(d)
    if not whole:
        return None
    ctx.note("collective", {"union_ms_per_step": whole / 1e6 / w[2],
                            "exposed_ms_per_step": exposed / 1e6 / w[2]})
    return exposed / 1e6 / w[2]
