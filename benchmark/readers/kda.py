"""Kimi Delta Attention's scan (``mxnet_tpu/ops/linear_attention.py``): device
time from the trace, operations and bytes from the shapes — **the mathematics
of a layer's pass, not an implementation**.  A pass's operations are the
recurrence's, a token and head: the decay of the state, ``k^T S``, the
rank-one write, ``q^T S`` and the vector updates between them, 7 dk dv
multiply-accumulates forward; the backward by the same rule carries the
state's cotangent back through the same four steps and forms the five
gradients, twice the forward.  Its bytes are q, k, v, g and beta read and o
written once (the backward: those and ``do`` in, five gradients out).  So
another chunk size, or a kernel split in two, reads the same work.

A pass is counted from the configuration — the KDA layers among the
``num_hidden_layers`` built, two forward passes a layer under ``remat`` and
one backward — not from how many calls the trace holds: the share is the
least time the chip could take over a step's passes over the device time a
step spends in the operations that carry the pattern.  By this count the scan
is bytes-bound on a v5e.  Where the trace has no such operation (a program
without the kernels, as the parent of the PR that brought these) the readers
return ``None`` and the metric is left out."""

from readers import roofline

KDA_FWD = "mxtpu_kda_fwd"
KDA_BWD = "mxtpu_kda_bwd"
_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _shapes(sizes, traffic):
    lin = sizes["linear_attn_config"]
    return dict(
        tokens=traffic["per_chip_batch"] * traffic["seq_len"],
        h=lin["num_heads"], d=lin["head_dim"],
        item=_ITEMSIZE[sizes["dtype"]],
        layers=sum(i <= sizes["num_hidden_layers"]
                   for i in lin["kda_layers"]),
        forwards=2 if sizes.get("remat") else 1)


def kda_fwd_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's forward pass: 7 dk dv multiply-
    accumulates a token and head; q, k, v read and o written in the compute
    dtype, the log-decay (a value a key channel) and beta (one a head) read
    in float32."""
    s = _shapes(sizes, traffic)
    flops = 2 * 7 * s["d"] * s["d"] * s["h"] * s["tokens"]
    nbytes = s["tokens"] * s["h"] * (4 * s["d"] * s["item"] + s["d"] * 4 + 4)
    return flops, nbytes


def kda_bwd_cost(sizes, traffic):
    """``(flops, bytes)`` of one layer's backward pass: twice the forward's
    operations; the forward's five operands and ``do`` read, dq, dk, dv
    written in the compute dtype, dg and dbeta in float32."""
    s = _shapes(sizes, traffic)
    flops = 2 * 2 * 7 * s["d"] * s["d"] * s["h"] * s["tokens"]
    nbytes = s["tokens"] * s["h"] * (7 * s["d"] * s["item"]
                                     + 2 * s["d"] * 4 + 2 * 4)
    return flops, nbytes


def _share(ctx, metric, pattern, cost, passes):
    """The least time for a step's ``passes`` over the device time a step
    spends under ``pattern``; the bound and both times go on a ``# bound.``
    line."""
    got = roofline.kernel_time(ctx, pattern)
    if got is None:
        return None
    ns, calls, steps = got
    flops, nbytes = cost(ctx.sizes, ctx.traffic)
    by_flops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes)
    ctx.note("bound." + metric,
             {"bound": "bytes" if by_bytes >= by_flops else "flops",
              "least_us_per_pass": least * 1e6, "passes_per_step": passes,
              "measured_us_per_step": ns / 1e3 / steps,
              "calls_per_step": calls / steps})
    return 100.0 * least * passes / (ns / 1e9 / steps)


def kda_fwd_ms(ctx):
    return roofline.ms_per_step(ctx, KDA_FWD)


def kda_fwd_roofline(ctx):
    s = _shapes(ctx.sizes, ctx.traffic)
    return _share(ctx, "kernel.kda_fwd_roofline", KDA_FWD, kda_fwd_cost,
                  s["layers"] * s["forwards"])


def kda_bwd_ms(ctx):
    return roofline.ms_per_step(ctx, KDA_BWD)


def kda_bwd_roofline(ctx):
    s = _shapes(ctx.sizes, ctx.traffic)
    return _share(ctx, "kernel.kda_bwd_roofline", KDA_BWD, kda_bwd_cost,
                  s["layers"])
